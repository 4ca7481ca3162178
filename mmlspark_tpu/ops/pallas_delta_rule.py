"""The gated delta rule's chunk-local half as Pallas TPU calls.

``ops/linear_attention.py``'s module docstring has the mathematics. All of
it but the walk of the state from chunk to chunk is local to one chunk of
64 tokens of one value head. The calls here make a chunk's tiles in VMEM,
reading q, k and v once from the ``(B, L, H * d)`` rows the projections
wrote (a value head's key head is picked by the block's index map:
nothing is repeated in HBM) and writing only what the walk and the output
read, laid out chunk-major as the scan wants them. A program works on
TWO chunks side by side, 128 tokens: every ``chunk x chunk`` tile is then
a 128 x 128 tile that is block-diagonal under the mask ``same chunk``,
which fills the lanes and the MXU's width where a 64 x 64 tile fills
half of each; nothing couples the two chunks. A program has ONE value head
where both head widths are multiples of 128, and ``heads_per_program``
heads where a head's lanes end inside a lane tile (96 x 192: four, 384
key lanes and 768 value lanes): its blocks of rows are that many heads
wide, each head's lanes are sliced in VMEM (a load or a store may start
inside a tile where a block or a view of one may not), its other blocks
have the heads on an axis of their own, and the last program of a head
count that is no multiple (30 = 7 x 4 + 2) skips the heads past the end.
Everything in HBM keeps the heads' own widths.

- ``delta_chunk_fwd`` (grid ``(B, blocks of up to 8 pairs, programs of
  value heads)``): ``G``, ``D``, ``K K^T``, ``Q K^T``, ``A``, ``T = (I +
  A)^-1`` stay in VMEM; out go ``W = T (beta e^G K)``, ``Kd = e^(G_C - G)
  K``, ``qe = e^G Q`` and ``P = (Q K^T) * D`` in the products' operand
  type (each is only ever read as such an operand) and ``U_0 = T (beta
  V)`` in float32 (the walk subtracts from it).
  The five carry ONE checkpoint name, ``DELTA_CHUNK_TILES``: a block
  recomputed under a policy that lists it
  (``models/zoo/decoder._remat_block``) keeps them and its backward pass
  holds no second forward call; under no policy, or another, the name
  changes nothing.
- ``delta_chunk_bwd`` (``jax.custom_vjp``; same grid, the value heads of
  one key head in turn so that dq and dk are summed over them in the
  output block): gets ``(q, k, v, g, beta)`` alone and makes the tiles
  again (nothing chunk-local is kept for IT, whatever a policy keeps of
  the forward's outputs for the walk and the output's backward) and
  takes the five cotangents back to q, k, v, g and beta. Through the
  inverse ``dA = -strict_lower(T^T dT T^T)``; through the decays ``dG_i =
  sum_j (dD * D)_ij - sum_j (dD * D)_ji`` and the ``e^G``, ``e^(G_C - G)``
  terms, then the reverse running sum.
- ``delta_chunk_out`` after the walk: ``O = qe S_0 + P U`` straight into
  ``(B, L, Hv * dv)`` rows (the state and the corrections are cast to
  ``dtype`` in VMEM); ``delta_chunk_out_bwd`` is its four products
  transposed.

``T`` and the two products of ``dA`` are float32 at the highest matmul
precision: the finite Neumann product on the 16-row diagonal blocks, the
blocks joined two and two up to the chunk, as
``linear_attention.inv_unit_lower`` (whole-tile products under block
masks here). Every other product takes ``dtype`` operands and accumulates
in float32. Decays are exponentials of differences that are not positive.

The calls carry their scope's name into the compiled program and a
device trace. Each is jitted, so the layers of a model lower one Mosaic
module a shape. On the CPU they run interpreted.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mmlspark_tpu.ops import pallas_attention

CHUNK = 64
LANES = 128
PAIR = 2 * CHUNK
# pairs a program: the (pairs, 128) blocks of g and beta want 8 sublanes
PAIRS_PER_PROGRAM = 8
# pairs a turn of a program's loop: two independent chains of products
# for the scheduler to interleave (the inverse is ten dependent products)
_UNROLL = 2
# heads a program at most, where one head's lanes end inside a lane tile
_MAX_HEADS = 4
_NEUMANN_ROWS = 16
_HIGHEST = jax.lax.Precision.HIGHEST
_FWD_NAME = "delta_chunk_fwd"
_BWD_NAME = "delta_chunk_bwd"
_OUT_NAME = "delta_chunk_out"
_OUT_BWD_NAME = "delta_chunk_out_bwd"
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b
_f32 = jnp.float32
# of a v5e's 128 MiB of VMEM; a backward program's blocks are about 9 MiB
_VMEM_LIMIT = 64 << 20


def heads_per_program(dk: int, dv: int) -> int:
    """The fewest heads side by side whose key lanes and whose value lanes
    both end on a lane tile: 1 where both widths are multiples of 128, 4
    at 96 x 192 (384 and 768 lanes)."""
    return math.lcm(LANES // math.gcd(dk, LANES), LANES // math.gcd(dv, LANES))


def supports(chunk: int, key_heads: int, value_heads: int, dk: int,
             dv: int) -> bool:
    """Shapes the calls take: the chunk they are written for and either
    head widths that fill the lanes, with whole groups of value heads a
    key head, or as many key heads as value heads of widths that fill the
    lanes ``_MAX_HEADS`` heads or fewer at a time (multiples of 32)."""
    per = heads_per_program(dk, dv)
    return (chunk == CHUNK and value_heads % key_heads == 0
            and (per == 1 or (per <= _MAX_HEADS
                              and key_heads == value_heads)))


def _pairs_per_program(pairs: int) -> int:
    return min(pairs, PAIRS_PER_PROGRAM)


def padded_length(length: int) -> int:
    """The row length the calls take for ``length`` tokens: whole pairs of
    chunks, in whole programs."""
    pairs = -(-length // PAIR)
    per = _pairs_per_program(pairs)
    return -(-pairs // per) * per * PAIR


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_f32)


def _dot32(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=_f32)


def _col(row, eye):
    """(1, R) -> (R, 1), exactly: no transposition of a vector in VMEM."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _same_block(r, c, rows: int):
    shift = rows.bit_length() - 1
    return (r >> shift) == (c >> shift)


def inv_unit_lower_tile(a, r, c):
    """``(I + a)^-1`` for a float32 tile ``a`` (R, R) that is strictly
    lower triangular inside diagonal blocks of ``min(R, 64)`` rows and
    zero outside them; ``r`` and ``c`` are its row and column indices. The
    Neumann product on the 16-row diagonal blocks, then ``D^-1 - D^-1 R
    D^-1`` for the block below the diagonal at each doubling."""
    top = min(a.shape[0], CHUNK)
    rows = min(top, _NEUMANN_ROWS)
    diag = jnp.where(_same_block(r, c, rows), a, 0.0)
    out = jnp.where(r == c, 1.0, 0.0) - diag
    power = diag
    for _ in range(max(0, rows.bit_length() - 2)):
        power = _dot32(power, power)
        out = out + _dot32(out, power)
    while rows < top:
        below = jnp.where(_same_block(r, c, 2 * rows)
                          & ~_same_block(r, c, rows), a, 0.0)
        out = out - _dot32(out, _dot32(below, out))
        rows *= 2
    return out


def _chunk_last(r, col):
    """Per row of the tile, ``col`` (R, 1) at the last row of its chunk."""
    out = col[CHUNK - 1:CHUNK]
    for lo in range(CHUNK, col.shape[0], CHUNK):
        out = jnp.where(r[:, :1] >= lo, col[lo + CHUNK - 1:lo + CHUNK], out)
    return out


def _masks(R: int):
    """Index masks of an (R, R) tile of chunks on the diagonal: made once
    a program, outside the loop over its pairs."""
    r = jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)
    same = _same_block(r, c, CHUNK)
    return dict(r=r, c=c, eye=r == c, same=same, seen=same & (r >= c),
                below=same & (r > c))


def _tiles(q, k, v, g_row, beta_row, dtype, m):
    """The tiles of the chunks of ``R`` tokens from their rows: q, k (R,
    dk) and v (R, dv) as they lie in HBM, g and beta (1, R); ``m`` =
    ``_masks(R)``."""
    q, k, v = q.astype(_f32), k.astype(_f32), v.astype(_f32)
    G = jnp.sum(jnp.where(m["seen"], g_row, 0.0), axis=1, keepdims=True)
    beta = _col(beta_row, m["eye"])
    D = jnp.where(m["seen"], jnp.exp(jnp.minimum(
        G - _row(G, m["eye"]), 0.0)), 0.0)
    qc, kc = q.astype(dtype), k.astype(dtype)
    kk = _dot(kc, kc, _NT)
    qk = _dot(qc, kc, _NT)
    T = inv_unit_lower_tile(
        jnp.where(m["below"], beta * D * kk, 0.0), m["r"], m["c"])
    eG = jnp.exp(G)
    return dict(
        q=q, k=k, v=v, qc=qc, kc=kc, beta=beta, D=D, kk=kk, qk=qk, T=T,
        Tc=T.astype(dtype), eG=eG, E=jnp.exp(_chunk_last(m["r"], G) - G),
        kb=(k * (beta * eG)).astype(dtype), vb=(v * beta).astype(dtype))


def _pairs_loop(pairs: int, body):
    unroll = _UNROLL if pairs % _UNROLL == 0 else 1

    def turn(i, _):
        for j in range(unroll):
            body(i * unroll + j)
    jax.lax.fori_loop(0, pairs // unroll, turn, None)


def _pair_rows(p: Any):
    return pl.ds(pl.multiple_of(p * PAIR, PAIR), PAIR)


def _chunks_of(ref, p, at=()):
    """The pair's two chunks of a chunk-major block, one under the other
    (``at``: ``_head_of`` the head, here and below)."""
    return jnp.concatenate(
        [ref[(2 * p,) + at], ref[(2 * p + 1,) + at]], axis=0)


def _to_chunks(ref, p, tile, at=()):
    ref[(2 * p,) + at] = tile[:CHUNK].astype(ref.dtype)
    ref[(2 * p + 1,) + at] = tile[CHUNK:].astype(ref.dtype)


def _lanes_of(ref, j: int, heads: int):
    """Head ``j``'s lanes of a block of rows that is ``heads`` heads wide
    (all of them where a program has one head): an index, because no view
    of a block may start inside a lane tile."""
    if heads == 1:
        return slice(None)
    width = ref.shape[-1] // heads
    return pl.ds(j * width, width)


def _head_of(j: int, heads: int) -> tuple:
    """Head ``j``'s index on the heads' axis of a block (no such axis
    where a program has one head)."""
    return () if heads == 1 else (j,)


def _each_head(heads: int, total: int, body):
    """``body(j)`` for each head of the program; the last program of a
    ragged grid (``total`` heads are no whole programs) skips the heads
    past the end, whose blocks lie outside the arrays."""
    for j in range(heads):
        if total % heads and j >= total % heads:
            pl.when(pl.program_id(2) * heads + j < total)(
                functools.partial(body, j))
        else:
            body(j)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                w_ref, u0_ref, kd_ref, qe_ref, p_ref, *, dtype,
                heads: int = 1, total: int = 1):
    m = _masks(PAIR)

    def head(j):
        kl, vl = (_lanes_of(r, j, heads) for r in (k_ref, v_ref))
        at = _head_of(j, heads)

        def pair(p):
            rows, one = _pair_rows(p), at + (pl.ds(p, 1), slice(None))
            t = _tiles(q_ref[rows, kl], k_ref[rows, kl], v_ref[rows, vl],
                       g_ref[one], beta_ref[one], dtype, m)
            _to_chunks(w_ref, p, _dot(t["Tc"], t["kb"]), at)
            _to_chunks(u0_ref, p, _dot(t["Tc"], t["vb"]), at)
            _to_chunks(kd_ref, p, t["k"] * t["E"], at)
            _to_chunks(qe_ref, p, t["q"] * t["eG"], at)
            p_ref[(p,) + at] = (t["qk"] * t["D"]).astype(p_ref.dtype)

        _pairs_loop(p_ref.shape[0], pair)

    _each_head(heads, total, head)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                dw_ref, du0_ref, dkd_ref, dqe_ref, dp_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, *, dtype,
                group: int, heads: int = 1, total: int = 1):
    # the value heads of one key head follow each other on the grid's last
    # axis: dq and dk of the key head are summed in the resident block
    @pl.when(pl.program_id(2) % group == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dk_ref[...] = jnp.zeros_like(dk_ref)

    m = _masks(PAIR)

    def head(j):
        kl, vl = (_lanes_of(r, j, heads) for r in (k_ref, v_ref))
        at = _head_of(j, heads)

        def pair(p):
            rows, one = _pair_rows(p), at + (pl.ds(p, 1), slice(None))
            t = _tiles(q_ref[rows, kl], k_ref[rows, kl], v_ref[rows, vl],
                       g_ref[one], beta_ref[one], dtype, m)
            q, k, v, qc, kc = t["q"], t["k"], t["v"], t["qc"], t["kc"]
            beta, D, eG, E, T, Tc = (t["beta"], t["D"], t["eG"], t["E"],
                                     t["T"], t["Tc"])
            dW = _chunks_of(dw_ref, p, at).astype(dtype)
            dU0 = _chunks_of(du0_ref, p, at).astype(dtype)
            dKd = _chunks_of(dkd_ref, p, at).astype(_f32)
            dqe = _chunks_of(dqe_ref, p, at).astype(_f32)
            dP = dp_ref[(p,) + at].astype(_f32)

            # W = T kb, U0 = T vb; A -> T = (I + A)^-1. What dT and dP
            # hold outside the chunks' diagonal blocks meets a mask or D
            # = 0
            dT = _dot(dW, t["kb"], _NT) + _dot(dU0, t["vb"], _NT)
            dkb = _dot(Tc, dW, _TN)
            dvb = _dot(Tc, dU0, _TN)
            dA = jnp.where(m["below"],
                           -_dot32(_dot32(T, dT, _TN), T, _NT), 0.0)
            # A = beta_i D_ij kk_ij; P = qk * D
            dAD, dPD = dA * D, dP * D
            dAk = dAD * t["kk"]
            dbeta = jnp.sum(dAk, axis=1, keepdims=True)
            dkk, dqk = (dAD * beta).astype(dtype), dPD.astype(dtype)
            dDD = dAk * beta + dPD * t["qk"]
            dG = jnp.sum(dDD, axis=1, keepdims=True) - _col(
                jnp.sum(dDD, axis=0, keepdims=True), m["eye"])

            scale = beta * eG                       # kb = k * (beta e^G)
            along = jnp.sum(dkb * k, axis=1, keepdims=True)
            dbeta += along * eG + jnp.sum(dvb * v, axis=1, keepdims=True)
            last = jnp.sum(dKd * k, axis=1, keepdims=True) * E  # Kd = k E
            dG += along * scale - last \
                + jnp.sum(dqe * q, axis=1, keepdims=True) * eG

            dq_ref[rows, kl] += _dot(dqk, kc) + dqe * eG
            dk_ref[rows, kl] += _dot(dqk, qc, _TN) + _dot(dkk, kc) \
                + _dot(dkk, kc, _TN) + dkb * scale + dKd * E
            dv_ref[rows, vl] = (dvb * beta).astype(dv_ref.dtype)
            # G = cumsum(g) a chunk: dg_j = sum over i >= j of dG_i; and
            # E's G_C is the chunk's last row, which is past every j of
            # the chunk
            dg_ref[one] = jnp.sum(
                jnp.where(m["seen"], dG, 0.0)
                + jnp.where(m["same"], last, 0.0), axis=0, keepdims=True)
            dbeta_ref[one] = _row(dbeta, m["eye"])

        _pairs_loop(dp_ref.shape[0], pair)

    _each_head(heads, total, head)


def _out_kernel(qe_ref, p_ref, s0_ref, u_ref, o_ref, *, dtype,
                heads: int = 1, total: int = 1):
    def head(j):
        at, lanes = _head_of(j, heads), _lanes_of(o_ref, j, heads)

        def pair(p):
            o_ref[_pair_rows(p), lanes] = jnp.concatenate(
                [_dot(qe_ref[(c,) + at], s0_ref[(c,) + at].astype(dtype))
                 for c in (2 * p, 2 * p + 1)], axis=0) \
                + _dot(p_ref[(p,) + at],
                       _chunks_of(u_ref, p, at).astype(dtype))

        _pairs_loop(p_ref.shape[0], pair)

    _each_head(heads, total, head)


def _out_bwd_kernel(qe_ref, p_ref, s0_ref, u_ref, do_ref,
                    dqe_ref, dp_ref, ds0_ref, du_ref, *, dtype,
                    heads: int = 1, total: int = 1):
    def head(j):
        at, lanes = _head_of(j, heads), _lanes_of(do_ref, j, heads)

        def pair(p):
            do = do_ref[_pair_rows(p), lanes].astype(dtype)
            for c, rows in ((2 * p, do[:CHUNK]), (2 * p + 1, do[CHUNK:])):
                c = (c,) + at
                dqe_ref[c] = _dot(rows, s0_ref[c].astype(dtype),
                                  _NT).astype(dqe_ref.dtype)
                ds0_ref[c] = _dot(qe_ref[c], rows, _TN)
            dp_ref[(p,) + at] = _dot(
                do, _chunks_of(u_ref, p, at).astype(dtype),
                _NT).astype(dp_ref.dtype)
            _to_chunks(du_ref, p, _dot(p_ref[(p,) + at], do, _TN), at)

        _pairs_loop(p_ref.shape[0], pair)

    _each_head(heads, total, head)


def _specs(B: int, N: int, Hk: int, Hv: int, heads: int = 1):
    """The grid (row, block of pairs, program of ``heads`` value heads)
    and its block specs: q, k, v rows (B, L, H * width), q and k at the
    head's key head; g and beta (B, Hv, N / 2, 128); the chunk-major
    arrays (N, B, Hv, 64, width); the pair-major ``P`` (N / 2, B, Hv,
    128, 128) and the states (N, B, Hv, dk, dv). With ``heads`` > 1 (``Hk
    = Hv`` then) a block holds the program's heads: side by side in the
    rows' lanes, on an axis of their own elsewhere; the last program's
    may reach past the arrays."""
    group, per = Hv // Hk, _pairs_per_program(N // 2)
    on_axis = None if heads == 1 else heads

    def rows(width, key_head=False):
        return pl.BlockSpec(
            (None, per * PAIR, heads * width),
            (lambda b, i, h: (b, i, h // group)) if key_head
            else (lambda b, i, h: (b, i, h)))

    def major(count, *tile):
        return pl.BlockSpec((count, None, on_axis) + tile,
                            lambda b, i, h: (i, b, h, 0, 0))
    scalars = pl.BlockSpec((None, on_axis, per, PAIR),
                           lambda b, i, h: (b, h, i, 0))
    return dict(grid=(B, N // 2 // per, -(-Hv // heads)), rows=rows,
                scalars=scalars,
                chunks=lambda width: major(2 * per, CHUNK, width),
                pairs=major(per, PAIR, PAIR),
                states=lambda dk, dv: major(2 * per, dk, dv),
                program=dict(heads=heads, total=Hv))


def _call(kernel, name, grid, in_specs, out_specs, out_shape, last_axis,
          *operands):
    # the scope's name is the call's instruction name in the compiled
    # program and so in a device trace
    with jax.named_scope(name):
        return pl.pallas_call(
            kernel, name=name, grid=grid, in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", last_axis),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=pallas_attention._interpret())(*operands)


def _tile_specs(s, dk, dv):
    """Specs of ``W``, ``U0``, ``Kd``, ``qe``, ``P``."""
    return [s["chunks"](dk), s["chunks"](dv), s["chunks"](dk),
            s["chunks"](dk), s["pairs"]]


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _forward(q, k, v, g, beta, dims, dtype):
    Hk, Hv, dk, dv = dims
    B, N = q.shape[0], q.shape[1] // CHUNK
    s = _specs(B, N, Hk, Hv, heads_per_program(dk, dv))

    def chunks(width, dt):
        return jax.ShapeDtypeStruct((N, B, Hv, CHUNK, width), dt)
    return _call(
        functools.partial(_fwd_kernel, dtype=dtype, **s["program"]),
        _FWD_NAME, s["grid"],
        [s["rows"](dk, True), s["rows"](dk, True), s["rows"](dv),
         s["scalars"], s["scalars"]], _tile_specs(s, dk, dv),
        [chunks(dk, dtype), chunks(dv, _f32), chunks(dk, dtype),
         chunks(dk, dtype),
         jax.ShapeDtypeStruct((N // 2, B, Hv, PAIR, PAIR), dtype)],
        "parallel", q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _backward(q, k, v, g, beta, cts, dims, dtype):
    Hk, Hv, dk, dv = dims
    s = _specs(q.shape[0], q.shape[1] // CHUNK, Hk, Hv,
               heads_per_program(dk, dv))
    ins = [s["rows"](dk, True), s["rows"](dk, True), s["rows"](dv),
           s["scalars"], s["scalars"]]
    return _call(
        functools.partial(_bwd_kernel, dtype=dtype, group=Hv // Hk,
                          **s["program"]), _BWD_NAME, s["grid"],
        ins + _tile_specs(s, dk, dv), ins,
        [jax.ShapeDtypeStruct(x.shape, x.dtype)
         for x in (q, k, v, g, beta)], "arbitrary", q, k, v, g, beta, *cts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def delta_chunk(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, dims: Tuple[int, int, int, int],
                dtype: Any):
    """The chunk-local tiles of the gated delta rule. ``q``, ``k`` (B, L,
    Hk * dk) float32, ``q`` already scaled; ``v`` (B, L, Hv * dv); ``g``
    and ``beta`` (B, Hv, L / 128, 128) float32; ``L = padded_length(L)``;
    ``dims`` = (Hk, Hv, dk, dv). Returns ``W``, ``U0``, ``Kd``, ``qe`` as
    (L / 64, B, Hv, 64, width), ``U0`` float32 and the others ``dtype``,
    and ``P`` (L / 128, B, Hv, 128, 128) ``dtype``: a pair of chunks'
    ``(Q K^T) * D`` on the diagonal, zeros beside them."""
    return tuple(_forward(q, k, v, g, beta, dims, jnp.dtype(dtype)))


# One name for all five: a policy that kept four of them would still run
# the call. The tiles returned and the tiles saved are the same named
# values; without a policy that lists the name it changes nothing.
DELTA_CHUNK_TILES = "delta_chunk_tiles"


def _fwd_rule(q, k, v, g, beta, dims, dtype):
    tiles = tuple(checkpoint_name(t, DELTA_CHUNK_TILES)
                  for t in delta_chunk(q, k, v, g, beta, dims, dtype))
    return tiles, (q, k, v, g, beta)


def _bwd_rule(dims, dtype, res, cts):
    return tuple(_backward(*res, tuple(cts), dims, jnp.dtype(dtype)))


delta_chunk.defvjp(_fwd_rule, _bwd_rule)


def _out_specs(qe, u):
    N, B, Hv, _, dk = qe.shape
    dv = u.shape[-1]
    s = _specs(B, N, Hv, Hv, heads_per_program(dk, dv))
    return s, [s["chunks"](dk), s["pairs"], s["states"](dk, dv),
               s["chunks"](dv)], s["rows"](dv)


def chunk_out_call(name: str, bwd_name: str):
    """``delta_chunk_out`` under ``name`` and its backward under
    ``bwd_name``: the rule with a decay a key channel (``ops/pallas_kda``)
    runs these kernels as they are, under names of its own."""
    @functools.partial(jax.jit, static_argnames=("dtype",))
    def _out_forward(qe, p, s0, u, dtype):
        N, B, Hv = qe.shape[:3]
        s, ins, rows = _out_specs(qe, u)
        return _call(
            functools.partial(_out_kernel, dtype=dtype, **s["program"]),
            name, s["grid"],
            ins, rows, jax.ShapeDtypeStruct(
                (B, N * CHUNK, Hv * u.shape[-1]), _f32), "parallel",
            qe, p, s0, u)

    @functools.partial(jax.jit, static_argnames=("dtype",))
    def _out_backward(qe, p, s0, u, do, dtype):
        s, ins, rows = _out_specs(qe, u)
        return _call(
            functools.partial(_out_bwd_kernel, dtype=dtype, **s["program"]),
            bwd_name, s["grid"], ins + [rows], ins,
            [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (qe, p, s0, u)],
            "parallel", qe, p, s0, u, do)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
    def delta_chunk_out(qe: jax.Array, p: jax.Array, s0: jax.Array,
                        u: jax.Array, dtype: Any) -> jax.Array:
        """``O = qe S_0 + P U`` chunk by chunk, written as (B, L, Hv * dv)
        float32 rows: ``qe`` and ``P`` from ``delta_chunk``, ``S_0`` (N, B,
        Hv, dk, dv) and ``U`` (N, B, Hv, 64, dv) float32 from the walk."""
        return _out_forward(qe, p, s0, u, jnp.dtype(dtype))

    def _out_fwd_rule(qe, p, s0, u, dtype):
        return delta_chunk_out(qe, p, s0, u, dtype), (qe, p, s0, u)

    def _out_bwd_rule(dtype, res, do):
        return tuple(_out_backward(*res, do, jnp.dtype(dtype)))

    delta_chunk_out.defvjp(_out_fwd_rule, _out_bwd_rule)
    return delta_chunk_out


delta_chunk_out = chunk_out_call(_OUT_NAME, _OUT_BWD_NAME)
