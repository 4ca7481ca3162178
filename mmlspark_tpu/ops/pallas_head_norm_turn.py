"""A head's RMS norm and its rotary turn as ONE pass over a projection's
rows, and their derivative as one more: Pallas TPU calls.

Between a q or k projection's output and the attention call a softmax
layer of ``models/zoo/parts.GroupedAttention`` norms each head
(``RMSNorm``) and turns it (``parts.rotary``). Both are element-wise in
float32 over ``(B, L, H, d)``; XLA makes them as a norm that WRITES float32
rows, a product with the pairing's permutation that reads and writes them
again, and a combination that reads them twice more. The arithmetic needs
one read of the projection's rows and one write of the turned ones.

``head_norm_turn(y, cos, sin, n, scale, eps)``, for a row tile of ``y``
``(B, L, H * d)``: ``x = f32(y)``; with ``scale`` ``x <- x rsqrt(mean_d(x^2)
+ eps) w``; ``out = x cos + pair(x) sin`` rounded to ``y``'s type ONCE, as
``RMSNorm`` + ``rotary`` round. ``pair(x) = [-x_2 | x_1 | 0]`` over the
first ``2 n`` channels of a head is a rotation of the lanes by ``n`` either
way, each against its signed half of ``sin`` (the sign and the zeros are in
the table, so nothing is selected and nothing multiplied by a matrix). A
head of 128 channels is one register's lanes. What the units cost decides
the order of the arithmetic (static bundle counts of the v5e's compiler and
the chip's sweep, PERF.md section 6, PR 50: a float32 lane rotation
occupies an XLU nine cycles a register and a lane sum seven, and three XLUs
are all there is): the norm's factor is a row's own, so it leaves the turn,
``out = r (x (w cos) + rot(x) (rot(w) sin))`` with the scale folded into
the tables, and the rotation is then of the ROWS AS THEY ARRIVE, two
bfloat16 rows a 32-bit lane, half the registers.

``head_norm_turn``'s derivative (``jax.custom_vjp``) is one pass of the
same kind: it reads the cotangent (and ``y`` where there is a norm),
turns the cotangent back (``pair^T = -pair``, again a rotation of the
arriving rows against tables rotated with them), makes the norm's factor
again, takes the norm's derivative and writes ``dy`` in ``y``'s type and a
program's float32 ``(1, 128)`` addend to the scale's gradient, which XLA
sums. Its residuals are the call's operands and nothing else: ``y`` is
the value a recomputed block keeps already (``parts.ATTN_QKV``); without a
norm it reads no ``y`` at all.

The turned rows leave the forward call, and their cotangent enters the
backward one, HEAD-MAJOR, ``(B, H, L, d)``: the layout the attention
kernels read q and k in and write their gradients in. XLA folded that
transposition into its own fusion and cannot fold it into a custom call,
where it would be a copy of the rows each way.

The calls carry their scope's name into the compiled program and a device
trace (``head_norm_turn_fwd`` / ``_bwd``). Each is jitted, so the layers of
a model lower one Mosaic module a shape. On the CPU they run interpreted.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mmlspark_tpu.ops import pallas_attention

LANES = 128
# a program's tile: rows of positions by lanes of whole heads
ROWS = 512
WIDTH = 1024
# rows of a tile in flight at once
CHUNK = 128
_FWD_NAME = "head_norm_turn_fwd"
_BWD_NAME = "head_norm_turn_bwd"
_f32 = jnp.float32


def supports(shape, n: int) -> bool:
    """Whether the calls take ``(B, L, H, d)`` rows turned by ``n``
    frequencies: a head is a register's 128 lanes, rows come in whole
    registers of 16 bfloat16 ones, and the turn lies inside the head.
    (Heads of 64, two a register, were built too: two masked lane sums and
    two rotations a register cost the compiler 12.4 and 17.8 bundles a
    1,024 elements, forward and backward, where heads of 128 cost 6.1 and
    10.9; ``lfm2_moe``'s step was not read with them on a chip, so they
    keep XLA's form and the code went: PERF.md section 6, PR 50.)"""
    _, L, _, d = shape
    return d == LANES and L % 16 == 0 and 0 < 2 * n <= d


def _row_chunks(tile: int, body) -> None:
    """``body(rows)`` over a tile's rows, ``CHUNK`` at a time: few enough
    for a chunk's float32 values to stay in registers from the read to the
    write."""
    if tile % CHUNK:
        return body(slice(None))

    def step(c, _):
        body(pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK))
    jax.lax.fori_loop(0, tile // CHUNK, step, None)


def _heads(ref):
    """A block of rows' heads, each a register's lanes."""
    return [pl.ds(h * LANES, LANES) for h in range(ref.shape[-1] // LANES)]


def _factor(x, eps: float):
    """The norm's factor ``rsqrt(mean(x^2) + eps)`` of a head's float32
    rows, a column."""
    return jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rotated(x, shift: int):
    """``x`` (rows, 128) with its lanes rotated, as float32. Rows of 16
    bits rotate two to a 32-bit lane: half the registers through the
    XLU."""
    if x.dtype.itemsize == 4:
        return pltpu.roll(x, shift, 1).astype(_f32)
    return pltpu.bitcast(pltpu.roll(
        pltpu.bitcast(x, jnp.uint32), shift, 1), x.dtype).astype(_f32)


def _turned(x, x32, tables, shifts):
    """``x t_0 + sum_k rot_k(x) t_k`` in float32: ``x`` as it arrived,
    which is what rotates, and as float32."""
    out = x32 * tables[0]
    for shift, table in zip(shifts, tables[1:]):
        out = out + _rotated(x, shift) * table
    return out


def _fwd_kernel(y_ref, *refs, shifts, eps):
    table_refs, o_ref = refs[:-1], refs[-1]

    def chunk(rows):
        tables = [t[rows, :] for t in table_refs]
        for h, lanes in enumerate(_heads(y_ref)):
            x = y_ref[rows, lanes]
            x32 = x.astype(_f32)
            out = _turned(x, x32, tables, shifts)
            if eps is not None:
                out = out * _factor(x32, eps)
            o_ref[h, rows, :] = out.astype(o_ref.dtype)
    _row_chunks(y_ref.shape[0], chunk)


def _turn_bwd_kernel(g_ref, *refs, shifts):
    """The turn alone undone: it takes no ``y``."""
    table_refs, dy_ref = refs[:-1], refs[-1]

    def chunk(rows):
        tables = [t[rows, :] for t in table_refs]
        for h, lanes in enumerate(_heads(dy_ref)):
            ct = g_ref[h, rows, :]
            dy_ref[rows, lanes] = _turned(
                ct, ct.astype(_f32), tables, shifts).astype(dy_ref.dtype)
    _row_chunks(dy_ref.shape[0], chunk)


def _bwd_kernel(y_ref, g_ref, w_ref, *refs, shifts, eps, rows):
    table_refs, dy_ref, dw_ref = refs[:-2], refs[-2], refs[-1]
    w, tile = w_ref[...], y_ref.shape[0]
    dw_ref[...] = jnp.zeros_like(dw_ref)
    # (read here: the interpreter finds no program_id inside a loop)
    first = pl.program_id(1) * tile

    def chunk(at):
        tables = [t[at, :] for t in table_refs]
        dw = 0.0
        for h, lanes in enumerate(_heads(y_ref)):
            ct = g_ref[h, at, :]
            dn = _turned(ct, ct.astype(_f32), tables, shifts)
            x = y_ref[at, lanes].astype(_f32)
            r = _factor(x, eps)
            xr = x * r
            dw = dw + dn * xr
            u = dn * w
            dy_ref[at, lanes] = (r * (u - xr * jnp.mean(
                u * xr, axis=-1, keepdims=True))).astype(dy_ref.dtype)
        if rows % tile:
            # the last tile's rows past the array hold whatever was there
            row = jax.lax.broadcasted_iota(jnp.int32, dw.shape, 0)
            if not isinstance(at, slice):
                row = row + at.start
            dw = jnp.where(first + row < rows, dw, 0.0)
        dw_ref[...] += jnp.sum(dw, axis=0, keepdims=True)
    _row_chunks(tile, chunk)


def _call(kernel, name, shape, dtype, rows_in, heads_in, tables, scale=None,
          backward=False):
    """One pass in tiles of (ROWS, WIDTH) over ``(B, L, H * 128)`` rows
    (``rows_in``) and head-major ``(B, H, L, 128)`` ones (``heads_in``),
    the tables' rows beside them; the grid's last axis walks a row tile's
    heads, so its tables stay where they are. The forward writes
    head-major, the backward rows, and with ``scale`` a program's addend
    to its gradient."""
    B, L, HD = shape
    T, W = min(ROWS, L), math.gcd(HD, WIDTH)
    grid = (B, pl.cdiv(L, T), HD // W)
    rows = pl.BlockSpec((None, T, W), lambda b, t, h: (b, t, h))
    heads = pl.BlockSpec((None, W // LANES, T, LANES),
                         lambda b, t, h: (b, h, t, 0))
    table = pl.BlockSpec((T, LANES), lambda b, t, h: (t, 0))
    operands = [*rows_in, *heads_in]
    in_specs = [rows] * len(rows_in) + [heads] * len(heads_in)
    if backward:
        out_specs, out_shape = rows, jax.ShapeDtypeStruct(shape, dtype)
    else:
        out_specs = heads
        out_shape = jax.ShapeDtypeStruct((B, HD // LANES, L, LANES), dtype)
    if scale is not None:
        operands.append(scale)
        in_specs.append(pl.BlockSpec((1, LANES), lambda b, t, h: (0, 0)))
        out_specs = [out_specs, pl.BlockSpec(
            (None, None, None, 1, LANES), lambda b, t, h: (b, t, h, 0, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct(grid + (1, LANES), _f32)]
    # the scope's name is the call's instruction name in the compiled
    # program and so in a device trace
    with jax.named_scope(name):
        return pl.pallas_call(
            kernel, name=name, grid=grid,
            in_specs=in_specs + [table] * len(tables),
            out_specs=out_specs, out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3),
            interpret=pallas_attention._interpret())(*operands, *tables)


def _rotations(cos, sin, n: int):
    """The turn as rotations of a head's lanes: the shifts, cos, and the
    signed sin each rotation meets, (L, 128) each (``x_{i+n}`` meets
    ``-sin`` on the first ``n`` channels, ``x_{i-n}`` meets ``sin`` on the
    next ``n``; one rotation serves both where ``2 n`` is the head)."""
    channel = jnp.arange(LANES)
    first = jnp.where(channel < n, -sin, 0.0)
    second = jnp.where((channel >= n) & (channel < 2 * n), sin, 0.0)
    if 2 * n == LANES:
        return (n,), cos, [first + second]
    return (LANES - n, n), cos, [first, second]


@functools.partial(jax.jit, static_argnames=("n", "eps"))
def _forward(y, scale, cos, sin, n, eps):
    shifts, cos, sins = _rotations(cos, sin, n)
    if scale is not None:
        # x w cos + rot(x w) sin = x (w cos) + rot(x) (rot(w) sin)
        cos = cos * scale
        sins = [s * jnp.roll(scale, shift) for s, shift in zip(sins, shifts)]
    return _call(functools.partial(_fwd_kernel, shifts=shifts, eps=eps),
                 _FWD_NAME, y.shape, y.dtype, [y], [], [cos, *sins])


@functools.partial(jax.jit, static_argnames=("n", "eps"))
def _backward(y, scale, cos, sin, g, n, eps):
    shifts, cos, sins = _rotations(cos, sin, n)
    # pair^T = -pair: rot_back(g sin) = rot_back(g) rot_back(sin)
    back = tuple(LANES - s for s in shifts)
    tables = [cos, *(jnp.roll(s, b, 1) for s, b in zip(sins, back))]
    if scale is None:
        return _call(functools.partial(_turn_bwd_kernel, shifts=back),
                     _BWD_NAME, y.shape, y.dtype, [], [g], tables,
                     backward=True), None
    dy, dw = _call(functools.partial(
        _bwd_kernel, shifts=back, eps=eps, rows=y.shape[1]), _BWD_NAME,
        y.shape, y.dtype, [y], [g], tables, scale[None, :], backward=True)
    return dy, dw.sum((0, 1, 2, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _norm_turn(y, scale, cos, sin, n, eps):
    """``y`` (B, L, H * 128) to the turned rows, head-major (B, H, L,
    128)."""
    return _forward(y, scale, cos, sin, n, eps)


def _fwd_rule(y, scale, cos, sin, n, eps):
    return _forward(y, scale, cos, sin, n, eps), (y, scale, cos, sin)


def _bwd_rule(n, eps, res, g):
    y, scale, cos, sin = res
    dy, dw = _backward(y, scale, cos, sin, g, n, eps)
    # the tables are made of positions and frequencies: nothing learns
    return dy, dw, jnp.zeros_like(cos), jnp.zeros_like(sin)


_norm_turn.defvjp(_fwd_rule, _bwd_rule)


def head_norm_turn(y: jax.Array, cos: jax.Array, sin: jax.Array, n: int,
                   scale: Optional[jax.Array] = None,
                   eps: Optional[float] = None) -> jax.Array:
    """``y`` (B, L, H, d) normed a head (with ``scale`` (d,) float32 and
    ``eps``; without, not) and turned by ``cos`` and ``sin`` (L, d)
    float32 as ``parts.rotary`` makes them for ``n`` frequencies, in
    ``y``'s type; a shape ``supports`` takes."""
    if (scale is None) != (eps is None):
        raise ValueError("a norm takes a scale and an eps")
    B, L, H, d = y.shape
    # head-major out of the call; the attention kernels' own transposition
    # of q and k undoes this one
    return _norm_turn(y.reshape(B, L, H * d), scale, cos, sin, n,
                      eps).transpose(0, 2, 1, 3)
