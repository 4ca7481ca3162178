"""Mamba-2's state-space rule: its chunk-local half as Pallas TPU calls.

``ops/linear_attention.py``'s module docstring has the mathematics. With
``g = dt A``, ``G`` its running sum inside a chunk of 256 tokens and ``D_ij
= exp(G_i - G_j)`` for ``j <= i``, everything but the walk of the state
from chunk to chunk is local to one chunk of one head, and ``B`` and ``C``
are shared by a group's heads. The calls here read ``x`` as the ``(B, L, H
* P)`` rows the convolution wrote, ``B`` and ``C`` as ``(B, L, G * N)``
rows and ``dt``, ``g`` as float32 rows of a chunk, ``(B, L / 256, H,
256)``; they make a chunk's running sum, its decays and its ``256 x 256``
decay mask a head in VMEM and write only what the walk and the layer read.
A program is ONE chunk of ``HEADS`` heads of one group (16 x 64: 1,024
lanes of ``x``); the group's ``B`` and ``C`` blocks stay where they are while the
grid's last axis walks the group's heads.

- ``ssd_chunk_fwd`` (grid ``(B, chunks, programs of heads)``): the chunk's
  addend to the state, ``Z_c = (exp(G_C - G) dt x)^T B``, float32, the
  program's heads in ONE product (the group's ``B`` is their second
  operand), written chunk-major in the layout the scan carries
  (``(chunks, B, G, (H / G) P, N)``: a head's state transposed, the
  group's heads one under the other, ``N`` in the lanes).
- ``ssd_chunk_bwd`` (``jax.custom_vjp``): ``dZ`` back to ``x``, ``B``,
  ``dt`` and ``g``; ``dB`` is summed over the group's programs in the
  resident output block (the grid's last axis is sequential).
- ``ssd_chunk_out`` after the walk: ``C B^T`` and ``C S_0`` (the states in
  the products' operand type: they are only ever read as one) once a
  program, then a head at a time ``y = ((C B^T) * D)(dt x) + exp(G) (C
  S_0)`` straight into ``(B, L, H * P)`` float32 rows; the masked score
  tile lives in VMEM only.
- ``ssd_chunk_out_bwd``: its products transposed; ``dG_i = sum_j (dD *
  D)_ij - sum_j (dD * D)_ji`` and the ``e^G`` term, then the reverse
  running sum; gradients to ``x``, ``B``, ``C``, ``dt``, ``g`` and the
  starting states.

A chunk's scalars arrive as rows (heads on the sublanes, tokens in the
lanes: four vector registers for sixteen heads). The running sum is eight
doubling steps of a rotation and a masked addition along the lanes; the
rows are turned into columns, and the gradients' columns back into rows,
by ONE transposition a program, so a head's column is exactly its row.
Every decay is the exponential of a difference that is not positive; none
is a quotient. Every product takes ``dtype`` operands and accumulates in
float32; ``G``, the decays, ``Z``, the state and ``y`` are float32.

The calls carry their scope's name into the compiled program and a device
trace. Each is jitted, so the layers of a model lower one Mosaic module a
shape. On the CPU they run interpreted.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mmlspark_tpu.ops import pallas_attention
from mmlspark_tpu.ops.pallas_delta_rule import (
    LANES, _NT, _TN, _VMEM_LIMIT, _dot, _f32)

CHUNK = 256
# heads a program: the (heads, 256) blocks of dt and g want whole tiles of 8
# sublanes; 16 measured 9% faster than 8 in every call (PERF.md, PR 39)
HEADS = 16
_FWD_NAME = "ssd_chunk_fwd"
_BWD_NAME = "ssd_chunk_bwd"
_OUT_NAME = "ssd_chunk_out"
_OUT_BWD_NAME = "ssd_chunk_out_bwd"


def supports(chunk: int, heads: int, head_dim: int, groups: int,
             state: int) -> bool:
    """Shapes the calls take: the chunk they are written for, a state
    width that fills the lanes, heads in whole groups and a group's heads
    in whole programs of ``HEADS`` that fill whole lanes."""
    return (chunk == CHUNK and state % LANES == 0 and heads % groups == 0
            and (heads // groups) % HEADS == 0
            and (HEADS * head_dim) % LANES == 0)


def _running_sum(rows, reverse: bool = False):
    """The running sum along the lanes of ``rows`` (R, 256), from the left
    or from the right: eight doubling steps of a rotation and a masked
    addition (a chunk's scalars of sixteen heads are four vector registers)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    step = 1
    while step < CHUNK:
        if reverse:
            rows = rows + jnp.where(lane < CHUNK - step, pltpu.roll(
                rows, CHUNK - step, 1), 0.0)
        else:
            rows = rows + jnp.where(lane >= step, pltpu.roll(
                rows, step, 1), 0.0)
        step *= 2
    return rows


def _columns(*rows):
    """Rows (HEADS, 256) each, one under the other and turned: (256, 128),
    the ``k``-th argument's head ``j`` in lane ``k * HEADS + j``. One
    transposition a program, so a head's column is exactly its row."""
    pad = jnp.zeros((LANES - len(rows) * HEADS, CHUNK), _f32)
    return jnp.concatenate(rows + (pad,), axis=0).T


def _scalars(dt_ref, g_ref):
    """A program's heads' scalars: ``G`` as rows (HEADS, 256), and ``cols``
    (256, 128) with dt in lanes [0, HEADS) and ``G`` in lanes [HEADS, 2
    HEADS)."""
    Gr = _running_sum(g_ref[...])
    return Gr, _columns(dt_ref[...], Gr)


def _seen():
    """``i >= j`` over a chunk's (256, 256) tile."""
    return jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)


def _decays(seen, G, G_row):
    """A head's decay mask ``D_ij = exp(G_i - G_j)`` for ``j <= i``, else
    0, from its running sum as a column and as a row."""
    return jnp.exp(jnp.where(seen, G - G_row, -jnp.inf))


def _head(j: int, P: int):
    """Head ``j``'s lanes of a block of rows, its rows of a block of
    states."""
    return pl.ds(j * P, P)


def _col(cols, k: int, j: int):
    """Head ``j``'s column of the ``k``-th argument of ``_columns``."""
    return cols[:, k * HEADS + j:k * HEADS + j + 1]


def _fwd_kernel(x_ref, b_ref, dt_ref, g_ref, z_ref, xs_ref, *, dtype, P):
    _, cols = _scalars(dt_ref, g_ref)
    for j in range(HEADS):
        h, G = _head(j, P), _col(cols, 1, j)
        scale = _col(cols, 0, j) * jnp.exp(G[CHUNK - 1:] - G)
        xs_ref[:, h] = (x_ref[:, h].astype(_f32) * scale).astype(dtype)
    # the program's heads in ONE product: the group's B is their second
    # operand, and a head's 64 lanes alone fill half the MXU's width
    z_ref[...] = _dot(xs_ref[...], b_ref[...].astype(dtype), _TN)


def _bwd_kernel(x_ref, b_ref, dt_ref, g_ref, dz_ref,
                dx_ref, db_ref, ddt_ref, dg_ref, cols_ref, xs_ref, dxs_ref,
                *, dtype, P, programs):
    # the programs of one group follow each other on the grid's last axis:
    # dB of the group is summed in the resident block
    @pl.when(pl.program_id(2) % programs == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)

    _, cols = _scalars(dt_ref, g_ref)
    dZ = dz_ref[...].astype(dtype)
    dxs_ref[...] = _dot(b_ref[...].astype(dtype), dZ, _NT)
    for j in range(HEADS):
        h, G = _head(j, P), _col(cols, 1, j)
        E = jnp.exp(G[CHUNK - 1:] - G)
        x, scale = x_ref[:, h].astype(_f32), _col(cols, 0, j) * E
        dxs = dxs_ref[:, h]
        xs_ref[:, h] = (x * scale).astype(dtype)
        dx_ref[:, h] = (dxs * scale).astype(dx_ref.dtype)
        ds = jnp.sum(dxs * x, axis=1, keepdims=True)
        cols_ref[:, j:j + 1] = ds * E
        cols_ref[:, HEADS + j:HEADS + j + 1] = ds * scale
    db_ref[...] += _dot(xs_ref[...], dZ)
    rows = cols_ref[...].T
    ddt_ref[...] = rows[:HEADS]
    # E_j = exp(G_C - G_j): dG_j = -dEE_j and dG_C = sum dEE, so the
    # reverse running sum is dg_j = sum over i < j of dEE_i
    dEE = rows[HEADS:2 * HEADS]
    dg_ref[...] = _running_sum(dEE) - dEE


def _out_kernel(x_ref, b_ref, c_ref, dt_ref, g_ref, s0_ref, o_ref, *,
                dtype, P):
    seen, (Gr, cols) = _seen(), _scalars(dt_ref, g_ref)
    Cc = c_ref[...].astype(dtype)
    CB = _dot(Cc, b_ref[...].astype(dtype), _NT)
    # C S_0 of the program's heads in ONE product, into the output block
    o_ref[...] = _dot(Cc, s0_ref[...].astype(dtype), _NT)
    for j in range(HEADS):
        h, G = _head(j, P), _col(cols, 1, j)
        D = _decays(seen, G, Gr[j:j + 1])
        v = (x_ref[:, h].astype(_f32) * _col(cols, 0, j)).astype(dtype)
        o_ref[:, h] = _dot((CB * D).astype(dtype), v) \
            + jnp.exp(G) * o_ref[:, h]


def _out_bwd_kernel(x_ref, b_ref, c_ref, dt_ref, g_ref, s0_ref, dy_ref,
                    dx_ref, db_ref, dc_ref, ddt_ref, dg_ref, ds0_ref,
                    dcb_ref, cols_ref, rows_ref, cs_ref, dcs_ref, *, dtype,
                    P, programs):
    @pl.when(pl.program_id(2) % programs == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    seen, (Gr, cols) = _seen(), _scalars(dt_ref, g_ref)
    Bc, Cc = b_ref[...].astype(dtype), c_ref[...].astype(dtype)
    S0 = s0_ref[...].astype(dtype)
    CB = _dot(Cc, Bc, _NT)
    cs_ref[...] = _dot(Cc, S0, _NT)
    dcb_ref[...] = jnp.zeros_like(dcb_ref)
    for j in range(HEADS):
        h, dt, G = _head(j, P), _col(cols, 0, j), _col(cols, 1, j)
        D = _decays(seen, G, Gr[j:j + 1])
        scores = CB * D
        x, dy = x_ref[:, h].astype(_f32), dy_ref[:, h]
        dyc = dy.astype(dtype)
        # y = scores v + e^G (C S_0^T), v = dt x
        dv = _dot(scores.astype(dtype), dyc, _TN)
        dP = _dot(dyc, (x * dt).astype(dtype), _NT)
        dx_ref[:, h] = (dv * dt).astype(dx_ref.dtype)
        cols_ref[:, j:j + 1] = jnp.sum(dv * x, axis=1, keepdims=True)
        dcb_ref[...] += dP * D
        # through the mask: dG_i = sum_j (dD * D)_ij - sum_j (dD * D)_ji,
        # the first a column, the second a row
        dDD = dP * scores
        rows_ref[j:j + 1, :] = jnp.sum(dDD, axis=0, keepdims=True)
        dCS = dy * jnp.exp(G)
        cols_ref[:, HEADS + j:HEADS + j + 1] = jnp.sum(
            dDD, axis=1, keepdims=True) + jnp.sum(
                dCS * cs_ref[:, h], axis=1, keepdims=True)
        dcs_ref[:, h] = dCS.astype(dtype)
    dCB, dCS = dcb_ref[...].astype(dtype), dcs_ref[...]
    dc_ref[...] += _dot(dCS, S0) + _dot(dCB, Bc)
    db_ref[...] += _dot(dCB, Cc, _TN)
    ds0_ref[...] = _dot(dCS, Cc, _TN).astype(ds0_ref.dtype)
    rows = cols_ref[...].T
    ddt_ref[...] = rows[:HEADS]
    # G = cumsum(g) a chunk: dg_j = sum over i >= j of dG_i
    dg_ref[...] = _running_sum(rows[HEADS:2 * HEADS] - rows_ref[...], True)


def _specs(B: int, Nc: int, dims):
    """The grid (row, chunk, program of ``HEADS`` heads) and its block
    specs: x rows (B, L, H * P); B and C rows (B, L, G * N) at the
    program's group; dt and g (B, Nc, H, 256); states (Nc, B, G, (H / G) P,
    N)."""
    H, P, Gr, N = dims
    programs = H // Gr // HEADS         # a group
    return dict(
        grid=(B, Nc, H // HEADS), programs=programs,
        rows=pl.BlockSpec((None, CHUNK, HEADS * P),
                          lambda b, c, h: (b, c, h)),
        shared=pl.BlockSpec((None, CHUNK, N),
                            lambda b, c, h: (b, c, h // programs)),
        scalars=pl.BlockSpec((None, None, HEADS, CHUNK),
                             lambda b, c, h: (b, c, h, 0)),
        states=pl.BlockSpec(
            (None, None, None, HEADS * P, N),
            lambda b, c, h: (c, b, h // programs, h % programs, 0)))


def _call(kernel, name, grid, in_specs, out_specs, out_shape, last_axis,
          scratch, *operands):
    # the scope's name is the call's instruction name in the compiled
    # program and so in a device trace
    with jax.named_scope(name):
        return pl.pallas_call(
            kernel, name=name, grid=grid, in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", last_axis),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=pallas_attention._interpret())(*operands)


def _cols():
    return pltpu.VMEM((CHUNK, LANES), _f32)


def _wide(P: int, dtype):
    """A program's heads side by side, as its block of rows."""
    return pltpu.VMEM((CHUNK, HEADS * P), dtype)


def _states(x, dims):
    H, P, Gr, N = dims
    return jax.ShapeDtypeStruct(
        (x.shape[1] // CHUNK, x.shape[0], Gr, H // Gr * P, N), _f32)


def _like(*arrays):
    return [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays]


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _forward(x, b, dt, g, dims, dtype):
    s = _specs(x.shape[0], x.shape[1] // CHUNK, dims)
    return _call(
        functools.partial(_fwd_kernel, dtype=dtype, P=dims[1]), _FWD_NAME,
        s["grid"], [s["rows"], s["shared"], s["scalars"], s["scalars"]],
        s["states"], _states(x, dims), "parallel", [_wide(dims[1], dtype)],
        x, b, dt, g)


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _backward(x, b, dt, g, dz, dims, dtype):
    s = _specs(x.shape[0], x.shape[1] // CHUNK, dims)
    ins = [s["rows"], s["shared"], s["scalars"], s["scalars"]]
    dx, db, ddt, dg = _call(
        functools.partial(_bwd_kernel, dtype=dtype, P=dims[1],
                          programs=s["programs"]), _BWD_NAME, s["grid"],
        ins + [s["states"]], ins,
        _like(x, jax.ShapeDtypeStruct(b.shape, _f32), dt, g), "arbitrary",
        [_cols(), _wide(dims[1], dtype), _wide(dims[1], _f32)],
        x, b, dt, g, dz)
    return dx, db.astype(b.dtype), ddt, dg


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def ssd_chunk(x: jax.Array, b: jax.Array, dt: jax.Array, g: jax.Array,
              dims: Tuple[int, int, int, int], dtype: Any) -> jax.Array:
    """The chunks' addends to the state. ``x`` (B, L, H * P) and ``b`` (B,
    L, G * N) rows, ``L`` whole chunks; ``dt`` and ``g = dt A`` (B, L / 256,
    H, 256) float32; ``dims`` = (H, P, G, N). Returns ``Z`` (L / 256, B, G,
    (H / G) P, N) float32."""
    return _forward(x, b, dt, g, dims, jnp.dtype(dtype))


def _fwd_rule(x, b, dt, g, dims, dtype):
    return ssd_chunk(x, b, dt, g, dims, dtype), (x, b, dt, g)


def _bwd_rule(dims, dtype, res, dz):
    return _backward(*res, dz, dims, jnp.dtype(dtype))


ssd_chunk.defvjp(_fwd_rule, _bwd_rule)


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _out_forward(x, b, c, dt, g, s0, dims, dtype):
    s = _specs(x.shape[0], x.shape[1] // CHUNK, dims)
    return _call(
        functools.partial(_out_kernel, dtype=dtype, P=dims[1]), _OUT_NAME,
        s["grid"], [s["rows"], s["shared"], s["shared"], s["scalars"],
                    s["scalars"], s["states"]], s["rows"],
        jax.ShapeDtypeStruct(x.shape, _f32), "parallel", [],
        x, b, c, dt, g, s0)


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _out_backward(x, b, c, dt, g, s0, dy, dims, dtype):
    s = _specs(x.shape[0], x.shape[1] // CHUNK, dims)
    ins = [s["rows"], s["shared"], s["shared"], s["scalars"], s["scalars"],
           s["states"]]
    shared = jax.ShapeDtypeStruct(b.shape, _f32)
    dx, db, dc, ddt, dg, ds0 = _call(
        functools.partial(_out_bwd_kernel, dtype=dtype, P=dims[1],
                          programs=s["programs"]), _OUT_BWD_NAME, s["grid"],
        ins + [s["rows"]], ins, _like(x, shared, shared, dt, g, s0),
        "arbitrary",
        [pltpu.VMEM((CHUNK, CHUNK), _f32), _cols(),
         pltpu.VMEM((HEADS, CHUNK), _f32), _wide(dims[1], _f32),
         _wide(dims[1], dtype)], x, b, c, dt, g, s0, dy)
    return dx, db.astype(b.dtype), dc.astype(c.dtype), ddt, dg, ds0


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def ssd_chunk_out(x: jax.Array, b: jax.Array, c: jax.Array, dt: jax.Array,
                  g: jax.Array, s0: jax.Array,
                  dims: Tuple[int, int, int, int], dtype: Any) -> jax.Array:
    """``y = ((C B^T) * D)(dt x) + exp(G) (C S_0)`` chunk by chunk, written
    as (B, L, H * P) float32 rows: the operands of ``ssd_chunk`` and ``c``
    as ``b``, ``s0`` (L / 256, B, G, (H / G) P, N) from the walk, in the
    products' operand type (it is only ever read as one)."""
    return _out_forward(x, b, c, dt, g, s0, dims, jnp.dtype(dtype))


def _out_fwd_rule(x, b, c, dt, g, s0, dims, dtype):
    return ssd_chunk_out(x, b, c, dt, g, s0, dims, dtype), \
        (x, b, c, dt, g, s0)


def _out_bwd_rule(dims, dtype, res, dy):
    return _out_backward(*res, dy, dims, jnp.dtype(dtype))


ssd_chunk_out.defvjp(_out_fwd_rule, _out_bwd_rule)
