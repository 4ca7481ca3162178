"""A projection's short causal convolution, its ``silu`` and a head's L2
norm as ONE pass over the projection's rows, and their derivative as one
more: Pallas TPU calls.

Between a q, k or v projection's output and the chunk calls of the delta
rule a layer of ``models/zoo/parts.KimiDeltaAttention`` convolves the rows
over the sequence (``linear_attention.causal_conv1d``, a handful of taps a
channel), takes ``silu`` and, for q and k, norms each head
(``l2_normalize``) and scales q. All of it is element-wise over ``(B, L, H
* d)`` but for a head's sum of squares; XLA makes it as a padded copy of
the rows, shifted bfloat16 products, a float32 copy for the sums and a
column broadcast back to full size. The arithmetic needs one read of the
projection's rows and one write of what the chunk call reads.

``conv_silu_norm(y, taps, heads, norm, scale)`` for ``y`` ``(B, L, H * d)``
and ``taps`` ``(W, H * d)`` float32: ``x = silu(sum_j taps[j] f32(y[t - (W
- 1) + j]))`` with zeros before a row's start; with ``norm`` ``x <- x
rsqrt(sum_d x^2 + 1e-6)`` over each head's ``d`` lanes, then times
``scale``, written float32; without, written in ``y``'s type. Float32 in
registers from the load to the store, each output rounded ONCE. A program
takes a tile of ``ROWS`` positions by ``WIDTH`` lanes of whole heads and
walks it ``CHUNK`` rows at a time; the ``W - 1`` rows before a tile come as
a second, small block of the same array through an index map of its own (a
packed register's rows, zeros at a row's start), never as a padded copy in
HBM. A chunk's rows delayed by ``k`` are LOADS: the chunk's float32 rows
are stored once into a scratch a register's lanes wide, the rows before
them above, and read back from ``k`` rows higher up, which costs the load
slots' and nothing of the VALU's (a rotation of the sublanes and a select
a register cost it two operations a delay; the order of arithmetic was
chosen from the v5e compiler's static bundle counts, PERF.md section 6, PR
52).

Its derivative (``jax.custom_vjp``) is one pass of the same kind: it reads
``y`` and the cotangent, makes the mix, the ``silu`` and the norm's factor
again, takes the norm's, the ``silu``'s and the convolution's derivatives
and writes ``dy`` in ``y``'s type and a program's float32 ``(W, lanes)``
addend to the taps' gradient (the pairs of a cotangent's row and a row of
``y`` that lies in the program's tile), which XLA sums. The convolution's
transpose
reads the mix's cotangent at the NEXT ``W - 1`` rows, so a tile's chunks
are walked from the last to the first through a second scratch of the same
kind, read from ``k`` rows further down, and the rows after the tile come
as the same kind of second block (of ``y`` and of the cotangent), nothing
at a row's end. Residuals: the call's operands and nothing else.

The calls carry their scope's name into the compiled program and a device
trace (``conv_silu_norm_fwd`` / ``_bwd``). Each is jitted, so the layers of
a model lower one Mosaic module a shape. On the CPU they run interpreted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mmlspark_tpu.ops import pallas_attention

LANES = 128
SUBLANES = 8
# a program's tile: rows of positions by lanes of whole heads
ROWS = 512
WIDTH = 1024
# rows of a tile in flight at once
CHUNK = 64
EPS = 1e-6
_FWD_NAME = "conv_silu_norm_fwd"
_BWD_NAME = "conv_silu_norm_bwd"
_f32 = jnp.float32


def _packed_rows(dtype) -> int:
    """The rows of one register of ``dtype``: 8 of float32, 16 of
    bfloat16."""
    return SUBLANES * 4 // jnp.dtype(dtype).itemsize


def supports(shape, width: int, dtype) -> bool:
    """Whether the calls take ``(B, L, H, d)`` rows of ``dtype`` under
    ``width`` taps: heads of whole registers, rows of bfloat16 or float32
    in whole tiles, and the ``width - 1`` rows before a tile inside one
    float32 register's eight."""
    _, L, _, d = shape
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(_f32)):
        return False
    return d % LANES == 0 and L % ROWS == 0 and 2 <= width <= SUBLANES + 1


def _lanes_of(columns: int, d: int, most: int) -> int:
    """A tile's lanes: whole heads, as many as ``most`` lanes hold and as
    divide the row."""
    heads = columns // d
    fit = max(1, most // d)
    return d * max(n for n in range(1, fit + 1) if heads % n == 0)


def _tail(ref, rows, lanes):
    """The last register of ``ref[rows, lanes]`` as float32 ``(8, 128)``:
    ``rows`` is one packed register's."""
    return ref[rows, lanes].astype(_f32)[-SUBLANES:]


def _slab(lanes) -> int:
    """Which register's width of a tile ``lanes`` is: the float32 rows the
    kernels shift lie a slab a register's lanes, ``(slabs, rows, 128)``,
    the one shape in which Mosaic loads from a row that is no multiple of
    eight (across more lanes it asks for proof of one)."""
    return lanes.start // LANES


def _delayed(rows_ref, lanes, at, before, x, width: int):
    """``x`` (rows, 128) float32, the tile's rows from ``at`` on, delayed
    by ``k`` rows for ``k`` in ``0 .. width - 1``, the rows ``before`` (8,
    128) let in at the top: through ``rows_ref``, which holds the tile's
    float32 rows a register further down and where a load may start at
    any row."""
    n, slab = x.shape[0], _slab(lanes)
    rows_ref[slab, pl.ds(at, SUBLANES), :] = before
    rows_ref[slab, pl.ds(at + SUBLANES, n), :] = x
    return [x] + [rows_ref[slab, pl.ds(at + SUBLANES - k, n), :]
                  for k in range(1, width)]


def _advanced(rows_ref, lanes, at, x, width: int):
    """``x`` (rows, 128), the tile's rows from ``at`` on, advanced by ``k``
    rows: through ``rows_ref``, which holds the rows after these
    already."""
    n, slab = x.shape[0], _slab(lanes)
    rows_ref[slab, pl.ds(at, n), :] = x
    return [x] + [rows_ref[slab, pl.ds(at + k, n), :]
                  for k in range(1, width)]


def _taps(taps_ref, lanes):
    """The taps of a register's lanes, a row each."""
    return [taps_ref[j:j + 1, lanes] for j in range(taps_ref.shape[0])]


def _mix(taps, shifted):
    """``sum_j taps[j] shifted[W - 1 - j]``: of rows delayed, ``sum_j
    taps[j] y[t - (W - 1) + j]`` in ``causal_conv1d``'s order."""
    width = len(shifted)
    out = taps[0] * shifted[width - 1]
    for j in range(1, width):
        out = out + taps[j] * shifted[width - 1 - j]
    return out


def _sigmoid(m):
    """``1 / (1 + exp(-m))``: the unit's reciprocal and one Newton step,
    which is what a float32 quotient is made of on the chip, without the
    quotient's care for zeros, infinities and NaNs that ``1 + exp`` never
    is (``m`` is held above -80: ``silu`` there is under 1e-33). The
    interpreter's approximate reciprocal is bfloat16's, so there the exact
    one takes its place."""
    e = 1.0 + jnp.exp(-jnp.maximum(m, -80.0))
    r = pl.reciprocal(e, approx=not pallas_attention._interpret())
    return r + r * (1.0 - r * e)


def _square_sum(xs):
    """A head's sum over its registers' lanes, a column."""
    out = jnp.sum(xs[0], axis=-1, keepdims=True)
    for x in xs[1:]:
        out = out + jnp.sum(x, axis=-1, keepdims=True)
    return out


def _heads(width: int, d: int):
    """A tile's heads, each the lane slices of its registers."""
    per = d // LANES
    return [[pl.ds((h * per + i) * LANES, LANES) for i in range(per)]
            for h in range(width // d)]


def _before(y_ref, before_ref, lanes, c, at, starts):
    """The register of float32 rows before a chunk that starts at row
    ``at`` of its tile: the tile's own, or at the tile's start the block
    before it, zeros at a row's start."""
    packed = before_ref.shape[0]
    inside = pl.ds(pl.multiple_of(jnp.maximum(at - packed, 0), packed),
                   packed)
    return jnp.where(c == 0, starts * _tail(before_ref, slice(None), lanes),
                     _tail(y_ref, inside, lanes))


def _fwd_kernel(y_ref, before_ref, taps_ref, o_ref, rows_ref, *, d, norm,
                scale, chunk):
    width = taps_ref.shape[0]
    # zeros before a row's start (read here: the interpreter finds no
    # program_id inside a loop)
    starts = (pl.program_id(1) > 0).astype(_f32)

    def step(c, _):
        at = pl.multiple_of(c * chunk, chunk)
        rows = pl.ds(at, chunk)
        for head in _heads(y_ref.shape[1], d):
            xs = []
            for lanes in head:
                m = _mix(_taps(taps_ref, lanes), _delayed(
                    rows_ref, lanes, at,
                    _before(y_ref, before_ref, lanes, c, at, starts),
                    y_ref[rows, lanes].astype(_f32), width))
                xs.append(m * _sigmoid(m))
            if norm:
                r = jax.lax.rsqrt(_square_sum([x * x for x in xs]) + EPS)
                xs = [x * r for x in xs]
                if scale != 1.0:
                    xs = [x * scale for x in xs]
            for lanes, x in zip(head, xs):
                o_ref[rows, lanes] = x.astype(o_ref.dtype)
    jax.lax.fori_loop(0, y_ref.shape[0] // chunk, step, None)


def _bwd_kernel(y_ref, before_ref, after_ref, g_ref, g_after_ref, taps_ref,
                dy_ref, dw_ref, rows_ref, ahead_ref, sums_ref, *, d, norm,
                scale, chunk):
    width = taps_ref.shape[0]
    tile, packed = y_ref.shape[0], before_ref.shape[0]
    n = tile // chunk
    heads = _heads(y_ref.shape[1], d)
    starts = (pl.program_id(1) > 0).astype(_f32)
    ends = (pl.program_id(1) < pl.num_programs(1) - 1).astype(_f32)
    sums_ref[...] = jnp.zeros_like(sums_ref)

    def mix_cotangent(head, at, befores, ys, gs):
        """A head's registers of the mix's cotangent ``dm``."""
        ms, sgs = [], []
        for lanes, before, y in zip(head, befores, ys):
            ms.append(_mix(_taps(taps_ref, lanes), _delayed(
                rows_ref, lanes, at, before, y, width)))
            sgs.append(_sigmoid(ms[-1]))
        if norm:
            xs = [m * s for m, s in zip(ms, sgs)]
            r = jax.lax.rsqrt(_square_sum([x * x for x in xs]) + EPS)
            ns = [x * r for x in xs]
            a = _square_sum([g * x for g, x in zip(gs, ns)])
            r = r * scale if scale != 1.0 else r
            gs = [(g - x * a) * r for g, x in zip(gs, ns)]
        return [g * (s * (1.0 + m * (1.0 - s)))
                for g, m, s in zip(gs, ms, sgs)]

    # the mix's cotangent at the rows after the tile: none at a row's end
    last = pl.ds(tile - packed, packed)
    for head in heads:
        dms = mix_cotangent(
            head, tile, [_tail(y_ref, last, lanes) for lanes in head],
            [after_ref[:, lanes].astype(_f32)[:SUBLANES] for lanes in head],
            [g_after_ref[:, lanes].astype(_f32)[:SUBLANES]
             for lanes in head])
        for lanes, dm in zip(head, dms):
            ahead_ref[_slab(lanes), tile:, :] = ends * dm

    def step(i, _):
        c = n - 1 - i
        at = pl.multiple_of(c * chunk, chunk)
        rows = pl.ds(at, chunk)
        for head in heads:
            ys = [y_ref[rows, lanes].astype(_f32) for lanes in head]
            dms = mix_cotangent(
                head, at, [_before(y_ref, before_ref, lanes, c, at, starts)
                           for lanes in head], ys,
                [g_ref[rows, lanes].astype(_f32) for lanes in head])
            for lanes, dm, y in zip(head, dms, ys):
                ahead = _advanced(ahead_ref, lanes, at, dm, width)
                # the transpose meets tap j at the row W - 1 - j ahead
                dy_ref[rows, lanes] = _mix(
                    _taps(taps_ref, lanes), ahead).astype(dy_ref.dtype)
                for k in range(width):
                    # sum_t dm[t] y[t - k] = sum_t dm[t + k] y[t]: a tile
                    # counts the pairs whose ROW of y is its own, so what
                    # meets the rows is what ``dy`` has loaded already. A
                    # register of partial sums a tap: the rows are summed
                    # once, when the tile is done
                    j = width - 1 - k
                    sums_ref[j * SUBLANES:(j + 1) * SUBLANES, lanes] += (
                        ahead[k] * y).reshape(-1, SUBLANES, LANES).sum(0)
    jax.lax.fori_loop(0, n, step, None)
    for j in range(width):
        dw_ref[j:j + 1, :] = jnp.sum(
            sums_ref[j * SUBLANES:(j + 1) * SUBLANES, :], axis=0,
            keepdims=True)


def _specs(shape, d: int, tile):
    """The grid, a tile's lanes and the blocks of one pass over ``(B, L,
    C)`` rows: the tile, and ``edge(dtype, after)``, one packed register's
    rows before or after it (held inside the row at its two ends, where
    the kernels put zeros)."""
    B, L, C = shape
    T, W = tile[0], _lanes_of(C, d, tile[1])
    grid = (B, L // T, C // W)

    def edge(dtype, after):
        rows = _packed_rows(dtype)
        per = T // rows
        if after:
            return pl.BlockSpec((None, rows, W), lambda b, t, h: (
                b, jnp.minimum((t + 1) * per, L // rows - 1), h))
        return pl.BlockSpec((None, rows, W), lambda b, t, h: (
            b, jnp.maximum(t * per - 1, 0), h))
    return grid, W, pl.BlockSpec((None, T, W), lambda b, t, h: (b, t, h)), \
        edge


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          operands):
    # the scope's name is the call's instruction name in the compiled
    # program and so in a device trace
    with jax.named_scope(name):
        return pl.pallas_call(
            kernel, name=name, grid=grid, in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM(shape, _f32) for shape in scratch],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3),
            interpret=pallas_attention._interpret())(*operands)


_STATIC = ("heads", "norm", "scale", "tile")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(y, taps, heads, norm, scale, tile):
    d = y.shape[-1] // heads
    grid, W, rows, edge = _specs(y.shape, d, tile)
    return _call(
        functools.partial(_fwd_kernel, d=d, norm=norm, scale=scale,
                          chunk=tile[2]),
        _FWD_NAME, grid,
        [rows, edge(y.dtype, False),
         pl.BlockSpec((taps.shape[0], W), lambda b, t, h: (0, h))], rows,
        jax.ShapeDtypeStruct(y.shape, _f32 if norm else y.dtype),
        [(W // LANES, tile[0] + SUBLANES, LANES)], (y, y, taps))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _backward(y, taps, g, heads, norm, scale, tile):
    d = y.shape[-1] // heads
    grid, W, rows, edge = _specs(y.shape, d, tile)
    width = taps.shape[0]
    dy, dw = _call(
        functools.partial(_bwd_kernel, d=d, norm=norm, scale=scale,
                          chunk=tile[2]),
        _BWD_NAME, grid,
        [rows, edge(y.dtype, False), edge(y.dtype, True), rows,
         edge(g.dtype, True),
         pl.BlockSpec((width, W), lambda b, t, h: (0, h))],
        [rows, pl.BlockSpec((None, None, width, W),
                            lambda b, t, h: (b, t, 0, h))],
        [jax.ShapeDtypeStruct(y.shape, y.dtype),
         jax.ShapeDtypeStruct(grid[:2] + taps.shape, _f32)],
        [(W // LANES, tile[0] + 2 * SUBLANES, LANES),
         (W // LANES, tile[0] + SUBLANES, LANES), (width * SUBLANES, W)],
        (y, y, y, g, g, taps))
    return dy, dw.sum((0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _conv_silu_norm(y, taps, heads, norm, scale, tile):
    return _forward(y, taps, heads, norm, scale, tile)


def _fwd_rule(y, taps, heads, norm, scale, tile):
    # through the custom_vjp itself, as ``pallas_kda``'s rule goes: a block
    # recomputed under a policy then lowers ONE forward module a shape and
    # one for its recomputation; ``_forward`` called here lowers one a CALL
    return _conv_silu_norm(y, taps, heads, norm, scale, tile), (y, taps)


def _bwd_rule(heads, norm, scale, tile, res, g):
    y, taps = res
    return _backward(y, taps, g, heads, norm, scale, tile)


_conv_silu_norm.defvjp(_fwd_rule, _bwd_rule)


def conv_silu_norm(y: jax.Array, taps: jax.Array, heads: int,
                   norm: bool = False, scale: float = 1.0) -> jax.Array:
    """``y`` (B, L, H * d) through the causal depthwise convolution of
    ``taps`` (W, H * d) float32 and ``silu``; with ``norm`` each of the
    ``heads`` heads L2-normed and times ``scale``, float32, else in ``y``'s
    type; a shape ``supports`` takes."""
    if not norm and scale != 1.0:
        raise ValueError("a scale comes with the norm")
    return _conv_silu_norm(y, taps.astype(_f32), heads, bool(norm),
                           float(scale), (ROWS, WIDTH, min(CHUNK, ROWS)))
