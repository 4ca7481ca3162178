"""Each query's ``k`` largest scores of its past, exactly, as a mask: one
Pallas TPU call over scores that lie keys-in-rows.

``topk_mask(scores, k)`` takes ``(B, L / t, L, t)`` float32 scores, ``[b,
i, s, j]`` the score of key ``s`` for query ``i t + j`` (a query tile a
slab, keys in rows: what ``ops/sparse_attention.indexer_scores`` writes and
the flash kernels read a selection by), and gives the int8 mask of the same
shape that keeps, for query ``q``, the ``min(k, q + 1)`` largest scores
among the keys ``s <= q``, a tie going to the lower ``s`` (``lax.top_k``'s
rule, which ``sparse_attention._topk_mask_xla`` is the statement of).

A program owns one query tile. Its keys' scores arrive a chunk of rows a
grid step and are kept in VMEM as ORDERED INTEGERS (a float's bits, the
negatives' magnitude flipped: integer order is float order, the two zeros
one integer; a key of the future is the least integer, below every
score). At the last chunk:

1. the ``kth`` largest of each lane's column by bisection on the integer's
   32 bits, from the top: a bit stays set where at least ``kth`` entries
   are still at or above the candidate. A pass is a compare and a count
   over the causal chunks in VMEM: 32 passes, no sort and no HBM traffic;
2. ``need`` = ``kth`` minus the count strictly above it, at least 1: how
   many of the entries EQUAL to it are kept, the first ones by row;
3. one pass in row order writes the mask: above, or equal with a rank
   among the equals of at most ``need``. A chunk's ranks are a product with
   a lower-triangular matrix of ones (counts to 512 are exact in the MXU's
   float32 accumulator) on top of the count the chunks before it left.

Everything is exact: no approximation, no choice by block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mmlspark_tpu.ops import pallas_attention
from mmlspark_tpu.ops.pallas_attention import _VMEM_CAP

_CHUNK = 512        # rows of keys a grid step brings and a pass walks
_INT_MIN = -2 ** 31


def supports(shape, chunk: int = _CHUNK) -> bool:
    """Whether ``topk_mask`` takes scores of this ``(B, L / t, L, t)``
    shape: query tiles of whole lanes, rows of whole chunks, and a tile's
    ordered integers (``L x t x 4`` bytes) with its mask within what a
    program may ask of VMEM."""
    _, n, L, t = shape
    chunk = min(chunk, L)
    return n * t == L and t % 128 == 0 and L % chunk == 0 \
        and chunk % 32 == 0 and _vmem_bytes(L, t, chunk) <= _VMEM_CAP


def _vmem_bytes(L: int, t: int, chunk: int) -> int:
    """The ordered integers, the mask's block twice (it is written back
    while the next tile fills), a chunk twice, the triangle, and about six
    chunk-sized temporaries."""
    return L * t * 4 + 2 * L * t + 2 * chunk * t * 4 + chunk * chunk * 2 \
        + 6 * chunk * t * 4


def _kernel(scores_ref, mask_ref, keys, *, k: int, chunk: int):
    qi, c = pl.program_id(1), pl.program_id(2)
    t = keys.shape[1]
    query = qi * t + jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
    rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
    key_of = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, t), 0)
    # a negative float's magnitude flipped, plus one: -0.0 is 0 as +0.0 is
    # (equal as floats, and so as integers), and the order stays
    bits = jax.lax.bitcast_convert_type(scores_ref[...], jnp.int32)
    ordered = jnp.where(bits < 0, (bits ^ jnp.int32(2 ** 31 - 1)) + 1, bits)
    keys[rows, :] = jnp.where(key_of <= query, ordered, jnp.int32(_INT_MIN))

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        # the chunks that hold a key of this tile's past
        causal = ((qi + 1) * t + chunk - 1) // chunk
        kth = jnp.minimum(k, query + 1)

        def count(test):
            def body(i, total):
                x = keys[pl.ds(pl.multiple_of(i * chunk, chunk), chunk), :]
                return total + jnp.sum(test(x).astype(jnp.int32), axis=0,
                                       keepdims=True)
            return jax.lax.fori_loop(0, causal, body,
                                     jnp.zeros((1, t), jnp.int32))

        def bit(i, found):
            # in offset binary (x ^ INT_MIN: unsigned order is signed order)
            cand = found | jax.lax.shift_left(jnp.int32(1), 31 - i)
            at_or_above = count(
                lambda x: x >= (cand ^ jnp.int32(_INT_MIN)))
            return jnp.where(at_or_above >= kth, cand, found)
        found = jax.lax.fori_loop(0, 32, bit, jnp.zeros((1, t), jnp.int32))
        thresh = found ^ jnp.int32(_INT_MIN)
        need = (kth - count(lambda x: x > thresh)).astype(jnp.float32)
        lower = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
                 >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
                 ).astype(jnp.bfloat16)

        def write(i, before):
            at = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
            x = keys[at, :]
            equal = x == thresh
            rank = before + jnp.dot(lower, equal.astype(jnp.bfloat16),
                                    preferred_element_type=jnp.float32)
            keep = (x > thresh) | (equal & (rank <= need))
            mask_ref[at, :] = keep.astype(jnp.int32).astype(jnp.int8)
            return rank[chunk - 1:, :]
        jax.lax.fori_loop(0, causal, write, jnp.zeros((1, t), jnp.float32))

        def blank(i, _):
            mask_ref[pl.ds(pl.multiple_of(i * chunk, chunk), chunk), :] = \
                jnp.zeros((chunk, t), jnp.int8)
        jax.lax.fori_loop(causal, pl.num_programs(2), blank, None)


_NAME = "topk_mask"


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def topk_mask(scores: jax.Array, k: int, chunk: int = _CHUNK) -> jax.Array:
    """``(B, L / t, L, t)`` float32 scores -> int8 mask of that shape (the
    module's docstring has the rule). ``supports`` says which shapes."""
    b, n, L, t = scores.shape
    chunk = min(chunk, L)
    if not supports(scores.shape, chunk):
        raise ValueError(f"topk_mask on scores of shape {scores.shape}: "
                         "supports refuses it")
    with jax.named_scope(_NAME):
        return pl.pallas_call(
            functools.partial(_kernel, k=int(k), chunk=chunk),
            name=_NAME,
            grid=(b, n, L // chunk),
            in_specs=[pl.BlockSpec((None, None, chunk, t),
                                   lambda bi, qi, c: (bi, qi, c, 0))],
            out_specs=pl.BlockSpec((None, None, L, t),
                                   lambda bi, qi, c: (bi, qi, 0, 0)),
            out_shape=jax.ShapeDtypeStruct(scores.shape, jnp.int8),
            scratch_shapes=[pltpu.VMEM((L, t), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=min(max(
                    _vmem_bytes(L, t, chunk) * 5 // 4, 32 << 20), _VMEM_CAP)),
            interpret=pallas_attention._interpret(),
        )(scores)
