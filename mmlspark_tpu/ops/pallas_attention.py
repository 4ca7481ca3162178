"""Fused attention as Pallas TPU kernels: flash for long sequences, one
whole-sequence call for short ones.

The L x L score matrix is the HBM killer in attention: plain
``softmax(q @ k^T) @ v`` materializes O(B*H*L^2) floats through HBM three
times (scores, softmax, weighted sum), and again in the backward pass.

- ``flash_attention`` (L a multiple of 256, at least 512) streams K/V
  blocks through VMEM with an online softmax — scores never leave VMEM,
  HBM traffic drops to O(B*H*L*D), and both matmuls tile the MXU back to
  back. Grid (B, H, L/block_q), each program owning one query block
  against the full K/V stream for its (batch, head).
- ``short_attention`` (a sequence that fits one VMEM block: ViT's 197
  tokens, an LM's short prompts) needs no online softmax: one program
  holds a batch row's whole sequence and ALL its heads, reads q, k, v
  once and writes the output once, forward and backward. It works on
  ``(B, L, H*D)`` — the layout the q/k/v projection matmuls write and
  the output projection reads — so no head-layout copy surrounds it, and
  handles a ragged L inside (``_short_fwd_kernel``).

Both are the single-device core that composes with the context-parallel
layer (``parallel/sequence.py``): ring attention rotates K/V blocks
BETWEEN chips with the same online-softmax algebra the flash kernel
applies WITHIN a chip, so `full_attention`'s reference, these kernels, and
the ring path all agree numerically (tests pin them together).

Interface layout: (B, L, H, D) like every attention_fn in the framework.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
BLOCK_Q = 256
BLOCK_K = 256


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
                  scale: float):
    # refs are (1, 1, L-block, D): batch and head ride the grid, so the
    # last two dims are the (8, 128)-tileable (rows, lanes) pair Mosaic
    # wants
    q = q_ref[0, 0, :, :].astype(jnp.float32) * scale        # (bq, d)
    bq = q.shape[0]
    L = k_ref.shape[2]
    d = q.shape[1]
    qi = pl.program_id(2)
    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.dslice(i * block_k, block_k), :]
        v = v_ref[0, 0, pl.dslice(i * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bq, bk)
        if causal:
            k_idx = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(k_idx <= q_idx, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                                # (bq, bk)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot(
            p, v.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    # causal: blocks entirely in the masked future contribute nothing —
    # bound the trip count by this program's query block (the dynamic
    # upper bound is supported; saves ~half the matmul work on decoders)
    n_blocks = L // block_k
    if causal:
        n_blocks = jnp.minimum(
            n_blocks, ((qi + 1) * bq + block_k - 1) // block_k)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    o_ref[0, 0, :, :] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, block_q: int = BLOCK_Q,
                    block_k: int = BLOCK_K) -> jax.Array:
    """(B, L, H, D) fused attention; requires L divisible by the blocks
    (``supports`` tells callers when to fall back). Differentiable: the
    backward pass recomputes attention blockwise (``_flash_bwd``), so
    training keeps the O(L * block) memory profile."""
    return _flash_forward(q, k, v, causal, block_q, block_k)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def _flash_forward(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool = False, block_q: int = BLOCK_Q,
                   block_k: int = BLOCK_K) -> jax.Array:
    b, L, h, d = q.shape
    scale = 1.0 / float(np.sqrt(d))
    vmem = pl.ANY if _interpret() else pltpu.VMEM
    kernel = functools.partial(_flash_kernel, block_k=block_k,
                               causal=causal, scale=scale)
    # (B, L, H, D) -> (B, H, L, D): head ahead of length so kernel blocks
    # end in the tileable (rows, lanes) pair; XLA fuses the transposes
    # into the surrounding program
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = pl.pallas_call(
        kernel,
        grid=(b, h, L // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0),
                         memory_space=vmem),
            pl.BlockSpec((1, 1, L, d), lambda bi, hi, qi: (bi, hi, 0, 0),
                         memory_space=vmem),
            pl.BlockSpec((1, 1, L, d), lambda bi, hi, qi: (bi, hi, 0, 0),
                         memory_space=vmem),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi: (bi, hi, qi, 0),
                               memory_space=vmem),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=_interpret(),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


# per-(batch, head) K and V stay fully VMEM-resident in the kernel, and
# Mosaic must fit them in its scoped VMEM: 16 MiB by default on a v5e (of
# 128 MiB physical). L * d <= 2^20 elements keeps K + V at 8 MiB in fp32
# (4 MiB in bf16) — half the limit, the rest left to the q/out blocks, the
# fp32 upcasts and the score tile. Measured on the chip (PR 21, d = 64):
# fp32 still compiles at K + V = 16 MiB (L = 32768) and bf16 at L = 65536;
# twice that is refused ("Scoped allocation with size 32.00M and limit
# 16.00M"), as is fp32 d = 128 at L = 16384 (16.50M).
_VMEM_KV_LIMIT = 1 << 20   # L * d elements


def supports(q_shape, block_q: int = BLOCK_Q, block_k: int = BLOCK_K) -> bool:
    """Whether the flash kernel applies: block-divisible length of at
    least two query blocks, a sublane-friendly head dim, and K + V of at
    most 2 * 2^20 elements (8 MiB in fp32) to hold per (batch, head) in
    Mosaic's 16 MiB scoped VMEM. ``full_attention`` asks this first, then
    ``supports_short``, and counts, or under ``use_flash="require"``
    refuses, a shape neither takes."""
    _, L, _, d = q_shape
    return L % block_q == 0 and L % block_k == 0 and L >= 2 * block_q \
        and d % 8 == 0 and L * d <= _VMEM_KV_LIMIT


def _flash_fwd_rule(q, k, v, causal, block_q, block_k):
    out = _flash_forward(q, k, v, causal, block_q, block_k)
    return out, (q, k, v, out)


@functools.partial(jax.jit, static_argnames=("causal", "block_k"))
def _flash_bwd_impl(q, k, v, out, do, causal, block_k):
    """Blockwise flash backward in plain jnp: one ``lax.scan`` over K/V
    blocks recomputes the probabilities from (q, k) plus a recomputed
    row log-sum-exp, and accumulates dq and the per-block dk/dv — memory
    stays O(L * block), never the O(L^2) score matrix, so long-context
    TRAINING keeps the flash memory profile. XLA compiles the scanned
    matmuls straight onto the MXU; no hand-written Mosaic backward
    needed for correctness or memory."""
    b, L, h, d = q.shape
    scale = 1.0 / float(np.sqrt(d))
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    n_idx = jnp.arange(L)

    # pass 1: row log-sum-exp by online max/sum over k blocks
    def lse_body(carry, kb):
        m, s = carry
        kblk, k0 = kb
        logit = jnp.einsum("blhd,bjhd->blhj", qf, kblk,
                           preferred_element_type=jnp.float32)
        if causal:
            mask = (k0 + jnp.arange(block_k))[None, None, None, :] \
                > n_idx[None, :, None, None]
            logit = jnp.where(mask, _NEG_INF, logit)
        m_new = jnp.maximum(m, logit.max(axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.exp(
            logit - m_new[..., None]).sum(axis=-1)
        return (m_new, s), None

    kblocks = kf.reshape(b, L // block_k, block_k, h, d).transpose(
        1, 0, 2, 3, 4)
    vblocks = vf.reshape(b, L // block_k, block_k, h, d).transpose(
        1, 0, 2, 3, 4)
    offsets = jnp.arange(L // block_k) * block_k
    m0 = jnp.full((b, L, h), _NEG_INF, jnp.float32)
    (m, s), _ = jax.lax.scan(lse_body, (m0, jnp.zeros((b, L, h))),
                             (kblocks, offsets))
    lse = m + jnp.log(jnp.maximum(s, 1e-30))

    # D_i = rowsum(do * o) (the softmax-jacobian contraction)
    Drow = (dof * out.astype(jnp.float32)).sum(axis=-1)      # (b, L, h)

    # pass 2: accumulate dq, and per-block dk/dv
    def grad_body(dq, blk):
        kblk, vblk, k0 = blk
        logit = jnp.einsum("blhd,bjhd->blhj", qf, kblk,
                           preferred_element_type=jnp.float32)
        if causal:
            mask = (k0 + jnp.arange(block_k))[None, None, None, :] \
                > n_idx[None, :, None, None]
            logit = jnp.where(mask, _NEG_INF, logit)
        p = jnp.exp(logit - lse[..., None])                  # (b,L,h,bk)
        dp = jnp.einsum("blhd,bjhd->blhj", dof, vblk,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - Drow[..., None])                      # (b,L,h,bk)
        dq = dq + jnp.einsum("blhj,bjhd->blhd", ds, kblk,
                             preferred_element_type=jnp.float32)
        dkb = jnp.einsum("blhj,blhd->bjhd", ds, qf,
                         preferred_element_type=jnp.float32)
        dvb = jnp.einsum("blhj,blhd->bjhd", p, dof,
                         preferred_element_type=jnp.float32)
        return dq, (dkb, dvb)

    dq0 = jnp.zeros((b, L, h, d), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(grad_body, dq0,
                                  (kblocks, vblocks, offsets))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(b, L, h, d)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(b, L, h, d)
    return ((dq * scale).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


def _flash_bwd_rule(causal, block_q, block_k, res, do):
    q, k, v, out = res
    # metadata only: XLA names the scans `%while.N`, so the scope is what a
    # reader joins instruction names against to find this backward's time
    with jax.named_scope("flash_attention_bwd"):
        return _flash_bwd_impl(q, k, v, out, do, causal, block_k)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# Short sequences: the whole sequence and every head of one batch row in ONE
# program, forward and backward.
#
# The arrays are (B, L, H*D): what the q/k/v projection matmuls write and
# the output projection reads, so XLA puts no head-layout copy around the
# call. Heads are never sliced out of the lane dimension. The kernel walks
# the H*D lanes in groups of 128 (128 // D heads to a group) and picks a
# head with a lane mask: ``q2 @ where(head, k2, 0)^T`` contracts over the
# head's D lanes only, and ``p @ v2`` is right in the head's lanes of the
# (L, 128) result. On a 128-wide MXU a D = 64 contraction fills half the
# array whichever way it is fed, so the mask costs nothing a sliced head
# would have saved.
#
# A ragged L (ViT's 197) is padded INSIDE: the blocks are (1, Lp, H*D) with
# Lp = L rounded up to the 128-lane tile, the rows past L that such a block
# reads are unspecified and are zeroed as they are loaded, the padded key
# columns are masked to -inf before the softmax, and the rows past L that
# it writes are dropped. The model still has L tokens.
_LANES = 128
# what one backward program may hold of Mosaic's 16 MiB scoped VMEM, by the
# estimate of ``_short_vmem_bytes``; the rest is the compiler's own
_SHORT_VMEM_BUDGET = 12 << 20

_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def _padded_len(L: int) -> int:
    return -(-L // _LANES) * _LANES


def _short_vmem_bytes(L: int, h: int, d: int, itemsize: int) -> int:
    """VMEM one BACKWARD program needs (the larger of the two, and the
    choice is made when the forward is traced): q, k, v, o, do in and dq,
    dk, dv out, each block double-buffered, the log-sum-exps padded to the
    lane tile, and about eight (Lp, Lp) float32 score-sized temporaries."""
    lp = _padded_len(L)
    blocks = 8 * 2 * lp * h * d * itemsize
    lse = 2 * lp * _LANES * 4
    return blocks + lse + 8 * lp * lp * 4


def supports_short(q_shape, itemsize: int = 2) -> bool:
    """Whether ``short_attention`` applies: a head dim that divides the
    128-lane tile (or is one), and a whole (sequence, heads) block of one
    batch row within ``_SHORT_VMEM_BUDGET``. ViT-B/16 (197, 12, 64) takes
    8.25 MiB in bf16; (512, 12, 64) does not fit."""
    _, L, h, d = q_shape
    return _LANES % d == 0 and d >= 8 \
        and _short_vmem_bytes(L, h, d, itemsize) <= _SHORT_VMEM_BUDGET


def _lane_groups(width: int):
    """(start, width) of each group of at most 128 lanes."""
    return [(g, min(_LANES, width - g)) for g in range(0, width, _LANES)]


def _load_rows(ref, g0: int, w: int, L: int):
    """Lanes [g0, g0 + w) of a (1, Lp, W) block, rows past L zeroed: they
    lie outside the array and hold whatever the buffer held."""
    x = ref[0, :, g0:g0 + w]
    if L == x.shape[0]:
        return x
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(rows < L, x, jnp.zeros_like(x))


def _score_mask(lp: int, L: int, causal: bool, rows_too: bool):
    """(Lp, Lp) bool: key column inside the sequence (and not in the
    query's future); with ``rows_too`` the query row inside it as well.
    None when nothing is masked."""
    if L == lp and not causal:
        return None
    row = jax.lax.broadcasted_iota(jnp.int32, (lp, lp), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (lp, lp), 1)
    ok = col < L
    if causal:
        ok = ok & (col <= row)
    if rows_too and L < lp:
        ok = ok & (row < L)
    return ok


def _head_masks(lp: int, w: int, d: int):
    """One (Lp, w) lane mask per head of a group; None for a lone head."""
    if w == d:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (lp, w), 1)
    return [(lane >= i * d) & (lane < (i + 1) * d) for i in range(w // d)]


def _pick(mask, x):
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _short_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, L: int, d: int,
                      causal: bool, scale: float):
    lp, width = q_ref.shape[1], q_ref.shape[2]
    ok = _score_mask(lp, L, causal, rows_too=False)
    h_lane = jax.lax.broadcasted_iota(jnp.int32, lse_ref.shape[1:], 1)
    lse = jnp.zeros(lse_ref.shape[1:], jnp.float32)
    for g0, w in _lane_groups(width):
        # q's rows past L give rows of the output that are dropped
        q2 = q_ref[0, :, g0:g0 + w] * scale
        k2 = _load_rows(k_ref, g0, w, L)
        v2 = _load_rows(v_ref, g0, w, L)
        o2 = jnp.zeros((lp, w), jnp.float32)
        for i, head in enumerate(_head_masks(lp, w, d)):
            s = jax.lax.dot_general(q2, _pick(head, k2), _NT,
                                    preferred_element_type=jnp.float32)
            if ok is not None:
                s = jnp.where(ok, s, _NEG_INF)
            m = s.max(axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = p.sum(axis=-1, keepdims=True)
            pv = jax.lax.dot(p.astype(v2.dtype), v2,
                             preferred_element_type=jnp.float32)
            pv = pv * (1.0 / l)
            o2 = pv if head is None else jnp.where(head, pv, o2)
            lse = jnp.where(h_lane == g0 // d + i, m + jnp.log(l), lse)
        o_ref[0, :, g0:g0 + w] = o2.astype(o_ref.dtype)
    lse_ref[0] = lse


def _short_bwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref,
                      dq_ref, dk_ref, dv_ref, *, L: int, d: int,
                      causal: bool, scale: float):
    lp, width = q_ref.shape[1], q_ref.shape[2]
    # a query row past L must give nothing to dk and dv
    ok = _score_mask(lp, L, causal, rows_too=True)
    h_lane = jax.lax.broadcasted_iota(jnp.int32, lse_ref.shape[1:], 1)
    lse_all = lse_ref[0]
    for g0, w in _lane_groups(width):
        q2 = _load_rows(q_ref, g0, w, L) * scale
        k2 = _load_rows(k_ref, g0, w, L)
        v2 = _load_rows(v_ref, g0, w, L)
        do2 = _load_rows(do_ref, g0, w, L)
        o2 = _load_rows(o_ref, g0, w, L)
        # rowsum(do * o) per head: the softmax jacobian's contraction
        dd = do2.astype(jnp.float32) * o2.astype(jnp.float32)
        dq2 = jnp.zeros((lp, w), jnp.float32)
        dk2 = jnp.zeros((lp, w), jnp.float32)
        dv2 = jnp.zeros((lp, w), jnp.float32)
        for i, head in enumerate(_head_masks(lp, w, d)):
            s = jax.lax.dot_general(q2, _pick(head, k2), _NT,
                                    preferred_element_type=jnp.float32)
            lse = jnp.where(h_lane == g0 // d + i, lse_all, 0.0).sum(
                axis=-1, keepdims=True)
            p = jnp.exp(s - lse)
            if ok is not None:
                p = jnp.where(ok, p, 0.0)
            dp = jax.lax.dot_general(do2, _pick(head, v2), _NT,
                                     preferred_element_type=jnp.float32)
            delta = _pick(head, dd).sum(axis=-1, keepdims=True)
            ds = (p * (dp - delta)).astype(q2.dtype)
            dv = jax.lax.dot_general(p.astype(do2.dtype), do2, _TN,
                                     preferred_element_type=jnp.float32)
            dk = jax.lax.dot_general(ds, q2, _TN,
                                     preferred_element_type=jnp.float32)
            dq = jax.lax.dot(ds, k2,
                             preferred_element_type=jnp.float32) * scale
            if head is None:
                dq2, dk2, dv2 = dq, dk, dv
            else:
                dq2 = jnp.where(head, dq, dq2)
                dk2 = jnp.where(head, dk, dk2)
                dv2 = jnp.where(head, dv, dv2)
        dq_ref[0, :, g0:g0 + w] = dq2.astype(dq_ref.dtype)
        dk_ref[0, :, g0:g0 + w] = dk2.astype(dk_ref.dtype)
        dv_ref[0, :, g0:g0 + w] = dv2.astype(dv_ref.dtype)


def _short_call(name: str, kernel, operands, outs, d: int, causal: bool):
    """One program per batch row over (B, L, .) operands; ``outs`` lists
    the (last dim, dtype) of the (B, L, .) results. ``name`` becomes, as a
    scope, the call's instruction name in the compiled program and so in a
    device trace (``%short_attention_fwd.3 = ... custom-call(``): the
    benchmark's ``kernel.attention_ms`` finds the calls by it."""
    b, L, _ = operands[0].shape
    lp = _padded_len(L)

    def rows(last):
        return pl.BlockSpec((1, lp, last), lambda i: (i, 0, 0))

    with jax.named_scope(name):
        return pl.pallas_call(
            functools.partial(kernel, L=L, d=d, causal=causal,
                              scale=1.0 / float(np.sqrt(d))),
            name=name,
            grid=(b,),
            in_specs=[rows(x.shape[2]) for x in operands],
            out_specs=[rows(last) for last, _ in outs],
            out_shape=[jax.ShapeDtypeStruct((b, L, last), dt)
                       for last, dt in outs],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=_interpret(),
        )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def short_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False) -> jax.Array:
    """(B, L, H, D) attention for a sequence that fits one VMEM block
    (``supports_short``): one fused call forward, one backward, nothing of
    size L x L in HBM. Operands stay in the input dtype on the MXU with
    float32 accumulation; scores and softmax are float32; P is cast to the
    input dtype for the second matmul, as the reference does. The scale
    1/sqrt(D) multiplies q in the input dtype (exact in bf16 for D = 16,
    64, 256)."""
    return _short_forward(q, k, v, causal)[0]


# Jitted, as ``_flash_forward`` is: the N blocks of a model then lower ONE
# Mosaic module and call it N times. That lowering runs in every process
# before the compile cache can be asked (its text is the key), so a call
# site of its own for each block is paid at every start.
@functools.partial(jax.jit, static_argnames=("causal",))
def _short_forward(q, k, v, causal):
    b, L, h, d = q.shape
    q3, k3, v3 = (x.reshape(b, L, h * d) for x in (q, k, v))
    o3, lse = _short_call("short_attention_fwd", _short_fwd_kernel,
                          (q3, k3, v3),
                          [(h * d, q.dtype), (h, jnp.float32)], d, causal)
    return o3.reshape(q.shape), (q3, k3, v3, o3, lse)


@functools.partial(jax.jit, static_argnames=("causal",))
def _short_backward(res, do, causal):
    q3, k3, v3, o3, lse = res
    d = do.shape[3]
    grads = _short_call(
        "short_attention_bwd", _short_bwd_kernel,
        (q3, k3, v3, o3, lse, do.reshape(q3.shape)),
        [(q3.shape[2], q3.dtype)] * 3, d, causal)
    return tuple(g.reshape(do.shape) for g in grads)


short_attention.defvjp(
    _short_forward, lambda causal, res, do: _short_backward(res, do, causal))
