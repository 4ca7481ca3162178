"""Fused flash attention as a Pallas TPU kernel.

The L x L score matrix is the HBM killer in long-context attention: plain
``softmax(q @ k^T) @ v`` materializes O(B*H*L^2) floats through HBM three
times (scores, softmax, weighted sum). The flash formulation streams K/V
blocks through VMEM with an online softmax — scores never leave VMEM, HBM
traffic drops to O(B*H*L*D), and both matmuls tile the MXU back to back.

This kernel is the single-device core that composes with the
context-parallel layer (``parallel/sequence.py``): ring attention rotates
K/V blocks BETWEEN chips with the same online-softmax algebra this kernel
applies WITHIN a chip, so `full_attention`'s fallback, this kernel, and
the ring path all agree numerically (tests pin them together).

Layout: (B, L, H, D) like every attention_fn in the framework; the grid
is (B, H, L/block_q), each program owning one query block against the
full K/V stream for its (batch, head).
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
    """Whether the fused kernel applies: block-divisible length of at
    least two query blocks, a sublane-friendly head dim, and K + V of at
    most 2 * 2^20 elements (8 MiB in fp32) to hold per (batch, head) in
    Mosaic's 16 MiB scoped VMEM. ``full_attention`` counts, or under
    ``use_flash="require"`` refuses, every other shape."""
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
    return _flash_bwd_impl(q, k, v, out, do, causal, block_k)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)
