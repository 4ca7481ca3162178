"""Linear attention with a recurrent state: the gated delta rule, and the
short causal convolution in front of it.

Per head, with a state ``S`` of ``(dk, dv)`` that is zero where the row
starts (Gated DeltaNet, Yang et al. 2024, arXiv:2412.06464)::

    S   <- exp(g_t) S                   # per-head, per-token decay, g_t <= 0
    u_t  = beta_t (v_t - S^T k_t)       # the delta rule's correction
    S   <- S + k_t u_t^T
    o_t  = S^T q_t

``gated_delta_rule`` is the one entry; it has two forms of the same
mathematics:

- **recurrent**: the four lines above under ``lax.scan``, one token a
  step, float32 (in blocks of ``chunk`` tokens whose inner loop the
  backward pass recomputes, so that a long row keeps a state a block).
  The form the tests hold the other to, and what rows shorter than a
  chunk take.
- **chunked**: tokens in chunks of ``chunk`` (64). With ``G`` the running
  sum of ``g`` inside a chunk, ``D_ij = exp(G_i - G_j)`` for ``j <= i`` and
  ``A_ij = beta_i D_ij (k_i . k_j)`` for ``j < i``, the corrections of a
  whole chunk solve ``(I + A) U = beta (V - exp(G) K S_0)``: with ``T = (I
  + A)^-1`` (the WY / UT transform), ``W = T (beta exp(G) K)`` and ``U_0 =
  T (beta V)``, ``U = U_0 - W S_0``; then ``O = (exp(G) Q) S_0 + ((Q K^T) *
  D) U`` and ``S_C = exp(G_C) S_0 + (exp(G_C - G) K)^T U``. Everything but
  the walk of ``S`` from chunk to chunk is batched matrix products over
  all chunks at once; the walk is ONE ``lax.scan`` with two products a
  step (``W S`` and ``K^T U``), under the scope ``gated_delta_rule``. Its
  backward pass is that scan's transpose: the state at each chunk's start
  is what it keeps (``(N, B, H, dk, dv)`` float32), never a state a token.

Every decay is the exponential of a difference that is not positive
(``G_i - G_j`` under the causal mask, ``G_i``, ``G_C - G_j``); none is a
quotient of two exponentials, which would be 0 / 0 once a head's decay
over a chunk passes float32's range (at ``g`` = -20 a token, three tokens
in). ``T`` is made in float32 at the highest matmul precision: blocks of at
most 16 rows by the finite Neumann product ``(I - A)(I + A^2)(I + A^4)(I +
A^8)``, joined two and two by ``[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R
P^-1, Q^-1]]``, so that no power of ``A`` past the fifteenth is formed.
The other products take ``dtype`` operands (bfloat16 on the chip) and
accumulate in float32; state, decay and sums are float32.

Counters, per TRACE: ``linear_attention.calls.<chunked|recurrent>``, and
``linear_attention.fallbacks`` for a trace on an accelerator that took the
token-by-token form under ``impl="auto"``.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from mmlspark_tpu.observability import metrics as obsmetrics

CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST
_NEUMANN_ROWS = 16


def causal_conv1d(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """Depthwise causal convolution over the sequence: ``x`` (B, L, C),
    ``kernel`` (W, C); ``y_t = sum_j kernel[j] * x_{t - (W-1) + j}`` with
    ``W - 1`` zeros before the row's start, no bias."""
    width, L = kernel.shape[0], x.shape[1]
    with jax.named_scope("gdn_conv"):
        xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
        return sum(xp[:, j:j + L] * kernel[j].astype(x.dtype)
                   for j in range(width))


def l2_normalize(x: jax.Array) -> jax.Array:
    """``x / sqrt(sum(x^2) + 1e-6)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _mm(eq: str, a, b, dtype):
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _mm32(a, b):
    return jnp.matmul(a, b, precision=_HIGHEST)


def inv_unit_lower(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for strictly lower triangular ``a`` (..., n, n)."""
    n = a.shape[-1]
    if n <= _NEUMANN_ROWS:
        out = jnp.eye(n, dtype=a.dtype) - a
        power = a
        for _ in range(max(0, math.ceil(math.log2(max(n, 1))) - 1)):
            power = _mm32(power, power)
            out = out + _mm32(out, power)
        return out
    h = n // 2
    p, q = inv_unit_lower(a[..., :h, :h]), inv_unit_lower(a[..., h:, h:])
    low = -_mm32(_mm32(q, a[..., h:, :h]), p)
    top = jnp.concatenate(
        [p, jnp.zeros(p.shape[:-1] + (n - h,), a.dtype)], -1)
    return jnp.concatenate([top, jnp.concatenate([low, q], -1)], -2)


def _recurrent(q, k, v, g, beta, block: int):
    """Token by token, in blocks of ``block`` tokens whose inner loop is
    recomputed in the backward pass: a state a block is kept, and a state
    a token only while one block's backward runs."""
    f32 = jnp.float32
    B, L, H, dk = q.shape
    pad = -L % block

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None, None] * S
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", S, k_t, precision=_HIGHEST))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HIGHEST)

    def blocks(x):      # (B, L, H, ...) -> (L / block, block, B, H, ...)
        x = jnp.pad(x.astype(f32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((-1, block) + x.shape[1:])
    _, o = jax.lax.scan(
        jax.checkpoint(lambda S, x: jax.lax.scan(step, S, x)),
        jnp.zeros((B, H, dk, v.shape[-1]), f32),
        tuple(blocks(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:]), 0, 1)[:, :L]


def _chunked(q, k, v, g, beta, chunk: int, dtype):
    f32 = jnp.float32
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    pad = -L % chunk
    if pad:     # g = 0, beta = 0, k = 0: the state passes through unchanged
        q, k, v, g, beta = (jnp.pad(
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    N, C = (L + pad) // chunk, chunk

    def split(x):               # (B, L, H, ...) -> (B, H, N, C, ...)
        x = x.reshape((B, N, C) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)
    q, k, v = (split(x.astype(f32)) for x in (q, k, v))
    g, beta = split(g.astype(f32)), split(beta.astype(f32))

    G = jnp.cumsum(g, axis=-1)                          # (B, H, N, C)
    rows = jnp.arange(C)
    seen = rows[:, None] >= rows[None, :]
    D = jnp.exp(jnp.where(seen, G[..., :, None] - G[..., None, :], -jnp.inf))
    kk = _mm("bhnid,bhnjd->bhnij", k, k, dtype)
    A = jnp.where(rows[:, None] > rows[None, :],
                  beta[..., None] * D * kk, 0.0)
    T = inv_unit_lower(A)
    eG = jnp.exp(G)[..., None]
    W = _mm("bhnij,bhnjd->bhnid", T, k * (beta[..., None] * eG), dtype)
    U0 = _mm("bhnij,bhnjd->bhnid", T, v * beta[..., None], dtype)
    P = _mm("bhnid,bhnjd->bhnij", q, k, dtype) * D
    Kd = k * jnp.exp(G[..., -1:] - G)[..., None]
    last = jnp.exp(G[..., -1])                          # (B, H, N)

    def walk(S, x):
        W_c, U0_c, Kd_c, a_c = x
        U_c = U0_c - _mm("bhid,bhde->bhie", W_c, S, dtype)
        nxt = a_c[..., None, None] * S \
            + _mm("bhid,bhie->bhde", Kd_c, U_c, dtype)
        return nxt, (S, U_c)

    with jax.named_scope("gated_delta_rule"):
        _, (S0, U) = jax.lax.scan(
            walk, jnp.zeros((B, H, dk, dv), f32),
            tuple(jnp.moveaxis(x, 2, 0) for x in (W, U0, Kd, last)))
    S0, U = jnp.moveaxis(S0, 0, 2), jnp.moveaxis(U, 0, 2)
    o = _mm("bhnid,bhnde->bhnie", q * eG, S0, dtype) \
        + _mm("bhnij,bhnje->bhnie", P, U, dtype)
    o = jnp.moveaxis(o, 1, 3).reshape(B, L + pad, H, dv)
    return o[:, :L]


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array,
                     g: jax.Array, beta: jax.Array, *, chunk: int = CHUNK,
                     impl: str = "auto", dtype: Any = None) -> jax.Array:
    """The gated delta rule over whole rows, state zero at each row's start.

    ``q``, ``k`` (B, L, H, dk), ``v`` (B, L, H, dv), ``g`` (log decay, <= 0)
    and ``beta`` (B, L, H); returns (B, L, H, dv) float32. ``q`` is scaled
    by ``dk ** -0.5``. ``impl``: "auto" (chunked from
    one whole chunk up, else token by token) | "chunked" | "recurrent".
    ``dtype``: the matrix products' operand type in the chunked form
    (default: ``q``'s own); the recurrent form is float32 throughout.
    """
    if impl not in ("auto", "chunked", "recurrent"):
        raise ValueError(f"unknown impl {impl!r}")
    if g.shape != q.shape[:3] or beta.shape != q.shape[:3] \
            or k.shape != q.shape or v.shape[:3] != q.shape[:3]:
        raise ValueError(
            f"shapes q {q.shape} k {k.shape} v {v.shape} g {g.shape} "
            f"beta {beta.shape}")
    dtype = dtype or q.dtype
    q = q.astype(jnp.float32) * q.shape[-1] ** -0.5
    taken = impl
    if impl == "auto":
        taken = "chunked" if q.shape[1] >= chunk else "recurrent"
        if taken == "recurrent" and jax.default_backend() != "cpu":
            obsmetrics.counter("linear_attention.fallbacks").inc()
    obsmetrics.counter(f"linear_attention.calls.{taken}").inc()
    if taken == "recurrent":
        return _recurrent(q, k, v, g, beta, chunk)
    return _chunked(q, k, v, g, beta, chunk, dtype)
