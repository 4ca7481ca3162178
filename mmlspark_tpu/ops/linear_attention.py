"""Linear attention with a recurrent state: two rules over one state a
head (the first with a decay a head or a key channel), and the short causal
convolution in front of them.

**The gated delta rule** (Gated DeltaNet, Yang et al. 2024,
arXiv:2412.06464). Per head, with a state ``S`` of ``(dk, dv)`` that is
zero where the row starts::

    S   <- exp(g_t) S                   # per-head, per-token decay, g_t <= 0
    u_t  = beta_t (v_t - S^T k_t)       # the delta rule's correction
    S   <- S + k_t u_t^T
    o_t  = S^T q_t

``gated_delta_rule`` is its entry; it has two forms of the same
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
  The batched half has two executors, picked from the call's shapes as
  ``full_attention`` picks its kernel: where ``chunk`` is 64 and either
  both head widths are multiples of 128 and the value heads are whole
  groups a key head, or there are as many key as value heads and four
  heads or fewer side by side fill whole lanes (96 x 192: 384 and 768)
  (``pallas_delta_rule.supports``), the Pallas calls of
  ``ops/pallas_delta_rule.py`` make a chunk's tiles in VMEM from q, k, v
  as ``(B, L, H * d)`` rows (q and k at KEY-head width: a value head
  reads key head ``h // (Hv / Hk)`` through the block's index map; heads
  that fill no whole lanes go to a program four at a time and it slices
  each one's lanes in VMEM) and hand the walk ``W``, ``Kd`` in ``dtype``
  and ``U_0`` in float32, chunk-major, at the heads' own widths (no
  parameter and no state is padded); after the walk a third call writes
  ``O`` as rows. Otherwise
  (``_chunked``) XLA's batched products over float32 head-major copies,
  q and k repeated to the value heads first: the form every test holds
  the calls to, and what other shapes run. The walk is the same scan.

**The same rule with a decay a key CHANNEL** (Kimi Delta Attention, "Kimi
Linear", arXiv:2510.26692): ``g_t`` is a vector over the ``dk`` key channels
and the first line reads ``S <- Diag(exp(g_t)) S``, row ``c`` of the state
times ``exp(g_t[c])``. ``gated_delta_rule`` takes it by ``g``'s shape, ``(B,
L, H, dk)`` for ``(B, L, H)`` (``_kda``; as many key as value heads), in
the same three forms. Chunked, ``G`` is a vector a token and every ``exp(G)``
above a scaling of the key channels (``W = T (beta (exp(G) * K))``, ``O =
(exp(G) * Q) S_0 + P U``, ``S_C = Diag(exp(G_C)) S_0 + (exp(G_C - G) * K)^T
U``: the walk is ``_delta_walk`` with ``last`` a vector, under the scope
``kda_state_walk``), but the decay of ``K K^T`` and ``Q K^T`` lies INSIDE
their contraction, ``A_ij = beta_i sum_c k_i[c] k_j[c] exp(G_i[c] -
G_j[c])``: no matrix ``D`` multiplies the product afterwards. The causal
triangle of a chunk is therefore split by the highest bit in which ``i``
and ``j`` differ (``_kda_products``): at level ``s`` row ``i`` lies in the
upper and ``j`` in the lower half of one block of ``2 s`` rows, and with
``R`` = ``G`` at the upper half's first row ``exp(G_i - G_j) = exp(G_i - R)
exp(R - G_j)``, both exponents <= 0, so the level is ONE product of two
scaled operands under its mask: six products for a chunk of 64 where the
scalar rule has one. The executors: the Pallas calls of
``ops/pallas_kda.py`` at chunks of 64 and heads of 128 x 128
(``pallas_kda.supports``; XLA makes the running sum), else ``_chunked_kda``.

**The state-space rule of Mamba-2** (SSD; Dao and Gu 2024,
arXiv:2405.21060). The same skeleton with the correction taken out (``T =
I``, ``W = 0``, ``U = V`` above), a scalar decay a head, and keys and
queries (``B`` and ``C``) shared by groups of heads. Per head, with a
state ``S`` of ``(N, P)``::

    S   <- exp(dt_t A) S + dt_t B_t x_t^T       # A < 0, dt_t > 0
    y_t  = S^T C_t

``ssd`` is its entry, with the same two forms. Chunked (256): with ``g_t
= dt_t A`` and ``G``, ``D`` as above, ``O = exp(G) (C S_0) + ((C B^T) * D)
(dt x)`` and ``S_C = exp(G_C) S_0 + B^T (exp(G_C - G) dt x)``. With no
correction a chunk's addend to the state does not depend on the state, so
it too is computed for all chunks at once, as are ``C B^T`` (per group) and
``C S_0``; neither ``B`` nor ``C`` is ever repeated to the heads. The walk
is ONE ``lax.scan`` (``_ssd_walk``, scope ``ssd_scan``) carrying the float32
state as ``(rows, G, (H / G) P, N)`` (a head's state transposed, a group's
heads one under the other, ``N`` in the lanes), a multiply and an add a
step; it hands out the state each chunk starts from. The chunk-local half
has two executors picked from the call's shapes (``pallas_ssd.supports``:
chunk 256, ``N`` a multiple of 128, sixteen heads of a group filling whole
lanes): the Pallas calls of ``ops/pallas_ssd.py``, else ``_ssd_chunked``.

Every decay is the exponential of a difference that is not positive
(``G_i - G_j`` under the causal mask, ``G_i``, ``G_C - G_j``); none is a
quotient of two exponentials, which would be 0 / 0 once a head's decay
over a chunk passes float32's range (at ``g`` = -20 a token, three tokens
in; at Mamba-2's ``dt A`` = -1.6 a token, a chunk of 256 passes it several
times over). ``T`` is made in float32 at the highest matmul precision:
blocks of at most 16 rows by the finite Neumann product ``(I - A)(I +
A^2)(I + A^4)(I + A^8)``, joined two and two by ``[[P, 0], [R, Q]]^-1 =
[[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]``, so that no power of ``A`` past the
fifteenth is formed.
The other products take ``dtype`` operands (bfloat16 on the chip) and
accumulate in float32; state, decay and sums are float32.

**What lies in front of the rules.** ``causal_conv1d`` (the short causal
depthwise convolution), ``l2_normalize`` (a head's L2 norm) and, for a layer
whose q, k and v each pass a convolution of their own and ``silu`` (Kimi
Delta Attention), ``conv_silu_norm``: the convolution, ``silu``, and for q
and k the norm and a scale, from a projection's rows to what the chunk call
reads. It has two forms picked from the shape alone
(``pallas_conv_norm.supports`` on a device's own part of the rows): heads
of whole registers (a multiple of 128 channels), rows of bfloat16 or float32
in whole tiles of 512 and at most nine taps take ONE Pallas pass and one
more for the derivative (``ops/pallas_conv_norm.py``: float32 in registers
from the load to the store, the taps as the parameter holds them, one
rounding; q and k leave as float32 rows, v in the rows' type; residuals the
rows and the taps); any other shape takes the first two functions and
``silu`` as XLA fuses them, bfloat16 products included. ``gated_delta_rule``
scales q by ``dk ** -0.5`` itself unless q comes ``q_scaled``, which
``conv_silu_norm``'s does in either form (the scale is its last multiply:
the value that enters the rule is the same float32 to the last bit, and no
float32 pass over q stands between the two).

Counters, per TRACE: ``linear_attention.conv_norm_calls.<pallas|xla>`` (the
form ``conv_silu_norm`` took),
``linear_attention.calls.<chunked|recurrent>``,
``linear_attention.rule_calls.<delta|kda|ssd>``, the executor of the batched
half ``linear_attention.chunk_calls.<pallas|xla>`` (delta rule),
``linear_attention.kda_chunk_calls.<pallas|xla>`` (a decay a key channel) and
``linear_attention.ssd_chunk_calls.<pallas|xla>`` (state-space rule), and
``linear_attention.fallbacks`` (token-by-token on a chip under "auto").
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from mmlspark_tpu.observability import metrics as obsmetrics
from mmlspark_tpu.ops import pallas_delta_rule as pdr

CHUNK = 64
SSD_CHUNK = 256
_HIGHEST = jax.lax.Precision.HIGHEST
_NEUMANN_ROWS = 16


def causal_conv1d(x: jax.Array, kernel: jax.Array,
                  bias: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal convolution over the sequence: ``x`` (B, L, C),
    ``kernel`` (W, C); ``y_t = sum_j kernel[j] * x_{t - (W-1) + j}`` with
    ``W - 1`` zeros before the row's start, plus ``bias`` (C,) if given."""
    width, L = kernel.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    y = sum(xp[:, j:j + L] * kernel[j].astype(x.dtype) for j in range(width))
    return y if bias is None else y + bias.astype(x.dtype)


def l2_normalize(x: jax.Array) -> jax.Array:
    """``x / sqrt(sum(x^2) + 1e-6)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def conv_silu_norm(y: jax.Array, taps: jax.Array, heads: int,
                   norm: bool = False, scale: float = 1.0) -> jax.Array:
    """What lies between a projection and the delta rule's chunk calls:
    ``y`` (B, L, H * d) through ``causal_conv1d`` under ``taps`` (W, H * d)
    and ``silu``; with ``norm`` each of the ``heads`` heads through
    ``l2_normalize`` and times ``scale``, float32, else in ``y``'s type.
    Two forms, picked from the shape alone (``pallas_conv_norm.supports``
    on a device's own part of the rows under a ``with mesh:`` block, as
    ``parallel/sequence.on_own_rows`` splits them): heads of whole
    registers, rows of bfloat16 or float32 in whole tiles and at most nine
    taps take ONE Pallas pass from the projection's rows to what the chunk
    call reads and one more for the derivative
    (``ops/pallas_conv_norm.py``: float32 from the load to the store, one
    rounding); any other shape takes the three functions above, as XLA
    fuses them. Counted a trace as
    ``linear_attention.conv_norm_calls.<pallas|xla>``."""
    from mmlspark_tpu.ops import pallas_conv_norm as kernel
    from mmlspark_tpu.parallel.sequence import on_own_rows, own_shape
    B, L, C = y.shape
    local = own_shape((B, L, heads, C // heads))
    if local is not None and kernel.supports(local, taps.shape[0], y.dtype):
        obsmetrics.counter("linear_attention.conv_norm_calls.pallas").inc()
        return on_own_rows(
            lambda y, taps: kernel.conv_silu_norm(
                y.reshape(y.shape[:2] + (-1,)),
                taps.reshape(taps.shape[0], -1), y.shape[2], norm,
                scale).reshape(y.shape),
            y.reshape(B, L, heads, -1),
            heads=(taps.reshape(taps.shape[0], heads, -1),)).reshape(B, L, C)
    obsmetrics.counter("linear_attention.conv_norm_calls.xla").inc()
    x = jax.nn.silu(causal_conv1d(y, taps))
    if not norm:
        return x
    x = l2_normalize(x.reshape(B, L, heads, -1))
    return (x if scale == 1.0 else x * scale).reshape(B, L, C)


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


def _token_blocks(x, block: int):
    """(B, L, ...) -> float32 (ceil(L / block), block, B, ...), zeros past
    the row's end."""
    pad = -x.shape[1] % block
    x = jnp.pad(x.astype(jnp.float32),
                ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    x = jnp.moveaxis(x, 1, 0)
    return x.reshape((-1, block) + x.shape[1:])


def _rows_of_blocks(o, length: int):
    """What ``_token_blocks`` split, put together again: (B, L, ...)."""
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:]), 0, 1)[:, :length]


def _recurrent(q, k, v, g, beta, block: int):
    """Token by token, in blocks of ``block`` tokens whose inner loop is
    recomputed in the backward pass: a state a block is kept, and a state
    a token only while one block's backward runs."""
    f32 = jnp.float32
    B, L, H, dk = q.shape

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        # a decay a head, or (B, H, dk) a key channel: row c of S times it
        S = jnp.exp(g_t)[(...,) + (None,) * (S.ndim - g_t.ndim)] * S
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", S, k_t, precision=_HIGHEST))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HIGHEST)

    _, o = jax.lax.scan(
        jax.checkpoint(lambda S, x: jax.lax.scan(step, S, x)),
        jnp.zeros((B, H, dk, v.shape[-1]), f32),
        tuple(_token_blocks(x, block) for x in (q, k, v, g, beta)))
    return _rows_of_blocks(o, L)


def _whole_chunks(xs, chunk: int):
    """Rows (B, L, ...) padded with zeros to whole chunks: the arrays and
    the number of chunks. Both rules pass their state through a token of
    zeros unchanged."""
    L = xs[0].shape[1]
    pad = -L % chunk
    if pad:
        xs = tuple(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                   for x in xs)
    return xs, (L + pad) // chunk


def _chunks(x, N: int):
    """(B, N * C, H, ...) -> (B, H, N, C, ...)."""
    x = x.reshape((x.shape[0], N, -1) + x.shape[2:])
    return jnp.moveaxis(x, 3, 1)


def _delta_walk(W, U0, Kd, last, dtype, scope: str = "gated_delta_rule"):
    """The walk of the state from chunk to chunk, ONE ``lax.scan``: ``W``,
    ``U0``, ``Kd`` (N, B, H, C, width) and ``last`` = exp(G_C) (N, B, H), or
    (N, B, H, dk) where the decay is a key channel's (row c of the state
    times ``last[c]``); the state each chunk starts from, (N, B, H, dk,
    dv), and the chunks' corrections ``U`` (N, B, H, C, dv), float32."""
    def walk(S, x):
        W_c, U0_c, Kd_c, a_c = x
        U_c = U0_c - _mm("bhid,bhde->bhie", W_c, S, dtype)
        nxt = a_c[(...,) + (None,) * (S.ndim - a_c.ndim)] * S \
            + _mm("bhid,bhie->bhde", Kd_c, U_c, dtype)
        return nxt, (S, U_c)

    with jax.named_scope(scope):
        _, (S0, U) = jax.lax.scan(
            walk, jnp.zeros(W.shape[1:3] + (W.shape[-1], U0.shape[-1]),
                            jnp.float32), (W, U0, Kd, last))
    return S0, U


def _chunked(q, k, v, g, beta, chunk: int, dtype):
    f32 = jnp.float32
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    # padding: g = 0, beta = 0, k = 0
    (q, k, v, g, beta), N = _whole_chunks((q, k, v, g, beta), chunk)
    C = chunk
    q, k, v = (_chunks(x.astype(f32), N) for x in (q, k, v))
    g, beta = _chunks(g.astype(f32), N), _chunks(beta.astype(f32), N)

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

    S0, U = (jnp.moveaxis(x, 0, 2) for x in _delta_walk(
        *(jnp.moveaxis(x, 2, 0) for x in (W, U0, Kd, last)), dtype))
    o = _mm("bhnid,bhnde->bhnie", q * eG, S0, dtype) \
        + _mm("bhnij,bhnje->bhnie", P, U, dtype)
    o = jnp.moveaxis(o, 1, 3).reshape(B, N * C, H, dv)
    return o[:, :L]


def _chunked_kernel(q, k, v, g, beta, dtype):
    """``_chunked`` with the chunk-local half in
    ``ops/pallas_delta_rule.delta_chunk``: q and k keep their key heads, no
    float32 head-major copy of anything a head wide is made, and the
    output products write (B, L, Hv, dv) as they are."""
    B, L, Hk, dk = q.shape
    Hv, dv = v.shape[2:]
    # padding: g = 0, beta = 0, k = 0; to whole programs of the calls
    (q, k, v, g, beta), _ = _whole_chunks(
        (q, k, v, g, beta), pdr.padded_length(L))
    Lp = q.shape[1]

    def rows(x):                # (B, L, H, d) -> (B, L, H * d), no copy
        return x.reshape(B, Lp, -1)

    def lanes(x):               # (B, L, Hv) -> float32 (B, Hv, pairs, 128)
        return jnp.moveaxis(x.astype(jnp.float32), 2, 1).reshape(
            B, Hv, -1, pdr.PAIR)
    g = lanes(g)
    W, U0, Kd, qe, P = pdr.delta_chunk(
        rows(q), rows(k.astype(jnp.float32)), rows(v), g, lanes(beta),
        (Hk, Hv, dk, dv), dtype)
    last = jnp.exp(jnp.sum(g.reshape(B, Hv, -1, pdr.CHUNK), -1))
    S0, U = _delta_walk(W, U0, Kd, jnp.moveaxis(last, 2, 0), dtype)
    o = pdr.delta_chunk_out(qe, P, S0, U, dtype)
    return o.reshape(B, Lp, Hv, dv)[:, :L]


# ------------------------------------------------- a decay a key channel
KDA_WALK_SCOPE = "kda_state_walk"


def _kda_levels(chunk: int):
    if chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk}: a decay a key channel splits the "
                         "chunk in halves, a power of two")
    return tuple(1 << b for b in range(chunk.bit_length() - 1))


def _kda_products(q, k, G, chunk: int, dtype):
    """``sum_c x_i[c] k_j[c] exp(G_i[c] - G_j[c])`` for x = k (``j < i``)
    and x = q (``j <= i``) over chunks (..., C, dk): the decay lies inside
    the contraction, so the causal triangle is split by the highest bit in
    which ``i`` and ``j`` differ. At level ``s`` row ``i`` is in the upper
    half of a block of ``2 s`` rows and ``j`` in its lower half; with ``R``
    = ``G`` at the upper half's first row ``exp(G_i - G_j) = exp(G_i - R)
    exp(R - G_j)``, both exponents <= 0: one product of two scaled
    operands a level, under the level's mask."""
    C = chunk
    rows = jnp.arange(C)
    differ = rows[:, None] ^ rows[None, :]
    kk = jnp.zeros(G.shape[:-1] + (C,), jnp.float32)
    qk = jnp.where(rows[:, None] == rows[None, :], _mm(
        "...id,...jd->...ij", q, k, dtype), 0.0)
    for s in _kda_levels(C):
        # G at the first row of each block of s rows, and of the next block
        first = G.reshape(G.shape[:-2] + (C // s, s, -1))[..., :1, :]
        own = jnp.broadcast_to(first, G.shape[:-2] + (C // s, s, G.shape[-1]))
        nxt = jnp.broadcast_to(jnp.roll(first, -1, axis=-3), own.shape)
        lhs = jnp.exp(G - own.reshape(G.shape))
        # the chunk's last block has no next one, and no column under a mask
        rhs = jnp.exp(jnp.where((rows < C - s)[:, None],
                                nxt.reshape(G.shape) - G, 0.0))
        mask = ((differ >> (s.bit_length() - 1)) == 1) \
            & (rows[:, None] > rows[None, :])
        kr = k * rhs
        kk += jnp.where(mask, _mm("...id,...jd->...ij", k * lhs, kr, dtype),
                        0.0)
        qk += jnp.where(mask, _mm("...id,...jd->...ij", q * lhs, kr, dtype),
                        0.0)
    return kk, qk


def _chunked_kda(q, k, v, g, beta, chunk: int, dtype):
    """``_chunked`` with ``g`` (B, L, H, dk): every ``exp(G)`` is a vector
    over the key channels, ``K K^T`` and ``Q K^T`` hold the decay inside
    (``_kda_products``) and the walk's ``last`` is a vector."""
    f32 = jnp.float32
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    # padding: g = 0, beta = 0, k = 0
    (q, k, v, g, beta), N = _whole_chunks((q, k, v, g, beta), chunk)
    C = chunk
    q, k, v, g = (_chunks(x.astype(f32), N) for x in (q, k, v, g))
    beta = _chunks(beta.astype(f32), N)[..., None]

    G = jnp.cumsum(g, axis=-2)                          # (B, H, N, C, dk)
    kk, P = _kda_products(q, k, G, C, dtype)
    T = inv_unit_lower(beta * kk)
    eG = jnp.exp(G)
    W = _mm("bhnij,bhnjd->bhnid", T, k * (beta * eG), dtype)
    U0 = _mm("bhnij,bhnjd->bhnid", T, v * beta, dtype)
    Kd = k * jnp.exp(G[..., -1:, :] - G)
    last = jnp.exp(G[..., -1, :])                       # (B, H, N, dk)

    S0, U = (jnp.moveaxis(x, 0, 2) for x in _delta_walk(
        *(jnp.moveaxis(x, 2, 0) for x in (W, U0, Kd, last)), dtype,
        KDA_WALK_SCOPE))
    o = _mm("bhnid,bhnde->bhnie", q * eG, S0, dtype) \
        + _mm("bhnij,bhnje->bhnie", P, U, dtype)
    o = jnp.moveaxis(o, 1, 3).reshape(B, N * C, H, dv)
    return o[:, :L]


def _chunked_kda_kernel(q, k, v, g, beta, dtype):
    """``_chunked_kda`` with the chunk-local half in the calls of
    ``ops/pallas_kda.py``; XLA makes the running sum of the decay inside
    each chunk (one cumulative sum over the rows) and the walk."""
    from mmlspark_tpu.ops import pallas_kda
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    (q, k, v, g, beta), _ = _whole_chunks(
        (q, k, v, g, beta), pdr.padded_length(L))
    Lp = q.shape[1]
    G = jnp.cumsum(g.astype(jnp.float32).reshape(B, -1, pdr.CHUNK, H, dk),
                   axis=2)
    beta = jnp.moveaxis(beta.astype(jnp.float32), 2, 1).reshape(
        B, H, -1, pdr.PAIR)
    W, U0, Kd, qe, P = pallas_kda.kda_chunk(
        q.reshape(B, Lp, -1), k.astype(jnp.float32).reshape(B, Lp, -1),
        v.reshape(B, Lp, -1), G.reshape(B, Lp, -1), beta, dtype)
    last = jnp.exp(jnp.moveaxis(G[:, :, -1], 1, 0))     # (N, B, H, dk)
    S0, U = _delta_walk(W, U0, Kd, last, dtype, KDA_WALK_SCOPE)
    o = pallas_kda.kda_chunk_out(qe, P, S0, U, dtype)
    return o.reshape(B, Lp, H, dv)[:, :L]


def _kda(q, k, v, g, beta, chunk: int, impl: str, dtype):
    """``gated_delta_rule`` for ``g`` (B, L, H, dk) and ``q`` scaled: as
    many key as value heads; the executor of the chunked form's batched
    half counted as ``linear_attention.kda_chunk_calls.<pallas|xla>``."""
    from mmlspark_tpu.ops import pallas_kda
    taken = _form("kda", impl, q.shape[1], chunk)
    if taken == "recurrent":
        return _recurrent(q, k, v, g, beta, chunk)
    if pallas_kda.supports(chunk, q.shape[2], v.shape[2], q.shape[-1],
                           v.shape[-1]):
        obsmetrics.counter("linear_attention.kda_chunk_calls.pallas").inc()
        return _chunked_kda_kernel(q, k, v, g, beta, dtype)
    obsmetrics.counter("linear_attention.kda_chunk_calls.xla").inc()
    return _chunked_kda(q, k, v, g, beta, chunk, dtype)


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array,
                     g: jax.Array, beta: jax.Array, *, chunk: int = CHUNK,
                     impl: str = "auto", dtype: Any = None,
                     q_scaled: bool = False) -> jax.Array:
    """The gated delta rule over whole rows, state zero at each row's start.

    ``q``, ``k`` (B, L, Hk, dk), ``v`` (B, L, Hv, dv), ``g`` (log decay, <=
    0) and ``beta`` (B, L, Hv); value head ``h`` reads key head ``h // (Hv /
    Hk)``; returns (B, L, Hv, dv) float32. The form is picked from ``g``'s
    shape: (B, L, Hv, dk) is a decay a key channel (Kimi Delta Attention;
    as many key as value heads), which takes the forms of its own (``_kda``:
    the same three, the Pallas calls of ``ops/pallas_kda.py`` at heads of
    128 x 128). ``q`` is scaled by ``dk ** -0.5`` here, unless it comes
    ``q_scaled`` (``conv_silu_norm``'s last multiply: no float32 pass of
    its own over q). ``impl``: "auto" (chunked from one whole chunk up,
    else token by token) | "chunked" | "recurrent". ``dtype``: the matrix products'
    operand type in the chunked form (default: ``q``'s own); the recurrent
    form is float32 throughout. The chunked form takes the Pallas calls
    where ``pallas_delta_rule.supports`` the shapes, else XLA's batched
    products: counted as ``linear_attention.chunk_calls.<pallas|xla>``.
    """
    Hk, Hv = q.shape[2], v.shape[2]
    channels = g.ndim == 4      # a decay a key channel: as many key heads
    if g.shape != (q.shape if channels else v.shape[:3]) \
            or beta.shape != v.shape[:3] \
            or k.shape != q.shape or v.shape[:2] != q.shape[:2] \
            or Hv % Hk or (channels and Hk != Hv):
        raise ValueError(
            f"shapes q {q.shape} k {k.shape} v {v.shape} g {g.shape} "
            f"beta {beta.shape}")
    dtype = dtype or q.dtype
    q = q.astype(jnp.float32)
    if not q_scaled:
        q = q * q.shape[-1] ** -0.5
    if channels:
        return _kda(q, k, v, g, beta, chunk, impl, dtype)
    taken = _form("delta", impl, q.shape[1], chunk)
    if taken == "chunked" and pdr.supports(
            chunk, Hk, Hv, q.shape[-1], v.shape[-1]):
        obsmetrics.counter("linear_attention.chunk_calls.pallas").inc()
        return _chunked_kernel(q, k, v, g, beta, dtype)
    if Hk != Hv:
        q, k = (jnp.repeat(x, Hv // Hk, axis=2) for x in (q, k))
    if taken == "recurrent":
        return _recurrent(q, k, v, g, beta, chunk)
    obsmetrics.counter("linear_attention.chunk_calls.xla").inc()
    return _chunked(q, k, v, g, beta, chunk, dtype)


def _form(rule: str, impl: str, length: int, chunk: int) -> str:
    """The form a call takes, counted: ``impl`` itself, or under "auto"
    the chunked one from one whole chunk up."""
    if impl not in ("auto", "chunked", "recurrent"):
        raise ValueError(f"unknown impl {impl!r}")
    taken = impl
    if impl == "auto":
        taken = "chunked" if length >= chunk else "recurrent"
        if taken == "recurrent" and jax.default_backend() != "cpu":
            obsmetrics.counter("linear_attention.fallbacks").inc()
    obsmetrics.counter(f"linear_attention.calls.{taken}").inc()
    obsmetrics.counter(f"linear_attention.rule_calls.{rule}").inc()
    return taken


# ---------------------------------------------------------------- Mamba-2
def _ssd_recurrent(x, dt, A, Bm, Cm, block: int):
    """Token by token, float32, in blocks whose inner loop the backward
    pass recomputes (as ``_recurrent``)."""
    B, L, H, P = x.shape
    rep = H // Bm.shape[2]

    def step(S, t):
        x_t, dt_t, B_t, C_t = t
        B_t, C_t = (jnp.repeat(m, rep, axis=1) for m in (B_t, C_t))
        S = jnp.exp(dt_t * A)[..., None, None] * S \
            + B_t[..., :, None] * (dt_t[..., None] * x_t)[..., None, :]
        return S, jnp.einsum("bhnp,bhn->bhp", S, C_t, precision=_HIGHEST)

    _, y = jax.lax.scan(
        jax.checkpoint(lambda S, t: jax.lax.scan(step, S, t)),
        jnp.zeros((B, H, Bm.shape[-1], P), jnp.float32),
        tuple(_token_blocks(a, block) for a in (x, dt, Bm, Cm)))
    return _rows_of_blocks(y, L)


def _ssd_walk(Z, last):
    """The walk of the state from chunk to chunk, ONE ``lax.scan``: the
    chunks' addends ``Z`` (Nc, B, Gr, rep * P, N) and ``last`` = exp(G_C)
    (Nc, B, Gr, rep * P, 1); the state each chunk starts from, as ``Z``."""
    def walk(S, z):
        Z_c, a_c = z
        return a_c * S + Z_c, S

    with jax.named_scope("ssd_scan"):
        _, S0 = jax.lax.scan(walk, jnp.zeros(Z.shape[1:], jnp.float32),
                             (Z, last))
    return S0


def _ssd_chunked(x, dt, A, Bm, Cm, chunk: int, dtype):
    f32 = jnp.float32
    B, L, H, P = x.shape
    Gr, N = Bm.shape[2:]
    rep = H // Gr
    # padding: dt = 0, no decay and no addend
    (x, dt, Bm, Cm), Nc = _whole_chunks((x, dt, Bm, Cm), chunk)
    C = chunk

    def heads(a):               # (B, H, ...) -> (B, Gr, rep, ...)
        return a.reshape((B, Gr, rep) + a.shape[2:])
    dt = dt.astype(f32)
    # v (B, Gr, rep, Nc, C, P); B, C (B, Gr, Nc, C, N); G (B, H, Nc, C)
    v = heads(_chunks(x.astype(f32) * dt[..., None], Nc))
    Bm, Cm = _chunks(Bm, Nc), _chunks(Cm, Nc)
    G = jnp.cumsum(_chunks(dt * A, Nc), axis=-1)
    rows = jnp.arange(C)
    seen = rows[:, None] >= rows[None, :]
    D = jnp.exp(jnp.where(seen, G[..., :, None] - G[..., None, :], -jnp.inf))
    scores = _mm("bgnid,bgnjd->bgnij", Cm, Bm, dtype)[:, :, None] * heads(D)
    inside = _mm("bgrnij,bgrnje->bgrnie", scores, v, dtype)
    # a chunk's addend to the state, all chunks at once: (exp(G_C - G) dt
    # x)^T B. A head's state lies transposed, (P, N), a group's heads one
    # under the other: N fills the lanes, and B stays the product's second
    # operand (the CPU's bfloat16 dot does not take it transposed as the
    # first, and the rehearsals run there)
    Z = _mm("bgrnje,bgnjd->bgnred",
            v * heads(jnp.exp(G[..., -1:] - G))[..., None], Bm, dtype)
    last = jnp.repeat(jnp.exp(G[..., -1]), P, axis=1)   # (B, H * P, Nc)
    S0 = _ssd_walk(
        jnp.moveaxis(Z.reshape(B, Gr, Nc, rep * P, N), 2, 0),
        jnp.moveaxis(last.reshape(B, Gr, rep * P, 1, Nc), 4, 0))
    S0 = jnp.moveaxis(S0, 0, 2).reshape(B, Gr, Nc, rep, P, N)
    y = inside + heads(jnp.exp(G))[..., None] * _mm(
        "bgnid,bgnred->bgrnie", Cm, S0, dtype)
    y = jnp.moveaxis(y.reshape(B, H, Nc, C, P), 1, 3).reshape(
        B, Nc * C, H, P)
    return y[:, :L]


def _ssd_chunked_kernel(x, dt, A, Bm, Cm, dtype):
    """``_ssd_chunked`` with the chunk-local half in the calls of
    ``ops/pallas_ssd.py``: x, B and C go in as the rows they are, no
    float32 head-major copy of anything a head wide is made, the addends
    come out in the layout the scan carries and the outputs as rows."""
    from mmlspark_tpu.ops import pallas_ssd as pss
    B, L, H, P = x.shape
    Gr, N = Bm.shape[2:]
    # padding: dt = 0, no decay and no addend
    (x, dt, Bm, Cm), Nc = _whole_chunks((x, dt, Bm, Cm), pss.CHUNK)

    def rows(a):                # (B, L, H, d) -> (B, L, H * d), no copy
        return a.reshape(B, Nc * pss.CHUNK, -1)
    x, Bm, Cm, dims = rows(x), rows(Bm), rows(Cm), (H, P, Gr, N)
    # a chunk's scalars as rows: float32 (B, Nc, H, 256)
    dt = jnp.swapaxes(
        dt.astype(jnp.float32).reshape(B, Nc, pss.CHUNK, H), 2, 3)
    g = dt * A[:, None]
    last = jnp.repeat(jnp.exp(jnp.sum(g, -1)), P, axis=2)   # (B, Nc, H * P)
    S0 = _ssd_walk(
        pss.ssd_chunk(x, Bm, dt, g, dims, dtype),
        jnp.moveaxis(last.reshape(B, Nc, Gr, H // Gr * P, 1), 1, 0))
    # the starting states are only ever read as a product's operand
    y = pss.ssd_chunk_out(x, Bm, Cm, dt, g, S0.astype(dtype), dims, dtype)
    return y.reshape(B, Nc * pss.CHUNK, H, P)[:, :L]


def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, *, chunk: int = SSD_CHUNK, impl: str = "auto",
        dtype: Any = None) -> jax.Array:
    """Mamba-2's state-space rule over whole rows, state zero at each
    row's start: ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T``, ``y_t =
    S_t^T C_t`` a head.

    ``x`` (B, L, H, P), ``dt`` (B, L, H) positive (after its softplus),
    ``A`` (H,) negative, ``B`` and ``C`` (B, L, G, N) with head ``h``
    reading group ``h // (H / G)``; returns (B, L, H, P) float32, without
    the skip term ``D x``. ``impl`` and ``dtype`` as ``gated_delta_rule``'s.
    The chunked form takes the Pallas calls where ``pallas_ssd.supports``
    the shapes, else XLA's batched products: counted as
    ``linear_attention.ssd_chunk_calls.<pallas|xla>``.
    """
    from mmlspark_tpu.ops import pallas_ssd as pss
    H, P = x.shape[2:]
    if dt.shape != x.shape[:3] or A.shape != (H,) or B.shape != C.shape \
            or B.shape[:2] != x.shape[:2] or H % B.shape[2]:
        raise ValueError(f"shapes x {x.shape} dt {dt.shape} A {A.shape} "
                         f"B {B.shape} C {C.shape}")
    taken = _form("ssd", impl, x.shape[1], chunk)
    A = A.astype(jnp.float32)
    # the whole rule under one name (the walk, ``ssd_scan``, inside it): a
    # row of its own in the split of device time by part
    with jax.named_scope("ssd"):
        if taken == "recurrent":
            return _ssd_recurrent(x, dt, A, B, C, chunk)
        if pss.supports(chunk, H, P, *B.shape[2:]):
            obsmetrics.counter("linear_attention.ssd_chunk_calls.pallas").inc()
            return _ssd_chunked_kernel(x, dt, A, B, C, dtype or x.dtype)
        obsmetrics.counter("linear_attention.ssd_chunk_calls.xla").inc()
        return _ssd_chunked(x, dt, A, B, C, chunk, dtype or x.dtype)
