"""The parts the decoder families are made of (``models/zoo/decoder.py``
holds the block, the one ``Decoder`` skeleton and the registry entries that
say which part sits at which layer). A part is a flax module ``x -> y`` on
``(B, L, dim)`` rows that owns its projections and its positions; it knows
``ops/`` and ``parallel/sequence`` and nothing of a family:

- ``RMSNorm``: float32 in and out, epsilon from the configuration; with
  ``offset`` the scale is ``1 + w`` and ``w`` starts at zero;
- ``rotary``: rotary positions on the last axis by given inverse
  frequencies (``plain_frequencies``, ``yarn_frequencies``) and a factor:
  ``n`` frequencies turn the first ``2 n`` dimensions, ``i`` with ``i +
  n``, the rest passing through; float32 angles, one product over the
  whole head. The one function that turns, in every family that turns
  (but ``GroupedAttention``'s heads of 128 channels, turned and
  normed in one pass by ``ops/pallas_head_norm_turn`` from the same
  ``rotary_tables``);
- ``MlaAttention``: multi-head latent attention in its expanded (training)
  form: a low-rank query (or, without a rank, a plain one), one compressed
  key/value row per token, a rotary slice on every query head and ONE
  rotary key shared by all heads (or, without the turn, no positions);
- ``GatedAttention``: softmax attention over grouped key/value heads (each
  repeated to the query heads it serves at the attention call, so the
  flash kernel and its backward run as they are), norms on q and k, a
  rotary slice, and a sigmoid gate on the output;
- ``GroupedAttention``: the same grouped heads with nothing else: no
  positions, no gate, a softmax scale of its own, and if asked an RMS norm
  over the whole q and the whole k projection, or (``norm_heads``) over
  each head's channels, (``rotary_freqs``) rotary positions, and
  (``block_diffusion``) a row of two copies under the block-diffusion mask,
  or (``indexer``) keys chosen for each query by an ``Indexer``;
- ``Indexer``: DeepSeek Sparse Attention's lightning indexer: a few small
  heads over ONE key head score every (query, key) pair of the past from a
  DETACHED input, and learn from a loss of their own;
- ``ShortConv``: LFM2's gated short convolution, which IS the mixer:
  ``[B | C | x] = u W_in``, a causal depthwise convolution of three taps
  over ``B * x`` with no activation, the gate ``C`` on its output;
- ``GatedDeltaNet``: linear attention with a recurrent state
  (``ops/linear_attention.py``): a short causal convolution, the gated
  delta rule with ``beta`` in (0, ``beta_scale``), a gated norm on the
  output;
- ``KimiDeltaAttention``: the same rule with a decay a key CHANNEL (Kimi
  Delta Attention): q, k and v each by its own projection and its own
  short convolution, a low-rank decay gate with a bias a channel, a
  low-rank sigmoid gate on the normed output;
- ``Mamba2Mixer``: the state-space layer (the same module's ``ssd``): a
  convolution with a bias over ``[x | B | C]``, a scalar decay a head,
  ``B`` and ``C`` shared by groups of heads, a skip, the gate BEFORE the
  norm;
- ``SwiGluMlp``: ``down(silu(gate x) * up x)``, no biases;
- ``Head``: the untied output matrix;
- (``zoo/moe.DroplessMoe``, the routed layer, lives in its own module.)

Parameter names hit the rules of ``parallel/sharding.DEFAULT_RULES``
(``attn_query*`` / ``attn_key*`` / ``attn_value`` / ``attn_qkvz`` /
``attn_gate_value_key_query_dt`` / ``attn_in`` / ``attn_out``, ``mlp_gate``
/ ``mlp_up`` / ``mlp_down``, ``lm_head``). The five checkpoint names are
the values a recomputed block may keep (``decoder._remat_block``); each sits
where its value is made.
"""
from __future__ import annotations

import contextlib
import math
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from mmlspark_tpu.observability import metrics as obsmetrics
from mmlspark_tpu.parallel.sequence import (
    full_attention, on_own_rows, own_shape)

_INIT = nn.initializers.normal(0.02)
# the checkpoint name of ``SwiGluMlp``'s gate and up products
MLP_GATE_UP = "mlp_gate_up"
# and of ``GatedDeltaNet``'s input projection's output
DELTA_NET_QKVZ = "delta_net_qkvz"
# and of ``ShortConv``'s
SHORT_CONV_IN = "short_conv_in"
# and of ``Mamba2Mixer``'s
MAMBA2_IN = "mamba2_in"
# and of ``GroupedAttention``'s q, k and v projections' outputs (turned,
# where a kernel turns q and k without a norm)
ATTN_QKV = "attn_qkv"
# and of the mask an ``Indexer``'s choice makes (int8, a byte a pair)
SELECTION = "attention_selection"


class RMSNorm(nn.Module):
    eps: float = 1e-5
    offset: bool = False        # scale = 1 + w, w zero at init

    @nn.compact
    def __call__(self, x, apply: bool = True):
        scale = self.param(
            "scale", nn.initializers.zeros if self.offset
            else nn.initializers.ones, (x.shape[-1],), jnp.float32)
        if self.offset:
            scale = 1.0 + scale
        if not apply:       # the scale alone, for a kernel that norms
            return scale
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps) * scale


def rotary(x: jax.Array, inv_freq: Tuple[float, ...],
           factor: float = 1.0,
           positions: Optional[jax.Array] = None) -> jax.Array:
    """Rotary positions by GIVEN inverse frequencies over the last axis of
    ``(B, L, H, R)``: the first ``2 n`` dimensions turn, ``n`` =
    ``len(inv_freq)``, position ``l`` turning the pair ``(i, i + n)`` by
    ``l * inv_freq[i]``, and the rest pass through; ``factor`` multiplies
    cos and sin both (YaRN's attention factor). The frequencies are made
    where the rule is known (``plain_frequencies``, ``yarn_frequencies``).
    ``positions`` ``(L,)`` gives row ``l`` another position than ``l`` (a
    row that holds two copies of a sequence repeats them).

    Computed as ``x cos + pair(x) sin`` over the WHOLE head, where
    ``pair(x) = [-x_2 | x_1 | 0]`` is a product with the pairing's signed
    permutation matrix (each output is plus or minus one input: exact in
    any dtype, float32 rows at ``Precision.HIGHEST``) and cos and sin are 1
    and 0 on the dimensions that pass. No half of a head is sliced out of
    the lanes: on the chip the split form (``[x_1 cos - x_2 sin | x_2 cos +
    x_1 sin]`` of the two halves) of a (2, 8192, 64, 128) bfloat16 tensor
    ran 4.83 ms forward and this one 1.30, with equal bits (PERF.md
    section 6, PR 44; at the other families' shapes, PR 45).

    This is XLA's form, and it runs wherever it is called: on
    ``MlaAttention``'s and ``GatedAttention``'s slices, and on the q and k
    of a ``GroupedAttention`` whose heads
    ``ops/pallas_head_norm_turn.supports`` declines. Heads of 128
    channels there take that module's one Pallas pass instead (the norm a
    head, where there is one, and the turn, from the same
    ``rotary_tables``), against which this function is the reference."""
    R, n = x.shape[-1], len(inv_freq)
    cos, sin = rotary_tables(x.shape[1], R, inv_freq, factor, positions)
    pair = np.zeros((R, R), np.float32)
    i = np.arange(n)
    pair[i + n, i], pair[i, i + n] = -1.0, 1.0
    paired = jnp.einsum(
        "blhr,rs->blhs", x, jnp.asarray(pair, x.dtype),
        preferred_element_type=jnp.float32,
        precision=None if x.dtype.itemsize < 4 else jax.lax.Precision.HIGHEST)
    return (x.astype(jnp.float32) * cos[None, :, None, :]
            + paired * sin[None, :, None, :]).astype(x.dtype)


def rotary_tables(L: int, R: int, inv_freq: Tuple[float, ...],
                  factor: float = 1.0,
                  positions: Optional[jax.Array] = None):
    """``rotary``'s cos and sin, ``(L, R)`` float32 each: ``factor cos(l
    f_i)`` on channels ``i`` and ``i + n``, 1 and 0 on those past ``2 n``."""
    n = len(inv_freq)
    if 2 * n > R:
        raise ValueError(f"{n} frequencies turn {2 * n} dimensions of {R}")
    if positions is None:
        positions = jnp.arange(L, dtype=jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    rest = (L, R - 2 * n)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    return (jnp.concatenate([cos, cos, jnp.ones(rest, jnp.float32)], -1),
            jnp.concatenate([sin, sin, jnp.zeros(rest, jnp.float32)], -1))


def _norm_turn(shape, n: int) -> Optional[Callable]:
    """A head's norm and turn as ``ops/pallas_head_norm_turn``'s one pass,
    on each device's own rows: ``(y, cos, sin[, scale, eps]) -> y``, or None
    for ``(B, L, H, d)`` rows turned by ``n`` frequencies whose part on a
    device it does not take (``supports``, by the shape alone) or that do
    not split over the mesh. (Imported here: Pallas costs every importer
    of the zoo over a second.)"""
    from mmlspark_tpu.ops import pallas_head_norm_turn as kernel
    local = own_shape(shape)
    if local is None or not kernel.supports(local, n):
        return None

    def call(y, cos, sin, scale=None, eps=None):
        return on_own_rows(
            lambda y, cos, sin, scale=None: kernel.head_norm_turn(
                y, cos, sin, n, scale, eps),
            y, whole=(cos, sin) if scale is None else (cos, sin, scale))
    return call


def plain_frequencies(width: int, theta: float) -> Tuple[float, ...]:
    """Plain rotary's ``width / 2`` inverse frequencies,
    ``theta**(-2i/width)``."""
    return tuple(theta ** (-2.0 * i / width) for i in range(width // 2))


def yarn_frequencies(width: int, theta: float, factor: float,
                     original: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0) -> Tuple[float, ...]:
    """YaRN's ``width / 2`` inverse frequencies (arXiv:2309.00071, as
    ``transformers``' ``_compute_yarn_parameters`` makes them): pair ``i``
    of plain rotary turns by ``theta**(-2i/width)``; a pair that turns
    more than ``beta_fast`` times over the ``original`` positions keeps
    that, one that turns fewer than ``beta_slow`` times turns ``factor``
    times slower, and between the two a linear ramp over the pair's index
    blends them (its ends the floor and the ceiling of the two pairs'
    indices, kept inside ``[0, width - 1]``). Float64 on the host, once."""
    def pair_turning(turns):
        return width * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), width - 1)
    if low == high:
        high += 0.001
    out = []
    for i, plain in enumerate(plain_frequencies(width, theta)):
        keep = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(plain / factor * (1.0 - keep) + plain * keep)
    return tuple(out)


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    kernel_init=_INIT, name=name)


class MlaAttention(nn.Module):
    """Multi-head latent attention, expanded form. Query/key heads are
    ``nope + rope`` wide and value heads ``v_dim``, which may be narrower
    (the attention call is the framework's ``(q, k, v, causal)`` on ``(B,
    L, H, D)`` with v's own last axis; the flash kernels take the two
    widths as they are). ``q_rank`` None: no low-rank query and no query
    norm, ``q = x W_q`` (``attn_query``). ``turn`` False: no rotary turn on
    the ``rope`` channels of q or of the one shared key, which then carry
    no positions (``mla_use_nope``). GLM-4.7-Flash has a rank, the turn and
    256 / 256; Kimi-Linear none, none and 192 / 128."""
    dim: int
    heads: int
    q_rank: Optional[int]
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    theta: float = 1e6
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    turn: bool = True

    @nn.compact
    def __call__(self, x):
        B, L, _ = x.shape
        H, dt = self.heads, self.dtype
        attn_fn = self.attention_fn or full_attention
        with jax.named_scope("mla_attention"):
            x = x.astype(dt)
            if self.q_rank is None:
                q = _dense(H * (self.nope + self.rope), dt, "attn_query")(x)
            else:
                cq = RMSNorm(self.eps, name="query_norm")(
                    _dense(self.q_rank, dt, "attn_query_a")(x)).astype(dt)
                q = _dense(H * (self.nope + self.rope), dt,
                           "attn_query_b")(cq)
            q = q.reshape(B, L, H, self.nope + self.rope)
            kva = _dense(self.kv_rank + self.rope, dt, "attn_key_value_a")(x)
            ckv = RMSNorm(self.eps, name="key_value_norm")(
                kva[..., :self.kv_rank]).astype(dt)
            kv = _dense(H * (self.nope + self.v_dim), dt,
                        "attn_key_value_b")(ckv).reshape(
                            B, L, H, self.nope + self.v_dim)
            if self.turn:
                freqs = plain_frequencies(self.rope, self.theta)
                q_r = rotary(q[..., self.nope:], freqs)
                # the one rotary key, shared by every head
                k_r = rotary(kva[..., None, self.kv_rank:], freqs)
                q = jnp.concatenate([q[..., :self.nope], q_r], -1)
            else:
                k_r = kva[..., None, self.kv_rank:]
            k = jnp.concatenate(
                [kv[..., :self.nope],
                 jnp.broadcast_to(k_r, (B, L, H, self.rope))], -1)
            o = attn_fn(q, k, kv[..., self.nope:], causal=True)
            return _dense(self.dim, dt, "attn_out")(
                o.reshape(B, L, H * self.v_dim))


class GatedAttention(nn.Module):
    """Softmax attention with grouped key/value heads, a norm on every q
    and k head, rotary positions on the first ``rotary_width`` of each
    head, and a sigmoid gate on the output: ``[q | gate] = x W_q`` (halves
    per head), ``o <- o * sigmoid(gate)``, ``y = o W_o``; no biases.

    Each key/value head is repeated to the ``heads / kv_heads`` query
    heads it serves where ``attention_fn(q, k, v)`` is called, so the
    fused kernels take it as any equal-headed call; the repeated K/V
    traffic is the price (a kernel that reads ``kv_heads`` heads for
    ``heads`` is not there yet)."""
    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    rotary_width: int
    theta: float = 1e7
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads over "
                             f"{self.kv_heads} key/value heads")
        B, L, _ = x.shape
        H, G, d, dt = self.heads, self.kv_heads, self.head_dim, self.dtype
        attn_fn = self.attention_fn or full_attention
        with jax.named_scope("gated_attention"):
            x = x.astype(dt)
            qg = _dense(H * 2 * d, dt, "attn_query_gate")(x).reshape(
                B, L, H, 2 * d)
            q, gate = qg[..., :d], qg[..., d:]
            k = _dense(G * d, dt, "attn_key")(x).reshape(B, L, G, d)
            v = _dense(G * d, dt, "attn_value")(x).reshape(B, L, G, d)
            q = RMSNorm(self.eps, offset=True, name="query_norm")(q)
            k = RMSNorm(self.eps, offset=True, name="key_norm")(k)
            freqs = plain_frequencies(self.rotary_width, self.theta)
            q = rotary(q, freqs).astype(dt)
            k = rotary(k, freqs).astype(dt)
            k, v = (jnp.repeat(t, H // G, axis=2) for t in (k, v))
            o = attn_fn(q, k, v, causal=True)
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dt)
            return _dense(self.dim, dt, "attn_out")(o.reshape(B, L, H * d))


class GatedDeltaNet(nn.Module):
    """Gated DeltaNet (arXiv:2412.06464) in flash-linear-attention's
    layout, which serves two published ones: Qwen3-Next's (16 key heads
    under 32 value heads, 128 x 128 a head, ``beta`` in (0, 1)) and
    Olmo-Hybrid's (as many key as value heads, 96 x 192 a head,
    ``beta_scale`` 2: ``linear_allow_neg_eigval``, a token's transition
    ``I - beta k k^T`` then has its eigenvalue ``1 - beta`` in (-1, 1)).
    ``[q | k | v | z] = x W_qkvz`` and ``[b | a] = x W_ba``; ``[q | k | v]``
    pass a causal depthwise convolution and ``silu``; ``beta = beta_scale
    sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)`` in float32; q
    and k are L2-normalised over the head, each key head serves
    ``value_heads / key_heads`` value heads; the gated delta rule
    (``ops/linear_attention.gated_delta_rule``: chunked on whole rows);
    ``o <- rmsnorm(o) * w_n * silu(z)`` over each head; ``y = o W_o``.
    Columns of ``W_qkvz`` are ``[q | k | v | z]``, head-major inside each
    (a checkpoint's per-key-head interleaving, or its four separate
    matrices, are a permutation of them)."""
    dim: int
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_width: int = 4
    eps: float = 1e-6
    chunk: int = 64
    dtype: Any = jnp.bfloat16
    beta_scale: float = 1.0

    @nn.compact
    def __call__(self, x):
        from mmlspark_tpu.ops import linear_attention as la
        if self.value_heads % self.key_heads:
            raise ValueError(f"{self.value_heads} value heads over "
                             f"{self.key_heads} key heads")
        B, L, _ = x.shape
        Hk, Hv, dk, dv = (self.key_heads, self.value_heads, self.key_dim,
                          self.value_dim)
        dt, f32 = self.dtype, jnp.float32
        with jax.named_scope("gated_delta_net"):
            x = x.astype(dt)
            qkvz = checkpoint_name(_dense(
                2 * Hk * dk + 2 * Hv * dv, dt, "attn_qkvz")(x),
                DELTA_NET_QKVZ)
            ba = _dense(2 * Hv, dt, "attn_ba")(x).astype(f32)
            conv = self.param("conv_kernel", _INIT,
                              (self.conv_width, 2 * Hk * dk + Hv * dv), f32)
            a_log = self.param(
                "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                    key, shape, f32, 1e-3, 16.0)), (Hv,))
            dt_bias = self.param("dt_bias", nn.initializers.ones, (Hv,), f32)
            with jax.named_scope("gdn_conv"):
                mixed = nn.silu(la.causal_conv1d(
                    qkvz[..., :2 * Hk * dk + Hv * dv], conv))
            z = qkvz[..., 2 * Hk * dk + Hv * dv:].reshape(B, L, Hv, dv)
            q = mixed[..., :Hk * dk].reshape(B, L, Hk, dk)
            k = mixed[..., Hk * dk:2 * Hk * dk].reshape(B, L, Hk, dk)
            v = mixed[..., 2 * Hk * dk:].reshape(B, L, Hv, dv)
            beta = jax.nn.sigmoid(ba[..., :Hv])
            if self.beta_scale != 1.0:
                beta = self.beta_scale * beta
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., Hv:] + dt_bias)
            o = la.gated_delta_rule(
                la.l2_normalize(q), la.l2_normalize(k), v, g, beta,
                chunk=self.chunk, dtype=dt)
            o = RMSNorm(self.eps, name="gate_norm")(o) \
                * nn.silu(z.astype(f32))
            return _dense(self.dim, dt, "attn_out")(
                o.astype(dt).reshape(B, L, Hv * dv))


class KimiDeltaAttention(nn.Module):
    """Kimi Delta Attention (KDA; "Kimi Linear", arXiv:2510.26692): the
    gated delta rule with a decay a key CHANNEL, ``heads`` heads of
    ``head_dim`` for keys and values alike. ``q = silu(conv_q(x W_q))``
    and so ``k`` and ``v``: three projections (``attn_query``,
    ``attn_key``, ``attn_value``), each through a causal depthwise
    convolution of its own (``conv_query``, ...); q and k L2-normalised
    over the head; the decay, float32, ``g = -exp(A_log_h) softplus((x
    W_fa) W_fb + dt_bias)`` through a rank of ``head_dim``
    (``attn_decay_a`` / ``_b``), ``dt_bias`` a channel and ``A_log`` a
    head: (B, L, H, head_dim), every entry <= 0; ``beta = sigmoid(x W_b)``
    a head; the rule (``ops/linear_attention.gated_delta_rule``, which
    reads the decay's kind from ``g``'s shape); ``o <- rmsnorm(o) w_n
    sigmoid((x W_ga) W_gb)`` over each head (``attn_gate_a`` / ``_b``, the
    same rank; ``w_n`` shared by the heads); ``y = o W_o``. No biases but
    ``dt_bias``. Scope ``kimi_delta_attention``; inside it ``kda_conv`` and
    ``kda_decay``, and the rule's walk under ``kda_state_walk``. The three
    projections' outputs carry ``DELTA_NET_QKVZ`` and the chunk calls'
    tiles ``DELTA_CHUNK_TILES``, as ``GatedDeltaNet``'s do.

    What lies between a projection and the rule (the convolution, ``silu``,
    and for q and k the L2 norm) is ONE entry,
    ``linear_attention.conv_silu_norm``, under ``kda_conv``: which form
    makes it is read from the shape alone and counted a trace as
    ``linear_attention.conv_norm_calls.<pallas|xla>``. Heads of whole
    registers and rows in whole tiles (``kimi_linear`` at 32 heads of 128)
    take one Pallas pass from the projection's rows to what the chunk call
    reads, float32 for q and k and the rows' type for v, and one more for
    the derivative, whose residuals are the projection's rows
    (``DELTA_NET_QKVZ``) and the taps; any other shape (the tiny presets'
    heads of 8) takes ``causal_conv1d``, ``silu`` and ``l2_normalize`` as
    XLA fuses them. q's ``head_dim ** -0.5`` is that entry's last multiply
    in either form, and the rule is told so (``q_scaled``): no float32 pass
    over q stands between the two."""
    dim: int
    heads: int
    head_dim: int
    conv_width: int = 4
    eps: float = 1e-5
    chunk: int = 64
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        from mmlspark_tpu.ops import linear_attention as la
        B, L, _ = x.shape
        H, d = self.heads, self.head_dim
        dt, f32 = self.dtype, jnp.float32
        with jax.named_scope("kimi_delta_attention"):
            x = x.astype(dt)

            def mixed(name, norm=False, scale=1.0):
                y = checkpoint_name(
                    _dense(H * d, dt, f"attn_{name}")(x), DELTA_NET_QKVZ)
                taps = self.param(f"conv_{name}", _INIT,
                                  (self.conv_width, H * d), f32)
                with jax.named_scope("kda_conv"):
                    return la.conv_silu_norm(y, taps, H, norm, scale).reshape(
                        B, L, H, d)

            def low_rank(name):
                return _dense(H * d, dt, f"attn_{name}_b")(
                    _dense(d, dt, f"attn_{name}_a")(x)).astype(f32).reshape(
                        B, L, H, d)
            q, k, v = (mixed("query", True, d ** -0.5), mixed("key", True),
                       mixed("value"))
            a_log = self.param(
                "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                    key, shape, f32, 1e-3, 16.0)), (H,))
            dt_bias = self.param("dt_bias", nn.initializers.ones, (H * d,),
                                 f32)
            with jax.named_scope("kda_decay"):
                g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                    low_rank("decay") + dt_bias.reshape(H, d))
            beta = jax.nn.sigmoid(_dense(H, dt, "attn_beta")(x).astype(f32))
            o = la.gated_delta_rule(q, k, v, g, beta, chunk=self.chunk,
                                    dtype=dt, q_scaled=True)
            o = RMSNorm(self.eps, name="gate_norm")(o) \
                * jax.nn.sigmoid(low_rank("gate"))
            return _dense(self.dim, dt, "attn_out")(
                o.astype(dt).reshape(B, L, H * d))


class GroupedAttention(nn.Module):
    """Causal softmax attention with grouped key/value heads and little
    else: no gate, no biases; ``softmax(scale x q k^T) v`` with a
    published ``scale`` that need not be ``head_dim ** -0.5``. Without
    ``rotary_freqs`` no positions (in ``granite_hybrid`` and
    ``olmo_hybrid`` the recurrent layers carry the order); with them q and
    k turn by ``rotary`` (``lfm2_moe``: the whole head, by
    ``plain_frequencies``). With ``qk_norm_eps`` an RMS norm with a plain
    scale on q and on k (scope ``qk_norm``): over the WHOLE projection
    before the split into heads (OLMo 2's ``q_norm`` / ``k_norm``), or
    with ``norm_heads`` over EACH head's ``head_dim`` channels, one scale
    of ``head_dim`` shared by the heads (LFM2's ``q_layernorm`` /
    ``k_layernorm``), float32 through the rotation; without, none. LFM2's
    softmax layer is this part with two arguments and not a third part:
    ``GatedAttention`` would need its ``1 + w`` scales, its rotary slice
    and its gate (which shapes ``W_q``) argued away.
    ``attention_fn(q, k, v)`` keeps its own ``head_dim ** -0.5``, so ``q``
    is multiplied by ``scale x head_dim ** 0.5`` before the call (0.125 in
    the published Granite: a power of two, exact in bfloat16). Each
    key/value head is repeated to the ``heads / kv_heads`` query heads it
    serves at that call, as in ``GatedAttention``.

    Laguna's two softmax layers are this part at two settings, by three
    more arguments that each default to nothing: ``window`` (a query at
    ``i`` sees the keys ``0 <= i - j < window``; handed to
    ``attention_fn`` as ``window=``, and the whole mixer then lies under
    the scope ``window_attention_layer``); ``rotary_factor`` (on cos and
    sin, beside ``rotary_freqs`` that turn the first ``2
    len(rotary_freqs)`` dimensions of a head: ``plain_frequencies`` in the
    sliding layers, YaRN's ``yarn_frequencies`` in the full ones);
    ``head_gate`` (one sigmoid gate a query head from the part's input,
    float32: ``g = sigmoid(x W_g)``, ``W_g`` ``dim x heads``, ``o_h <- g_h
    o_h`` before ``W_o``; scope ``head_gate``). It grew and no part was
    added beside it: the projections, the grouping, the repeat and the
    call are these to the letter, and ``GatedAttention``'s gate is an
    element's, shapes ``W_q`` and comes with norms of the ``1 + w``
    kind.

    ``block_diffusion`` = ``B`` makes it ``sdar_moe``'s mixer by one more
    argument of the same kind: the part's rows are ``[noised copy | clean
    copy]`` of a sequence, ``L`` positions each in blocks of ``B``. Row
    ``l`` then turns by position ``l mod L`` (both copies of a token stand
    at its position) and the call is handed ``block_diffusion=(L, B)`` in
    ``causal``'s company (``parallel/sequence.full_attention`` has the
    mask), under the scope ``block_diffusion_attention``. Projections,
    norms, grouping and repeat are a position's own and do not change.

    Which form makes a q or k head's norm and its turn is read from the
    shape alone (``_norm_turn``; every trace counts under
    ``attn.norm_turn_calls.pallas`` or ``.xla``): with ``rotary_freqs`` and
    heads of 128 channels (``sdar_moe``, ``laguna``) ONE Pallas pass over the projection's rows
    (``ops/pallas_head_norm_turn``: the norm a head where ``norm_heads``,
    the turn, float32 in registers, one rounding; under the scope
    ``qk_norm`` where it norms) and one more for the derivative, whose
    residuals are the projection's rows that ``ATTN_QKV`` names (without a
    norm the derivative reads no rows, and ``ATTN_QKV`` names the TURNED
    ones: a recomputed block then makes no turn again); any other
    head width (``lfm2_moe``'s 64, the tiny presets'), a layer without
    positions
    (``granite_hybrid``, ``olmo_hybrid``) and a norm over the whole
    projection keep XLA's form, ``RMSNorm`` then ``rotary``.

    ``indexer`` = ``(heads, head_dim, top_k, rotary_freqs)`` makes it
    ``keye_vl2``'s mixer (DeepSeek Sparse Attention over grouped heads): an
    ``Indexer`` of that many heads, turned by its own frequencies, scores
    every pair of the past from the part's input,
    each query keeps its ``top_k`` best keys (``ops/sparse_attention``: the
    choice is exact and a mask, no gradient passes through it), and the
    softmax runs over the kept keys alone. Projections, norms, turn and
    grouping do not change; the call is ``sparse_attention.selected_core``
    and not ``attention_fn`` (no window, no block diffusion, no
    ``attention_fn`` with it). The part then returns ``(y, stats)``:
    ``aux_loss``, the indexer's own loss (``sparse_attention.indexer_loss``
    against this layer's mean probabilities over the kept keys, which the
    main weights take no gradient from), and under ``counts`` the pairs
    the mask keeps beside the causal ones; the mask is sown as
    ``selection`` ``(B, L, L)`` bool, ``[b, t, s]``. Everything lies under
    the scope ``sparse_attention_layer``."""
    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    scale: Optional[float] = None       # None: head_dim ** -0.5
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    qk_norm_eps: Optional[float] = None     # None: no norm on q and k
    norm_heads: bool = False            # the norm over each head, not all
    window: Optional[int] = None        # None: the whole causal half
    rotary_freqs: Optional[Tuple[float, ...]] = None    # None: no positions
    rotary_factor: float = 1.0
    head_gate: bool = False
    block_diffusion: Optional[int] = None   # None: one copy, causal
    # (heads, width, top_k, the indexer's own rotary_freqs or None)
    indexer: Optional[Tuple[int, int, int, Optional[Tuple[float, ...]]]] \
        = None

    @nn.compact
    def __call__(self, x):
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads over "
                             f"{self.kv_heads} key/value heads")
        if self.indexer is not None and (
                self.window is not None or self.block_diffusion is not None
                or self.attention_fn is not None):
            raise ValueError(
                f"indexer {self.indexer} with window {self.window}, "
                f"block_diffusion {self.block_diffusion} or an attention_fn:"
                " a selection is made over the whole causal half")
        B, L, _ = x.shape
        H, G, d, dt = self.heads, self.kv_heads, self.head_dim, self.dtype
        attn_fn = self.attention_fn or full_attention
        positions = None
        if self.block_diffusion is not None:
            if L % 2 or self.window is not None:
                raise ValueError(
                    f"block_diffusion on rows of {L} with window "
                    f"{self.window}: [noised | clean] halves, no window")
            positions = jnp.arange(L, dtype=jnp.float32) % (L // 2)
        banded = contextlib.nullcontext() if self.window is None \
            else jax.named_scope("window_attention_layer")
        if self.indexer is not None:
            banded = jax.named_scope("sparse_attention_layer")
        with jax.named_scope("grouped_attention"), banded:
            x32, x = x, x.astype(dt)

            def heads_of(name, heads, norm=None):
                y = _dense(heads * d, dt, name)(x)
                normed = norm and self.qk_norm_eps is not None
                a_head = normed and self.norm_heads
                fused = None
                if norm:
                    if self.rotary_freqs is not None:
                        fused = _norm_turn((B, L, heads, d),
                                           len(self.rotary_freqs))
                    obsmetrics.counter("attn.norm_turn_calls."
                                       + ("pallas" if fused else "xla")).inc()
                if fused:
                    tables = rotary_tables(L, d, self.rotary_freqs,
                                           self.rotary_factor, positions)
                    if not normed:
                        # the turn's derivative reads no rows, so the TURNED
                        # ones are what the block keeps: not made again
                        return checkpoint_name(fused(
                            y.reshape(B, L, heads, d), *tables), ATTN_QKV)
                y = checkpoint_name(y, ATTN_QKV)
                if normed and not self.norm_heads:
                    with jax.named_scope("qk_norm"):
                        y = RMSNorm(self.qk_norm_eps, name=norm)(y).astype(dt)
                y = y.reshape(B, L, heads, d)
                if fused and not a_head:
                    return fused(y, *tables)
                if fused:
                    with jax.named_scope("qk_norm"):
                        return fused(y, *tables, RMSNorm(
                            self.qk_norm_eps, name=norm)(y, apply=False),
                            self.qk_norm_eps)
                if a_head:
                    with jax.named_scope("qk_norm"):
                        y = RMSNorm(self.qk_norm_eps, name=norm)(y)
                if norm and self.rotary_freqs is not None:
                    y = rotary(y, self.rotary_freqs, self.rotary_factor,
                               positions)
                return y.astype(dt)
            q = heads_of("attn_query", H, "query_norm")
            k = heads_of("attn_key", G, "key_norm")
            v = heads_of("attn_value", G)
            if self.scale is not None:
                q = q * jnp.asarray(self.scale * d ** 0.5, dt)
            if self.indexer is not None:
                from mmlspark_tpu.ops import sparse_attention as sparse
                heads, width, top_k, freqs = self.indexer
                scored = Indexer(heads, width, freqs, dt,
                                 name="indexer")(x32)
                with jax.named_scope("indexer"):
                    scores = sparse.indexer_scores(
                        *scored, sparse.tile_of(L))
                mask = checkpoint_name(sparse.select(scores, top_k),
                                       SELECTION)
                self.sow("intermediates", "selection", mask.transpose(
                    0, 1, 3, 2).reshape(B, L, L) != 0)
                o, lse = sparse.selected_core(q, k, v, mask)
                with jax.named_scope("indexer_loss"):
                    aux = sparse.indexer_loss(*scored, q, k, lse, mask)
                kept, causal = sparse.pair_counts(mask)
                return _dense(self.dim, dt, "attn_out")(
                    o.reshape(B, L, H * d)), {
                        "aux_loss": aux, "counts": {
                            "sparse_attention.selected_pairs": kept,
                            "sparse_attention.causal_pairs": jnp.asarray(
                                causal, jnp.float32)}}
            k, v = (jnp.repeat(t, H // G, axis=2) for t in (k, v))
            if self.block_diffusion is not None:
                with jax.named_scope("block_diffusion_attention"):
                    o = attn_fn(q, k, v, causal=True, block_diffusion=(
                        L // 2, self.block_diffusion))
            elif self.window is None:
                o = attn_fn(q, k, v, causal=True)
            else:
                o = attn_fn(q, k, v, causal=True, window=self.window)
            if self.head_gate:
                with jax.named_scope("head_gate"):
                    gate = jax.nn.sigmoid(nn.Dense(
                        H, use_bias=False, dtype=jnp.float32,
                        param_dtype=jnp.float32, kernel_init=_INIT,
                        name="attn_head_gate")(x32.astype(jnp.float32)))
                    o = o * gate[..., None].astype(dt)
            return _dense(self.dim, dt, "attn_out")(o.reshape(B, L, H * d))


class Indexer(nn.Module):
    """DeepSeek Sparse Attention's lightning indexer (DeepSeek-V3.2-Exp's
    report, arXiv:2512.02556; its published ``inference/model.py``
    ``Indexer`` without the Hadamard rotation and the fp8 storage): ``x
    (B, L, dim) -> (qI (B, L, heads, head_dim), kI (B, L, head_dim), w (B,
    L, heads))``, the operands of the score of every (query, key) pair of
    a row, ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``
    (``ops/sparse_attention.indexer_scores``). On ``x`` DETACHED: ``qI =
    turn(x W_qI)``; ``kI = turn(LayerNorm(x W_kI))``, ONE head (scale and
    bias, eps 1e-6); ``w = (x W_w) heads^-1/2 head_dim^-1/2``. ``qI`` and
    ``kI`` are ``dtype`` (operands of the scores' product); ``w``, the
    LayerNorm and the turn float32. ``rotary_freqs`` turn the first ``2
    len`` channels by ``rotary`` (None: no positions). Nothing here
    receives a gradient from what the scores are used to CHOOSE (a choice
    passes none, and the input is detached): the four leaves learn from
    whatever loss reads the scores themselves."""
    heads: int
    head_dim: int
    rotary_freqs: Optional[Tuple[float, ...]] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, L, _ = x.shape
        Hi, di, dt = self.heads, self.head_dim, self.dtype
        with jax.named_scope("indexer"):
            x = jax.lax.stop_gradient(x).astype(dt)
            q = _dense(Hi * di, dt, "index_query")(x).reshape(B, L, Hi, di)
            k = nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32,
                             name="index_key_norm")(
                _dense(di, dt, "index_key")(x).astype(jnp.float32))
            k = k[:, :, None, :]
            if self.rotary_freqs is not None:
                q, k = (rotary(t, self.rotary_freqs) for t in (q, k))
            w = jnp.dot(x, self.param(
                "index_weight", _INIT, (x.shape[-1], Hi),
                jnp.float32).astype(dt), preferred_element_type=jnp.float32
                ) * (Hi ** -0.5 * di ** -0.5)
            return q.astype(dt), k[:, :, 0].astype(dt), w


class ShortConv(nn.Module):
    """LFM2's gated short convolution (``Lfm2ShortConv``; the ``conv``
    entries of ``lfm2_moe``'s ``layer_types``), which is the whole mixer
    and feeds no recurrence: ``[B | C | x] = u W_in`` (``dim -> 3 dim``);
    ``z = B * x``; ``c_t = sum_j k_j z_{t - (taps-1) + j}``, depthwise and
    causal with zeros before a row's start, NO activation
    (``ops/linear_attention.causal_conv1d`` as it is); ``y = (C * c)
    W_out``. No biases in the published model; ``bias`` adds the
    convolution's. Scope ``short_conv``, and inside it ``gate_conv`` for
    everything between the two projections (the split, both gates, the
    taps): memory-bound, and what ``shortconv.gate_conv_roofline`` reads.
    Columns of ``W_in`` are ``[B | C | x]``, each ``dim`` wide."""
    dim: int
    taps: int = 3
    bias: bool = False
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        from mmlspark_tpu.ops import linear_attention as la
        dt = self.dtype
        obsmetrics.counter("short_conv.calls").inc()
        with jax.named_scope("short_conv"):
            bcx = checkpoint_name(
                _dense(3 * self.dim, dt, "attn_in")(u.astype(dt)),
                SHORT_CONV_IN)
            kernel = self.param("conv_kernel", _INIT, (self.taps, self.dim),
                                jnp.float32)
            conv_bias = self.param("conv_bias", _INIT, (self.dim,),
                                   jnp.float32) if self.bias else None
            with jax.named_scope("gate_conv"):
                b, c, x = jnp.split(bcx, 3, axis=-1)
                y = c * la.causal_conv1d(b * x, kernel, conv_bias)
            return _dense(self.dim, dt, "attn_out")(y)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``dt_bias`` such that ``softplus(dt_bias) = exp(U(log 1e-3, log
    1e-1))`` floored at 1e-4: Mamba-2's own initialiser."""
    dt = jnp.maximum(1e-4, jnp.exp(jax.random.uniform(
        key, shape, dtype, jnp.log(1e-3), jnp.log(1e-1))))
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba2Mixer(nn.Module):
    """Mamba-2 (arXiv:2405.21060) as ``granite_hybrid`` lays it out: ``[z |
    xBC | dt] = u W_in``; ``xBC <- silu(conv(xBC) + b)`` (causal,
    depthwise); ``[x | B | C] = xBC`` with ``x`` on ``heads`` heads of
    ``head_dim`` and ``B``, ``C`` on ``groups`` groups of ``state``, head
    ``h`` reading group ``h // (heads / groups)``; ``dt = softplus(dt +
    dt_bias)`` (no clamp) and ``A = -exp(A_log)`` a head, float32; the
    state-space rule (``ops/linear_attention.ssd``: chunked on whole rows)
    plus the skip ``D x``; ``y <- rmsnorm(y * silu(z)) * w_n``, the gate
    first and the mean square over all ``heads x head_dim`` channels; ``out
    = y W_out``. No biases but the convolution's. Columns of ``W_in`` are
    ``[z | x | B | C | dt]``, head-major inside each."""
    dim: int
    heads: int
    head_dim: int
    state: int
    groups: int = 1
    conv_width: int = 4
    eps: float = 1e-5
    chunk: int = 256
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        from mmlspark_tpu.ops import linear_attention as la
        if self.heads % self.groups:
            raise ValueError(f"{self.heads} heads over {self.groups} groups")
        B, L, _ = u.shape
        H, P, N, G = self.heads, self.head_dim, self.state, self.groups
        d_in, mixed, dt_, f32 = H * P, H * P + 2 * G * N, self.dtype, \
            jnp.float32
        with jax.named_scope("mamba2_mixer"):
            zxbcdt = checkpoint_name(
                _dense(d_in + mixed + H, dt_,
                       "attn_gate_value_key_query_dt")(u.astype(dt_)),
                MAMBA2_IN)
            conv = self.param("conv_kernel", _INIT,
                              (self.conv_width, mixed), f32)
            conv_bias = self.param("conv_bias", _INIT, (mixed,), f32)
            a_log = self.param(
                "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                    key, shape, f32, 1.0, 16.0)), (H,))
            dt_bias = self.param("dt_bias", _dt_bias_init, (H,), f32)
            skip = self.param("D_skip", nn.initializers.ones, (H,), f32)
            z = zxbcdt[..., :d_in]
            with jax.named_scope("ssm_conv"):
                xbc = nn.silu(la.causal_conv1d(
                    zxbcdt[..., d_in:d_in + mixed], conv, conv_bias))
            x = xbc[..., :d_in].reshape(B, L, H, P)
            Bm = xbc[..., d_in:d_in + G * N].reshape(B, L, G, N)
            Cm = xbc[..., d_in + G * N:].reshape(B, L, G, N)
            dt = jax.nn.softplus(
                zxbcdt[..., d_in + mixed:].astype(f32) + dt_bias)
            y = la.ssd(x, dt, -jnp.exp(a_log), Bm, Cm, chunk=self.chunk,
                       dtype=dt_)
            y = y + skip[:, None] * x.astype(f32)
            y = y.reshape(B, L, d_in) * nn.silu(z.astype(f32))
            y = RMSNorm(self.eps, name="gate_norm")(y)
            return _dense(self.dim, dt_, "attn_out")(y.astype(dt_))


class SwiGluMlp(nn.Module):
    dim: int
    hidden: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        # the scope names the dense feed-forward part wherever it runs (a
        # block's own, a routed layer's shared expert) for the split of
        # device time by part (``observability/scopes.py``)
        with jax.named_scope("ffn"):
            x = x.astype(self.dtype)
            gate = checkpoint_name(
                _dense(self.hidden, self.dtype, "mlp_gate")(x), MLP_GATE_UP)
            up = checkpoint_name(
                _dense(self.hidden, self.dtype, "mlp_up")(x), MLP_GATE_UP)
            return _dense(self.dim, self.dtype, "mlp_down")(
                nn.silu(gate) * up)


class Head(nn.Module):
    """The untied output head; the chunked loss reads ``kernel`` itself
    (``train/lm_loss.py``) and never calls this on a whole batch."""
    vocab: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", _INIT, (x.shape[-1], self.vocab),
                            jnp.float32)
        return jnp.dot(x.astype(jnp.float32), kernel)
