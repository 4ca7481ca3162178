"""Decoder LMs built from parts, and the five families made of them:
``glm4_moe_lite`` (GLM-4.7-Flash; the layers are DeepSeek-V3's),
``qwen3_next`` (Qwen3-Next: three Gated DeltaNet layers to one gated
softmax layer, every feed-forward part a routed layer),
``granite_hybrid`` (Granite 4.0-H: Mamba-2 mixers and grouped softmax
attention without positions in the order of a published list, a dense
SwiGLU part in every layer, scaled residual additions, one table for the
embedding and the head) and ``olmo_hybrid`` (Olmo-Hybrid: Gated DeltaNet
layers whose state's transition may have negative eigenvalues and plain
softmax attention without positions in the order of a published list, a
dense SwiGLU part in every layer, OLMo 2's norms on each half's OUTPUT)
and ``lfm2_moe`` (LFM2-24B-A2B: gated short convolutions and rotary
grouped attention in the order of a published list, a dense SwiGLU part in
the leading layers and a routed layer in every later one, one table).

``PartsBlock`` is the pre-norm residual block with nothing fixed: its norm,
its attention (which owns its projections and its positions) and its
feed-forward layer are factories ``name -> nn.Module``. A new decoder family
is a set of parts, not a third trunk beside ``TransformerLM`` (whose
parameter names and tied head stay as they are for the generate lane).

Parts here:

- ``RMSNorm``: float32 in and out, epsilon from the configuration; with
  ``offset`` the scale is ``1 + w`` and ``w`` starts at zero;
- ``rotary``: rotary positions on the last axis, half-split pairing
  (dimension ``i`` turns with ``i + R/2``), float32 angles; with ``width``
  on the first ``width`` dimensions only, the rest passing through;
- ``MlaAttention``: multi-head latent attention in its expanded (training)
  form: a low-rank query, one compressed key/value row per token, a rotary
  slice on every query head and ONE rotary key shared by all heads;
- ``GatedAttention``: softmax attention over grouped key/value heads (each
  repeated to the query heads it serves at the attention call, so the
  flash kernel and its backward run as they are), norms on q and k, a
  rotary slice, and a sigmoid gate on the output;
- ``GroupedAttention``: the same grouped heads with nothing else: no
  positions, no gate, a softmax scale of its own, and if asked an RMS norm
  over the whole q and the whole k projection, or (``norm_heads``) over
  each head's channels, and (``theta``) rotary positions on the whole head;
- ``ShortConv``: LFM2's gated short convolution, which IS the mixer:
  ``[B | C | x] = u W_in``, a causal depthwise convolution of three taps
  over ``B * x`` with no activation, the gate ``C`` on its output;
- ``GatedDeltaNet``: linear attention with a recurrent state
  (``ops/linear_attention.py``): a short causal convolution, the gated
  delta rule with ``beta`` in (0, ``beta_scale``), a gated norm on the
  output;
- ``Mamba2Mixer``: the state-space layer (the same module's ``ssd``): a
  convolution with a bias over ``[x | B | C]``, a scalar decay a head,
  ``B`` and ``C`` shared by groups of heads, a skip, the gate BEFORE the
  norm;
- ``SwiGluMlp``: ``down(silu(gate x) * up x)``, no biases;
- ``zoo/moe.DroplessMoe``: the routed layer, told which experts it holds.

Parameter names hit the rules of ``parallel/sharding.DEFAULT_RULES``
(``attn_query*`` / ``attn_key*`` / ``attn_value`` / ``attn_qkvz`` /
``attn_gate_value_key_query_dt`` / ``attn_in`` / ``attn_out``, ``mlp_gate``
/ ``mlp_up`` / ``mlp_down``, ``experts_*``, ``router``, ``lm_head``,
``token_embedding``).

Blocks are recomputed in the backward pass one by one (``nn.remat``), which
is what lets 4,096-token rows train beside the optimizer's state on one
chip. A block keeps its input and a short list of named values whose
recomputation costs more than their bytes (``_remat_block``): the
flash kernel's output and log-sum-exps and the five tiles the gated delta
rule's forward call writes, so that each kernel's forward runs once a
block and not twice, and the SwiGLU gate and up products. The list is one
for every family (a name that no value of a block carries costs nothing)
but where a family's state leaves no room for all of it: that family
hands ``_remat_block`` the names it lets go.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from mmlspark_tpu.models.zoo import register_model
from mmlspark_tpu.models.zoo.moe import DroplessMoe
from mmlspark_tpu.observability import metrics as obsmetrics
from mmlspark_tpu.parallel.sequence import full_attention

_INIT = nn.initializers.normal(0.02)
# the checkpoint name of ``SwiGluMlp``'s gate and up products
MLP_GATE_UP = "mlp_gate_up"
# and of ``GatedDeltaNet``'s input projection's output
DELTA_NET_QKVZ = "delta_net_qkvz"
# and of ``ShortConv``'s
SHORT_CONV_IN = "short_conv_in"


class RMSNorm(nn.Module):
    eps: float = 1e-5
    offset: bool = False        # scale = 1 + w, w zero at init

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.initializers.zeros if self.offset
            else nn.initializers.ones, (x.shape[-1],), jnp.float32)
        if self.offset:
            scale = 1.0 + scale
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps) * scale


def rotary(x: jax.Array, theta: float,
           width: Optional[int] = None) -> jax.Array:
    """Rotary positions over the last axis of ``(B, L, H, R)``: position
    ``l`` turns the pair ``(i, i + R/2)`` by ``l * theta**(-2i/R)``. With
    ``width`` only the first ``width`` dimensions turn (pairs ``(i, i +
    width/2)``, angles over ``width``) and the rest pass through."""
    if width is not None and width != x.shape[-1]:
        return jnp.concatenate(
            [rotary(x[..., :width], theta), x[..., width:]], -1)
    L, R = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    kernel_init=_INIT, name=name)


class MlaAttention(nn.Module):
    """Multi-head latent attention, expanded form. Query/key heads are
    ``nope + rope`` wide and value heads ``v_dim``; the attention call is
    the framework's ``(q, k, v, causal)`` on ``(B, L, H, D)``, so the two
    have to be equally wide (they are, 256, in the published model)."""
    dim: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    theta: float = 1e6
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        if self.nope + self.rope != self.v_dim:
            raise ValueError(
                f"query/key heads are {self.nope + self.rope} wide and "
                f"value heads {self.v_dim}: attention_fn(q, k, v) takes "
                "one head width")
        B, L, _ = x.shape
        H, dt = self.heads, self.dtype
        attn_fn = self.attention_fn or full_attention
        with jax.named_scope("mla_attention"):
            x = x.astype(dt)
            cq = RMSNorm(self.eps, name="query_norm")(
                _dense(self.q_rank, dt, "attn_query_a")(x)).astype(dt)
            q = _dense(H * (self.nope + self.rope), dt, "attn_query_b")(
                cq).reshape(B, L, H, self.nope + self.rope)
            kva = _dense(self.kv_rank + self.rope, dt, "attn_key_value_a")(x)
            ckv = RMSNorm(self.eps, name="key_value_norm")(
                kva[..., :self.kv_rank]).astype(dt)
            kv = _dense(H * (self.nope + self.v_dim), dt,
                        "attn_key_value_b")(ckv).reshape(
                            B, L, H, self.nope + self.v_dim)
            q_r = rotary(q[..., self.nope:], self.theta)
            # the one rotary key, shared by every head
            k_r = rotary(kva[..., None, self.kv_rank:], self.theta)
            q = jnp.concatenate([q[..., :self.nope], q_r], -1)
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
            q = rotary(q, self.theta, self.rotary_width).astype(dt)
            k = rotary(k, self.theta, self.rotary_width).astype(dt)
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


class GroupedAttention(nn.Module):
    """Causal softmax attention with grouped key/value heads and little
    else: no gate, no biases; ``softmax(scale x q k^T) v`` with a
    published ``scale`` that need not be ``head_dim ** -0.5``. Without
    ``theta`` no positions (in ``granite_hybrid`` and ``olmo_hybrid`` the
    recurrent layers carry the order); with it rotary positions on the
    whole head of q and k (``lfm2_moe``). With ``qk_norm_eps`` an RMS norm
    with a plain scale on q and on k (scope ``qk_norm``): over the WHOLE
    projection before the split into heads (OLMo 2's ``q_norm`` /
    ``k_norm``), or with ``norm_heads`` over EACH head's ``head_dim``
    channels, one scale of ``head_dim`` shared by the heads (LFM2's
    ``q_layernorm`` / ``k_layernorm``), float32 through the rotation;
    without, none. LFM2's softmax layer is this part with two arguments
    and not a third part: ``GatedAttention`` would need its ``1 + w``
    scales, its rotary slice and its gate (which shapes ``W_q``) argued
    away.
    ``attention_fn(q, k, v)`` keeps its own ``head_dim ** -0.5``, so ``q``
    is multiplied by ``scale x head_dim ** 0.5`` before the call (0.125 in
    the published Granite: a power of two, exact in bfloat16). Each
    key/value head is repeated to the ``heads / kv_heads`` query heads it
    serves at that call, as in ``GatedAttention``."""
    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    scale: Optional[float] = None       # None: head_dim ** -0.5
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    qk_norm_eps: Optional[float] = None     # None: no norm on q and k
    norm_heads: bool = False            # the norm over each head, not all
    theta: Optional[float] = None       # None: no positions

    @nn.compact
    def __call__(self, x):
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads over "
                             f"{self.kv_heads} key/value heads")
        B, L, _ = x.shape
        H, G, d, dt = self.heads, self.kv_heads, self.head_dim, self.dtype
        attn_fn = self.attention_fn or full_attention
        with jax.named_scope("grouped_attention"):
            x = x.astype(dt)

            def heads_of(name, heads, norm=None):
                y = _dense(heads * d, dt, name)(x)
                normed = norm and self.qk_norm_eps is not None
                if normed and not self.norm_heads:
                    with jax.named_scope("qk_norm"):
                        y = RMSNorm(self.qk_norm_eps, name=norm)(y).astype(dt)
                y = y.reshape(B, L, heads, d)
                if normed and self.norm_heads:
                    with jax.named_scope("qk_norm"):
                        y = RMSNorm(self.qk_norm_eps, name=norm)(y)
                if norm and self.theta is not None:
                    y = rotary(y, self.theta)
                return y.astype(dt)
            q = heads_of("attn_query", H, "query_norm")
            k = heads_of("attn_key", G, "key_norm")
            v = heads_of("attn_value", G)
            if self.scale is not None:
                q = q * jnp.asarray(self.scale * d ** 0.5, dt)
            k, v = (jnp.repeat(t, H // G, axis=2) for t in (k, v))
            o = attn_fn(q, k, v, causal=True)
            return _dense(self.dim, dt, "attn_out")(o.reshape(B, L, H * d))


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
            zxbcdt = _dense(d_in + mixed + H, dt_,
                            "attn_gate_value_key_query_dt")(u.astype(dt_))
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


class PartsBlock(nn.Module):
    """``h = x + r attention(norm(x))``, ``y = h + r ffn(norm(h))``, ``r``
    = ``residual_scale`` (1 in most families). A feed-forward part may
    return ``(y, stats)``, ``stats`` a dict of scalars (a routed layer's
    load); the block returns ``(y, stats)`` always. With ``norm_output``
    the norms sit on each half's OUTPUT, OLMo 2's wiring: ``h = x + r
    norm(attention(x))``, ``y = h + r norm(ffn(h))`` (scope
    ``post_norm``). It is an argument of the block and not a wrapper
    around each part, so that ``norm1`` and ``norm2`` stay the block's own
    in the parameter tree and in the split of device time, as every
    family's norms of the residual stream are."""
    norm: Callable[[str], nn.Module]
    attention: Callable[[str], nn.Module]
    ffn: Callable[[str], nn.Module]
    residual_scale: float = 1.0
    norm_output: bool = False

    @nn.compact
    def __call__(self, x) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        h = _add(x, _half(self.norm("norm1"), self.attention("attn"), x,
                          self.norm_output), self.residual_scale)
        out = _half(self.norm("norm2"), self.ffn("ffn"), h, self.norm_output)
        y, stats = out if isinstance(out, tuple) else (out, {})
        return _add(h, y, self.residual_scale), stats


def _half(norm, part, x, norm_output: bool):
    """One half of a block before its residual addition: ``part(norm(x))``,
    or with ``norm_output`` ``norm(part(x))``; a part's ``stats`` pass."""
    if not norm_output:
        return part(norm(x))
    out = part(x)
    y, rest = (out[0], out[1:]) if isinstance(out, tuple) else (out, ())
    with jax.named_scope("post_norm"):
        y = norm(y)
    return (y,) + rest if rest else y


def _add(x, y, scale: float):
    """The residual addition ``x + scale y`` in ``x``'s type."""
    y = y.astype(x.dtype)
    return x + y if scale == 1.0 else x + jnp.asarray(scale, x.dtype) * y


class SplitBlock(nn.Module):
    """``PartsBlock`` with its two halves as methods (``mix``: ``x +
    attention(norm(x))``; ``feed``: ``h + ffn(norm(h))``), so that each can
    be a unit of recomputation of its own (``residual_scale`` and
    ``norm_output`` as there): what the backward pass of the feed-forward
    half keeps never lies beside what the mixer's keeps. The
    parts are made in ``setup`` under ``PartsBlock``'s names (``norm1``,
    ``attn``, ``norm2``, ``ffn``); the factories are called with no name."""
    make_norm: Callable[[Optional[str]], nn.Module]
    make_attention: Callable[[Optional[str]], nn.Module]
    make_ffn: Callable[[Optional[str]], nn.Module]
    residual_scale: float = 1.0
    norm_output: bool = False

    def setup(self):
        self.norm1, self.attn = self.make_norm(None), self.make_attention(None)
        self.norm2, self.ffn = self.make_norm(None), self.make_ffn(None)

    def mix(self, x):
        return _add(x, _half(self.norm1, self.attn, x, self.norm_output),
                    self.residual_scale)

    def feed(self, h) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        out = _half(self.norm2, self.ffn, h, self.norm_output)
        y, stats = out if isinstance(out, tuple) else (out, {})
        return _add(h, y, self.residual_scale), stats

    def __call__(self, x) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        return self.feed(self.mix(x))


def _remat_block(norm, attention, ffn, name: str, split: bool = False,
                 residual_scale: float = 1.0, norm_output: bool = False,
                 let_go: Tuple[str, ...] = ()) -> nn.Module:
    """A ``PartsBlock`` recomputed in the backward pass, but for what is
    named here: ONE list for every family, because a name that no value of
    a block carries costs nothing (the names sit where the values are
    made); ``let_go`` names what a family whose state leaves no room for
    all of it does without (``OlmoHybrid``; its row is in that class's
    docstring). In units of the block's input, bf16 (B, L, dim): the flash
    kernel's output and log-sum-exps, 2.5, without which its forward call
    runs twice a block; the SwiGLU gate and up products, 10 in
    ``glm4_moe_lite``'s dense block, 1.5 in a routed block's shared
    expert, 8 in every ``granite_hybrid`` block (2.68 GB a step: its mark
    11.77 -> 14.16 GB of the chip's 16.91); the five tiles the gated delta
    rule's forward call writes, 12 a Gated DeltaNet block, without which
    ``delta_chunk_fwd`` runs twice a block; that block's input projection
    (``DELTA_NET_QKVZ``, the ``[q | k | v | z]`` rows), 6; a
    ``ShortConv``'s (``SHORT_CONV_IN``, the ``[B | C | x]`` rows), 3. Each
    paid on the chip (PERF.md section 6; PR 29: +5.6% and +1.5% of a
    ``glm4_moe_lite`` step; PR 36: the tiles +3.7% and the projection
    +2.0% of a ``qwen3_next`` step, the products +5.3% of a
    ``granite_hybrid`` step; PR 40: the short convolution's projection
    +3.5% of an ``lfm2_moe`` step, whose mark went 13.64 -> 14.43 GB).
    Left to the recomputation: the residual stream after attention (1 a
    block, +0.7%: under the 1% a name has to pay); q, k, v (7.5 a block, 1.5 GB a step, for under 10 ms); the
    routed experts' ragged_dot intermediates (1 GB a step for 5 ms, and
    the benchmark's moe.expert_matmul_roofline counts their recomputation
    as required work); ``granite_hybrid``'s mixer's input projection
    (1.26 GB a step for 13.7 ms: one run read +3.4%, the next issue's to
    measure, PERF.md section 7);
    dots_with_no_batch_dims_saveable (about 3 GB: no room beside
    AdamW's state). Attention that is not the flash kernel carries no
    such name and keeps what it kept before; a delta rule that runs XLA's
    batched form (head widths ``pallas_delta_rule.supports`` refuses: the
    tiny presets') names no tiles, and either rule's walk keeps a state a
    chunk across ITS backward inside the
    recomputation, where no name reaches. ``split`` recomputes the
    block's two halves apart (``SplitBlock``) and keeps the residual
    stream between them: for a block whose halves' backward passes do not
    fit side by side. ``residual_scale`` and ``norm_output`` are the
    block's. (Imported here: Pallas costs every importer of the zoo over a
    second.)"""
    from mmlspark_tpu.ops.pallas_attention import FLASH_RESIDUALS
    from mmlspark_tpu.ops.pallas_delta_rule import DELTA_CHUNK_TILES
    policy = jax.checkpoint_policies.save_only_these_names(*(
        n for n in (FLASH_RESIDUALS, MLP_GATE_UP, DELTA_CHUNK_TILES,
                    DELTA_NET_QKVZ, SHORT_CONV_IN) if n not in let_go))
    if split:
        return nn.remat(SplitBlock, policy=policy, methods=("mix", "feed"))(
            norm, attention, ffn, residual_scale, norm_output, name=name)
    return nn.remat(PartsBlock, policy=policy)(
        norm, attention, ffn, residual_scale, norm_output, name=name)


class Head(nn.Module):
    """The untied output head; the chunked loss reads ``kernel`` itself
    (``train/lm_loss.py``) and never calls this on a whole batch."""
    vocab: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", _INIT, (x.shape[-1], self.vocab),
                            jnp.float32)
        return jnp.dot(x.astype(jnp.float32), kernel)


class Glm4MoeLite(nn.Module):
    """``glm4_moe_lite``: ``dense_layers`` SwiGLU blocks, then routed
    blocks up to ``depth``, all with latent attention; one multi-token-
    prediction module (DeepSeek-V3 section 2.2) when ``mtp`` is set.

    ``__call__(tokens)`` gives ``(B, L, vocab)`` float32 logits of the
    main head. ``__call__(tokens, hidden=True)`` gives what the chunked
    loss wants instead: ``{"hidden", "mtp_hidden", "stats"}``, the normed
    rows each head reads and the routed layers' load (``_load_stats``).
    """
    vocab: int
    dim: int
    depth: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    mlp_hidden: int
    expert_hidden: int
    num_experts: int
    top_k: int
    experts_held: Optional[Tuple[int, int]] = None   # (count, first index)
    shared_experts: int = 1
    scaling: float = 1.0
    dense_layers: int = 1
    mtp: bool = True
    theta: float = 1e6
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None

    def _block(self, routed: bool, name: str) -> nn.Module:
        dt = self.dtype

        def attention(n):
            return MlaAttention(
                self.dim, self.heads, self.q_rank, self.kv_rank, self.nope,
                self.rope, self.v_dim, self.theta, self.eps, dt,
                self.attention_fn, name=n)

        def ffn(n):
            if not routed:
                return SwiGluMlp(self.dim, self.mlp_hidden, dt, name=n)
            return DroplessMoe(
                self.dim, self.num_experts, self.expert_hidden, self.top_k,
                experts_held=self.experts_held, scaling=self.scaling,
                shared=(lambda m: SwiGluMlp(
                    self.dim, self.shared_experts * self.expert_hidden, dt,
                    name=m)) if self.shared_experts else None,
                dtype=dt, name=n)

        return _remat_block(lambda n: RMSNorm(self.eps, name=n), attention,
                            ffn, name)

    @nn.compact
    def __call__(self, tokens, hidden: bool = False):
        embed = nn.Embed(self.vocab, self.dim, dtype=self.dtype,
                         embedding_init=_INIT, name="token_embedding")
        final_norm = RMSNorm(self.eps, name="final_norm")
        head = Head(self.vocab, name="lm_head")
        x = embed(tokens)
        loads = []
        for i in range(self.depth):
            x, stats = self._block(i >= self.dense_layers, f"block{i}")(x)
            loads.append(stats)
        out = {"hidden": final_norm(x)}
        self.sow("intermediates", "hidden", out["hidden"])
        if self.mtp:
            with jax.named_scope("mtp"):
                # row i joins the trunk's state with token i + 1 and
                # predicts token i + 2; the last row joins a wrapped token
                # that no earlier row can see (causal) and no loss counts
                nxt = embed(jnp.roll(tokens, -1, axis=1))
                z = jnp.concatenate(
                    [RMSNorm(self.eps, name="mtp_hnorm")(x),
                     RMSNorm(self.eps, name="mtp_enorm")(nxt)], -1)
                z = _dense(self.dim, self.dtype, "mtp_eh_proj")(
                    z.astype(self.dtype))
                z, stats = self._block(True, "mtp_block")(z)
                loads.append(stats)
                out["mtp_hidden"] = final_norm(z)
        if not hidden:
            return head(out["hidden"])
        if self.is_initializing():
            head(out["hidden"][:, :1])
        out["stats"] = _load_stats(loads)
        return out


def _load_stats(loads) -> Dict[str, jax.Array]:
    """The routed layers' load as the trainer's ring carries it:
    ``moe.slots_here`` and ``moe.rows_moved`` (the rows of the rungs their
    expert-order buffers took) summed over them, ``moe.overflow_layers``
    (how many of them ran at full size this step),
    ``moe.load_max_over_mean`` of the worst."""
    loads = [s for s in loads if s]
    if not loads:
        return {}
    return {"moe.slots_here": sum(
                s["slots_here"] for s in loads).astype(jnp.float32),
            "moe.rows_moved": sum(
                s["rows"] for s in loads).astype(jnp.float32),
            "moe.overflow_layers": sum(
                s["overflowed"] for s in loads).astype(jnp.float32),
            "moe.load_max_over_mean": jnp.max(jnp.stack(
                [s["load_max_over_mean"] for s in loads]))}


class Qwen3Next(nn.Module):
    """``qwen3_next``: layer ``l`` is ``GatedAttention`` when ``(l + 1) %
    attention_interval == 0``, else ``GatedDeltaNet``; every feed-forward
    part is a ``DroplessMoe`` routed by softmax with a gated shared expert;
    norms are ``1 + w``; final norm, untied head, no multi-token-prediction
    module. ``__call__`` as ``Glm4MoeLite``'s: logits, or with
    ``hidden=True`` ``{"hidden", "stats"}`` for the chunked loss."""
    vocab: int
    dim: int
    depth: int
    heads: int
    kv_heads: int
    head_dim: int
    rotary_width: int
    linear_key_heads: int
    linear_value_heads: int
    linear_key_dim: int
    linear_value_dim: int
    conv_width: int
    expert_hidden: int
    shared_hidden: int
    num_experts: int
    top_k: int
    experts_held: Optional[Tuple[int, int]] = None   # (count, first index)
    attention_interval: int = 4
    theta: float = 1e7
    eps: float = 1e-6
    chunk: int = 64
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None

    def softmax_layer(self, index: int) -> bool:
        return (index + 1) % self.attention_interval == 0

    def _block(self, index: int, name: str) -> nn.Module:
        dt = self.dtype

        def attention(n):
            if self.softmax_layer(index):
                return GatedAttention(
                    self.dim, self.heads, self.kv_heads, self.head_dim,
                    self.rotary_width, self.theta, self.eps, dt,
                    self.attention_fn, name=n)
            return GatedDeltaNet(
                self.dim, self.linear_key_heads, self.linear_value_heads,
                self.linear_key_dim, self.linear_value_dim, self.conv_width,
                self.eps, self.chunk, dt, name=n)

        def ffn(n):
            return DroplessMoe(
                self.dim, self.num_experts, self.expert_hidden, self.top_k,
                experts_held=self.experts_held,
                shared=lambda m: SwiGluMlp(self.dim, self.shared_hidden, dt,
                                           name=m),
                dtype=dt, scores="softmax", shared_gate=True, name=n)

        return _remat_block(
            lambda n: RMSNorm(self.eps, offset=True, name=n), attention,
            ffn, name, split=True)

    @nn.compact
    def __call__(self, tokens, hidden: bool = False):
        embed = nn.Embed(self.vocab, self.dim, dtype=self.dtype,
                         embedding_init=_INIT, name="token_embedding")
        head = Head(self.vocab, name="lm_head")
        x = embed(tokens)
        loads = []
        for i in range(self.depth):
            x, stats = self._block(i, f"block{i}")(x)
            loads.append(stats)
        out = {"hidden": RMSNorm(self.eps, offset=True,
                                 name="final_norm")(x)}
        self.sow("intermediates", "hidden", out["hidden"])
        if not hidden:
            return head(out["hidden"])
        if self.is_initializing():
            head(out["hidden"][:, :1])
        out["stats"] = _load_stats(loads)
        return out


class GraniteHybrid(nn.Module):
    """``granite_hybrid`` (``model_type: granitemoehybrid`` without routed
    experts): ``h_0 = embedding_multiplier x E[token]``; layer ``l`` is ``h
    <- h + r mixer_l(norm(h))``, ``h <- h + r mlp(norm(h))`` with ``r`` =
    ``residual_multiplier``, ``mixer_l`` a ``Mamba2Mixer`` where
    ``layer_types[l]`` is ``"mamba"`` and a ``GroupedAttention`` (no
    positions, softmax scale ``attention_multiplier``) where it is
    ``"attention"``, the feed-forward part a dense ``SwiGluMlp``; plain
    RMS norms, a final norm, ``logits = (h E^T) / logits_scaling``: ONE
    table, read by the embedding's gather and by the head.

    ``__call__(tokens)`` gives ``(B, L, vocab)`` float32 logits.
    ``__call__(tokens, hidden=True)`` gives ``{"hidden", "stats"}`` for
    the chunked loss: the normed rows ALREADY divided by
    ``logits_scaling``, so that ``next_token_loss(out, E^T, tokens)`` with
    ``E = params["token_embedding"]["embedding"]`` is the model's loss;
    ``stats`` is empty (no routed layer). Each block is recomputed in the
    backward pass in halves and keeps what ``_remat_block`` names for
    every family: here the flash kernel's residuals in the softmax layers
    and the gate and up products, 8,192 wide, in every layer."""
    vocab: int
    dim: int
    layer_types: Tuple[str, ...]
    heads: int
    kv_heads: int
    head_dim: int
    mamba_heads: int
    mamba_head_dim: int
    state: int
    groups: int
    conv_width: int
    mlp_hidden: int
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    eps: float = 1e-5
    chunk: int = 256
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None

    def _block(self, kind: str, name: str) -> nn.Module:
        dt = self.dtype

        def attention(n):
            if kind == "attention":
                return GroupedAttention(
                    self.dim, self.heads, self.kv_heads, self.head_dim,
                    self.attention_multiplier, dt, self.attention_fn, name=n)
            return Mamba2Mixer(
                self.dim, self.mamba_heads, self.mamba_head_dim, self.state,
                self.groups, self.conv_width, self.eps, self.chunk, dt,
                name=n)

        return _remat_block(
            lambda n: RMSNorm(self.eps, name=n), attention,
            lambda n: SwiGluMlp(self.dim, self.mlp_hidden, dt, name=n),
            name, split=True, residual_scale=self.residual_multiplier)

    @nn.compact
    def __call__(self, tokens, hidden: bool = False):
        if not self.layer_types \
                or set(self.layer_types) - {"mamba", "attention"}:
            raise ValueError(f"layer_types {self.layer_types!r}: "
                             "'mamba' or 'attention' a layer")
        embed = nn.Embed(self.vocab, self.dim, dtype=self.dtype,
                         embedding_init=_INIT, name="token_embedding")
        x = embed(tokens) * jnp.asarray(self.embedding_multiplier,
                                        self.dtype)
        for i, kind in enumerate(self.layer_types):
            x, _ = self._block(kind, f"block{i}")(x)
        normed = RMSNorm(self.eps, name="final_norm")(x)
        self.sow("intermediates", "hidden", normed)
        h = normed / self.logits_scaling
        if not hidden:
            return jnp.dot(h, embed.embedding.T)
        return {"hidden": h, "stats": {}}


class OlmoHybrid(nn.Module):
    """``olmo_hybrid`` (``model_type: olmo_hybrid``): ``h_0 = E[token]``;
    layer ``l`` is ``h <- h + norm(mixer_l(h))``, ``h <- h + norm(mlp(h))``
    (the norm on each half's OUTPUT, none on its input), ``mixer_l`` a
    ``GatedDeltaNet`` with ``beta`` in (0, 2) where ``layer_types[l]`` is
    ``"linear_attention"`` and a ``GroupedAttention`` with as many
    key/value as query heads, an RMS norm over the whole q and the whole k
    projection and no positions where it is ``"full_attention"``, the
    feed-forward part a dense ``SwiGluMlp``; plain RMS norms, a final
    norm, an untied head. ``__call__`` as ``Qwen3Next``'s (``stats`` is
    empty: no routed layer).

    Each block is recomputed in the backward pass in halves and keeps
    ``_remat_block``'s names but ``LET_GO``: this family's 928.9M
    parameters at the benchmark's cut are 11.15 GB of weights and moments
    on a chip of 16.91, the least room of any family here. The step
    compiled for a described v5e peaks at 17.19 GB with all four names
    (refused), 16.41 without the delta net's input projection, 16.35
    without the SwiGLU gate and up products (11,008 wide: 5.7 units of the
    block's bf16 input a layer, the cheapest name a byte by PR 36's
    readings), 15.74 with the flash kernel's residuals alone; on the chip
    (PERF.md section 6, PR 38) the sub-list without the products ran
    1.640 rows/s, the one without the projection 1.622, tiles and
    residuals alone 1.564."""
    vocab: int
    dim: int
    layer_types: Tuple[str, ...]
    heads: int
    head_dim: int
    linear_key_heads: int
    linear_value_heads: int
    linear_key_dim: int
    linear_value_dim: int
    conv_width: int
    mlp_hidden: int
    eps: float = 1e-6
    chunk: int = 64
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None

    # of ``_remat_block``'s names, those this family lets go
    LET_GO = (MLP_GATE_UP,)

    def _block(self, kind: str, name: str) -> nn.Module:
        dt = self.dtype

        def attention(n):
            if kind == "full_attention":
                return GroupedAttention(
                    self.dim, self.heads, self.heads, self.head_dim, None, dt,
                    self.attention_fn, self.eps, name=n)
            return GatedDeltaNet(
                self.dim, self.linear_key_heads, self.linear_value_heads,
                self.linear_key_dim, self.linear_value_dim, self.conv_width,
                self.eps, self.chunk, dt, beta_scale=2.0, name=n)

        return _remat_block(
            lambda n: RMSNorm(self.eps, name=n), attention,
            lambda n: SwiGluMlp(self.dim, self.mlp_hidden, dt, name=n),
            name, split=True, norm_output=True, let_go=self.LET_GO)

    @nn.compact
    def __call__(self, tokens, hidden: bool = False):
        if not self.layer_types or set(self.layer_types) - {
                "linear_attention", "full_attention"}:
            raise ValueError(f"layer_types {self.layer_types!r}: "
                             "'linear_attention' or 'full_attention' a layer")
        embed = nn.Embed(self.vocab, self.dim, dtype=self.dtype,
                         embedding_init=_INIT, name="token_embedding")
        head = Head(self.vocab, name="lm_head")
        x = embed(tokens)
        for i, kind in enumerate(self.layer_types):
            x, _ = self._block(kind, f"block{i}")(x)
        out = {"hidden": RMSNorm(self.eps, name="final_norm")(x)}
        self.sow("intermediates", "hidden", out["hidden"])
        if not hidden:
            return head(out["hidden"])
        if self.is_initializing():
            head(out["hidden"][:, :1])
        out["stats"] = {}
        return out


class Lfm2Moe(nn.Module):
    """``lfm2_moe`` (``model_type: lfm2_moe``): ``h_0 = E[token]``; layer
    ``l`` is ``h <- h + op_l(norm(h))``, ``h <- h + ffn_l(norm(h))``. The
    mixer's kind and the feed-forward part's kind both depend on the
    layer's index, each on its own: ``op_l`` is a ``ShortConv`` where
    ``layer_types[l]`` is ``"conv"`` and a ``GroupedAttention`` with a
    norm over each q and k head and rotary positions on the whole head
    where it is ``"full_attention"``; ``ffn_l`` is a dense ``SwiGluMlp``
    for ``l < dense_layers`` and from there on a ``DroplessMoe`` routed by
    sigmoid scores over all ``num_experts`` (the choice the top ``top_k``
    of score plus bias, the weights over their sum plus ``weight_eps``, no
    shared expert), of which ``experts_held`` live here. Plain RMS norms,
    a final norm, ONE table read by the embedding's gather and by the head
    (``GraniteHybrid``'s way, without its multipliers). ``gate_grad`` is
    the routed layers' (``DroplessMoe``).

    ``__call__(tokens)`` gives ``(B, L, vocab)`` float32 logits.
    ``__call__(tokens, hidden=True)`` gives ``{"hidden", "stats"}`` for
    the chunked loss, ``stats`` the routed layers' load (``_load_stats``),
    so that ``next_token_loss(out, E^T, tokens)`` with ``E =
    params["token_embedding"]["embedding"]`` is the model's loss. Each
    block is recomputed in the backward pass and keeps ``_remat_block``'s
    names: here the flash kernel's residuals in the softmax layers, the
    gate and up products of the dense part and each short convolution's
    ``[B | C | x]`` rows."""
    vocab: int
    dim: int
    layer_types: Tuple[str, ...]
    heads: int
    kv_heads: int
    head_dim: int
    mlp_hidden: int
    expert_hidden: int
    num_experts: int
    top_k: int
    experts_held: Optional[Tuple[int, int]] = None   # (count, first index)
    dense_layers: int = 2
    conv_taps: int = 3
    conv_bias: bool = False
    scaling: float = 1.0
    weight_eps: float = 1e-6
    gate_grad: bool = True
    theta: float = 1e6
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None

    def _block(self, index: int, name: str) -> nn.Module:
        dt = self.dtype

        def attention(n):
            if self.layer_types[index] == "full_attention":
                return GroupedAttention(
                    self.dim, self.heads, self.kv_heads, self.head_dim, None,
                    dt, self.attention_fn, self.eps, norm_heads=True,
                    theta=self.theta, name=n)
            return ShortConv(self.dim, self.conv_taps, self.conv_bias, dt,
                             name=n)

        def ffn(n):
            if index < self.dense_layers:
                return SwiGluMlp(self.dim, self.mlp_hidden, dt, name=n)
            return DroplessMoe(
                self.dim, self.num_experts, self.expert_hidden, self.top_k,
                experts_held=self.experts_held, scaling=self.scaling,
                dtype=dt, weight_eps=self.weight_eps,
                gate_grad=self.gate_grad, name=n)

        return _remat_block(lambda n: RMSNorm(self.eps, name=n), attention,
                            ffn, name)

    @nn.compact
    def __call__(self, tokens, hidden: bool = False):
        if not self.layer_types or set(self.layer_types) - {
                "conv", "full_attention"}:
            raise ValueError(f"layer_types {self.layer_types!r}: "
                             "'conv' or 'full_attention' a layer")
        embed = nn.Embed(self.vocab, self.dim, dtype=self.dtype,
                         embedding_init=_INIT, name="token_embedding")
        x = embed(tokens)
        loads = []
        for i in range(len(self.layer_types)):
            x, stats = self._block(i, f"block{i}")(x)
            loads.append(stats)
        normed = RMSNorm(self.eps, name="final_norm")(x)
        self.sow("intermediates", "hidden", normed)
        if not hidden:
            return jnp.dot(normed, embed.embedding.T)
        return {"hidden": normed, "stats": _load_stats(loads)}


def _spec(module: nn.Module, max_len: int):
    return dict(
        module=module, input_shape=(max_len,), input_dtype="int32",
        feature_layer="hidden", feature_dim=module.dim,
        layer_names=["hidden", "logits"],
        # blocks use the (q, k, v, causal) attention contract
        seq_attention=True)


@register_model("glm4_moe_lite")
def glm4_moe_lite(vocab: int = 154880, dim: int = 2048, depth: int = 47,
                  heads: int = 20, q_rank: int = 768, kv_rank: int = 512,
                  nope: int = 192, rope: int = 64, v_dim: int = 256,
                  mlp_hidden: int = 10240, expert_hidden: int = 1536,
                  num_experts: int = 64, top_k: int = 4,
                  experts_held=None, shared_experts: int = 1,
                  scaling: float = 1.8, dense_layers: int = 1,
                  mtp: bool = True, theta: float = 1e6, eps: float = 1e-5,
                  max_len: int = 4096, dtype=jnp.bfloat16,
                  attention_fn=None):
    """GLM-4.7-Flash as published (huggingface.co/zai-org/GLM-4.7-Flash
    ``config.json``, ``model_type: glm4_moe_lite``). ``experts_held`` =
    ``(count, first)`` is this chip's share of each routed layer: the
    router still scores all ``num_experts``."""
    held = None if experts_held is None else tuple(experts_held)
    return _spec(Glm4MoeLite(
        vocab, dim, depth, heads, q_rank, kv_rank, nope, rope, v_dim,
        mlp_hidden, expert_hidden, num_experts, top_k, held,
        shared_experts, scaling, dense_layers, mtp, theta, eps, dtype,
        attention_fn), max_len)


_TINY = dict(vocab=96, dim=32, depth=3, heads=2, q_rank=24, kv_rank=16,
             nope=12, rope=4, v_dim=16, mlp_hidden=64, expert_hidden=16,
             num_experts=8, top_k=2, max_len=64, dtype=jnp.float32)


@register_model("glm4_moe_lite_tiny")
def glm4_moe_lite_tiny(**overrides):
    """Test-scale ``glm4_moe_lite`` (float32, so CPU parity is tight)."""
    return glm4_moe_lite(**{**_TINY, **overrides})


@register_model("qwen3_next")
def qwen3_next(vocab: int = 151936, dim: int = 2048, depth: int = 48,
               heads: int = 16, kv_heads: int = 2, head_dim: int = 256,
               rotary_fraction: float = 0.25, linear_key_heads: int = 16,
               linear_value_heads: int = 32, linear_key_dim: int = 128,
               linear_value_dim: int = 128, conv_width: int = 4,
               expert_hidden: int = 512, shared_hidden: int = 512,
               num_experts: int = 512, top_k: int = 10, experts_held=None,
               attention_interval: int = 4, theta: float = 1e7,
               eps: float = 1e-6, chunk: int = 64, max_len: int = 4096,
               dtype=jnp.bfloat16, attention_fn=None):
    """Qwen3-Next-80B-A3B as published (huggingface.co/Qwen/
    Qwen3-Next-80B-A3B-Instruct ``config.json``, ``model_type:
    qwen3_next``), without its multi-token-prediction module.
    ``experts_held`` = ``(count, first)`` as for ``glm4_moe_lite``."""
    held = None if experts_held is None else tuple(experts_held)
    return _spec(Qwen3Next(
        vocab, dim, depth, heads, kv_heads, head_dim,
        int(head_dim * rotary_fraction), linear_key_heads,
        linear_value_heads, linear_key_dim, linear_value_dim, conv_width,
        expert_hidden, shared_hidden, num_experts, top_k, held,
        attention_interval, theta, eps, chunk, dtype, attention_fn), max_len)


_QWEN_TINY = dict(vocab=96, dim=32, depth=4, heads=4, kv_heads=2, head_dim=16,
                  linear_key_heads=2, linear_value_heads=4, linear_key_dim=8,
                  linear_value_dim=8, expert_hidden=16, shared_hidden=16,
                  num_experts=16, top_k=3, chunk=8, max_len=64,
                  dtype=jnp.float32)


@register_model("qwen3_next_tiny")
def qwen3_next_tiny(**overrides):
    """Test-scale ``qwen3_next`` (float32, so CPU parity is tight): one
    period of the layer pattern, chunks of 8 tokens."""
    return qwen3_next(**{**_QWEN_TINY, **overrides})


GRANITE_4_H_MICRO_LAYERS = (("mamba",) * 5 + ("attention",)
                            + ("mamba",) * 9 + ("attention",)
                            + ("mamba",) * 9 + ("attention",)
                            + ("mamba",) * 9 + ("attention",)
                            + ("mamba",) * 4)


@register_model("granite_hybrid")
def granite_hybrid(vocab: int = 100352, dim: int = 2048,
                   layer_types=GRANITE_4_H_MICRO_LAYERS, heads: int = 32,
                   kv_heads: int = 8, head_dim: int = 64,
                   mamba_heads: int = 64, mamba_head_dim: int = 64,
                   state: int = 128, groups: int = 1, conv_width: int = 4,
                   mlp_hidden: int = 8192, embedding_multiplier: float = 12.0,
                   attention_multiplier: float = 0.015625,
                   residual_multiplier: float = 0.22,
                   logits_scaling: float = 8.0, eps: float = 1e-5,
                   chunk: int = 256, max_len: int = 8192,
                   dtype=jnp.bfloat16, attention_fn=None):
    """Granite 4.0-H Micro as published (huggingface.co/ibm-granite/
    granite-4.0-h-micro ``config.json``, ``model_type: granitemoehybrid``
    with ``num_local_experts`` 0): forty layers, a Mamba-2 mixer or
    grouped attention without positions by ``layer_types``, a dense SwiGLU
    part in each, four published multipliers, a tied head."""
    return _spec(GraniteHybrid(
        vocab, dim, tuple(layer_types), heads, kv_heads, head_dim,
        mamba_heads, mamba_head_dim, state, groups, conv_width, mlp_hidden,
        embedding_multiplier, attention_multiplier, residual_multiplier,
        logits_scaling, eps, chunk, dtype, attention_fn), max_len)


_GRANITE_TINY = dict(vocab=96, dim=32,
                     layer_types=("mamba", "attention", "mamba") * 2, heads=4,
                     kv_heads=2, head_dim=8, mamba_heads=4, mamba_head_dim=8,
                     state=8, groups=1, mlp_hidden=48,
                     embedding_multiplier=3.0, attention_multiplier=0.25,
                     residual_multiplier=0.5, logits_scaling=2.0, chunk=8,
                     max_len=64, dtype=jnp.float32)


@register_model("granite_hybrid_tiny")
def granite_hybrid_tiny(**overrides):
    """Test-scale ``granite_hybrid`` (float32, so CPU parity is tight): two
    periods of a short pattern with both kinds of layer, chunks of 8
    tokens, multipliers that are not 1 and a softmax scale that is not
    ``head_dim ** -0.5``."""
    return granite_hybrid(**{**_GRANITE_TINY, **overrides})


OLMO_HYBRID_7B_LAYERS = (("linear_attention",) * 3 + ("full_attention",)) * 8


@register_model("olmo_hybrid")
def olmo_hybrid(vocab: int = 100352, dim: int = 3840,
                layer_types=OLMO_HYBRID_7B_LAYERS, heads: int = 30,
                head_dim: int = 128, linear_key_heads: int = 30,
                linear_value_heads: int = 30, linear_key_dim: int = 96,
                linear_value_dim: int = 192, conv_width: int = 4,
                mlp_hidden: int = 11008, eps: float = 1e-6, chunk: int = 64,
                max_len: int = 8192, dtype=jnp.bfloat16, attention_fn=None):
    """Olmo-Hybrid-7B as published (huggingface.co/allenai/Olmo-Hybrid-7B
    ``config.json``, ``model_type: olmo_hybrid``): thirty-two layers, three
    Gated DeltaNet layers (96 x 192 a head, ``linear_allow_neg_eigval``) to
    one full-attention layer without positions, a dense SwiGLU part in
    each, the norms on each half's output, an untied head. ``head_dim`` =
    ``hidden_size / num_attention_heads`` (the config has no key for it)."""
    return _spec(OlmoHybrid(
        vocab, dim, tuple(layer_types), heads, head_dim, linear_key_heads,
        linear_value_heads, linear_key_dim, linear_value_dim, conv_width,
        mlp_hidden, eps, chunk, dtype, attention_fn), max_len)


_OLMO_TINY = dict(vocab=96, dim=32,
                  layer_types=("linear_attention",) * 3 + ("full_attention",),
                  heads=4, head_dim=8, linear_key_heads=4,
                  linear_value_heads=4, linear_key_dim=8, linear_value_dim=16,
                  mlp_hidden=48, chunk=8, max_len=64, dtype=jnp.float32)


@register_model("olmo_hybrid_tiny")
def olmo_hybrid_tiny(**overrides):
    """Test-scale ``olmo_hybrid`` (float32, so CPU parity is tight): one
    period of the layer pattern, head widths in the published 1 : 2 ratio,
    chunks of 8 tokens."""
    return olmo_hybrid(**{**_OLMO_TINY, **overrides})


LFM2_24B_A2B_LAYERS = ("conv", "conv") + (
    "full_attention", "conv", "conv", "conv") * 9 + ("full_attention", "conv")


@register_model("lfm2_moe")
def lfm2_moe(vocab: int = 65536, dim: int = 2048,
             layer_types=LFM2_24B_A2B_LAYERS, heads: int = 32,
             kv_heads: int = 8, head_dim: int = 64, mlp_hidden: int = 11776,
             expert_hidden: int = 1536, num_experts: int = 64,
             top_k: int = 4, experts_held=None, dense_layers: int = 2,
             conv_taps: int = 3, conv_bias: bool = False,
             scaling: float = 1.0, weight_eps: float = 1e-6,
             gate_grad: bool = True, theta: float = 1e6, eps: float = 1e-5,
             max_len: int = 8192, dtype=jnp.bfloat16, attention_fn=None):
    """LFM2-24B-A2B as published (huggingface.co/LiquidAI/LFM2-24B-A2B
    ``config.json``, ``model_type: lfm2_moe``): forty layers, a gated
    short convolution or rotary grouped attention by ``layer_types``
    (layers 2, 6, ..., 38 attend), a dense SwiGLU part in the first two
    and 64 sigmoid-routed experts, four a token, in every later one, one
    table. ``head_dim`` = ``hidden_size / num_attention_heads`` (the
    config has no key for it). ``experts_held`` = ``(count, first)`` as
    for ``glm4_moe_lite``; ``gate_grad=False`` for a share trained without
    its exchange (``DroplessMoe``)."""
    held = None if experts_held is None else tuple(experts_held)
    return _spec(Lfm2Moe(
        vocab, dim, tuple(layer_types), heads, kv_heads, head_dim,
        mlp_hidden, expert_hidden, num_experts, top_k, held, dense_layers,
        conv_taps, conv_bias, scaling, weight_eps, gate_grad, theta, eps,
        dtype, attention_fn), max_len)


_LFM2_TINY = dict(vocab=96, dim=32,
                  layer_types=("conv", "full_attention", "conv", "conv"),
                  heads=4, kv_heads=2, head_dim=8, mlp_hidden=48,
                  expert_hidden=16, num_experts=8, top_k=2, dense_layers=1,
                  max_len=64, dtype=jnp.float32)


@register_model("lfm2_moe_tiny")
def lfm2_moe_tiny(**overrides):
    """Test-scale ``lfm2_moe`` (float32, so CPU parity is tight): one
    leading dense layer, then one period's kinds of mixer under routed
    layers of eight experts, two a token."""
    return lfm2_moe(**{**_LFM2_TINY, **overrides})
