"""Decoder LMs: ONE skeleton (``Decoder``: embed, blocks, final norm,
head) over the parts of ``models/zoo/parts.py``, and the nine families as
registry entries that say which part sits at which layer:
``glm4_moe_lite`` (GLM-4.7-Flash; the layers are DeepSeek-V3's),
``qwen3_next`` (Qwen3-Next: three Gated DeltaNet layers to one gated
softmax layer, every feed-forward part a routed layer),
``granite_hybrid`` (Granite 4.0-H: Mamba-2 mixers and grouped softmax
attention without positions in the order of a published list, a dense
SwiGLU part in every layer, scaled residual additions, one table for the
embedding and the head), ``olmo_hybrid`` (Olmo-Hybrid: Gated DeltaNet
layers whose state's transition may have negative eigenvalues and plain
softmax attention without positions in the order of a published list, a
dense SwiGLU part in every layer, OLMo 2's norms on each half's OUTPUT)
and ``lfm2_moe`` (LFM2-24B-A2B: gated short convolutions and rotary
grouped attention in the order of a published list, a dense SwiGLU part in
the leading layers and a routed layer in every later one, one table) and
``laguna`` (Laguna-XS.2: window-512 and full softmax layers three to one,
the same part at two settings with different head counts and rotary rules
and a sigmoid gate a head, a dense part then small sigmoid-routed experts
beside a shared one by a published list) and ``sdar_moe``
(SDAR-30B-A3B-Chat: equal layers of rotary grouped attention with a norm a
head under softmax-routed experts with no shared one, called on rows of
``[noised copy | clean copy]`` under the block-diffusion mask) and
``kimi_linear`` (Kimi-Linear-48B-A3B: Kimi Delta Attention, the delta rule
with a decay a key channel, three to one with latent attention that has
no query rank, no positions and keys wider than its values, by two
published lists; a dense part, then sigmoid-routed experts beside a shared
one) and ``keye_vl2`` (Keye-VL-2.0-30B-A3B's language model: ``sdar_moe``'s
block under a plain causal loss, each query's keys chosen by a learned
indexer that has a loss of its own).

What a family IS lives in its entry, beside the name of the published
``config.json`` it reads: the mixer and the feed-forward part of layer
``l`` (two tuples of factories ``name -> nn.Module``), the norm, where the
norms sit, the multipliers, which head. A new family is one more entry, any
part it lacks (in ``parts.py``) and its tests: no class here is edited, and
``tests/test_decoder_programs.py`` says whether the older families'
programs moved. ``TransformerLM`` stays a trunk of its own (its parameter
names and tied head are the generate lane's).

Blocks are recomputed in the backward pass one by one (``nn.remat``), which
is what lets 4,096-token rows train beside the optimizer's state on one
chip. A block keeps its input and a short list of named values whose
recomputation costs more than their bytes (``_remat_block``): the
flash kernel's output and log-sum-exps and the five tiles the gated delta
rule's forward call writes, so that each kernel's forward runs once a
block and not twice, and the SwiGLU gate and up products. The list is one
for every family (a name that no value of a block carries costs nothing)
but where a family's state leaves no room for all of it: that family's
entry hands ``Decoder`` the names it lets go.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from mmlspark_tpu.models.zoo import register_model
from mmlspark_tpu.models.zoo.moe import DroplessMoe
from mmlspark_tpu.models.zoo.parts import (
    _INIT, ATTN_QKV, DELTA_NET_QKVZ, MAMBA2_IN, MLP_GATE_UP, SELECTION,
    SHORT_CONV_IN, GatedAttention, GatedDeltaNet, GroupedAttention, Head,
    KimiDeltaAttention, Mamba2Mixer, MlaAttention, RMSNorm, ShortConv,
    SwiGluMlp, _dense, plain_frequencies, yarn_frequencies)

# a part's factory: the flax name (None inside a block, whose ``setup``
# names its parts by attribute) -> the module
Part = Callable[[Optional[str]], nn.Module]


class PartsBlock(nn.Module):
    """The pre-norm residual block with nothing fixed: ``h = x + r
    attention(norm(x))`` (``mix``), ``y = h + r ffn(norm(h))`` (``feed``),
    ``r`` = ``residual_scale`` (1 in most families). Its norm, its
    attention (which owns its projections and its positions) and its
    feed-forward part are factories, called in ``setup`` with no name: the
    parts are ``norm1``, ``attn``, ``norm2``, ``ffn`` in the parameter
    tree. A feed-forward part may return ``(y, stats)``, ``stats`` a dict
    of scalars (a routed layer's load), and so may a mixer (a loss of its
    own under ``aux_loss``, a dict of what it counts under ``counts``);
    ``mix``, ``feed`` and the block return ``(y, stats)`` always, the
    block the two parts' joined. With ``norm_output`` the norms sit on
    each half's OUTPUT,
    OLMo 2's wiring: ``h = x + r norm(attention(x))``, ``y = h + r
    norm(ffn(h))`` (scope ``post_norm``). It is an argument of the block
    and not a wrapper around each part, so that ``norm1`` and ``norm2``
    stay the block's own in the parameter tree and in the split of device
    time, as every family's norms of the residual stream are. The two
    halves are methods so that each can be a unit of recomputation of its
    own (``_remat_block``'s ``split``): what the backward pass of the
    feed-forward half keeps then never lies beside what the mixer's
    keeps."""
    make_norm: Part
    make_attention: Part
    make_ffn: Part
    residual_scale: float = 1.0
    norm_output: bool = False

    def setup(self):
        self.norm1, self.attn = self.make_norm(None), self.make_attention(None)
        self.norm2, self.ffn = self.make_norm(None), self.make_ffn(None)

    def mix(self, x) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        out = _half(self.norm1, self.attn, x, self.norm_output)
        y, stats = out if isinstance(out, tuple) else (out, {})
        return _add(x, y, self.residual_scale), stats

    def feed(self, h) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        out = _half(self.norm2, self.ffn, h, self.norm_output)
        y, stats = out if isinstance(out, tuple) else (out, {})
        return _add(h, y, self.residual_scale), stats

    def __call__(self, x) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        h, mixed = self.mix(x)
        y, stats = self.feed(h)
        return y, {**mixed, **stats}


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


def _remat_block(norm, attention, ffn, name: str, split: bool = False,
                 residual_scale: float = 1.0, norm_output: bool = False,
                 let_go: Tuple[str, ...] = ()) -> nn.Module:
    """A ``PartsBlock`` recomputed in the backward pass, but for what is
    named here: ONE list for every family, because a name that no value of
    a block carries costs nothing (the names sit where the values are
    made); ``let_go`` names what a family whose state leaves no room for
    all of it does without (``olmo_hybrid``; its row is in that entry's
    docstring). In units of the block's input, bf16 (B, L, dim): the
    flash kernel's output and log-sum-exps, 2.5, without which its forward
    call runs twice a block; the SwiGLU gate and up products, 10 in
    ``glm4_moe_lite``'s dense block, 1.5 in a routed block's shared
    expert, 8 in every ``granite_hybrid`` block (2.68 GB a step: its mark
    11.77 -> 14.16 GB of the chip's 16.91); the five tiles the gated delta
    rule's forward call writes, 12 a Gated DeltaNet block, without which
    ``delta_chunk_fwd`` runs twice a block; that block's input projection
    (``DELTA_NET_QKVZ``, the ``[q | k | v | z]`` rows), 6; a
    ``ShortConv``'s (``SHORT_CONV_IN``, the ``[B | C | x]`` rows), 3; a
    ``Mamba2Mixer``'s (``MAMBA2_IN``, the ``[z | x | B | C | dt]`` rows),
    4.2 (1.26 GB a step over ``granite_hybrid``'s nine mixers); a
    ``GroupedAttention``'s q, k and v projections' outputs (``ATTN_QKV``:
    named before the norm a head, the turn, the scale and the repeat of K
    and V, which are bytes and no product and stay recomputed; where
    ``ops/pallas_head_norm_turn`` turns a layer's q and k with no norm,
    ``laguna``'s, the TURNED rows carry the name: the turn's derivative
    reads no rows, so as many bytes are kept and no layer turns twice), 5 in
    ``laguna``'s sliding blocks and 4 in its full ones (1.54 GB a step),
    1.5 in ``granite_hybrid``'s and ``lfm2_moe``'s; the mask of a selection
    (``SELECTION``: int8, a byte a pair of the row, 4 in ``keye_vl2``'s
    blocks at a row of 16,384; without it the indexer's scores and the
    choice among them, which pass no gradient, would be made again for the
    core's backward pass, and what the selected core kept under
    ``FLASH_RESIDUALS`` would have to match a second choice bit for bit).
    Each but the last paid on the chip
    (PERF.md section 6; PR 29: +5.6% and +1.5% of a ``glm4_moe_lite``
    step; PR 36: the tiles +3.7% and the projection +2.0% of a
    ``qwen3_next`` step, the products +5.3% of a ``granite_hybrid`` step;
    PR 40: the short convolution's projection +3.5% of an ``lfm2_moe``
    step, whose mark went 13.64 -> 14.43 GB; PR 46: the mixer's rows, with
    the one softmax layer's q, k, v, +3.8% of a ``granite_hybrid`` step,
    13.8 + 0.6 ms of a recompute column of 44.0, mark 14.04 -> 15.09 GB;
    q, k, v +3.5% of a ``laguna`` step, 18.9 ms of 91.9, 15.10 -> 16.02
    GB, and +0.4% of an ``lfm2_moe`` step, 14.51 -> 14.71 GB).
    Left to the recomputation: the residual stream after attention (1 a
    block, +0.7%: under the 1% a name has to pay); ``MlaAttention``'s q,
    k, v (7.5 a block, 1.5 GB a step, for under 10 ms: PR 29, so that part
    carries no name, nor does ``GatedAttention``); the routed experts'
    ragged_dot intermediates (1 GB a step for 5 ms, and the benchmark's
    moe.expert_matmul_roofline counts their recomputation as required
    work); dots_with_no_batch_dims_saveable (about 3 GB: no room beside
    AdamW's state). Attention that is not the flash kernel carries no
    residuals' name and keeps of its core what it kept before; a delta
    rule that runs XLA's batched form (head widths
    ``pallas_delta_rule.supports`` refuses: the tiny presets') names no
    tiles, and either rule's walk keeps a state a chunk across ITS
    backward inside the recomputation, where no name reaches. ``split``
    recomputes the block's two halves apart (``mix``, ``feed``) and keeps
    the residual stream between them: for a block whose halves' backward
    passes do not fit side by side. ``residual_scale`` and ``norm_output``
    are the block's. (Imported here: Pallas costs every importer of the
    zoo over a second.)"""
    from mmlspark_tpu.ops.pallas_attention import FLASH_RESIDUALS
    from mmlspark_tpu.ops.pallas_delta_rule import DELTA_CHUNK_TILES
    policy = jax.checkpoint_policies.save_only_these_names(*(
        n for n in (FLASH_RESIDUALS, MLP_GATE_UP, DELTA_CHUNK_TILES,
                    DELTA_NET_QKVZ, SHORT_CONV_IN, MAMBA2_IN, ATTN_QKV,
                    SELECTION)
        if n not in let_go))
    return nn.remat(PartsBlock, policy=policy,
                    methods=("mix", "feed") if split else None)(
        norm, attention, ffn, residual_scale, norm_output, name=name)


class Decoder(nn.Module):
    """The skeleton every family shares, and nothing of any family: ``h_0 =
    embedding_multiplier x E[token]``; layer ``l`` is a ``PartsBlock`` of
    ``mixers[l]`` and ``ffns[l]`` under ``norm`` (with ``split``,
    ``residual_scale``, ``norm_output`` and ``let_go`` as ``_remat_block``
    takes them); a final norm; ``logits = head(h) / logits_scaling``, the
    head an untied ``lm_head`` or with ``tied`` the embedding's own table
    transposed. A multiplier or divisor of exactly 1 emits no operation.
    ``mtp`` = ``(mixer, ffn)`` adds ONE multi-token-prediction module
    (DeepSeek-V3 section 2.2; ``glm4_moe_lite`` alone sets it): the
    trunk's state before the final norm joined with the next token's
    embedding, one more block of those parts, the same final norm.

    ``__call__(tokens)`` gives ``(B, L, vocab)`` float32 logits of the
    main head. ``__call__(tokens, hidden=True)`` gives what the chunked
    loss wants instead: ``{"hidden", "stats"}`` and with ``mtp``
    ``"mtp_hidden"``, the normed rows each head reads ALREADY divided by
    ``logits_scaling``, and the routed layers' load (``_load_stats``;
    empty without a routed layer), and ``"aux_loss"`` where a mixer has a
    loss of its own (the layers' sum), so that ``next_token_loss(out, W,
    tokens)`` with ``W`` the head's kernel (``params["lm_head"]["kernel"]``,
    or ``params["token_embedding"]["embedding"].T``) is the model's loss.
    """
    vocab: int
    dim: int
    mixers: Tuple[Part, ...]
    ffns: Tuple[Part, ...]
    norm: Part
    split: bool = False
    residual_scale: float = 1.0
    norm_output: bool = False
    let_go: Tuple[str, ...] = ()
    embedding_multiplier: float = 1.0
    logits_scaling: float = 1.0
    tied: bool = False
    mtp: Optional[Tuple[Part, Part]] = None
    dtype: Any = jnp.bfloat16

    def _block(self, mixer: Part, ffn: Part, name: str) -> nn.Module:
        return _remat_block(
            self.norm, mixer, ffn, name, split=self.split,
            residual_scale=self.residual_scale,
            norm_output=self.norm_output, let_go=self.let_go)

    @nn.compact
    def __call__(self, tokens, hidden: bool = False):
        table = nn.Embed(self.vocab, self.dim, dtype=self.dtype,
                         embedding_init=_INIT, name="token_embedding")
        final_norm = self.norm("final_norm")

        def embed(tokens):
            x = table(tokens)
            return x if self.embedding_multiplier == 1.0 else x * jnp.asarray(
                self.embedding_multiplier, self.dtype)

        def scaled(h):
            return h if self.logits_scaling == 1.0 \
                else h / self.logits_scaling

        if self.tied:
            def head(h):
                return jnp.dot(h, table.embedding.T)
        else:
            head = Head(self.vocab, name="lm_head")
        x = embed(tokens)
        loads = []
        for i, (mixer, ffn) in enumerate(zip(self.mixers, self.ffns)):
            x, stats = self._block(mixer, ffn, f"block{i}")(x)
            loads.append(stats)
        normed = final_norm(x)
        self.sow("intermediates", "hidden", normed)
        out = {"hidden": scaled(normed)}
        if self.mtp is not None:
            with jax.named_scope("mtp"):
                # row i joins the trunk's state with token i + 1 and
                # predicts token i + 2; the last row joins a wrapped token
                # that no earlier row can see (causal) and no loss counts
                nxt = embed(jnp.roll(tokens, -1, axis=1))
                z = jnp.concatenate([self.norm("mtp_hnorm")(x),
                                     self.norm("mtp_enorm")(nxt)], -1)
                z = _dense(self.dim, self.dtype, "mtp_eh_proj")(
                    z.astype(self.dtype))
                z, stats = self._block(*self.mtp, "mtp_block")(z)
                loads.append(stats)
                out["mtp_hidden"] = scaled(final_norm(z))
        if not hidden:
            return head(out["hidden"])
        if not self.tied and self.is_initializing():
            head(out["hidden"][:, :1])
        out["stats"] = _load_stats(loads)
        aux = [s["aux_loss"] for s in loads if "aux_loss" in s]
        if aux:
            out["aux_loss"] = sum(aux)
        return out


def _load_stats(loads) -> Dict[str, jax.Array]:
    """The routed layers' load as the trainer's ring carries it:
    ``moe.slots_here`` and ``moe.rows_moved`` (the rows of the rungs their
    expert-order buffers took) summed over them, ``moe.overflow_layers``
    (how many of them ran at full size this step),
    ``moe.load_max_over_mean`` of the worst; and what the mixers count
    (a layer's ``counts``, by the names they give), summed over the
    layers."""
    mixers = [s["counts"] for s in loads if "counts" in s]
    counts = {k: sum(c[k] for c in mixers)
              for k in (mixers[0] if mixers else ())}
    loads = [s for s in loads if "slots_here" in s]
    if not loads:
        return counts
    return {**counts, "moe.slots_here": sum(
                s["slots_here"] for s in loads).astype(jnp.float32),
            "moe.rows_moved": sum(
                s["rows"] for s in loads).astype(jnp.float32),
            "moe.overflow_layers": sum(
                s["overflowed"] for s in loads).astype(jnp.float32),
            "moe.load_max_over_mean": jnp.max(jnp.stack(
                [s["load_max_over_mean"] for s in loads]))}


def _spec(module: nn.Module, max_len: int):
    return dict(
        module=module, input_shape=(max_len,), input_dtype="int32",
        feature_layer="hidden", feature_dim=module.dim,
        layer_names=["hidden", "logits"],
        # blocks use the (q, k, v, causal) attention contract
        seq_attention=True)


def _rms(eps: float, offset: bool = False) -> Part:
    return lambda n: RMSNorm(eps, offset, name=n)


def _by_kind(layer_types, parts: Dict[str, Part]) -> Tuple[Part, ...]:
    """Each layer's mixer from a published ``layer_types``, which is input
    from a configuration file: an empty list or a kind no part answers to
    is refused here."""
    kinds = tuple(layer_types)
    if not kinds or set(kinds) - set(parts):
        raise ValueError(f"layer_types {kinds!r}: "
                         + " or ".join(map(repr, parts)) + " a layer")
    return tuple(parts[kind] for kind in kinds)


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
    ``config.json``, ``model_type: glm4_moe_lite``): ``dense_layers``
    SwiGLU blocks, then routed blocks up to ``depth``, all with latent
    attention, plain RMS norms, an untied head; one multi-token-prediction
    module of a routed block when ``mtp`` is set. ``experts_held`` =
    ``(count, first)`` is this chip's share of each routed layer: the
    router still scores all ``num_experts``."""
    held = None if experts_held is None else tuple(experts_held)

    def attention(n):
        return MlaAttention(dim, heads, q_rank, kv_rank, nope, rope, v_dim,
                            theta, eps, dtype, attention_fn, name=n)

    def dense(n):
        return SwiGluMlp(dim, mlp_hidden, dtype, name=n)

    def routed(n):
        return DroplessMoe(
            dim, num_experts, expert_hidden, top_k, experts_held=held,
            scaling=scaling, shared=(lambda m: SwiGluMlp(
                dim, shared_experts * expert_hidden, dtype,
                name=m)) if shared_experts else None, dtype=dtype, name=n)

    return _spec(Decoder(
        vocab, dim, (attention,) * depth,
        tuple(dense if l < dense_layers else routed for l in range(depth)),
        _rms(eps), mtp=(attention, routed) if mtp else None, dtype=dtype),
        max_len)


_TINY = dict(vocab=96, dim=32, depth=3, heads=2, q_rank=24, kv_rank=16,
             nope=12, rope=4, v_dim=16, mlp_hidden=64, expert_hidden=16,
             num_experts=8, top_k=2, max_len=64, dtype=jnp.float32)


@register_model("glm4_moe_lite_tiny")
def glm4_moe_lite_tiny(**overrides):
    """Test-scale ``glm4_moe_lite`` (float32, so CPU parity is tight)."""
    return glm4_moe_lite(**{**_TINY, **overrides})


def qwen3_next_layers(depth: int, attention_interval: int) -> Tuple[str, ...]:
    """Qwen3-Next's rule as a list of kinds: layer ``l`` attends when ``(l
    + 1) % attention_interval == 0``."""
    return tuple(
        "full_attention" if (l + 1) % attention_interval == 0
        else "linear_attention" for l in range(depth))


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
    qwen3_next``), without its multi-token-prediction module: a
    ``GatedAttention`` layer every ``attention_interval`` layers
    (``qwen3_next_layers``) and ``GatedDeltaNet`` layers between; every
    feed-forward part a ``DroplessMoe`` routed by softmax with a gated
    shared expert; norms ``1 + w``; an untied head; each block recomputed
    in halves. ``experts_held`` = ``(count, first)`` as for
    ``glm4_moe_lite``."""
    held = None if experts_held is None else tuple(experts_held)

    def attention(n):
        return GatedAttention(
            dim, heads, kv_heads, head_dim, int(head_dim * rotary_fraction),
            theta, eps, dtype, attention_fn, name=n)

    def linear(n):
        return GatedDeltaNet(
            dim, linear_key_heads, linear_value_heads, linear_key_dim,
            linear_value_dim, conv_width, eps, chunk, dtype, name=n)

    def routed(n):
        return DroplessMoe(
            dim, num_experts, expert_hidden, top_k, experts_held=held,
            shared=lambda m: SwiGluMlp(dim, shared_hidden, dtype, name=m),
            dtype=dtype, scores="softmax", shared_gate=True, name=n)

    return _spec(Decoder(
        vocab, dim, _by_kind(
            qwen3_next_layers(depth, attention_interval),
            {"linear_attention": linear, "full_attention": attention}),
        (routed,) * depth, _rms(eps, offset=True), split=True, dtype=dtype),
        max_len)


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
    with ``num_local_experts`` 0): forty layers, a ``Mamba2Mixer`` where
    ``layer_types[l]`` is ``"mamba"`` and a ``GroupedAttention`` (no
    positions, softmax scale ``attention_multiplier``) where it is
    ``"attention"``, a dense SwiGLU part in each, plain RMS norms, four
    published multipliers (``h_0 = embedding_multiplier x E[token]``, every
    residual addition ``h + residual_multiplier x part(norm(h))``, ``logits
    = (h E^T) / logits_scaling``), ONE table read by the embedding's
    gather and by the head. Each block is recomputed in halves and keeps
    what ``_remat_block`` names for every family: here the flash kernel's
    residuals and the q, k and v rows in the softmax layers, each mixer's
    ``[z | x | B | C | dt]`` rows and the gate and up products, 8,192
    wide, in every layer."""
    def attention(n):
        return GroupedAttention(dim, heads, kv_heads, head_dim,
                                attention_multiplier, dtype, attention_fn,
                                name=n)

    def mamba(n):
        return Mamba2Mixer(dim, mamba_heads, mamba_head_dim, state, groups,
                           conv_width, eps, chunk, dtype, name=n)

    mixers = _by_kind(layer_types, {"mamba": mamba, "attention": attention})
    return _spec(Decoder(
        vocab, dim, mixers,
        (lambda n: SwiGluMlp(dim, mlp_hidden, dtype, name=n),) * len(mixers),
        _rms(eps), split=True, residual_scale=residual_multiplier,
        embedding_multiplier=embedding_multiplier,
        logits_scaling=logits_scaling, tied=True, dtype=dtype), max_len)


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
    ``config.json``, ``model_type: olmo_hybrid``): thirty-two layers, a
    ``GatedDeltaNet`` (96 x 192 a head, ``beta`` in (0, 2):
    ``linear_allow_neg_eigval``) where ``layer_types[l]`` is
    ``"linear_attention"`` and where it is ``"full_attention"`` a
    ``GroupedAttention`` with as many key/value as query heads, an RMS norm
    over the whole q and the whole k projection and no positions; a dense
    SwiGLU part in each; plain RMS norms on each half's OUTPUT and none on
    its input; an untied head. ``head_dim`` = ``hidden_size /
    num_attention_heads`` (the config has no key for it).

    Each block is recomputed in halves and keeps ``_remat_block``'s names
    but the SwiGLU products and the softmax layer's q, k and v rows
    (``let_go``): this family's 928.9M parameters at the benchmark's cut
    are 11.15 GB of weights and moments on a chip of 16.91, the least room
    of any family here. The step compiled for a described v5e peaks at
    17.19 GB with the four names a block of PR 38 carried (refused), 16.41
    without the delta net's input projection, 16.35 without the SwiGLU
    gate and up products (11,008 wide: 5.7 units of the block's bf16 input
    a layer, the cheapest name a byte by PR 36's readings), 15.74 with the
    flash kernel's residuals alone; on the chip (PERF.md section 6, PR 38)
    the sub-list without the products ran 1.640 rows/s, the one without
    the projection 1.622, tiles and residuals alone 1.564. ``ATTN_QKV``
    (PR 46) would be 0.19 GB of the 0.38 the step's mark, 16.53 GB, leaves:
    let go unmeasured, and the step lowers to the text it had (PERF.md
    section 6, PR 46)."""
    def attention(n):
        return GroupedAttention(dim, heads, heads, head_dim, None, dtype,
                                attention_fn, eps, name=n)

    def linear(n):
        return GatedDeltaNet(
            dim, linear_key_heads, linear_value_heads, linear_key_dim,
            linear_value_dim, conv_width, eps, chunk, dtype, beta_scale=2.0,
            name=n)

    mixers = _by_kind(layer_types, {"linear_attention": linear,
                                    "full_attention": attention})
    return _spec(Decoder(
        vocab, dim, mixers,
        (lambda n: SwiGluMlp(dim, mlp_hidden, dtype, name=n),) * len(mixers),
        _rms(eps), split=True, norm_output=True,
        let_go=(MLP_GATE_UP, ATTN_QKV), dtype=dtype), max_len)


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
    ``config.json``, ``model_type: lfm2_moe``): forty layers whose mixer
    AND feed-forward part both depend on the layer's index, each on its
    own. The mixer is a ``ShortConv`` where ``layer_types[l]`` is
    ``"conv"`` and where it is ``"full_attention"`` (layers 2, 6, ..., 38)
    a ``GroupedAttention`` with a norm over each q and k head and rotary
    positions on the whole head; the feed-forward part is a dense
    ``SwiGluMlp`` for ``l < dense_layers`` and from there on a
    ``DroplessMoe`` routed by sigmoid scores over all ``num_experts`` (the
    choice the top ``top_k`` of score plus bias, the weights over their sum
    plus ``weight_eps``, no shared expert). Plain RMS norms, ONE table read
    by the embedding's gather and by the head (``granite_hybrid``'s way,
    without its multipliers). ``head_dim`` = ``hidden_size /
    num_attention_heads`` (the config has no key for it). ``experts_held``
    = ``(count, first)`` as for ``glm4_moe_lite``; ``gate_grad=False`` for
    a share trained without its exchange (``DroplessMoe``). Each block is
    recomputed whole and keeps ``_remat_block``'s names: here the flash
    kernel's residuals and the q, k and v rows in the softmax layers, the
    gate and up products of the dense part and each short convolution's
    ``[B | C | x]`` rows."""
    held = None if experts_held is None else tuple(experts_held)

    def attention(n):
        return GroupedAttention(dim, heads, kv_heads, head_dim, None, dtype,
                                attention_fn, eps, norm_heads=True,
                                rotary_freqs=plain_frequencies(
                                    head_dim, theta), name=n)

    def conv(n):
        return ShortConv(dim, conv_taps, conv_bias, dtype, name=n)

    def dense(n):
        return SwiGluMlp(dim, mlp_hidden, dtype, name=n)

    def routed(n):
        return DroplessMoe(
            dim, num_experts, expert_hidden, top_k, experts_held=held,
            scaling=scaling, dtype=dtype, weight_eps=weight_eps,
            gate_grad=gate_grad, name=n)

    mixers = _by_kind(layer_types, {"conv": conv,
                                    "full_attention": attention})
    return _spec(Decoder(
        vocab, dim, mixers, tuple(
            dense if l < dense_layers else routed
            for l in range(len(mixers))), _rms(eps), tied=True, dtype=dtype),
        max_len)


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


LAGUNA_XS2_LAYERS = ("full_attention",) + ("sliding_attention",) * 3
LAGUNA_XS2_HEADS = {"full_attention": 48, "sliding_attention": 64}


@register_model("laguna")
def laguna(vocab: int = 100352, dim: int = 2048,
           layer_types=LAGUNA_XS2_LAYERS * 10, heads_per_layer=None,
           mlp_layer_types=("dense",) + ("sparse",) * 39, kv_heads: int = 8,
           head_dim: int = 128, window: int = 512, mlp_hidden: int = 8192,
           expert_hidden: int = 512, shared_hidden: int = 512,
           num_experts: int = 256, top_k: int = 8, experts_held=None,
           scaling: float = 2.5, gate_grad: bool = True,
           head_gate: bool = True, full_theta: float = 5e5,
           full_rotary_fraction: float = 0.5, yarn_factor: float = 64.0,
           yarn_original: int = 4096, yarn_beta_fast: float = 64.0,
           yarn_beta_slow: float = 1.0,
           yarn_attention_factor: float = 1.4158883083359672,
           window_theta: float = 1e4, eps: float = 1e-6,
           max_len: int = 8192, dtype=jnp.bfloat16, attention_fn=None):
    """Laguna-XS.2 as published (huggingface.co/poolside/Laguna-XS.2
    ``config.json``, ``model_type: laguna``): forty layers whose two kinds
    of mixer are the SAME part, ``GroupedAttention``, at two settings, in
    the order of a published list (``layer_types``) with the query heads
    of a second (``num_attention_heads_per_layer``; ``heads_per_layer``
    None reads 48 for a full and 64 for a sliding layer, which is that
    list): ``"full_attention"`` sees the whole causal half and turns the
    first ``full_rotary_fraction`` of each head by YaRN
    (``parts.yarn_frequencies``; cos and sin times
    ``yarn_attention_factor``); ``"sliding_attention"`` sees the ``window``
    keys ``0 <= i - j < window`` and turns the whole head by plain rotary
    at ``window_theta`` (both through ``parts.rotary``).
    Every layer has ``kv_heads`` key/value heads of ``head_dim``, no norm
    on q or k, and (``gating``) one sigmoid gate a query head from the
    layer's input. The feed-forward part by a third
    list (``mlp_layer_types``): ``"dense"`` a ``SwiGluMlp`` of
    ``mlp_hidden``, ``"sparse"`` a ``DroplessMoe`` routed by sigmoid
    scores over all ``num_experts`` (DeepSeek-V3's rule, as
    ``glm4_moe_lite``: the choice on score plus bias, the weights over
    their sum times ``scaling``, on the experts' OUTPUT) beside one
    ungated shared expert of ``shared_hidden``. Plain RMS norms, untied
    tables. ``experts_held`` = ``(count, first)`` as for ``glm4_moe_lite``;
    ``gate_grad=False`` for a share trained without its exchange
    (``DroplessMoe``).

    Each block is recomputed whole and keeps all of ``_remat_block``'s
    names: here the kernels' residuals, the SwiGLU products of the dense
    part and the shared experts, and every layer's q, k and v rows before
    the turn and the repeat (1.54 GB a step at the benchmark's cut, for
    18.9 ms of products not made twice: the step's mark 15.10 -> 16.02 GB
    of 16.91, PERF.md section 6, PR 46; what a block still makes again is
    the gate a head, the turn, the repeat and the output projection's
    input)."""
    held = None if experts_held is None else tuple(experts_held)
    kinds = tuple(layer_types)
    heads = tuple(LAGUNA_XS2_HEADS.get(k) for k in kinds) \
        if heads_per_layer is None else tuple(heads_per_layer)
    feeds = tuple(mlp_layer_types)
    if not (len(kinds) == len(heads) == len(feeds)):
        raise ValueError(f"{len(kinds)} layer_types, {len(heads)} head "
                         f"counts, {len(feeds)} mlp_layer_types")
    yarn = yarn_frequencies(
        int(head_dim * full_rotary_fraction), full_theta, yarn_factor,
        yarn_original, yarn_beta_fast, yarn_beta_slow)

    def attention(h, **positions):
        return lambda n: GroupedAttention(
            dim, h, kv_heads, head_dim, None, dtype, attention_fn,
            head_gate=head_gate, name=n, **positions)

    # the kind's setting, then the layer's head count
    settings = _by_kind(kinds, {
        "full_attention": dict(rotary_freqs=yarn,
                               rotary_factor=yarn_attention_factor),
        "sliding_attention": dict(
            rotary_freqs=plain_frequencies(head_dim, window_theta),
            window=window)})

    def dense(n):
        return SwiGluMlp(dim, mlp_hidden, dtype, name=n)

    def routed(n):
        return DroplessMoe(
            dim, num_experts, expert_hidden, top_k, experts_held=held,
            scaling=scaling, shared=lambda m: SwiGluMlp(
                dim, shared_hidden, dtype, name=m), dtype=dtype,
            gate_grad=gate_grad, name=n)

    return _spec(Decoder(
        vocab, dim,
        tuple(attention(h, **kw) for h, kw in zip(heads, settings)),
        _by_kind(feeds, {"dense": dense, "sparse": routed}), _rms(eps),
        dtype=dtype), max_len)


_LAGUNA_TINY = dict(vocab=96, dim=32,
                    layer_types=("full_attention", "sliding_attention",
                                 "sliding_attention", "full_attention"),
                    heads_per_layer=(2, 4, 4, 2),
                    mlp_layer_types=("dense", "sparse", "sparse", "sparse"),
                    kv_heads=2, head_dim=8, window=8, mlp_hidden=48,
                    expert_hidden=8, shared_hidden=8, num_experts=8, top_k=2,
                    yarn_factor=4.0, yarn_original=8, yarn_beta_fast=4.0,
                    max_len=64, dtype=jnp.float32)


@register_model("laguna_tiny")
def laguna_tiny(**overrides):
    """Test-scale ``laguna`` (float32, so CPU parity is tight): a leading
    dense layer under a full layer, then sliding, sliding, full under
    routed layers of eight experts, two a token; unequal head counts, a
    window shorter than the rows, YaRN past its original length."""
    return laguna(**{**_LAGUNA_TINY, **overrides})


@register_model("sdar_moe")
def sdar_moe(vocab: int = 151936, dim: int = 2048, depth: int = 48,
             heads: int = 32, kv_heads: int = 4, head_dim: int = 128,
             expert_hidden: int = 768, num_experts: int = 128,
             top_k: int = 8, experts_held=None, block_length: int = 4,
             gate_grad: bool = True, theta: float = 1e6, eps: float = 1e-6,
             max_len: int = 8192, dtype=jnp.bfloat16, attention_fn=None):
    """SDAR-30B-A3B-Chat as published (huggingface.co/JetLM/
    SDAR-30B-A3B-Chat ``config.json``, ``model_type: sdar_moe``; SDAR,
    arXiv:2510.06303), on the TRAINING path of block diffusion (BD3-LM,
    arXiv:2503.09573): ``depth`` equal layers, each a ``GroupedAttention``
    (``heads`` query heads over ``kv_heads`` of ``head_dim``, an RMS norm
    over each q and k head, plain rotary on the whole head) under a
    ``DroplessMoe`` routed by softmax over all ``num_experts``, the top
    ``top_k`` weighted by their scores over their sum, NO shared expert;
    plain RMS norms, untied tables. It is Qwen3-MoE's block; what makes it
    this family is the row: the ``Decoder`` is called on ``(rows, 2 L)``
    ids ``[noised copy | clean copy]`` (``max_len`` = ``2 L``), both copies
    of a token at its position, and every mixer sees the block-diffusion
    mask at ``block_length`` (``GroupedAttention(block_diffusion=)``,
    which turns by ``parts.rotary(positions=)`` and calls
    ``parallel/sequence.full_attention(block_diffusion=(L, B))``: within a
    block the noised copy attends in both directions, across blocks the
    model is autoregressive over the clean copy). The loss is
    ``train/lm_loss.masked_diffusion_loss`` on the noised half's rows; the
    noise is the input pipeline's. ``experts_held`` = ``(count, first)`` as
    for ``glm4_moe_lite``; ``gate_grad=False`` for a share trained without
    its exchange (``DroplessMoe``). Each block is recomputed whole and
    keeps all of ``_remat_block``'s names: here the kernels' residuals
    and the q, k and v rows."""
    held = None if experts_held is None else tuple(experts_held)

    def attention(n):
        return GroupedAttention(
            dim, heads, kv_heads, head_dim, None, dtype, attention_fn, eps,
            norm_heads=True, rotary_freqs=plain_frequencies(head_dim, theta),
            block_diffusion=block_length, name=n)

    def routed(n):
        return DroplessMoe(
            dim, num_experts, expert_hidden, top_k, experts_held=held,
            dtype=dtype, scores="softmax", gate_grad=gate_grad, name=n)

    return _spec(Decoder(vocab, dim, (attention,) * depth, (routed,) * depth,
                         _rms(eps), dtype=dtype), max_len)


_SDAR_TINY = dict(vocab=96, dim=32, depth=2, heads=4, kv_heads=2, head_dim=8,
                  expert_hidden=16, num_experts=8, top_k=2, block_length=4,
                  max_len=64, dtype=jnp.float32)


@register_model("sdar_moe_tiny")
def sdar_moe_tiny(**overrides):
    """Test-scale ``sdar_moe`` (float32, so CPU parity is tight): two
    layers of eight experts, two a token, rows of two copies of 32
    positions in blocks of 4."""
    return sdar_moe(**{**_SDAR_TINY, **overrides})


@register_model("keye_vl2")
def keye_vl2(vocab: int = 151936, dim: int = 2048, depth: int = 48,
             heads: int = 32, kv_heads: int = 4, head_dim: int = 128,
             index_heads: int = 16, index_head_dim: int = 64,
             index_top_k: int = 2048, expert_hidden: int = 768,
             num_experts: int = 128, top_k: int = 8, experts_held=None,
             gate_grad: bool = True, theta: float = 1e7, eps: float = 1e-6,
             max_len: int = 16384, dtype=jnp.bfloat16):
    """Keye-VL-2.0-30B-A3B's LANGUAGE MODEL as published (huggingface.co/
    Kwai-Keye/Keye-VL-2.0-30B-A3B ``config.json``, ``model_type: KeyeVL2``;
    the vision tower is not here, the model reads token ids): ``depth``
    equal layers of Qwen3-MoE's block, ``sdar_moe``'s to the letter (a
    ``GroupedAttention`` of ``heads`` query heads over ``kv_heads`` of
    ``head_dim``, an RMS norm over each q and k head, plain rotary on the
    whole head: on token ids the three streams of ``mrope_section`` are one
    and the sectioned turn is the plain one; a ``DroplessMoe`` routed by
    softmax over all ``num_experts``, the top ``top_k`` weighted by their
    scores over their sum, no shared expert; plain RMS norms, untied
    tables), in which every mixer's keys are CHOSEN (``sa_config``:
    DeepSeek Sparse Attention, arXiv:2512.02556): an ``Indexer`` of
    ``index_heads`` heads of ``index_head_dim`` over one key head, turned
    at the model's ``theta`` over its whole width, scores the past, and a
    query keeps its ``index_top_k`` best keys
    (``GroupedAttention(indexer=)``, ``ops/sparse_attention``). Under a
    plain causal next-token loss; each layer's indexer learns from its own
    term, which ``Decoder`` hands the loss as ``aux_loss``
    (``train/lm_loss.next_token_loss`` adds it), and takes no gradient from
    the language-model loss, as no other leaf takes any from that term.
    ``experts_held`` and ``gate_grad`` as ``sdar_moe``'s. Each block is
    recomputed whole and keeps all of ``_remat_block``'s names: here the
    selected core's residuals, the selection, and the q, k and v rows."""
    held = None if experts_held is None else tuple(experts_held)

    def attention(n):
        return GroupedAttention(
            dim, heads, kv_heads, head_dim, None, dtype, None, eps,
            norm_heads=True, rotary_freqs=plain_frequencies(head_dim, theta),
            indexer=(index_heads, index_head_dim, index_top_k,
                     plain_frequencies(index_head_dim, theta)), name=n)

    def routed(n):
        return DroplessMoe(
            dim, num_experts, expert_hidden, top_k, experts_held=held,
            dtype=dtype, scores="softmax", gate_grad=gate_grad, name=n)

    return _spec(Decoder(vocab, dim, (attention,) * depth, (routed,) * depth,
                         _rms(eps), dtype=dtype), max_len)


_KEYE_TINY = dict(vocab=96, dim=32, depth=2, heads=4, kv_heads=2, head_dim=8,
                  index_heads=2, index_head_dim=8, index_top_k=8,
                  expert_hidden=16, num_experts=8, top_k=2, max_len=64,
                  dtype=jnp.float32)


@register_model("keye_vl2_tiny")
def keye_vl2_tiny(**overrides):
    """Test-scale ``keye_vl2`` (float32, so CPU parity is tight): two
    layers of eight experts, two a token; an indexer of two heads of 8 that
    keeps 8 keys a query, fewer than the tests' rows hold."""
    return keye_vl2(**{**_KEYE_TINY, **overrides})


KIMI_LINEAR_FULL_LAYERS = (4, 8, 12, 16, 20, 24, 27)
KIMI_LINEAR_KDA_LAYERS = tuple(
    l for l in range(1, 28) if l not in KIMI_LINEAR_FULL_LAYERS)


@register_model("kimi_linear")
def kimi_linear(vocab: int = 163840, dim: int = 2304,
                kda_layers=KIMI_LINEAR_KDA_LAYERS,
                full_attn_layers=KIMI_LINEAR_FULL_LAYERS, heads: int = 32,
                kv_rank: int = 512, nope: int = 128, rope: int = 64,
                v_dim: int = 128, linear_heads: int = 32,
                linear_head_dim: int = 128, conv_width: int = 4,
                mlp_hidden: int = 9216, expert_hidden: int = 1024,
                shared_hidden: int = 1024, num_experts: int = 256,
                top_k: int = 8, experts_held=None, scaling: float = 2.446,
                dense_layers: int = 1, gate_grad: bool = True,
                eps: float = 1e-5, chunk: int = 64, max_len: int = 16384,
                dtype=jnp.bfloat16, attention_fn=None):
    """Kimi-Linear-48B-A3B-Instruct as published (huggingface.co/moonshotai/
    Kimi-Linear-48B-A3B-Instruct ``config.json``, ``model_type:
    kimi_linear``; "Kimi Linear", arXiv:2510.26692): twenty-seven layers
    numbered from 1 in two published lists, a ``KimiDeltaAttention`` (the
    delta rule with a decay a key channel, 32 heads of 128 x 128) at
    ``kda_layers`` and at ``full_attn_layers`` a ``MlaAttention`` with no
    query rank, no rotary turn (``mla_use_nope``: the KDA layers carry the
    order) and keys of ``nope + rope`` = 192 over values of 128: three to
    one. The feed-forward part is a dense ``SwiGluMlp`` in the first
    ``dense_layers`` layers and from there on a ``DroplessMoe`` routed by
    sigmoid scores over all ``num_experts`` (DeepSeek-V3's rule, as
    ``glm4_moe_lite`` and ``laguna``: the choice on score plus bias, the
    weights over their sum times ``scaling``, on the experts' OUTPUT)
    beside one ungated shared expert of ``shared_hidden``. Plain RMS
    norms, untied tables, no multi-token-prediction module
    (``num_nextn_predict_layers`` 0). ``experts_held`` = ``(count, first)``
    as for ``glm4_moe_lite``; ``gate_grad=False`` for a share trained
    without its exchange (``DroplessMoe``).

    Each block is recomputed in halves and keeps of ``_remat_block``'s
    names the flash kernel's residuals alone; let go (``let_go``, as
    ``olmo_hybrid`` does): the KDA chunk calls' tiles, the KDA layers'
    three projections and the SwiGLU products. At the benchmark's cut,
    602M parameters (7.23 GB of weights and moments) under ONE row of
    16,384 tokens, the step's
    high-water mark is the backward pass of the FIRST routed layer, whose
    full-size rung holds 131,072 slots of 2,304 in float32 three times
    over (5.3 GB) beside every later layer's gradient, and what the blocks
    before it keep lies under it. Compiled for a described v5e (16.91 GB):
    23.46 GB with every name kept (refused), 17.11 without the tiles
    (refused), 16.94 without the SwiGLU products too (refused), 16.27
    without the projections as well, 16.14 with no name at all (PERF.md
    section 6, PR 51). What a recomputed KDA block therefore makes AGAIN in
    its backward pass: the three projections, each one's pass through
    ``linear_attention.conv_silu_norm`` (at these widths one Pallas call a
    projection, ``ops/pallas_conv_norm.py``: 24 forward calls a step of
    four layers and 12 backward ones, whose residuals are the projections'
    rows and so made again too), the decay's gate and running sum, and
    ``kda_chunk_fwd`` (PERF.md section 6, PR 52)."""
    from mmlspark_tpu.ops.pallas_delta_rule import DELTA_CHUNK_TILES
    held = None if experts_held is None else tuple(experts_held)
    kda, full = set(kda_layers), set(full_attn_layers)
    depth = len(kda) + len(full)
    if kda & full or kda | full != set(range(1, depth + 1)):
        raise ValueError(
            f"kda_layers {tuple(kda_layers)!r} and full_attn_layers "
            f"{tuple(full_attn_layers)!r}: each of the layers 1 to "
            f"{depth} in one of them")

    def attention(n):
        return MlaAttention(dim, heads, None, kv_rank, nope, rope, v_dim,
                            eps=eps, dtype=dtype, attention_fn=attention_fn,
                            turn=False, name=n)

    def linear(n):
        return KimiDeltaAttention(dim, linear_heads, linear_head_dim,
                                  conv_width, eps, chunk, dtype, name=n)

    def dense(n):
        return SwiGluMlp(dim, mlp_hidden, dtype, name=n)

    def routed(n):
        return DroplessMoe(
            dim, num_experts, expert_hidden, top_k, experts_held=held,
            scaling=scaling, shared=lambda m: SwiGluMlp(
                dim, shared_hidden, dtype, name=m), dtype=dtype,
            gate_grad=gate_grad, name=n)

    return _spec(Decoder(
        vocab, dim, _by_kind(
            ("kda" if l in kda else "mla" for l in range(1, depth + 1)),
            {"kda": linear, "mla": attention}),
        tuple(dense if l < dense_layers else routed for l in range(depth)),
        _rms(eps), split=True,
        let_go=(DELTA_CHUNK_TILES, DELTA_NET_QKVZ, MLP_GATE_UP),
        dtype=dtype), max_len)


_KIMI_TINY = dict(vocab=96, dim=32, kda_layers=(1, 2, 3, 5),
                  full_attn_layers=(4,), heads=2, kv_rank=16, nope=8, rope=4,
                  v_dim=8, linear_heads=2, linear_head_dim=8, mlp_hidden=48,
                  expert_hidden=8, shared_hidden=8, num_experts=8, top_k=2,
                  chunk=8, max_len=64, dtype=jnp.float32)


@register_model("kimi_linear_tiny")
def kimi_linear_tiny(**overrides):
    """Test-scale ``kimi_linear`` (float32, so CPU parity is tight): the
    benchmark's cut at toy widths, a leading dense layer under a KDA mixer,
    then KDA, KDA, latent, KDA under routed layers of eight experts, two a
    token; keys of 12 over values of 8, chunks of 8 tokens."""
    return kimi_linear(**{**_KIMI_TINY, **overrides})
