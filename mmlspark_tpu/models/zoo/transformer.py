"""Decoder-only transformer LM — the long-context flagship.

The model family the sequence-parallel layer exists for: every block calls a
pluggable ``attention_fn(q, k, v, causal=...)`` so the same module runs
single-device (``full_attention``), context-parallel (``ring_attention``)
or all-to-all (``ulysses_attention``) — see ``parallel/sequence.py``.

Param names are chosen to hit the tensor-parallel sharding rules
(``parallel/sharding.DEFAULT_RULES``): ``attn_query/key/value`` kernels shard
(fsdp, tensor), ``attn_out`` (tensor, fsdp), ``mlp_up``/``mlp_down``
likewise, token embedding shards vocab over ``tensor``.

bfloat16 compute, fp32 norms and logits (MXU-friendly).
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax.numpy as jnp

from mmlspark_tpu.models.zoo import register_model
from mmlspark_tpu.parallel.sequence import full_attention


class DecoderBlock(nn.Module):
    """Pre-norm decoder block with pluggable attention AND FFN.

    ``ffn_factory(name) -> nn.Module`` swaps the dense MLP for a routed one
    (``zoo/moe.MoeMlp``) without duplicating the attention half — there is
    exactly one attention implementation to fix.
    """
    dim: int
    heads: int
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    ffn_factory: Optional[Callable[[str], nn.Module]] = None

    @nn.compact
    def __call__(self, x):
        B, L, _ = x.shape
        D = self.dim // self.heads
        # a block with no attention of its own takes its trunk's, so one
        # made by a ``block_factory`` needs nothing baked into it
        attn_fn = self.attention_fn \
            or getattr(self.parent, "attention_fn", None) or full_attention
        y = nn.LayerNorm(dtype=jnp.float32, name="norm1")(x)
        q = nn.Dense(self.dim, dtype=self.dtype, name="attn_query")(y)
        k = nn.Dense(self.dim, dtype=self.dtype, name="attn_key")(y)
        v = nn.Dense(self.dim, dtype=self.dtype, name="attn_value")(y)
        shape = (B, L, self.heads, D)
        o = attn_fn(q.reshape(shape), k.reshape(shape), v.reshape(shape),
                    causal=True)
        x = x + nn.Dense(self.dim, dtype=self.dtype,
                         name="attn_out")(o.reshape(B, L, self.dim))
        y = nn.LayerNorm(dtype=jnp.float32, name="norm2")(x)
        if self.ffn_factory is not None:
            return x + self.ffn_factory("ffn")(y)
        h = nn.Dense(self.dim * self.mlp_ratio, dtype=self.dtype,
                     name="mlp_up")(y)
        h = nn.gelu(h)
        x = x + nn.Dense(self.dim, dtype=self.dtype, name="mlp_down")(h)
        return x


class TransformerLM(nn.Module):
    """Decoder LM trunk. ``block_factory(layer_idx, name) -> nn.Module``
    customizes individual layers (e.g. MoE FFNs on odd layers) while the
    embedding / positional / tied-head plumbing stays in one place."""
    vocab: int = 32000
    dim: int = 512
    depth: int = 6
    heads: int = 8
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    block_factory: Optional[Callable[[int, str], nn.Module]] = None

    @nn.compact
    def __call__(self, tokens, positions=None):
        """tokens (B, L) int32 -> logits (B, L, vocab) fp32. ``positions``
        (B, L) int32 says where in its sequence each token stands (the
        generate lane's steps start mid-sequence); None is ``0..L-1``."""
        B, L = tokens.shape
        emb = nn.Embed(self.vocab, self.dim, dtype=self.dtype,
                       name="token_embedding")
        x = emb(tokens)
        pos = self.param("pos_embedding", nn.initializers.normal(0.02),
                         (1, self.max_len, self.dim), jnp.float32)
        if positions is None:
            x = x + pos[:, :L].astype(x.dtype)
        else:
            x = x + jnp.take(pos[0], positions, axis=0).astype(x.dtype)
        for i in range(self.depth):
            if self.block_factory is not None:
                block = self.block_factory(i, f"block{i}")
            else:
                block = DecoderBlock(self.dim, self.heads, dtype=self.dtype,
                                     attention_fn=self.attention_fn,
                                     name=f"block{i}")
            x = block(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="final_norm")(x)
        self.sow("intermediates", "hidden", x)
        # tied head, explicitly fp32 (Embed.attend would demote to self.dtype)
        table = self.get_variable("params", "token_embedding")["embedding"]
        return jnp.einsum("bld,vd->blv", x.astype(jnp.float32),
                          table.astype(jnp.float32))


@register_model("transformer_lm")
def transformer_lm(vocab: int = 32000, dim: int = 512, depth: int = 6,
                   heads: int = 8, max_len: int = 2048,
                   dtype=jnp.bfloat16, attention_fn=None):
    return dict(
        module=TransformerLM(vocab=vocab, dim=dim, depth=depth, heads=heads,
                             max_len=max_len, dtype=dtype,
                             attention_fn=attention_fn),
        input_shape=(max_len,), input_dtype="int32",
        feature_layer="hidden", feature_dim=dim,
        layer_names=["hidden", "logits"],
        # decoder blocks use the (q, k, v, causal) attention contract, so
        # the ring/Ulysses kernels can be swapped in for seq-parallel runs
        seq_attention=True,
    )


@register_model("transformer_lm_tiny")
def transformer_lm_tiny(vocab: int = 256, dim: int = 64, depth: int = 2,
                        heads: int = 4, max_len: int = 128,
                        dtype=jnp.float32, attention_fn=None):
    """Test-scale LM (fp32 so CPU-mesh parity checks are tight)."""
    return dict(
        module=TransformerLM(vocab=vocab, dim=dim, depth=depth, heads=heads,
                             max_len=max_len, dtype=dtype,
                             attention_fn=attention_fn),
        input_shape=(max_len,), input_dtype="int32",
        feature_layer="hidden", feature_dim=dim,
        layer_names=["hidden", "logits"],
        # decoder blocks use the (q, k, v, causal) attention contract, so
        # the ring/Ulysses kernels can be swapped in for seq-parallel runs
        seq_attention=True,
    )
