"""ViT-B/16 — target of the fused-Pallas-preprocessing config
(BASELINE.json config 5) and the long-context flagship: every encoder block
takes a pluggable ``attention_fn(q, k, v, causal=...)`` over (B, L, H, D),
the hook through which the sequence-parallel/ring attention implementations
in ``mmlspark_tpu.parallel`` are swapped in for long inputs. The default is
``parallel.sequence.full_attention``, which picks a fused kernel from the
shape (197 tokens: one short-sequence call per block).

Standard pre-norm ViT: patchify conv -> [CLS] -> encoder blocks
(MHA + MLP, GELU) -> head. bfloat16 compute, fp32 norms/logits.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from mmlspark_tpu.models.zoo import register_model
from mmlspark_tpu.parallel.sequence import full_attention


class MlpBlock(nn.Module):
    dim: int
    hidden: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        # the decoder families' name for this part (``SwiGluMlp``): one
        # key for the split of device time by part
        with jax.named_scope("ffn"):
            h = nn.Dense(self.hidden, dtype=self.dtype, name="mlp_up")(x)
            h = nn.gelu(h)
            return nn.Dense(self.dim, dtype=self.dtype, name="mlp_down")(h)


class SelfAttention(nn.Module):
    """Non-causal multi-head self-attention that owns its projections,
    under the parameter names and shapes of flax's
    ``nn.MultiHeadDotProductAttention`` (``query|key|value`` kernels
    ``[dim, heads, head_dim]``, ``out`` ``[heads, head_dim, dim]``), so
    checkpoints of either load into the other. q, k, v leave the
    projections as (B, L, H, D) and the output projection contracts (H, D)
    again: row-major that is (B, L, dim) on both sides, the layout the
    fused kernels work in, so no per-head layout copy is needed around
    them (XLA still transposes each operand once out of its own
    batch-minor layout: ``PERF.md`` section 7)."""
    dim: int
    heads: int
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        attn_fn = self.attention_fn or full_attention
        q, k, v = (nn.DenseGeneral((self.heads, self.dim // self.heads),
                                   dtype=self.dtype, name=name)(x)
                   for name in ("query", "key", "value"))
        o = attn_fn(q, k, v, causal=False)
        return nn.DenseGeneral(self.dim, axis=(-2, -1), dtype=self.dtype,
                               name="out")(o)


class EncoderBlock(nn.Module):
    dim: int
    heads: int
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None  # pluggable (ring attention)

    @nn.compact
    def __call__(self, x):
        y = nn.LayerNorm(dtype=jnp.float32, name="norm1")(x)
        x = x + SelfAttention(self.dim, self.heads, self.dtype,
                              self.attention_fn, name="attn")(y)
        y = nn.LayerNorm(dtype=jnp.float32, name="norm2")(x)
        x = x + MlpBlock(self.dim, self.dim * self.mlp_ratio, self.dtype,
                         name="mlp")(y)
        return x


class ViT(nn.Module):
    patch: int = 16
    dim: int = 768
    depth: int = 12
    heads: int = 12
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        B = x.shape[0]
        x = nn.Conv(self.dim, (self.patch, self.patch),
                    strides=(self.patch, self.patch), dtype=self.dtype,
                    name="patch_embedding")(x.astype(self.dtype))
        x = x.reshape(B, -1, self.dim)
        cls = self.param("cls", nn.initializers.zeros, (1, 1, self.dim),
                         jnp.float32)
        x = jnp.concatenate([jnp.broadcast_to(cls.astype(x.dtype),
                                              (B, 1, self.dim)), x], axis=1)
        pos = self.param("pos_embedding", nn.initializers.normal(0.02),
                         (1, x.shape[1], self.dim), jnp.float32)
        x = x + pos.astype(x.dtype)
        for i in range(self.depth):
            x = EncoderBlock(self.dim, self.heads, dtype=self.dtype,
                             attention_fn=self.attention_fn,
                             name=f"block{i}")(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="final_norm")(x)
        x = x[:, 0]
        self.sow("intermediates", "pool", x)
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)


@register_model("vit_b16")
def vit_b16(num_classes: int = 1000, image_size: int = 224,
            dtype=jnp.bfloat16, attention_fn=None):
    return dict(
        module=ViT(patch=16, dim=768, depth=12, heads=12,
                   num_classes=num_classes, dtype=dtype,
                   attention_fn=attention_fn),
        input_shape=(image_size, image_size, 3),
        feature_layer="pool", feature_dim=768,
        layer_names=["pool", "head"],
    )


@register_model("vit_tiny")
def vit_tiny(num_classes: int = 10, image_size: int = 32, patch: int = 4,
             dtype=jnp.bfloat16, attention_fn=None):
    """Small ViT for tests and CIFAR-scale experiments."""
    return dict(
        module=ViT(patch=patch, dim=192, depth=4, heads=3,
                   num_classes=num_classes, dtype=dtype,
                   attention_fn=attention_fn),
        input_shape=(image_size, image_size, 3),
        feature_layer="pool", feature_dim=192,
        layer_names=["pool", "head"],
    )
