"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The long-context capability the task brief makes first-class (the reference
predates attention entirely — SURVEY.md §5 "Long-context / sequence
parallelism: absent"), built the TPU way:

- **Ring attention** (`ring_attention`): Q stays put; K/V blocks rotate
  around the ``seq`` mesh axis via ``ppermute`` (one ICI hop per step) while
  each device accumulates its queries' attention with the online-softmax
  (flash) recurrence. Peak memory per device is O(L_local^2) and the K/V
  transfer overlaps compute on real ICI. Blockwise-parallel-transformer /
  RingAttention pattern (Liu et al. 2023), PAPERS.md.
- **Ulysses** (`ulysses_attention`): two ``all_to_all``s swap the sharded
  axis sequence<->heads so each device computes FULL-sequence attention for
  a head subset. Cheaper at moderate L (2 collectives instead of S ppermute
  steps) but requires heads % seq_axis_size == 0.

Both are drop-in ``attention_fn`` implementations for
``models/zoo/transformer.py`` and differentiate through ``shard_map``
(ppermute's transpose is the reverse ppermute, so the backward pass is a
ring in the opposite direction — no custom VJP needed).

Shapes follow the framework convention (B, L, H, D) with L sharded over the
``seq`` axis at the boundary (``sharding.batch_sharding(seq_axis=...)``).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from mmlspark_tpu.observability import metrics as obsmetrics
from mmlspark_tpu.parallel.mesh import ambient_mesh
from mmlspark_tpu.parallel.sharding import active_batch_axes


def _on_chip() -> bool:
    return jax.default_backend() != "cpu"


def _reference_attention(q, k, v, causal: bool,
                         window: Optional[int] = None,
                         block_diffusion: Optional[Tuple[int, int]] = None):
    """Plain attention: matmuls in the input dtype (bf16 tiles the MXU);
    scores, softmax and the output accumulation in fp32, cast back once
    at the end. The L x L scores go through HBM. With ``window`` a query
    at ``i`` sees the keys ``j`` with ``0 <= i - j < window``; with
    ``block_diffusion`` those of ``pallas_attention.block_diffusion_mask``."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("blhd,bkhd->bhlk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if block_diffusion is not None:
        from mmlspark_tpu.ops.pallas_attention import block_diffusion_mask
        s = jnp.where(block_diffusion_mask(*block_diffusion), s, -jnp.inf)
    elif causal:
        L, K = s.shape[-2], s.shape[-1]
        mask = jnp.arange(K)[None, :] > jnp.arange(L)[:, None]
        if window is not None:
            mask = mask | (jnp.arange(L)[:, None] - jnp.arange(K)[None, :]
                           >= window)
        s = jnp.where(mask, -jnp.inf, s)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhlk,bkhd->blhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _per_device(fn, mesh: Mesh, rows, whole, heads=()):
    """``fn(*rows, *whole, *heads)`` shard_mapped so that each device runs
    it on its own batch rows (and its own heads on a tensor axis) of every
    ``(B, L, H, D)`` array of ``rows``, each array of ``whole`` whole on
    every device, and of each ``(..., H, D)`` array of ``heads`` (a
    parameter a head: no batch) the heads the device holds of the rows.
    Attention, and what is made of a head before it, is independent across
    batch and heads, so this needs no collective, but a Pallas kernel is an
    opaque custom call the SPMD partitioner cannot split: bare inside a
    multi-device jit it is refused, or all-gathered to run every (batch,
    head) on every chip."""
    specs = tuple(_qkv_spec(mesh, None, r.shape[2]) for r in rows)
    everywhere = P()  # lint: allow-spec (shard_map spec)
    a_head = tuple(
        P(*(None,) * (h.ndim - 2), specs[0][2], None)  # lint: allow-spec
        for h in heads)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=specs + (everywhere,) * len(whole) + a_head,
        out_specs=specs[0], check_vma=False)


def _mesh_to_map() -> Optional[Mesh]:
    """The mesh of the enclosing ``with mesh:`` block where a kernel has
    to be shard_mapped over it; None outside a mesh, on one device, and
    inside a ``shard_map`` body (the operands are one device's already)."""
    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1 \
            or jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return mesh


def own_shape(shape) -> Optional[Tuple[int, ...]]:
    """The part of ``(B, L, H, D)`` rows a device holds where
    ``on_own_rows`` runs a kernel on them (all of it, for a bare call);
    None when the batch does not split over the mesh's batch axes."""
    mesh = _mesh_to_map()
    if mesh is None:
        return tuple(shape)
    batch = math.prod(
        mesh.shape[a] for a in active_batch_axes(mesh) or ())
    if shape[0] % batch:
        return None
    heads = mesh.shape["tensor"] if _qkv_spec(mesh, None, shape[2])[2] \
        else 1
    return (shape[0] // batch, shape[1], shape[2] // heads, shape[3])


def on_own_rows(kernel, *rows, whole=(), heads=()):
    """``kernel(*rows, *whole, *heads)`` per device (``_per_device``) over
    the mesh of the enclosing ``with mesh:`` block, or None when the batch
    does not split over that mesh (``own_shape``); where no mesh is to be
    mapped over (``_mesh_to_map``), the bare call."""
    mesh = _mesh_to_map()
    if mesh is None:
        return kernel(*rows, *whole, *heads)
    if own_shape(rows[0].shape) is None:
        return None
    return _per_device(kernel, mesh, rows, whole, heads)(
        *rows, *whole, *heads)


def full_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   causal: bool = True,
                   use_flash: str = "auto",
                   window: Optional[int] = None,
                   block_diffusion: Optional[Tuple[int, int]] = None
                   ) -> jnp.ndarray:
    """Attention (B, L, H, D) with the whole sequence on each device.

    The one place that picks the implementation, from the shape alone
    (``ops/pallas_attention.py``): a length the flash kernel ``supports``
    (multiples of 256 from 512 up) streams K/V blocks through it; a
    sequence whose one block fits VMEM (``supports_short``: ViT's 197
    tokens, short prompts) takes the short kernel, one fused call forward
    and one backward; in both the L x L scores never touch HBM. Whatever
    neither takes, and every shape on the CPU, runs the jnp reference.
    Under a multi-device ``with mesh:`` the kernel runs shard_mapped on
    each device's own batch rows (``on_own_rows``).

    ``use_flash``: "auto" | "never" (reference path, used by the parity
    tests themselves) | "require" (a fused kernel or a ValueError, on the
    CPU in interpret mode — for callers whose result is only meaningful
    on a kernel: the ``longctx`` bench lane, ``chip_smoke.py``). Every
    trace increments ``attention.fused_calls.<short|flash|window|
    block_diffusion|reference>``; under "auto" on an accelerator a trace that
    takes the reference also increments ``attention.flash_fallbacks``, so the
    downgrade is visible in metrics and reports.

    ``window`` (causal only): a query at ``i`` sees the keys ``j`` with
    ``0 <= i - j < window``; a window of the whole row or more is the
    plain causal call. The flash kernel takes a band (its calls are then
    named ``window_attention_fwd`` / ``_bwd`` and counted under
    ``.window``); the short kernel does not, so a windowed shape the
    flash kernel refuses runs the masked reference and counts as a
    fallback like any other.

    ``block_diffusion`` = ``(L, B)`` (causal only, no window): the row is
    ``[noised copy | clean copy]`` of ``L`` positions each in blocks of
    ``B``; a noised query sees the clean keys of the blocks before its own
    and the noised keys of its own block, a clean query the clean keys up
    to its own block's end (``pallas_attention.block_diffusion_mask``).
    The flash kernel takes it where ``supports_block_diffusion`` says (its
    calls are then named ``block_diffusion_attention_fwd`` / ``_bwd`` and
    counted under ``.block_diffusion``); elsewhere the reference masks the
    dense product, a fallback like any other.
    """
    if use_flash not in ("auto", "never", "require"):
        raise ValueError(f"unknown use_flash {use_flash!r}")
    if window is not None:
        from mmlspark_tpu.ops.pallas_attention import band
        window = band(window, causal, q.shape[1])
    if block_diffusion is not None:
        from mmlspark_tpu.ops.pallas_attention import diffusion_blocks
        block_diffusion = diffusion_blocks(block_diffusion, causal, window,
                                           q.shape[1])
    if use_flash == "require" or (use_flash == "auto" and _on_chip()):
        from mmlspark_tpu.ops import pallas_attention
        name = kernel = None
        if block_diffusion is not None:
            if pallas_attention.supports_block_diffusion(
                    q.shape, block_diffusion):
                name, kernel = "block_diffusion", partial(
                    pallas_attention.flash_attention,
                    block_diffusion=block_diffusion)
        elif pallas_attention.supports(q.shape, v_dim=v.shape[-1],
                                       itemsize=q.dtype.itemsize):
            name, kernel = "flash", pallas_attention.flash_attention
            if window is not None:
                name, kernel = "window", partial(kernel, window=window)
        elif window is None and pallas_attention.supports_short(
                q.shape, q.dtype.itemsize):
            name, kernel = "short", pallas_attention.short_attention
        out = None if kernel is None else on_own_rows(
            lambda q, k, v: kernel(q, k, v, causal), q, k, v)
        if out is not None:
            obsmetrics.counter(f"attention.fused_calls.{name}").inc()
            return out
        if use_flash == "require":
            raise ValueError(
                f"use_flash='require': no fused kernel takes q shape "
                f"{tuple(q.shape)} here (pallas_attention.supports, "
                f"supports_short; the batch must split over the mesh)")
        obsmetrics.counter("attention.flash_fallbacks").inc()
    obsmetrics.counter("attention.fused_calls.reference").inc()
    return _reference_attention(q, k, v, causal, window, block_diffusion)


# ---------------------------------------------------------------------------
def _ring_attention_local(q, k, v, axis_name: str, causal: bool):
    """Per-device body: accumulate over rotating K/V blocks (online softmax)."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    scale = 1.0 / np.sqrt(D)
    q_pos = my_idx * Lq + jnp.arange(Lq)                   # global positions

    def step(carry, i):
        # accumulators (o, m, l) live in fp32 — bf16 rounding would compound
        # across ring steps (flash-attention convention); k/v stay in the
        # input dtype so the rotating transfers and matmuls remain cheap
        o, m, l, k, v = carry
        owner = (my_idx - i) % axis_size                   # whose block is here
        s = jnp.einsum("blhd,bkhd->bhlk", q, k,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            k_pos = owner * Lk + jnp.arange(Lk)
            mask = k_pos[None, :] > q_pos[:, None]          # (Lq, Lk)
            s = jnp.where(mask[None, None], -jnp.inf, s)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # exp(-inf - -inf) guard: fully-masked rows keep m=-inf, p=0
        p = jnp.exp(s - jnp.where(jnp.isinf(m_new), 0.0, m_new)[..., None])
        p = jnp.where(jnp.isinf(s), 0.0, p)
        corr = jnp.exp(jnp.where(jnp.isinf(m), 0.0, m)
                       - jnp.where(jnp.isinf(m_new), 0.0, m_new))
        corr = jnp.where(jnp.isinf(m), 0.0, corr)
        l_new = l * corr + p.sum(axis=-1)
        o_new = (o * corr[..., None]
                 + jnp.einsum("bhlk,bkhd->bhld", p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32))
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        return (o_new, m_new, l_new, k, v), None

    o0 = jnp.zeros((B, H, Lq, D), jnp.float32)
    m0 = jnp.full((B, H, Lq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)
    (o, m, l, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(axis_size))
    out = o / jnp.maximum(l, 1e-30)[..., None]             # (B,H,Lq,D)
    return out.astype(q.dtype).transpose(0, 2, 1, 3)       # (B,Lq,H,D)


def _qkv_spec(mesh: Mesh, seq_axis: Optional[str], n_heads: int) -> P:
    """(B, L, H, D) spec: batch over data axes, L over seq, and — when the
    head count divides it — H over ``tensor``, so a tp x sp mesh keeps the
    tensor-sharded qkv projections sharded through attention instead of
    all-gathering and redundantly computing every head per tensor shard."""
    batch = active_batch_axes(mesh)
    t = mesh.shape.get("tensor", 1)
    head = "tensor" if t > 1 and n_heads % t == 0 else None
    return P(batch, seq_axis, head, None)  # lint: allow-spec (shard_map spec)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mesh: Mesh, seq_axis: str = "seq",
                   causal: bool = True) -> jnp.ndarray:
    """Context-parallel attention; (B, L, H, D) with L sharded over seq_axis."""
    if mesh.shape.get(seq_axis, 1) == 1:
        return full_attention(q, k, v, causal)
    spec = _qkv_spec(mesh, seq_axis, q.shape[2])
    fn = jax.shard_map(
        partial(_ring_attention_local, axis_name=seq_axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
def _ulysses_local(q, k, v, axis_name: str, causal: bool):
    """all_to_all seq<->heads, full-sequence attention on a head subset."""
    a2a = partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    # (B, L/s, H, D) -> (B, L, H/s, D): gather sequence, scatter heads
    q, k, v = (a2a(x, split_axis=2, concat_axis=1) for x in (q, k, v))
    o = full_attention(q, k, v, causal)
    # back: (B, L, H/s, D) -> (B, L/s, H, D)
    return a2a(o, split_axis=1, concat_axis=2)


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      mesh: Mesh, seq_axis: str = "seq",
                      causal: bool = True) -> jnp.ndarray:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses pattern).

    Requires n_heads divisible by the seq axis size.
    """
    s = mesh.shape.get(seq_axis, 1)
    if s == 1:
        return full_attention(q, k, v, causal)
    spec = _qkv_spec(mesh, seq_axis, q.shape[2])
    # the all_to_all splits the LOCAL head count (after any tensor sharding)
    local_heads = q.shape[2] // (mesh.shape.get("tensor", 1)
                                 if spec[2] == "tensor" else 1)
    if local_heads % s:
        raise ValueError(
            f"ulysses needs per-shard heads ({local_heads}) divisible by "
            f"|{seq_axis}|={s}")
    fn = jax.shard_map(
        partial(_ulysses_local, axis_name=seq_axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def sharded_full_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           mesh: Mesh, causal: bool = True,
                           use_flash: str = "auto") -> jnp.ndarray:
    """``full_attention`` per device (``_per_device``) over an explicit
    ``mesh``: what ``full_attention`` does by itself under a ``with
    mesh:`` block, for callers that hold the mesh and no such block."""
    return _per_device(
        partial(full_attention, causal=causal, use_flash=use_flash),
        mesh, (q, k, v), ())(q, k, v)


def make_attention_fn(mesh: Optional[Mesh], impl: str = "auto",
                      seq_axis: str = "seq"):
    """attention_fn factory for TransformerLM: 'full' | 'ring' | 'ulysses' |
    'auto' (ring when the mesh has a non-trivial seq axis)."""
    if impl == "auto":
        impl = ("ring" if mesh is not None
                and mesh.shape.get(seq_axis, 1) > 1 else "full")
    if impl == "full":
        if mesh is None or mesh.size == 1:
            return full_attention
        return partial(sharded_full_attention, mesh=mesh)
    if impl == "ring":
        return partial(ring_attention, mesh=mesh, seq_axis=seq_axis)
    if impl == "ulysses":
        return partial(ulysses_attention, mesh=mesh, seq_axis=seq_axis)
    raise ValueError(f"unknown attention impl {impl!r}")
