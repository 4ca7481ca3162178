"""Pipeline parallelism over the ``pipe`` mesh axis.

GPipe-style microbatch schedule, expressed the TPU way: every pipe rank
holds ONE stage's params (a stacked pytree sharded over ``pipe``), and a
single ``lax.scan`` of M + S - 1 ticks moves activations rank->rank with
``ppermute`` — a neighbor ICI hop per tick, no host involvement. The whole
schedule is one XLA program; reverse-mode AD differentiates through it
(ppermute's transpose is the reverse permute), so the backward pass is the
mirrored pipeline automatically.

Constraints (standard for pipelined transformer stacks):
- every stage maps activations to the SAME shape (embed/head layers belong
  outside the pipelined region);
- global batch must divide into ``n_microbatches`` equal microbatches.

Bubble fraction is (S-1)/(M+S-1): choose n_microbatches >= 4*|pipe| to keep
it small.

Composes with the other axes: batch stays sharded over data/fsdp inside the
shard_map; tensor/seq parallel can live inside ``stage_fn``.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from mmlspark_tpu.parallel.sharding import active_batch_axes


def stack_stage_params(params_list: Sequence[Any]) -> Any:
    """Per-stage param pytrees -> one pytree with a leading stage dim."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params_list)


def init_stage_params(stage_init: Callable[[jax.Array, int], Any],
                      n_stages: int, rng: jax.Array) -> Any:
    """Initialize S stages with distinct keys; returns the stacked pytree.

    ``stage_init(key, stage_index) -> params`` for one stage.
    """
    keys = jax.random.split(rng, n_stages)
    return stack_stage_params(
        [stage_init(keys[i], i) for i in range(n_stages)])


def pipeline_spec(mesh: Mesh, pipe_axis: str = "pipe") -> P:
    """PartitionSpec for stacked stage params: stage dim over ``pipe``."""
    return P(pipe_axis)  # lint: allow-spec (shard_map axis local to this module)


def pipeline_apply(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stacked_params: Any, x: jnp.ndarray, mesh: Mesh,
                   n_microbatches: int, pipe_axis: str = "pipe") -> jnp.ndarray:
    """Run x through S pipelined stages; returns the last stage's output.

    stacked_params: pytree whose leaves have leading dim n_stages (sharded
    over ``pipe``); x: (B, ...) activations entering stage 0. n_stages may
    be any multiple of |pipe|: each rank chains its contiguous block of
    stages per tick (virtual-pipeline super-stages), so an 8-layer stack on
    a 4-rank pipe computes layers [0,1] -> [2,3] -> [4,5] -> [6,7].
    """
    S = mesh.shape.get(pipe_axis, 1)
    n_stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if n_stages % S:
        raise ValueError(
            f"stacked stage count {n_stages} must be a multiple of "
            f"|{pipe_axis}|={S}")
    if S == 1:
        def body(x, i):
            p = jax.tree_util.tree_map(lambda a: a[i], stacked_params)
            return stage_fn(p, x), None
        out, _ = jax.lax.scan(body, x, jnp.arange(n_stages))
        return out

    B = x.shape[0]
    M = n_microbatches
    batch = active_batch_axes(mesh)
    n_data_shards = int(np.prod([mesh.shape[a] for a in (batch or ())]))
    local_B = B // max(n_data_shards, 1)
    if B % max(n_data_shards, 1) or local_B % M:
        raise ValueError(
            f"per-data-shard batch {B}/{n_data_shards} must divide into "
            f"n_microbatches={M}")
    x_spec = P(batch)  # lint: allow-spec (shard_map in/out spec)

    k_local = n_stages // S  # stages chained per rank (virtual pipeline)

    def local(params, x):
        idx = jax.lax.axis_index(pipe_axis)
        mb = x.shape[0] // M
        xs = x.reshape((M, mb) + x.shape[1:])
        perm = [(i, i + 1) for i in range(S - 1)]
        zero = jnp.zeros_like(xs[0])

        def super_stage(params, x):
            def body(x, i):
                p = jax.tree_util.tree_map(lambda a: a[i], params)
                return stage_fn(p, x), None
            out, _ = jax.lax.scan(body, x, jnp.arange(k_local))
            return out

        def tick(carry, t):
            recv, acc = carry
            mb_idx = t - idx
            feed = xs[jnp.clip(mb_idx, 0, M - 1)]
            inp = jnp.where(idx == 0, feed, recv)
            out = super_stage(params, inp)
            active = (mb_idx >= 0) & (mb_idx < M)
            out = jnp.where(active, out, 0.0)
            # last rank banks each microbatch as it completes
            bank = jnp.where(active & (idx == S - 1), out, 0.0)
            acc = acc.at[jnp.clip(mb_idx, 0, M - 1)].add(bank)
            recv = jax.lax.ppermute(out, pipe_axis, perm)
            return (recv, acc), None

        acc0 = jnp.zeros_like(xs)
        (_, acc), _ = jax.lax.scan(
            tick, (zero, acc0), jnp.arange(M + S - 1))
        # outputs live on the last rank only: psum broadcasts them everywhere
        acc = jax.lax.psum(
            jnp.where(idx == S - 1, acc, jnp.zeros_like(acc)), pipe_axis)
        return acc.reshape(x.shape)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(pipeline_spec(mesh, pipe_axis), x_spec),
        out_specs=x_spec, check_vma=False)
    return fn(stacked_params, x)
