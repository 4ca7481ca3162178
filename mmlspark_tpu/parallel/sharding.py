"""Sharding rules: map pytrees of arrays onto the mesh.

GSPMD style: we annotate shardings with ``NamedSharding`` and let XLA insert
the collectives (psum for gradient allreduce over ``data``+``fsdp``,
all-gather/reduce-scatter for fsdp params, all-to-all for expert dispatch) —
the in-compiler replacement for the reference's explicit MPI ring
(``CommandBuilders.scala:73-93``).

Rules are name-pattern based (à la t5x/flax partitioning): a list of
(regex, PartitionSpec) tried in order against the '/'-joined param path.
"""
from __future__ import annotations

import re
from functools import lru_cache
from typing import Any, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Sequence[Tuple[str, P]]

# Default rules for transformer/conv models on a (data, fsdp, ..., tensor) mesh:
# - large matmul weights: shard output features over `tensor`, input over `fsdp`
# - embeddings: shard vocab over `tensor`
# - biases/norm scales: replicated
DEFAULT_RULES: List[Tuple[str, P]] = [
    # MoE expert banks: leading E dim over `expert` (the all-to-all axis),
    # hidden dims over fsdp/tensor like their dense counterparts. The router
    # stays replicated — it is tiny and every token needs it.
    (r".*experts?_(up|wi|gate).*", P("expert", "fsdp", "tensor")),
    (r".*experts?_(down|wo|out).*", P("expert", "tensor", "fsdp")),
    (r".*router.*", P()),
    (r".*(attention|attn).*(query|key|value|qkv).*kernel", P("fsdp", "tensor")),
    (r".*(attention|attn).*out.*kernel", P("tensor", "fsdp")),
    (r".*mlp.*(up|gate|wi|fc1|intermediate).*kernel", P("fsdp", "tensor")),
    (r".*mlp.*(down|wo|fc2|output).*kernel", P("tensor", "fsdp")),
    # nn.Embed LEAVES only (path ends in 'embedding'): a trailing-anywhere
    # match also caught conv kernels under layers NAMED *_embedding (ViT's
    # patch_embedding/kernel) and sharded their SPATIAL dim over `tensor`
    # — which XLA's SPMD partitioner has been observed to silently
    # miscompile on the CPU backend, and would at best buy halo exchanges
    (r".*embedding$", P("tensor", None)),
    (r".*(head|logits|classifier).*kernel", P("fsdp", "tensor")),
    (r".*kernel", P(None, "fsdp")),   # generic dense/conv: shard last-in dim
    (r".*", P()),                     # everything else replicated
]

# Catch-all patterns in DEFAULT_RULES whose 2-D specs must NOT be stretched
# onto >2-D conv kernels — those get the spatial-safe default instead. Only
# consulted when the DEFAULT rules are in effect; caller-supplied rules are
# authoritative as written.
_GENERIC_PATTERNS = {r".*kernel", r".*"}

def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):       # DictKey — falsy keys (0, '') included
            name = k.key
        elif hasattr(k, "name"):    # GetAttrKey
            name = k.name
        elif hasattr(k, "idx"):     # SequenceKey
            name = k.idx
        else:
            name = k
        parts.append(str(name))
    return "/".join(parts).lower()


def _fit_spec(spec: P, ndim: int, mesh: Mesh, shape) -> P:
    """Clamp a rule's PartitionSpec to the array's rank and divisibility.
    Axes the mesh doesn't carry count as size 1 (user-built meshes may
    name only the axes they use)."""
    entries = list(spec) + [None] * (ndim - len(spec))
    entries = entries[:ndim]
    fixed = []
    for dim, axis in zip(shape, entries):
        if axis is None:
            fixed.append(None)
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        size = np.prod([mesh.shape.get(a, 1) for a in axes])
        present = all(a in mesh.shape for a in axes)
        fixed.append(axis if present and size > 1 and dim % size == 0
                     else None)
    return P(*fixed)


def param_shardings(params: Any, mesh: Mesh,
                    rules: Optional[Rules] = None) -> Any:
    """NamedSharding pytree for model params using name-pattern rules."""
    using_defaults = rules is None
    rules = list(rules) if rules is not None else DEFAULT_RULES

    def conv_safe(ndim):
        # conv kernels (H, W, in, out) etc.: never shard spatial dims —
        # that buys halo collectives for nothing. Shard only the output
        # features (last dim) over fsdp when divisible.
        return P(*([None] * (ndim - 1) + ["fsdp"]))

    def assign(path, leaf):
        name = _path_str(path)
        ndim = getattr(leaf, "ndim", 0)
        shape = getattr(leaf, "shape", ())
        for pattern, spec in rules:
            if re.fullmatch(pattern, name):
                if (ndim > 2 and using_defaults
                        and pattern in _GENERIC_PATTERNS):
                    spec = conv_safe(ndim)
                return NamedSharding(mesh, _fit_spec(spec, ndim, mesh, shape))
        if ndim > 2:
            return NamedSharding(
                mesh, _fit_spec(conv_safe(ndim), ndim, mesh, shape))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(assign, params)


def pipeline_stacked_rules(base: Optional[Rules] = None,
                           prefix: str = "stages") -> List[Tuple[str, P]]:
    """Rules for a state tree containing a STACKED pipeline-stage subtree
    (leaves under ``prefix`` carry a leading stage dim, per
    ``pipeline_parallel.stack_stage_params``): every base rule is
    mirrored with ``prefix`` required in the path and ``pipe`` prepended
    to its spec — stage dim over the ``pipe`` axis, the remaining dims
    placed exactly as their non-pipelined counterparts — ahead of the
    unmodified base rules for the leaves outside the pipelined region
    (embed/head stay un-stacked). THE one home for the 3-D
    ``(data, tensor, pipe)`` placement policy (lint Rule 14): trainers
    composing ``pipeline_apply`` pass ``rules=pipeline_stacked_rules()``
    and the whole train state (params + optimizer mirrors) shards in one
    pass."""
    base = list(base) if base is not None else list(DEFAULT_RULES)
    anchor = r"(?=.*" + re.escape(prefix) + r"/)"
    staged = [(anchor + pat, P(*(("pipe",) + tuple(spec))))
              for pat, spec in base]
    return staged + base


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def tensor_axis_size(mesh: Optional[Mesh]) -> int:
    """Size of the model (``tensor``) axis; 1 for no mesh / axis absent."""
    return int(mesh.shape.get("tensor", 1)) if mesh is not None else 1


def embedding_table_sharding(mesh: Optional[Mesh]) -> NamedSharding:
    """Placement for an embedding table (rows, dim): rows over ``tensor``
    — the model-parallel split that lets a table bigger than one chip's
    HBM live on the mesh with each chip holding a contiguous row range
    (the same split DEFAULT_RULES' ``.*embedding$`` rule gives nn.Embed
    leaves, spelled once for the embed/ subsystem). Replicated when the
    mesh has no non-trivial ``tensor`` axis."""
    if tensor_axis_size(mesh) > 1:
        return NamedSharding(mesh, P("tensor", None))
    return NamedSharding(mesh, P())


def embedding_lookup_specs(mesh: Mesh) -> Tuple[P, P, P]:
    """``(table, ids, out)`` PartitionSpecs for the embed/ fused-lookup
    ``shard_map``: table rows over ``tensor``, the id batch over the data
    axes (replicated over ``tensor`` — every model shard sees every id so
    it can answer for the rows it owns), bags back over the data axes.
    Weights share the ids spec. THE one place these specs are written
    (lint Rule 14); ``embed/tables.py`` imports them."""
    axes = active_batch_axes(mesh)
    return P("tensor", None), P(axes, None), P(axes, None)


def kv_arena_sharding(mesh: Mesh, heads: int) -> NamedSharding:
    """Placement for a paged KV arena (layers, blocks, block_tokens, heads,
    head_dim): the head axis over ``tensor`` when the model axis is
    non-trivial and divides the head count — the same split the attention
    projections use, so each model shard attends over exactly the heads it
    computed, with no cross-shard gather of K/V. Otherwise replicated."""
    t = tensor_axis_size(mesh)
    if t > 1 and heads % t == 0:
        return NamedSharding(mesh, P(None, None, None, "tensor", None))
    return NamedSharding(mesh, P())


def kv_scale_sharding(mesh: Mesh) -> NamedSharding:
    """Quantization scales (layers, blocks, block_tokens) carry no head
    axis — replicate them (they are ~head_dim x smaller than the arena)."""
    return NamedSharding(mesh, P())


def epoch_cache_sharding(mesh: Mesh, ndim: int,
                         seq_axis: Optional[str] = None) -> NamedSharding:
    """Placement for a device-resident epoch cache array (E, B, ...): the
    leading epoch dim replicated, batch over the data axes, and — for >2-D
    arrays when requested — the third (sequence) dim over ``seq``."""
    axes = active_batch_axes(mesh)
    if ndim > 2 and seq_axis and mesh.shape.get(seq_axis, 1) > 1:
        return NamedSharding(mesh, P(None, axes, seq_axis))
    return NamedSharding(mesh, P(None, axes))


BATCH_AXES = ("data", "fsdp")


def active_batch_axes(mesh: Mesh,
                      batch_axes: Sequence[str] = BATCH_AXES):
    """The non-trivial data-parallel axes of this mesh (None if all size 1).

    THE single definition of which axes shard the batch dimension — the
    sequence- and pipeline-parallel modules build their shard_map specs from
    this too, so the policy can't drift between modules.
    """
    return tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1) or None


def map_batch_shards(fn, mesh: Optional[Mesh], batched: Sequence[bool]):
    """``fn`` run per device on that device's own batch rows.

    The wrapper an opaque custom call (a Pallas kernel) needs inside a
    multi-device jit: the SPMD partitioner cannot split a Mosaic call, so
    it would all-gather the operands and run the whole batch on every
    chip. ``batched[i]`` says whether argument ``i`` carries the batch on
    dim 0 (split over the mesh's batch axes) or is replicated; the output
    is batched. No mesh, or one device, returns ``fn`` unchanged."""
    if mesh is None or mesh.size == 1:
        return fn
    rows = P(active_batch_axes(mesh))
    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(rows if b else P() for b in batched),
        out_specs=rows, check_vma=False)


def batch_sharding(mesh: Mesh, batch_axes: Sequence[str] = BATCH_AXES,
                   seq_axis: Optional[str] = None) -> NamedSharding:
    """Batch dim sharded over the data-parallel axes; optionally the second
    (sequence) dim over `seq` for context parallelism."""
    axes = active_batch_axes(mesh, batch_axes)
    if seq_axis and mesh.shape.get(seq_axis, 1) > 1:
        return NamedSharding(mesh, P(axes, seq_axis))
    return NamedSharding(mesh, P(axes))


def mesh_spans_processes(mesh: Mesh) -> bool:
    """True when the mesh contains devices this process cannot address —
    the multi-host case where a plain ``device_put`` would raise."""
    return _spans(mesh)


def is_cpu_mesh(mesh: Mesh) -> bool:
    """True when the mesh runs on the CPU collective runtime — which
    needs serialized multi-device program streams (its collective
    rendezvous can deadlock/starve under concurrent or deeply queued
    programs). Keyed on the MESH's devices, not ``default_backend()``:
    a CPU-device mesh on an accelerator host is still the CPU runtime."""
    return mesh.devices.flat[0].platform == "cpu"


@lru_cache(maxsize=None)
def _spans(mesh: Mesh) -> bool:
    pid = jax.process_index()
    return any(d.process_index != pid for d in mesh.devices.flat)


@lru_cache(maxsize=None)
def batch_share(mesh: Mesh, axes: Optional[Tuple[str, ...]] = None
                ) -> Tuple[int, int]:
    """(local, total) batch-dim shard counts for this process.

    ``total`` is how many blocks the batch dimension splits into over the
    data axes; ``local`` is how many of those blocks have at least one
    device owned by this process. A process's share of a global batch of
    ``b`` rows is ``b * local / total`` — THE division of labor for
    per-host batch assembly (each host feeds only the rows its devices
    hold, the TPU-native replacement for the reference's shared-filesystem
    hand-off where every MPI rank re-read the whole dataset).
    """
    axes = active_batch_axes(mesh) if axes is None else axes
    if not axes:
        return 1, 1
    names = list(mesh.axis_names)
    dev = mesh.devices
    ax_idx = [names.index(a) for a in axes]
    order = ax_idx + [i for i in range(dev.ndim) if i not in ax_idx]
    total = int(np.prod([dev.shape[i] for i in ax_idx]))
    blocks = np.transpose(dev, order).reshape(total, -1)
    pid = jax.process_index()
    local = sum(1 for i in range(total)
                if any(d.process_index == pid for d in blocks[i]))
    return local, total


def local_batch_rows(mesh: Mesh, global_rows: int) -> int:
    """Rows of a ``global_rows`` batch this process must supply.

    THE one place the division of labor is computed — shard_batch and
    DeviceEpochCache both defer here, so the share formula cannot drift."""
    local, total = batch_share(mesh)
    if global_rows % total:
        raise ValueError(
            f"global batch of {global_rows} rows does not split into "
            f"{total} equal batch shards")
    return global_rows // total * local


def shard_batch(mesh: Mesh, batch: Any,
                seq_axis: Optional[str] = None) -> Any:
    """Place a host batch onto the mesh, sharded over data axes.

    This is the host->HBM hand-off replacing the reference's shared-filesystem
    data channel (``DataConversion.scala:106-173``): one device_put of a
    contiguous host array per input, no text files, no per-element copies.

    Under a multi-process launch (``mesh_spans_processes``), ``batch`` holds
    this process's LOCAL rows — ``local_batch_rows(mesh, b)`` of a global
    batch of ``b`` — and the global array assembles from every process's
    contribution without any cross-host copy of the data itself (each
    host's rows land on its own devices; only metadata rendezvous).
    Global row order is process order: process 0's rows first.
    """
    spans = mesh_spans_processes(mesh)

    def put(x):
        x = np.asarray(x)
        sharding = batch_sharding(mesh, seq_axis=seq_axis if x.ndim > 1 else None)
        if spans:
            local, total = batch_share(mesh)
            if x.shape[0] % local:
                raise ValueError(
                    f"local batch of {x.shape[0]} rows does not split into "
                    f"this process's {local} batch shards (of {total} "
                    "global)")
            gshape = (x.shape[0] // local * total,) + x.shape[1:]
            return jax.make_array_from_process_local_data(sharding, x, gshape)
        return jax.device_put(x, sharding)
    return jax.tree_util.tree_map(put, batch)
