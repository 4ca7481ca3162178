"""Device mesh construction + multi-host initialization.

The TPU-native replacement for the reference's distributed launch machinery:

- device discovery: ``jax.devices()`` replaces shelling out to ``nvidia-smi``
  (``core/env/src/main/scala/EnvironmentUtils.scala:20-50``);
- multi-host: ``jax.distributed.initialize`` replaces the MPI hostfile
  launcher (``cntk-train/src/main/scala/CommandBuilders.scala:95-117``);
- the mesh axes are the vocabulary the whole parallel layer speaks:
  ``data`` (batch), ``fsdp`` (sharded params+batch), ``tensor`` (intra-layer
  model parallel), ``pipe`` (pipeline stages), ``seq`` (sequence/context
  parallel for long inputs), ``expert`` (MoE).

Axis layout matters physically: the LAST mesh dimensions map to the
innermost (fastest, torus-adjacent) ICI rings on real TPU slices, so
``tensor``/``seq`` — the axes with per-step collectives — are placed last.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("data", "fsdp", "pipe", "expert", "seq", "tensor")


@dataclass(frozen=True)
class MeshSpec:
    """Sizes per logical axis; -1 on `data` means "absorb remaining devices"."""
    data: int = -1
    fsdp: int = 1
    pipe: int = 1
    expert: int = 1
    seq: int = 1
    tensor: int = 1

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = {"data": self.data, "fsdp": self.fsdp, "pipe": self.pipe,
                 "expert": self.expert, "seq": self.seq, "tensor": self.tensor}
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if n_devices % fixed:
            raise ValueError(
                f"{n_devices} devices not divisible by fixed axes product {fixed}")
        free = [k for k, v in sizes.items() if v == -1]
        if len(free) > 1:
            raise ValueError(f"only one axis may be -1, got {free}")
        if free:
            sizes[free[0]] = n_devices // fixed
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"axis sizes {sizes} do not multiply to {n_devices} devices")
        return sizes


def make_mesh(spec: Optional[MeshSpec] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh over all (or given) devices with the standard axis order."""
    devices = list(devices) if devices is not None else jax.devices()
    spec = spec or MeshSpec()
    sizes = spec.resolve(len(devices))
    shape = tuple(sizes[a] for a in AXES)
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, AXES)


def data_parallel_mesh(devices: Optional[Sequence] = None) -> Mesh:
    return make_mesh(MeshSpec(data=-1), devices)


def parse_mesh_axes(text: str) -> Dict[str, int]:
    """'data=-1,tensor=2' -> {'data': -1, 'tensor': 2}, validated against
    AXES. The one parser behind both the launcher's --mesh flag and the
    ``runtime.mesh`` config key."""
    axes: Dict[str, int] = {}
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        axis, eq, size = part.partition("=")
        if not eq or not size:
            raise ValueError(f"bad mesh entry {part!r}: want axis=size")
        if axis not in AXES:
            raise ValueError(f"unknown mesh axis {axis!r}; have {AXES}")
        n = int(size)
        if n == 0 or n < -1:
            raise ValueError(
                f"bad size {n} for mesh axis {axis!r}: want a positive "
                "size or -1 (absorb remaining devices)")
        axes[axis] = n
    return axes


def parse_mesh_shape(text: str) -> MeshSpec:
    """'4x2' -> MeshSpec(data=4, tensor=2): the (data, model[, pipe])
    shorthand behind the ``parallel.mesh_shape`` config key. The first
    factor is the data axis (-1 absorbs remaining devices), the second the
    model (``tensor``) axis — placed last so per-layer collectives ride the
    innermost ICI ring — and an optional third factor is the ``pipe``
    (pipeline-stage) axis: '2x2x2' lays a 3-D (data=2, tensor=2, pipe=2)
    topology. A single factor ('8') means pure data parallel."""
    parts = [p.strip() for p in text.lower().split("x") if p.strip()]
    if not parts or len(parts) > 3:
        raise ValueError(
            f"bad mesh shape {text!r}: want 'DATAxMODEL' (e.g. '4x2'), "
            "'DATAxMODELxPIPE' (e.g. '2x2x2'), or a single data-parallel "
            "size")
    sizes = [int(p) for p in parts]
    for n in sizes:
        if n == 0 or n < -1:
            raise ValueError(
                f"bad size {n} in mesh shape {text!r}: want a positive "
                "size or -1 (absorb remaining devices)")
    if len(sizes) == 1:
        return MeshSpec(data=sizes[0])
    if any(n == -1 for n in sizes[1:]):
        raise ValueError(
            f"bad mesh shape {text!r}: only the data factor may be -1")
    if len(sizes) == 2:
        return MeshSpec(data=sizes[0], tensor=sizes[1])
    return MeshSpec(data=sizes[0], tensor=sizes[1], pipe=sizes[2])


def mesh_from_config(devices: Optional[Sequence] = None) -> Mesh:
    """Mesh from config: ``parallel.mesh_shape`` (the 2-D 'DxT' shorthand,
    e.g. '4x2') first, else the ``runtime.mesh`` axis-map key (set by the
    launcher's ``--mesh data=-1,tensor=2`` flag or MMLSPARK_TPU_RUNTIME_MESH).
    Falls back to all-devices data parallel when both are unset — so library
    code can default to this and the same script scales by flag alone."""
    from mmlspark_tpu.utils import config
    shape = config.get("parallel.mesh_shape", "")
    if shape:
        return make_mesh(parse_mesh_shape(shape), devices)
    text = config.get("runtime.mesh")
    if not text:
        return data_parallel_mesh(devices)
    return make_mesh(MeshSpec(**parse_mesh_axes(text)), devices)


def ambient_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing ``with mesh:`` block (how the trainer,
    ``JaxModel`` and the runners scope their jitted calls), None outside
    one. For code that is traced deep inside a model and has to wrap an
    opaque call in ``shard_map`` without a mesh argument to be handed."""
    from jax._src.mesh import thread_resources
    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def resolve_mesh(mesh_spec) -> Mesh:
    """MeshSpec | axis-size dict | "data=2,tensor=4" string | Mesh | None
    -> Mesh. None consults the launcher's ``runtime.mesh`` config (falling
    back to all-devices data parallel), so ``mmlspark-tpu run train.py
    --mesh data=2,tensor=4`` reshapes TRAINING without touching the
    script; the string form is the same syntax as that flag. (JaxModel
    scoring treats an unset meshSpec as the single-device fast path
    instead — scoring rarely needs a mesh and must not silently change
    shape under a launcher flag meant for training.)"""
    if mesh_spec is None:
        return mesh_from_config()
    if isinstance(mesh_spec, Mesh):
        return mesh_spec
    if isinstance(mesh_spec, str):
        mesh_spec = parse_mesh_axes(mesh_spec)
    if isinstance(mesh_spec, dict):
        unknown = sorted(set(mesh_spec) - set(AXES))
        if unknown:
            raise ValueError(
                f"unknown mesh axes {unknown}; valid axes are {AXES}")
        mesh_spec = MeshSpec(**mesh_spec)
    return make_mesh(mesh_spec)


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None) -> None:
    """Join the jax.distributed process group (idempotent).

    One program domain replaces the reference's three-channel split
    (Spark RPC + MPI ring + shared filesystem, SURVEY.md §2.6): after this
    call every host sees the global device set and collectives ride ICI
    within a slice / DCN across slices.
    """
    # Do NOT probe jax.process_count() here: it initializes the backend,
    # after which distributed init is impossible. "Already initialized" is
    # detected from initialize()'s own error instead of private state.
    kwargs = {}
    if coordinator_address is not None:
        kwargs = dict(coordinator_address=coordinator_address,
                      num_processes=num_processes, process_id=process_id)
    elif num_processes is not None or process_id is not None:
        # Worker flags without a coordinator would silently train alone
        # while the rest of the cluster hangs at the barrier — refuse.
        raise ValueError(
            "num_processes/process_id were given without a "
            "coordinator_address; pass all three (or none, for "
            "single-process / auto-detected cluster runs)")
    try:
        jax.distributed.initialize(**kwargs)
    except RuntimeError as e:
        msg = str(e).lower()
        if "already" in msg:
            return
        if coordinator_address is None and "backend" in msg:
            # single-process convenience call after the backend is live
            # (e.g. `mmlspark-tpu run` inside an interactive session):
            # nothing to join, nothing to do
            return
        raise  # a real multi-host init failure must not be silent
    except ValueError:
        if coordinator_address is not None:
            raise  # explicit cluster config that failed is an error
        # else: no cluster auto-detected — single-process dev/test env


def device_count_summary() -> Dict[str, int]:
    """The `nvidia-smi -L` replacement: structured device inventory."""
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
    }
