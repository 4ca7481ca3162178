"""DistributedTrainer: pjit-sharded training steps over the device mesh.

The in-process replacement for the reference's distributed training path
(``CNTKLearner.fit`` writing text files + launching ``mpiexec -n G cntk ...
parallelTrain=true``, ``cntk-train/src/main/scala/CNTKLearner.scala:52-162``):

- no subprocess: the train step is one jitted XLA program;
- no MPI ring: gradients allreduce via the collectives XLA inserts from the
  GSPMD shardings (psum over ``data``/``fsdp`` riding ICI);
- no filesystem hand-off: host batches stream via ``shard_batch``;
- multi-host via ``jax.distributed`` (mesh.py) instead of hostfiles.

Supports dp / fsdp / tensor-parallel out of the box through the sharding
rules; pipeline and sequence parallel live in their own modules and compose
via the same mesh.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

# DevicePrefetcher moved to data/prefetch.py (the streaming input pipeline's
# terminal stage); re-exported here because trainer.DevicePrefetcher is the
# documented import path for existing callers (train/deep.py, tests).
from mmlspark_tpu.data.pipeline import Dataset
from mmlspark_tpu.data.prefetch import DevicePrefetcher  # noqa: F401
from mmlspark_tpu.parallel.mesh import mesh_from_config
from mmlspark_tpu.observability import compiles as obscompiles
from mmlspark_tpu.observability import events as obsevents
from mmlspark_tpu.observability import metrics as obsmetrics
from mmlspark_tpu.observability import scopes as obsscopes
from mmlspark_tpu.observability import spans as obsspans
from mmlspark_tpu.observability import syncs as obssyncs
from mmlspark_tpu.reliability import watchdog as _watchdog
from mmlspark_tpu.reliability.faults import fault_site
from mmlspark_tpu.parallel.sharding import (
    batch_sharding, epoch_cache_sharding, is_cpu_mesh, local_batch_rows,
    mesh_spans_processes, param_shardings, replicated, Rules, shard_batch,
)
from mmlspark_tpu.utils import config as mmlconfig
from mmlspark_tpu.utils.logging import MetricLogger

LossFn = Callable[[Any, Dict[str, jax.Array], jax.Array], jax.Array]


_SPLIT_JIT = None


def _shared_split_jit():
    """One process-wide jitted epoch splitter shared by every cache
    instance (a per-instance jit would re-trace and re-compile for every
    fresh cache). The step count is a STATIC argument: all indices are
    compile-time constants, so materializing an epoch is ONE dispatch
    with zero host->device scalar transfers, where a per-batch
    traced-index slicer ships a scalar per batch."""
    global _SPLIT_JIT
    if _SPLIT_JIT is None:
        _SPLIT_JIT = jax.jit(
            lambda d, steps: [
                jax.tree_util.tree_map(lambda a: a[i], d)
                for i in range(steps)],
            static_argnums=1)
    return _SPLIT_JIT


class DeviceEpochCache:
    """Device-resident epoch: one host->HBM transfer, batches sliced on device.

    Streaming a host batch per step is the CNTKModel anti-pattern's last
    residue — where host->HBM transfers contend with execution (PCIe
    under load), every per-step ``device_put`` stalls the pipeline. When
    the (featurized) epoch fits in an HBM budget, the
    TPU-first move is residency: transfer once, then every batch is an XLA
    slice of an already-on-device array — zero steady-state transfer.

    Layout: each column is reshaped host-side to ``(steps, batch, ...)`` and
    placed with the BATCH dim (axis 1) sharded over the mesh's data axes, so
    slicing out batch ``i`` along the replicated axis 0 moves no data across
    devices and yields exactly the sharding ``put_batch`` would have
    committed. Optional per-epoch shuffling permutes rows on device with a
    ``fold_in(seed, epoch)`` key — deterministic, so elastic resume replays
    the same order (the contract DeepClassifier's streaming path keeps).

    Rows beyond ``steps * batch_size`` are dropped; callers that need the
    tail pad-and-mask FIRST (``_pad_xyw``) and let the pad rows ride along
    with zero weight.

    Multi-process: ``batch_size`` is the GLOBAL batch and ``data`` holds
    this process's LOCAL rows — its ``batch_share`` of every batch, in
    process order (process 0's rows sort first within each batch). The
    epoch assembles into one global jax.Array whose shards live on each
    host's own devices; the device-side shuffle then permutes GLOBALLY
    (same fold_in key on every process under SPMD), so batch composition
    is identical to a single-process cache over the concatenated rows.
    """

    def __init__(self, data: Dict[str, np.ndarray], batch_size: int,
                 mesh: Optional[Mesh] = None, seq_axis: Optional[str] = None,
                 shuffle: bool = False, seed: int = 0):
        self.mesh = mesh or mesh_from_config()
        self.batch_size = int(batch_size)
        self._spans = mesh_spans_processes(self.mesh)
        self.local_batch = (local_batch_rows(self.mesh, self.batch_size)
                            if self._spans else self.batch_size)
        first = next(iter(data.values()))
        n = first.shape[0]
        self.steps_per_epoch = n // self.local_batch
        if self.steps_per_epoch < 1:
            raise ValueError(
                f"epoch of {n} local rows is smaller than the local batch "
                f"{self.local_batch}")
        self.shuffle = shuffle
        self.seed = seed
        self._epoch: Optional[int] = None

        keep = self.steps_per_epoch * self.local_batch
        if keep < n:
            import warnings
            warnings.warn(
                f"DeviceEpochCache drops {n - keep} of {n} rows beyond "
                f"steps*batch_size ({self.steps_per_epoch}*{self.local_batch});"
                " pad-and-mask the tail first (learners._pad_xyw) to train on"
                " every row", stacklevel=2)
        with self.mesh:
            def put(name, x):
                x = np.ascontiguousarray(
                    np.asarray(x)[:keep].reshape(
                        (self.steps_per_epoch, self.local_batch)
                        + np.asarray(x).shape[1:]))
                sharding = epoch_cache_sharding(self.mesh, x.ndim,
                                                seq_axis=seq_axis)
                if self._spans:
                    gshape = ((self.steps_per_epoch, self.batch_size)
                              + x.shape[2:])
                    return jax.make_array_from_process_local_data(
                        sharding, x, gshape)
                return jax.device_put(x, sharding)

            base = {k: put(k, v) for k, v in data.items()}
            self._nbytes = sum(int(a.nbytes) for a in base.values())
            self._split = _shared_split_jit()
            if shuffle:
                self._base = base
                self._batches = None  # built per epoch in batches()
                def permute(d, key):
                    m = self.steps_per_epoch * self.batch_size
                    perm = jax.random.permutation(key, m)
                    def one(a):
                        flat = a.reshape((m,) + a.shape[2:])
                        return jnp.take(flat, perm, axis=0).reshape(a.shape)
                    return jax.tree_util.tree_map(one, d)
                self._permute = jax.jit(
                    permute,
                    out_shardings=jax.tree_util.tree_map(
                        lambda a: a.sharding, base))
            else:
                # materialize once; the epoch tensor itself is then free
                self._base = None
                self._batches = self._materialize(base)

    @property
    def nbytes(self) -> int:
        return self._nbytes

    @staticmethod
    def fits(data: Dict[str, np.ndarray],
             budget_mb: Optional[float] = None,
             shuffle: bool = False) -> bool:
        """Would this host epoch fit the ``runtime.device_cache_mb`` budget?
        ``data`` may hold real arrays OR shape/dtype-only stand-ins (e.g.
        ``np.broadcast_to`` views), so callers can budget-check WITHOUT
        materializing the epoch. ``shuffle=True`` charges 3x: base + the
        transient permuted tensor + the materialized batch slices are all
        simultaneously resident at the peak of each epoch's shuffle.
        Unshuffled charges 2x for the build-time peak (epoch tensor + its
        slices; the tensor frees after)."""
        if budget_mb is None:
            budget_mb = float(mmlconfig.get("runtime.device_cache_mb"))
        total = sum(np.asarray(v).nbytes for v in data.values())
        return total * (3 if shuffle else 2) <= budget_mb * 1e6

    def _materialize(self, tensor_dict):
        """Slice the (steps, batch, ...) epoch into per-batch arrays.

        The split program is queued AHEAD of any consumer step, so the
        runtime's program order already guarantees batches exist before a
        step reads them — the host does not need to wait, and a
        synchronous wait here would serialize (transfer, then step
        dispatch) where async overlaps them. The CPU runtime is the
        exception and DOES block: its collective rendezvous can deadlock
        when a second multi-device program stream interleaves with step
        collectives."""
        with self.mesh:
            batches = self._split(tensor_dict, self.steps_per_epoch)
            if is_cpu_mesh(self.mesh):
                obssyncs.block_until_ready(batches, "trainer.materialize")
        return batches

    def batches(self, epoch: int = 0):
        """Device batch dicts for one epoch (shuffled iff ``shuffle``)."""
        if self.shuffle and self._epoch != epoch:
            with self.mesh:
                permuted = self._permute(
                    self._base, jax.random.fold_in(
                        jax.random.PRNGKey(self.seed), epoch))
            # permuted frees after slicing; steady state = base + batches
            self._batches = self._materialize(permuted)
            self._epoch = epoch
        yield from self._batches


class _AuxKeys(Exception):
    """Raised inside the first trace of a step whose loss reports scalars
    beside itself: their keys, which the metrics ring has no slots for
    yet. ``train_step`` catches it, makes the ring and traces again."""

    def __init__(self, keys: Tuple[str, ...]):
        super().__init__(keys)
        self.keys = keys


class _StartSpan:
    """A part of the trainer's start: the cold span ``trainer:<detail>``,
    inside ``outer`` (the first step's ``trainer:dispatch``, or nothing),
    its seconds also set on the always-on gauge ``gauge``, so that a
    reader needs neither the event log nor the flight recorder's ring."""

    __slots__ = ("_span", "_outer", "_gauge", "_start")

    def __init__(self, detail: str, gauge: Optional[str],
                 outer=obsspans.NOOP, **attrs: Any):
        self._span = obsspans.span("trainer", detail, **attrs)
        self._outer, self._gauge = outer, gauge

    def __enter__(self) -> "_StartSpan":
        self._outer.__enter__()
        self._start = obsevents.perf()
        self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._span.__exit__(exc_type, exc, tb)
        if self._gauge:
            obsmetrics.gauge(self._gauge).set(
                obsevents.perf() - self._start)
        self._outer.__exit__(exc_type, exc, tb)
        return False


class DistributedTrainer:
    """Builds sharded init/train/eval steps for a pure loss function.

    loss_fn(params, batch, rng) -> scalar loss (fp32). The whole step —
    forward, backward, allreduce, optimizer — compiles to one XLA program.

    ``loss_fn`` may also return ``(loss, aux)``, ``aux`` a flat dict of
    float32 scalars (the parts of a sum, a routed layer's load). Each
    rides the device-resident ring beside ``loss`` (no host sync), comes
    back from ``train_step`` / ``flush_metrics`` under its key, and
    ``flush_metrics`` publishes its newest value as a gauge of that name.
    The keys are found in the first trace of the step: it stops after the
    forward pass, the ring gains a slot per key, and the step is traced
    again (one more trace of the forward pass, nothing compiled or run).
    A loss that returns a bare scalar is traced once, into the step
    program it always ran.

    ``remat=True`` wraps the WHOLE loss in ``jax.checkpoint``: the
    backward pass then recomputes the forward once and still holds every
    activation while it runs, so no memory is saved. Recompute per block
    inside the model instead (``nn.remat`` on the block, as
    ``models/zoo/decoder.py`` does).
    """

    def __init__(self, loss_fn: LossFn, optimizer: optax.GradientTransformation,
                 mesh: Optional[Mesh] = None, rules: Optional[Rules] = None,
                 accum_steps: int = 1, seq_axis: Optional[str] = None,
                 remat: bool = False):
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        # default honors the launcher's --mesh/runtime.mesh (all-devices
        # data-parallel when unset), like DeepClassifier's mesh resolution
        self.mesh = mesh or mesh_from_config()
        self.rules = rules
        self.accum_steps = accum_steps
        self.seq_axis = seq_axis
        self.remat = remat
        self._state_shardings = None
        # two jitted step variants, keyed by whether the batch buffers are
        # donated (fit's streaming path donates; direct callers feeding
        # reused device batches — DeviceEpochCache epochs — must not)
        self._train_steps: Dict[bool, Any] = {}
        # set where a variant is built, taken back by its first call: that
        # call is a ``trainer:first_step`` span (the step's traces, its
        # lowering, its build or load); every later one tests this and
        # goes on
        self._starting = False
        self._eval_step = None
        # Device-resident metrics ring (ROADMAP item 4, "kill the overhead
        # floor"): per-step scalars (loss, step counter) accumulate in a
        # ring CARRIED THROUGH the jitted step instead of a host-side list
        # of device scalars, so steady-state stepping performs ZERO host
        # syncs. The ring is fetched ("flushed") once every
        # ``train.metrics_flush_steps`` steps; on the multi-device CPU
        # runtime that flush doubles as the dispatch-depth throttle (its
        # collective rendezvous can starve under hundreds of queued async
        # steps — real TPU runtimes bound their own launch queue).
        self._ring: Optional[Dict[str, jax.Array]] = None
        # keys of the scalars the loss reports beside itself, found in the
        # first trace of the step (``_AuxKeys``)
        self._aux_names: Tuple[str, ...] = ()
        self._flush_steps: Optional[int] = None  # resolved at first step
        self._steps_since_flush = 0
        self._throttled = is_cpu_mesh(self.mesh)
        # per-step spans (``trainer:dispatch``, ``trainer:flush``) and the
        # ``trainer.steps_dispatched`` counter: ``_resolve_hot`` runs at the
        # first step and again when a ``fit`` begins, never per step;
        # ``_dispatched`` is the ``step`` those spans carry
        self._hot = None  # the span constructor while the gate is on
        self._steps_dispatched: Optional[obsmetrics.Counter] = None
        self._dispatched = 0
        # the step variants whose program ``_publish_scopes`` has published
        # (under the same gate, at their first dispatch)
        self._scoped: set = set()
        self._scope_program: Optional[str] = None
        # what jax compiles from here on has a row in the program's ledger
        # (listeners on compile events, none per step)
        obscompiles.install()

    # -- state -------------------------------------------------------------
    def _full_init_fn(self, init_params_fn: Callable[[], Any]):
        def full_init():
            params = init_params_fn()
            return {"params": params,
                    "opt_state": self.optimizer.init(params),
                    "step": jnp.zeros((), jnp.int32)}
        return full_init

    def _abstract_state(self, full_init):
        abstract = jax.eval_shape(full_init)
        # Optimizer state mirrors the param tree (adam mu/nu paths contain the
        # same leaf names), so one rule pass shards params AND opt state.
        self._state_shardings = param_shardings(abstract, self.mesh, self.rules)
        return abstract, self._state_shardings

    def abstract_state(self, init_params_fn: Callable[[], Any]):
        """(abstract shapes, shardings) of the train state WITHOUT
        materializing it — the checkpoint-restore target (checkpoint.py).
        Also establishes this trainer's sharding spec."""
        return self._abstract_state(self._full_init_fn(init_params_fn))

    def init(self, init_params_fn: Callable[[], Any]) -> Dict[str, Any]:
        """Initialize sharded state; params materialize directly into their
        shards (no host-side full copy on any single device)."""
        full_init = self._full_init_fn(init_params_fn)
        # host time to trace (``eval_shape`` does, the jitted call finds
        # the jaxpr made), build or load and dispatch the state's program
        with _StartSpan("init", "trainer.init_s"):
            self._abstract_state(full_init)
            with self.mesh:
                return jax.jit(full_init,
                               out_shardings=self._state_shardings)()

    def state_sharding_spec(self) -> Any:
        return self._state_shardings

    # -- steps -------------------------------------------------------------
    def flush_steps(self) -> int:
        """Steps between metric-ring flushes (``train.metrics_flush_steps``,
        resolved once at first use — the ring length is a compile-time
        constant of the step program)."""
        if self._flush_steps is None:
            self._flush_steps = max(
                1, int(mmlconfig.get("train.metrics_flush_steps")))
        return self._flush_steps

    def _init_ring(self) -> Dict[str, jax.Array]:
        """Fresh device-resident metrics ring: a ``flush_steps``-long loss
        ring (and one per aux scalar of the loss) plus the step counter of
        the latest step written. Replicated on purpose — every process
        flushes identical values under SPMD."""
        repl = replicated(self.mesh)
        with self.mesh:
            return {name: jax.device_put(np.zeros(shape, dtype), repl)
                    for name, (shape, dtype) in self._ring_layout().items()}

    def _ring_layout(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """Name -> (shape, dtype) of the ring's arrays, as they are now: an
        aux scalar's slot is there once the first step has found it."""
        layout = {name: ((self.flush_steps(),), np.float32)
                  for name in ("loss",) + self._aux_names}
        layout["step"] = ((), np.int32)
        return layout

    def _build_train_step(self, donate_batch: bool):
        loss_fn = self.loss_fn
        if self.remat:
            loss_fn = jax.checkpoint(loss_fn)
        accum = self.accum_steps
        flush = self.flush_steps()

        def loss_and_aux(params, batch, rng):
            out = loss_fn(params, batch, rng)
            loss, aux = out if isinstance(out, tuple) else (out, {})
            if not isinstance(aux, dict):
                raise TypeError("loss_fn has to return a loss or (loss, "
                                "aux), aux a flat dict of scalars")
            aux = {k: jnp.asarray(v, jnp.float32) for k, v in aux.items()}
            if tuple(aux) != self._aux_names:
                if any(v.shape != () for v in aux.values()):
                    raise TypeError("aux has to be a flat dict of scalars")
                if {"loss", "step"} & set(aux):
                    raise ValueError("aux may not hold 'loss' or 'step': "
                                     "the ring holds those itself")
                raise _AuxKeys(tuple(aux))  # the ring has no room for them
            return loss, aux

        # The named scopes are metadata only (no operation changes): stable
        # names for the step's phases in xprof/TensorBoard, and the seam for
        # a split of device time by phase (PERF.md, Open questions).
        def single_grad(params, batch, rng):
            with jax.named_scope("loss_and_grad"):
                return jax.value_and_grad(loss_and_aux, has_aux=True)(
                    params, batch, rng)

        def step(state, ring, batch, rng):
            params = state["params"]
            rng = jax.random.fold_in(rng, state["step"])
            if accum > 1:
                # microbatch gradient accumulation via scan: trades HBM for
                # one weight update per `accum` forward/backward passes
                def micro(carry, mb_and_idx):
                    mb, idx = mb_and_idx
                    acc, grad_acc = carry
                    # distinct rng per microbatch (dropout must differ)
                    scalars, grads = single_grad(
                        params, mb, jax.random.fold_in(rng, idx))
                    return (jax.tree_util.tree_map(jnp.add, acc, scalars),
                            jax.tree_util.tree_map(jnp.add, grad_acc, grads)), None
                microbatches = jax.tree_util.tree_map(
                    lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
                    batch)
                zero = jax.tree_util.tree_map(jnp.zeros_like, params)
                ((loss, aux), grads), _ = jax.lax.scan(
                    micro, ((0.0, {k: 0.0 for k in self._aux_names}), zero),
                    (microbatches, jnp.arange(accum)))
                loss = loss / accum
                aux = {k: v / accum for k, v in aux.items()}
                grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
            else:
                (loss, aux), grads = single_grad(params, batch, rng)
            with jax.named_scope("optimizer_update"):
                updates, opt_state = self.optimizer.update(
                    grads, state["opt_state"], params)
                new_params = optax.apply_updates(params, updates)
            new_state = {"params": new_params, "opt_state": opt_state,
                         "step": state["step"] + 1}
            # metrics ring: the loss lands in slot (step mod flush) ON
            # device — no per-step host traffic; the host reads the whole
            # ring once per flush interval
            with jax.named_scope("metrics_ring"):
                scalars = {"loss": loss, **aux}
                slot = jnp.mod(state["step"], flush)
                new_ring = {k: ring[k].at[slot].set(v)
                            for k, v in scalars.items()}
                new_ring["step"] = new_state["step"]
            return new_state, new_ring, scalars

        # Batch shardings are NOT pinned here: put_batch commits per-leaf
        # shardings (rank-aware — labels are rank-1, activations rank-N) and
        # jit infers from the committed arrays. Pinning a rank-2 spec on the
        # whole batch dict would crash on rank-1 leaves. Donation extends
        # the same rank-awareness: state and ring always donate (their
        # buffers are dead the instant the step returns); the batch donates
        # only on the streaming path (argnum 2, per-leaf committed
        # shardings), where each put_batch transfer is single-use — donating
        # it stops the step from double-buffering its inputs. Reused device
        # batches (DeviceEpochCache epochs) take the non-donating variant.
        return jax.jit(
            step,
            out_shardings=(self._state_shardings, replicated(self.mesh),
                           None),
            donate_argnums=(0, 1, 2) if donate_batch else (0, 1))

    def _get_train_step(self, donate_batch: bool):
        fn = self._train_steps.get(donate_batch)
        if fn is None:
            if self._state_shardings is None:
                raise RuntimeError("call init() before train_step()")
            fn = self._build_train_step(donate_batch)
            self._train_steps[donate_batch] = fn
            self._starting = True
        return fn

    def train_step(self, state, batch, rng, *,
                   donate_batch: bool = False
                   ) -> Tuple[Any, Dict[str, jax.Array]]:
        """One async sharded step. ``donate_batch=True`` additionally
        donates the batch buffers to the step program (no input
        double-buffering) — callers must treat those device arrays as
        CONSUMED; ``fit``'s streaming path opts in, DeviceEpochCache
        consumers that replay batches across epochs must not."""
        # reliability hook: a FaultPlan can kill the Nth step to reproduce a
        # preemption bit-for-bit (a no-op global read when no plan is active)
        fault_site("trainer.train_step")
        fn = self._get_train_step(donate_batch)
        if self._ring is None:
            self._ring = self._init_ring()
        if self._steps_dispatched is None:
            self._resolve_hot()
        # host time to enqueue one step; off, one boolean test
        if self._hot:
            dispatch = self._hot("trainer", "dispatch",
                                 step=self._dispatched, donate=donate_batch)
            if donate_batch not in self._scoped:
                self._publish_scopes(fn, donate_batch, state, batch, rng)
        else:
            dispatch = obsspans.NOOP
        if self._starting:
            # the variant's first call, the ``_AuxKeys`` retrace included,
            # as the one child of its ``trainer:dispatch``; the gauge is
            # the first variant's
            self._starting = False
            dispatch = _StartSpan(
                "first_step", "trainer.first_step_s"
                if len(self._train_steps) == 1 else None,
                outer=dispatch, donate=donate_batch)

        def call():
            try:
                return fn(state, self._ring, batch, rng)
            except _AuxKeys as found:
                # only a trace has run (nothing was donated): give the ring
                # a slot per scalar the loss reports, and trace again
                self._aux_names = found.keys
                self._ring = self._init_ring()
                return fn(state, self._ring, batch, rng)

        with self.mesh:
            if donate_batch:
                # batch donation is best-effort: leaves whose buffers cannot
                # alias any output (labels vs param-shaped outputs) make XLA
                # warn "donated buffers were not usable" at lowering — the
                # expected cost of rank-aware donation, not a bug
                import warnings
                with warnings.catch_warnings():
                    warnings.filterwarnings(
                        "ignore",
                        message="Some donated buffers were not usable")
                    with dispatch:
                        new_state, self._ring, metrics = call()
            else:
                with dispatch:
                    new_state, self._ring, metrics = call()
        self._dispatched += 1
        self._steps_dispatched.inc()
        # Steady state performs ZERO host syncs: the only wait is the ring
        # flush every flush_steps, which on the multi-device CPU runtime
        # also bounds async dispatch depth (hundreds of un-retired step
        # programs can starve its collective rendezvous — 7-of-8 threads
        # arrive, the runtime aborts). Real TPU runtimes bound their own
        # launch queue, so only the CPU mesh pays the flush wait.
        self._steps_since_flush += 1
        if self._throttled and self._steps_since_flush >= self.flush_steps():
            self.flush_metrics()
        return new_state, metrics

    def flush_metrics(self) -> Optional[Dict[str, np.ndarray]]:
        """Fetch the device metrics ring: ONE counted host sync
        (``trainer.flush``) retiring every step dispatched since the last
        flush. Returns ``{"loss": (flush_steps,) float32, "step": int32}``
        host values (and a ring per aux scalar), or None when no step
        has run. Callers that want periodic loss telemetry WITHOUT
        per-step syncs read it here. The newest value of each aux scalar
        is published as a gauge of its name."""
        if self._ring is None:
            return None
        with (self._hot("trainer", "flush", steps=self._steps_since_flush)
              if self._hot else obsspans.NOOP):
            vals = obssyncs.device_get(self._ring, "trainer.flush")
        self._steps_since_flush = 0
        out = {k: np.asarray(v) for k, v in vals.items()}
        if self._aux_names and int(out["step"]) > 0:
            newest = (int(out["step"]) - 1) % self.flush_steps()
            for name in self._aux_names:
                obsmetrics.gauge(name).set(float(out[name][newest]))
        return out

    def eval_step(self, state, batch, rng) -> jax.Array:
        if self._state_shardings is None:
            raise RuntimeError("call init() before eval_step()")
        if self._eval_step is None:
            def loss_alone(params, batch, rng):
                out = self.loss_fn(params, batch, rng)
                return out[0] if isinstance(out, tuple) else out
            self._eval_step = jax.jit(loss_alone)
        with self.mesh:
            return self._eval_step(state["params"], batch, rng)

    # -- telemetry ---------------------------------------------------------
    def _resolve_hot(self) -> None:
        """Resolve the per-step telemetry once: the hot-span gate and the
        counter object, so that a step pays neither a config lookup nor
        the registry's lock."""
        self._hot = obsspans.hot_spans()
        self._steps_dispatched = obsmetrics.counter(
            "trainer.steps_dispatched")

    def _compile_step(self, fn, state, batch, rng):
        """The already-jitted step ``fn`` lowered and compiled again (a
        load where the persistent cache holds it), on arrays or on their
        ``jax.ShapeDtypeStruct``s; the ring by its layout when this runs.
        No step loop calls it."""
        repl = replicated(self.mesh)
        ring = {name: jax.ShapeDtypeStruct(shape, dtype, sharding=repl)
                for name, (shape, dtype) in self._ring_layout().items()}
        with self.mesh:
            return fn.lower(state, ring, batch, rng).compile()

    def _publish_scopes(self, fn, donate_batch: bool, state, batch,
                        rng) -> None:
        """Publish the step program's scope table
        (``observability/scopes.py``) under the name a device trace gives
        the program: a thunk over the jitted step and the shapes and
        shardings of the arguments of this call. No buffer is kept and
        nothing is lowered until somebody asks for the table."""
        def spec(x):
            committed = getattr(x, "committed", False)
            return jax.ShapeDtypeStruct(
                np.shape(x), x.dtype,
                sharding=x.sharding if committed else None)
        args = jax.tree_util.tree_map(spec, (state, batch, rng))
        self._scoped.add(donate_batch)
        self._scope_program = "jit_" + fn.__name__
        obsscopes.publish(self._scope_program,
                          lambda: self._compile_step(fn, *args))

    def step_scopes(self) -> Optional[obsscopes.Table]:
        """Instruction name -> ``Scope(path, tops)`` of the step program
        last published: which ``jax.named_scope`` each operation of a
        device profile belongs to (``docs/OBSERVABILITY.md``). ``None``
        until a step has been dispatched with ``observability.annotate``
        or the event log on. The first call lowers and compiles the step
        again; no step loop calls it."""
        if self._scope_program is None:
            return None
        return obsscopes.table(self._scope_program)

    def step_memory(self) -> Optional[obsscopes.Memory]:
        """What the step program last published occupies on a device, in
        bytes: ``argument``, ``output``, ``alias``, ``temp``,
        ``generated_code`` and ``peak_memory`` of its
        ``memory_analysis()``. From the same one compile as
        :meth:`step_scopes`, whichever is asked first; ``None`` where that
        gives ``None``."""
        if self._scope_program is None:
            return None
        return obsscopes.memory(self._scope_program)

    def _estimate_flops(self, state, batch, rng) -> float:
        """FLOPs of one compiled train step via XLA cost analysis (a
        Mosaic custom call inside the step counts as zero). Lowers and
        compiles the already-jitted step again, so no step loop calls it
        (``bench.py`` does, once). A backend that cannot answer raises."""
        fn = next(iter(self._train_steps.values()))
        cost = self._compile_step(fn, state, batch, rng).cost_analysis()
        return float(cost["flops"])

    def _finish_epoch_telemetry(self, steps: int, rows: int,
                                wall_s: float) -> None:
        """End-of-epoch throughput gauge + ``train.fit`` event."""
        eps = rows / max(wall_s, 1e-9)
        obsmetrics.gauge("trainer.examples_per_sec").set(eps)
        if obsevents.events_enabled():
            obsevents.emit("event", "train.fit", steps=steps, rows=rows,
                           wall_s=round(wall_s, 6),
                           examples_per_sec=round(eps, 3))

    # -- data --------------------------------------------------------------
    def put_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        with self.mesh:
            return shard_batch(self.mesh, batch, seq_axis=self.seq_axis)

    def fit(self, state, batches: Iterable[Dict[str, np.ndarray]],
            rng: Optional[jax.Array] = None,
            log_every: int = 0,
            log_fn: Callable[[int, float], None] = None,
            prefetch: Optional[int] = None,
            collect_losses: bool = True) -> Tuple[Any, list]:
        """Drive an epoch of host batches through the sharded step.

        ``batches`` is any iterable of host-batch dicts — a list, a
        generator, or a streaming ``mmlspark_tpu.data.Dataset`` (its
        iterator is built here; pass the Dataset itself, not ``.iter()``,
        unless mid-epoch state must be owned by the caller).

        Host->HBM transfer is double-buffered: a DevicePrefetcher thread
        assembles host batches ahead of the loop, and each ``device_put``
        dispatches asynchronously on this thread so the transfer overlaps
        the still-running step (depth from ``prefetch`` or the
        ``runtime.prefetch_depth`` config key). ``log_every``>0 emits
        step/loss/examples-per-sec through the MetricLogger (or a custom
        ``log_fn(step, loss)``). ``collect_losses=False`` skips
        materializing the per-step loss history (it costs a device stack +
        transfer at the end) and returns an empty list.

        The whole call is one ``trainer:fit`` span, the parent of the
        per-step ``trainer:dispatch`` / ``input:*`` spans; the ``step`` a
        dispatch carries restarts here, so that it equals the ``batch``
        ordinal of the ``input:*`` spans that fed it.
        """
        depth = prefetch if prefetch is not None else int(
            mmlconfig.get("runtime.prefetch_depth"))
        self._resolve_hot()
        self._dispatched = 0
        with obsspans.span("trainer", "fit", prefetch=depth):
            return self._fit(state, batches, rng, log_every, log_fn, depth,
                             collect_losses)

    def _fit(self, state, batches, rng, log_every, log_fn, prefetch,
             collect_losses) -> Tuple[Any, list]:
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        if isinstance(batches, Dataset):
            batches = batches.iter()
        losses = []
        metric_log = (MetricLogger(every=log_every)
                      if log_every and log_fn is None else None)
        # telemetry is decided ONCE per fit, outside the step loop — with
        # observability.* unset the loop body pays a single falsy check per
        # step (no clock read, no histogram, no device sync)
        telemetry = obsmetrics.metrics_enabled() or obsevents.events_enabled()
        steps = rows_total = 0
        if telemetry:
            step_hist = obsmetrics.histogram("trainer.step_time_seconds")
            t_start = t_prev = obsevents.perf()
            sync_t0 = obssyncs.total()
            # ring flushes are amortized bookkeeping, not per-step stalls:
            # the steady-state gauge excludes them (tracked by site delta)
            flush_t0 = obsmetrics.counter(
                "observability.sync_points.trainer.flush").value
        prefetcher = DevicePrefetcher(batches, self.put_batch, depth=prefetch)
        # liveness: one beat per dispatched step — a wedged collective or
        # stuck input shows up as this heartbeat going silent, and the
        # watchdog dumps every thread's stack while the hang is live
        hb = _watchdog.register("trainer.fit")
        try:
            for i, batch in enumerate(prefetcher):
                hb.beat()
                rows = next(iter(batch.values())).shape[0] if batch else 0
                # streaming batches are single-use device transfers, so the
                # step donates them (no input double-buffering in HBM)
                state, metrics = self.train_step(state, batch, rng,
                                                 donate_batch=True)
                losses.append(metrics["loss"])  # device scalar: no per-step sync
                if telemetry:
                    # dispatch-to-dispatch wall time: non-blocking (the loss
                    # stays a device scalar; JAX dispatch is async, so this
                    # tracks the pipeline's sustained rate, not device
                    # latency of one step)
                    now = obsevents.perf()
                    step_hist.observe(now - t_prev)
                    t_prev = now
                    steps += 1
                    rows_total += rows
                if log_fn is not None and log_every and i % log_every == 0:
                    log_fn(i, float(losses[-1]))
                elif metric_log is not None:  # cadence handled inside (no
                    metric_log(i, {"loss": losses[-1]},  # sync off-cadence)
                               batch_rows=rows)
        finally:
            hb.close()          # deregister: a finished fit never "stalls"
            prefetcher.close()  # stops the producer if we exited early
            closer = getattr(batches, "close", None)
            if callable(closer):  # pipeline iterators own decode pools
                closer()
        if telemetry and steps:
            # the ROADMAP item-4 scoreboard, sampled BEFORE the epoch-end
            # wait below and net of ring flushes: steady-state stepping
            # itself performs zero host round trips, and this gauge reads
            # exactly that (0.0) instead of charging the epoch's amortized
            # bookkeeping to the step loop
            flush_delta = (obsmetrics.counter(
                "observability.sync_points.trainer.flush").value - flush_t0)
            obsmetrics.gauge("train.sync_points_per_step").set(
                max(0.0, obssyncs.total() - sync_t0 - flush_delta) / steps)
            # one sync per EPOCH (the exit paths below all wait on the last
            # loss anyway) so throughput covers completed device work, not
            # just async dispatch
            obssyncs.block_until_ready(losses[-1],
                                       "trainer.epoch_telemetry")
            self._finish_epoch_telemetry(steps, rows_total,
                                         obsevents.perf() - t_start)
        if not losses:
            return state, []
        if not collect_losses:
            obssyncs.block_until_ready(losses[-1], "trainer.fit_exit")
            return state, []
        # one stack + one transfer: device_get on a LIST of device scalars
        # fetches each individually — a round trip per step on remote chips
        with self.mesh:
            stacked = jnp.stack(losses)
        return state, [float(l) for l in np.asarray(
            obssyncs.device_get(stacked, "trainer.collect_losses"))]
