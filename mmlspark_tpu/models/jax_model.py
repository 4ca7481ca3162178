"""JaxModel: score a serialized neural net over frame columns.

The CNTKModel re-expression (``cntk-model/src/main/scala/CNTKModel.scala``):

- the reference broadcast model bytes and ran a per-partition minibatch loop
  filling ``FloatVectorVector`` element-by-element (``:50-104``) — the perf
  sin SURVEY.md §7 calls out. Here the model jits ONCE per batch shape and
  whole contiguous host arrays stream to HBM;
- final-batch padding + unpadding matches the reference's workaround
  (``:71-76``, ``:95-97``) but exists for a TPU reason: one static batch
  shape = one compiled program, no retrace;
- input coercion Double/Vector -> float32 (``:195-212``) happens in numpy on
  the host side;
- output node selection by layer name (``:185-193``) maps to capturing a
  named intermediate of the zoo module (``cutOutputLayers``/``layerNames``
  contract used by ImageFeaturizer).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu.core.frame import Frame
from mmlspark_tpu.core.params import (
    AnyParam, DictParam, HasInputCol, HasOutputCol, IntParam, StringParam,
)
from mmlspark_tpu.core.pipeline import Model
from mmlspark_tpu.core.schema import ColumnSchema, DType, SchemaError
from mmlspark_tpu.core.serialization import register_stage
from mmlspark_tpu.models.zoo import build_model
from mmlspark_tpu.observability import syncs as obssyncs


@register_stage
class JaxModel(HasInputCol, HasOutputCol, Model):
    """Scores a zoo architecture with given params over a vector/image column."""

    architecture = StringParam("architecture", "model zoo architecture name", "")
    architectureArgs = DictParam("architectureArgs",
                                 "kwargs for the architecture builder", {})
    miniBatchSize = IntParam("miniBatchSize", "rows per device batch", 1024,
                             validator=lambda v: v > 0)
    outputNodeName = StringParam(
        "outputNodeName", "layer to emit ('' = final output)", "")
    devicePreprocess = DictParam(
        "devicePreprocess", "on-device input preprocessing fused into the "
        "scoring jit: {'srcShape': [h, w, c], 'crop': [ch, cw], "
        "'resize': [H, W]} reshapes the flat wire vector to srcShape, "
        "center-crops, and bilinear-resizes to the model input ON DEVICE "
        "({} = off; crop/resize each optional). The north-star fusion: "
        "raw uint8 crosses host->HBM and crop+resize+normalize run as "
        "ONE Pallas kernel ahead of the first layer instead of per-image "
        "on the host.", {})
    meshSpec = AnyParam(
        "meshSpec", "shard SCORING over a device mesh (MeshSpec / "
        "axis-size dict / Mesh; None = single-device jit). Params shard "
        "by the standard rules (tensor/fsdp for the big matmuls) and the "
        "batch over the data axes — model-parallel inference for nets one "
        "chip cannot hold, a capability the reference's single-graph "
        "CNTKModel had no analogue for. Per-host: each process scores its "
        "own rows on a process-local mesh.", None)
    deviceCache = StringParam(
        "deviceCache", "keep the coerced input resident in HBM across "
        "transform calls and slice batches on device: 'auto' caches when "
        "it fits runtime.device_cache_mb, 'on' forces, 'off' streams. "
        "Repeat scoring of the same frame (FindBestModel candidates, "
        "evaluation passes) then transfers the input ONCE — the "
        "inference face of DeviceEpochCache.", "auto",
        domain=("auto", "on", "off"))
    computeDtype = StringParam(
        "computeDtype", "matmul/conv compute precision: 'bfloat16' casts "
        "float params + activations to bf16 inside the jit (MXU-native) "
        "AND keeps the fetched output in bf16 on the wire — half the "
        "device->host bytes for wide feature outputs; the emitted column "
        "is still float32 (cast on host). 'float32' preserves exact "
        "CNTKModel-parity numerics. Integer inputs (token models) are "
        "never cast.", "float32", domain=("float32", "bfloat16"))

    def set_model(self, architecture: str, params: Optional[Any] = None,
                  seed: int = 0, input_mean=None, input_std=None,
                  **arch_kwargs) -> "JaxModel":
        """Attach architecture + params (random-init if params is None).

        ``input_mean``/``input_std`` (per-channel, scalar, or anything
        broadcastable against the model input) record the normalization
        the net was trained with — fused on device ahead of the first
        layer. THE single place this plumbing lives; downloader and
        featurizer route through here."""
        self.set_params(architecture=architecture,
                        architectureArgs=dict(arch_kwargs))
        spec = build_model(architecture, **arch_kwargs)
        if params is None:
            module = spec["module"]
            shape = (1,) + tuple(spec["input_shape"])
            dtype = jnp.int32 if spec.get("input_dtype") == "int32" else jnp.float32
            x = jnp.zeros(shape, dtype)
            params = module.init(jax.random.PRNGKey(seed), x)
        state = {"params": _to_plain(params)}
        if input_mean is not None or input_std is not None:
            state["input_mu"] = np.asarray(
                input_mean if input_mean is not None else [0.0], np.float32)
            state["input_sigma"] = np.asarray(
                input_std if input_std is not None else [1.0], np.float32)
        # _set_state (not a bare assignment) so a previously compiled
        # closure over OLD params is invalidated
        self._set_state(state)
        return self

    # -- internals ---------------------------------------------------------
    def _spec(self, mesh=None) -> Dict[str, Any]:
        """Build the zoo spec; with a ``seq``-parallel scoring mesh, inject
        the ring/Ulysses attention_fn into builders that accept one — long-
        context INFERENCE rides the same sequence-parallel machinery as
        training, chosen by mesh shape rather than serialized state (an
        attention_fn is process-bound and never persists)."""
        if not self.architecture:
            raise SchemaError("JaxModel: no architecture set; call set_model()")
        args = dict(self.get("architectureArgs"))
        spec = build_model(self.architecture, **args)
        if mesh is not None and mesh.shape.get("seq", 1) > 1 \
                and "attention_fn" not in args:
            # OPT-IN per architecture (spec flag), never by signature
            # sniffing: the ring/Ulysses kernels implement the decoder
            # (q, k, v, causal) contract — injecting them into, e.g., a
            # ViT (bidirectional, CLS token making the length odd) would
            # crash or silently corrupt
            if spec.get("seq_attention"):
                from mmlspark_tpu.parallel.sequence import make_attention_fn
                args["attention_fn"] = make_attention_fn(mesh, "auto")
                spec = build_model(self.architecture, **args)
        return spec

    @property
    def layer_names(self):
        return list(self._spec()["layer_names"])

    def _resolve_score_mesh(self):
        """The scoring mesh, or None for the single-device fast path."""
        if self.get("meshSpec") is None:
            return None
        from mmlspark_tpu.parallel.mesh import resolve_mesh
        from mmlspark_tpu.parallel.sharding import mesh_spans_processes
        mesh = resolve_mesh(self.get("meshSpec"))
        if mesh_spans_processes(mesh):
            raise SchemaError(
                "JaxModel scoring is per-host (each process scores its own "
                "rows); use a process-local mesh, not one spanning "
                "processes")
        return mesh

    def _build_apply(self):
        mesh = self._resolve_score_mesh()
        spec = self._spec(mesh)
        module = spec["module"]
        # params are ARGUMENTS of the jitted function, never closure
        # captures: closed-over arrays inline into the HLO as constants,
        # which for a ResNet-50/ViT-B bloats the program by the full
        # parameter size and multiplies compile time (or overflows
        # remote-compile request limits outright)
        cdt = (jnp.bfloat16 if self.get("computeDtype") == "bfloat16"
               else None)
        if mesh is not None:
            # model-parallel scoring: HOST numpy -> sharded device arrays
            # in one hop (device_put against the NamedSharding tree), so
            # each chip receives only its shard — a model bigger than one
            # chip's HBM never materializes a full replica on any device.
            # The bf16 cast happens on host for the same reason.
            from mmlspark_tpu.parallel.sharding import param_shardings
            params = self._state["params"]
            if cdt is not None:
                params = jax.tree_util.tree_map(
                    lambda a: a.astype(cdt)
                    if np.issubdtype(np.asarray(a).dtype, np.floating)
                    else np.asarray(a), params)
            with mesh:
                params = jax.device_put(
                    params, param_shardings(params, mesh))
        else:
            params = jax.tree_util.tree_map(jnp.asarray,
                                            self._state["params"])
            if cdt is not None:
                params = jax.tree_util.tree_map(
                    lambda a: a.astype(cdt)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
        node = self.outputNodeName

        # Optional input standardization: models trained on z-scored inputs
        # (e.g. DeepClassifier) carry fit-time statistics so extraction sees
        # the same distribution the net was trained on. Shapes must broadcast
        # against the model input shape.
        dp = self.get("devicePreprocess")
        mu = self._state.get("input_mu")
        if dp:
            src = tuple(int(v) for v in dp["srcShape"])
            dst = tuple(int(v) for v in dp.get("resize") or ())
            crop = tuple(int(v) for v in dp.get("crop") or ()) or None

            from mmlspark_tpu.ops.pallas_preprocess import (
                device_resize_bilinear, make_fused_preprocess_fn,
            )

            # scalar / per-channel normalization folds INTO the Pallas
            # kernel; anything wider (a full-image mean) can't ride its
            # per-row constants and takes the jnp path below
            mean_a = (np.asarray(mu, np.float32).ravel()
                      if mu is not None else np.zeros(1, np.float32))
            std_a = (np.asarray(self._state["input_sigma"],
                                np.float32).ravel()
                     if mu is not None else np.ones(1, np.float32))
            foldable = mean_a.size in (1, src[2]) \
                and std_a.size in (1, src[2])
            fused = make_fused_preprocess_fn(
                src, resize=dst or None, crop=crop,
                mean=mean_a, std=std_a,
                out_dtype=jnp.float32) if foldable else None

            def base(x):
                was_u8 = x.dtype == jnp.uint8
                x = _to_float(x.reshape((x.shape[0],) + src))
                if crop:
                    oh = (src[0] - crop[0]) // 2
                    ow = (src[1] - crop[1]) // 2
                    x = x[:, oh:oh + crop[0], ow:ow + crop[1]]
                if dst and dst != (crop or src[:2]):
                    x = device_resize_bilinear(x, dst[0], dst[1])
                    if was_u8:
                        # emulate the host path's uint8 re-quantization
                        # (image/ops.py _resize_stack clips+rints back to
                        # uint8), so a dataset mixing fused and host routes
                        # scores identical images identically
                        x = jnp.clip(jnp.round(x), 0.0, 255.0)
                return x
        else:
            base = _to_float
            fused = None

        if mu is not None:
            mu_d = jnp.asarray(mu)
            sigma_d = jnp.asarray(self._state["input_sigma"])
            norm = lambda x: (base(x) - mu_d) / sigma_d
        else:
            norm = base

        if fused is not None:
            # uint8 wire input runs the single fused Pallas kernel
            # (crop+resize+requantize+normalize, SURVEY §7); float input —
            # the lossless path — keeps the jnp route, numerically the
            # same pipeline
            pre = lambda x: fused(x) if x.dtype == jnp.uint8 else norm(x)
        else:
            pre = norm

        if cdt is not None:
            # bf16 enters HERE, after the full-precision preprocess
            # (resize interpolation + normalization stay fp32-exact);
            # integer token inputs pass through untouched
            def pre(x, _pre=pre):
                y = _pre(x)
                return (y.astype(cdt)
                        if jnp.issubdtype(y.dtype, jnp.floating) else y)

        def bind(jitted):
            if mesh is None:
                call = lambda x: jitted(params, x)
            else:
                def call(x):
                    with mesh:
                        return jitted(params, x)
            # the serving registry AOT-compiles one executable per batch
            # bucket via jitted.lower(params, spec).compile(); expose the
            # raw jitted fn + bound params on the closure rather than
            # widening the transform-path return tuple
            call._jitted = jitted
            call._params = params
            call._mesh = mesh
            return call

        def bind_stack(fn):
            """Whole-pass program over the resident (steps, bs, ...) stack:
            ``lax.map`` runs the per-batch body as ONE compiled scan — one
            dispatch and one fetch for the entire pass, where a Python
            loop pays per-batch dispatch (the
            body still compiles once, and per-iteration activations free
            across scan steps, so memory stays at one batch's worth plus
            the output). Single-device only; mesh scoring keeps its loop
            (batch shardings don't thread through lax.map's carry)."""
            if mesh is not None:
                return None
            stack_jit = jax.jit(
                lambda p, stack: jax.lax.map(lambda x: fn(p, x), stack))
            return lambda stack: stack_jit(params, stack)

        if not node:
            jitted = jax.jit(lambda p, x: module.apply(p, pre(x)))
            inner = lambda p, x: module.apply(p, pre(x))
            return bind(jitted), bind_stack(inner), None, mesh

        from mmlspark_tpu.models.zoo.resnet import apply_with_intermediates

        def select(inters):
            return [v for k, v in sorted(inters.items())
                    if k == node or k.endswith("/" + node)]

        # Probe (shape-only, no compile) whether the node is an explicitly
        # sown layer; capture_intermediates=True records EVERY submodule
        # output and costs ~3x at runtime, so it is the fallback, not the
        # default. On a scoring mesh the probe batch must satisfy the
        # shard_map divisibility of any injected seq-parallel attention
        # (ring shards the batch over the data axes), so probe with one
        # row per batch shard instead of one row total.
        probe_rows = 1
        if mesh is not None:
            from mmlspark_tpu.parallel.sharding import batch_share
            probe_rows = batch_share(mesh)[1]
        if dp:
            probe_shape = (probe_rows, int(np.prod(src)))
        else:
            probe_shape = (probe_rows,) + tuple(spec["input_shape"])
        dt = jnp.int32 if spec.get("input_dtype") == "int32" else jnp.float32
        probe = jax.eval_shape(
            lambda x: apply_with_intermediates(module, params, pre(x))[1],
            jax.ShapeDtypeStruct(probe_shape, dt))
        capture_all = not select(probe)

        def inner(p, x):
            _, inters = apply_with_intermediates(module, p, pre(x),
                                                 capture_all=capture_all)
            matches = select(inters)
            if not matches:
                raise SchemaError(
                    f"output node {node!r} not found; have {sorted(inters)}")
            return matches[0]

        jitted = jax.jit(inner)
        return bind(jitted), bind_stack(inner), node, mesh

    def _coerce_batch(self, arr: np.ndarray, spec) -> np.ndarray:
        """Host-side input coercion (reference UDFs :195-212) + reshape.
        uint8 inputs stay uint8 — they cross host->HBM at 1/4 the bytes and
        cast to float INSIDE the jit (the fused-preprocess fast path)."""
        want_int = spec.get("input_dtype") == "int32"
        arr = np.asarray(arr)
        if arr.dtype != np.uint8 or want_int:
            arr = arr.astype(np.int32 if want_int else np.float32)
        dp = self.get("devicePreprocess")
        if dp:
            # the jit reshapes/resizes on device; ship the flat wire vector
            want = int(np.prod(dp["srcShape"]))
            if arr.ndim != 2 or arr.shape[1] != want:
                raise SchemaError(
                    f"devicePreprocess srcShape {dp['srcShape']} wants flat "
                    f"width {want}, got {arr.shape}")
            return arr
        in_shape = tuple(spec["input_shape"])
        if arr.ndim == 2 and len(in_shape) > 1:
            if int(np.prod(in_shape)) != arr.shape[1]:
                raise SchemaError(
                    f"input width {arr.shape[1]} != prod{in_shape}")
            arr = arr.reshape((arr.shape[0],) + in_shape)
        return arr

    def transform(self, frame: Frame) -> Frame:
        spec = self._spec()
        apply, apply_stack, _, mesh = self._cached_jit(
            lambda: self._build_apply(),
            key=(self.architecture, repr(self.get("architectureArgs")),
                 self.outputNodeName, repr(self.get("devicePreprocess")),
                 repr(self.get("meshSpec")), self.get("computeDtype"),
                 ))
        bs = self.miniBatchSize
        if mesh is not None:
            return self._transform_sharded(frame, spec, apply, mesh, bs)
        if self.get("deviceCache") == "on" \
                and getattr(frame, "_out_of_core", False):
            raise ValueError(
                "deviceCache='on' would materialize an out-of-core "
                "DiskFrame; score it with deviceCache='auto'/'off' "
                "(streams), or materialize it to an in-memory Frame "
                "first if it fits")
        if self.get("deviceCache") != "off" and frame.count():
            dev = self._resident_input(frame, spec, bs)
            if dev is not None:
                # the whole-pass program materializes the ENTIRE output
                # stack in HBM before the one fetch — fine for logits or
                # pooled features, not for a wide intermediate layer on a
                # big frame. Over-budget outputs fall back to per-batch
                # slices of the resident input with bounded retire windows.
                from mmlspark_tpu.models import residency
                # eval_shape abstractly traces the whole stack program
                # (milliseconds for a ResNet-50 — real per-call overhead);
                # the answer depends only on the input aval and the built
                # closure, so memoize on exactly those (a rebuilt closure
                # after set_model/_set_state gets a fresh entry)
                spec_key = (dev.shape, str(dev.dtype), apply_stack)
                cached = getattr(self, "_out_spec_cache", None)
                if cached is not None and cached[0] == spec_key:
                    out_spec = cached[1]
                else:
                    out_spec = jax.eval_shape(apply_stack, dev)
                    self._out_spec_cache = (spec_key, out_spec)
                out_bytes = int(np.prod(out_spec.shape)
                                * out_spec.dtype.itemsize)
                if self.get("deviceCache") == "on" \
                        or residency._fits(dev.nbytes + out_bytes):
                    return self._transform_resident(frame, apply_stack,
                                                    dev, bs)
                return self._transform_resident_windowed(frame, apply,
                                                         dev, bs)
        # Async scoring loop: a batch's transfer + forward is DISPATCHED
        # before earlier results are fetched (JAX dispatch returns
        # immediately), so host->device DMA overlaps compute instead of the
        # reference's strictly serial fill/evaluate/copy-back minibatch
        # loop (CNTKModel.scala:50-104).
        #
        # Transfers are BATCHED: ``put_window`` minibatches stack into ONE
        # host->HBM put, then each batch is a device-side slice. A transfer
        # issued while executes are in flight drains the pipeline (tens of
        # ms on a PCIe-contended link), so fewer, larger puts
        # keep the device fed — the scoring-side face of DeviceEpochCache.
        #
        # Outputs retire in bounded windows: one device-side concat + ONE
        # transfer per window — a round trip per window instead of per
        # batch, without accumulating the whole output (which for
        # intermediate-layer extraction is NOT small) or building a concat
        # whose operand count scales with the dataset.
        put_window = 8         # minibatches per host->device transfer
        window = 32            # output batches fetched per round trip
        in_flight = 8          # bound dispatched-but-unexecuted inputs (HBM)
        dev_outs: list = []
        outs: list = []
        pending: list = []     # coerced host batches awaiting one put

        def retire():
            if not dev_outs:
                return
            stacked = dev_outs[0] if len(dev_outs) == 1 \
                else jnp.concatenate(dev_outs, axis=0)
            outs.append(np.asarray(
                obssyncs.device_get(stacked, "transform.retire")))
            dev_outs.clear()

        def flush():
            if not pending:
                return
            dev = jnp.asarray(np.stack([x for x, _ in pending]))
            for i, (_, n) in enumerate(pending):
                dev_outs.append(apply(dev[i])[:n])
                if len(dev_outs) >= window:
                    retire()
                elif len(dev_outs) >= in_flight:
                    obssyncs.block_until_ready(
                        dev_outs[-in_flight], "transform.backpressure")
            pending.clear()

        for batch in frame.batches(bs, cols=[self.inputCol]):
            x = self._coerce_batch(batch[self.inputCol], spec)
            n = x.shape[0]
            if n < bs:  # pad final batch: keep ONE compiled shape
                pad = np.zeros((bs - n,) + x.shape[1:], x.dtype)
                x = np.concatenate([x, pad], axis=0)
            pending.append((x, n))
            if len(pending) >= put_window:
                flush()
        flush()
        retire()
        return self._emit(frame, outs)

    def _resident_input(self, frame: Frame, spec, bs: int):
        """The frame's coerced input as a device-resident (steps, bs, ...)
        stack shared across transform calls (and across models with the
        same coercion — the FindBestModel case), or None when over budget
        with deviceCache='auto'."""
        from mmlspark_tpu.models import residency
        # everything that shapes the coerced stack is part of the key:
        # input_shape drives _coerce_batch's reshape, so two models with
        # different input shapes must not share an upload (architecture
        # itself stays OUT — identical-input models sharing is the point)
        fingerprint = (self.inputCol, bs, spec.get("input_dtype"),
                       tuple(spec["input_shape"]),
                       repr(self.get("devicePreprocess")))
        # size hint from one coerced row, so an over-budget frame is
        # rejected before build() materializes a full-dataset host copy
        steps = int(np.ceil(frame.count() / bs))
        head = self._coerce_batch(
            np.asarray([np.asarray(frame.head(1)[0][self.inputCol])]), spec)
        hint = steps * bs * head[0].nbytes

        def build() -> np.ndarray:
            stacked = []
            for batch in frame.batches(bs, cols=[self.inputCol]):
                x = self._coerce_batch(batch[self.inputCol], spec)
                if x.shape[0] < bs:
                    pad = np.zeros((bs - x.shape[0],) + x.shape[1:], x.dtype)
                    x = np.concatenate([x, pad], axis=0)
                stacked.append(x)
            return np.stack(stacked)

        return residency.resident_batches(
            frame, fingerprint, build,
            force=self.get("deviceCache") == "on", nbytes_hint=hint)

    def _transform_resident(self, frame: Frame, apply_stack, dev,
                            bs: int) -> Frame:
        """Score from the resident stack as ONE compiled whole-pass
        program (``lax.map`` over the (steps, bs, ...) stack): zero
        steady-state host->HBM input transfer AND a single dispatch +
        single output fetch for the entire pass. Pad rows sit at the tail
        of the last batch, so one flat slice drops them."""
        n_total = frame.count()
        out = apply_stack(dev)                      # (steps, bs, ...)
        out = np.asarray(obssyncs.device_get(out, "transform.resident"))
        out = out.reshape((out.shape[0] * out.shape[1],) + out.shape[2:])
        return self._emit(frame, [out[:n_total]])

    def _transform_resident_windowed(self, frame: Frame, apply, dev,
                                     bs: int) -> Frame:
        """Resident INPUT, bounded output: per-batch device slices of the
        resident stack through the per-batch apply, outputs retired in
        windows — for outputs too wide to co-reside as one stack."""
        window, in_flight = 32, 8
        n_total = frame.count()
        dev_outs: list = []
        outs: list = []

        def retire():
            if not dev_outs:
                return
            stacked = dev_outs[0] if len(dev_outs) == 1 \
                else jnp.concatenate(dev_outs, axis=0)
            outs.append(np.asarray(
                obssyncs.device_get(stacked, "transform.retire")))
            dev_outs.clear()

        for i in range(dev.shape[0]):
            n = min(bs, n_total - i * bs)
            dev_outs.append(apply(dev[i])[:n])
            if len(dev_outs) >= window:
                retire()
            elif len(dev_outs) >= in_flight:
                obssyncs.block_until_ready(
                    dev_outs[-in_flight], "transform.backpressure")
        retire()
        return self._emit(frame, outs)

    def _emit(self, frame: Frame, outs: list) -> Frame:
        """Fetched output batches -> the scored frame column.

        Copy-frugal on purpose: a whole-pass transform hands exactly one
        multi-MB batch here, where a single-element ``np.concatenate``
        still copies and ``astype(float32)`` copies even when the dtype
        already matches — two dataset-sized host copies of pure overhead
        on the resident fast path.

        Ownership contract: on the single-batch path the emitted column
        ALIASES ``outs[0]`` (no copy is taken when it is already 2-D
        float32). Callers hand the buffers over — every internal caller
        builds ``outs`` from freshly fetched device outputs and drops its
        reference. A caller that keeps the input reachable and mutates it
        afterwards would corrupt the scored frame; defensively copy on
        that side, not here."""
        if not outs:
            out = np.zeros((0, 1), np.float32)
        elif len(outs) == 1:
            out = outs[0]
        else:
            out = np.concatenate(outs, axis=0)
        if out.ndim == 1:
            out = out[:, None]
        out = np.asarray(out, np.float32)   # no-copy when already fp32
        col = ColumnSchema(self.outputCol, DType.VECTOR, int(out.shape[1]),
                           metadata={"model_uid": self.uid,
                                     "architecture": self.architecture})
        return frame.with_column_values(col, out)

    def _transform_sharded(self, frame: Frame, spec, apply, mesh,
                           bs: int) -> Frame:
        """Mesh-mode scoring loop: each padded batch is committed with its
        batch dim over the data axes and runs through the pjit'd apply —
        the sharded counterpart of the single-device windowed loop
        (model-parallel scoring targets big models where compute, not the
        wire, dominates, so it does without the transfer batching)."""
        from mmlspark_tpu.parallel.sharding import batch_share, shard_batch
        _, total = batch_share(mesh)
        bs = int(np.ceil(bs / total) * total)  # divisible over data axes
        outs: list = []
        pending: list = []

        def retire(down_to: int) -> None:
            while len(pending) > down_to:
                out, n = pending.pop(0)
                outs.append(np.asarray(
                    obssyncs.device_get(out, "transform.sharded"))[:n])

        # sequence dim (tokens are (B, L)) shards over `seq` only for
        # architectures that OPTED INTO seq-parallel attention — for
        # anything else dim 1 is features/spatial, where a seq sharding
        # would at best crash on divisibility and at worst hit the
        # spatial-sharding miscompiles the sharding rules guard against
        seq_axis = ("seq" if mesh.shape.get("seq", 1) > 1
                    and spec.get("seq_attention") else None)
        # no outer mesh context: `apply` is self-contained (bind() enters
        # the mesh), and device_put/device_get need none
        for batch in frame.batches(bs, cols=[self.inputCol]):
            x = self._coerce_batch(batch[self.inputCol], spec)
            n = x.shape[0]
            if n < bs:
                pad = np.zeros((bs - n,) + x.shape[1:], x.dtype)
                x = np.concatenate([x, pad], axis=0)
            xd = shard_batch(mesh, {"x": x}, seq_axis=seq_axis)["x"]
            pending.append((apply(xd), n))  # async dispatch
            retire(down_to=8)  # bound outputs resident in HBM
        retire(down_to=0)
        return self._emit(frame, outs)

    def transform_schema(self, schema):
        return schema.add(ColumnSchema(self.outputCol, DType.VECTOR, None))


def _to_float(x):
    """uint8 wire format -> float32 on device; other dtypes untouched
    (int32 token models must stay integer)."""
    return x.astype(jnp.float32) if x.dtype == jnp.uint8 else x


def _to_plain(tree):
    """FrozenDict / jax arrays -> plain dict of numpy (serializable).

    Device leaves start their host copies ASYNC before any is awaited:
    a per-leaf ``np.asarray`` is one synchronous round trip per leaf —
    a 100-leaf param tree pays 100 latencies in series; overlapped it is
    one latency plus the wire time of the whole tree."""
    try:
        from flax.core import unfreeze
        tree = unfreeze(tree)
    except (ImportError, TypeError, ValueError):
        pass  # no flax, or already a plain container
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            try:
                leaf.copy_to_host_async()
            except (RuntimeError, ValueError):
                pass  # committed-to-host or non-device arrays
    return jax.tree_util.tree_map(lambda x: np.asarray(x), tree)
