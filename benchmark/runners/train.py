"""Runner kind ``train``: steady training steps through ``DistributedTrainer``.

Builds the trainer exactly as ``chip_smoke.leg_trainer`` does
(``DeviceEpochCache`` -> ``DistributedTrainer.init`` -> ``train_step`` with
the fused Pallas normalize inside the loss), from the configuration file,
with weights and rows made from ``--seed`` by the benchmark.

One object, the compiled step with its state, is built in set-up, driven
through its first ``check_steps`` steps (which the plain reference follows
after the window) and one warm segment, and handed to the window.

The window is a whole number of segments of ``segment_steps`` steps. The
host dispatches segment ``s + 1`` and only then waits for the last loss of
segment ``s``, so the device never runs dry and every segment has a true
completion instant. ``items_s_chip`` is ALL the window's items over ALL its
time, first completion instant to last: a stall of the device inside the
window, whatever causes it, moves it. The per-segment rates are printed on
an earlier line, and their median is the per-layer ``trainer.step_ms``.
"""
from __future__ import annotations

import itertools
import time
from typing import Any, Dict, List

import numpy as np

from benchmark.harness import stats
from benchmark.harness.report import note
from benchmark.harness.spec import load_plugin


def _norm_gap(prog: List[float], ref: List[float]) -> float:
    """Worst leaf: the gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    floor = float(np.median(ref))
    return max(abs(p - r) / max(r, floor) for p, r in zip(prog, ref))


def _rows_from_seed(seed: int, rows: int, width: int, classes: int):
    rng_np = np.random.default_rng(seed)
    images = rng_np.integers(0, 256, size=(rows, width), dtype=np.uint8)
    labels = rng_np.integers(0, classes, size=(rows,)).astype(np.int32)
    return images, labels


def compare(got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
    """The numbers a run compares: each step's loss, the first gradient's
    norm and the parameters' change, the last two by the worst leaf."""
    out = {f"loss_step{s}_rel_gap": abs(g - w) / abs(w)
           for s, (g, w) in enumerate(zip(got["losses"], want["losses"]))}
    out["first_grad_norm_worst_leaf_gap"] = _norm_gap(
        got["grad_norms"], want["grad_norms"])
    out["param_change_norm_worst_leaf_gap"] = _norm_gap(
        got["delta_norms"], want["delta_norms"])
    # the norm of the difference, over the whole gradient: first order in
    # a precision's error where a gap between norms is second order, so
    # this is the number that a lower precision has to fail
    diff = sum(float(np.sum(np.square(g.astype(np.float64) - w)))
               for g, w in zip(got["first_grad"], want["first_grad"]))
    size = sum(float(np.sum(np.square(w.astype(np.float64))))
               for w in want["first_grad"])
    out["first_grad_rel_diff"] = float(np.sqrt(diff / size))
    return out


def limit_of(name: str) -> str:
    return {"first_grad_norm_worst_leaf_gap": "grad_norm_gap",
            "param_change_norm_worst_leaf_gap": "delta_norm_gap",
            "first_grad_rel_diff": "grad_rel_diff"}.get(name, "loss_rel_gap")


def _reference(cell, seed: int, images, labels, batch: int, quant=None):
    cfg, traffic = cell.config, cell.traffic
    ref = load_plugin("references", cfg["reference"])
    steps = int(traffic["check_steps"])
    mean = float(cfg["program"]["pixel_mean_std"])
    out = ref.train_reference(
        cfg, seed, images[:steps * batch].reshape(steps, batch, -1),
        labels[:steps * batch].reshape(steps, batch), steps=steps,
        lr=float(cfg["optimizer"]["learning_rate"]),
        momentum=float(cfg["optimizer"]["momentum"]), mean=mean, std=mean,
        block_rows=int(traffic["reference_block_rows"]), quant=quant)
    return {"losses": out["losses"], "first_grad": out["first_grad"],
            "grad_norms": list(out["grad_norms"].values()),
            "delta_norms": list(out["delta_norms"].values())}


def control(cell, seed: int, precision: str) -> Dict[str, Any]:
    """The reference in the program's place, one precision down: what the
    comparison reads then (``benchmark/tools/control.py``)."""
    cfg, traffic = cell.config, cell.traffic
    batch = int(traffic["batch_per_chip"]) * cell.chips
    side, chan = int(cfg["image_size"]), int(cfg["num_channels"])
    images, labels = _rows_from_seed(
        seed, int(traffic["check_steps"]) * batch, side * side * chan,
        int(cfg["num_classes"]))
    want = _reference(cell, seed, images, labels, batch)
    got = _reference(cell, seed, images, labels, batch, quant=precision)
    return {"compared": compare(got, want), "limits": cfg["limits"]}


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import optax
    from mmlspark_tpu.models.zoo import build_model
    from mmlspark_tpu.observability import syncs as obssyncs
    from mmlspark_tpu.ops.pallas_preprocess import make_preprocess_fn
    from mmlspark_tpu.parallel.mesh import mesh_from_config
    from mmlspark_tpu.parallel.trainer import (DeviceEpochCache,
                                               DistributedTrainer)

    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    ref = load_plugin("references", cfg["reference"])
    prog, opt = cfg["program"], cfg["optimizer"]
    chips = ctx.cell.chips
    devices = ctx.device["devices"][:chips]
    batch = int(traffic["batch_per_chip"]) * chips
    seg_steps = int(traffic["segment_steps"])
    check_steps = int(traffic["check_steps"])
    rows = int(traffic["resident_batches"]) * batch
    side, chan = int(cfg["image_size"]), int(cfg["num_channels"])
    shape = (side, side, chan)
    classes = int(cfg["num_classes"])
    mean = std = float(prog["pixel_mean_std"])
    if int(traffic["resident_batches"]) < check_steps:
        raise ValueError("resident_batches must cover check_steps")

    # -- rows and weights from the seed ---------------------------------
    images, labels = _rows_from_seed(ctx.seed, rows, int(np.prod(shape)),
                                     classes)

    module = build_model(prog["zoo"], **prog.get("zoo_args", {}))["module"]
    mesh = mesh_from_config(devices)
    pre = make_preprocess_fn(shape, mean=(mean,) * chan, std=(std,) * chan,
                             mesh=mesh)

    def loss_fn(params, batch_, rng):
        logits = module.apply(params, pre(batch_["image"])).astype(
            jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch_["label"]).mean()

    trainer = DistributedTrainer(
        loss_fn, optax.sgd(float(opt["learning_rate"]),
                           momentum=float(opt["momentum"])), mesh=mesh)
    # The trainer's own init builds the sharded state (its program is the
    # same for every seed); the seeded weights then take the params' place
    # like a restored checkpoint. The key is an argument of that program,
    # never a constant: a new seed compiles nothing.
    t = time.perf_counter()
    key = jax.random.PRNGKey(ctx.seed)
    state = trainer.init(lambda: ref.init_params(cfg, jax.random.PRNGKey(0)))
    with trainer.mesh:
        state["params"] = jax.jit(
            lambda k: ref.init_params(cfg, k),
            out_shardings=trainer.state_sharding_spec()["params"])(key)
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t
    rng = jax.random.PRNGKey(1)
    cache = DeviceEpochCache({"image": images, "label": labels}, batch,
                             mesh=trainer.mesh)
    epoch = list(cache.batches(0))
    feed = itertools.cycle(epoch)

    def step():
        nonlocal state
        state, m = trainer.train_step(state, next(feed), rng)
        return m["loss"]

    # per-leaf norms of the momentum trace (= the first gradient as the
    # optimizer got it, after one step) and of the parameters' change
    norms = jax.jit(lambda tree: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree_util.tree_leaves(tree)])
    moved = jax.jit(lambda params, k: [jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b))) for a, b in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(ref.init_params(cfg, k)))])

    # -- the first steps, through the window's own call and feed --------
    t = time.perf_counter()
    first_losses, grad_norms = [], None
    for s in range(check_steps):
        first_losses.append(float(step()))
        if s == 0:
            grad_norms = [float(v) for v in norms(state["opt_state"])]
            first_grad = [np.asarray(x) for x in jax.device_get(
                jax.tree_util.tree_leaves(state["opt_state"]))]
    delta_norms = [float(v) for v in moved(state["params"], key)]
    first_steps_s = time.perf_counter() - t
    n_leaves = len(jax.tree_util.tree_leaves(state["params"]))
    if len(grad_norms) != n_leaves:
        raise RuntimeError(
            f"optimizer state has {len(grad_norms)} leaves, the params "
            f"{n_leaves}: the momentum trace is not one tree of the params")

    # -- one warm segment (dropped), then the window --------------------
    # The window opens at the warm segment's completion instant, with the
    # window's first segment already dispatched: the device is never idle
    # at a segment's edge, so every segment is a steady one.
    seconds = ctx.window_seconds
    losses: List[Any] = []
    stamps: List[float] = []

    def dispatch():
        with jax.profiler.TraceAnnotation("bench:dispatch_segment"):
            for _ in range(seg_steps):
                losses.append(step())
        return losses[-1]

    def wait(x):
        with jax.profiler.TraceAnnotation("bench:wait_segment"):
            jax.block_until_ready(x)
        stamps.append(time.perf_counter())

    ctx.tracer.start()
    t = time.perf_counter()
    pending = [dispatch(), dispatch()]         # warm segment, segment 0
    del losses[:seg_steps]                     # the warm one is not counted
    wait(pending.pop(0))
    warm_segment_s = stamps[0] - t
    ctx.tracer.open()
    before, syncs0 = ctx.meter.snapshot(), obssyncs.total()
    ctx.window_opens(stamps[0])
    while True:
        pending.append(dispatch())
        wait(pending.pop(0))
        if stamps[-1] - stamps[0] >= seconds:
            break
    wait(pending.pop(0))                       # the one still in flight
    syncs_in_window = obssyncs.total() - syncs0
    ctx.tracer.stop()
    compiled = ctx.meter.since(before)
    memory_peak = ctx.memory_peak()
    note("memory_stats", **{k: v for k, v in (
        devices[0].memory_stats() or {}).items()})

    n_seg = len(stamps) - 1
    window_s = stamps[-1] - stamps[0]
    items_s = n_seg * seg_steps * batch / window_s
    rates = stats.segment_rates(stamps, [seg_steps * batch] * n_seg)
    note("segments", steps_per_segment=seg_steps, items_per_step=batch,
         items_s_chip=[round(r / chips, 3) for r in rates],
         median_of_segments=round(stats.median(rates) / chips, 3),
         total_over_window=round(items_s / chips, 3),
         window_s=round(window_s, 4))
    loss_host = np.asarray(jax.device_get(jnp.stack(losses)), np.float32)
    attempted = int(loss_host.size)
    failed = int((~np.isfinite(loss_host)).sum())
    step_count = int(jax.device_get(state["step"]))

    # -- free the program's state, then follow it with the reference ----
    del state, cache, epoch, feed, pending, losses
    trainer = None
    t = time.perf_counter()
    expect = _reference(ctx.cell, ctx.seed, images, labels, batch)
    reference_s = time.perf_counter() - t

    lim = cfg["limits"]
    checks = ctx.checks
    got = {"losses": first_losses, "grad_norms": grad_norms,
           "first_grad": first_grad, "delta_norms": delta_norms}
    for name, value in compare(got, expect).items():
        checks.add(name, value, lim[limit_of(name)])
    checks.add("window_compiles", compiled["programs"], 0)
    checks.add("nonfinite_losses", failed, 0)
    checks.add("state_step_count_gap", abs(
        check_steps + seg_steps + attempted - step_count), 0)

    note("setup", init_s=round(init_s, 3),
         first_steps_s=round(first_steps_s, 3),
         warm_segment_s=round(warm_segment_s, 3),
         reference_s=round(reference_s, 3))
    return {
        "end_to_end": {"items_s_chip": items_s / chips},
        "attempted": attempted, "failed": failed,
        "memory_peak_bytes": memory_peak,
        "spans": {"segment_step_ms": [
            (b1 - a1) / seg_steps * 1e3
            for a1, b1 in zip(stamps[:-1], stamps[1:])]},
        "counters": {"syncs_in_window": syncs_in_window,
                     "steps_in_window": attempted,
                     "window_compiles": compiled["programs"]},
        "work": {"items_s": items_s,
                 "flops_per_item": ref.train_flops_per_item(cfg),
                 "chips": chips,
                 "kernel_calls": {"normalize": {
                     "rows": batch // chips, "width": int(np.prod(shape)),
                     "out_bytes": 0}}},
    }
