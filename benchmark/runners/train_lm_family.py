"""Runner kind ``train_lm_family``: ``train_lm``'s run for a language model
of any family. What differs by family is asked of the configuration's
reference module (``references/<reference>.py``), never read from the
configuration by a key's name:

- ``zoo_args(cfg, length)``: the zoo entry's arguments;
- ``routed_blocks(cfg)``: the blocks with a routed layer, in the order of
  the reference's ``routing``;
- ``LOSS_PARTS``: the heads of the loss beside the whole (``("main",)``
  here; ``train_lm``'s model has ``("main", "mtp")``), and ``AUX``: the
  scalars the trainer's ring carries beside the loss;
- ``kernel_calls(cfg, rows, length, slots)``: the shapes of the kernels'
  work for the roofline readers;
- ``init_params``, ``train_reference``, ``train_flops_per_item``.

The window, the stamps, ``items_s_chip``, the annotations, the comparison
and its control (``opening``, ``_compared``, ``limit_of``, ``control``) and
the result's ``spans`` / ``counters`` / ``work`` keys are
``runners/train_lm.py``'s, the helpers by import; a head the family lacks
is an empty list on both sides, so nothing of it is compared.
``program.mtp_weight`` is read only where the family has such a head.
Held to 0 beside ``attention.flash_fallbacks``: ``linear_attention.
fallbacks`` (a trace on an accelerator that took the token-by-token form).
The step's own high-water mark (``bytes_in_use`` + ``bytes_reserved`` after
the window; ``PERF.md`` section 7 h) is printed beside the peak.
"""
from __future__ import annotations

import itertools
import time
from typing import Any, Dict, List

import numpy as np

from benchmark.harness import stats
from benchmark.harness.report import note
from benchmark.harness.spec import load_plugin
from benchmark.runners import train_lm
from benchmark.runners.train_lm import (  # noqa: F401  (control: the tool's)
    _compared, control, limit_of, opening)


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import optax
    from mmlspark_tpu.models.zoo import build_model
    from mmlspark_tpu.observability import metrics as obsmetrics
    from mmlspark_tpu.observability import syncs as obssyncs
    from mmlspark_tpu.parallel.mesh import mesh_from_config
    from mmlspark_tpu.parallel.trainer import (DeviceEpochCache,
                                               DistributedTrainer)
    from mmlspark_tpu.train.lm_loss import next_token_loss

    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    ref = load_plugin("references", cfg["reference"])
    prog, opt = cfg["program"], cfg["optimizer"]
    chips = ctx.cell.chips
    devices = ctx.device["devices"][:chips]
    batch = int(traffic["batch_per_chip"]) * chips
    length = int(traffic["tokens_per_row"])
    seg_steps = int(traffic["segment_steps"])
    check_steps = int(traffic["check_steps"])
    if int(traffic["resident_batches"]) < check_steps:
        raise ValueError("resident_batches must cover check_steps")
    parts = tuple(ref.LOSS_PARTS)

    # -- rows and weights from the seed ---------------------------------
    tokens = train_lm._all_tokens(ctx.cell, ctx.seed)
    module = build_model(prog["zoo"], **ref.zoo_args(cfg, length))["module"]
    mesh = mesh_from_config(devices)

    def loss_fn(params, batch_, rng):
        out = module.apply(params, batch_["tokens"], hidden=True)
        loss, heads = next_token_loss(
            out, params["params"]["lm_head"]["kernel"], batch_["tokens"],
            mtp_weight=float(prog.get("mtp_weight", 0.0)),
            chunk=int(prog["loss_chunk"]))
        return loss, {**heads, **out["stats"]}

    b1 = float(opt["beta1"])
    trainer = DistributedTrainer(
        loss_fn, optax.adamw(
            float(opt["learning_rate"]), b1=b1, b2=float(opt["beta2"]),
            eps=float(opt["eps"]), weight_decay=float(opt["weight_decay"]),
            mask=lambda p: jax.tree_util.tree_map(
                lambda x: x.ndim >= 2, p)),
        mesh=mesh)
    # as train_lm: the trainer's own init builds the sharded state, the
    # seeded weights take the params' place like a restored checkpoint
    t = time.perf_counter()
    key = jax.random.PRNGKey(ctx.seed)
    shapes = jax.eval_shape(lambda: ref.init_params(cfg, key))
    state = trainer.init(lambda: jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype), shapes))
    del state["params"]
    seeded = jax.jit(lambda k: ref.init_params(cfg, k),
                     out_shardings=trainer.state_sharding_spec()["params"])
    with trainer.mesh:
        state["params"] = seeded(key)
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t
    rng = jax.random.PRNGKey(1)
    cache = DeviceEpochCache({"tokens": tokens}, batch, mesh=trainer.mesh)
    epoch = list(cache.batches(0))
    feed = itertools.cycle(epoch)

    def step():
        nonlocal state
        state, m = trainer.train_step(state, next(feed), rng)
        return m

    norms = jax.jit(lambda tree: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) / (1.0 - b1)
        for x in jax.tree_util.tree_leaves(tree)])
    moved = jax.jit(lambda params, start: [jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b))) for a, b in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(start))])

    # -- the first steps, through the window's own call and feed --------
    t = time.perf_counter()
    first: Dict[str, List[float]] = {"losses": [], "main": [], "mtp": []}
    for s in range(check_steps):
        m = step()
        first["losses"].append(float(m["loss"]))
        for part in parts:
            first[part].append(float(m[f"loss.{part}"]))
        if s == 0:
            # AdamW's first moment after one step is (1 - beta1) x the
            # first gradient
            mu = state["opt_state"][0].mu
            grad_norms = [float(v) for v in norms(mu)]
            first_grad = [np.asarray(x) / np.float32(1.0 - b1)
                          for x in jax.device_get(
                              jax.tree_util.tree_leaves(mu))]
            del mu
    with trainer.mesh:         # the seeded weights again, for the change
        delta_norms = [float(v) for v in moved(state["params"], seeded(key))]
    first_steps_s = time.perf_counter() - t
    n_leaves = len(jax.tree_util.tree_leaves(state["params"]))
    if len(grad_norms) != n_leaves:
        raise RuntimeError(
            f"AdamW's first moment has {len(grad_norms)} leaves, the "
            f"params {n_leaves}")

    # -- one warm segment (dropped), then the window --------------------
    seconds = ctx.window_seconds
    losses: List[Any] = []
    stamps: List[float] = []

    def dispatch():
        with jax.profiler.TraceAnnotation("bench:dispatch_segment"):
            for _ in range(seg_steps):
                losses.append(step()["loss"])
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
    memory = devices[0].memory_stats() or {}
    note("memory_stats", **memory)
    note("step_high_water", gb=round((
        memory.get("bytes_in_use", 0) + memory.get("bytes_reserved", 0))
        / 1e9, 4), peak_gb=round(memory_peak / 1e9, 4))

    dispatched = len(losses)
    late = opening(stamps)
    if late:
        note("window_opened_late", segments_left_out=late,
             their_seconds=round(stamps[late] - stamps[0], 4))
        del stamps[:late], losses[:late * seg_steps]
    n_seg = len(stamps) - 1
    window_s = stamps[-1] - stamps[0]
    items_s = n_seg * seg_steps * batch / window_s
    rates = stats.segment_rates(stamps, [seg_steps * batch] * n_seg)
    note("segments", steps_per_segment=seg_steps, items_per_step=batch,
         items_s_chip=[round(r / chips, 5) for r in rates],
         median_of_segments=round(stats.median(rates) / chips, 5),
         total_over_window=round(items_s / chips, 5),
         window_s=round(window_s, 4))
    loss_host = np.asarray(jax.device_get(jnp.stack(losses)), np.float32)
    attempted = int(loss_host.size)
    failed = int((~np.isfinite(loss_host)).sum())
    step_count = int(jax.device_get(state["step"]))
    # the ring's last steps, all inside the window: one fetch, after it
    ring = trainer.flush_metrics()
    ring_steps = min(attempted, len(ring["loss"]))
    load = float(np.median(ring["moe.load_max_over_mean"][:ring_steps]))
    slots = float(np.median(ring["moe.slots_here"][:ring_steps]))
    note("ring", steps=ring_steps, **{k: [round(float(v), 4) for v in
                                          ring[k][:ring_steps]]
                                      for k in ref.AUX})
    gauges = {k: obsmetrics.gauge(k).value for k in ref.AUX}
    held_to_zero = {k: obsmetrics.counter(k).value for k in (
        "attention.flash_fallbacks", "linear_attention.fallbacks")}
    calls = {k: obsmetrics.counter(k).value for k in (
        "attention.fused_calls.flash", "attention.fused_calls.short",
        "attention.fused_calls.reference", "moe.grouped_calls.ragged_dot",
        "linear_attention.calls.chunked",
        "linear_attention.calls.recurrent")}
    note("program_counters", gauges=gauges, **held_to_zero, **calls)

    # -- free the program's state; its routing of step 0, from the seeded
    # weights again; then the reference follows ---------------------------
    del state, cache, pending, losses, feed
    trainer = None
    t = time.perf_counter()
    with mesh:
        choices = jax.jit(lambda p, x: module.apply(
            p, x, hidden=True, mutable=["intermediates"])[1][
                "intermediates"])(seeded(key), epoch[0]["tokens"])
    names = ref.routed_blocks(cfg)
    got = {**first, "grad_norms": grad_norms, "first_grad": first_grad,
           "delta_norms": delta_norms,
           "choices": [np.asarray(choices[n]["ffn"]["router_choice"][0])
                       for n in names]}
    del choices, epoch
    routing_s = time.perf_counter() - t
    t = time.perf_counter()
    compared = _compared(ctx.cell, ctx.seed, tokens, got)
    reference_s = time.perf_counter() - t

    lim = cfg["limits"]
    checks = ctx.checks
    # a kind of limit the configuration does not set is printed, not held
    held = {k: v for k, v in compared.items() if limit_of(k) in lim}
    note("compared_not_held", **{k: v for k, v in compared.items()
                                 if k not in held})
    for name, value in held.items():
        checks.add(name, value, lim[limit_of(name)])
    checks.add("window_compiles", compiled["programs"], 0)
    checks.add("nonfinite_losses", failed, 0)
    checks.add("state_step_count_gap", abs(
        check_steps + seg_steps + dispatched - step_count), 0)
    for name, value in held_to_zero.items():
        checks.add(name, value, 0)

    note("setup", init_s=round(init_s, 3),
         first_steps_s=round(first_steps_s, 3),
         warm_segment_s=round(warm_segment_s, 3),
         routing_s=round(routing_s, 3),
         reference_s=round(reference_s, 3))
    return {
        "end_to_end": {"items_s_chip": items_s / chips},
        "attempted": attempted, "failed": failed,
        "memory_peak_bytes": memory_peak,
        "spans": {"segment_step_ms": [
            (b1_ - a1) / seg_steps * 1e3
            for a1, b1_ in zip(stamps[:-1], stamps[1:])]},
        "counters": {"syncs_in_window": syncs_in_window,
                     "steps_in_window": attempted,
                     "window_compiles": compiled["programs"],
                     "moe.load_max_over_mean": load,
                     "moe.slots_here": slots, **held_to_zero},
        "work": {"items_s": items_s,
                 "flops_per_item": ref.train_flops_per_item(cfg, length),
                 "chips": chips,
                 "kernel_calls": ref.kernel_calls(
                     cfg, batch // chips, length, slots / chips)},
    }
