"""Runner kind ``train_lm_dense``: ``train_lm_family``'s run for a language
model that need not have a routed layer, nor a head of its own. What
differs by family is asked of the configuration's reference module
(``references/<reference>.py``), never read from the configuration by a
key's name:

- ``zoo_args(cfg, length)``: the zoo entry's arguments;
- ``routed_blocks(cfg)``: the blocks with a routed layer, in the order of
  the reference's ``routing``; EMPTY for a dense model, and then nothing
  of a routed layer is read: not the ring's ``moe.*`` scalars, not the
  routing pass after the window, not the flips in ``compare``, and
  ``kernel_calls`` gets 0 slots;
- ``head_kernel(params)``: the ``(dim, vocab)`` output matrix the chunked
  loss reads (a tied model hands its table transposed); a reference
  without it has the untied head at ``params["lm_head"]["kernel"]``;
- ``LOSS_PARTS``: the heads of the loss beside the whole, and ``AUX``: the
  scalars the trainer's ring carries beside the loss;
- ``kernel_calls(cfg, rows, length, slots)``: the shapes of the kernels'
  work for the roofline readers;
- ``init_params``, ``train_reference``, ``train_flops_per_item``.

Read of the configuration file: ``runner``, ``reference``, ``vocab_size``
(the ids' range), ``program.zoo`` / ``.loss_chunk`` / ``.mtp_weight``
(only where the family has such a head), ``optimizer.learning_rate`` /
``.beta1`` / ``.beta2`` / ``.eps`` / ``.weight_decay`` (AdamW, decay on
leaves of two and more dimensions), ``limits`` (``loss_rel_gap``,
``grad_norm_gap``, ``grad_rel_diff``, ``delta_norm_gap``, and for a routed
family ``routing_flip_share`` / ``routing_flip_margin``: a kind the file
does not set is printed, not held). Of the traffic file:
``batch_per_chip``, ``tokens_per_row``, ``resident_batches``,
``segment_steps``, ``check_steps``, ``trace_seconds``.

The window, the stamps, ``items_s_chip``, the annotations and the result's
``spans`` / ``counters`` / ``work`` keys are ``runners/train_lm.py``'s, the
helpers (``opening``, ``_all_tokens``, ``_reference``, ``_rel_diff``,
``limit_of``) by import. Compared and held: ``loss{,_main}_step{0,1,2}_
rel_gap``, ``first_grad_rel_diff``, ``first_grad_norm_worst_leaf_gap``,
``param_change_norm_worst_leaf_gap`` (and the flips of a routed family);
held to 0: ``window_compiles``, ``nonfinite_losses``,
``state_step_count_gap``, ``attention.flash_fallbacks``,
``linear_attention.fallbacks``. ``control`` is ``tools/control.py``'s.

This runner runs the two routed configurations too (``routed_blocks``
non-empty, no ``head_kernel``: ``benchmark/tests/
test_rehearsal_lm_dense.py`` holds it to ``train_lm_family``'s numbers on
the routed toy), so a ``benchmark`` issue can point them at it and delete
``train_lm.run`` and ``train_lm_family.run``.
"""
from __future__ import annotations

import itertools
import time
from typing import Any, Dict, List

import numpy as np

from benchmark.harness import stats
from benchmark.harness.report import note
from benchmark.harness.spec import load_plugin
from benchmark.runners.train import _norm_gap
from benchmark.runners.train_lm import (
    _all_tokens, _flips, _reference, _rel_diff, limit_of, opening)


def _untied_head(params):
    return params["params"]["lm_head"]["kernel"]


def compare(got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
    """The numbers a run compares: ``train_lm.compare``'s, the flips only
    where the reference routed anything."""
    out = {}
    for key, label in (("losses", "loss"), ("main", "loss_main"),
                       ("mtp", "loss_mtp")):
        for s, (g, w) in enumerate(zip(got[key], want[key])):
            out[f"{label}_step{s}_rel_gap"] = abs(g - w) / abs(w)
    out["first_grad_norm_worst_leaf_gap"] = _norm_gap(
        got["grad_norms"], want["grad_norms"])
    out["param_change_norm_worst_leaf_gap"] = _norm_gap(
        got["delta_norms"], want["delta_norms"])
    out["first_grad_rel_diff"] = _rel_diff(
        got["first_grad"], want["first_grad"])
    if want["routing"]:
        flips = _flips(got["choices"], want["routing"])
        out["routing_flip_share"] = float(np.mean([f[0] for f in flips]))
        out["routing_flip_margin"] = max(f[1] for f in flips)
        for i, (share, margin) in enumerate(flips):
            out[f"routing_flip_share_layer{i}"] = share
            out[f"routing_flip_margin_layer{i}"] = margin
    return out


def _compared(cell, seed: int, tokens, got) -> Dict[str, float]:
    t = time.perf_counter()
    want = _reference(cell, seed, tokens)
    reference_s = time.perf_counter() - t
    out = compare(got, want)
    note("reference_timing", reference_s=round(reference_s, 3),
         compare_s=round(time.perf_counter() - t - reference_s, 3),
         **want["timing"])
    return out


def control(cell, seed: int, precision: str) -> Dict[str, Any]:
    """The reference in the program's place, one precision down: what the
    comparison reads then (``benchmark/tools/control.py``)."""
    tokens = _all_tokens(cell, seed)
    got = _reference(cell, seed, tokens, quant=precision)
    got["choices"] = [r["choice"] for r in got["routing"]]
    return {"compared": _compared(cell, seed, tokens, got),
            "limits": cell.config["limits"]}


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
    routed = ref.routed_blocks(cfg)
    head_kernel = getattr(ref, "head_kernel", _untied_head)

    # -- rows and weights from the seed ---------------------------------
    tokens = _all_tokens(ctx.cell, ctx.seed)
    module = build_model(prog["zoo"], **ref.zoo_args(cfg, length))["module"]
    mesh = mesh_from_config(devices)

    def loss_fn(params, batch_, rng):
        out = module.apply(params, batch_["tokens"], hidden=True)
        loss, heads = next_token_loss(
            out, head_kernel(params), batch_["tokens"],
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
    moe = {k: float(np.median(ring[k][:ring_steps])) for k in (
        "moe.load_max_over_mean", "moe.slots_here")} if routed else {}
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
        "linear_attention.calls.recurrent",
        "linear_attention.rule_calls.delta",
        "linear_attention.rule_calls.ssd")}
    note("program_counters", gauges=gauges, **held_to_zero, **calls)

    # -- free the program's state; where a layer is routed, its routing of
    # step 0, from the seeded weights again; then the reference follows ---
    del state, cache, pending, losses, feed
    trainer = None
    t = time.perf_counter()
    got = {**first, "grad_norms": grad_norms, "first_grad": first_grad,
           "delta_norms": delta_norms, "choices": []}
    if routed:
        with mesh:
            choices = jax.jit(lambda p, x: module.apply(
                p, x, hidden=True, mutable=["intermediates"])[1][
                    "intermediates"])(seeded(key), epoch[0]["tokens"])
        got["choices"] = [np.asarray(
            choices[n]["ffn"]["router_choice"][0]) for n in routed]
        del choices
    del epoch
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
                     **moe, **held_to_zero},
        "work": {"items_s": items_s,
                 "flops_per_item": ref.train_flops_per_item(cfg, length),
                 "chips": chips,
                 "kernel_calls": ref.kernel_calls(
                     cfg, batch // chips, length,
                     moe.get("moe.slots_here", 0.0) / chips)},
    }
