"""Runner kind ``train_lm_diffusion``: ``train_lm_dense``'s run for a
language model trained by block diffusion (``configs/sdar-30b-a3b.json``),
whose batch is not a row of tokens but ``{tokens, noised, weight}`` and
whose loss is not next-token.

An item is one row of ``tokens_per_row`` CLEAN token ids, uniform over the
vocabulary slice below the mask token, from ``--seed``; its noised copy and
the weights of its masked positions come from ``--seed`` too
(``references/<reference>.noise``: every block of ``block_length``
positions draws ``t ~ U(noise_eps, 1)``, each of its tokens becomes the
mask token with probability ``t`` and then weighs ``1 / t``) and are FIXED
with each resident batch, as a job's collator makes them in the input
pipeline. The model sees ``[noised | tokens]``, ``2 x tokens_per_row``
positions a row; the loss is ``train/lm_loss.masked_diffusion_loss`` on the
noised half. The reference is given the same noised ids and weights.

Asked of the reference module beside what ``train_lm_dense`` asks
(``zoo_args``, ``routed_blocks``, ``LOSS_PARTS``, ``AUX``,
``kernel_calls``, ``init_params``, ``train_reference``,
``train_flops_per_item``): ``noise`` and ``mask_token``. Read of the
configuration file what ``train_lm_dense`` reads (the head is untied and
every layer routed: no ``head_kernel``, no ``mtp_weight``); of the traffic
file also ``block_length`` (which must be the configuration's
``program.zoo_args.block_length``) and ``noise_eps``.

The window, the stamps, ``items_s_chip``, the annotations, the comparison
(``train_lm_dense.compare``) and the result's keys are that runner's, the
helpers by import; the ``# ring`` and ``# diffusion`` notes gain
``diffusion.masked_share`` (the ring's: the share of noised positions that
carry a weight). ``control(cell, seed, precision)``: ``precision`` "fp8"
is the reference through fp8 in the program's place, "causal" the float32
reference under a plain causal mask over the ``2 L`` positions; both have
to come out as not correct.
"""
from __future__ import annotations

import itertools
import time
from typing import Any, Dict, List

import numpy as np

from benchmark.harness import stats
from benchmark.harness.report import note
from benchmark.harness.spec import load_plugin
from benchmark.runners.train_lm import _tokens_from_seed, limit_of, opening
from benchmark.runners.train_lm_dense import compare


def _rows(cell, seed: int) -> Dict[str, np.ndarray]:
    """Every resident row: ``tokens`` (clean ids below the mask token),
    ``noised`` and ``weight``, ``(resident_batches x batch, L)`` each."""
    cfg, traffic = cell.config, cell.traffic
    ref = load_plugin("references", cfg["reference"])
    block = int(traffic["block_length"])
    if block != int(cfg["program"]["zoo_args"]["block_length"]):
        raise ValueError(
            f"traffic block_length {block} against the configuration's "
            f"{cfg['program']['zoo_args']['block_length']}")
    batch = int(traffic["batch_per_chip"]) * cell.chips
    mask = ref.mask_token(cfg)
    tokens = _tokens_from_seed(
        seed, int(traffic["resident_batches"]) * batch,
        int(traffic["tokens_per_row"]), mask)
    noised, weight = ref.noise(seed, tokens, block,
                               float(traffic["noise_eps"]), mask)
    return {"tokens": tokens, "noised": noised, "weight": weight}


def _reference(cell, seed: int, rows, quant=None, mask=None):
    cfg, traffic = cell.config, cell.traffic
    ref = load_plugin("references", cfg["reference"])
    steps = int(traffic["check_steps"])
    batch = int(traffic["batch_per_chip"]) * cell.chips
    out = ref.train_reference(
        cfg, seed, *(rows[k][:steps * batch].reshape(steps, batch, -1)
                     for k in ("tokens", "noised", "weight")),
        steps=steps, optimizer=cfg["optimizer"], quant=quant, mask=mask)
    for key in ("grad_norms", "delta_norms"):
        out[key] = list(out[key].values())
    return out


def _compared(cell, seed: int, rows, got) -> Dict[str, float]:
    t = time.perf_counter()
    want = _reference(cell, seed, rows)
    reference_s = time.perf_counter() - t
    out = compare(got, want)
    note("reference_timing", reference_s=round(reference_s, 3),
         compare_s=round(time.perf_counter() - t - reference_s, 3),
         **want["timing"])
    return out


def control(cell, seed: int, precision: str) -> Dict[str, Any]:
    """The reference in the program's place, what the comparison reads
    then (``benchmark/tools/control.py``): one precision down, or with
    ``precision`` "causal" in float32 under a plain causal mask."""
    rows = _rows(cell, seed)
    got = _reference(cell, seed, rows, **(
        {"mask": "causal"} if precision == "causal"
        else {"quant": precision}))
    got["choices"] = [r["choice"] for r in got["routing"]]
    return {"compared": _compared(cell, seed, rows, got),
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
    from mmlspark_tpu.train.lm_loss import masked_diffusion_loss

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

    # -- rows, their noise and the weights from the seed -----------------
    rows = _rows(ctx.cell, ctx.seed)
    module = build_model(prog["zoo"], **ref.zoo_args(cfg, length))["module"]
    mesh = mesh_from_config(devices)

    def ids(batch_):
        """The row the model sees: ``[noised copy | clean copy]``."""
        return jnp.concatenate([batch_["noised"], batch_["tokens"]], axis=1)

    def loss_fn(params, batch_, rng):
        out = module.apply(params, ids(batch_), hidden=True)
        loss, heads = masked_diffusion_loss(
            out, params["params"]["lm_head"]["kernel"], batch_["tokens"],
            batch_["weight"], chunk=int(prog["loss_chunk"]))
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
    cache = DeviceEpochCache(rows, batch, mesh=trainer.mesh)
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
        "moe.load_max_over_mean", "moe.slots_here")}
    note("ring", steps=ring_steps, **{k: [round(float(v), 4) for v in
                                          ring[k][:ring_steps]]
                                      for k in ref.AUX})
    gauges = {k: obsmetrics.gauge(k).value for k in ref.AUX}
    held_to_zero = {"attention.flash_fallbacks": obsmetrics.counter(
        "attention.flash_fallbacks").value}
    calls = {k: obsmetrics.counter(k).value for k in (
        "attention.fused_calls.block_diffusion",
        "attention.fused_calls.flash", "attention.fused_calls.reference",
        "moe.grouped_calls.ragged_dot")}
    note("program_counters", gauges=gauges, **held_to_zero, **calls)

    # -- free the program's state; its routing of step 0, from the seeded
    # weights again; then the reference follows --------------------------
    del state, cache, pending, losses, feed
    trainer = None
    t = time.perf_counter()
    got = {**first, "grad_norms": grad_norms, "first_grad": first_grad,
           "delta_norms": delta_norms}
    with mesh:
        choices = jax.jit(lambda p, x: module.apply(
            p, x, hidden=True, mutable=["intermediates"])[1][
                "intermediates"])(seeded(key), ids(epoch[0]))
    got["choices"] = [np.asarray(
        choices[n]["ffn"]["router_choice"][0]) for n in routed]
    del choices, epoch
    routing_s = time.perf_counter() - t
    t = time.perf_counter()
    compared = _compared(ctx.cell, ctx.seed, rows, got)
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
    note("diffusion", block_length=int(traffic["block_length"]),
         noise_eps=float(traffic["noise_eps"]),
         masked_share=round(float(np.median(
             ring["diffusion.masked_share"][:ring_steps])), 5),
         weight_mean=round(float(rows["weight"].mean()), 5),
         weight_max=round(float(rows["weight"].max()), 2))

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
