"""Runner kind ``train_lm``: steady language-model training steps through
``DistributedTrainer``, token rows in place of image rows.

The model comes from the zoo by the configuration's published keys
(``build_model`` -> ``DistributedTrainer.init`` / ``train_step``, AdamW on
the trainer's own state path), the loss is the program's chunked
next-token loss with its multi-token-prediction term
(``mmlspark_tpu/train/lm_loss.py``), weights and token rows are the
benchmark's own, made from ``--seed``. An item is one packed row of
``tokens_per_row`` tokens.

The window, the stamps, ``items_s_chip`` (ALL the window's items over ALL
its time), the ``bench:dispatch_segment`` / ``bench:wait_segment``
annotations and the result's ``spans`` / ``counters`` / ``work`` keys are
those of ``runners/train.py``, so that the readers that are there read this
runner unchanged. ``runners/README.md`` says what is read from which file.

``correct``: the reference follows the first ``check_steps`` steps, which
set-up drove through the window's own call and feed. Compared are each
step's loss (whole, main head, MTP head), the first gradient (AdamW's first
moment after one step over ``1 - beta1``) by its relative difference and
by the worst leaf's norm, and the parameters' change after the steps, all
against the reference routed by its own router. Routing is discrete: a
bfloat16 program may choose another expert than the float32 reference
where two scores lie closer than its rounding, so the share of (token,
layer, choice) triples that differ is held to a limit, and so is the
reference's own margin at the worst of them over every routed layer (how
far below its last chosen score the program's other choice lay; past the
first routed layer a token whose earlier choice flipped arrives in another
state, so the margin there is wider than rounding alone). The program's
choices are those of its forward pass on step 0's batch from the seeded
weights. Also false: a compile in the window, a non-finite loss, a wrong
step count, an attention call that fell back from the fused kernel.
"""
from __future__ import annotations

import itertools
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark.harness import stats
from benchmark.harness.report import note
from benchmark.harness.spec import load_plugin
from benchmark.runners.train import _norm_gap

AUX = ("loss.main", "loss.mtp", "moe.slots_here", "moe.load_max_over_mean")


def opening(stamps: List[float]) -> int:
    """Index of the stamp the window opens at. A host that stalls before
    it takes the warm segment's stamp (seen once in 26 runs on the chip,
    for 8 s) finds the next segment complete already: its stamp follows
    within milliseconds, and its items would count in a window that holds
    none of its time. The window opens past every such segment (shorter
    than half the median one)."""
    spans = np.diff(stamps)
    first = 0
    while first < len(spans) - 2 and spans[first] < 0.5 * np.median(spans):
        first += 1
    return first


def _tokens_from_seed(seed: int, rows: int, length: int, vocab: int):
    return np.random.default_rng(seed).integers(
        0, vocab, size=(rows, length)).astype(np.int32)


def zoo_args(cfg: Dict[str, Any], length: int) -> Dict[str, Any]:
    """The configuration's published keys as the zoo entry's arguments."""
    dep = cfg["deployment"]
    return dict(
        vocab=int(cfg["vocab_size"]), dim=int(cfg["hidden_size"]),
        depth=int(cfg["num_hidden_layers"]),
        heads=int(cfg["num_attention_heads"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v_dim=int(cfg["v_head_dim"]),
        mlp_hidden=int(cfg["intermediate_size"]),
        expert_hidden=int(cfg["moe_intermediate_size"]),
        num_experts=int(dep["n_routed_experts_published"]),
        top_k=int(cfg["num_experts_per_tok"]),
        experts_held=(int(cfg["n_routed_experts"]),
                      int(dep["experts_first"])),
        shared_experts=int(cfg["n_shared_experts"]),
        scaling=float(cfg["routed_scaling_factor"]),
        dense_layers=int(cfg["first_k_dense_replace"]),
        mtp=bool(cfg["num_nextn_predict_layers"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        max_len=length, **cfg["program"].get("zoo_args", {}))


def _flips(got: List[np.ndarray], want: List[Dict[str, np.ndarray]]):
    """Per routed layer: (share of the program's choices the reference did
    not make, the reference's widest margin at one of them: how far below
    its last chosen score the program's other choice lay)."""
    rows = []
    for choice, ref in zip(got, want):
        ranked = ref["ranked"]
        last = np.take_along_axis(ranked, ref["choice"], 1).min(1)
        other = ~(choice[:, :, None] == ref["choice"][:, None, :]).any(2)
        gap = last[:, None] - np.take_along_axis(ranked, choice, 1)
        rows.append((float(other.mean()),
                     float(gap[other].max()) if other.any() else 0.0))
    return rows


def _rel_diff(got: List[np.ndarray], want: List[np.ndarray]) -> float:
    """Norm of the difference over the reference's norm, all leaves."""
    diff = size = 0.0
    for g, w in zip(got, want):      # a leaf in float32, the leaves in
        d = (g - w).ravel()          # float64: 706M elements in seconds
        w = w.ravel()
        diff += float(d @ d)
        size += float(w @ w)
    return float(np.sqrt(diff / size))


def compare(got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
    """The numbers a run compares."""
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
    flips = _flips(got["choices"], want["routing"])
    out["routing_flip_share"] = float(np.mean([f[0] for f in flips]))
    out["routing_flip_margin"] = max(f[1] for f in flips)
    for i, (share, margin) in enumerate(flips):
        out[f"routing_flip_share_layer{i}"] = share
        out[f"routing_flip_margin_layer{i}"] = margin
    return out


def limit_of(name: str) -> Optional[str]:
    """The key of ``limits`` a compared number is held to; None for one
    that is printed and not held."""
    if name.startswith("loss"):
        return "loss_rel_gap"
    return {"first_grad_norm_worst_leaf_gap": "grad_norm_gap",
            "param_change_norm_worst_leaf_gap": "delta_norm_gap",
            "first_grad_rel_diff": "grad_rel_diff",
            "routing_flip_share": "routing_flip_share",
            "routing_flip_margin": "routing_flip_margin"}.get(name)


def _reference(cell, seed: int, tokens, quant=None):
    cfg, traffic = cell.config, cell.traffic
    ref = load_plugin("references", cfg["reference"])
    steps = int(traffic["check_steps"])
    batch = int(traffic["batch_per_chip"]) * cell.chips
    out = ref.train_reference(
        cfg, seed, tokens[:steps * batch].reshape(steps, batch, -1),
        steps=steps, optimizer=cfg["optimizer"], quant=quant)
    for key in ("grad_norms", "delta_norms"):
        if key in out:
            out[key] = list(out[key].values())
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


def _all_tokens(cell, seed: int):
    cfg, traffic = cell.config, cell.traffic
    batch = int(traffic["batch_per_chip"]) * cell.chips
    return _tokens_from_seed(
        seed, int(traffic["resident_batches"]) * batch,
        int(traffic["tokens_per_row"]), int(cfg["vocab_size"]))


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

    # -- rows and weights from the seed ---------------------------------
    tokens = _all_tokens(ctx.cell, ctx.seed)
    module = build_model(prog["zoo"], **zoo_args(cfg, length))["module"]
    mesh = mesh_from_config(devices)

    def loss_fn(params, batch_, rng):
        out = module.apply(params, batch_["tokens"], hidden=True)
        loss, parts = next_token_loss(
            out, params["params"]["lm_head"]["kernel"], batch_["tokens"],
            mtp_weight=float(prog["mtp_weight"]),
            chunk=int(prog["loss_chunk"]))
        return loss, {**parts, **out["stats"]}

    b1 = float(opt["beta1"])
    trainer = DistributedTrainer(
        loss_fn, optax.adamw(
            float(opt["learning_rate"]), b1=b1, b2=float(opt["beta2"]),
            eps=float(opt["eps"]), weight_decay=float(opt["weight_decay"]),
            mask=lambda p: jax.tree_util.tree_map(
                lambda x: x.ndim >= 2, p)),
        mesh=mesh)
    # The trainer's own init builds the sharded state (zero weights,
    # AdamW's two moments); the seeded weights then take the params' place
    # like a restored checkpoint, the zeros freed before they are made. The
    # key is an argument of that program: a new seed compiles nothing.
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
        first["main"].append(float(m["loss.main"]))
        first["mtp"].append(float(m["loss.mtp"]))
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
    note("memory_stats", **{k: v for k, v in (
        devices[0].memory_stats() or {}).items()})

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
                                          ring[k][:ring_steps]] for k in AUX})
    gauges = {k: obsmetrics.gauge(k).value for k in AUX}
    fallbacks = obsmetrics.counter("attention.flash_fallbacks").value
    fused = {k: obsmetrics.counter(k).value for k in (
        "attention.fused_calls.flash", "attention.fused_calls.short",
        "attention.fused_calls.reference", "moe.grouped_calls.ragged_dot")}
    note("program_counters", flash_fallbacks=fallbacks, gauges=gauges,
         **fused)

    # -- free the program's state; its routing of step 0, from the seeded
    # weights again; then the reference follows ---------------------------
    del state, cache, pending, losses, feed
    trainer = None
    t = time.perf_counter()
    with mesh:
        choices = jax.jit(lambda p, x: module.apply(
            p, x, hidden=True, mutable=["intermediates"])[1][
                "intermediates"])(seeded(key), epoch[0]["tokens"])
    names = [f"block{i}" for i in range(
        int(cfg["first_k_dense_replace"]), int(cfg["num_hidden_layers"]))]
    if cfg["num_nextn_predict_layers"]:
        names.append("mtp_block")
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
    note("compared_not_held", **{k: v for k, v in compared.items()
                                 if limit_of(k) is None})
    for name, value in compared.items():
        if limit_of(name) is not None:
            checks.add(name, value, lim[limit_of(name)])
    checks.add("window_compiles", compiled["programs"], 0)
    checks.add("nonfinite_losses", failed, 0)
    checks.add("state_step_count_gap", abs(
        check_steps + seg_steps + dispatched - step_count), 0)
    checks.add("attention.flash_fallbacks", fallbacks, 0)

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
                     "moe.slots_here": slots,
                     "attention.flash_fallbacks": fallbacks},
        "work": {"items_s": items_s,
                 "flops_per_item": ref.train_flops_per_item(cfg, length),
                 "chips": chips,
                 "kernel_calls": {
                     "flash_fwd": {
                         "rows": batch // chips, "len": length,
                         "heads": int(cfg["num_attention_heads"]),
                         "head_dim": int(cfg["v_head_dim"])},
                     "expert_matmul": {
                         "slots": slots / chips,
                         "dim": int(cfg["hidden_size"]),
                         "width": int(cfg["moe_intermediate_size"]),
                         "held": int(cfg["n_routed_experts"]),
                         "layers": len(names)}}},
    }
