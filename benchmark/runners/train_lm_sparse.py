"""Runner kind ``train_lm_sparse``: ``train_lm_dense``'s run for a language
model whose attention keeps, for each query, keys that a learned indexer
chooses (``configs/keye-vl-2.0-30b-a3b.json``), and whose step has a second
loss that trains the indexer.

What differs from ``train_lm_dense`` (whose window, stamps,
``items_s_chip``, annotations and result keys these are, the helpers by
import; ``train_lm_sparse_keye.md`` says what is read from which file):

- the loss has two parts beside the whole, ``LOSS_PARTS`` = ``("main",
  "indexer")``: ``train/lm_loss.next_token_loss`` adds the model's
  ``aux_loss`` itself, and each step's ``loss.main`` and ``loss.indexer``
  are compared against the reference's (``loss_main_step*``,
  ``loss_indexer_step*``) and HELD: the whole and the indexer's part to
  ``loss_rel_gap``, the main part to ``loss_main_rel_gap``; the first
  gradient is read by class of leaf as well
  (``references/<reference>.leaf_class``), the indexer's own leaves held
  to their own two limits;
- the SELECTION is compared as the routing is: the program's forward pass
  on step 0's batch from the seeded weights sows each layer's ``(L, L)``
  mask, which stays on the device, a bit a pair, and is handed to the
  reference (``train_reference(observe=)``); per layer it reads the pairs
  on which the program's choice and its own differ, EITHER way, over the
  ``min(k, t + 1)`` a query must keep, how far from its own last chosen
  score the worst of them lay, and how many pairs the program kept beside
  how many it must: ``selection_flip_share`` (the layers' mean),
  ``selection_flip_margin`` (their worst) and ``selection_pairs_gap`` (the
  worst layer's, held to 0: the count is exact), held to the limits of
  those names; and the reference's step 0 then FOLLOWS the program's
  selection (its core and its loss run over the handed pairs), so that
  the first gradient is held against the same discrete choice (steps 1
  and 2 choose for themselves: their losses, by part, hold the choice):
  with seeded weights ONE flipped key can be the one a query attends to,
  and each side choosing for itself read a gradient 1.2 times its own
  norm away on the chip (``references/keye_vl2.py`` has the readings);
- asked of the reference module beside ``train_lm_dense``'s list:
  ``selecting_blocks(cfg)``; ``train_reference`` takes ``observe``,
  ``keep_selection`` and ``mask``;
- held to 0 beside ``attention.flash_fallbacks``:
  ``sparse_attention.fallbacks`` (a trace on an accelerator whose choice or
  core took XLA's form);
- the ring carries ``sparse_attention.selected_pairs`` / ``.causal_pairs``
  (counted from the masks, summed over the layers); their ratio is the
  counter ``sparse_attention.selected_share``.

``control(cell, seed, precision)``: "fp8" is the reference through fp8 in
the program's place, "causal" the float32 reference with every causal key
chosen (the selection left out); both have to come out as not correct.
"""
from __future__ import annotations

import itertools
import time
from typing import Any, Dict, List

import numpy as np

from benchmark.harness import stats
from benchmark.harness.report import note
from benchmark.harness.spec import load_plugin
from benchmark.runners import train_lm, train_lm_dense
from benchmark.runners.train import _norm_gap
from benchmark.runners.train_lm import _all_tokens, _rel_diff, opening


def limit_of(name: str):
    """``train_lm.limit_of``, the main part of the loss under a limit of
    its own (``loss_main_rel_gap``; the whole and the indexer's part under
    ``loss_rel_gap``), the selection's three, and a class of leaves' own
    (``grad_rel_diff_indexer``, ``grad_norm_gap_indexer``: held where the
    configuration sets them)."""
    if name.startswith("loss_main"):    # no control moves it: its own
        return "loss_main_rel_gap"
    for whole, key in (("first_grad_rel_diff_", "grad_rel_diff_"),
                       ("first_grad_norm_worst_leaf_gap_", "grad_norm_gap_")):
        if name.startswith(whole):      # a class of leaves: its own limit
            return key + name[len(whole):]
    return name if name in ("selection_flip_share", "selection_flip_margin",
                            "selection_pairs_gap") \
        else train_lm.limit_of(name)


def _by_sequence(ref, layers):
    """Per layer ``(B, L, L)`` bool -> per sequence ``(layers, L, L / 8)``
    bits (``references/<reference>.pack``), on the device."""
    import jax
    import jax.numpy as jnp
    pack = jax.jit(lambda layers, b: jnp.stack(
        [ref.pack(layer[b]) for layer in layers]), static_argnums=1)
    return [pack(layers, b) for b in range(layers[0].shape[0])]


def _reference(cell, seed: int, tokens, **how):
    cfg, traffic = cell.config, cell.traffic
    ref = load_plugin("references", cfg["reference"])
    steps = int(traffic["check_steps"])
    batch = int(traffic["batch_per_chip"]) * cell.chips
    out = ref.train_reference(
        cfg, seed, tokens[:steps * batch].reshape(steps, batch, -1),
        steps=steps, optimizer=cfg["optimizer"], **how)
    for key in ("grad_norms", "delta_norms"):
        out[key] = list(out[key].values())
    return out


def _by_class(got, want, classes: List[str]) -> Dict[str, float]:
    """The first gradient's two numbers over the leaves of each class
    alone (``references/<reference>.leaf_class``): the whole tree's
    relative difference is the largest class's, and a class of small
    leaves that has gone to zero moves it by nothing."""
    out = {}
    for kind in sorted(set(classes)):
        at = [i for i, k in enumerate(classes) if k == kind]
        if not any(want["grad_norms"][i] for i in at):
            continue            # a class without gradient (none here)
        out[f"first_grad_rel_diff_{kind}"] = _rel_diff(
            [got["first_grad"][i] for i in at],
            [want["first_grad"][i] for i in at])
        out[f"first_grad_norm_worst_leaf_gap_{kind}"] = _norm_gap(
            [got["grad_norms"][i] for i in at],
            [want["grad_norms"][i] for i in at])
    return out


def _worst(got: List[float], want: List[float], names: List[str]) -> str:
    """The leaf ``_norm_gap`` read its worst at."""
    floor = float(np.median(want))
    return names[int(np.argmax([abs(g - w) / max(w, floor)
                                for g, w in zip(got, want)]))]


def compare(got: Dict[str, Any], want: Dict[str, Any],
            classes: List[str] = ()) -> Dict[str, float]:
    """``train_lm_dense.compare``'s numbers, the indexer's part of each
    step's loss, the first gradient by class of leaf (``classes``: a name
    a leaf, in the tree's order), and the selection's flips and counts as
    ``want`` read them of ``got``'s selection
    (``train_reference(observe=)``)."""
    out = train_lm_dense.compare(got, want)
    out.update(_by_class(got, want, list(classes)))
    for s, (g, w) in enumerate(zip(got["indexer"], want["indexer"])):
        out[f"loss_indexer_step{s}_rel_gap"] = abs(g - w) / abs(w)
    flips = want["selection_flips"]
    out["selection_flip_share"] = float(np.mean([f[0] for f in flips]))
    out["selection_flip_margin"] = max(f[1] for f in flips)
    out["selection_pairs_gap"] = max(
        abs(kept - must) / must for kept, must in want["selection_pairs"])
    for i, (share, margin) in enumerate(flips):
        out[f"selection_flip_share_layer{i}"] = share
        out[f"selection_flip_margin_layer{i}"] = margin
    return out


def _compared(cell, seed: int, tokens, got) -> Dict[str, float]:
    t = time.perf_counter()
    want = _reference(cell, seed, tokens, observe=got.pop("selection"))
    reference_s = time.perf_counter() - t
    ref = load_plugin("references", cell.config["reference"])
    names = ref.leaf_names(cell.config)
    out = compare(got, want, [ref.leaf_class(n) for n in names])
    note("worst_leaves", **{
        key: _worst(got[key], want[key], names)
        for key in ("grad_norms", "delta_norms")})
    note("reference_timing", reference_s=round(reference_s, 3),
         compare_s=round(time.perf_counter() - t - reference_s, 3),
         **want["timing"])
    return out


def control(cell, seed: int, precision: str) -> Dict[str, Any]:
    """The reference in the program's place, what the comparison reads
    then (``benchmark/tools/control.py``): one precision down, or with
    ``precision`` "causal" in float32 with every causal key chosen."""
    tokens = _all_tokens(cell, seed)
    got = _reference(cell, seed, tokens, keep_selection=True, **(
        {"mask": "causal"} if precision == "causal"
        else {"quant": precision}))
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
    routed, selecting = ref.routed_blocks(cfg), ref.selecting_blocks(cfg)

    # -- rows and weights from the seed ---------------------------------
    tokens = _all_tokens(ctx.cell, ctx.seed)
    module = build_model(prog["zoo"], **ref.zoo_args(cfg, length))["module"]
    mesh = mesh_from_config(devices)

    def loss_fn(params, batch_, rng):
        out = module.apply(params, batch_["tokens"], hidden=True)
        loss, heads = next_token_loss(
            out, params["params"]["lm_head"]["kernel"], batch_["tokens"],
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
    first: Dict[str, List[float]] = {"losses": [], "mtp": [],
                                     **{part: [] for part in parts}}
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
    pairs = {k: float(np.median(ring[f"sparse_attention.{k}"][:ring_steps]))
             for k in ("selected_pairs", "causal_pairs")}
    selected_share = pairs["selected_pairs"] / pairs["causal_pairs"]
    note("ring", steps=ring_steps, **{k: [round(float(v), 4) for v in
                                          ring[k][:ring_steps]]
                                      for k in ref.AUX})
    gauges = {k: obsmetrics.gauge(k).value for k in ref.AUX}
    held_to_zero = {k: obsmetrics.counter(k).value for k in (
        "attention.flash_fallbacks", "sparse_attention.fallbacks")}
    calls = {k: obsmetrics.counter(k).value for k in (
        "sparse_attention.core_calls.pallas",
        "sparse_attention.core_calls.xla",
        "sparse_attention.select_calls.pallas",
        "sparse_attention.select_calls.xla",
        "attention.flash_bwd_calls.pallas", "attn.norm_turn_calls.pallas",
        "attn.norm_turn_calls.xla", "moe.grouped_calls.ragged_dot")}
    note("program_counters", gauges=gauges, **held_to_zero, **calls)
    note("selection", **pairs, selected_share=round(selected_share, 6))

    # -- free the program's state; its routing and its selections of step
    # 0, from the seeded weights again (the selections stay on the device:
    # a byte a pair and layer); then the reference follows ---------------
    del state, cache, pending, losses, feed
    trainer = None
    t = time.perf_counter()
    with mesh:
        choices = jax.jit(lambda p, x: module.apply(
            p, x, hidden=True, mutable=["intermediates"])[1][
                "intermediates"])(seeded(key), epoch[0]["tokens"])
    got = {**first, "grad_norms": grad_norms, "first_grad": first_grad,
           "delta_norms": delta_norms,
           "choices": [np.asarray(choices[n]["ffn"]["router_choice"][0])
                       for n in routed],
           "selection": _by_sequence(ref, [
               choices[n]["attn"]["selection"][0] for n in selecting])}
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
                     "sparse_attention.selected_share": selected_share,
                     **moe, **held_to_zero},
        "work": {"items_s": items_s,
                 "flops_per_item": ref.train_flops_per_item(cfg, length),
                 "chips": chips,
                 "kernel_calls": ref.kernel_calls(
                     cfg, batch // chips, length,
                     moe["moe.slots_here"] / chips)},
    }
