#!/usr/bin/env python3
"""First light on the chip: does the main path start, compile and finish?

    python chip_smoke.py          # no flags, no environment switches

One process drives the system once through the entry points a user calls,
at the published width of every model it touches and at the kernel shapes
``bench.py`` uses, with weights and data made from seeds:

- **trainer** — ViT-B/16 @ 224, bf16, batch 128, built exactly like
  ``bench.config_train_large``: ``DeviceEpochCache`` ->
  ``DistributedTrainer.init`` -> ``train_step`` with the fused Pallas
  normalize inside the loss. One compiling step, then a steady window;
- **server** — one ``Server`` holding ResNet-50 (bf16 compute) answering
  ``submit`` calls in four batch buckets, checked against the direct jitted
  apply; then ``Server.generate`` on ``transformer_lm`` (vocab 32000,
  dim 512, depth 6, heads 8) at ``generate.max_seq_len`` 512: four
  concurrent prompts of different lengths through the continuous batcher
  and the paged KV arena, checked against a full-recompute reference;
- **kernels** — ``fused_normalize``, the fused crop+normalize,
  ``flash_attention`` (forward and backward) at bench width and
  ``short_attention`` (forward and backward) at ViT-B/16's shape, each checked
  against its jnp/numpy reference and each proven to have lowered to a
  Mosaic call, so an interpreted or reference path cannot pass.

It is not a benchmark: it proves the program runs on the device and says
how long compiling took. It exits non-zero — and prints no result —
unless ``jax.default_backend() == "tpu"``, and whenever any check fails;
nothing is caught and downgraded. On success stdout holds two lines, each
one JSON object. First the report: ``{"ok", "device", "versions", "cache",
"legs", ...}`` with per-leg compile and steady seconds and the
compile-cache directory with its hit/miss counts. Then, LAST, the verdict
the driver reads, with exactly these keys and the device as jax reports it:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Run it twice against one cache directory: the second run must show cache
hits, far fewer compile seconds and the same output digests.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else
``<checkout>/.jax_cache`` (``mmlspark_tpu.compile_cache.enable``). One
process uses the chip; nothing is spawned.

``run(sizes, rehearsal=True)`` is the CPU rehearsal ``tests/
test_chip_smoke.py`` drives at toy sizes with the kernels interpreted.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.metadata
import itertools
import json
import os
import sys
import time
from typing import Any, Dict, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))

# stated tolerances (relative Frobenius error against the reference)
BF16_REL_TOL = 2e-2     # bf16 has an 8-bit mantissa; outputs round once more
FP32_REL_TOL = 2e-2     # the TPU's default fp32 matmul is bf16 passes too
# a served first token's reference logit may trail the reference argmax by
# at most this fraction of the row's largest |logit| (bf16 model, three
# different attention paths: prefill, paged decode, full recompute)
LOGIT_GAP_TOL = 2e-2
RESULT_TIMEOUT_S = 600.0


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What each leg runs at. The defaults are the real thing: published
    model widths and ``bench.py``'s kernel shapes. Only the tier-1
    rehearsal passes anything else."""
    # trainer: bench.config_train_large
    train_model: str = "vit_b16"
    train_model_args: Tuple[Tuple[str, Any], ...] = (("num_classes", 1000),)
    train_image: int = 224
    train_batch: int = 128
    train_rows: int = 256
    train_steps: int = 8
    # server, scoring: rows per submit -> buckets 1, 4, 16, 32
    score_model: str = "resnet50"
    score_model_args: Tuple[Tuple[str, Any], ...] = (("num_classes", 1000),)
    score_max_batch: int = 32
    score_requests: Tuple[int, ...] = (1, 3, 10, 32)
    # server, generate: transformer_lm at its registered width
    lm_model: str = "transformer_lm"
    lm_max_seq_len: int = 512
    lm_block_tokens: int = 16
    lm_prompts: Tuple[int, ...] = (9, 40, 130, 300)
    lm_new_tokens: int = 32
    # kernels: (batch, image shape) for fused_normalize; (batch, src, dst)
    # for the fused crop; (B, L, H, D) for flash and short attention
    normalize: Tuple[Tuple[int, Tuple[int, int, int]], ...] = (
        (128, (224, 224, 3)), (256, (32, 32, 3)))
    crop: Tuple[int, int, int] = (32, 256, 224)
    flash_bf16: Tuple[int, int, int, int] = (1, 8192, 8, 64)
    # the call of benchmark cell glm-4.7-flash-train-ep8share: head width 256
    flash_bf16_d256: Tuple[int, int, int, int] = (2, 4096, 20, 256)
    flash_fp32: Tuple[int, int, int, int] = (1, 16384, 2, 64)
    short_bf16: Tuple[int, int, int, int] = (128, 197, 12, 64)
    # the gated delta rule as benchmark cell qwen3-next-train-ep16share
    # calls it: (B, L, value heads, head width), chunks of 64
    gated_delta: Tuple[int, int, int, int] = (2, 4096, 32, 128)
    gated_delta_chunk: int = 64
    # and as cell olmo-hybrid-7b-train-8k calls it: (B, L, heads, key
    # width, value width), a state that fills no whole lanes, beta up to 2
    gated_delta_wide: Tuple[int, int, int, int, int] = (1, 8192, 30, 96, 192)
    # a head's norm and turn in one pass: ((B, L, H, d), frequencies,
    # normed) as cells sdar-30b-a3b-train-ep8share-4k (q) and
    # laguna-xs.2-train-ep8share-8k (a full layer's q: half the head) call it
    head_norm_turn: Tuple[Tuple[Tuple[int, int, int, int], int, bool], ...] \
        = (((4, 8192, 32, 128), 64, True), ((2, 8192, 64, 128), 32, False))


FULL = Sizes()


class SmokeFailure(AssertionError):
    """A check failed. Never caught here: it ends the run non-zero."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"# chip_smoke: {msg}", file=sys.stderr, flush=True)


# what jax compiled, from the program's own ledger of its compile events
# (``mmlspark_tpu/observability/compiles.py``, listening since
# ``compile_cache.enable``): every program built or loaded with the seconds
# of its backend stage, and the persistent cache's hits and misses
_COMPILED = {"compiles": "compile.programs", "compile_s": "compile.backend_s",
             "cache_hits": "compile.cache_hits",
             "cache_misses": "compile.cache_misses"}


def compiled() -> Dict[str, float]:
    from mmlspark_tpu.observability import metrics
    now = {key: metrics.counter(name).value
           for key, name in _COMPILED.items()}
    return {k: v if k == "compile_s" else int(v) for k, v in now.items()}


def compiled_since(before: Dict[str, float]) -> Dict[str, float]:
    now = compiled()
    return {"xla_" + k: round(now[k] - before[k], 3) for k in now}


def _rel_err(got, ref) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(bool(np.isfinite(got).all()), "non-finite values in a result")
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


def _digest(*arrays) -> str:
    import numpy as np
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


def _timed(fn):
    """(result, seconds) with the result blocked to completion."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# -- leg 1: the trainer -------------------------------------------------------

def leg_trainer(sz: Sizes) -> Dict[str, Any]:
    """bench.config_train_large's framework side, plus the checks a bench
    never makes: sharding coverage, step count, params moved, zero syncs
    and zero compiles in the steady window."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from mmlspark_tpu.models.zoo import build_model
    from mmlspark_tpu.observability import syncs as obssyncs
    from mmlspark_tpu.ops.pallas_preprocess import make_preprocess_fn
    from mmlspark_tpu.parallel.mesh import mesh_from_config
    from mmlspark_tpu.parallel.trainer import (DeviceEpochCache,
                                               DistributedTrainer)

    bs, steps, n = sz.train_batch, sz.train_steps, sz.train_rows
    shape = (sz.train_image, sz.train_image, 3)
    args = dict(sz.train_model_args)
    rng_np = np.random.default_rng(7)
    images = rng_np.integers(0, 256, size=(n, int(np.prod(shape))),
                             dtype=np.uint8)
    labels = rng_np.integers(0, args["num_classes"],
                             size=(n,)).astype(np.int32)

    module = build_model(sz.train_model, **args)["module"]
    mesh = mesh_from_config()
    pre = make_preprocess_fn(shape, mean=(127.5,) * 3, std=(127.5,) * 3,
                             mesh=mesh)

    def loss_fn(params, batch, rng):
        logits = module.apply(params, pre(batch["image"])).astype(jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["label"]).mean()

    trainer = DistributedTrainer(loss_fn, optax.sgd(0.01, momentum=0.9),
                                 mesh=mesh)
    state, init_s = _timed(lambda: trainer.init(
        lambda: module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1,) + shape, jnp.float32))))
    rng = jax.random.PRNGKey(1)
    cache = DeviceEpochCache({"image": images, "label": labels}, bs,
                             mesh=trainer.mesh)
    epoch = list(cache.batches(0))
    it = itertools.cycle(epoch)

    # every local device holds its share of the state and of each batch
    local = set(jax.local_devices())
    for leaf in jax.tree_util.tree_leaves(state):
        check({s.device for s in leaf.addressable_shards} == local,
              "a train-state leaf does not cover every local device")
    image0 = epoch[0]["image"]
    rows = {s.data.shape[0] for s in image0.addressable_shards}
    check({s.device for s in image0.addressable_shards} == local
          and rows == {bs // len(local)},
          f"batch not sliced over every local device: shard rows {rows}")

    # the state is donated to each step: keep a device copy to diff against
    params0 = jax.tree_util.tree_map(jnp.copy, state["params"])

    def first():
        nonlocal state
        state, m = trainer.train_step(state, next(it), rng)
        return m["loss"]
    loss_first, compile_s = _timed(first)

    before, syncs0 = compiled(), obssyncs.total()
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = trainer.train_step(state, next(it), rng)
    syncs_in_window = obssyncs.total() - syncs0   # before the closing wait
    jax.block_until_ready(m["loss"])
    steady_s = time.perf_counter() - t0
    steady = compiled_since(before)

    ring = trainer.flush_metrics()
    losses = ring["loss"][:steps + 1]
    moved = float(jax.jit(lambda a, b: sum(
        jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)).sum()
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))))(
        state["params"], params0))
    step_count = int(obssyncs.device_get(state["step"],
                                         "chip_smoke.trainer"))

    check(bool(np.isfinite(losses).all()), f"non-finite loss: {losses}")
    check(step_count == steps + 1 == int(ring["step"]),
          f"state step {step_count}, ring step {int(ring['step'])}, "
          f"took {steps + 1}")
    check(moved > 0.0, "params did not change")
    check(syncs_in_window == 0,
          f"{syncs_in_window} host syncs inside the steady window")
    check(steady["xla_compiles"] == 0,
          f"{steady['xla_compiles']} compiles after the first step")
    return {"model": sz.train_model, "batch": bs, "image": sz.train_image,
            "devices": len(local), "batch_rows_per_device": bs // len(local),
            "init_s": round(init_s, 3), "compile_s": round(compile_s, 3),
            "steady_s": round(steady_s, 3), "steps": steps,
            "step_ms": round(steady_s / steps * 1e3, 3),
            "loss_first": round(float(loss_first), 5),
            "loss_last": round(float(losses[-1]), 5),
            "sync_points_per_step": syncs_in_window / steps,
            "steady_compiles": int(steady["xla_compiles"]),
            "param_l1_moved": round(moved, 3)}


# -- leg 2: the server --------------------------------------------------------

def _score_part(server, sz: Sizes) -> Dict[str, Any]:
    import numpy as np
    from mmlspark_tpu.serve.batcher import bucket_for

    entry = server.registry.get("score")
    width = int(np.prod(entry._spec["input_shape"]))
    rng = np.random.default_rng(11)
    requests = [rng.normal(0, 1, (rows, width)).astype(np.float32)
                for rows in sz.score_requests]
    buckets = [bucket_for(x.shape[0], server.buckets) for x in requests]
    check(len(set(buckets)) >= 3,
          f"requests land in buckets {buckets}: want at least three")

    def one_pass():
        return [server.submit("score", x, timeout=RESULT_TIMEOUT_S)
                for x in requests]

    t0 = time.perf_counter()
    first = one_pass()                      # each new bucket compiles here
    compile_s = time.perf_counter() - t0
    built = entry.compile_count + entry.cache_hits
    before = compiled()
    t0 = time.perf_counter()
    outs = one_pass()
    steady_s = time.perf_counter() - t0
    steady_programs = entry.compile_count + entry.cache_hits - built
    check(steady_programs == 0 and compiled_since(before)["xla_compiles"] == 0,
          "the scoring server compiled in steady state")

    # reference: the direct jitted apply on the same bucket-padded rows
    apply = entry.ensure_apply()
    worst = 0.0
    for x, bucket, out, again in zip(requests, buckets, first, outs):
        rows = x.shape[0]
        coerced = entry.coerce(x)
        padded = np.zeros((bucket,) + coerced.shape[1:], coerced.dtype)
        padded[:rows] = coerced
        ref = np.asarray(apply._jitted(apply._params, padded),
                         np.float32)[:rows]
        check(out.shape == ref.shape, f"served {out.shape}, want {ref.shape}")
        check(np.array_equal(out, again),
              "the same request scored differently the second time")
        worst = max(worst, _rel_err(out, ref))
    check(worst <= BF16_REL_TOL,
          f"served scores off the direct apply by {worst:.3g} "
          f"(tolerance {BF16_REL_TOL})")
    return {"model": sz.score_model,
            "buckets": sorted(set(int(b) for b in buckets)),
            "compile_s": round(compile_s, 3), "steady_s": round(steady_s, 3),
            "programs_compiled": entry.compile_count,
            "programs_loaded": entry.cache_hits,
            "steady_compiles": int(steady_programs),
            "rel_err_vs_direct_apply": round(worst, 6),
            "digest": _digest(*outs)}


def _generate_part(server, sz: Sizes) -> Dict[str, Any]:
    import numpy as np
    from mmlspark_tpu.serve.batcher import bucket_for

    lane = server.enable_generate("lm")
    gen, entry = lane.gen, server.registry.get("lm")
    check(gen.max_seq_len == sz.lm_max_seq_len,
          f"lane max_seq_len {gen.max_seq_len} != {sz.lm_max_seq_len}")
    new = sz.lm_new_tokens

    def prompts(seed: int) -> List[np.ndarray]:
        rng = np.random.default_rng(seed)
        return [rng.integers(1, gen.vocab, size=lp).astype(np.int32)
                for lp in sz.lm_prompts]

    def one_round(ps) -> List[Dict[str, Any]]:
        # four sequences in flight at once: admitted back to back, decoded
        # together by the continuous batcher
        futures = [server.submit_generate("lm", p.tolist(),
                                          max_new_tokens=new) for p in ps]
        return [f.result(RESULT_TIMEOUT_S) for f in futures]

    warm_prompts, steady_prompts = prompts(21), prompts(22)
    t0 = time.perf_counter()
    warm = one_round(warm_prompts)
    # the ramp alone can skip a decode bucket a later drain-down then hits
    for lp in sz.lm_prompts:
        gen.program_for("prefill", bucket_for(lp, gen.prefill_buckets))
    for b in gen.decode_buckets:
        gen.program_for("decode", b)
    compile_s = time.perf_counter() - t0

    built = entry.compile_count + entry.cache_hits
    t0 = time.perf_counter()
    steady = one_round(steady_prompts)
    steady_s = time.perf_counter() - t0
    steady_programs = entry.compile_count + entry.cache_hits - built
    check(steady_programs == 0,
          f"{steady_programs} generate programs built after the warm round")

    # full-recompute reference, teacher-forced on the served tokens: one
    # fixed-shape forward per sequence (causal masking makes the trailing
    # pad harmless), read at each generated position
    apply = entry.ensure_apply()
    agree = first_agree = total = 0
    worst_gap = 0.0
    for ps, results in ((warm_prompts, warm), (steady_prompts, steady)):
        for p, res in zip(ps, results):
            toks = res["tokens"]
            check(len(toks) == new and res["finish_reason"] == "length",
                  f"sequence ended early: {len(toks)} tokens, "
                  f"{res['finish_reason']}")
            buf = np.zeros((1, gen.max_seq_len), np.int32)
            buf[0, :p.size] = p
            buf[0, p.size:p.size + new] = toks
            logits = np.asarray(apply._jitted(apply._params, buf),
                                np.float32)[0]
            for i, tok in enumerate(toks):
                row = logits[p.size - 1 + i]
                hit = int(np.argmax(row)) == tok
                agree += hit
                total += 1
                if i == 0:
                    first_agree += hit
                    gap = float(row.max() - row[tok]) / float(
                        np.abs(row).max())
                    worst_gap = max(worst_gap, gap)
    check(worst_gap <= LOGIT_GAP_TOL,
          f"a first served token trails the full-recompute argmax by "
          f"{worst_gap:.3g} of the logit scale (tolerance {LOGIT_GAP_TOL})")
    stats = lane.stats()
    return {"model": sz.lm_model, "vocab": gen.vocab, "dim": gen.dim,
            "depth": gen.depth, "heads": gen.heads,
            "max_seq_len": gen.max_seq_len, "prompts": list(sz.lm_prompts),
            "new_tokens": new, "compile_s": round(compile_s, 3),
            "steady_s": round(steady_s, 3),
            "programs_compiled": entry.compile_count,
            "programs_loaded": entry.cache_hits,
            "steady_compiles": int(steady_programs),
            "decode_steps": int(stats["steps"]),
            "kv_blocks": gen.kv.num_blocks,
            "first_step_logit_gap": round(worst_gap, 6),
            "first_token_agreement": round(first_agree / (2 * len(
                sz.lm_prompts)), 4),
            "token_agreement": round(agree / total, 4),
            "ttft_ms": [round(r["ttft_ms"], 1) for r in steady],
            "digest": _digest(*[r["tokens"] for r in warm + steady])}


def leg_server(sz: Sizes) -> Dict[str, Any]:
    from mmlspark_tpu import compile_cache
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.serve import Server
    from mmlspark_tpu.utils import config as mmlconfig

    keys = {"generate.max_seq_len": sz.lm_max_seq_len,
            "generate.kv_block_tokens": sz.lm_block_tokens}
    prior = {k: mmlconfig.get(k) for k in keys}
    for k, v in keys.items():
        mmlconfig.set(k, v)
    aot0 = compile_cache.stats()
    t0 = time.perf_counter()
    score = JaxModel(inputCol="x", outputCol="y", computeDtype="bfloat16")
    score.set_model(sz.score_model, seed=0, **dict(sz.score_model_args))
    lm = JaxModel().set_model(sz.lm_model, seed=0)
    server = Server({"score": score, "lm": lm},
                    max_batch=sz.score_max_batch)
    load_s = time.perf_counter() - t0
    try:
        scoring = _score_part(server, sz)
        generate = _generate_part(server, sz)
    finally:
        server.close()
        for k, v in prior.items():
            mmlconfig.set(k, v)
    aot = {k: v - aot0[k] for k, v in compile_cache.stats().items()}
    return {"placement": "device 0 only (JaxModel without meshSpec)",
            "load_s": round(load_s, 3),
            "compile_s": round(scoring["compile_s"]
                               + generate["compile_s"], 3),
            "steady_s": round(scoring["steady_s"] + generate["steady_s"], 3),
            "aot_cache": aot, "score": scoring, "generate": generate}


# -- leg 3: the kernels -------------------------------------------------------

def _require_mosaic(jitted, args, what: str, rehearsal: bool,
                    calls: int = 1) -> None:
    """The lowered program must hold ``calls`` Mosaic custom calls:
    neither the Pallas interpreter nor a jnp reference lowers to one."""
    if rehearsal:
        return
    found = jitted.lower(*args).as_text().count("tpu_custom_call")
    check(found >= calls, f"{what}: {found} Mosaic call(s) in the lowered "
                          f"program, {calls} expected")


def _kernel_run(jitted, args) -> Tuple[Any, float, float]:
    out, compile_s = _timed(lambda: jitted(*args))
    out, steady_s = _timed(lambda: jitted(*args))
    return out, compile_s, steady_s


def _qkvw(shape, dtype):
    import jax
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def _flash_pair():
    """(kernel-or-raise, jnp reference), both causal, both jitted."""
    import jax
    from mmlspark_tpu.parallel.sequence import full_attention
    return (jax.jit(lambda q, k, v: full_attention(
                q, k, v, causal=True, use_flash="require")),
            jax.jit(lambda q, k, v: full_attention(
                q, k, v, causal=True, use_flash="never")))


def kernel_normalize(sz: Sizes, rehearsal: bool, record) -> None:
    """fused_normalize: uint8 rows -> normalized bf16, bench train widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mmlspark_tpu.ops.pallas_preprocess import make_preprocess_fn

    rng = np.random.default_rng(5)
    mean, std = (125.3, 123.0, 113.9), (63.0, 62.1, 66.7)
    for b, shape in sz.normalize:
        n = int(np.prod(shape))
        u8 = rng.integers(0, 256, size=(b, n), dtype=np.uint8)
        fn = jax.jit(make_preprocess_fn(shape, mean=mean, std=std))
        _require_mosaic(fn, (u8,), f"fused_normalize {b}x{n}", rehearsal)
        got, c, s = _kernel_run(fn, (u8,))
        ref = (u8.reshape((-1,) + shape).astype(np.float32)
               - np.asarray(mean, np.float32)) / np.asarray(std, np.float32)
        err = _rel_err(got, ref)
        check(got.shape == ref.shape and got.dtype == jnp.bfloat16
              and err <= BF16_REL_TOL,
              f"fused_normalize {b}x{n}: rel err {err:.3g}")
        record(f"fused_normalize_{b}x{n}", (b, n), c, s, err)


def kernel_crop(sz: Sizes, rehearsal: bool, record) -> None:
    """Fused crop + normalize: bench.config_vit_preprocess's kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mmlspark_tpu.ops.pallas_preprocess import make_fused_preprocess_fn

    b, src, dst = sz.crop
    u8 = np.random.default_rng(4).integers(
        0, 256, size=(b, src * src * 3), dtype=np.uint8)
    fn = jax.jit(make_fused_preprocess_fn(
        (src, src, 3), crop=(dst, dst), mean=(127.5,) * 3,
        std=(127.5,) * 3, out_dtype=jnp.bfloat16))
    _require_mosaic(fn, (u8,), "fused crop+normalize", rehearsal)
    got, c, s = _kernel_run(fn, (u8,))
    off = (src - dst) // 2
    ref = (u8.reshape(b, src, src, 3)[:, off:off + dst, off:off + dst]
           .astype(np.float32) - 127.5) / 127.5
    err = _rel_err(got, ref)
    check(got.shape == ref.shape and err <= BF16_REL_TOL,
          f"fused crop+normalize: rel err {err:.3g}")
    record(f"fused_crop_{src}to{dst}_b{b}", (b, src, src, 3), c, s, err)


def kernel_flash_forward(sz: Sizes, rehearsal: bool, record) -> None:
    """Causal flash forward at bench width, then one fp32 case on the
    edge of supports()."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops import pallas_attention

    flash, reference = _flash_pair()
    for name, shape, dtype, tol in (
            ("flash_fwd_bf16", sz.flash_bf16, jnp.bfloat16, BF16_REL_TOL),
            ("flash_fwd_fp32", sz.flash_fp32, jnp.float32, FP32_REL_TOL)):
        q, k, v, _ = _qkvw(shape, dtype)
        _require_mosaic(flash, (q, k, v), name, rehearsal)
        got, c, s = _kernel_run(flash, (q, k, v))
        err = _rel_err(got, reference(q, k, v))
        check(got.shape == shape and err <= tol, f"{name}: rel err {err:.3g}")
        record(name, shape, c, s, err)
    if not rehearsal:
        # the fp32 case sits ON the edge: one more block is refused
        b, L, h, d = sz.flash_fp32
        check(pallas_attention.supports((b, L, h, d))
              and not pallas_attention.supports(
                  (b, L + pallas_attention.BLOCK_K, h, d)),
              "flash_fp32 is not on the edge of supports()")


def kernel_flash_backward(sz: Sizes, rehearsal: bool, record) -> None:
    """Gradients through the flash kernel, forward and backward both
    Mosaic calls, at bench width (head width 64) and at the call shape of
    the benchmark's language-model cell (head width 256)."""
    import jax
    import jax.numpy as jnp

    flash, reference = _flash_pair()
    # reference gradients one (row, head) at a time: the whole-tensor jnp
    # backward would hold several L x L fp32 score matrices per head at once
    ref_grad = jax.jit(jax.grad(
        lambda q, k, v, w: (reference(q, k, v).astype(jnp.float32)
                            * w).sum(), argnums=(0, 1, 2)))
    for name, shape in (("flash_bwd_bf16", sz.flash_bf16),
                        ("flash_bwd_bf16_d256", sz.flash_bf16_d256)):
        q, k, v, w = _qkvw(shape, jnp.bfloat16)
        w = w.astype(jnp.float32)
        grad = jax.jit(jax.grad(
            lambda q, k, v: (flash(q, k, v).astype(jnp.float32) * w).sum(),
            argnums=(0, 1, 2)))
        _require_mosaic(grad, (q, k, v), name, rehearsal, calls=2)
        got, c, s = _kernel_run(grad, (q, k, v))
        err = 0.0
        for b in range(shape[0]):
            for i in range(shape[2]):
                want = ref_grad(*(x[b:b + 1, :, i:i + 1]
                                  for x in (q, k, v, w)))
                err = max(err, *(_rel_err(g[b:b + 1, :, i:i + 1], r)
                                 for g, r in zip(got, want)))
        check(err <= BF16_REL_TOL, f"{name}: rel err {err:.3g}")
        record(name, shape, c, s, err)


def kernel_short_attention(sz: Sizes, rehearsal: bool, record) -> None:
    """The short-sequence kernel, forward and backward, at the shape every
    ViT-B/16 block calls it with (non-causal, 197 ragged tokens): a jax
    upgrade that breaks its Mosaic lowering is caught here, before a
    benchmark run."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops import pallas_attention
    from mmlspark_tpu.parallel.sequence import full_attention

    check(pallas_attention.supports_short(sz.short_bf16)
          and not pallas_attention.supports(sz.short_bf16),
          "short_bf16 is not a shape the short kernel takes")
    q, k, v, w = _qkvw(sz.short_bf16, jnp.bfloat16)
    w = w.astype(jnp.float32)

    def both(use_flash):
        def loss(q, k, v):
            out = full_attention(q, k, v, causal=False, use_flash=use_flash)
            return (out.astype(jnp.float32) * w).sum(), out
        return jax.jit(lambda q, k, v: jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v))

    short, reference = both("require"), both("never")
    _require_mosaic(short, (q, k, v), "short attention", rehearsal)
    ((_, out), grads), c, s = _kernel_run(short, (q, k, v))
    (_, ref_out), ref_grads = reference(q, k, v)
    err = max(_rel_err(g, r) for g, r in zip((out,) + grads,
                                             (ref_out,) + ref_grads))
    check(err <= BF16_REL_TOL, f"short attention: rel err {err:.3g}")
    record("short_fwd_bwd_bf16", sz.short_bf16, c, s, err)


def kernel_gated_delta(sz: Sizes, rehearsal: bool, record) -> None:
    """The chunked gated delta rule (``ops/linear_attention.py``: the
    chunk-local half as the Pallas calls of ``ops/pallas_delta_rule.py``
    where they take the shape, else XLA's batched products; one scan
    either way), forward and backward with bfloat16 operands, against its
    token-by-token float32 form: the worst relative error over the output
    and the five gradients, and the path the trace took (the counter
    ``linear_attention.chunk_calls.*``). Three times: q and k at
    value-head width, at key-head width (half the heads), the shape
    ``GatedDeltaNet`` sends for ``qwen3_next``, and at a state of 96 x
    192 a head with ``beta`` up to 2, the shape it sends for
    ``olmo_hybrid``. Decays as the model's init makes them (``-A
    softplus(1)``, ``A`` up to 16)."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.observability import metrics as obsmetrics
    from mmlspark_tpu.ops import linear_attention as la
    from mmlspark_tpu.ops import pallas_delta_rule as pdr

    def both(impl, dtype, w):
        def out(*a):
            return la.gated_delta_rule(
                *a, chunk=sz.gated_delta_chunk, impl=impl, dtype=dtype)
        return jax.jit(lambda *a: (out(*a), jax.grad(
            lambda *b: (out(*b) * w).sum(), argnums=(0, 1, 2, 3, 4))(*a)))

    def paths():
        return {p: obsmetrics.counter(
            f"linear_attention.chunk_calls.{p}").value
            for p in ("pallas", "xla")}
    B, L, H, d = sz.gated_delta
    wide = sz.gated_delta_wide
    for name, (B, L, H, dk, dv), key_heads, beta_scale in (
            ("gated_delta_bf16", (B, L, H, d, d), H, 1.0),
            ("gated_delta_bf16_key_heads", (B, L, H, d, d),
             max(1, H // 2), 1.0),
            ("gated_delta_bf16_wide", wide, wide[2], 2.0)):
        shape = (B, L, H, dv)
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        v, w = (jax.random.normal(kk, shape) for kk in ks[2:4])
        g = -jnp.linspace(1e-3, 16.0, H) * jax.nn.softplus(
            1.0 + jax.random.normal(ks[4], (B, L, H)))
        beta = beta_scale * jax.nn.sigmoid(
            jax.random.normal(ks[5], (B, L, H)))
        q, k = (la.l2_normalize(jax.random.normal(kk, (B, L, key_heads, dk)))
                for kk in ks[:2])
        args = (q, k, v, g, beta)
        before = paths()
        chunked = both("chunked", jnp.bfloat16, w)
        if pdr.supports(sz.gated_delta_chunk, key_heads, H, dk, dv):
            _require_mosaic(chunked, args, name, rehearsal, calls=2)
        (got, got_g), c, s = _kernel_run(chunked, args)
        took = [p for p, n in paths().items() if n > before[p]]
        want, want_g = both("recurrent", jnp.float32, w)(*args)
        err = max(_rel_err(got, want),
                  *(_rel_err(a, b) for a, b in zip(got_g, want_g)))
        check(got.shape == shape and err <= BF16_REL_TOL,
              f"{name}: rel err {err:.3g}")
        check(len(took) == 1, f"{name}: the trace took {took}")
        record(name, shape, c, s, err, path=took[0], key_heads=key_heads)


def kernel_head_norm_turn(sz: Sizes, rehearsal: bool, record) -> None:
    """``ops/pallas_head_norm_turn`` forward and backward against the form
    other shapes keep (``parts.RMSNorm``'s arithmetic, then
    ``parts.rotary``, float32 through both): the worst relative error over
    the turned rows, the rows' gradient and the scale's."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.zoo import parts
    from mmlspark_tpu.ops import pallas_head_norm_turn as hnt

    eps = 1e-6
    for shape, n, normed in sz.head_norm_turn:
        L, d = shape[1], shape[3]
        freqs = parts.plain_frequencies(2 * n, 1e6)
        y, w = (x.astype(jnp.bfloat16)
                for x in _qkvw(shape, jnp.float32)[:2])
        scale = jnp.linspace(0.5, 1.5, d) if normed else None
        cos, sin = parts.rotary_tables(L, d, freqs)

        def fused(y, scale):
            return hnt.head_norm_turn(y, cos, sin, n, scale,
                                      eps if normed else None)

        def plain(y, scale):
            y = y.astype(jnp.float32)
            if normed:
                y = y * jax.lax.rsqrt(jnp.mean(
                    jnp.square(y), -1, keepdims=True) + eps) * scale
            return parts.rotary(y, freqs)

        def both(f):
            # (``w`` an argument: a turn's derivative without a norm reads
            # the cotangent alone, and a program of constants is folded by
            # XLA's evaluator, not run)
            return jax.jit(lambda y, scale, w: (f(y, scale), jax.grad(
                lambda *a: (f(*a).astype(jnp.float32) * w).sum(),
                argnums=(0, 1) if normed else 0)(y, scale)))
        name = f"head_norm_turn_d{d}_n{n}" + ("_normed" if normed else "")
        check(hnt.supports(shape, n), f"{name}: supports() declines {shape}")
        _require_mosaic(both(fused), (y, scale, w), name, rehearsal, calls=2)
        (got, got_g), c, s = _kernel_run(both(fused), (y, scale, w))
        want, want_g = both(plain)(y, scale, w)
        err = max(_rel_err(a, b) for a, b in zip(
            jax.tree_util.tree_leaves((got, got_g)),
            jax.tree_util.tree_leaves((want, want_g))))
        check(got.shape == shape and err <= BF16_REL_TOL,
              f"{name}: rel err {err:.3g}")
        record(name, shape, c, s, err)


def kernel_flash_sharded(sz: Sizes, rehearsal: bool, record) -> None:
    """Data-parallel flash on a multi-device host: each device runs the
    kernel on its own batch row (nothing to do on one device)."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.parallel.mesh import mesh_from_config
    from mmlspark_tpu.parallel.sequence import make_attention_fn
    from mmlspark_tpu.parallel.sharding import batch_sharding

    n_dev = len(jax.devices())
    if n_dev == 1:
        return
    flash, _ = _flash_pair()
    mesh = mesh_from_config()
    attn = make_attention_fn(mesh, "full")
    shape = (n_dev,) + tuple(sz.flash_bf16[1:])
    q, k, v = (jax.device_put(x, batch_sharding(mesh))
               for x in _qkvw(shape, jnp.bfloat16)[:3])
    sharded = jax.jit(lambda q, k, v: attn(
        q, k, v, causal=True, use_flash="require"))
    with mesh:
        _require_mosaic(sharded, (q, k, v), "sharded flash", rehearsal)
        got, c, s = _kernel_run(sharded, (q, k, v))
    check({sh.device for sh in got.addressable_shards}
          == set(jax.local_devices())
          and {sh.data.shape[0] for sh in got.addressable_shards} == {1},
          "sharded flash output is not one batch row per device")
    err = max(_rel_err(got[i:i + 1], flash(
        *(jax.device_put(x[i:i + 1], jax.devices()[0])
          for x in (q, k, v)))) for i in range(n_dev))
    check(err <= 1e-6,
          f"sharded flash differs from one-device flash by {err:.3g}")
    record(f"flash_fwd_bf16_sharded_x{n_dev}", shape, c, s, err)


KERNEL_CHECKS = (kernel_normalize, kernel_crop, kernel_flash_forward,
                 kernel_flash_backward, kernel_gated_delta,
                 kernel_short_attention, kernel_head_norm_turn,
                 kernel_flash_sharded)


def leg_kernels(sz: Sizes, rehearsal: bool) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    compile_s = steady_s = 0.0

    def record(name: str, shape, c: float, s: float, err: float,
               **more) -> None:
        nonlocal compile_s, steady_s
        compile_s += c
        steady_s += s
        out[name] = {"shape": list(shape), "compile_s": round(c, 3),
                     "steady_ms": round(s * 1e3, 3), "rel_err": round(err, 6),
                     **more}

    for kernel_check in KERNEL_CHECKS:
        kernel_check(sz, rehearsal, record)
    return {"compile_s": round(compile_s, 3), "steady_s": round(steady_s, 3),
            "mosaic_lowering_proven": not rehearsal, "kernels": out}


# -- driver -------------------------------------------------------------------

def run(sizes: Sizes = FULL, *, rehearsal: bool = False,
        default_cache_dir: str = os.path.join(HERE, ".jax_cache")
        ) -> Dict[str, Any]:
    """All three legs; returns the report ``emit`` prints.
    ``rehearsal=True`` is the tier-1 CPU drive: any backend is accepted
    and the Mosaic-lowering proofs are skipped (the kernels run in the
    Pallas interpreter there). Everything else is checked the same."""
    import flax
    import jax
    import jaxlib
    from mmlspark_tpu import compile_cache

    backend = jax.default_backend()
    if backend != "tpu" and not rehearsal:
        raise SystemExit(
            f"chip_smoke: needs a TPU, but jax initialized the {backend!r} "
            f"backend ({jax.devices()[0].device_kind}); refusing to run — "
            "a pass from any other device would say nothing about the chip")
    cache_dir = compile_cache.enable(default_cache_dir)
    device = jax.devices()[0]
    t_start = time.perf_counter()
    started = compiled()
    legs: Dict[str, Any] = {}
    for name, leg in (
            ("trainer", lambda: leg_trainer(sizes)),
            ("server", lambda: leg_server(sizes)),
            ("kernels", lambda: leg_kernels(sizes, rehearsal))):
        say(f"{name} ...")
        before, t0 = compiled(), time.perf_counter()
        legs[name] = leg()
        legs[name].update(compiled_since(before),
                          wall_s=round(time.perf_counter() - t0, 3))
        say(f"{name} ok: {json.dumps(legs[name])}")
    totals = compiled_since(started)
    return {
        "ok": True,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())},
        "versions": {"python": sys.version.split()[0],
                     "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": importlib.metadata.version("libtpu"),
                     "flax": flax.__version__},
        "cache": {"dir": cache_dir,
                  "from_env": bool(os.environ.get(compile_cache.ENV_VAR)),
                  "xla_hits": totals["xla_cache_hits"],
                  "xla_misses": totals["xla_cache_misses"],
                  "xla_compile_s": totals["xla_compile_s"],
                  "aot": compile_cache.stats()},
        "wall_s": round(time.perf_counter() - t_start, 3),
        "legs": legs,
    }


def emit(report: Dict[str, Any]) -> None:
    """The report, then as the LAST line of stdout the verdict: ``ok`` and
    ``device`` and no other key — the driver parses that line strictly."""
    print(json.dumps(report), flush=True)
    print(json.dumps({"ok": report["ok"], "device": report["device"]}),
          flush=True)


def main() -> int:
    sys.path.insert(0, HERE)
    import mmlspark_tpu
    if os.path.dirname(os.path.abspath(mmlspark_tpu.__file__)) \
            != os.path.join(HERE, "mmlspark_tpu"):
        raise SystemExit(
            "chip_smoke: run it from a checkout — mmlspark_tpu resolved to "
            f"{mmlspark_tpu.__file__}, not the package beside this file")
    emit(run())
    return 0


if __name__ == "__main__":
    sys.exit(main())
