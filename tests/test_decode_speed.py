"""Decode raw-speed features (ISSUE 12): shared-prefix KV reuse,
chunked prefill, speculative decoding, int8 KV blocks.

Same discipline as ``test_generate.py``: CPU, manually stepped lanes,
no threads. The acceptance spine:

- every feature keeps greedy decode BIT-IDENTICAL to the naive
  full-recompute reference (int8 excepted — that one is quality-gated
  in the bench lane, here it just has to run green and buy capacity);
- seeded sampling replays token-identically with speculation on;
- prefix hits/CoW/speculation counters tell the truth;
- warm restart with ALL features enabled still pays zero compiles
  (chunk + verify + cow programs included).
"""
import numpy as np
import pytest

from mmlspark_tpu.models.jax_model import JaxModel
from mmlspark_tpu.observability import metrics
from mmlspark_tpu.serve import Server
from mmlspark_tpu.serve.kvcache import KVCacheManager
from mmlspark_tpu.utils import config

_KEYS = ("generate.max_seq_len", "generate.max_sequences",
         "generate.kv_block_tokens", "generate.max_new_tokens",
         "generate.arena_mb", "generate.prefill_buckets",
         "generate.prefix_cache", "generate.prefill_chunk",
         "generate.kv_dtype", "generate.draft_model",
         "generate.spec_tokens", "runtime.compile_cache_dir")


@pytest.fixture(autouse=True)
def _lane_config():
    prior = {k: config.get(k) for k in _KEYS}
    config.set("generate.max_seq_len", 64)
    config.set("generate.max_sequences", 4)
    config.set("generate.kv_block_tokens", 8)
    metrics.get_registry().reset()
    yield
    for k, v in prior.items():
        config.set(k, v)
    metrics.get_registry().reset()


def make_lm(seed=0):
    return JaxModel().set_model("transformer_lm_tiny", seed=seed)


def _run_lane(srv, lane, futs, max_steps=96):
    for _ in range(max_steps):
        if all(f.done() for f in futs):
            break
        lane.step()
    return [f.result(1) for f in futs]


def _reference_greedy(srv, model, prompt, max_new):
    apply = srv.registry.get(model).ensure_apply()
    toks = list(prompt)
    for _ in range(max_new):
        logits = np.asarray(
            apply._jitted(apply._params, np.asarray([toks], np.int32)))
        toks.append(int(np.argmax(logits[0, -1])))
    return toks[len(prompt):]


SYSTEM = [7, 3, 11, 19, 2, 5, 13, 17, 23, 29, 4, 8, 15, 16, 42, 99,
          31, 37, 41, 43, 47, 53, 59, 61]          # 3 full blocks at bt=8


# -- shared-prefix KV reuse --------------------------------------------------

def test_shared_prefix_partial_hit_bit_identical():
    """Requests diverging after a shared system prompt: the later ones
    ride the cached prefix blocks and still emit the exact reference
    tokens."""
    srv = Server({"lm": make_lm()}, start=False)
    try:
        lane = srv.enable_generate("lm", start=False)
        prompts = [SYSTEM + [100 + i, 200 + i, 55] for i in range(3)]
        outs = []
        for p in prompts:                          # sequential: 2nd+ hit
            f = srv.submit_generate("lm", p, max_new_tokens=5)
            outs.extend(_run_lane(srv, lane, [f]))
        for p, out in zip(prompts, outs):
            assert out["tokens"] == _reference_greedy(srv, "lm", p, 5)
        st = lane.stats()
        assert st["prefix_hits"] >= 6              # 3 blocks x 2 followers
        assert st["kv.used_blocks"] == 0           # all leases returned
        assert lane.gen.kv.check_conservation()
    finally:
        srv.close()


def test_identical_prompt_full_hit_cow_bit_identical():
    """The SAME prompt twice is a full hit: the repeat re-prefills
    nothing, pays one copy-on-write, and emits identical tokens."""
    srv = Server({"lm": make_lm()}, start=False)
    try:
        lane = srv.enable_generate("lm", start=False)
        prompt = SYSTEM[:16]                       # block-aligned prompt
        f0 = srv.submit_generate("lm", prompt, max_new_tokens=6)
        out0, = _run_lane(srv, lane, [f0])
        f1 = srv.submit_generate("lm", prompt, max_new_tokens=6)
        out1, = _run_lane(srv, lane, [f1])
        assert out0["tokens"] == out1["tokens"] \
            == _reference_greedy(srv, "lm", prompt, 6)
        st = lane.stats()
        assert st["prefix_hits"] == 2 and st["cow_copies"] == 1
    finally:
        srv.close()


def test_prefix_cache_concurrent_sharers_and_kill():
    """Sharers in flight TOGETHER: refcounts > 1 on the shared blocks,
    and a mid-flight cancel of one sharer leaves the survivor's blocks
    and output intact."""
    srv = Server({"lm": make_lm()}, start=False)
    try:
        lane = srv.enable_generate("lm", start=False)
        warm = srv.submit_generate("lm", SYSTEM + [1], max_new_tokens=2)
        _run_lane(srv, lane, [warm])               # seed the prefix index
        fa = srv.submit_generate("lm", SYSTEM + [2], max_new_tokens=8)
        fb = srv.submit_generate("lm", SYSTEM + [3], max_new_tokens=8)
        lane.step()                                # both admitted, sharing
        kv = lane.gen.kv
        shared = [b for s in lane.batcher.active
                  for b in kv.blocks_for(s.seq_id)
                  if kv.block_refcount(b) > 1]
        assert shared                              # something IS shared
        # kill one sharer mid-stream (the chaos scenario in miniature)
        victims = [s for s in lane.batcher.active if not s.future.done()]
        lane._fail_seq(victims[0], RuntimeError("killed"))
        lane.batcher.leave(victims[0])
        survivors = [f for f in (fa, fb) if f is not victims[0].future]
        _run_lane(srv, lane, survivors)
        for f in survivors:
            toks = f.result(1)["tokens"]
            assert len(toks) == 8
        assert kv.used_blocks == 0 and kv.check_conservation()
        with pytest.raises(RuntimeError):
            victims[0].future.result(1)
    finally:
        srv.close()


def test_prefix_cache_off_still_bit_identical():
    config.set("generate.prefix_cache", False)
    srv = Server({"lm": make_lm()}, start=False)
    try:
        lane = srv.enable_generate("lm", start=False)
        futs = [srv.submit_generate("lm", SYSTEM + [i], max_new_tokens=4)
                for i in range(2)]
        outs = _run_lane(srv, lane, futs)
        for i, out in enumerate(outs):
            assert out["tokens"] == _reference_greedy(
                srv, "lm", SYSTEM + [i], 4)
        assert lane.stats()["prefix_hits"] == 0    # feature truly off
    finally:
        srv.close()


# -- chunked prefill ---------------------------------------------------------

def test_chunked_prefill_bit_identical_and_interleaved():
    """A long joiner prefilling in chunks must not perturb its own
    tokens OR the already-running sequence it interleaves with."""
    config.set("generate.prefill_chunk", 8)
    config.set("generate.prefix_cache", False)     # isolate the feature
    srv = Server({"lm": make_lm()}, start=False)
    try:
        lane = srv.enable_generate("lm", start=False)
        short = [5, 9, 17]
        f0 = srv.submit_generate("lm", short, max_new_tokens=10)
        lane.step()                                # short is decoding
        long_p = list(range(2, 29))                # 27 tokens -> 4 chunks
        f1 = srv.submit_generate("lm", long_p, max_new_tokens=5)
        # the joiner must NOT monopolize steps: the running sequence
        # keeps emitting while chunks land
        before = len(f0.result(0.0)["tokens"]) if f0.done() else \
            len(lane.batcher.active[0].generated)
        lane.step()
        assert len(lane.batcher.active[0].generated) > before
        outs = _run_lane(srv, lane, [f0, f1])
        assert outs[0]["tokens"] == _reference_greedy(srv, "lm", short, 10)
        assert outs[1]["tokens"] == _reference_greedy(srv, "lm", long_p, 5)
    finally:
        srv.close()


def test_chunked_prefill_with_prefix_cache_combined():
    config.set("generate.prefill_chunk", 8)
    srv = Server({"lm": make_lm()}, start=False)
    try:
        lane = srv.enable_generate("lm", start=False)
        p0 = SYSTEM + [77]
        f0 = srv.submit_generate("lm", p0, max_new_tokens=4)
        out0, = _run_lane(srv, lane, [f0])
        p1 = SYSTEM + [88, 89]                     # hits 3 cached blocks
        f1 = srv.submit_generate("lm", p1, max_new_tokens=4)
        out1, = _run_lane(srv, lane, [f1])
        assert out0["tokens"] == _reference_greedy(srv, "lm", p0, 4)
        assert out1["tokens"] == _reference_greedy(srv, "lm", p1, 4)
        assert lane.stats()["prefix_hits"] >= 3
    finally:
        srv.close()


# -- a block the lane has never seen -----------------------------------------

def test_lane_serves_an_unseen_block_without_an_edit():
    """The lane holds no model mathematics: a trunk whose blocks come
    from a ``block_factory`` and carry a gated feed-forward layer
    (``ffn/mlp_gate``, ``ffn/mlp_up``, ``ffn/mlp_down``) decodes through
    prefill, decode and chunk to the tokens of a full recompute. The
    model is this test's own, not the zoo's."""
    import jax.numpy as jnp
    from mmlspark_tpu.models import zoo
    from mmlspark_tpu.models.zoo.parts import SwiGluMlp
    from mmlspark_tpu.models.zoo.transformer import (
        DecoderBlock, TransformerLM,
    )
    dim, heads, max_len = 64, 4, 128

    def block_factory(i, name):
        return DecoderBlock(
            dim, heads, dtype=jnp.float32, name=name,
            ffn_factory=lambda n: SwiGluMlp(dim, 96, jnp.float32, name=n))

    @zoo.register_model("_test_lm_swiglu")
    def _lm():
        return dict(
            module=TransformerLM(vocab=256, dim=dim, depth=2, heads=heads,
                                 max_len=max_len, dtype=jnp.float32,
                                 block_factory=block_factory),
            input_shape=(max_len,), input_dtype="int32",
            feature_layer="hidden", feature_dim=dim,
            layer_names=["hidden", "logits"], seq_attention=True)

    config.set("generate.prefill_chunk", 8)
    config.set("generate.prefix_cache", False)
    srv = Server({"lm": JaxModel().set_model("_test_lm_swiglu", seed=3)},
                 start=False)
    try:
        lane = srv.enable_generate("lm", start=False)
        params = lane.gen.params["params"]
        assert set(params["block0"]["ffn"]) == {"mlp_gate", "mlp_up",
                                                "mlp_down"}
        short, long_p = [5, 9, 17], list(range(2, 29))   # whole, 4 chunks
        futs = [srv.submit_generate("lm", p, max_new_tokens=6)
                for p in (short, long_p)]
        outs = _run_lane(srv, lane, futs)
        assert {k for k, _ in lane.gen._programs} == {"prefill", "chunk",
                                                      "decode"}
        assert outs[0]["tokens"] == _reference_greedy(srv, "lm", short, 6)
        assert outs[1]["tokens"] == _reference_greedy(srv, "lm", long_p, 6)
    finally:
        srv.close()
        zoo._ZOO.pop("_test_lm_swiglu", None)


# -- speculative decoding ----------------------------------------------------

def _spec_server(draft_seed, spec_tokens=3):
    config.set("generate.draft_model", "draft")
    config.set("generate.spec_tokens", spec_tokens)
    return Server({"lm": make_lm(seed=0), "draft": make_lm(seed=draft_seed)},
                  start=False)


def test_speculative_same_weights_draft_accepts_everything():
    """Draft == target: every proposal verifies, so N tokens arrive in
    ~N/(k+1) steps and the output is still bit-identical."""
    srv = _spec_server(draft_seed=0)
    try:
        lane = srv.enable_generate("lm", start=False)
        assert lane.draft is not None
        prompt = [5, 9, 17, 3, 250]
        f = srv.submit_generate("lm", prompt, max_new_tokens=8)
        out, = _run_lane(srv, lane, [f])
        assert out["tokens"] == _reference_greedy(srv, "lm", prompt, 8)
        st = lane.stats()
        assert st["spec_proposed"] > 0
        assert st["spec_accepted"] == st["spec_proposed"]  # identical draft
        assert st["steps"] <= 4                    # 8 tokens, k=3 -> ceil(8/4)+1
        assert st["draft.kv.used_blocks"] == 0     # draft leases returned too
    finally:
        srv.close()


def test_speculative_divergent_draft_still_bit_identical():
    """A draft with DIFFERENT weights mis-proposes; rejection must leave
    greedy output bit-identical to the non-speculative reference — the
    whole point of the verify step."""
    srv = _spec_server(draft_seed=3)
    try:
        lane = srv.enable_generate("lm", start=False)
        prompts = [[5, 9, 17, 3, 250], [1, 2, 3, 4], [200, 100]]
        futs = [srv.submit_generate("lm", p, max_new_tokens=6)
                for p in prompts]
        outs = _run_lane(srv, lane, futs)
        for p, out in zip(prompts, outs):
            assert out["tokens"] == _reference_greedy(srv, "lm", p, 6)
        st = lane.stats()
        assert st["spec_proposed"] > 0
        assert st["spec_accepted"] <= st["spec_proposed"]
    finally:
        srv.close()


def test_speculative_seeded_sampling_replays_identically():
    """Seeded sampling (temperature > 0) with speculation ON must emit
    the same tokens as the plain lane with the same seed: proposals are
    drawn with the same (seed, position) stream the verifier uses."""
    def run(spec):
        if spec:
            srv = _spec_server(draft_seed=0)
        else:
            config.set("generate.draft_model", "")
            srv = Server({"lm": make_lm(seed=0)}, start=False)
        try:
            lane = srv.enable_generate("lm", start=False)
            f = srv.submit_generate("lm", [5, 9, 17, 3], max_new_tokens=8,
                                    temperature=0.8, top_k=4, seed=1234)
            out, = _run_lane(srv, lane, [f])
            return out["tokens"]
        finally:
            srv.close()

    assert run(spec=True) == run(spec=False)


def test_draft_side_prefix_reuse_counter():
    """The draft arena reuses shared-prefix blocks too: the second
    sequence over the same system prompt re-leases the draft's cached
    blocks, counted by ``generate.draft_prefix_hits`` — and reuse on
    BOTH arenas keeps greedy output bit-identical."""
    srv = _spec_server(draft_seed=0)
    try:
        lane = srv.enable_generate("lm", start=False)
        p0 = SYSTEM + [77]
        f0 = srv.submit_generate("lm", p0, max_new_tokens=4)
        out0, = _run_lane(srv, lane, [f0])
        assert lane.stats()["draft_prefix_hits"] == 0   # cold draft arena
        p1 = SYSTEM + [88, 89]                  # shares 3 full blocks
        f1 = srv.submit_generate("lm", p1, max_new_tokens=4)
        out1, = _run_lane(srv, lane, [f1])
        st = lane.stats()
        assert st["draft_prefix_hits"] >= 3
        assert st["draft_prefix_hits"] <= st["prefix_hits"]
        assert out0["tokens"] == _reference_greedy(srv, "lm", p0, 4)
        assert out1["tokens"] == _reference_greedy(srv, "lm", p1, 4)
    finally:
        srv.close()


def test_speculation_skipped_when_draft_arena_sheds():
    """Draft-side reservation is best-effort: when the draft arena has
    no room the sequence decodes unspeculated instead of shedding."""
    srv = _spec_server(draft_seed=0)
    try:
        lane = srv.enable_generate("lm", start=False)
        # exhaust the draft arena behind the lane's back
        d = lane.draft.kv
        hog = d.try_reserve("hog", d.free_blocks * d.block_tokens)
        assert hog is not None and d.free_blocks == 0
        f = srv.submit_generate("lm", [5, 9, 17], max_new_tokens=4)
        out, = _run_lane(srv, lane, [f])
        assert out["tokens"] == _reference_greedy(srv, "lm", [5, 9, 17], 4)
        assert lane.stats()["spec_proposed"] == 0  # ran plain, not shed
        d.free("hog")
    finally:
        srv.close()


# -- int8 KV blocks ----------------------------------------------------------

def test_int8_arena_buys_capacity_at_fixed_bytes():
    """At a fixed ``generate.arena_mb`` the int8 arena must hold >=1.8x
    the blocks of the fp32 one (the ISSUE's capacity acceptance bar) —
    per-row fp32 scales are the only overhead."""
    config.set("generate.arena_mb", 0.5)
    config.set("generate.kv_dtype", "")
    fp = KVCacheManager.from_config(layers=2, heads=2, head_dim=16)
    config.set("generate.kv_dtype", "int8")
    q = KVCacheManager.from_config(layers=2, heads=2, head_dim=16)
    assert q.quantized and not fp.quantized
    assert q.num_blocks >= 1.8 * fp.num_blocks
    # and the ledger charges the REAL width: int8 arena + scales < fp32
    assert q.arena_bytes() < q.unquantized_arena_bytes()


def test_int8_lane_runs_green_and_reports_width():
    config.set("generate.kv_dtype", "int8")
    srv = Server({"lm": make_lm()}, start=False)
    try:
        lane = srv.enable_generate("lm", start=False)
        assert lane.gen.kv.quantized
        futs = [srv.submit_generate("lm", [5, 9, 17, 3], max_new_tokens=6),
                srv.submit_generate("lm", [1, 2, 3], max_new_tokens=6)]
        outs = _run_lane(srv, lane, futs)
        for out in outs:
            assert len(out["tokens"]) == 6
            assert all(0 <= t < lane.gen.vocab for t in out["tokens"])
        assert lane.stats()["kv.used_blocks"] == 0
    finally:
        srv.close()


# -- warm restart with everything on -----------------------------------------

def test_warm_restart_zero_compiles_all_features(tmp_path):
    """Chunk, verify, and cow programs must flow through the persistent
    program cache like prefill/decode: a restarted process with every
    feature enabled pays ZERO XLA compiles."""
    config.set("runtime.compile_cache_dir", str(tmp_path))
    config.set("generate.prefill_chunk", 8)
    config.set("generate.draft_model", "draft")
    config.set("generate.spec_tokens", 3)

    def run():
        srv = Server({"lm": make_lm(seed=0), "draft": make_lm(seed=0)},
                     start=False)
        try:
            lane = srv.enable_generate("lm", start=False)
            # identical prompts -> full hit -> cow program; long prompt
            # -> chunk program; draft -> verify program
            futs = [srv.submit_generate("lm", SYSTEM[:16], max_new_tokens=4)]
            _run_lane(srv, lane, futs)
            futs = [srv.submit_generate("lm", SYSTEM[:16], max_new_tokens=4),
                    srv.submit_generate("lm", list(range(2, 29)),
                                        max_new_tokens=4)]
            toks = [o["tokens"] for o in _run_lane(srv, lane, futs)]
            compiles = lane.gen.entry.compile_count
            hits = lane.gen.entry.cache_hits
            if lane.draft is not None:
                compiles += lane.draft.entry.compile_count
                hits += lane.draft.entry.cache_hits
            assert lane.stats()["cow_copies"] >= 1   # cow program exercised
            return toks, compiles, hits
        finally:
            srv.close()

    toks_cold, compiles_cold, _ = run()
    toks_warm, compiles_warm, hits_warm = run()
    assert compiles_cold >= 4          # prefill + decode + chunk + verify
    assert compiles_warm == 0          # the whole point
    assert hits_warm >= compiles_cold
    assert toks_warm == toks_cold
