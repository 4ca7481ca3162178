"""Persistent compilation cache (mmlspark_tpu/compile_cache.py) + the
device-fused eval sync contract.

The acceptance spine (ISSUE 8):

- a second serve startup against a warm ``runtime.compile_cache_dir``
  skips every bucket compile (hit counters > 0, ``compile_count == 0``)
  and returns BIT-IDENTICAL scores;
- corrupt entries, stale-toolchain entries, and concurrent writers all
  fall back to a fresh compile — with a quarantine/stale event and
  bit-identical scores — never to a wrong or torn program;
- ``Fleet.rollout``'s warm path routes through the cache;
- identical padded bucket shapes share ONE compiled program
  (``ModelEntry._program_key`` dedupe);
- ``ComputeModelStatistics`` performs exactly ONE counted host sync per
  call on the device path (the ``observability.sync_points.evaluate.*``
  counters);
- benchgate treats ``compile_ms``/``cold_start_ms`` as informational.
"""
import json
import os
import threading

import numpy as np
import pytest

from mmlspark_tpu import compile_cache
from mmlspark_tpu.models.jax_model import JaxModel
from mmlspark_tpu.observability import events, metrics
from mmlspark_tpu.serve import Server
from mmlspark_tpu.serve import registry as registry_mod
from mmlspark_tpu.utils import config


@pytest.fixture(autouse=True)
def _clean_slate():
    metrics.get_registry().reset()
    config.unset("runtime.compile_cache_dir")
    yield
    metrics.get_registry().reset()
    config.unset("runtime.compile_cache_dir")


@pytest.fixture()
def cache_dir(tmp_path):
    d = str(tmp_path / "ccache")
    config.set("runtime.compile_cache_dir", d)
    return d


@pytest.fixture()
def events_file(tmp_path):
    path = str(tmp_path / "events.jsonl")
    config.set("observability.events_path", path)
    yield path
    config.unset("observability.events_path")
    events.close()


def _load_events(path):
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def make_model(dim=8, classes=3, seed=0):
    m = JaxModel(inputCol="x", outputCol="y", miniBatchSize=8)
    m.set_model("mlp_tabular", input_dim=dim, hidden=[16],
                num_classes=classes, seed=seed)
    return m


def _jitted_and_params():
    """A minimal (jitted, params) pair shaped like the registry's AOT
    seam: the program is called as ``program(params, x)``."""
    import jax

    params = {"w": np.arange(32, dtype=np.float32).reshape(8, 4)}
    jitted = jax.jit(lambda p, x: x @ p["w"])
    return jitted, params


def _entry_path(root, model="m", version="v1", bucket=4, row=(8,),
                dtype="float32"):
    return os.path.join(
        root, "aot",
        compile_cache.entry_key(model, version, bucket, row, dtype)
        + ".xprog")


# -- load_or_compile core ----------------------------------------------------

def test_bypass_when_cache_dir_unset():
    jitted, params = _jitted_and_params()
    res = compile_cache.load_or_compile("m", "v1", 4, (8,), np.float32,
                                        jitted, params)
    assert res.source == "bypass" and not res.hit
    x = np.ones((4, 8), np.float32)
    np.testing.assert_array_equal(np.asarray(res.program(params, x)),
                                  x @ params["w"])
    assert compile_cache.stats()["bypasses"] == 1
    assert compile_cache.stats()["stores"] == 0


def test_miss_stores_then_hit_is_bit_identical(cache_dir, events_file):
    jitted, params = _jitted_and_params()
    x = np.linspace(-1, 1, 32, dtype=np.float32).reshape(4, 8)

    first = compile_cache.load_or_compile("m", "v1", 4, (8,), np.float32,
                                          jitted, params)
    assert first.source == "miss"
    assert os.path.exists(_entry_path(cache_dir))

    second = compile_cache.load_or_compile("m", "v1", 4, (8,), np.float32,
                                           jitted, params)
    assert second.hit
    np.testing.assert_array_equal(np.asarray(first.program(params, x)),
                                  np.asarray(second.program(params, x)))
    st = compile_cache.stats()
    assert st["hits"] == 1 and st["misses"] == 1 and st["stores"] == 1
    events.close()
    names = [e["name"] for e in _load_events(events_file)
             if e.get("type") == "compile_cache"]
    assert "miss" in names and "store" in names and "hit" in names


def test_corrupt_entry_quarantined_to_fresh_compile(cache_dir, events_file):
    jitted, params = _jitted_and_params()
    x = np.ones((4, 8), np.float32)
    ref = np.asarray(compile_cache.load_or_compile(
        "m", "v1", 4, (8,), np.float32, jitted, params).program(params, x))

    path = _entry_path(cache_dir)
    with open(path, "rb") as f:
        good = f.read()
    # flip bits in the BODY: the header still parses, sha256 must catch it
    with open(path, "wb") as f:
        f.write(good[:-16] + b"\x00" * 16)

    res = compile_cache.load_or_compile("m", "v1", 4, (8,), np.float32,
                                        jitted, params)
    assert not res.hit
    np.testing.assert_array_equal(np.asarray(res.program(params, x)), ref)
    assert os.path.exists(path + ".corrupt")   # evidence kept aside
    assert os.path.exists(path)                # fresh store replaced it
    assert compile_cache.stats()["quarantined"] == 1
    events.close()
    quar = [e for e in _load_events(events_file)
            if e.get("type") == "compile_cache"
            and e.get("name") == "quarantine"]
    assert quar and "sha256" in quar[0]["reason"]

    # garbage header (not even JSON) quarantines too
    with open(path, "wb") as f:
        f.write(b"\x00garbage\n\x01\x02")
    res = compile_cache.load_or_compile("m", "v1", 4, (8,), np.float32,
                                        jitted, params)
    assert not res.hit
    np.testing.assert_array_equal(np.asarray(res.program(params, x)), ref)
    assert compile_cache.stats()["quarantined"] == 2


def test_stale_toolchain_entry_bypassed_and_overwritten(cache_dir,
                                                        events_file):
    jitted, params = _jitted_and_params()
    x = np.ones((4, 8), np.float32)
    ref = np.asarray(compile_cache.load_or_compile(
        "m", "v1", 4, (8,), np.float32, jitted, params).program(params, x))

    # rewrite the header with a different jax-version fingerprint, body
    # intact — exactly what a jax upgrade leaves behind
    path = _entry_path(cache_dir)
    with open(path, "rb") as f:
        header = json.loads(f.readline())
        body = f.read()
    header["env"] = "jax=0.0.1|jaxlib=0.0.1|platform=cpu|kind=cpu|n=1"
    with open(path, "wb") as f:
        f.write(json.dumps(header, sort_keys=True).encode() + b"\n" + body)

    res = compile_cache.load_or_compile("m", "v1", 4, (8,), np.float32,
                                        jitted, params)
    assert res.source == "stale" and not res.hit
    np.testing.assert_array_equal(np.asarray(res.program(params, x)), ref)
    assert compile_cache.stats()["stale"] == 1
    events.close()
    stale = [e for e in _load_events(events_file)
             if e.get("type") == "compile_cache" and e.get("name") == "stale"]
    assert stale and stale[0]["entry_env"].startswith("jax=0.0.1")

    # the fresh compile overwrote the entry for THIS environment: next
    # lookup is a clean hit
    assert compile_cache.load_or_compile(
        "m", "v1", 4, (8,), np.float32, jitted, params).hit


def test_concurrent_writers_never_tear_the_entry(cache_dir):
    """Two writers racing on one key (the two-process startup race; tmp
    names are pid+thread unique, publish is ``os.replace``): both
    compile fresh, last store wins WHOLE, and a reader afterwards gets a
    verified hit — never a torn file."""
    jitted, params = _jitted_and_params()
    x = np.ones((4, 8), np.float32)
    results, errors = [], []

    def writer():
        try:
            results.append(compile_cache.load_or_compile(
                "m", "v1", 4, (8,), np.float32, jitted, params))
        except Exception as e:  # pragma: no cover - the failure mode
            errors.append(e)

    threads = [threading.Thread(target=writer, daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    ref = np.asarray(results[0].program(params, x))
    for r in results[1:]:
        np.testing.assert_array_equal(np.asarray(r.program(params, x)), ref)
    # no tmp droppings survive the race, and the published entry verifies
    aot = os.path.join(cache_dir, "aot")
    assert all(n.endswith(".xprog") for n in os.listdir(aot))
    final = compile_cache.load_or_compile("m", "v1", 4, (8,), np.float32,
                                          jitted, params)
    assert final.hit
    np.testing.assert_array_equal(np.asarray(final.program(params, x)), ref)


def test_entry_key_separates_models_versions_and_shapes():
    k = compile_cache.entry_key
    base = k("m", "v1", 4, (8,), "float32")
    assert k("m", "v1", 4, (8,), "float32") == base
    assert k("m", "v2", 4, (8,), "float32") != base
    assert k("m2", "v1", 4, (8,), "float32") != base
    assert k("m", "v1", 8, (8,), "float32") != base
    assert k("m", "v1", 4, (16,), "float32") != base
    assert k("m", "v1", 4, (8,), "bfloat16") != base


# -- serve integration -------------------------------------------------------

def test_second_serve_startup_skips_bucket_compiles(cache_dir):
    """The headline acceptance: warm cache dir => the second server's
    buckets load from disk (hit counters > 0, compile count == 0) and
    score bit-identically."""
    X = np.random.default_rng(3).normal(size=(8, 8)).astype(np.float32)

    srv = Server({"mlp": make_model()}, max_batch=8, max_wait_ms=1.0,
                 buckets=(1, 8))
    try:
        cold = [np.asarray(srv.submit("mlp", X[:1], timeout=30)),
                np.asarray(srv.submit("mlp", X, timeout=30))]
        stats1 = srv.stats()
    finally:
        srv.close()
    assert stats1["registry.compiles"] > 0  # first process paid the compiles
    assert compile_cache.stats()["stores"] > 0

    metrics.get_registry().reset()
    srv2 = Server({"mlp": make_model()}, max_batch=8, max_wait_ms=1.0,
                  buckets=(1, 8))
    try:
        warm = [np.asarray(srv2.submit("mlp", X[:1], timeout=30)),
                np.asarray(srv2.submit("mlp", X, timeout=30))]
        stats2 = srv2.stats()
    finally:
        srv2.close()
    assert stats2["registry.compiles"] == 0, \
        "warm startup recompiled a bucket"
    assert stats2["registry.compile_cache_hits"] > 0
    assert compile_cache.stats()["hits"] >= 2
    for c, w in zip(cold, warm):
        np.testing.assert_array_equal(c, w)


def test_uncached_and_cached_servers_score_bit_identically(tmp_path):
    X = np.random.default_rng(5).normal(size=(4, 8)).astype(np.float32)

    def scores():
        srv = Server({"mlp": make_model()}, max_batch=4, max_wait_ms=1.0,
                     buckets=(4,))
        try:
            return np.asarray(srv.submit("mlp", X, timeout=30))
        finally:
            srv.close()

    uncached = scores()                                   # bypass path
    config.set("runtime.compile_cache_dir", str(tmp_path / "cc"))
    cached_miss = scores()                                # compile + store
    cached_hit = scores()                                 # loaded from disk
    np.testing.assert_array_equal(uncached, cached_miss)
    np.testing.assert_array_equal(uncached, cached_hit)


def test_identical_padded_shapes_share_one_program(monkeypatch):
    """Satellite bugfix: dtype spellings / repeated lookups of one padded
    shape must resolve to ONE ``_compile`` call, not one per spelling."""
    key = registry_mod.ModelEntry._program_key
    assert key(4, (8,), "f4") == key(4, (8,), np.float32)
    assert key(4, (8,), np.dtype("float32")) == key(4, (8,), "float32")
    assert key(4, (8,), np.float32) != key(8, (8,), np.float32)

    compiled = []
    orig = registry_mod.ModelEntry._compile

    def spy(self, bucket, row_shape, dtype):
        compiled.append((bucket, tuple(row_shape), np.dtype(dtype).name))
        return orig(self, bucket, row_shape, dtype)

    monkeypatch.setattr(registry_mod.ModelEntry, "_compile", spy)
    entry = registry_mod.ModelEntry("m", make_model())
    x32 = np.zeros((4, 8), np.float32)
    entry.program_for(4, x32)
    entry.program_for(4, x32.astype("f4"))
    entry.program_for(4, np.asarray(x32, np.dtype("float32")))
    assert len(compiled) == 1, f"duplicate compiles: {compiled}"


def test_fleet_rollout_warm_uses_the_cache(cache_dir, events_file):
    """Rollout warms every shifted-in replica through the cache: replica
    1..N-1 (and any later rollout of the same version) load the program
    replica 0 stored instead of recompiling."""
    from mmlspark_tpu.serve import Fleet

    X = np.random.default_rng(9).normal(size=(4, 8)).astype(np.float32)
    fleet = Fleet({"mlp": make_model(seed=0)}, replicas=2,
                  server_kwargs={"max_batch": 4, "max_wait_ms": 1.0,
                                 "buckets": (4,)})
    try:
        fleet.submit("mlp", X)                    # v1 programs in rotation
        report = fleet.rollout("mlp", make_model(seed=1), "v2", warm_x=X)
        assert all(r["status"] == "updated" for r in report["replicas"])
        after = np.asarray(fleet.submit("mlp", X))
    finally:
        fleet.close()

    st = compile_cache.stats()
    assert st["stores"] > 0, "rollout warm never reached the cache seam"
    # replica 0 compiled v2 and stored it; the other replica's warm hit
    assert st["hits"] > 0, "second replica's warm recompiled instead of " \
                           f"loading the stored program ({st})"
    events.close()
    warm_events = [e for e in _load_events(events_file)
                   if e.get("type") == "rollout" and e.get("name") == "warm"]
    assert warm_events and all("compile_cache_hits" in e
                               for e in warm_events)

    # a FRESH fleet of the rolled-out version starts fully warm
    metrics.get_registry().reset()
    fleet2 = Fleet({"mlp": make_model(seed=1)}, replicas=2,
                   server_kwargs={"max_batch": 4, "max_wait_ms": 1.0,
                                  "buckets": (4,)})
    try:
        again = np.asarray(fleet2.submit("mlp", X))
    finally:
        fleet2.close()
    np.testing.assert_array_equal(after, again)


# -- cache_dir / enable: one function decides the directory ------------------

def test_enable_wires_jax_from_config_and_is_idempotent(cache_dir):
    import jax

    prior = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == cache_dir
        assert jax.config.jax_compilation_cache_dir == cache_dir
        assert os.path.isdir(cache_dir)
        assert compile_cache.enable() == cache_dir  # idempotent
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)


def test_enable_noop_when_unset_and_default_dir_when_given(tmp_path):
    import jax

    assert compile_cache.enable() is None
    prior = jax.config.jax_compilation_cache_dir
    default = str(tmp_path / "default_cache")
    try:
        assert compile_cache.enable(default) == default
        # the AOT layer follows the default: one directory for both
        assert compile_cache.cache_dir() == default
        assert jax.config.jax_compilation_cache_dir == default
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)


def test_env_var_is_the_cache_for_both_layers(
        cache_dir, tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, that directory is the cache for
    both layers: it outranks the config key and any entry-point default
    (that no jax.config.update of the directory runs is asserted in
    tests/test_chip_smoke.py)."""
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
    assert compile_cache.cache_dir() == env_dir
    assert compile_cache.enable(str(tmp_path / "default")) == env_dir
    assert compile_cache.worker_env() == {compile_cache.ENV_VAR: env_dir}
    # AOT entries land under it
    jitted, params = _jitted_and_params()
    res = compile_cache.load_or_compile("m", "v1", 4, (8,), np.float32,
                                        jitted, params)
    assert res.source == "miss"
    assert os.path.exists(_entry_path(env_dir))
    assert not os.path.exists(cache_dir)


def test_lane_scopes_aot_entries_under_the_root(cache_dir, tmp_path):
    jitted, params = _jitted_and_params()
    with compile_cache.lane("harness", str(tmp_path / "unused")) as path:
        assert path == os.path.join(cache_dir, "lanes", "harness")
        compile_cache.load_or_compile("m", "v1", 4, (8,), np.float32,
                                      jitted, params)
        assert os.path.exists(_entry_path(path))
    assert not os.path.exists(_entry_path(cache_dir))
    # every entry starts the lane empty; the root outside it is untouched
    compile_cache.load_or_compile("m", "v1", 4, (8,), np.float32,
                                  jitted, params)
    with compile_cache.lane("harness", str(tmp_path / "unused")) as path:
        assert not os.path.exists(_entry_path(path))
    assert os.path.exists(_entry_path(cache_dir))
    # nothing configured: the fallback root carries the lane
    config.unset("runtime.compile_cache_dir")
    fallback = str(tmp_path / "fallback")
    with compile_cache.lane("harness", fallback) as path:
        assert path == os.path.join(fallback, "lanes", "harness")
        assert compile_cache.cache_dir() == fallback
    assert compile_cache.cache_dir() == ""


# -- device-fused eval: the one-sync contract --------------------------------

def _scored_frame(n=64):
    from mmlspark_tpu.core.frame import Frame
    from mmlspark_tpu.core.schema import (
        ColumnSchema, DType, ScoreKind, set_score_column,
    )
    rng = np.random.default_rng(7)
    y = rng.integers(0, 2, n).astype(np.float64)
    s1 = np.clip(rng.normal(0.3 + 0.4 * y, 0.3, n), 0, 1)
    scores = np.stack([1 - s1, s1], axis=1).astype(np.float32)
    frame = Frame.from_dict({"label": y,
                             "scored_labels": (s1 > 0.5).astype(np.float64)})
    frame = frame.with_column_values(
        ColumnSchema("scores", DType.VECTOR), scores)
    schema = set_score_column(frame.schema, "scores", "m1",
                              ScoreKind.SCORES, ScoreKind.CLASSIFICATION)
    schema = set_score_column(schema, "scored_labels", "m1",
                              ScoreKind.SCORED_LABELS,
                              ScoreKind.CLASSIFICATION)
    return Frame(schema, frame.partitions)


def test_eval_device_path_is_exactly_one_counted_sync():
    from mmlspark_tpu.evaluate.compute_model_statistics import (
        ComputeModelStatistics,
    )

    frame = _scored_frame()
    config.set("evaluate.device_rows", 1)
    try:
        ComputeModelStatistics().transform(frame)
    finally:
        config.unset("evaluate.device_rows")
    evaluate_syncs = {
        k: v["value"] for k, v in metrics.get_registry().to_dict().items()
        if k.startswith("observability.sync_points.evaluate.")}
    assert evaluate_syncs == {
        "observability.sync_points.evaluate.finalize": 1.0}, evaluate_syncs

    # a second call costs exactly one more
    config.set("evaluate.device_rows", 1)
    try:
        ComputeModelStatistics().transform(frame)
    finally:
        config.unset("evaluate.device_rows")
    reg = metrics.get_registry().to_dict()
    assert reg["observability.sync_points.evaluate.finalize"]["value"] == 2.0


# -- benchgate: compile_ms is informational ----------------------------------

def test_benchgate_compile_ms_never_red():
    from mmlspark_tpu.observability import benchgate

    base = {"configs": {"serving": {
        "value": 100.0, "compile_ms": 50.0, "cold_start_ms": 80.0}}}
    # compile_ms 10x worse: reported, but the lane stays green
    fresh = {"configs": {"serving": {
        "value": 100.0, "compile_ms": 500.0, "cold_start_ms": 800.0}}}
    verdict = benchgate.compare(fresh, base)
    assert verdict["green"]
    checks = {c["metric"]: c for c in verdict["lanes"]["serving"]["checks"]}
    assert checks["compile_ms"]["informational"]
    assert checks["compile_ms"]["ok"]
    assert checks["cold_start_ms"]["informational"]
    # a genuine value regression still turns the lane red
    fresh["configs"]["serving"]["value"] = 10.0
    assert not benchgate.compare(fresh, base)["green"]


# -- report: the compile_cache section ---------------------------------------

def test_report_renders_compile_cache_section(cache_dir, events_file,
                                              tmp_path):
    from mmlspark_tpu.observability.report import build_report, render_report

    jitted, params = _jitted_and_params()
    compile_cache.load_or_compile("m", "v1", 4, (8,), np.float32,
                                  jitted, params)          # miss + store
    compile_cache.load_or_compile("m", "v1", 4, (8,), np.float32,
                                  jitted, params)          # hit
    events.close()

    r = build_report(events_file)
    cc = r["compile_cache"]
    assert cc["hits"] == 1 and cc["misses"] == 1 and cc["stores"] == 1
    assert cc["hit_rate"] == 50.0
    text = render_report(events_file)
    assert "compile cache:" in text and "50.0% hit rate" in text
