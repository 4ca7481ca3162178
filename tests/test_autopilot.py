"""Autopilot (control/autopilot.py): the SLO-driven fleet control loop.

The acceptance spine, mirroring docs/AUTOPILOT.md:

- :func:`decide` is a PURE function of ``(signals, policy, state)`` —
  every row of the signal -> lever matrix is a table test: queue
  pressure scales up, idleness scales down, an error-rate outlier is
  shifted out and shifted back on recovery, burn tightens admission,
  recovery relaxes it;
- every bound is a VISIBLE veto (max replicas, HBM headroom, admission
  floor) and every hold a visible suppression (cooldown, action-budget
  window) — suppressed decisions carry their replay payload into the
  event stream exactly like actuated ones;
- hysteresis is structural: both directions of a lever share one
  cooldown key, so an A -> B -> A reversal inside one cooldown window
  cannot happen — asserted per-table and under seeded fuzz;
- the closed loop actually moves a live fleet (scale out under queue
  pressure, back down when idle) while served scores stay bit-identical
  to a single server, and the rollout guard aborts a burning canary;
- the decision stream renders in ``mmlspark-tpu report`` and ``top``;
- the chaos scenario (static fleet vs autopiloted fleet, same seeded
  spike + kill) is a pure function of its seed (tier-1 smoke).
"""
import json
import random

import numpy as np
import pytest

from mmlspark_tpu.control.autopilot import (
    Autopilot, AutopilotPolicy, AutopilotState, advance_state,
    cooldown_key, decide, fleet_signals,
)
from mmlspark_tpu.models.jax_model import JaxModel
from mmlspark_tpu.observability import events, metrics
from mmlspark_tpu.serve import Fleet, Server
from mmlspark_tpu.utils import config

_DIM = 4


def _model(seed: int = 7) -> JaxModel:
    m = JaxModel(inputCol="x", outputCol="y", miniBatchSize=8)
    m.set_model("mlp_tabular", input_dim=_DIM, hidden=[8],
                num_classes=3, seed=seed)
    return m


def rep(ready=True, weight=1.0, q=0.0, completed=0.0, failed=0.0):
    return {"ready": ready, "live": ready, "weight": weight,
            "queue_depth": q, "inflight": 0.0,
            "completed": completed, "failed": failed, "shed": 0.0}


def sig(now=1000.0, replicas=None, burning=False, burn_fast=0.0,
        hbm=0.0, admission=None):
    s = {"now": now, "replicas": replicas or {},
         "slo": {"burning": burning, "breaching": False,
                 "burn_fast": burn_fast},
         "memory": {"total_bytes": hbm}}
    if admission:
        s["admission"] = admission
    return s


POLICY = AutopilotPolicy(
    tick_s=30.0, min_replicas=1, max_replicas=4,
    scale_up_queue=4.0, scale_down_queue=0.0, scale_cooldown_s=60.0,
    shift_error_rate=0.5, shift_recover_rate=0.05, shift_step=0.5,
    shift_cooldown_s=40.0, admission_factor=0.5,
    admission_floor_frac=0.25, admission_relax_burn=1.0,
    admission_cooldown_s=60.0, window_s=300.0, max_actions_per_window=8)


def acted(decisions):
    return [d for d in decisions if not d["suppressed"]]


def held(decisions):
    return [d for d in decisions if d["suppressed"]]


# -- policy -------------------------------------------------------------------

def test_policy_from_config_reads_autopilot_keys():
    p = AutopilotPolicy.from_config()
    assert p.min_replicas == int(config.get("autopilot.min_replicas"))
    assert p.max_replicas == int(config.get("autopilot.max_replicas"))
    assert p.scale_up_queue == float(config.get("autopilot.scale_up_queue"))
    assert AutopilotPolicy.from_config(max_replicas=3).max_replicas == 3


@pytest.mark.parametrize("bad", [
    dict(min_replicas=0),
    dict(min_replicas=4, max_replicas=2),
    dict(shift_step=0.0),
    dict(shift_recover_rate=0.9, shift_error_rate=0.5),
    dict(scale_down_queue=9.0, scale_up_queue=4.0),
    dict(admission_factor=1.0),
])
def test_policy_validation_rejects_inverted_hysteresis(bad):
    with pytest.raises(ValueError):
        AutopilotPolicy(**bad)


# -- the decision table -------------------------------------------------------

def test_queue_pressure_scales_up():
    st = AutopilotState()
    s = sig(replicas={"r0": rep(q=6.0), "r1": rep(q=4.0)})
    ds = decide(s, POLICY, st)
    assert [d["action"] for d in acted(ds)] == ["scale_up"]
    d = acted(ds)[0]
    assert d["lever"] == "scale" and d["queue_mean"] == 5.0
    assert d["t"] == 1000.0 and "mean queue" in d["reason"]


def test_scale_cooldown_suppresses_with_replayable_reason():
    st = AutopilotState()
    s = sig(now=1000.0, replicas={"r0": rep(q=6.0)})
    advance_state(st, decide(s, POLICY, st), s, window_s=POLICY.window_s)
    s2 = sig(now=1030.0, replicas={"r0": rep(q=6.0), "r1": rep(q=6.0)})
    ds = decide(s2, POLICY, st)
    assert not acted(ds)
    (d,) = held(ds)
    assert d["reason"].startswith("cooldown:scale")
    assert "wanted:" in d["reason"]        # the held intent is replayable
    # past the cooldown the same pressure acts
    s3 = sig(now=1060.0, replicas={"r0": rep(q=6.0), "r1": rep(q=6.0)})
    assert [d["action"] for d in acted(decide(s3, POLICY, st))] \
        == ["scale_up"]


def test_action_budget_window_holds_excess_actions():
    policy = AutopilotPolicy(max_actions_per_window=1, window_s=300.0)
    st = AutopilotState()
    # two levers want to fire: scale (queue) and admission (burn)
    s = sig(replicas={"r0": rep(q=9.0)}, burning=True, burn_fast=20.0,
            admission={"capacity_rows": 24, "baseline_rows": 24})
    ds = decide(s, policy, st)
    assert len(acted(ds)) == 1
    assert any(d["reason"].startswith("window:1/1") for d in held(ds))


def test_scale_up_vetoed_at_max_replicas():
    policy = AutopilotPolicy(min_replicas=1, max_replicas=2)
    st = AutopilotState()
    s = sig(replicas={"r0": rep(q=9.0), "r1": rep(q=9.0)})
    (d,) = decide(s, policy, st)
    assert d["suppressed"] and d["action"] == "scale_up"
    assert d["reason"].startswith("bounds:max_replicas")


def test_scale_up_vetoed_by_hbm_headroom():
    policy = AutopilotPolicy(max_replicas=8, hbm_limit_bytes=1000)
    st = AutopilotState()
    # 2 live replicas at 900 bytes total: +1 projects 1350 > 1000
    s = sig(replicas={"r0": rep(q=9.0), "r1": rep(q=9.0)}, hbm=900.0)
    (d,) = decide(s, policy, st)
    assert d["suppressed"] and d["reason"].startswith("bounds:hbm")
    assert d["hbm_bytes"] == 900


def test_scale_up_repairs_below_min_even_with_empty_queues():
    policy = AutopilotPolicy(min_replicas=3, max_replicas=6)
    st = AutopilotState()
    s = sig(replicas={"r0": rep(), "r1": rep(), "r2": rep(ready=False)})
    ups = [d for d in acted(decide(s, policy, st))
           if d["action"] == "scale_up"]
    assert len(ups) == 1 and "min" in ups[0]["reason"]


def test_idle_scale_down_picks_highest_numbered_replica():
    st = AutopilotState()
    s = sig(replicas={"r2": rep(), "r10": rep(), "r9": rep()})
    downs = [d for d in acted(decide(s, POLICY, st))
             if d["action"] == "scale_down"]
    assert [d["target"] for d in downs] == ["r10"]


def test_burn_shifts_out_the_erroring_replica_and_tightens_admission():
    st = AutopilotState()
    st.prev = {"r0": {"completed": 10.0, "failed": 0.0},
               "r1": {"completed": 10.0, "failed": 0.0}}
    s = sig(replicas={"r0": rep(completed=20.0, failed=0.0),
                      "r1": rep(completed=10.0, failed=8.0)},
            burning=True, burn_fast=15.0,
            admission={"capacity_rows": 24, "baseline_rows": 24})
    ds = acted(decide(s, POLICY, st))
    by = {d["action"]: d for d in ds}
    assert by["shift_down"]["target"] == "r1"       # not the healthy r0
    assert by["shift_down"]["new_weight"] == 0.5
    assert by["shift_down"]["error_rate"] == 1.0
    assert by["admission_tighten"]["new_capacity"] == 12
    assert "shift_up" not in by and "scale_down" not in by


def test_admission_floor_is_a_visible_veto():
    st = AutopilotState()
    s = sig(burning=True, burn_fast=20.0,
            admission={"capacity_rows": 6, "baseline_rows": 24})
    (d,) = [d for d in decide(s, POLICY, st) if d["lever"] == "admission"]
    assert d["suppressed"] and d["reason"].startswith("bounds:floor")


def test_admission_relaxes_toward_baseline_after_recovery():
    st = AutopilotState()
    s = sig(burning=False, burn_fast=0.2,
            admission={"capacity_rows": 6, "baseline_rows": 24})
    relax = [d for d in acted(decide(s, POLICY, st))
             if d["action"] == "admission_relax"]
    assert relax and relax[0]["new_capacity"] == 12   # one step, not a snap


def test_shift_reversal_cannot_happen_inside_one_cooldown():
    st = AutopilotState()
    st.prev = {"r0": {"completed": 0.0, "failed": 0.0}}
    bad = sig(now=1000.0,
              replicas={"r0": rep(completed=1.0, failed=9.0)})
    ds = decide(bad, POLICY, st)
    assert [d["action"] for d in acted(ds)] == ["shift_down"]
    advance_state(st, ds, bad, window_s=POLICY.window_s)
    # instant recovery: shift_up is WANTED but held by the shared key
    good = sig(now=1010.0,
               replicas={"r0": rep(weight=0.5, completed=21.0,
                                   failed=9.0)})
    ds2 = decide(good, POLICY, st)
    assert not acted(ds2)
    (d,) = held(ds2)
    assert d["reason"].startswith("cooldown:shift:r0")
    advance_state(st, ds2, good, window_s=POLICY.window_s)
    # after the cooldown the recovery acts
    late = sig(now=1040.0,
               replicas={"r0": rep(weight=0.5, completed=41.0,
                                   failed=9.0)})
    ups = acted(decide(late, POLICY, st))
    assert [d["action"] for d in ups] == ["shift_up"]
    assert ups[0]["new_weight"] == 1.0


def test_no_flap_under_seeded_fuzz():
    cooldowns = {"shift": POLICY.shift_cooldown_s,
                 "scale": POLICY.scale_cooldown_s,
                 "admission": POLICY.admission_cooldown_s}
    for seed in range(5):
        rng = random.Random(seed)
        st = AutopilotState()
        log = []
        now, completed, failed = 1000.0, [0.0] * 3, [0.0] * 3
        cap = {"capacity_rows": 24, "baseline_rows": 24}
        for _ in range(60):
            for i in range(3):
                completed[i] += rng.randint(0, 20)
                failed[i] += rng.randint(0, 6)
            s = sig(now=now,
                    replicas={f"r{i}": rep(
                        ready=rng.random() > 0.1,
                        weight=rng.choice([0.0, 0.5, 1.0]),
                        q=rng.uniform(0.0, 8.0),
                        completed=completed[i], failed=failed[i])
                        for i in range(3)},
                    burning=rng.random() < 0.4,
                    burn_fast=rng.uniform(0.0, 30.0),
                    admission=dict(cap))
            ds = decide(s, POLICY, st)
            for d in acted(ds):
                if d["action"] == "admission_tighten":
                    cap["capacity_rows"] = d["new_capacity"]
                elif d["action"] == "admission_relax":
                    cap["capacity_rows"] = d["new_capacity"]
                log.append(d)
            advance_state(st, ds, s, window_s=POLICY.window_s)
            now += rng.choice([10.0, 30.0, 50.0])
        last = {}
        for d in log:
            key = cooldown_key(d["lever"], d.get("target", ""))
            prev = last.get(key)
            if prev is not None:
                pa, pt = prev
                if pa != d["action"]:
                    assert d["t"] - pt >= cooldowns[d["lever"]], \
                        f"seed {seed}: {pa} -> {d['action']} on {key} " \
                        f"after {d['t'] - pt}s"
            last[key] = (d["action"], d["t"])


def test_advance_state_trims_window_and_rebases_counters():
    st = AutopilotState()
    s = sig(now=1000.0, replicas={"r0": rep(q=9.0, completed=5.0)})
    advance_state(st, decide(s, POLICY, st), s, window_s=100.0)
    assert st.prev["r0"]["completed"] == 5.0
    assert len(st.actions) == 1 and st.ticks == 1
    s2 = sig(now=1100.0, replicas={"r0": rep(completed=6.0)})
    advance_state(st, [], s2, window_s=100.0)
    assert not st.actions                 # the old action aged out
    assert st.prev["r0"]["completed"] == 6.0


# -- the closed loop against a live fleet ------------------------------------

def test_autopilot_scales_fleet_out_and_back_bit_identically(tmp_path):
    model = _model()
    xs = [np.arange(_DIM, dtype=np.float32) + i for i in range(12)]
    # one bucket on both sides: float bits are only guaranteed equal
    # under the same program, and the fleet batches what the reference
    # scores one by one
    ref_server = Server({"m": model}, max_batch=4, queue_depth=32,
                        buckets=(4,))
    try:
        reference = [np.asarray(ref_server.submit("m", x, timeout=30))
                     for x in xs]
    finally:
        ref_server.close()

    path = str(tmp_path / "events.jsonl")
    config.set("observability.events_path", path)
    try:
        vclock = {"t": 1000.0}
        fleet = Fleet({"m": model}, replicas=1, start=False,
                      server_kwargs={"max_batch": 4, "queue_depth": 32,
                                     "buckets": (4,)})
        policy = AutopilotPolicy(
            min_replicas=1, max_replicas=2, scale_up_queue=2.0,
            scale_down_queue=0.0, scale_cooldown_s=10.0,
            window_s=120.0, max_actions_per_window=8)
        pilot = Autopilot(fleet, policy=policy,
                          clock=lambda: vclock["t"])
        try:
            futs = [fleet.replicas[0].server.submit_async("m", x)
                    for x in xs]
            ds = pilot.tick()                       # sees the backlog
            assert [d["action"] for d in acted(ds)] == ["scale_up"]
            assert len(fleet.replicas) == 2
            assert acted(ds)[0]["replica"] == "r1"
            for r in fleet.replicas:
                r.server.pump()
            results = [np.asarray(f.result(timeout=5)) for f in futs]
            assert all(np.array_equal(a, b)
                       for a, b in zip(results, reference))
            vclock["t"] += 30.0
            ds2 = pilot.tick()                      # idle: unwind
            downs = [d for d in acted(ds2)
                     if d["action"] == "scale_down"]
            assert [d["target"] for d in downs] == ["r1"]
            assert len(fleet.replicas) == 1
            assert pilot.stats()["ticks"] == 2
            assert pilot.stats()["by_action"]["scale_up"] == 1
        finally:
            fleet.close()
    finally:
        events.close()
        config.unset("observability.events_path")
    lines = [json.loads(l) for l in open(path)]
    ap = [e for e in lines if e["type"] == "autopilot"]
    assert {"scale_up", "scale_down"} <= {e["name"] for e in ap}
    # fleet lifecycle events rode along with the actuations
    assert {"scale_up", "scale_down"} <= {
        e["name"] for e in lines if e["type"] == "fleet"}


def test_suppressed_decision_reaches_events_and_metrics(tmp_path):
    path = str(tmp_path / "events.jsonl")
    config.set("observability.events_path", path)
    config.set("observability.metrics", True)
    model = _model()
    fleet = Fleet({"m": model}, replicas=1, start=False,
                  server_kwargs={"max_batch": 4, "queue_depth": 16})
    try:
        policy = AutopilotPolicy(min_replicas=1, max_replicas=1,
                                 scale_up_queue=1.0)
        pilot = Autopilot(fleet, policy=policy, clock=lambda: 1000.0)
        before = metrics.counter("autopilot.suppressed").value
        for x in (np.zeros(_DIM, np.float32),) * 3:
            fleet.replicas[0].server.submit_async("m", x)
        ds = pilot.tick()
        assert held(ds) and not acted(ds)
        assert metrics.counter("autopilot.suppressed").value > before
    finally:
        fleet.close()
        events.close()
        config.unset("observability.events_path")
        config.unset("observability.metrics")
    (e,) = [json.loads(l) for l in open(path)
            if json.loads(l).get("type") == "autopilot"]
    # the suppressed decision carries its full replay payload
    assert e["suppressed"] is True
    assert e["name"] == "scale_up"
    assert e["reason"].startswith("bounds:max_replicas")
    assert e["lever"] == "scale" and "t" in e and "queue_mean" in e


class _BurningEngine:
    def __init__(self, burning):
        self.burning = burning

    def observe(self, sample):
        return [{"objective": "availability", "burning": self.burning,
                 "breaching": False,
                 "burn_fast": 42.0 if self.burning else 0.0}]


def test_rollout_guard_aborts_burning_canary(tmp_path):
    from mmlspark_tpu.serve.fleet import RolloutAborted
    model = _model()
    fleet = Fleet({"m": model}, replicas=2,
                  server_kwargs={"max_batch": 4, "queue_depth": 16})
    path = str(tmp_path / "events.jsonl")
    config.set("observability.events_path", path)
    try:
        pilot = Autopilot(fleet, engine=_BurningEngine(True),
                          clock=lambda: 1000.0)
        with pytest.raises(RolloutAborted) as ei:
            fleet.rollout("m", _model(seed=8), "v2",
                          warm_x=np.zeros(_DIM, np.float32),
                          guard=pilot.rollout_guard)
        assert "canary SLO burning" in str(ei.value)
        st = pilot.stats()
        assert st["by_action"]["rollout_abort"] == 1
        assert st["actions"] == 1
    finally:
        fleet.close()
        events.close()
        config.unset("observability.events_path")
    lines = [json.loads(l) for l in open(path)]
    aborts = [e for e in lines
              if e["type"] == "autopilot" and e["name"] == "rollout_abort"]
    assert len(aborts) == 1 and not aborts[0]["suppressed"]
    assert any(e["type"] == "rollout" and e["name"] == "abort"
               for e in lines)


def test_rollout_guard_records_the_healthy_hold():
    model = _model()
    fleet = Fleet({"m": model}, replicas=2,
                  server_kwargs={"max_batch": 4, "queue_depth": 16})
    try:
        pilot = Autopilot(fleet, engine=_BurningEngine(False),
                          clock=lambda: 1000.0)
        report = fleet.rollout("m", _model(seed=8), "v2",
                               warm_x=np.zeros(_DIM, np.float32),
                               guard=pilot.rollout_guard)
        assert all(r["status"] == "updated"
                   for r in report["replicas"])
        st = pilot.stats()
        assert st["suppressed"] == 2     # one visible hold per canary
        assert all(d["reason"].startswith("hold:canary-healthy")
                   for d in st["recent"])
    finally:
        fleet.close()


# -- observability surfaces ---------------------------------------------------

def test_fleet_signals_distills_scrape_router_and_admission():
    from mmlspark_tpu.observability.aggregate import FleetScraper
    model = _model()
    fleet = Fleet({"m": model}, replicas=2, start=False,
                  server_kwargs={"max_batch": 4, "queue_depth": 16})
    try:
        fleet.replicas[0].server.submit_async(
            "m", np.zeros(_DIM, np.float32))
        scraper = FleetScraper(fleet, clock=lambda: 5.0)
        snap = scraper.scrape()
        s = fleet_signals(snap, [{"burning": True, "burn_fast": 3.0}],
                          fleet.router.stats(), 5.0,
                          admission={"capacity_rows": 8,
                                     "baseline_rows": 32})
        assert set(s["replicas"]) == {"r0", "r1"}
        assert s["replicas"]["r0"]["queue_depth"] == 1.0
        assert s["replicas"]["r0"]["weight"] == 1.0
        assert s["slo"]["burning"] and s["slo"]["burn_fast"] == 3.0
        assert s["admission"]["baseline_rows"] == 32
    finally:
        fleet.close()


def test_scraper_exports_per_replica_queue_gauges_and_sees_scale_up():
    from mmlspark_tpu.observability.aggregate import FleetScraper
    model = _model()
    fleet = Fleet({"m": model}, replicas=2, start=False,
                  server_kwargs={"max_batch": 4, "queue_depth": 16})
    try:
        for _ in range(3):
            fleet.replicas[1].server.submit_async(
                "m", np.zeros(_DIM, np.float32))
        scraper = FleetScraper(fleet, clock=lambda: 1.0)
        scraper.scrape()
        reg = scraper.registry.to_dict()
        for key in ("serving.queue_depth", "serving.inflight"):
            assert reg[key]["type"] == "gauge"
            by_rep = {s["labels"]["replica"]: s["value"]
                      for s in reg[key]["series"]}
            assert set(by_rep) == {"r0", "r1"}
        assert by_rep["r1"] == 3.0        # inflight == queued, unpumped
        # a replica added AFTER the scraper was built is picked up on the
        # next scrape (the autopilot scales mid-flight)
        name = fleet.scale_up()
        snap = scraper.scrape()
        assert name in snap["replicas"]
        assert name in {s["labels"]["replica"] for s in
                        scraper.registry.to_dict()
                        ["serving.queue_depth"]["series"]}
    finally:
        fleet.close()


def test_report_renders_autopilot_section(tmp_path):
    p = tmp_path / "ev.jsonl"
    config.set("observability.events_path", str(p))
    try:
        events.emit("autopilot", "scale_up", lever="scale", target="",
                    t=1000.0, suppressed=False, reason="mean queue 5.0",
                    queue_mean=5.0)
        events.emit("autopilot", "scale_up", lever="scale", target="",
                    t=1030.0, suppressed=True,
                    reason="cooldown:scale (30s of 60s; wanted: x)")
        events.emit("autopilot", "shift_down", lever="shift",
                    target="r1", t=1060.0, suppressed=False,
                    reason="error rate 0.80 >= 0.50", new_weight=0.5)
        events.emit("autopilot", "scale_up", lever="scale", target="",
                    t=1090.0, suppressed=True,
                    reason="bounds:max_replicas (4 >= 4; wanted: y)")
    finally:
        events.close()
        config.unset("observability.events_path")
    from mmlspark_tpu.observability.report import (build_report,
                                                   render_report)
    rep_ = build_report(str(p))
    ap = rep_["autopilot"]
    assert ap["decisions"] == 4
    assert ap["actions"] == 2 and ap["suppressed"] == 2
    assert ap["by_action"] == {"scale_up": 1, "shift_down": 1}
    assert ap["suppressed_reasons"] == {"cooldown": 1,
                                        "bounds:max_replicas": 1}
    assert ap["last"][-1]["action"] == "shift_down"
    text = render_report(str(p))
    assert "autopilot:" in text
    assert "2 actuated, 2 suppressed" in text
    assert "shift_down r1: error rate 0.80 >= 0.50" in text


def test_top_dashboard_shows_autopilot_panel():
    from mmlspark_tpu.observability.aggregate import FleetScraper
    from mmlspark_tpu.observability.dashboard import TopDashboard

    class _Pilot:
        def stats(self):
            return {"ticks": 12, "actions": 3, "suppressed": 5,
                    "errors": 0,
                    "recent": [{"action": "scale_up", "target": "",
                                "suppressed": False, "reason": "q"},
                               {"action": "shift_down", "target": "r1",
                                "suppressed": True, "reason": "cool"}]}

    dash = TopDashboard(FleetScraper([]), autopilot=_Pilot())
    frame = dash.render(dash.scraper.scrape())
    (line,) = [l for l in frame.splitlines()
               if l.startswith("autopilot")]
    assert "ticks 12" in line and "actions 3" in line
    assert "suppressed 5" in line
    assert "last scale_up" in line and "shift_down" not in line


# -- chaos scenario (tier-1 smoke) -------------------------------------------

def test_chaos_autopilot_scenario_is_deterministic(tmp_path):
    from mmlspark_tpu.reliability import chaos

    v1 = chaos.run_autopilot_scenario(0, str(tmp_path / "a"))
    metrics.get_registry().reset()
    v2 = chaos.run_autopilot_scenario(0, str(tmp_path / "b"))
    for v in (v1, v2):
        assert v["passed"], v["invariants"]
        assert v["invariants"]["autopilot_sheds_fewer"]
        assert v["invariants"]["no_flap"]
        assert v["invariants"]["scores_bit_identical"]
        assert v["invariants"]["steady_compiles_zero"]
        assert v["autopilot"]["shed"] < v["static"]["shed"]
    # the verdict is a pure function of the seed
    assert v1["schedule"] == v2["schedule"]
    assert v1["autopilot"]["by_action"] == v2["autopilot"]["by_action"]
    assert v1["static"] == v2["static"]
    # the event stream the no-flap invariant was computed from is real
    ev = [json.loads(l)
          for l in open(tmp_path / "b" / "autopilot_events.jsonl")]
    ap = [e for e in ev if e["type"] == "autopilot"]
    assert any(e["suppressed"] for e in ap)
    assert any(e["name"] == "scale_up" and not e["suppressed"]
               for e in ap)
    on_disk = json.loads(
        (tmp_path / "a" / chaos.VERDICT_FILE).read_text())
    assert on_disk["passed"] is True


# -- the fifth lever: elastic mesh reshard ------------------------------------

def _mesh_sig(shape="", **kw):
    s = sig(**kw)
    s["mesh"] = {"shape": shape}
    return s


RESHARD_POLICY = AutopilotPolicy(
    max_replicas=2, hbm_limit_bytes=1000,
    reshard_wide="2x4", reshard_narrow="4x2",
    reshard_hbm_frac=0.85, reshard_cooldown_s=120.0)


def test_reshard_policy_validation():
    with pytest.raises(ValueError):
        AutopilotPolicy(reshard_hbm_frac=0.0)
    with pytest.raises(ValueError):
        AutopilotPolicy(reshard_wide="4x2", reshard_narrow="4x2")
    # both directions off by default — the lever is opt-in
    assert AutopilotPolicy().reshard_wide == ""
    p = AutopilotPolicy.from_config()
    assert p.reshard_hbm_frac == float(
        config.get("autopilot.reshard_hbm_frac"))


def test_hbm_pressure_reshards_wide():
    st = AutopilotState()
    s = _mesh_sig("4x2", replicas={"r0": rep()}, hbm=900.0)
    ds = decide(s, RESHARD_POLICY, st)
    resh = [d for d in ds if d["lever"] == "reshard"]
    assert [d["action"] for d in acted(resh)] == ["reshard_wide"]
    d = acted(resh)[0]
    assert d["target"] == "2x4" and d["mesh_shape"] == "4x2"
    assert d["hbm_bytes"] == 900 and "hbm" in d["reason"]


def test_reshard_wide_at_target_is_a_visible_veto():
    st = AutopilotState()
    s = _mesh_sig("2x4", replicas={"r0": rep()}, hbm=900.0)
    resh = [d for d in decide(s, RESHARD_POLICY, st)
            if d["lever"] == "reshard"]
    (d,) = resh
    assert d["suppressed"] and d["reason"].startswith("bounds:at_target")


def test_queue_pressure_past_max_replicas_reshards_narrow():
    st = AutopilotState()
    # queue wants replicas, the scale lever is at max -> narrow reshard
    s = _mesh_sig("2x4", replicas={"r0": rep(q=9.0), "r1": rep(q=9.0)})
    ds = decide(s, RESHARD_POLICY, st)
    assert any(d["suppressed"] and d["reason"].startswith(
        "bounds:max_replicas") for d in ds if d["lever"] == "scale")
    resh = [d for d in ds if d["lever"] == "reshard"]
    assert [d["action"] for d in acted(resh)] == ["reshard_narrow"]
    assert acted(resh)[0]["target"] == "4x2"


def test_reshard_cooldown_is_shared_across_directions():
    """Both directions share ONE 'reshard' cooldown key — the structural
    guarantee placements cannot oscillate inside a cooldown."""
    assert cooldown_key("reshard", "2x4") == "reshard" \
        == cooldown_key("reshard", "4x2")
    st = AutopilotState()
    s = _mesh_sig("4x2", now=1000.0, replicas={"r0": rep()}, hbm=900.0)
    advance_state(st, decide(s, RESHARD_POLICY, st), s,
                  window_s=RESHARD_POLICY.window_s)
    # seconds later the OPPOSITE direction wants to fire: held
    s2 = _mesh_sig("2x4", now=1030.0,
                   replicas={"r0": rep(q=9.0), "r1": rep(q=9.0)})
    resh = [d for d in decide(s2, RESHARD_POLICY, st)
            if d["lever"] == "reshard"]
    (d,) = resh
    assert d["suppressed"] and d["reason"].startswith("cooldown:reshard")
    assert "wanted:" in d["reason"]
    # past the cooldown the narrow direction acts
    s3 = _mesh_sig("2x4", now=1130.0,
                   replicas={"r0": rep(q=9.0), "r1": rep(q=9.0)})
    resh3 = [d for d in decide(s3, RESHARD_POLICY, st)
             if d["lever"] == "reshard"]
    assert [d["action"] for d in acted(resh3)] == ["reshard_narrow"]


def test_reshard_disabled_policy_never_fires():
    st = AutopilotState()
    s = _mesh_sig("4x2", replicas={"r0": rep(q=9.0)}, hbm=99999.0)
    assert not [d for d in decide(s, POLICY, st)
                if d["lever"] == "reshard"]


def test_fleet_signals_carries_mesh_shape():
    snap = {"replicas": {"r0": {"ready": True, "live": True,
                                "stats": {"queue_depth": 1.0}}},
            "memory": {"total_bytes": 10.0}}
    s = fleet_signals(snap, [], {"replicas": {}}, 123.0,
                      mesh_shape="2x2x2")
    assert s["mesh"] == {"shape": "2x2x2"}
    # absent mesh_shape -> no mesh key (decide treats it as "")
    s2 = fleet_signals(snap, [], {"replicas": {}}, 123.0)
    assert "mesh" not in s2


def test_autopilot_actuates_reshard_on_live_fleet(tmp_path):
    """Closed loop: HBM pressure + a reshard_wide policy actuate
    ``Fleet.reshard`` through ``_actuate``; the fleet's mesh_shape
    feeds back so the next tick vetoes at-target."""
    x = np.zeros((1, _DIM), np.float32)
    clock = lambda: 1000.0  # noqa: E731
    with Fleet({"mlp": _model()}, replicas=1,
               server_kwargs={"max_batch": 4}) as fleet:
        fleet.submit("mlp", x)
        policy = AutopilotPolicy(
            min_replicas=1, max_replicas=1, hbm_limit_bytes=1,
            reshard_wide="4x2", reshard_hbm_frac=0.5,
            reshard_cooldown_s=0.0, scale_down_queue=-1.0)
        ap = Autopilot(fleet, policy=policy, clock=clock)
        ds = ap.tick()
        resh = [d for d in ds if d["lever"] == "reshard"]
        assert [d["action"] for d in acted(resh)] == ["reshard_wide"]
        assert "error" not in acted(resh)[0]
        assert acted(resh)[0]["report"]["resharded"] == 1
        assert fleet.mesh_shape == "4x2"
        spec = fleet.servers[0].registry.get("mlp").model.get("meshSpec")
        assert (spec.data, spec.tensor) == (4, 2)
        # feedback: the fleet now reports the target shape -> veto
        ds2 = ap.tick()
        resh2 = [d for d in ds2 if d["lever"] == "reshard"]
        assert resh2 and all(d["suppressed"] for d in resh2)
        assert resh2[0]["reason"].startswith("bounds:at_target")
        fleet.submit("mlp", x)
