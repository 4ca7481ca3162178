"""Supervisor restart state machine under a virtual clock.

Every test drives :meth:`Supervisor.poll_once` by hand with injected
``clock``/``sleep`` and fake spawners/handles — no real process is ever
forked here (that's ``test_cli.py``'s fleet smoke and the host chaos
scenario). The hysteresis tests pin the no-flapping contract: a
crash-looper trips its breaker OPEN, spawns NOTHING during the cooldown,
gets exactly ONE half-open probe respawn, and a probe crash re-opens.
"""
import json
import os

import pytest

from mmlspark_tpu.observability import events
from mmlspark_tpu.serve.supervisor import ProcessSpawner, Supervisor
from mmlspark_tpu.utils import config as mmlconfig


class VClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += float(s)


class FakeHandle:
    """A worker handle whose death the test scripts explicitly."""

    def __init__(self, pid, addr):
        self.pid = pid
        self.addr = addr
        self.rc = None
        self.terminated = False
        self.killed = False
        self.closed = False

    def await_announce(self, timeout):
        return bool(self.addr)

    def poll(self):
        return self.rc

    def terminate(self):
        self.terminated = True
        if self.rc is None:
            self.rc = 0          # graceful drain: exits clean

    def kill(self):
        self.killed = True
        if self.rc is None:
            self.rc = -9

    def wait(self, timeout=None):
        return self.rc

    def close(self):
        self.closed = True

    def die(self, rc=1):
        self.rc = rc


class FakeSpawner:
    """Hands out live FakeHandles with distinct pids/ports."""

    def __init__(self):
        self.count = 0
        self.handles = {}

    def spawn(self, name):
        self.count += 1
        h = FakeHandle(1000 + self.count, f"127.0.0.1:{9000 + self.count}")
        self.handles.setdefault(name, []).append(h)
        return h


class DeadSpawner:
    """Every child is dead at birth: the crash-loop stimulus."""

    def __init__(self):
        self.count = 0

    def spawn(self, name):
        self.count += 1
        h = FakeHandle(2000 + self.count, "")
        h.rc = 1
        return h


class FakeRouter:
    """Mirrors the real Router's registration semantics: set_weight on
    an unknown name KeyErrors, removing the last replica ValueErrors."""

    def __init__(self, names):
        self.weights = {n: 1.0 for n in names}
        self.resets = []
        self.probes = 0
        self.added = []
        self.removed = []
        self.weight_trace = []

    def add_replica(self, rep, weight=1.0):
        if rep.name in self.weights:
            raise ValueError(f"duplicate replica {rep.name}")
        self.weights[rep.name] = float(weight)
        self.added.append((rep.name, float(weight)))

    def remove_replica(self, name):
        if name not in self.weights:
            raise KeyError(name)
        if len(self.weights) == 1:
            raise ValueError("cannot remove the last replica")
        del self.weights[name]
        self.removed.append(name)

    def set_weight(self, name, w):
        if name not in self.weights:
            raise KeyError(name)
        self.weights[name] = float(w)
        self.weight_trace.append((name, float(w)))

    def reset_breaker(self, name):
        self.resets.append(name)

    def probe(self):
        self.probes += 1
        return {}

    def stats(self):
        return {"replicas": {n: {"weight": w}
                             for n, w in self.weights.items()}}


def make_sup(spawner, names, clock, **kw):
    kw.setdefault("min_uptime_s", 1.0)
    kw.setdefault("base_delay_s", 2.0)
    kw.setdefault("max_delay_s", 8.0)
    kw.setdefault("ready_timeout_s", 5.0)
    kw.setdefault("breaker_failures", 3)
    kw.setdefault("breaker_reset_s", 60.0)
    kw.setdefault("ready_fn", lambda replica, handle: True)
    return Supervisor(spawner, names, clock=clock,
                      sleep=lambda s: clock.advance(s), **kw)


def test_start_spawns_all_and_registers_addrs():
    clock = VClock()
    sp = FakeSpawner()
    sup = make_sup(sp, ["a", "b"], clock)
    sup.start()
    full = sup.stats()
    assert full["desired_replicas"] == 2 and full["live_replicas"] == 2
    st = full["replicas"]
    assert st["a"]["running"] and st["b"]["running"]
    assert st["a"]["spawns"] == 1 and st["b"]["spawns"] == 1
    # the announce addr lands on the pre-built HttpReplica, normalized
    assert sup.replica("a").addr == "http://127.0.0.1:9001"
    assert sup.replica("b").addr == "http://127.0.0.1:9002"
    assert sup.pid("a") == 1001


def test_names_validated():
    clock = VClock()
    with pytest.raises(ValueError):
        make_sup(FakeSpawner(), [], clock)
    with pytest.raises(ValueError):
        make_sup(FakeSpawner(), ["a", "a"], clock)


def test_crash_backs_off_restarts_and_reregisters(tmp_path):
    ev_path = tmp_path / "events.jsonl"
    mmlconfig.set("observability.events_path", str(ev_path))
    try:
        clock = VClock()
        sp = FakeSpawner()
        sup = make_sup(sp, ["a"], clock)
        router = FakeRouter(["a"])
        sup.attach_router(router)
        sup.start()
        # survive min_uptime -> incarnation confirmed, breaker success
        clock.advance(1.5)
        sup.poll_once()
        assert sup.stats()["replicas"]["a"]["consecutive_crashes"] == 0

        sp.handles["a"][0].die(3)
        sup.poll_once()
        # out of rotation immediately; restart scheduled at +base_delay
        assert router.weights["a"] == 0.0
        assert sup.stats()["replicas"]["a"]["running"] is False
        sup.poll_once()                     # before the backoff expires
        assert sup.stats()["replicas"]["a"]["spawns"] == 1

        clock.advance(2.0)                  # base_delay
        sup.poll_once()
        st = sup.stats()["replicas"]["a"]
        assert st["running"] and st["spawns"] == 2
        # re-registered: weight restored, fleet breaker reset, new addr
        assert router.weights["a"] == 1.0
        assert router.resets and set(router.resets) == {"a"}
        assert sup.replica("a").addr == "http://127.0.0.1:9002"
        assert sup.pid("a") == 1002
    finally:
        mmlconfig.unset("observability.events_path")
        events.close()
    names = [json.loads(line)["name"] for line in
             ev_path.read_text().splitlines()
             if json.loads(line)["type"] == "supervisor"]
    for expected in ("spawn", "exit", "backoff", "restart"):
        assert expected in names, f"missing supervisor.{expected}"


def test_confirmed_uptime_resets_consecutive_crashes():
    clock = VClock()
    sp = FakeSpawner()
    sup = make_sup(sp, ["a"], clock)
    sup.start()
    # two crash/restart rounds WITHOUT confirmation stack up
    for expected_delay in (2.0, 4.0):
        sp.handles["a"][-1].die(1)
        sup.poll_once()
        clock.advance(expected_delay)
        sup.poll_once()
        assert sup.stats()["replicas"]["a"]["running"]
    assert sup.stats()["replicas"]["a"]["consecutive_crashes"] == 2
    # surviving min_uptime clears the streak and the breaker
    clock.advance(1.5)
    sup.poll_once()
    st = sup.stats()["replicas"]["a"]
    assert st["consecutive_crashes"] == 0
    assert st["breaker"] == "closed"
    # the next crash starts the backoff ladder from the bottom again
    sp.handles["a"][-1].die(1)
    sup.poll_once()
    clock.advance(1.9)
    sup.poll_once()
    assert not sup.stats()["replicas"]["a"]["running"]   # 2.0 s not yet elapsed
    clock.advance(0.1)
    sup.poll_once()
    assert sup.stats()["replicas"]["a"]["running"]


def test_crash_loop_opens_breaker_no_flapping():
    """THE hysteresis contract: threshold crashes -> OPEN -> nothing
    spawns during the cooldown -> exactly one half-open probe -> a probe
    crash re-opens with a fresh cooldown."""
    clock = VClock()
    sp = DeadSpawner()
    sup = make_sup(sp, ["a"], clock, ready_fn=lambda r, h: False)
    sup.start()
    opened_at = None
    spawns_at_open = 0
    trace = []
    for _ in range(200):
        sup.poll_once()
        state = sup.breaker_state("a")
        trace.append((clock.t, sp.count, state))
        if opened_at is None and state == "open":
            opened_at = clock.t
            spawns_at_open = sp.count
        clock.advance(1.0)
        if opened_at is not None and clock.t > opened_at + 75.0:
            break
    assert opened_at is not None, "breaker never opened"
    # it took exactly `breaker_failures` dead spawns to trip
    assert spawns_at_open == 3
    # cooldown: NO spawn while the breaker holds the replica out
    in_cooldown = [s for t, s, _ in trace
                   if opened_at <= t < opened_at + 59.0]
    assert in_cooldown and max(in_cooldown) == spawns_at_open
    # exactly ONE half-open probe respawn, whose crash re-opened
    assert sp.count == 4
    assert sup.breaker_state("a") == "open"
    assert sup.stats()["replicas"]["a"]["breaker"] == "open"


def test_shutdown_drains_children_and_stops_restarting():
    clock = VClock()
    sp = FakeSpawner()
    sup = make_sup(sp, ["a", "b"], clock)
    sup.start()
    sup.shutdown(reason="test")
    assert all(h.terminated for hs in sp.handles.values() for h in hs)
    # closed: no further supervision, no respawns
    sup.poll_once()
    assert sp.count == 2
    sup.shutdown()                           # idempotent
    assert sp.count == 2


def test_shutdown_kills_stragglers_past_drain_budget():
    clock = VClock()

    class WedgedHandle(FakeHandle):
        def terminate(self):
            self.terminated = True           # ignores SIGTERM

        def wait(self, timeout=None):
            return self.rc                   # None while alive

    class WedgedSpawner(FakeSpawner):
        def spawn(self, name):
            self.count += 1
            h = WedgedHandle(3000 + self.count, "127.0.0.1:9100")
            self.handles.setdefault(name, []).append(h)
            return h

    sp = WedgedSpawner()
    sup = make_sup(sp, ["a"], clock)
    sup.start()
    sup.shutdown(drain_timeout_s=0.0)
    h = sp.handles["a"][0]
    assert h.terminated and h.killed


def test_kill_replica_idempotent():
    clock = VClock()
    sp = FakeSpawner()
    sup = make_sup(sp, ["a"], clock)
    sup.start()
    pid = sup.kill_replica("a")
    assert pid == 1001
    assert sp.handles["a"][0].killed
    # second kill on the already-dead slot is a no-op, not an error
    assert sup.kill_replica("a") is None
    # after the restart the lever works again on the NEW pid
    sup.poll_once()
    clock.advance(2.0)
    sup.poll_once()
    assert sup.kill_replica("a") == 1002


def test_context_manager_shuts_down():
    clock = VClock()
    sp = FakeSpawner()
    with make_sup(sp, ["a"], clock) as sup:
        sup.start()
        assert sup.stats()["replicas"]["a"]["running"]
    assert sp.handles["a"][0].terminated


# -- ProcessSpawner construction (no process spawned) -------------------------

def test_process_spawner_argv_and_env(tmp_path):
    sp = ProcessSpawner(["m=mlp_tabular:{}"], host="127.0.0.9",
                        events_dir=str(tmp_path / "ev"),
                        compile_cache_dir=str(tmp_path / "cache"),
                        extra_args=["--max-batch", "4"])
    argv = sp.build_argv("w0")
    assert argv[1:4] == ["-m", "mmlspark_tpu.cli", "serve"]
    assert argv[argv.index("--host") + 1] == "127.0.0.9"
    assert argv[argv.index("--port") + 1] == "0"     # child announces
    assert argv[argv.index("--model") + 1] == "m=mlp_tabular:{}"
    assert argv[argv.index("--events-dir") + 1] == str(tmp_path / "ev")
    assert argv[-2:] == ["--max-batch", "4"]
    env = sp.build_env()
    # announce line must cross the pipe unbuffered
    assert env["PYTHONUNBUFFERED"] == "1"
    # the shared compile cache rides the env into the child, in the
    # spelling jax itself reads
    assert env["JAX_COMPILATION_CACHE_DIR"] == \
        os.path.abspath(str(tmp_path / "cache"))
    # children import the tree the supervisor runs from
    import mmlspark_tpu
    pkg_parent = os.path.dirname(os.path.dirname(
        os.path.abspath(mmlspark_tpu.__file__)))
    assert env["PYTHONPATH"].split(os.pathsep)[0] == pkg_parent


def test_process_spawner_requires_models():
    with pytest.raises(ValueError):
        ProcessSpawner([])


def test_process_spawner_device_pinning_disjoint_per_slot(tmp_path):
    sp = ProcessSpawner(["m=mlp_tabular:{}"],
                        events_dir=str(tmp_path / "ev"),
                        devices_per_worker=2)
    # slots are assigned at first sight and stable thereafter
    assert sp.slot_of("w0") == 0
    assert sp.slot_of("w1") == 1
    assert sp.slot_of("w0") == 0
    # slot i sees chips [i*K, (i+1)*K): disjoint visible-device sets
    e0, e1 = sp.device_env("w0"), sp.device_env("w1")
    assert e0["TPU_VISIBLE_CHIPS"] == "0,1"
    assert e1["TPU_VISIBLE_CHIPS"] == "2,3"
    # TPU_VISIBLE_CHIPS alone leaves every process but the first dead on
    # libtpu's lockfile: each worker is declared a one-process 2-chip slice
    for e in (e0, e1):
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    with pytest.raises(ValueError, match="devices_per_worker=3"):
        ProcessSpawner(["m=mlp_tabular:{}"], devices_per_worker=3)
    # the pinning rides build_env into the child process, and a worker
    # that owns chips is told to use them: with the platform pinned, jax
    # fails at backend init instead of falling back to the CPU
    env = sp.build_env("w1")
    assert env["TPU_VISIBLE_CHIPS"] == "2,3"
    assert env["JAX_PLATFORMS"] == "tpu"


def test_process_spawner_device_pinning_off_by_default(tmp_path):
    sp = ProcessSpawner(["m=mlp_tabular:{}"],
                        events_dir=str(tmp_path / "ev"))
    assert sp.device_env("w0") == {}     # 0 = the worker owns no chip
    assert "TPU_VISIBLE_CHIPS" not in sp.build_env("w0")


def test_process_spawner_never_leaves_the_platform_unpinned(
        tmp_path, monkeypatch):
    """Every worker starts with an explicit JAX_PLATFORMS: the spawner's
    env, else its own chips, else what this process inherited — and
    having none of them is an error, not a child that takes whatever
    backend initializes."""
    mk = lambda **kw: ProcessSpawner(["m=mlp_tabular:{}"],
                                     events_dir=str(tmp_path / "ev"), **kw)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert mk().build_env("w0")["JAX_PLATFORMS"] == "cpu"     # inherited
    assert mk(devices_per_worker=1).platform() == "tpu"       # own chips
    assert mk(devices_per_worker=1,
              env={"JAX_PLATFORMS": "cpu"}).platform() == "cpu"  # operator
    monkeypatch.delenv("JAX_PLATFORMS")
    assert mk(env={"JAX_PLATFORMS": "cpu"}).build_env(
        "w0")["JAX_PLATFORMS"] == "cpu"
    with pytest.raises(ValueError, match="not pinned"):
        mk().build_env("w0")


def test_fleet_parent_never_loads_jax():
    """The `mmlspark-tpu fleet` process only spawns, routes and scrapes. A
    chip belongs to the one process that initializes it, so the parent
    must leave jax alone — cheapest proof: everything `cmd_fleet` imports
    (autopilot included) comes up without jax in `sys.modules`."""
    import subprocess
    import sys
    code = (
        "import sys\n"
        "from mmlspark_tpu import cli\n"
        "from mmlspark_tpu.observability.aggregate import FleetScraper\n"
        "from mmlspark_tpu.reliability import preemption\n"
        "from mmlspark_tpu.serve.http import serve_http\n"
        "from mmlspark_tpu.serve.router import Router\n"
        "from mmlspark_tpu.serve.supervisor import ProcessSpawner, "
        "Supervisor\n"
        "from mmlspark_tpu.control.autopilot import Autopilot\n"
        "from mmlspark_tpu.serve.fleet import ProcessFleet\n"
        "sys.exit(1 if 'jax' in sys.modules else 0)\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], cwd=repo,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_worker_told_to_use_a_chip_that_finds_none_exits_nonzero(tmp_path):
    """``serve`` under JAX_PLATFORMS=tpu on a host with no TPU must die at
    backend init (rc != 0, no announce) — not announce itself from the
    CPU."""
    import subprocess
    import sys
    sp = ProcessSpawner(['m=mlp_tabular:{"input_dim": 4}'],
                        events_dir=str(tmp_path / "ev"),
                        devices_per_worker=1,
                        env={"TPU_VISIBLE_CHIPS": "63"})  # no such chip
    proc = subprocess.run(sp.build_argv("w0"), env=sp.build_env("w0"),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"serving"' not in proc.stdout


def test_process_spawner_explicit_env_outranks_pinning(tmp_path):
    sp = ProcessSpawner(["m=mlp_tabular:{}"],
                        events_dir=str(tmp_path / "ev"),
                        devices_per_worker=1,
                        env={"TPU_VISIBLE_CHIPS": "7"})
    # operator-supplied env wins over the computed pinning
    assert sp.build_env("w0")["TPU_VISIBLE_CHIPS"] == "7"


# -- chaos: scenario registry + host scenario ---------------------------------

def test_chaos_scenario_registry_covers_all_runners():
    from mmlspark_tpu.reliability import chaos
    assert set(chaos.SCENARIOS) == {"train", "fleet", "decode", "host",
                                    "fleet_sharded", "decode_sharded",
                                    "autopilot", "elastic", "recommender",
                                    "fleetprefix", "reshard"}
    assert all(desc for desc in chaos.SCENARIOS.values())


def test_cli_chaos_unknown_scenario_lists_registry(capsys):
    from mmlspark_tpu.cli import main
    assert main(["chaos", "--scenario", "bogus"]) == 2
    err = capsys.readouterr().err
    assert "bogus" in err
    for name in ("train", "fleet", "decode", "host",
                 "fleet_sharded", "decode_sharded"):
        assert name in err


def test_chaos_host_scenario_green(tmp_path):
    """ISSUE 11 acceptance: SIGKILL a real worker process under fire ->
    warm restart (shared compile cache hits), zero failed requests,
    supervisor events in the merged per-pid report, crash-loop breaker
    hysteresis — all from one seeded run."""
    from mmlspark_tpu.reliability import chaos
    verdict = chaos.run_host_scenario(0, str(tmp_path / "out"),
                                      replicas=2, requests=6)
    assert verdict["passed"], verdict
    inv = verdict["invariants"]
    assert inv["zero_failed_requests"]
    assert inv["warm_restart"]            # compile_cache hits > 0 post-kill
    assert inv["supervisor_events"]
    assert inv["merged_report_coherent"]
    assert inv["crash_loop_breaker_open"]
    assert inv["no_restart_flapping"]
    # the verdict file is on disk and agrees
    on_disk = json.loads(
        (tmp_path / "out" / chaos.VERDICT_FILE).read_text())
    assert on_disk["passed"] is True
    assert on_disk["schedule"]["kill_at"] == verdict["schedule"]["kill_at"]


@pytest.mark.slow
def test_chaos_host_schedule_deterministic(tmp_path):
    """Two same-seed runs draw the same kill point and kill target (pids
    and wall timings legitimately differ between runs)."""
    from mmlspark_tpu.reliability import chaos
    v1 = chaos.run_host_scenario(0, str(tmp_path / "a"),
                                 replicas=2, requests=6)
    v2 = chaos.run_host_scenario(0, str(tmp_path / "b"),
                                 replicas=2, requests=6)
    assert v1["passed"] and v2["passed"]
    for key in ("kill_at", "kill_replica"):
        assert v1["schedule"][key] == v2["schedule"][key]
    assert v1["crash_loop"] == v2["crash_loop"]   # pure virtual clock


# -- elasticity: add_slot / retire_slot ---------------------------------------

def test_add_slot_weight_lifecycle(tmp_path):
    """A new slot registers at weight 0, spawns, and only _on_ready
    lifts it to full weight (with a fleet-breaker reset)."""
    ev_path = tmp_path / "events.jsonl"
    mmlconfig.set("observability.events_path", str(ev_path))
    try:
        clock = VClock()
        sp = FakeSpawner()
        sup = make_sup(sp, ["a"], clock)
        router = FakeRouter(["a"])
        sup.attach_router(router)
        sup.start()

        name = sup.add_slot()
        assert name == "w0"                      # smallest unused w<i>
        assert router.added == [("w0", 0.0)]     # registered BEFORE spawn
        assert router.weights["w0"] == 1.0       # lifted by _on_ready
        assert "w0" in router.resets
        assert "w0" in sup.breakers
        full = sup.stats()
        assert full["desired_replicas"] == 2
        assert full["live_replicas"] == 2
        assert full["spawns_in_flight"] == 0
        assert full["replicas"]["w0"]["ready_spawns"] == 1
        assert full["spawn_to_ready_ms"]["count"] >= 1

        with pytest.raises(ValueError):
            sup.add_slot(name="a")               # duplicate name
    finally:
        mmlconfig.unset("observability.events_path")
        events.close()
    sup_events = [json.loads(line) for line in
                  ev_path.read_text().splitlines()
                  if json.loads(line)["type"] == "supervisor"]
    names = [e["name"] for e in sup_events]
    assert "add_slot" in names and "ready" in names
    add = next(e for e in sup_events if e["name"] == "add_slot")
    assert add["replica"] == "w0" and add["desired"] == 2
    ready = next(e for e in sup_events
                 if e["name"] == "ready" and e["replica"] == "w0")
    assert ready["spawn_to_ready_ms"] >= 0.0


def test_add_slot_dead_spawn_reconciles_via_poll():
    """A slot whose first spawn dies mid-handshake is reaped by the
    ordinary supervision loop and respawned at full saved weight —
    never a half-registered zombie."""
    clock = VClock()

    class DieFirstSpawner(FakeSpawner):
        def spawn(self, name):
            h = super().spawn(name)
            if name == "w0" and len(self.handles["w0"]) == 1:
                h.rc = 1                     # dead before /readyz
            return h

    sp = DieFirstSpawner()
    sup = make_sup(sp, ["a"], clock)
    router = FakeRouter(["a"])
    sup.attach_router(router)
    sup.start()

    name = sup.add_slot()
    assert name == "w0"
    assert router.weights["w0"] == 0.0           # never lifted
    st = sup.stats()["replicas"]["w0"]
    assert st["spawns"] == 1 and st["ready_spawns"] == 0

    sup.poll_once()                              # reap + schedule backoff
    assert sup.stats()["replicas"]["w0"]["running"] is False
    clock.advance(2.0)                           # base_delay
    sup.poll_once()                              # respawn, now live
    st = sup.stats()["replicas"]["w0"]
    assert st["running"] and st["ready_spawns"] == st["spawns"] == 2
    # the slot never carried traffic, so it re-enters at FULL weight
    assert router.weights["w0"] == 1.0


def test_retire_slot_drain_ordering(tmp_path):
    """Retire: weight->0 strictly before SIGTERM, removal from the
    router after the drain, state + breaker cleaned up."""
    ev_path = tmp_path / "events.jsonl"
    mmlconfig.set("observability.events_path", str(ev_path))
    try:
        clock = VClock()
        sp = FakeSpawner()
        sup = make_sup(sp, ["a", "b"], clock)
        router = FakeRouter(["a", "b"])
        sup.attach_router(router)
        sup.start()

        h = sp.handles["b"][0]
        weight_at_terminate = {}
        orig_terminate = h.terminate

        def spy_terminate():
            weight_at_terminate["b"] = router.weights["b"]
            orig_terminate()

        h.terminate = spy_terminate
        assert sup.retire_slot("b") is True
        assert weight_at_terminate["b"] == 0.0   # drained AFTER weight->0
        assert h.closed
        assert router.removed == ["b"]
        assert "b" not in sup.breakers
        full = sup.stats()
        assert full["desired_replicas"] == 1
        assert "b" not in full["replicas"]
        assert len(sup.replicas) == 1
    finally:
        mmlconfig.unset("observability.events_path")
        events.close()
    sup_events = [json.loads(line) for line in
                  ev_path.read_text().splitlines()
                  if json.loads(line)["type"] == "supervisor"]
    retire = next(e for e in sup_events if e["name"] == "retire")
    assert retire["replica"] == "b" and retire["drained"] is True
    assert retire["desired"] == 1


def test_retire_slot_idempotent_noop(tmp_path):
    ev_path = tmp_path / "events.jsonl"
    mmlconfig.set("observability.events_path", str(ev_path))
    try:
        clock = VClock()
        sup = make_sup(FakeSpawner(), ["a", "b"], clock)
        sup.attach_router(FakeRouter(["a", "b"]))
        sup.start()
        assert sup.retire_slot("nope") is False   # unknown: no KeyError
        assert sup.retire_slot("b") is True
        assert sup.retire_slot("b") is False      # double-retire: no-op
    finally:
        mmlconfig.unset("observability.events_path")
        events.close()
    noops = [json.loads(line) for line in ev_path.read_text().splitlines()
             if json.loads(line)["type"] == "supervisor"
             and json.loads(line)["name"] == "retire_noop"]
    assert [e["replica"] for e in noops] == ["nope", "b"]


def test_retire_last_replica_stays_registered_at_zero():
    """The router refuses to go empty; the retired last slot stays
    registered at weight 0 (out of rotation) instead of raising."""
    clock = VClock()
    sup = make_sup(FakeSpawner(), ["a"], clock)
    router = FakeRouter(["a"])
    sup.attach_router(router)
    sup.start()
    assert sup.retire_slot("a") is True
    assert router.weights == {"a": 0.0}          # registered, weightless
    assert sup.stats()["desired_replicas"] == 0


def test_retire_slot_sigkills_straggler():
    clock = VClock()
    sp = FakeSpawner()
    sup = make_sup(sp, ["a", "b"], clock)
    sup.attach_router(FakeRouter(["a", "b"]))
    sup.start()
    h = sp.handles["b"][0]
    h.terminate = lambda: None                   # ignores SIGTERM
    h.wait = lambda timeout=None: None if not h.killed else -9
    assert sup.retire_slot("b", drain_timeout_s=0.0) is True
    assert h.killed                              # SIGKILL past the budget


def test_add_slot_closed_supervisor_raises():
    clock = VClock()
    sup = make_sup(FakeSpawner(), ["a"], clock)
    sup.start()
    sup.shutdown()
    with pytest.raises(RuntimeError):
        sup.add_slot()


def test_process_fleet_routes_scale_through_supervisor():
    from mmlspark_tpu.serve.fleet import ProcessFleet
    clock = VClock()
    sup = make_sup(FakeSpawner(), ["a"], clock)
    router = FakeRouter(["a"])
    fleet = ProcessFleet(sup, router)
    assert sup.router is router                  # auto-attached
    sup.start()
    name = fleet.scale_up()
    assert name == "w0" and router.weights["w0"] == 1.0
    stats = fleet.stats()
    assert stats["supervisor"]["desired_replicas"] == 2
    fleet.scale_down("w0")
    assert "w0" not in router.weights
    fleet.scale_down("w0")                       # idempotent, no raise
    assert sup.stats()["desired_replicas"] == 1


def test_top_dashboard_supervisor_panel():
    from mmlspark_tpu.observability.dashboard import TopDashboard

    class StubScraper:
        def scrape(self):
            return {"ts": 0.0, "fleet": {}, "replicas": {},
                    "memory": {}, "scrape_ms": 0.1}

    class StubSup:
        def stats(self):
            return {"desired_replicas": 3, "live_replicas": 2,
                    "spawns_in_flight": 1, "retiring": 0,
                    "spawn_to_ready_ms": {"count": 2, "p50": 900.0,
                                          "p99": 1500.0, "max": 1500.0}}

    dash = TopDashboard(StubScraper(), supervisor=StubSup())
    frame = dash.tick()
    assert "workers" in frame
    assert "desired 3" in frame and "live 2 (!)" in frame
    assert "spawning 1" in frame
    assert "spawn->ready p50 900ms" in frame
