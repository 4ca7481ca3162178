"""``mmlspark-tpu`` CLI: the spark-submit-style launcher.

The reference ships ``tools/bin/mml-exec`` (runs spark-shell/pyspark/
spark-submit against the local build); the TPU-native equivalent launches a
user script into an initialized distributed JAX process group:

    mmlspark-tpu run train.py --mesh data=-1,tensor=2 \
        --coordinator 10.0.0.1:8476 --num-processes 16 --process-id 3 -- \
        --script-arg value

On a single host ``mmlspark-tpu run train.py`` just runs the script (JAX
auto-detects any cluster env). The ``--mesh`` axes land in the config tier
(``runtime.mesh``) where ``parallel.mesh.mesh_from_config`` and
DeepClassifier's default mesh resolution pick them up, so the same script
scales from laptop CPU to a multi-host slice without edits.

Other subcommands: ``info`` (device + config inventory), ``bench`` (runs
the repo benchmark when present), ``serve`` (the micro-batching inference
server over HTTP — docs/SERVING.md), ``check`` (reliability lint),
``chaos`` (seeded train-kill-resume-then-serve fault scenario —
docs/RELIABILITY.md), ``report`` (render a telemetry event log).
"""
from __future__ import annotations

import argparse
import json
import os
import runpy
import sys
from typing import List, Optional


def _parse_mesh(text: str) -> dict:
    """'data=-1,tensor=2' -> {'data': -1, 'tensor': 2} (validated)."""
    from mmlspark_tpu.parallel.mesh import parse_mesh_axes
    try:
        return parse_mesh_axes(text)
    except ValueError as e:
        raise SystemExit(f"--mesh: {e}")


def _resolve_hosts(args) -> None:
    """Fill coordinator/num_processes/process_id from the ``--hosts``
    list or the env contract, unless given explicitly.

    The pod-launch UX (docs/DEPLOY.md): every host runs the IDENTICAL
    command line (the ``gcloud ... ssh --worker=all`` pattern) with
    ``--hosts h0,h1,...``; each process derives its own process-id by
    matching its identity against the list — MMLSPARK_HOST_INDEX when
    set (CI / heterogeneous naming), otherwise hostname/FQDN match.
    host 0 is the coordinator (``--port`` selects the port).

    Env fallbacks (external launchers: k8s indexed jobs, batch systems):
    MMLSPARK_COORDINATOR, MMLSPARK_NUM_PROCESSES, MMLSPARK_PROCESS_ID.
    On a real TPU pod none of this is needed — jax.distributed
    auto-discovers from the TPU metadata when everything is left unset.
    """
    def env_int(name: str):
        raw = os.environ.get(name)
        if raw is None:
            return None
        try:
            val = int(raw)
        except ValueError:
            raise SystemExit(
                f"{name}={raw!r} is not an integer (unexpanded template "
                "variable?)")
        if val < 0:
            raise SystemExit(f"{name}={val} must be >= 0")
        return val

    if args.coordinator is None:
        args.coordinator = os.environ.get("MMLSPARK_COORDINATOR")
    if args.num_processes is None:
        args.num_processes = env_int("MMLSPARK_NUM_PROCESSES")
    if args.process_id is None:
        args.process_id = env_int("MMLSPARK_PROCESS_ID")
    def check_range():
        if args.process_id is not None and args.num_processes is not None \
                and args.process_id >= args.num_processes:
            raise SystemExit(
                f"process id {args.process_id} out of range for "
                f"{args.num_processes} processes")

    if not args.hosts:
        check_range()   # the pure-env contract must fail fast too, not
        return          # hang a jax.distributed rendezvous on a bad id
    hosts = [h.strip() for h in args.hosts.split(",") if h.strip()]
    if not hosts:
        raise SystemExit("--hosts: empty host list")
    if args.coordinator is None:
        args.coordinator = f"{hosts[0]}:{args.port}"
    if args.num_processes is None:
        args.num_processes = len(hosts)
    if args.process_id is None:
        if os.environ.get("MMLSPARK_HOST_INDEX") is not None:
            args.process_id = env_int("MMLSPARK_HOST_INDEX")
        else:
            import socket
            me = {socket.gethostname(), socket.getfqdn(),
                  socket.gethostname().split(".")[0]}
            matches = [i for i, h in enumerate(hosts)
                       if h in me or h.split(".")[0] in me]
            if len(matches) != 1:
                raise SystemExit(
                    f"--hosts: cannot identify this host among {hosts} "
                    f"(I am {sorted(me)}); set MMLSPARK_HOST_INDEX or "
                    "pass --process-id")
            args.process_id = matches[0]
    check_range()


def cmd_run(args, passthrough: List[str]) -> int:
    from mmlspark_tpu.utils import config
    script = args.script
    if not os.path.exists(script):  # before any process-state mutation
        raise SystemExit(f"script not found: {script}")
    _resolve_hosts(args)
    if args.mesh:
        _parse_mesh(args.mesh)  # fail fast on a bad flag
        # config tier: visible to mesh_from_config() in the user script AND
        # to DeepClassifier/DistributedTrainer default mesh resolution
        os.environ["MMLSPARK_TPU_RUNTIME_MESH"] = args.mesh
        config.set("runtime.mesh", args.mesh)
    saved_platform = None
    # main() is also an importable in-process API (tests, notebooks) — every
    # mutation below is restored in the finally, whether the failure is in
    # the process-group join or the script itself (it is scoped to this
    # launch, not the process)
    try:
        if args.platform:
            # must land BEFORE the backend initializes; an explicit config
            # value outranks a JAX_PLATFORMS the environment carries
            import jax
            saved_platform = (jax.config.jax_platforms,)
            jax.config.update("jax_platforms", args.platform)
        from mmlspark_tpu.parallel.mesh import initialize_multihost
        try:
            initialize_multihost(coordinator_address=args.coordinator,
                                 num_processes=args.num_processes,
                                 process_id=args.process_id)
        except ValueError as e:
            raise SystemExit(str(e))
        if args.platform:
            # jax accepts a jax_platforms update silently once a backend
            # is live (an in-process caller touched JAX first), so check
            # the backend that actually initialized rather than run the
            # user script on the wrong platform
            try:
                backend = jax.default_backend()
            except RuntimeError as e:
                # e.g. --platform tpu on a host with no TPU: surface the
                # launcher's clean error style, not a raw traceback
                raise SystemExit(f"--platform {args.platform}: {e}")
            accept = {"gpu": {"gpu", "cuda", "rocm"}}.get(
                args.platform, {args.platform})
            if backend not in accept:
                raise SystemExit(
                    f"--platform {args.platform}: backend initialized as "
                    f"{backend!r} (JAX was touched before the launcher "
                    "could pin the platform)")
        # persistent compile cache: on before the user script compiles
        # anything (no-op when no directory is configured)
        from mmlspark_tpu import compile_cache
        compile_cache.enable()
        saved_argv, saved_path = sys.argv, list(sys.path)
        sys.argv = [script] + passthrough
        sys.path.insert(0, os.path.dirname(os.path.abspath(script)))
        try:
            runpy.run_path(script, run_name="__main__")
        finally:
            sys.argv, sys.path[:] = saved_argv, saved_path
    finally:
        if args.mesh:
            config.unset("runtime.mesh")
            os.environ.pop("MMLSPARK_TPU_RUNTIME_MESH", None)
        if saved_platform is not None:
            # restore the config for in-process callers (the already-live
            # backend is not torn down, but the next launch decides afresh)
            import jax
            try:
                jax.config.update("jax_platforms", saved_platform[0])
            except RuntimeError:
                pass
    return 0


def build_pod_argv(args, passthrough: List[str]) -> List[str]:
    """The ``gcloud compute tpus tpu-vm ssh --worker=all`` argv for a pod
    launch (docs/DEPLOY.md §2) — every worker runs the IDENTICAL
    ``mmlspark-tpu run`` command and jax.distributed auto-discovers the
    process group from the TPU metadata. Split out from cmd_launch_pod so
    tests can pin the exact constructed argv (the reference's live-cluster
    E2E — ``_e2e_script_action``/``_e2e_ssh`` in tools/runme/build.sh —
    verified its HDI script action the expensive way; the argv contract
    is the hardware-free part)."""
    import shlex

    def quote_dir(p: str) -> str:
        # a leading ~ (bare, ~/path, or ~user/path) must stay OUTSIDE the
        # quotes or the remote shell never tilde-expands it (cd '~/app'
        # fails where cd ~/app works). The unquoted prefix is allowed ONLY
        # when it is a legal-username shape — anything else (spaces, shell
        # metacharacters) is fully quoted, trading expansion for safety.
        import re
        if p.startswith("~"):
            prefix, sep, rest = p.partition("/")
            if re.fullmatch(r"~[A-Za-z0-9._-]*", prefix):
                if not sep:
                    return prefix          # '~' or '~user'
                return prefix + "/" + (shlex.quote(rest) if rest else "")
        return shlex.quote(p)

    inner = ["mmlspark-tpu", "run", args.script]
    if args.mesh:
        inner += ["--mesh", args.mesh]
    if passthrough:
        inner += ["--"] + list(passthrough)
    command = "cd " + quote_dir(args.app_dir) + " && " \
        + " ".join(shlex.quote(a) for a in inner)
    argv = ["gcloud", "compute", "tpus", "tpu-vm", "ssh", args.name,
            f"--worker={args.worker}"]
    if args.zone:
        argv += ["--zone", args.zone]
    if args.project:
        argv += ["--project", args.project]
    argv += ["--command", command]
    return argv


def cmd_launch_pod(args, passthrough: List[str]) -> int:
    if args.mesh:
        _parse_mesh(args.mesh)  # fail fast before touching the cluster
    argv = build_pod_argv(args, passthrough)
    if args.dry_run:
        print(json.dumps(argv))  # lint: allow-print (stdout IS the contract)
        return 0
    import subprocess
    return subprocess.call(argv)


def cmd_info(args, passthrough) -> int:
    from mmlspark_tpu.parallel.mesh import device_count_summary
    from mmlspark_tpu.utils import config
    info = {"devices": device_count_summary(), "config": config.snapshot()}
    try:
        import jax
        info["backend"] = jax.default_backend()
    except Exception as e:  # pragma: no cover - backendless env
        info["backend_error"] = str(e)
    print(json.dumps(info, indent=2, default=str))  # lint: allow-print
    return 0


def cmd_check(args, passthrough) -> int:
    """Static reliability lint (urlopen-without-timeout, swallowed
    excepts, print-in-library-code, implicit-daemon threads, unbounded
    queues) over the installed package, or explicit roots."""
    from mmlspark_tpu.reliability import lint
    roots = args.roots or [os.path.dirname(
        os.path.abspath(__import__("mmlspark_tpu").__file__))]
    return lint.main(roots)


def cmd_report(args, passthrough) -> int:
    """Render a run report from one or more telemetry event logs (JSONL,
    per-pid sidecars merge natively; --glob adds a pattern); --json for
    the structured form, --trace to also export a Chrome-trace/Perfetto
    timeline of the same log."""
    from mmlspark_tpu.observability.aggregate import expand_event_paths
    paths = expand_event_paths(args.events, getattr(args, "glob", "")
                               or None)
    if not paths:
        raise SystemExit("report: no event logs matched")
    target = paths[0] if len(paths) == 1 else paths
    if getattr(args, "trace", None):
        if len(paths) > 1:
            raise SystemExit(
                "--trace exports one log at a time; pass a single events "
                "path")
        from mmlspark_tpu.observability.trace import export_trace
        stats = export_trace(paths[0], args.trace)
        print(f"trace: {stats['out']} ({stats['spans']} spans, "  # lint: allow-print
              f"{stats['events']} events, {stats['tracks']} tracks) — "
              "open in https://ui.perfetto.dev")
    if getattr(args, "json", False):
        from mmlspark_tpu.observability.report import build_report
        print(json.dumps(build_report(target, top=args.top),  # lint: allow-print
                         sort_keys=True))
    else:
        from mmlspark_tpu.observability.report import render_report
        print(render_report(target, top=args.top))  # lint: allow-print
    return 0


def cmd_top(args, passthrough) -> int:
    """Live fleet dashboard over HTTP replicas: scrapes ``/metrics`` +
    ``/readyz`` from every --replica through per-host circuit breakers
    and redraws a plain-ANSI frame (per-replica readiness, queue depth,
    QPS, p50/p99, shed, SLO burn, HBM occupancy). ``--once`` prints a
    single frame and exits (tests/CI)."""
    from mmlspark_tpu.observability.aggregate import FleetScraper
    from mmlspark_tpu.observability.dashboard import TopDashboard
    from mmlspark_tpu.observability.slo import SloEngine
    from mmlspark_tpu.serve.router import HttpReplica
    if not args.replica:
        raise SystemExit("top: at least one --replica HOST:PORT required")
    replicas = [HttpReplica(addr) for addr in args.replica]
    scraper = FleetScraper(replicas, timeout_s=args.timeout)
    dash = TopDashboard(scraper, SloEngine(), interval_s=args.interval)
    dash.run(once=args.once)
    return 0


def cmd_loadgen(args, passthrough) -> int:
    """Preview a seeded open-loop workload schedule (testing/loadgen):
    prints the trace spec, arrival count, offered QPS, per-bucket
    arrival counts, and the sha256 schedule fingerprint — the replay
    contract (same seed + trace -> same fingerprint, byte for byte)."""
    from mmlspark_tpu.testing import loadgen
    trace = loadgen.Trace(
        duration_s=args.duration, rate=args.rate, shape=args.shape,
        process=args.process, spike_start_s=args.spike_start,
        spike_len_s=args.spike_len, spike_factor=args.spike_factor,
        pareto_alpha=args.pareto_alpha,
        session_turns=args.session_turns, think_s=args.think)
    schedule = loadgen.generate(trace, args.seed)
    fingerprint = loadgen.schedule_fingerprint(schedule)
    buckets = loadgen.bucket_counts(schedule, args.bucket) \
        if args.bucket > 0 else []
    offered_qps = (len(schedule) / trace.duration_s
                   if trace.duration_s > 0 else 0.0)
    if getattr(args, "json", False):
        print(json.dumps({  # lint: allow-print
            "trace": trace.describe(), "seed": args.seed,
            "arrivals": len(schedule), "fingerprint": fingerprint,
            "offered_qps": round(offered_qps, 4),
            "bucket_s": args.bucket, "buckets": buckets},
            sort_keys=True))
        return 0
    print(f"trace: {trace.describe()}")  # lint: allow-print
    print(f"seed {args.seed}: {len(schedule)} arrivals "  # lint: allow-print
          f"({offered_qps:.2f} offered qps)")
    if buckets:
        print(f"per-{args.bucket:g}s buckets: {buckets}")  # lint: allow-print
    print(f"fingerprint: {fingerprint}")  # lint: allow-print
    return 0


def _parse_model_flag(text: str):
    """``NAME=ARCH[:JSON-kwargs]`` -> (name, architecture, kwargs)."""
    name, sep, rest = text.partition("=")
    if not sep or not name or not rest:
        raise SystemExit(
            f"--model: expected NAME=ARCH[:JSON-kwargs], got {text!r}")
    arch, sep2, blob = rest.partition(":")
    kwargs = {}
    if sep2:
        try:
            kwargs = json.loads(blob)
        except json.JSONDecodeError as e:
            raise SystemExit(f"--model {name}: bad JSON kwargs ({e})")
        if not isinstance(kwargs, dict):
            raise SystemExit(
                f"--model {name}: kwargs must be a JSON object, got "
                f"{type(kwargs).__name__}")
    return name, arch, kwargs


def cmd_serve(args, passthrough) -> int:
    """Start the micro-batching inference server behind the stdlib HTTP
    front-end (docs/SERVING.md). Blocks until interrupted; SIGTERM/SIGINT
    drain gracefully — admission stops (503 + Retry-After), in-flight
    batches finish, then the server closes (docs/RELIABILITY.md)."""
    import threading
    from mmlspark_tpu import compile_cache
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu.reliability import preemption
    from mmlspark_tpu.reliability.watchdog import Watchdog
    from mmlspark_tpu.serve.http import serve_http
    from mmlspark_tpu.serve.server import Server
    from mmlspark_tpu.utils import config as mmlconfig
    if getattr(args, "events_dir", ""):
        # per-pid sidecar convention: this worker appends to its OWN
        # events-<pid>.jsonl under the shared directory; the supervisor
        # (or `mmlspark-tpu report --glob`) merges them into one view
        os.makedirs(args.events_dir, exist_ok=True)
        mmlconfig.set("observability.events_path",
                      os.path.join(args.events_dir,
                                   f"events-{os.getpid()}.jsonl"))
    # second startup against a warm compile cache skips every bucket
    # compile: jax's cache for jit paths + the AOT program cache consulted
    # by ModelEntry._compile (docs/PERFORMANCE.md)
    compile_cache.enable()
    if not args.model:
        raise SystemExit(
            "serve: at least one --model NAME=ARCH[:JSON-kwargs] required "
            '(e.g. --model "mlp=mlp_tabular:{\\"input_dim\\": 8}")')
    models = {}
    for spec in args.model:
        name, arch, kwargs = _parse_model_flag(spec)
        m = JaxModel(inputCol="x", outputCol="y")
        try:
            m.set_model(arch, **kwargs)
        except (KeyError, TypeError, ValueError) as e:
            raise SystemExit(f"--model {name}: {e}")
        models[name] = m
    buckets = [int(b) for b in args.buckets.split(",") if b.strip()] \
        if args.buckets else None
    server_kwargs = dict(max_batch=args.max_batch,
                         max_wait_ms=args.max_wait_ms,
                         queue_depth=args.queue_depth, buckets=buckets)
    from mmlspark_tpu.observability import memory as devmem
    devmem.start_audit_poller()  # no-op unless observability.memory_poll_s
    fleet = None
    scraper = None
    autopilot = None
    if args.replicas > 1:
        # fleet mode: N in-process replicas behind the health-checked
        # router (failover, fairness, rolling rollout; docs/SERVING.md)
        from mmlspark_tpu.observability.aggregate import FleetScraper
        from mmlspark_tpu.serve.fleet import Fleet
        fleet = Fleet(models, replicas=args.replicas,
                      server_kwargs=server_kwargs)
        fleet.router.start_prober()
        # background fleet scrape keeps the aggregated per-replica view
        # (and the HBM ledger gauges) warm for `mmlspark-tpu top`
        scraper = FleetScraper(fleet)
        scraper.start()
        if args.autopilot or bool(mmlconfig.get("autopilot.enabled")):
            # the SLO-driven control loop over this fleet: traffic shift,
            # replica scale, adaptive admission (docs/AUTOPILOT.md); its
            # decisions land in the events sidecar as autopilot.* lines
            from mmlspark_tpu.control.autopilot import Autopilot
            autopilot = Autopilot(fleet)
            autopilot.start()
        front = fleet.router
    elif args.autopilot:
        raise SystemExit("serve: --autopilot needs --replicas > 1 "
                         "(the levers act on a fleet)")
    else:
        server = Server(models, **server_kwargs)
        front = server
    httpd, addr = serve_http(front, host=args.host, port=args.port)
    # stdout contract: one JSON line announcing the bound address, so
    # wrappers can discover an ephemeral --port 0; liveness and readiness
    # are reported SEPARATELY (the /livez vs /readyz split)
    h = front.health()
    print(json.dumps({"serving": addr,                 # lint: allow-print
                      "models": front.registry.names(),
                      "replicas": args.replicas, "pid": os.getpid(),
                      "live": h["live"], "ready": h["ready"]}),
          flush=True)  # a supervisor reads this over a block-buffered pipe
    # graceful preemption: SIGTERM/SIGINT flip the process-wide signal;
    # this monitor turns it into drain (stop admission, finish in-flight)
    # then unblocks serve_forever. Handlers only install on the main
    # thread — in-process callers off-main keep plain Ctrl-C semantics.
    preemption.install_handlers()
    watchdog = Watchdog() \
        if float(mmlconfig.get("reliability.stall_timeout_s")) > 0 else None

    def monitor():
        preemption.get_signal().wait()
        reason = preemption.preemption_reason() or "signal"
        if fleet is not None:
            fleet.drain(reason=reason)
        else:
            server.drain(reason=reason)
        httpd.shutdown()

    mon = threading.Thread(target=monitor, daemon=True,
                           name="mmlspark-tpu-serve-drain")
    mon.start()
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass  # clean Ctrl-C shutdown path (no handler installed off-main)
    finally:
        httpd.server_close()
        if autopilot is not None:
            autopilot.stop()
        if scraper is not None:
            scraper.stop()
        if fleet is not None:
            fleet.close()
        else:
            server.close()
        if watchdog is not None:
            watchdog.close()
        devmem.stop_audit_poller()
    return 0


def cmd_fleet(args, passthrough) -> int:
    """Launch a REAL process fleet (docs/SERVING.md "Process fleet"):
    every replica is its own ``mmlspark-tpu serve`` OS process — own
    ephemeral port, own ``events-<pid>.jsonl`` sidecar, the SHARED
    persistent compile cache — supervised with restart-on-crash
    (exponential backoff + per-replica circuit breaker) behind the
    health-checked HTTP router. SIGTERM drains every child before the
    front closes. Args after ``--`` are forwarded to each worker's
    ``serve`` command line verbatim.

    ``--hosts h1,h2`` (or ``--hosts-file``) switches to the multi-host
    launcher: one fleet (supervisor + workers) per host, each announced
    front stitched behind ONE router/scraper control plane here, with
    per-host ``supervisor.*`` event sidecars under
    ``EVENTS_DIR/host-<host>/`` merging into one report. ``--autopilot``
    (single-host mode) runs the SLO-driven control loop with the scale
    lever actuating REAL worker processes through the supervisor
    (``Supervisor.add_slot``/``retire_slot`` via ``ProcessFleet``)."""
    import threading
    from mmlspark_tpu.observability.aggregate import FleetScraper
    from mmlspark_tpu.reliability import preemption
    from mmlspark_tpu.serve.http import serve_http
    from mmlspark_tpu.serve.router import Router
    from mmlspark_tpu.serve.supervisor import ProcessSpawner, Supervisor
    from mmlspark_tpu.utils import config as mmlconfig
    if not args.model:
        raise SystemExit(
            "fleet: at least one --model NAME=ARCH[:JSON-kwargs] required "
            '(e.g. --model "mlp=mlp_tabular:{\\"input_dim\\": 8}")')
    for spec in args.model:
        _parse_model_flag(spec)  # fail fast BEFORE spawning any worker
    replicas = args.replicas if args.replicas is not None \
        else int(mmlconfig.get("fleet.replicas"))
    if replicas < 1:
        raise SystemExit(f"fleet: --replicas must be >= 1, got {replicas}")
    hosts_spec = args.hosts or str(mmlconfig.get("fleet.hosts"))
    if args.hosts_file:
        from mmlspark_tpu.serve.launcher import read_hosts_file
        if hosts_spec:
            raise SystemExit("fleet: --hosts and --hosts-file are "
                             "mutually exclusive")
        hosts = read_hosts_file(args.hosts_file)
    else:
        from mmlspark_tpu.serve.launcher import parse_hosts
        hosts = parse_hosts(hosts_spec)
    if hosts:
        if args.autopilot:
            raise SystemExit(
                "fleet: --autopilot is single-host for now (each host's "
                "fleet supervises its own workers; run the autopilot "
                "per host)")
        return _fleet_multi_host(args, passthrough, hosts, replicas)
    events_dir = args.events_dir or os.path.join(os.getcwd(), "fleet-events")
    os.makedirs(events_dir, exist_ok=True)
    # the supervisor writes its OWN per-pid sidecar next to the workers'
    # so the merged report carries the supervisor.* decisions too:
    #   mmlspark-tpu report --glob 'EVENTS_DIR/events-*.jsonl'
    mmlconfig.set("observability.events_path",
                  os.path.join(events_dir, f"events-{os.getpid()}.jsonl"))
    from mmlspark_tpu import compile_cache
    cache_dir = args.compile_cache_dir or compile_cache.cache_dir()
    dpw = args.devices_per_worker if args.devices_per_worker is not None \
        else int(mmlconfig.get("fleet.devices_per_worker"))
    if dpw < 0:
        raise SystemExit(
            f"fleet: --devices-per-worker must be >= 0, got {dpw}")
    # this process never initializes a jax backend (it only routes), so
    # the chips are the workers' alone — each worker's, not all workers'
    try:
        spawner = ProcessSpawner(
            args.model, host=args.host, events_dir=events_dir,
            compile_cache_dir=cache_dir or None,
            extra_args=list(passthrough), devices_per_worker=dpw)
        platform = spawner.platform()
    except ValueError as e:
        raise SystemExit(f"fleet: {e}")
    if platform.split(",")[0] == "tpu" and dpw == 0 and replicas > 1:
        raise SystemExit(
            f"fleet: {replicas} workers would all reach for the host's "
            "chips, and a chip belongs to one process; pass "
            "--devices-per-worker K so each worker owns its own")
    sup = Supervisor(spawner, [f"w{i}" for i in range(replicas)])
    scraper = None
    httpd = None
    autopilot = None
    try:
        sup.start()
        router = Router(sup.replicas)
        sup.attach_router(router)
        router.probe()
        router.start_prober()
        # background fleet scrape keeps the aggregated per-replica view
        # warm for `mmlspark-tpu top` pointed at the workers
        scraper = FleetScraper(router)
        scraper.start()
        sup.start_monitor()
        if args.autopilot or bool(mmlconfig.get("autopilot.enabled")):
            backend = str(mmlconfig.get("autopilot.scale_backend"))
            if backend == "inprocess":
                raise SystemExit(
                    "fleet: --autopilot over worker processes needs "
                    "autopilot.scale_backend=process (or auto), got "
                    f"{backend!r}")
            # the scale lever actuates REAL processes: scale_up spawns a
            # supervised worker (warm via the shared compile cache),
            # scale_down drains + retires one (docs/AUTOPILOT.md)
            from mmlspark_tpu.control.autopilot import Autopilot
            from mmlspark_tpu.serve.fleet import ProcessFleet
            autopilot = Autopilot(ProcessFleet(sup, router),
                                  scraper=scraper)
            autopilot.start()
        httpd, addr = serve_http(router, host=args.host, port=args.port)
        h = router.health()
        print(json.dumps({"serving": addr,             # lint: allow-print
                          "replicas": replicas, "pid": os.getpid(),
                          "workers": sup.stats(),
                          "events_dir": events_dir,
                          "live": h["live"], "ready": h["ready"]},
                         default=str), flush=True)
        # SIGTERM/SIGINT -> drain every child through its own preemption
        # handler, stop restarting, then unblock serve_forever
        preemption.install_handlers()

        def monitor():
            preemption.get_signal().wait()
            reason = preemption.preemption_reason() or "signal"
            sup.shutdown(reason=reason)
            httpd.shutdown()

        mon = threading.Thread(target=monitor, daemon=True,
                               name="mmlspark-tpu-fleet-drain")
        mon.start()
        try:
            httpd.serve_forever()
        except KeyboardInterrupt:
            pass  # clean Ctrl-C shutdown path
    finally:
        if httpd is not None:
            httpd.server_close()
        if autopilot is not None:
            autopilot.stop()
        if scraper is not None:
            scraper.stop()
        sup.shutdown()
    return 0


def _fleet_multi_host(args, passthrough, hosts, replicas) -> int:
    """The ``fleet --hosts`` control plane: one fleet process per host
    via :class:`~mmlspark_tpu.serve.launcher.HostLauncher`, every
    announced host front behind one router + scraper here, SIGTERM
    fanning the drain out to every host."""
    import threading
    from mmlspark_tpu.observability.aggregate import FleetScraper
    from mmlspark_tpu.reliability import preemption
    from mmlspark_tpu.serve.http import serve_http
    from mmlspark_tpu.serve.launcher import HostLauncher
    from mmlspark_tpu.serve.router import Router
    from mmlspark_tpu.utils import config as mmlconfig
    events_dir = args.events_dir or os.path.join(os.getcwd(), "fleet-events")
    os.makedirs(events_dir, exist_ok=True)
    # the control plane's own sidecar (launcher.* events) sits next to
    # the per-host subdirectories; merge everything with
    #   mmlspark-tpu report --glob 'EVENTS_DIR/**/events-*.jsonl'
    mmlconfig.set("observability.events_path",
                  os.path.join(events_dir, f"events-{os.getpid()}.jsonl"))
    extra = list(passthrough)
    if args.compile_cache_dir:
        extra = ["--compile-cache-dir", args.compile_cache_dir] + extra
    if args.devices_per_worker is not None:
        extra = ["--devices-per-worker",
                 str(args.devices_per_worker)] + extra
    launcher = HostLauncher(hosts, args.model,
                            replicas_per_host=replicas,
                            events_dir=events_dir, extra_args=extra)
    scraper = None
    httpd = None
    try:
        launcher.launch()
        router = Router(launcher.replicas())
        router.probe()
        router.start_prober()
        scraper = FleetScraper(router)
        scraper.start()
        httpd, addr = serve_http(router, host=args.host, port=args.port)
        h = router.health()
        print(json.dumps({"serving": addr,             # lint: allow-print
                          "hosts": launcher.stats(),
                          "replicas_per_host": replicas,
                          "pid": os.getpid(),
                          "events_dir": events_dir,
                          "live": h["live"], "ready": h["ready"]},
                         default=str), flush=True)
        preemption.install_handlers()

        def monitor():
            preemption.get_signal().wait()
            launcher.shutdown()
            httpd.shutdown()

        mon = threading.Thread(target=monitor, daemon=True,
                               name="mmlspark-tpu-hosts-drain")
        mon.start()
        try:
            httpd.serve_forever()
        except KeyboardInterrupt:
            pass  # clean Ctrl-C shutdown path
    finally:
        if httpd is not None:
            httpd.server_close()
        if scraper is not None:
            scraper.stop()
        launcher.shutdown()
    return 0


def cmd_autopilot(args, passthrough) -> int:
    """Autopilot offline tooling. ``replay``: re-run the pure decision
    core over recorded ``autopilot_signals`` telemetry under the
    recorded policy (fidelity must be byte-identical) and any number of
    candidate threshold overrides, ranked by counterfactual shed / SLO
    burn / action count (docs/AUTOPILOT.md "Replay runbook")."""
    from mmlspark_tpu.control import replay as rp
    if args.subcommand != "replay":  # pragma: no cover - argparse gates
        raise SystemExit(f"autopilot: unknown subcommand "
                         f"{args.subcommand!r}")
    log = rp.load_log(args.events)
    if not log["ticks"]:
        raise SystemExit(
            "autopilot replay: no autopilot_signals/tick events in the "
            "given log(s) — record a run with observability.events_path "
            "set and the autopilot on")
    recorded = rp.policy_from_fields(log["policy"] or {})
    fidelity = rp.fidelity_check(
        log["decisions"], rp.replay_decisions(log["ticks"], recorded))
    candidates = {"recorded": recorded}
    for spec in args.candidate:
        label, sep, rest = spec.partition(":")
        if not sep or not label:
            raise SystemExit(
                f"--candidate: expected LABEL:key=val[,key=val...], "
                f"got {spec!r}")
        try:
            candidates[label] = rp.policy_from_fields(
                log["policy"] or {}, rp.parse_overrides(rest))
        except ValueError as e:
            raise SystemExit(f"--candidate {label}: {e}")
    ranked = rp.rank_policies(log["ticks"], candidates)
    if args.json:
        print(json.dumps({"fidelity": fidelity,    # lint: allow-print
                          "ranking": ranked}, sort_keys=True))
    else:
        print(rp.format_ranking(ranked, fidelity))  # lint: allow-print
    if log["policy"] is not None and not fidelity["identical"]:
        return 1  # the replay-sufficiency contract broke: make it loud
    return 0


def cmd_chaos(args, passthrough) -> int:
    """Seeded chaos scenario (docs/RELIABILITY.md). ``--scenario train``
    (default): train under a deterministic fault schedule generated from
    --seed, kill + resume to bit-identical params, then serve traffic
    under injected faults while polling /healthz. ``--scenario fleet``:
    kill a replica of an N-wide fleet under fire; zero dropped requests,
    scores bit-identical to a single server, deterministic schedule.
    ``--scenario decode``: kill a replica MID-GENERATION; every sequence
    completes via failover-restart from its prompt with token streams
    bit-identical to a single server (seeded sampling). ``--scenario
    host``: SIGKILL a real worker PROCESS under fire; the supervisor
    warm-restarts it from the shared compile cache with zero failed
    requests, and a crash-looper ends breaker-open, not flapping.
    ``--scenario autopilot``: the same seeded load spike + replica kill
    against a static fleet and an autopiloted one — the autopilot must
    shed strictly less, recover, and never flap (docs/AUTOPILOT.md).
    ``--scenario elastic``: SIGKILL a worker process MID
    autopilot-driven supervised scale-up; zero failed requests, the
    half-spawned slot completes registration or is cleanly reaped (no
    zombie in the router rotation), desired == live after
    reconciliation, and the warm scale-up pays zero XLA compiles.
    ``--scenario recommender``: kill a replica mid-scoring with
    row-sharded embedding tables resident; zero failed requests,
    scores bit-identical to an unsharded single server, and the HBM
    ledger's kind="table" lines reconcile to zero on close.
    ``--scenario fleetprefix``: kill the replica holding the hottest
    ADVERTISED prefix chains mid-stream (docs/SERVING.md "fleet as one
    cache"); zero failed requests, survivors absorb the session keys,
    tokens bit-identical to a single server, and the prefix hit rate
    recovers with zero new compiles.
    ``--scenario reshard``: SIGKILL a replica MID-RESHARD while the
    fleet moves onto a new mesh placement under fire; zero failed
    requests, scores bit-identical to an untouched reference on both
    placements, survivors finish the reshard, and the HBM ledger
    reconciles to zero on close.
    Writes ``chaos_verdict.json`` under --out; exit 0 iff every
    invariant held."""
    if (args.scenario.endswith("_sharded")
            or args.scenario in ("recommender", "reshard")) \
            and "jax" not in sys.modules:
        # the 2-D mesh needs >= 4 devices: raise the host-platform count
        # BEFORE jax first loads so a CPU-only host can form it (same
        # seam as bench.py's xl lanes; on accelerator hosts the flag
        # only shapes the unused CPU platform). Read once at backend
        # init, so too late once jax is imported.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count=8"
            ).strip()
    from mmlspark_tpu.reliability import chaos
    if args.scenario not in chaos.SCENARIOS:
        known = "\n".join(f"  {name:8s} {desc}" for name, desc
                          in sorted(chaos.SCENARIOS.items()))
        print(f"chaos: unknown scenario {args.scenario!r}; "  # lint: allow-print
              f"registered scenarios:\n{known}", file=sys.stderr)
        return 2
    outdir = args.out or os.path.join(
        os.getcwd(), f"chaos-{args.scenario}-seed{args.seed}")
    if args.scenario in ("fleet", "fleet_sharded"):
        verdict = chaos.run_fleet_scenario(
            args.seed, outdir, replicas=args.replicas,
            requests=args.requests,
            mesh=chaos.SHARDED_MESH if args.scenario.endswith("_sharded")
            else "")
    elif args.scenario in ("decode", "decode_sharded"):
        verdict = chaos.run_decode_scenario(
            args.seed, outdir, replicas=args.replicas,
            requests=args.requests,
            mesh=chaos.SHARDED_MESH if args.scenario.endswith("_sharded")
            else "")
    elif args.scenario == "host":
        verdict = chaos.run_host_scenario(
            args.seed, outdir, replicas=args.replicas,
            requests=args.requests)
    elif args.scenario == "autopilot":
        verdict = chaos.run_autopilot_scenario(
            args.seed, outdir, replicas=args.replicas)
    elif args.scenario == "elastic":
        verdict = chaos.run_elastic_scenario(
            args.seed, outdir, replicas=args.replicas,
            requests=args.requests)
    elif args.scenario == "recommender":
        verdict = chaos.run_recommender_scenario(
            args.seed, outdir, replicas=args.replicas,
            requests=args.requests)
    elif args.scenario == "fleetprefix":
        verdict = chaos.run_fleetprefix_scenario(
            args.seed, outdir, replicas=args.replicas,
            requests=args.requests)
    elif args.scenario == "reshard":
        verdict = chaos.run_reshard_scenario(
            args.seed, outdir, replicas=args.replicas,
            requests=args.requests)
    else:
        verdict = chaos.run_scenario(
            args.seed, outdir, total_steps=args.steps,
            save_every=args.save_every, requests=args.requests)
    # stdout contract: the verdict JSON, so wrappers don't re-read the file
    print(json.dumps(verdict, indent=2,       # lint: allow-print
                     sort_keys=True))
    return 0 if verdict["passed"] else 1


def cmd_bench(args, passthrough) -> int:
    path = os.path.join(os.getcwd(), "bench.py")
    if not os.path.exists(path):
        raise SystemExit("no bench.py in the current directory")
    saved_argv = sys.argv
    extra = ["--baseline", args.baseline] if getattr(args, "baseline", "") \
        else []
    sys.argv = [path] + extra + passthrough
    try:
        runpy.run_path(path, run_name="__main__")
    finally:
        sys.argv = saved_argv
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # split off script passthrough args after `--`
    passthrough: List[str] = []
    if "--" in argv:
        cut = argv.index("--")
        argv, passthrough = argv[:cut], argv[cut + 1:]

    parser = argparse.ArgumentParser(
        prog="mmlspark-tpu",
        description="TPU-native ML pipeline framework launcher")
    sub = parser.add_subparsers(dest="command", required=True)

    run_p = sub.add_parser("run", help="run a script in the process group")
    run_p.add_argument("script")
    run_p.add_argument("--mesh", default="",
                       help="axis sizes, e.g. data=-1,tensor=2 (-1 absorbs)")
    run_p.add_argument("--coordinator", default=None,
                       help="host:port of process 0 (multi-host)")
    run_p.add_argument("--num-processes", type=int, default=None)
    run_p.add_argument("--process-id", type=int, default=None)
    run_p.add_argument("--hosts", default="",
                       help="comma list of participating hosts; run the "
                       "SAME command on every host and each derives its "
                       "process-id (MMLSPARK_HOST_INDEX or hostname "
                       "match), with host 0 as coordinator — see "
                       "docs/DEPLOY.md")
    run_p.add_argument("--port", type=int, default=8476,
                       help="coordinator port used with --hosts")
    run_p.add_argument("--platform", default=None,
                       choices=["cpu", "tpu", "gpu"],
                       help="force the jax platform before the process "
                       "group forms; outranks the environment "
                       "— e.g. --platform cpu for the virtual-device test "
                       "mesh")
    run_p.set_defaults(fn=cmd_run)

    pod_p = sub.add_parser(
        "launch-pod",
        help="run a script on every worker of a TPU pod via gcloud ssh")
    pod_p.add_argument("name", help="TPU VM / pod slice name")
    pod_p.add_argument("script", help="script path on the workers")
    pod_p.add_argument("--mesh", default="",
                       help="forwarded to `mmlspark-tpu run` on each worker")
    pod_p.add_argument("--zone", default="")
    pod_p.add_argument("--project", default="")
    pod_p.add_argument("--worker", default="all",
                       help="gcloud --worker selector (default: all)")
    pod_p.add_argument("--app-dir", default="~/app",
                       help="directory cd'd into on each worker")
    pod_p.add_argument("--dry-run", action="store_true",
                       help="print the gcloud argv as JSON, don't execute")
    pod_p.set_defaults(fn=cmd_launch_pod)

    info_p = sub.add_parser("info", help="device + config inventory")
    info_p.set_defaults(fn=cmd_info)

    bench_p = sub.add_parser("bench", help="run ./bench.py")
    bench_p.add_argument("--baseline", default="",
                         help="committed bench JSON (e.g. BENCH_r05.json) "
                         "to gate against: per-lane regression thresholds, "
                         "verdict on stdout, exit nonzero on red")
    bench_p.set_defaults(fn=cmd_bench)

    check_p = sub.add_parser(
        "check", help="static reliability lint (timeouts, swallowed "
                      "excepts, unbounded queues)")
    check_p.add_argument("roots", nargs="*",
                         help="files/dirs to lint (default: the installed "
                         "mmlspark_tpu package)")
    check_p.set_defaults(fn=cmd_check)

    serve_p = sub.add_parser(
        "serve",
        help="serve models over HTTP with dynamic micro-batching")
    serve_p.add_argument("--model", action="append", default=[],
                         metavar="NAME=ARCH[:JSON-kwargs]",
                         help="register a model under NAME (repeatable), "
                         'e.g. mlp=mlp_tabular:{"input_dim": 8}')
    serve_p.add_argument("--host", default="127.0.0.1")
    serve_p.add_argument("--port", type=int, default=8080,
                         help="0 = pick an ephemeral port (announced on "
                         "stdout)")
    serve_p.add_argument("--max-batch", type=int, default=None,
                         help="rows per micro-batch (serving.max_batch)")
    serve_p.add_argument("--max-wait-ms", type=float, default=None,
                         help="max coalescing wait (serving.max_wait_ms)")
    serve_p.add_argument("--queue-depth", type=int, default=None,
                         help="admission queue bound (serving.queue_depth)")
    serve_p.add_argument("--buckets", default="",
                         help='batch-shape buckets, e.g. "1,8,64" '
                         "(serving.buckets)")
    serve_p.add_argument("--replicas", type=int, default=1,
                         help="in-process serving replicas behind the "
                         "fleet router (failover, health probing, "
                         "rolling rollout; default 1 = plain server)")
    serve_p.add_argument("--autopilot", action="store_true",
                         help="run the SLO-driven autopilot over the "
                         "fleet (traffic shift, replica scale, adaptive "
                         "admission; needs --replicas > 1; "
                         "docs/AUTOPILOT.md). Also on when "
                         "autopilot.enabled is set")
    serve_p.add_argument("--events-dir", default="",
                         help="write this process's telemetry to "
                         "EVENTS_DIR/events-<pid>.jsonl (the per-pid "
                         "sidecar convention; supervisors and `report "
                         "--glob` merge them)")
    serve_p.set_defaults(fn=cmd_serve)

    fleet_p = sub.add_parser(
        "fleet",
        help="launch N `serve` worker PROCESSES behind the router, "
             "supervised with restart-on-crash (backoff + breaker); "
             "SIGTERM drains every child")
    fleet_p.add_argument("--model", action="append", default=[],
                         metavar="NAME=ARCH[:JSON-kwargs]",
                         help="model spec forwarded to every worker "
                         "(repeatable)")
    fleet_p.add_argument("--host", default="127.0.0.1")
    fleet_p.add_argument("--port", type=int, default=8080,
                         help="front router port (0 = ephemeral, "
                         "announced on stdout); workers always bind "
                         "ephemeral ports")
    fleet_p.add_argument("--replicas", type=int, default=None,
                         help="worker process count (default: "
                         "fleet.replicas config)")
    fleet_p.add_argument("--events-dir", default="",
                         help="shared telemetry directory: every process "
                         "(workers AND supervisor) appends its own "
                         "events-<pid>.jsonl there (default "
                         "./fleet-events)")
    fleet_p.add_argument("--compile-cache-dir", default="",
                         help="shared persistent compile cache exported "
                         "to every worker; restarted replicas LOAD "
                         "compiled programs instead of recompiling "
                         "(default: runtime.compile_cache_dir)")
    fleet_p.add_argument("--devices-per-worker", type=int, default=None,
                         help="give each worker K chips of its own "
                         "(slot i sees chips [i*K, (i+1)*K) and starts "
                         "with JAX_PLATFORMS=tpu); 0 = workers own no "
                         "chip and start on the JAX_PLATFORMS this "
                         "process inherited (default: "
                         "fleet.devices_per_worker config)")
    fleet_p.add_argument("--hosts", default="",
                         help="comma list of hosts to fan one fleet out "
                         "to each ('local' runs on this machine, other "
                         "names go over ssh); the announced host fronts "
                         "are stitched behind one router here (default: "
                         "fleet.hosts config; empty = single host)")
    fleet_p.add_argument("--hosts-file", default="",
                         help="file with one host per line (# comments); "
                         "mutually exclusive with --hosts")
    fleet_p.add_argument("--autopilot", action="store_true",
                         help="run the SLO-driven autopilot with the "
                         "scale lever actuating real worker processes "
                         "(Supervisor.add_slot/retire_slot; single-host "
                         "mode only; also on when autopilot.enabled is "
                         "set — see autopilot.scale_backend)")
    fleet_p.set_defaults(fn=cmd_fleet)

    autopilot_p = sub.add_parser(
        "autopilot",
        help="autopilot offline tooling (counterfactual policy replay "
             "over recorded decision telemetry)")
    ap_sub = autopilot_p.add_subparsers(dest="subcommand", required=True)
    replay_p = ap_sub.add_parser(
        "replay",
        help="re-run the pure decide() core over recorded "
             "autopilot_signals events; verify byte-identical fidelity "
             "under the recorded policy and rank candidate threshold "
             "overrides by counterfactual shed/SLO/action outcome")
    replay_p.add_argument("events", nargs="+",
                          help="event JSONL path(s) from a recorded "
                          "autopilot run (per-pid/per-host sidecars "
                          "merge)")
    replay_p.add_argument("--candidate", action="append", default=[],
                          metavar="LABEL:KEY=VAL[,KEY=VAL...]",
                          help="candidate policy: recorded thresholds "
                          "with these overrides (repeatable), e.g. "
                          "eager:scale_up_queue=2,scale_cooldown_s=10")
    replay_p.add_argument("--json", action="store_true",
                          help="emit fidelity + ranking as one JSON "
                          "object instead of the table")
    replay_p.set_defaults(fn=cmd_autopilot)

    chaos_p = sub.add_parser(
        "chaos",
        help="seeded chaos scenario (train-kill-resume-then-serve, or "
             "kill-a-fleet-replica-under-fire); exits 0 iff all "
             "invariants hold")
    chaos_p.add_argument("--scenario", default="train",
                         help="train: kill+resume then serve under faults; "
                         "fleet: kill one of N replicas mid-stream; "
                         "decode: kill a replica mid-generation, every "
                         "sequence completes via failover-restart; "
                         "host: SIGKILL a worker PROCESS under fire, "
                         "warm restart from the shared compile cache; "
                         "autopilot: seeded load spike + replica kill, "
                         "static fleet vs autopiloted fleet; "
                         "elastic: SIGKILL a worker mid autopilot-driven "
                         "supervised scale-up — no zombie slot, desired "
                         "== live after reconciliation; "
                         "recommender: kill a replica mid-scoring with "
                         "row-sharded embedding tables resident — "
                         "bit-identical scores, ledger reconciles "
                         "(default: train; unknown scenarios list the "
                         "registry and exit 2)")
    chaos_p.add_argument("--seed", type=int, default=0,
                         help="fault-schedule seed (same seed => same "
                         "kills, same verdict)")
    chaos_p.add_argument("--out", default="",
                         help="verdict/checkpoint directory (default "
                         "./chaos-<SCENARIO>-seed<SEED>)")
    chaos_p.add_argument("--steps", type=int, default=8,
                         help="train steps in each run (default 8)")
    chaos_p.add_argument("--save-every", type=int, default=2,
                         help="checkpoint cadence in steps (default 2)")
    chaos_p.add_argument("--requests", type=int, default=12,
                         help="serve-phase request count (default 12)")
    chaos_p.add_argument("--replicas", type=int, default=3,
                         help="fleet width for --scenario "
                         "fleet/decode/recommender; worker-process count "
                         "for --scenario host/elastic (default 3)")
    chaos_p.set_defaults(fn=cmd_chaos)

    report_p = sub.add_parser(
        "report", help="render a run report from telemetry event log(s)")
    report_p.add_argument("events", nargs="*",
                          help="path(s) to events.jsonl written with "
                          "observability.events_path set; per-pid "
                          "sidecars merge (inline globs OK; may be "
                          "omitted when --glob is given)")
    report_p.add_argument("--glob", default="",
                          help="additionally merge every log matching "
                          "this glob (e.g. 'run1/events-*.jsonl')")
    report_p.add_argument("--top", type=int, default=10,
                          help="rows in the slowest-span table (default 10)")
    report_p.add_argument("--trace", default="",
                          help="also export a Chrome-trace/Perfetto JSON "
                          "timeline to this path")
    report_p.add_argument("--json", action="store_true",
                          help="emit the structured report as one JSON "
                          "object instead of text")
    report_p.set_defaults(fn=cmd_report)

    top_p = sub.add_parser(
        "top", help="live fleet dashboard (scrapes /metrics + /readyz)")
    top_p.add_argument("--replica", action="append", default=[],
                       metavar="HOST:PORT",
                       help="replica address to scrape (repeatable)")
    top_p.add_argument("--interval", type=float, default=2.0,
                       help="redraw interval in seconds (default 2)")
    top_p.add_argument("--once", action="store_true",
                       help="print one frame and exit (tests/CI)")
    top_p.add_argument("--timeout", type=float, default=2.0,
                       help="per-replica scrape timeout in seconds")
    top_p.set_defaults(fn=cmd_top)

    loadgen_p = sub.add_parser(
        "loadgen", help="preview a seeded open-loop workload schedule")
    loadgen_p.add_argument("--rate", type=float, default=8.0,
                           help="base arrivals/second (default 8)")
    loadgen_p.add_argument("--duration", type=float, default=10.0,
                           help="trace length in seconds (default 10)")
    loadgen_p.add_argument("--shape", default="constant",
                           choices=["constant", "diurnal", "spike"],
                           help="rate curve (default constant)")
    loadgen_p.add_argument("--process", default="poisson",
                           choices=["poisson", "pareto"],
                           help="arrival process (default poisson)")
    loadgen_p.add_argument("--spike-start", type=float, default=0.0,
                           help="spike window start (s)")
    loadgen_p.add_argument("--spike-len", type=float, default=0.0,
                           help="spike window length (s)")
    loadgen_p.add_argument("--spike-factor", type=float, default=1.0,
                           help="rate multiplier inside the spike window")
    loadgen_p.add_argument("--pareto-alpha", type=float, default=1.5,
                           help="pareto tail shape (must be > 1)")
    loadgen_p.add_argument("--session-turns", type=int, default=1,
                           help="max turns per session (default 1: no "
                           "sessions)")
    loadgen_p.add_argument("--think", type=float, default=0.0,
                           help="inter-turn think time (s)")
    loadgen_p.add_argument("--seed", type=int, default=0,
                           help="schedule seed (default 0)")
    loadgen_p.add_argument("--bucket", type=float, default=1.0,
                           help="bucket size for per-bucket counts "
                           "(default 1s; 0 disables)")
    loadgen_p.add_argument("--json", action="store_true",
                           help="emit the preview as one JSON object")
    loadgen_p.set_defaults(fn=cmd_loadgen)

    args = parser.parse_args(argv)
    try:
        return args.fn(args, passthrough)
    except Exception:
        # last-gasp: persist the flight recorder so the crash ships its
        # own context even when observability.events_path was never set
        try:
            from mmlspark_tpu.observability import flightrec
            dumped = flightrec.dump(reason="crash")
            if dumped:
                print(f"flight recorder dumped to {dumped}",  # lint: allow-print
                      file=sys.stderr)
        except (ImportError, OSError):  # dump() itself never raises
            dumped = None
        raise


if __name__ == "__main__":
    sys.exit(main())
