"""Global configuration tier: namespaced knobs with env-var overrides.

Re-expression of the reference's typesafe-config scheme
(``core/env/src/main/scala/Configuration.scala:28-46``), which exposed a
``mmlspark.{sdk,cntk,tlc}`` namespace tree. Here the namespaces are
``mmlspark_tpu.{runtime,logging,profiling}`` and every key resolves, in
order: programmatic ``set()`` > environment variable
``MMLSPARK_TPU_<NAMESPACE>_<KEY>`` (upper-cased) > registered default.

This is the third config tier next to (1) per-stage ``Param``s and (2) the
launcher's CLI flags — the same three-tier split as the reference
(SURVEY.md §5 "Config / flag system").
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Optional

_DEFAULTS: Dict[str, Any] = {
    # runtime
    "runtime.prefetch_depth": 2,      # host->device prefetch queue depth
    "runtime.decode_threads": 0,      # 0 = native codec picks (ncpu)
    "runtime.mesh": "",               # launcher default, e.g. "data=-1,tensor=2"
    "runtime.device_cache_mb": 1024,  # HBM budget for device-resident epochs
    "runtime.compile_cache_dir": "",  # non-empty = persist compiled XLA
                                      # programs here: wires jax's
                                      # jax_compilation_cache_dir for every
                                      # jit path AND the serve-side AOT
                                      # program cache (compile_cache.py) so
                                      # restarts/rollouts skip bucket
                                      # compiles (docs/PERFORMANCE.md)
    # train (sync-free stepping; parallel/trainer.py, docs/PERFORMANCE.md)
    "train.metrics_flush_steps": 16,  # steps between device->host metric
                                      # ring flushes; also the dispatch-
                                      # depth bound on the CPU mesh (the
                                      # old throttle synced EVERY step)
    # data (streaming input pipeline; data/ package — see docs/DATA.md).
    # Values are validated at stage construction: window/workers must be
    # >= 1, prefetch_depth >= 0.
    "data.shuffle_window": 1024,   # records per windowed-shuffle block
    "data.decode_workers": 4,      # parallel decode worker threads
    "data.prefetch_depth": 0,      # to_device_iterator queue depth
                                   # (0 = inherit runtime.prefetch_depth)
    # evaluation: rows above which evaluators run as jitted XLA programs
    # instead of driver numpy. The device path wins once the scored column
    # is large enough that crossing PCIe once beats funneling through
    # single-threaded numpy sorts; below it the transfer dominates.
    "evaluate.device_rows": 1_000_000,
    # reliability (retry/backoff + network timeouts; reliability/ package)
    "reliability.http_timeout": 30.0,  # seconds per urlopen (downloader)
    "reliability.max_attempts": 3,     # default RetryPolicy attempt cap
    "reliability.base_delay": 0.2,     # first backoff delay (seconds)
    # liveness layer (watchdog / circuit breakers; see docs/RELIABILITY.md)
    "reliability.stall_timeout_s": 0.0,   # 0 = watchdog stall detection off
    "reliability.watchdog_poll_s": 1.0,   # monitor thread poll cadence
    "reliability.breaker_failures": 5,    # consecutive failures -> open
    "reliability.breaker_reset_s": 30.0,  # open -> half-open probe delay
    # serving (dynamic micro-batching inference server; serve/ package)
    "serving.max_batch": 64,          # rows per flushed micro-batch
    "serving.max_wait_ms": 5.0,       # max coalescing wait before flush
    "serving.queue_depth": 256,       # bounded admission queue (overload
                                      # beyond this sheds, never queues)
    "serving.buckets": "",            # "" = {1, max/8, max/2, max}; else
                                      # e.g. "1,8,64" (largest >= max_batch)
    "serving.default_deadline_ms": 0.0,  # 0 = requests never expire
    "serving.drain_timeout_s": 10.0,  # graceful-drain budget before close
    "serving.retry_after_s": 0.0,     # Retry-After hint on a queue-full
                                      # shed (draining replicas hint 1.0)
    # generate (autoregressive decode lane; serve/generate.py + kvcache.py
    # — see docs/SERVING.md "Generative lane" and the KV sizing runbook)
    "generate.max_seq_len": 512,      # hard cap on prompt + generated
    "generate.prefill_buckets": "",   # "" = powers of two up to max_seq_len
                                      # starting at kv_block_tokens; else
                                      # e.g. "32,128,512" (prompt-length
                                      # buckets; one prefill program each)
    "generate.kv_block_tokens": 16,   # tokens per paged KV block (the
                                      # arena allocation granule)
    "generate.max_sequences": 8,      # decode batch cap = in-flight
                                      # sequence cap (batch-size buckets
                                      # derive from it: {1, /4, /2, max})
    "generate.max_new_tokens": 64,    # default generation budget per
                                      # request (callers can lower/raise)
    "generate.arena_mb": 0.0,         # fixed KV arena size; 0 = derive
                                      # from max_sequences x max_seq_len.
                                      # Accounted under
                                      # runtime.device_cache_mb either way
    "generate.prefix_cache": True,    # shared-prefix KV reuse: hash full
                                      # prompt blocks so N requests with
                                      # one system prompt pay prefill once
                                      # (refcounted blocks, copy-on-write)
    "generate.prefill_chunk": 0,      # >0: split prompts into chunks of
                                      # this many tokens, interleaved with
                                      # decode steps so a long joiner never
                                      # stalls the running batch's ITL
    "generate.kv_dtype": "",          # "" = model dtype; "int8" stores KV
                                      # blocks quantized (per-row scales,
                                      # dequant fused into decode) — ~2x
                                      # arena capacity, quality-gated
    "generate.draft_model": "",       # registered model name proposing
                                      # draft tokens (speculative decode);
                                      # "" disables speculation
    "generate.spec_tokens": 3,        # draft tokens proposed+verified per
                                      # target step when draft_model is set
    "generate.advertise_top_k": 8,    # resident prefix chains summarized
                                      # into the replica's PrefixDigest
                                      # (kvcache stats -> scraper -> router
                                      # affinity; 0 disables advertisement)
    "generate.shard_kv": True,        # on a tensor-parallel model mesh,
                                      # shard the KV arena's head axis over
                                      # `tensor` (requires heads % |tensor|
                                      # == 0); False keeps it replicated
    # parallel (mesh topology; parallel/mesh.py — see docs/PERFORMANCE.md
    # "2-D data x model mesh")
    "parallel.mesh_shape": "",        # "DxT" shorthand, e.g. "4x2" =
                                      # data=4, tensor=2. Takes precedence
                                      # over runtime.mesh; "" defers to it
    # embed (row-sharded recommender tables; embed/ package — see
    # docs/RECOMMENDER.md)
    "embed.row_multiple": 8,          # table rows round up to this multiple
                                      # so any tensor axis up to it shards
                                      # every table evenly (the shard
                                      # granule; rows beyond the declared
                                      # count are zero pad)
    "embed.fused_lookup": True,       # tensor meshes use the fused
                                      # bucketize/all-to-all lookup and the
                                      # sparse all-gather scatter-add
                                      # gradient; False falls back to the
                                      # reference gather (GSPMD partitions
                                      # it against the sharded table) for
                                      # numerics triage
    # fleet (multi-replica router + rolling rollout; see docs/SERVING.md)
    "fleet.replicas": 2,              # in-process replicas per Fleet
    "fleet.failover_attempts": 2,     # routing tries per request (1 = no
                                      # failover; 2 = one retry elsewhere)
    "fleet.failover_delay_s": 0.0,    # backoff between failover attempts
    "fleet.probe_interval_s": 1.0,    # background health-probe cadence
    "fleet.capacity_rows": 0,         # tenant-fairness capacity (0 =
                                      # derive from replica queue depths)
    "fleet.tenant_weights": "",       # "gold=3,free=1"; unlisted tenants
                                      # get fleet.tenant_default_weight
    "fleet.tenant_default_weight": 1.0,
    # prefix-affinity routing (serve/affinity.py — see docs/SERVING.md
    # "fleet as one cache"): replicas advertise their resident prefix
    # chains; the router scores READY replicas by expected hit depth
    # before the smooth-WRR tie-break. Breaker/overload/failover always
    # override affinity — a cache hit is never worth a down replica.
    "fleet.affinity_enabled": True,   # False = prefix-blind WRR only
    "fleet.affinity_min_depth": 1,    # matched blocks required before
                                      # prefix affinity overrides WRR
    "fleet.affinity_vnodes": 64,      # virtual nodes per replica on the
                                      # session consistent-hash ring
    "fleet.affinity_seed": 0,         # ring placement seed (deterministic)
    "fleet.affinity_prewarm": 4,      # hottest prompt prefixes replayed
                                      # through a rollout canary's prefill
                                      # before it takes weight (0 = off)
    "fleet.affinity_spill_factor": 1.5,  # bounded load: an affinity
                                      # leader whose in-flight count
                                      # exceeds factor*(fleet mean + 1)
                                      # spills the pick back to WRR — a
                                      # cache hit is never worth a hot
                                      # spot (0 = never spill)
    # process-fleet supervisor (serve/supervisor.py — real worker
    # processes with restart-on-crash; see docs/SERVING.md runbook)
    "fleet.supervisor_min_uptime_s": 5.0,   # a child dying sooner counts
                                            # as a crash-loop failure
    "fleet.supervisor_base_delay_s": 0.5,   # first restart backoff
    "fleet.supervisor_max_delay_s": 30.0,   # restart backoff cap
    "fleet.supervisor_ready_timeout_s": 120.0,  # spawn -> ready budget
                                                # (includes child imports)
    "fleet.supervisor_breaker_failures": 3,  # consecutive short-lived
                                             # crashes -> breaker open,
                                             # replica out of rotation
    "fleet.supervisor_breaker_reset_s": 60.0,  # open -> one probe respawn
    "fleet.supervisor_poll_s": 0.2,          # monitor thread cadence
    "fleet.devices_per_worker": 0,    # >0: each spawned worker process
                                      # owns a disjoint block of K local
                                      # chips (TPU_VISIBLE_CHIPS) and
                                      # starts with JAX_PLATFORMS=tpu;
                                      # 0 = no chip of its own (CLI:
                                      # `fleet --devices-per-worker K`)
    "fleet.hosts": "",                # comma list of hosts for the multi-
                                      # host launcher (serve/launcher.py;
                                      # "local" runs on this machine, any
                                      # other name goes over ssh); "" =
                                      # single-host supervisor fleet. CLI:
                                      # `fleet --hosts h1,h2` / --hosts-file
    # logging
    "logging.level": "INFO",
    "logging.metrics_every": 0,       # default train-metric log cadence (steps)
    "logging.history_max": 1000,      # MetricLogger history cap (entries)
    # profiling
    "profiling.trace_dir": "",        # non-empty = capture jax traces here
    # observability (spans + event log + metrics registry; observability/)
    "observability.events_path": "",  # non-empty = append JSONL events here
    "observability.metrics": False,   # hot-path (per-step) metric collection
    "observability.annotate": False,  # span() also opens a TraceAnnotation
    "observability.trace_slow_ms": 0.0,  # >0 = serve requests slower than
                                         # this emit full span detail +
                                         # histogram exemplars (tail
                                         # sampling; docs/OBSERVABILITY.md)
    "observability.flight_recorder_size": 256,  # last-N in-memory event
                                                # ring, dumped on stall/
                                                # chaos-red/crash (0 = off)
    "observability.scrape_interval_s": 5.0,  # FleetScraper background poll
                                             # cadence (start_scraper)
    "observability.memory_poll_s": 0.0,      # >0 = periodic HBM ledger
                                             # audit (jax.live_arrays sweep)
    # SLO objectives (observability/slo.py): evaluated over rolling
    # windows against the aggregated fleet view with multi-window
    # burn-rate alerting (fast/slow windows, SRE-workbook recipe)
    "slo.availability_target": 0.999,  # 1 - bad/admitted objective
    "slo.latency_p99_ms": 0.0,         # >0 = p99 total-latency budget (ms)
    "slo.ttft_p99_ms": 0.0,            # >0 = generate-lane TTFT p99 budget
    "slo.fast_window_s": 300.0,        # fast burn window (page-now signal)
    "slo.slow_window_s": 3600.0,       # slow burn window (sustained burn)
    "slo.fast_burn": 14.4,             # burn-rate threshold, fast window
    "slo.slow_burn": 6.0,              # burn-rate threshold, slow window
    # autopilot (control/autopilot.py — the SLO-driven control loop that
    # actuates router weights, replica count, admission quotas, and
    # rollout aborts from the scraper/SLO/ledger signals; every decision
    # and every suppressed decision is an `autopilot.*` event; see
    # docs/AUTOPILOT.md for the signal -> lever matrix and tuning runbook)
    "autopilot.enabled": False,        # `serve --autopilot` flips this on
    "autopilot.tick_s": 5.0,           # evaluation cadence (injectable
                                       # clock; one decide() per tick)
    "autopilot.min_replicas": 1,       # scale floor — also the repair
                                       # target after a replica death
    "autopilot.max_replicas": 8,       # scale ceiling (bounds veto)
    "autopilot.hbm_limit_bytes": 0,    # >0 = veto scale-up when projected
                                       # fleet HBM (ledger total + one
                                       # replica's share) would exceed it
    "autopilot.scale_up_queue": 4.0,   # mean queue depth per ready
                                       # replica at/above which the fleet
                                       # grows one replica
    "autopilot.scale_down_queue": 0.0,  # mean queue depth at/below which
                                        # an idle, non-burning fleet
                                        # shrinks (hysteresis gap vs up)
    "autopilot.scale_cooldown_s": 25.0,
    "autopilot.shift_error_rate": 0.5,  # per-tick failure fraction
                                        # at/above which traffic ramps
                                        # OFF a replica (outlier shift)
    "autopilot.shift_recover_rate": 0.05,  # fraction at/below which a
                                           # ready replica's weight ramps
                                           # back (separate up threshold)
    "autopilot.shift_step": 0.5,       # router weight moved per action
    "autopilot.shift_cooldown_s": 20.0,
    "autopilot.admission_factor": 0.5,  # capacity_rows multiplier per
                                        # tighten (relax divides by it)
    "autopilot.admission_floor_frac": 0.25,  # tighten floor as a fraction
                                             # of the baseline capacity
    "autopilot.admission_relax_burn": 1.0,  # fast burn at/below which a
                                            # tightened quota relaxes
    "autopilot.admission_cooldown_s": 25.0,
    "autopilot.reshard_wide": "",      # fifth lever: mesh shape to reshard
                                       # TO under HBM-ledger pressure
                                       # (e.g. "2x4" — wider tensor axis,
                                       # smaller per-chip shard); "" = off
    "autopilot.reshard_narrow": "",    # mesh shape to reshard TO when
                                       # queue depth wants replicas past
                                       # max_replicas (e.g. "4x2"); "" =
                                       # off. wide != narrow: the gap is
                                       # the hysteresis band
    "autopilot.reshard_hbm_frac": 0.85,  # HBM fraction of hbm_limit_bytes
                                         # at/above which the wide reshard
                                         # fires
    "autopilot.reshard_cooldown_s": 60.0,  # shared by BOTH directions (one
                                           # "reshard" cooldown key), so
                                           # placements cannot oscillate
    "autopilot.window_s": 120.0,       # rolling actuation-budget window
    "autopilot.max_actions_per_window": 8,  # hard budget: decisions past
                                            # it are suppressed ("window")
    "autopilot.scale_backend": "auto",  # what the scale lever actuates:
                                        # "inprocess" = Fleet server
                                        # threads, "process" = supervised
                                        # worker processes (Supervisor.
                                        # add_slot/retire_slot via
                                        # ProcessFleet), "auto" = process
                                        # when a supervisor backs the
                                        # fleet, else in-process
}

_lock = threading.Lock()
_overrides: Dict[str, Any] = {}


def _env_key(key: str) -> str:
    return "MMLSPARK_TPU_" + key.replace(".", "_").upper()


def get(key: str, default: Any = None) -> Any:
    """Resolve a config key (``namespace.name``)."""
    with _lock:
        if key in _overrides:
            return _overrides[key]
    env = os.environ.get(_env_key(key))
    if env is not None:
        base = _DEFAULTS.get(key, default)
        return _coerce(env, base)
    if key in _DEFAULTS:
        return _DEFAULTS[key]
    if default is not None:
        return default
    raise KeyError(f"unknown config key {key!r}; known: {sorted(_DEFAULTS)}")


def set(key: str, value: Any) -> None:  # noqa: A001 - mirrors typesafe API
    """Programmatic override (highest precedence). Unknown keys are allowed
    so applications can park their own knobs in the same tree."""
    with _lock:
        _overrides[key] = value


def unset(key: str) -> None:
    with _lock:
        _overrides.pop(key, None)


def snapshot() -> Dict[str, Any]:
    """Fully-resolved view of every known key (for logs / debugging)."""
    merged = dict(_DEFAULTS)
    with _lock:
        merged.update(_overrides)
    return {k: get(k, merged[k]) for k in sorted(merged)}


def _coerce(text: str, like: Any) -> Any:
    if isinstance(like, bool):
        return text.lower() in ("1", "true", "yes", "on")
    if isinstance(like, int):
        return int(text)
    if isinstance(like, float):
        return float(text)
    return text
