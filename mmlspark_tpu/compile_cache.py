"""Persistent compilation cache: recompilation is the other overhead floor.

BENCH_r05's low-MFU lanes are dispatch-bound (killed by the sync-free
stepping in :mod:`~mmlspark_tpu.parallel.trainer`), but every process
RESTART and every :meth:`Fleet.rollout` replica warm pays a second tax —
recompiling programs whose HLO has not changed. This module removes it in
two layers that share ONE directory, decided by :func:`cache_dir` alone:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (jax reads that
variable itself, so nothing in code overrides it), else the
``runtime.compile_cache_dir`` config key ("" = off, nothing touches disk):

1. :func:`enable` turns on jax's own persistent compilation cache at that
   directory so EVERY jit path — trainer steps, eval programs, transform
   closures — reuses XLA output across processes. Idempotent; call it
   once at process entry (the CLI, ``bench.py`` and ``chip_smoke.py`` do;
   the last two pass ``<checkout>/.jax_cache`` as the default).

2. :func:`load_or_compile` — an on-disk AOT *program* cache for the serve
   bucket executables behind :meth:`ModelEntry._compile`. jax's cache only
   skips XLA backend work; the serve path AOT-compiles concrete
   executables, and ``jax.experimental.serialize_executable`` lets the
   whole loaded program skip lowering too. Entries are keyed on
   (model name+version, padded bucket shape, dtype) in the file NAME and
   carry the (jax version, jaxlib version, device fingerprint) environment
   in the file HEADER, so a stale toolchain is *detected* (bypass event +
   fresh compile overwrites) rather than silently misloaded. Writes go
   through the reliability layer's tmp-file + ``os.replace`` atomic
   pattern — a concurrent writer loses the race harmlessly and readers
   never observe a torn file; payloads are sha256-verified on load and
   corrupt entries are quarantined aside (``.corrupt``) to a fresh
   compile.

Every outcome is counted (``compile_cache.hits/misses/bypasses/stale/
quarantined/stores`` counters) and evented (``compile_cache.*``), feeding
the ``mmlspark-tpu report`` compile-cache section. This module is also the
sanctioned compile seam for serve code: lint Rule 9 flags any
``lower().compile()`` / ``jax.jit`` call site under ``serve/`` that does
not route here (``# lint: allow-compile`` opts out deliberately).
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import shutil
import threading
from typing import Any, Callable, Dict, Iterator, NamedTuple, Optional, Tuple

from mmlspark_tpu.observability import compiles, events, metrics
from mmlspark_tpu.utils import config as mmlconfig
from mmlspark_tpu.utils.logging import get_logger

logger = get_logger("compile_cache")

_FORMAT_VERSION = 2   # v2: header carries the program's device ids
_SUFFIX = ".xprog"

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# AOT namespace under the cache root, set by :func:`lane` ("" = the root's
# own ``aot/``). Process-wide on purpose: in-process fleet replicas compile
# on their own threads and must land in the lane the harness opened.
_lane = ""


class CacheResult(NamedTuple):
    """What :func:`load_or_compile` did: the executable plus provenance
    (``source`` in {hit, miss, stale, corrupt, bypass}) so callers count
    real compiles separately from cache loads."""
    program: Callable
    source: str

    @property
    def hit(self) -> bool:
        return self.source == "hit"


def cache_dir() -> str:
    """THE decision of where compiled programs persist in this process
    ("" = nowhere). ``JAX_COMPILATION_CACHE_DIR`` wins when set: jax reads
    it at import for its own cache, the AOT entries go under the same
    directory, and no code sets another. Otherwise the
    ``runtime.compile_cache_dir`` config key."""
    return os.environ.get(ENV_VAR) \
        or str(mmlconfig.get("runtime.compile_cache_dir") or "")


def worker_env(root: Optional[str] = None) -> Dict[str, str]:
    """Environment exports that point a CHILD process at a persistent
    cache (``root``, default this process's own). The process-fleet
    supervisor spawns each replica with this merged into its environment,
    so replica N+1 (and every warm restart) cold-starts by LOADING the
    programs replica N stored — multi-reader is safe by construction
    here: entries publish via tmp-file + ``os.replace`` and are
    sha256-verified on load, so a concurrent writer loses the race
    harmlessly and a reader never observes a torn file. Returns ``{}``
    when caching is off."""
    root = cache_dir() if root is None else str(root or "")
    if not root:
        return {}
    return {ENV_VAR: os.path.abspath(root)}


def enable(default_dir: str = "") -> Optional[str]:
    """Turn on jax's persistent compilation cache at :func:`cache_dir` for
    all jit paths; returns the directory, or None when caching is off.
    ``default_dir`` is what an entry point falls back to when neither the
    environment nor the config names a directory (it becomes
    ``runtime.compile_cache_dir``, so the AOT layer follows). Idempotent;
    call before the first compile — jax binds its cache to one directory
    for the life of the process. Whether or not a cache is on, the
    program's ledger of what jax compiles (``observability/compiles.py``,
    the ``compile.*`` counters) listens from here."""
    compiles.install()
    root = cache_dir()
    if not root and default_dir:
        root = os.path.abspath(default_dir)
        mmlconfig.set("runtime.compile_cache_dir", root)
    if not root:
        return None
    import jax
    if not os.environ.get(ENV_VAR) \
            and jax.config.jax_compilation_cache_dir != root:
        os.makedirs(root, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", root)
        if events.recording_enabled():
            events.emit("compile_cache", "enabled", dir=root)
        logger.info("persistent compilation cache at %s", root)
    # cache tiny programs too: the serve buckets and bench lanes this
    # exists for compile in well under jax's 1 s default threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return root


@contextlib.contextmanager
def lane(name: str, fallback_root: str) -> Iterator[str]:
    """Give a bench lane or chaos scenario its own, initially EMPTY, AOT
    namespace ``<cache_dir()>/lanes/<name>`` for the duration; yields that
    directory (what a harness hands its spawned workers as their cache
    root). AOT entries are keyed by model NAME + version, so harnesses
    that reuse a serving name for different architectures must not share
    entries; and their counts (compiles, hits, cold -> warm) are only a
    function of the seed if every run starts cold. A fixed name under the
    resolved root — not a temporary directory — keeps everything a run
    writes where the cache was placed. ``fallback_root`` is used (as
    ``runtime.compile_cache_dir``) only when no cache is configured."""
    global _lane
    fell_back = not cache_dir()
    if fell_back:
        mmlconfig.set("runtime.compile_cache_dir",
                      os.path.abspath(fallback_root))
    prior_lane, _lane = _lane, os.path.join("lanes", name)
    path = os.path.join(cache_dir(), _lane)
    shutil.rmtree(path, ignore_errors=True)
    try:
        yield path
    finally:
        _lane = prior_lane
        if fell_back:
            mmlconfig.unset("runtime.compile_cache_dir")


def device_fingerprint() -> str:
    """Stable identity of the toolchain + attached devices: a serialized
    executable is only loadable onto the platform/topology it was built
    for, and a jax/jaxlib bump invalidates the wire format."""
    import jax
    import jaxlib
    devs = jax.devices()
    return "|".join([
        f"jax={jax.__version__}",
        f"jaxlib={jaxlib.__version__}",
        f"platform={devs[0].platform}",
        f"kind={devs[0].device_kind}",
        f"n={len(devs)}",
    ])


def entry_key(model: str, version: str, bucket: int,
              row_shape: Tuple[int, ...], dtype: str,
              mesh_key: str = "") -> str:
    """Filename stem for one program: the model+shape identity. The
    environment (jax/device fingerprint) lives in the header, not the
    name, so a toolchain bump is a *detected* stale entry, not a silent
    cache miss that leaves garbage behind. ``mesh_key`` is the placement
    identity ('' for single-device): an elastic reshard serves the same
    model+version under DIFFERENT mesh placements, and their partitioned
    executables must coexist, never collide (the score-path twin of the
    generative lane's ``|mesh=`` shape_key suffix)."""
    parts = [model, version, str(int(bucket)),
             ",".join(str(int(d)) for d in row_shape), str(dtype)]
    if mesh_key:
        parts.append(f"mesh={mesh_key}")
    ident = "\x00".join(parts)
    return hashlib.sha256(ident.encode("utf-8")).hexdigest()[:40]


def _aot_dir(root: str) -> str:
    # separate the AOT program entries from jax's own cache files
    return os.path.join(root, _lane, "aot")


def _counter(name: str):
    return metrics.counter(f"compile_cache.{name}")


def _event(name: str, **fields: Any) -> None:
    if events.recording_enabled():
        events.emit("compile_cache", name, **fields)


def _quarantine(path: str) -> None:
    """Move a bad entry aside (atomic; never deletes evidence) so the next
    writer starts clean and the corruption is inspectable."""
    try:
        os.replace(path, path + ".corrupt")
    except OSError:
        pass  # raced with another quarantining process: already gone
    _counter("quarantined").inc()


def _load_entry(path: str, fingerprint: str) -> CacheResult | None:
    """Deserialize one on-disk program; None means the caller compiles
    fresh (the entry was absent, stale, or quarantined-corrupt)."""
    try:
        with open(path, "rb") as f:
            header_line = f.readline()
            body = f.read()
        header = json.loads(header_line.decode("utf-8"))
        if header.get("v") != _FORMAT_VERSION:
            raise ValueError(f"format v{header.get('v')}")
    except FileNotFoundError:
        return None
    except (OSError, ValueError, UnicodeDecodeError) as e:
        logger.warning("compile cache entry %s unreadable (%s); "
                       "quarantined", path, e)
        _event("quarantine", path=path, reason=f"header: {e}")
        _quarantine(path)
        return None
    if header.get("env") != fingerprint:
        # a different toolchain/topology wrote this: bypass it and let the
        # fresh compile overwrite the entry for the current environment
        _counter("stale").inc()
        _event("stale", path=path, entry_env=header.get("env"),
               env=fingerprint)
        return CacheResult(None, "stale")  # type: ignore[arg-type]
    digest = hashlib.sha256(body).hexdigest()
    if digest != header.get("sha256"):
        logger.warning("compile cache entry %s failed sha256 verification; "
                       "quarantined", path)
        _event("quarantine", path=path, reason="sha256 mismatch")
        _quarantine(path)
        return None
    try:
        import jax
        from jax.experimental import serialize_executable
        payload, in_tree, out_tree = pickle.loads(body)
        # pin the program to the devices it was compiled for: left to its
        # default, the loader spreads it over EVERY device of the backend
        # and a one-device program comes back expecting N input shards
        by_id = {d.id: d for d in jax.devices()}
        program = serialize_executable.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in header["devices"]])
    except Exception as e:  # deserialization is version-fragile by nature
        logger.warning("compile cache entry %s failed to deserialize "
                       "(%s: %s); quarantined", path, type(e).__name__, e)
        _event("quarantine", path=path,
               reason=f"{type(e).__name__}: {e}")
        _quarantine(path)
        return None
    _charge_program(header.get("model"), path, len(body))
    return CacheResult(program, "hit")


def _charge_program(model: Any, path: str, nbytes: int) -> None:
    """Report one loaded/stored executable's serialized size into the HBM
    ledger (kind=``program``, keyed by cache path so reloads never
    double-charge). Best-effort: accounting must never fail a compile."""
    if not model:
        return
    try:
        from mmlspark_tpu.observability import memory as devmem
        devmem.get_ledger().note_program(str(model), path, int(nbytes))
    except Exception as e:  # pragma: no cover - defensive
        logger.warning("program-bytes ledger charge failed for %s (%s)",
                       path, e)


def _store_entry(path: str, program, meta: Dict[str, Any],
                 fingerprint: str) -> bool:
    """Serialize + atomically publish one compiled program. False when the
    executable does not support serialization (counted as a bypass — the
    compile still happened and serving proceeds uncached)."""
    try:
        from jax.experimental import serialize_executable
        body = pickle.dumps(serialize_executable.serialize(program))
        devices = [d.id for d in
                   program.runtime_executable().local_devices()]
    except Exception as e:
        _counter("bypasses").inc()
        _event("bypass", reason=f"serialize: {type(e).__name__}: {e}",
               **meta)
        return False
    header = dict(meta, v=_FORMAT_VERSION, env=fingerprint,
                  devices=devices,
                  sha256=hashlib.sha256(body).hexdigest(), size=len(body))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        with open(tmp, "wb") as f:
            f.write(json.dumps(header, sort_keys=True).encode("utf-8"))
            f.write(b"\n")
            f.write(body)
        os.replace(tmp, path)  # atomic: concurrent writers last-win whole
    except OSError as e:
        logger.warning("compile cache store failed for %s (%s)", path, e)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False
    _counter("stores").inc()
    _charge_program(meta.get("model"), path, len(body))
    _event("store", path=path, bytes=len(body), **meta)
    return True


def _cached_compile(stem: str, meta: Dict[str, Any],
                    fresh: Callable[[], Callable]) -> CacheResult:
    """Shared load -> verify -> compile -> store body behind both public
    entry points. ``stem`` is the filename stem (shape identity), ``fresh``
    the closure that actually compiles when the cache cannot serve."""
    root = cache_dir()
    if not root:
        _counter("bypasses").inc()
        _event("bypass", reason="no compile cache directory", **meta)
        return CacheResult(fresh(), "bypass")
    path = os.path.join(_aot_dir(root), stem + _SUFFIX)
    fingerprint = device_fingerprint()
    loaded = _load_entry(path, fingerprint)
    if loaded is not None and loaded.source == "hit":
        _counter("hits").inc()
        _event("hit", path=path, **meta)
        return loaded
    source = loaded.source if loaded is not None else "miss"
    if source == "miss":
        _counter("misses").inc()
        _event("miss", path=path, **meta)
    program = fresh()
    _store_entry(path, program, meta, fingerprint)
    return CacheResult(program, source)


def load_or_compile(model: str, version: str, bucket: int,
                    row_shape: Tuple[int, ...], dtype: Any,
                    jitted, params, mesh_key: str = "") -> CacheResult:
    """The serve-side compile seam: return the AOT executable for one
    padded bucket shape, loading it from ``runtime.compile_cache_dir``
    when a verified entry exists and compiling (then storing) otherwise.

    ``jitted`` is the model's raw jitted apply (``apply._jitted``) and
    ``params`` its device-resident tree — the compile itself happens HERE
    so serve/ modules never spell ``lower().compile()`` (lint Rule 9).
    The returned program is called as ``program(params, x)``.
    ``mesh_key`` carries the placement identity for mesh-bound models
    (see :func:`entry_key`) so resharded placements get their own
    entries.
    """
    import jax
    import numpy as np
    dtype_name = np.dtype(dtype).name
    spec = jax.ShapeDtypeStruct((int(bucket),) + tuple(row_shape),
                                np.dtype(dtype))
    meta = {"model": model, "version": version, "bucket": int(bucket),
            "row_shape": list(int(d) for d in row_shape),
            "dtype": dtype_name}
    if mesh_key:
        meta["mesh"] = mesh_key

    def fresh() -> Callable:
        return jitted.lower(params, spec).compile()

    return _cached_compile(
        entry_key(model, version, bucket, tuple(row_shape), dtype_name,
                  mesh_key),
        meta, fresh)


def program_key(model: str, version: str, kind: str, shape_key: str) -> str:
    """Filename stem for a generalized AOT program (the generative lane's
    prefill/decode executables): identity is (model+version, program kind,
    caller-provided shape string). Same header-carries-environment contract
    as :func:`entry_key`."""
    ident = "\x00".join([model, version, kind, shape_key])
    return hashlib.sha256(ident.encode("utf-8")).hexdigest()[:40]


def load_or_compile_program(model: str, version: str, kind: str,
                            shape_key: str, jitted,
                            *abstract_args: Any) -> CacheResult:
    """Generalized sibling of :func:`load_or_compile` for programs whose
    signature is richer than ``(params, x)`` — the generative lane's
    bucketed prefill and decode executables take KV arenas, token ids,
    position/block-table operands, and declare arena donation on the
    jitted function itself.

    ``abstract_args`` are exactly what ``jitted.lower`` receives: concrete
    params trees and ``jax.ShapeDtypeStruct`` placeholders. Donation
    semantics ride on ``jitted`` (``jax.jit(..., donate_argnums=...)``);
    backends that cannot donate (CPU test mesh) warn harmlessly, so that
    specific warning is silenced at the compile site here.
    """
    import warnings
    meta = {"model": model, "version": version, "kind": kind,
            "shape_key": shape_key}

    def fresh() -> Callable:
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message=".*[Dd]onat")  # CPU: donation unsupported
            return jitted.lower(*abstract_args).compile()

    return _cached_compile(program_key(model, version, kind, shape_key),
                           meta, fresh)


def stats() -> Dict[str, int]:
    """Hit/miss/bypass/stale/quarantine/store counter snapshot (the report
    section and tests read this)."""
    return {name: int(_counter(name).value)
            for name in ("hits", "misses", "bypasses", "stale",
                         "quarantined", "stores")}
