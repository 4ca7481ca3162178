"""Generative serving lane: continuous batching over a paged KV arena.

The ``/score`` lane batches REQUESTS — admit, coalesce, one program call,
respond. Autoregressive generation cannot ride that shape: one request is
hundreds of sequential single-token steps, and naive request-batching
either runs each sequence alone (device idle at batch 1) or locks a batch
together until its LONGEST member finishes (finished sequences pad along,
waiting prompts starve). This module is the decode-native lane:

- :class:`GenerativeEntry` — the compiled half: program shapes,
  buckets and the compile seam, and nothing of the model or of the
  arena's format. Every program that computes is the served flax module
  itself, applied with a :class:`~mmlspark_tpu.serve.kvcache.CacheView`
  as its ``attention_fn`` (the seam ring and Ulysses attention use): the
  view writes each layer's new K/V rows into the arena and reads the
  paged context back, so the numerics are the served model's by
  construction and a new block needs no edit here. One **prefill**
  program per prompt-length bucket, and ONE **step** body at three
  leading shapes: **decode** ``(B, 1)`` per batch-size bucket, **chunk**
  ``(1, C)``, **verify** ``(B, spec_width)``. All programs AOT-compile
  through :meth:`GenerativeEntry._compile` — the generative twin of
  ``ModelEntry._compile`` — into the persistent program cache, so a warm
  replica restart pays ZERO compiles.
- :class:`ContinuousBatcher` — the policy half, pure logic like
  ``MicroBatcher``: sequences JOIN the in-flight batch the step a slot
  frees and LEAVE the step they finish; nobody waits for anyone else's
  completion.
- :class:`GenerateLane` — the executor half: a single thread owning the
  arena; each pass admits joiners (prefill + first sampled token = TTFT),
  then runs one bucketed decode step over the whole active set.

Admission reserves a sequence's FULL block budget (prompt + max-new) up
front from the :class:`~mmlspark_tpu.serve.kvcache.KVCacheManager`; when
the free list cannot cover it the request sheds with a retryable
``ServerOverloaded`` — decode never OOMs mid-flight and the fleet router
retries elsewhere. Sampling (greedy, temperature/top-k) is seeded per
(seed, position), so a failover RESTART from the prompt on a surviving
replica replays the exact token stream.

Every program donates the arena buffers (in-place on TPU). Attention
over the arena (decode, chunk, verify) is jnp in the cache view: a scatter
of the new rows, a gather of every sequence's full block table, a masked
softmax; no kernel. Only prefill, which attends over the prompt's own
rows, goes through ``full_attention`` (a fused Pallas kernel on real
chips for the lengths it takes, the jnp reference on the CPU test mesh).

Four compounding raw-speed attacks ride the same seams (all
config-gated, all compiled through :meth:`GenerativeEntry._compile` so a
warm restart still pays zero XLA compiles):

- **Shared-prefix KV reuse** (``generate.prefix_cache``): admission
  hashes the prompt's full blocks (chained — see
  :func:`~mmlspark_tpu.serve.kvcache.prefix_block_hashes`) and
  ``KVCacheManager.try_reserve`` shares already-cached blocks, so N
  requests behind one system prompt pay prefill ONCE; only the uncached
  suffix runs through the **chunk** program. A full-prompt hit schedules
  a copy-on-write of the final block (no block is ever written while
  shared) and recomputes just the last position for its first token.
- **Chunked prefill** (``generate.prefill_chunk``): long prompts split
  into fixed-width chunks processed one per lane step, interleaved with
  decode — a long joiner never stalls the running batch's ITL.
- **Speculative decoding** (``generate.draft_model`` +
  ``generate.spec_tokens``): a small draft model (its own
  :class:`GenerativeEntry` + arena) proposes k tokens per step; the
  target checks them in ONE **verify** program call (the step body at
  k+1 positions a lane). Accept/reject replays the exact
  per-(seed, position) sampler, so greedy AND seeded-sampling outputs
  are token-identical to the non-speculative lane by construction.
- **int8 KV blocks** (``generate.kv_dtype=int8``): the arena stores
  quantized rows (~2x concurrent-sequence capacity at fixed bytes);
  the cache view quantizes what it writes and dequantizes what it reads
  inside the programs, and nothing here knows the format.
"""
from __future__ import annotations

import math
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from mmlspark_tpu.observability import events, metrics, spans, syncs
from mmlspark_tpu.reliability import watchdog as _watchdog
from mmlspark_tpu.reliability.faults import fault_site
from mmlspark_tpu.serve.batcher import bucket_for, default_buckets
from mmlspark_tpu.serve.kvcache import (
    RESERVED_BLOCK, CacheView, KVCacheManager, blocks_needed,
    prefix_block_hashes,
)
from mmlspark_tpu.utils import config as mmlconfig
from mmlspark_tpu.utils.logging import get_logger

logger = get_logger("serve.generate")

_STOP = object()


# ---------------------------------------------------------------------------
# buckets


def parse_prefill_buckets(text: str, max_seq_len: int,
                          block_tokens: int) -> Tuple[int, ...]:
    """``generate.prefill_buckets`` config -> ascending bucket tuple.
    Every bucket must be a multiple of ``block_tokens`` (prefill scatters
    whole blocks) and the ladder must cover ``max_seq_len``. "" derives
    powers of two from ``block_tokens`` up to ``max_seq_len``."""
    if text.strip():
        vals = sorted({int(v) for v in text.split(",") if v.strip()})
    else:
        vals, b = [], block_tokens
        while b < max_seq_len:
            vals.append(b)
            b *= 2
        vals.append(b)
    bad = [v for v in vals if v < 1 or v % block_tokens]
    if bad:
        raise ValueError(
            f"prefill buckets must be positive multiples of "
            f"kv_block_tokens={block_tokens}, got {bad}")
    if vals[-1] < max_seq_len:
        raise ValueError(
            f"largest prefill bucket {vals[-1]} < max_seq_len "
            f"{max_seq_len}; the longest admissible prompt would have no "
            "compiled shape")
    return tuple(vals)


# ---------------------------------------------------------------------------
# sampling — host-side, deterministic per (seed, position) so a failover
# restart from the prompt replays the identical token stream


def sample_token(logits: np.ndarray, *, temperature: float, top_k: int,
                 seed: int, position: int) -> int:
    """One next-token draw from a (vocab,) logits row. ``temperature <= 0``
    is greedy (pure argmax, no RNG at all); otherwise top-k + temperature
    with an RNG derived from (seed, position) — the same (seed, position)
    always yields the same token regardless of replica or retry."""
    if temperature <= 0:
        return int(np.argmax(logits))
    scaled = logits.astype(np.float64) / float(temperature)
    if top_k > 0 and top_k < scaled.size:
        cutoff = np.partition(scaled, -top_k)[-top_k]
        scaled = np.where(scaled < cutoff, -np.inf, scaled)
    scaled = scaled - scaled.max()
    p = np.exp(scaled)
    p /= p.sum()
    rng = np.random.default_rng((int(seed) & 0x7FFFFFFF, int(position)))
    return int(rng.choice(p.size, p=p))


# ---------------------------------------------------------------------------
# requests and sequences


@dataclass
class GenerateRequest:
    """One admitted generation ask (the ``/generate`` wire shape)."""
    model: str
    prompt: Sequence[int]
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    eos_id: Optional[int] = None
    deadline_ms: Optional[float] = None
    trace_id: str = ""


class _Seq:
    """One in-flight sequence: prompt, sampled tokens, leased blocks, and
    the latency ledger (TTFT + inter-token gaps) its caller is owed."""

    __slots__ = ("seq_id", "prompt", "max_new", "temperature", "top_k",
                 "seed", "eos_id", "future", "trace_id", "enqueued",
                 "deadline", "generated", "ttft_s", "last_t", "itl_s",
                 "finish", "prefill_pos", "hashes", "spec_ok",
                 "spec_proposed", "spec_accepted", "prefix_hits",
                 "draft_hashes")

    def __init__(self, seq_id: str, req: GenerateRequest, future: Future,
                 enqueued: float, deadline: Optional[float]):
        self.seq_id = seq_id
        self.prompt = np.asarray(req.prompt, np.int32).ravel()
        self.max_new = int(req.max_new_tokens)
        self.temperature = float(req.temperature)
        self.top_k = int(req.top_k)
        self.seed = int(req.seed)
        self.eos_id = req.eos_id
        self.future = future
        self.trace_id = req.trace_id
        self.enqueued = enqueued
        self.deadline = deadline
        self.generated: List[int] = []
        self.ttft_s: Optional[float] = None
        self.last_t = enqueued
        self.itl_s: List[float] = []
        self.finish = ""
        self.prefill_pos = 0            # next prompt position to prefill
        self.hashes: List[str] = []     # chained full-block prefix hashes
        self.spec_ok = False            # draft arena reserved: may ride
        self.spec_proposed = 0          # speculation for this sequence
        self.spec_accepted = 0
        self.prefix_hits = 0            # prefix blocks shared at reserve
        self.draft_hashes: List[str] = []  # draft-arena prefix hashes

    @property
    def seq_len(self) -> int:
        return len(self.prompt) + len(self.generated)

    def expired(self, now: float) -> bool:
        return self.deadline is not None and now > self.deadline

    def result(self) -> Dict[str, Any]:
        itl = self.itl_s
        return {
            "tokens": list(self.generated),
            "finish_reason": self.finish,
            "ttft_ms": round((self.ttft_s or 0.0) * 1e3, 3),
            "itl_mean_ms": round(sum(itl) / len(itl) * 1e3, 3) if itl
            else 0.0,
            # prefix blocks shared at admission: the per-request ground
            # truth the fleet bench sums into its hit rate — a router
            # that CLAIMS affinity steered well is checked against what
            # the replica's arena actually re-used
            "prefix_hits": int(self.prefix_hits),
            "trace_id": self.trace_id,
        }


# ---------------------------------------------------------------------------
# continuous batching policy (pure logic, injectable clock, no threads)


class ContinuousBatcher:
    """The continuous-batching sibling of
    :class:`~mmlspark_tpu.serve.batcher.MicroBatcher`, speaking the same
    ``offer``/``ready``/``wait_s``/``take`` vocabulary so the executor
    loop reads identically — with one structural difference: ``take``
    admits JOINERS into a persistent ``active`` set (capped at
    ``max_sequences``) instead of flushing a transient group, and
    :meth:`leave` retires a finished sequence the same step it finishes,
    freeing its slot for the next waiter. Not thread-safe by itself; the
    lane's single executor thread is the only caller."""

    def __init__(self, max_sequences: int,
                 clock: Callable[[], float] = time.monotonic):
        if max_sequences < 1:
            raise ValueError(
                f"max_sequences must be >= 1, got {max_sequences}")
        self.max_sequences = int(max_sequences)
        self.clock = clock
        self._waiting: "deque[_Seq]" = deque()
        self._active: List[_Seq] = []

    def __len__(self) -> int:
        return len(self._waiting)

    @property
    def active(self) -> List[_Seq]:
        return list(self._active)

    @property
    def free_slots(self) -> int:
        return self.max_sequences - len(self._active)

    def offer(self, seq: _Seq) -> None:
        self._waiting.append(seq)

    def ready(self, now: Optional[float] = None) -> bool:
        """A step is due whenever anything is decoding or a waiter can
        join — continuous batching has no coalescing delay to wait out."""
        if self._active:
            return True
        return bool(self._waiting) and self.free_slots > 0

    def wait_s(self, now: Optional[float] = None) -> Optional[float]:
        return 0.0 if self.ready(now) else None

    def take(self, now: Optional[float] = None) -> List[_Seq]:
        """Pop the joiners for THIS step: FIFO waiters up to the free
        slots. The caller prefills each and confirms with :meth:`join`
        (or sheds/expires it without joining)."""
        out: List[_Seq] = []
        while self._waiting and len(self._active) + len(out) \
                < self.max_sequences:
            out.append(self._waiting.popleft())
        return out

    def requeue(self, seq: _Seq) -> None:
        """Put a taken-but-not-admitted waiter back at the FRONT of the
        queue (its slot this step went to a sequence still mid-chunked-
        prefill); it stays first in line for the next step."""
        self._waiting.appendleft(seq)

    def join(self, seq: _Seq) -> None:
        if len(self._active) >= self.max_sequences:
            raise ValueError("active set full; take() admitted too many")
        self._active.append(seq)

    def leave(self, seq: _Seq) -> None:
        self._active.remove(seq)

    def drain(self) -> List[_Seq]:
        """Everything still owned by the batcher (waiting + active), for
        shutdown paths. Leaves the batcher empty."""
        out = list(self._waiting) + list(self._active)
        self._waiting.clear()
        self._active.clear()
        return out


# ---------------------------------------------------------------------------
# compiled programs


def _int32(*shape: int):
    """An int32 operand's placeholder to lower a program on."""
    import jax
    return jax.ShapeDtypeStruct(shape, np.int32)


class GenerativeEntry:
    """Compiled generative artifacts for one registered model: the KV
    arena plus bucketed prefill / decode executables.

    :meth:`_compile` is THE generative compile seam — the twin of
    ``ModelEntry._compile`` that tests wrap to assert one compile per
    (kind, bucket) — and it funnels through
    :func:`mmlspark_tpu.compile_cache.load_or_compile_program`, so every
    program lands in the persistent on-disk cache and a warm replica
    restart loads instead of compiling. Real compiles and cache loads
    are accounted on the UNDERLYING ``ModelEntry`` (``compile_count`` /
    ``cache_hits``), so registry stats and the bench gate see scoring and
    generative compiles in one ledger.
    """

    def __init__(self, entry, *, max_seq_len: Optional[int] = None,
                 max_sequences: Optional[int] = None):
        self.entry = entry
        apply = entry.ensure_apply()
        # mesh-bound models decode too: params stay in their (tensor/fsdp)
        # placement and the KV arena below joins them on the same mesh, so
        # a model bigger than one chip's HBM serves the generative lane
        self.mesh = getattr(apply, "_mesh", None)
        spec = entry.model._spec()
        module = spec.get("module")
        for attr in ("vocab", "dim", "depth", "heads", "max_len"):
            if not hasattr(module, attr):
                raise ValueError(
                    f"model {entry.name!r} ({type(module).__name__}) is "
                    "not a decoder LM; the generative lane serves "
                    "TransformerLM-shaped architectures")
        self.module = module
        self.params = apply._params
        self.vocab = int(module.vocab)
        self.dim = int(module.dim)
        self.depth = int(module.depth)
        self.heads = int(module.heads)
        self.head_dim = self.dim // self.heads
        self.dtype = module.dtype
        cap = int(max_seq_len if max_seq_len is not None
                  else mmlconfig.get("generate.max_seq_len"))
        self.max_seq_len = min(cap, int(module.max_len))
        self.max_sequences = int(
            max_sequences if max_sequences is not None
            else mmlconfig.get("generate.max_sequences"))
        self.kv = KVCacheManager.from_config(
            layers=self.depth, heads=self.heads, head_dim=self.head_dim,
            dtype=np.dtype(self.dtype), mesh=self.mesh,
            shard_heads=bool(mmlconfig.get("generate.shard_kv")))
        self.block_tokens = self.kv.block_tokens
        # block-table width: every sequence's table is padded to the
        # blocks a max-length sequence needs, so ONE decode program shape
        # serves every occupancy
        self.table_width = blocks_needed(self.max_seq_len,
                                         self.block_tokens)
        self.prefill_buckets = parse_prefill_buckets(
            str(mmlconfig.get("generate.prefill_buckets")),
            self.max_seq_len, self.block_tokens)
        self.decode_buckets = default_buckets(self.max_sequences)
        self.prefix_cache = bool(mmlconfig.get("generate.prefix_cache"))
        self.prefill_chunk = max(0, int(mmlconfig.get(
            "generate.prefill_chunk")))
        # the chunk program's width: the configured chunk, else one block
        # (the chunk path also serves the uncached-SUFFIX prefill after a
        # prefix hit, so it exists even with chunking nominally off)
        self.chunk_width = min(self.max_seq_len,
                               self.prefill_chunk if self.prefill_chunk > 0
                               else self.block_tokens)
        self.spec_tokens = max(0, int(mmlconfig.get("generate.spec_tokens")))
        self.spec_width = self.spec_tokens + 1
        self._programs: Dict[Tuple[str, int], Callable] = {}
        # the arena is HBM this model now pins: charge it to the registry
        # entry so the device-cache LRU sees params + arena as one tenant.
        # PER-SHARD bytes: a head-sharded arena costs each chip 1/|tensor|
        # of the logical total, and that is what the budget must see.
        entry.kv_arena_bytes = self.kv.arena_shard_bytes()

    # -- compile seam ------------------------------------------------------
    def program_for(self, kind: str, bucket: int) -> Callable:
        key = (kind, int(bucket))
        prog = self._programs.get(key)
        if prog is None:
            prog = self._compile(kind, int(bucket))
            self._programs[key] = prog
        return prog

    def _compile(self, kind: str, bucket: int) -> Callable:
        """Build (or cache-load) the executable for one (kind, bucket).
        Every generative compilation funnels through here exactly once
        per key — the compile-discipline tests wrap this method. Every
        program takes ``params``, then the arena set (donated), then its
        kind's operands, and returns the arena set followed by its
        payload, if it has one."""
        import jax
        from mmlspark_tpu import compile_cache
        if kind == "prefill":
            body, operands = self._prefill_spec(bucket)
        elif kind in ("decode", "chunk", "verify"):
            body, operands = self._step_spec(kind, bucket)
        elif kind == "cow":
            body, operands = self._cow_spec()
        else:
            raise ValueError(f"unknown program kind {kind!r}")
        arenas = self.kv.abstract_arenas()
        n_arenas = len(arenas)

        def program(params, *args):
            return body(params, args[:n_arenas], *args[n_arenas:])

        jitted = jax.jit(  # lint: allow-compile
            program, donate_argnums=tuple(range(1, n_arenas + 1)))
        shape_key = (f"{kind}:{bucket}|arena={self.kv.num_blocks}x"
                     f"{self.block_tokens}x{self.heads}x{self.head_dim}"
                     f"|layers={self.depth}|W={self.table_width}"
                     f"|dtype={self.kv.dtype.name}")
        if kind == "verify":
            shape_key += f"|C={self.spec_width}"
        if self.mesh is not None:
            # mesh identity: the same bucket lowered for a different
            # topology (or head-sharded vs replicated arena) is a
            # DIFFERENT executable — its input shardings are baked in
            axes = ",".join(f"{a}{n}" for a, n in self.mesh.shape.items()
                            if n > 1) or "1"
            spec = getattr(self.kv.arena_sharding, "spec", ())
            shape_key += f"|mesh={axes}|kvspec={tuple(spec)!r}"
        result = compile_cache.load_or_compile_program(
            self.entry.name, self.entry.version, kind, shape_key,
            jitted, self.params, *arenas, *operands)
        if result.hit:
            self.entry.cache_hits += 1
        else:
            self.entry.compile_count += 1
        return result.program

    # -- prefill -----------------------------------------------------------
    def _prefill_spec(self, bucket: int):
        """Prefill for one prompt-length bucket ``Lb``: the module over
        the whole padded prompt, its attention a :class:`CacheView` that
        attends over the rows themselves (``full_attention``: the flash
        kernel on the chip) and writes every layer's K/V into the
        sequence's leading blocks; returns the last live position's
        logits row."""
        import jax.numpy as jnp
        module = self.module
        nb = bucket // self.block_tokens

        def prefill(params, arenas, tokens, last_pos, block_ids):
            view = CacheView(arenas, block_ids[None])
            logits = module.clone(attention_fn=view).apply(params, tokens)
            return (*view.arenas(), jnp.take(logits[0], last_pos, axis=0))

        return prefill, (_int32(1, bucket), _int32(), _int32(nb))

    # -- decode, chunk, verify ---------------------------------------------
    def _step_spec(self, kind: str, bucket: int):
        """The step program: ``C`` new tokens of each of ``B`` sequences
        at their ``positions``, through the module with a
        :class:`CacheView` over ``block_tables`` as its attention, which
        writes each layer's new K/V rows first and then reads the paged
        history, so a row attends itself and the rows before it in its
        window. Row ``j`` of a sequence's logits is the model's
        next-token distribution after its fed token ``j``. Rows from
        ``n_valid`` on write to the reserved scratch block and their
        logits are ignored host-side — the program never branches on
        occupancy.

        The three kinds are this body at three leading shapes, each with
        the operands and the payload its callers know:

        - ``decode``, ``(B, 1)``: one token per lane of a batch bucket;
          a lane without a live sequence has ``seq_lens == 0``.
        - ``chunk``, ``(1, C)``: ``C`` consecutive prompt positions of
          ONE sequence — chunked prefill, and the uncached suffix after a
          prefix hit (``positions`` start at the first uncached token;
          earlier shared blocks are only READ). Returns row
          ``n_valid - 1``.
        - ``verify``, ``(B, spec_width)``: a lane feeds ``n_valid`` in
          ``[1, C]`` tokens, its last sampled one and the draft's
          proposals (1 = plain decode riding the same program); the host
          accepts proposals left to right while they match the target's
          own sampler.
        """
        import jax.numpy as jnp
        module, W = self.module, self.table_width

        def step(params, arenas, tokens, positions, block_tables, n_valid):
            valid = jnp.arange(tokens.shape[1])[None, :] < n_valid[:, None]
            view = CacheView(arenas, block_tables, positions, valid)
            logits = module.clone(attention_fn=view).apply(
                params, tokens, positions=positions)
            return (*view.arenas(), logits)

        if kind == "verify":
            C = self.spec_width
            return step, (_int32(bucket, C), _int32(bucket, C),
                          _int32(bucket, W), _int32(bucket))
        if kind == "decode":
            def decode(params, arenas, tokens, positions, block_tables,
                       seq_lens):
                *arenas, logits = step(
                    params, arenas, tokens[:, None], positions[:, None],
                    block_tables, (seq_lens > 0).astype(jnp.int32))
                return (*arenas, logits[:, 0])

            return decode, (_int32(bucket), _int32(bucket),
                            _int32(bucket, W), _int32(bucket))

        def chunk(params, arenas, tokens, positions, table_row, n_valid):
            *arenas, logits = step(params, arenas, tokens[None],
                                   positions[None], table_row[None],
                                   n_valid[None])
            return (*arenas, jnp.take(logits[0], jnp.maximum(n_valid - 1, 0),
                                      axis=0))

        return chunk, (_int32(bucket), _int32(bucket), _int32(W), _int32())

    # -- copy-on-write block copy -----------------------------------------
    def _cow_spec(self):
        """Device block copy ``src -> dst`` across every layer of every
        array of the arena set — the copy-on-write a full-prefix-hit
        joiner owes before it may write its final prompt block."""
        def cow(params, arenas, src, dst):
            return tuple(a.at[:, dst].set(a[:, src]) for a in arenas)

        return cow, (_int32(), _int32())

    def release(self) -> None:
        """Drop programs + arena accounting (lane shutdown)."""
        self._programs.clear()
        self.entry.kv_arena_bytes = 0


# ---------------------------------------------------------------------------
# the lane executor


class GenerateLane:
    """Single-threaded decode executor for one generative model.

    Owns the arena and the active set; caller threads only touch the
    admission queue and the (thread-safe) block ledger. ``start=False``
    leaves the thread unstarted so tests drive :meth:`step` directly
    under an injected clock.
    """

    def __init__(self, server, model: str, *, clock=None,
                 start: bool = True):
        self.server = server
        self.model = model
        self.clock = clock if clock is not None else server.clock
        entry = server.registry.get(model)
        self.gen = GenerativeEntry(entry)
        server.registry.touch(entry)
        # speculative decoding: the draft model gets its OWN entry (its
        # own arena + programs) sized to the same sequence envelope, so
        # target and draft block ledgers never interact
        self.draft: Optional[GenerativeEntry] = None
        draft_name = str(mmlconfig.get("generate.draft_model")).strip()
        if draft_name and self.gen.spec_tokens > 0:
            dentry = server.registry.get(draft_name)
            self.draft = GenerativeEntry(
                dentry, max_seq_len=self.gen.max_seq_len,
                max_sequences=self.gen.max_sequences)
            if self.draft.vocab != self.gen.vocab:
                raise ValueError(
                    f"draft model {draft_name!r} vocab {self.draft.vocab} "
                    f"!= target {model!r} vocab {self.gen.vocab}")
            server.registry.touch(dentry)
        self.batcher = ContinuousBatcher(self.gen.max_sequences,
                                         clock=self.clock)
        self._prefilling: List[_Seq] = []   # joined the arena, mid-chunk
        # deliberately unbounded: backpressure is the KV arena — submit()
        # reserved every enqueued sequence's full block budget, so the
        # queue can never hold more than the arena admits
        self._queue: "queue.Queue" = queue.Queue(maxsize=0)
        self._lock = threading.Lock()
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        self._seq_ids = 0
        self._admitted = server._twin("generate.admitted")
        self._shed = server._twin("generate.shed")
        self._expired = server._twin("generate.expired")
        self._completed = server._twin("generate.completed")
        self._failed = server._twin("generate.failed")
        self._prefix_hits = server._twin("generate.prefix_hits")
        self._prefix_misses = server._twin("generate.prefix_misses")
        self._cow_copies = server._twin("generate.cow_copies")
        self._spec_proposed = server._twin("generate.spec_proposed")
        self._spec_accepted = server._twin("generate.spec_accepted")
        self._draft_prefix_hits = server._twin("generate.draft_prefix_hits")
        self.steps = 0          # decode steps taken (chaos kill trigger)
        if events.recording_enabled():
            kv = self.gen.kv
            events.emit("decode", "arena", model=self.model,
                        blocks=kv.num_blocks,
                        block_tokens=kv.block_tokens,
                        kv_dtype=str(kv.dtype),
                        arena_bytes=kv.arena_bytes(),
                        unquantized_bytes=kv.unquantized_arena_bytes())
        if start:
            self.start()

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, name=f"mmlspark-tpu-generate-{self.model}",
            daemon=True)
        self._thread.start()

    def close(self, timeout_s: Optional[float] = None) -> None:
        """Stop the executor and fail everything unfinished with
        :class:`ServerClosed` — generation state dies with the replica,
        and the fleet router maps a closed replica to a failover that
        RESTARTS the sequence from its prompt on a survivor (seeded
        sampling replays the identical tokens). Idempotent."""
        from mmlspark_tpu.serve.server import ServerClosed
        with self._lock:
            if self._closed:
                return
            self._closed = True
        if timeout_s is None:
            timeout_s = float(mmlconfig.get("serving.drain_timeout_s"))
        if self._thread is not None:
            self._queue.put(_STOP)
            self._thread.join(timeout=max(timeout_s, 0.1))
            self._thread = None
        leftovers = [s for s in self._drain_queue() if s is not _STOP]
        leftovers.extend(self.batcher.drain())
        leftovers.extend(self._prefilling)
        self._prefilling.clear()
        for seq in leftovers:
            self._release_blocks(seq)
            if not seq.future.done():
                self._failed.inc()
                seq.future.set_exception(ServerClosed(
                    "server closed mid-generation; restart from prompt "
                    "elsewhere"))
        self.gen.release()
        if self.draft is not None:
            self.draft.release()

    @property
    def closed(self) -> bool:
        return self._closed

    # -- admission (caller threads) ---------------------------------------
    def submit(self, req: GenerateRequest) -> Future:
        from mmlspark_tpu.serve.server import (
            ServerClosed, ServerOverloaded, _mint_trace_id,
        )
        if self._closed:
            raise ServerClosed("generate lane closed")
        prompt = np.asarray(req.prompt, np.int32).ravel()
        if prompt.size < 1:
            raise ValueError("prompt must contain at least one token")
        if prompt.size >= self.gen.max_seq_len:
            raise ValueError(
                f"prompt of {prompt.size} tokens leaves no room under "
                f"generate.max_seq_len={self.gen.max_seq_len}")
        max_new = min(int(req.max_new_tokens),
                      self.gen.max_seq_len - int(prompt.size))
        if max_new < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {req.max_new_tokens}")
        req = GenerateRequest(
            model=req.model, prompt=prompt, max_new_tokens=max_new,
            temperature=req.temperature, top_k=req.top_k, seed=req.seed,
            eos_id=req.eos_id, deadline_ms=req.deadline_ms,
            trace_id=req.trace_id or _mint_trace_id())
        now = self.clock()
        deadline = now + req.deadline_ms / 1e3 if req.deadline_ms else None
        with self._lock:
            if self._closed:
                raise ServerClosed("generate lane closed")
            self._seq_ids += 1
            seq_id = f"{self.model}/s{self._seq_ids}"
        # the whole lifetime's blocks up front: the prefill bucket's span
        # now, the generated tail later — admission is the ONLY place a
        # sequence can fail for memory
        bucket = bucket_for(prompt.size, self.gen.prefill_buckets)
        span_tokens = max(bucket, prompt.size + max_new)
        hashes: List[str] = []
        if self.gen.prefix_cache:
            hashes = prefix_block_hashes(
                self.model, self.gen.kv.dtype.name, prompt,
                self.gen.block_tokens)
        fault_site("generate.enqueue", {"model": self.model,
                                        "prompt": int(prompt.size)})
        blocks = self.gen.kv.try_reserve(
            seq_id, span_tokens, prefix_hashes=hashes,
            prompt_tokens=int(prompt.size))
        if blocks is None:
            self._shed.inc()
            if events.recording_enabled():
                events.emit("generate", "shed", model=self.model,
                            prompt=int(prompt.size), tokens=span_tokens,
                            free_blocks=self.gen.kv.free_blocks,
                            trace_id=req.trace_id)
            raise ServerOverloaded(
                f"KV arena full ({self.gen.kv.free_blocks} free blocks < "
                f"{blocks_needed(span_tokens, self.gen.block_tokens)} "
                "needed); retry with backoff",
                retry_after=float(mmlconfig.get("serving.retry_after_s")))
        seq = _Seq(seq_id, req, Future(), now, deadline)
        seq.future.trace_id = req.trace_id
        seq.hashes = hashes
        info = self.gen.kv.reserve_info(seq_id)
        seq.prefix_hits = int(info["hits"])
        if info["hits"]:
            self._prefix_hits.inc(info["hits"])
        if info["misses"]:
            self._prefix_misses.inc(info["misses"])
        if self.draft is not None:
            # best-effort: a full draft arena only disables speculation
            # for this sequence, it never sheds the request. The draft
            # reservation goes through the SAME prefix-matching admission
            # as the target's, keyed by the draft's own (name, dtype) —
            # a repeated prompt skips the draft prefill compute too.
            dhashes: List[str] = []
            if self.draft.prefix_cache:
                dhashes = prefix_block_hashes(
                    self.draft.entry.name, self.draft.kv.dtype.name,
                    prompt, self.draft.block_tokens)
            seq.spec_ok = self.draft.kv.try_reserve(
                seq_id, span_tokens, prefix_hashes=dhashes,
                prompt_tokens=int(prompt.size)) is not None
            if seq.spec_ok:
                seq.draft_hashes = dhashes
                dhits = int(self.draft.kv.reserve_info(seq_id)["hits"])
                if dhits:
                    self._draft_prefix_hits.inc(dhits)
        if hashes and events.recording_enabled():
            events.emit("decode", "prefix", model=self.model,
                        hits=int(info["hits"]), misses=int(info["misses"]),
                        cached_tokens=int(info["cached_tokens"]),
                        cow=bool(info["pending_cow"]),
                        trace_id=req.trace_id)
        self._queue.put(seq)
        self._admitted.inc()
        return seq.future

    # -- executor ----------------------------------------------------------
    def _run(self) -> None:
        hb = _watchdog.register(f"generate.{self.model}")
        try:
            self._run_loop(hb)
        finally:
            hb.close()

    def _run_loop(self, hb) -> None:
        stopping = False
        while True:
            hb.beat()
            busy = self.batcher.ready() or bool(self._prefilling)
            try:
                item = self._queue.get(timeout=0.0 if busy else 0.05)
            except queue.Empty:
                item = None
            if item is _STOP:
                stopping = True
            elif item is not None:
                self.batcher.offer(item)
            for s in self._drain_queue():
                if s is _STOP:
                    stopping = True
                else:
                    self.batcher.offer(s)
            if stopping:
                return              # close() resolves whatever is left
            if self.batcher.ready() or self._prefilling:
                self.step()

    def _drain_queue(self) -> List:
        out: List = []
        while True:
            try:
                out.append(self._queue.get_nowait())
            except queue.Empty:
                return out

    # -- one continuous-batching step (public: tests drive it) ------------
    def step(self) -> None:
        """Advance mid-prefill sequences one chunk, admit joiners
        (prefill + first token), then run ONE decode step over the active
        set — chunked prefill interleaves with decode at exactly this
        boundary, so a long joiner costs the running batch one chunk of
        latency per step instead of its whole prompt. Sequences finishing
        this step leave and free their blocks before the next step's
        joiners are considered."""
        for s in self._drain_queue():
            if s is not _STOP:
                self.batcher.offer(s)
        for seq in list(self._prefilling):
            self._prefill_chunk_step(seq)
        taken = self.batcher.take()
        room = max(0, self.batcher.free_slots - len(self._prefilling))
        for seq in reversed(taken[room:]):
            self.batcher.requeue(seq)   # slots held by mid-chunk prefills
        for seq in taken[:room]:
            self._admit_one(seq)
        if self.batcher.active:
            if self.draft is not None:
                self._decode_step_spec()
            else:
                self._decode_step()
        if metrics.metrics_enabled():
            metrics.gauge("generate.kv_occupancy").set(
                self.gen.kv.occupancy())

    def _admit_one(self, seq: _Seq) -> None:
        now = self.clock()
        if seq.expired(now):
            from mmlspark_tpu.serve.server import RequestExpired
            self._release_blocks(seq)
            self._expired.inc()
            if events.recording_enabled():
                events.emit("generate", "expired", model=self.model,
                            trace_id=seq.trace_id,
                            waited_ms=round((now - seq.enqueued) * 1e3, 3))
            seq.future.set_exception(RequestExpired(
                "deadline passed before prefill"))
            return
        gen = self.gen
        Lp = int(seq.prompt.size)
        info = gen.kv.reserve_info(seq.seq_id)
        cached = min(int(info["cached_tokens"]), Lp)
        cow = gen.kv.take_pending_cow(seq.seq_id)
        if cow is not None:
            # full-prefix hit: copy the final shared block into this
            # sequence's owned block BEFORE its first (re)write
            try:
                self._cow_copy(gen, cow)
            except Exception as e:
                logger.error("cow copy failed for %s: %s", seq.seq_id, e)
                self._fail_seq(seq, e)
                return
            gen.kv.cow_done(seq.seq_id)
            self._cow_copies.inc()
            if events.recording_enabled():
                events.emit("decode", "cow", model=self.model,
                            src=cow[0], dst=cow[1], trace_id=seq.trace_id)
        # the legacy whole-prompt prefill scatters EVERY leading block,
        # so any reservation that shares cached blocks must take the
        # chunk path (it only writes from the first uncached position)
        use_chunk = cached > 0 or (gen.prefill_chunk > 0
                                   and Lp > gen.chunk_width)
        if not use_chunk:
            try:
                self._prefill(seq)
            except Exception as e:
                logger.error("prefill failed for %s: %s", seq.seq_id, e)
                self._fail_seq(seq, e)
                return
            self.batcher.join(seq)
            if seq.finish:          # eos / budget hit on the first token
                self._finish(seq)
            return
        # chunk path: compute only the uncached suffix, one chunk per
        # lane step; a FULL hit recomputes just the last prompt position
        # (into the CoW'd block) to sample its first token
        seq.prefill_pos = cached if cached < Lp else max(Lp - 1, 0)
        self._prefilling.append(seq)
        self._prefill_chunk_step(seq)

    def _prefill(self, seq: _Seq) -> None:
        gen = self.gen
        Lp = int(seq.prompt.size)
        bucket = bucket_for(Lp, gen.prefill_buckets)
        nb = bucket // gen.block_tokens
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :Lp] = seq.prompt
        block_ids = np.asarray(gen.kv.blocks_for(seq.seq_id)[:nb], np.int32)
        program = gen.program_for("prefill", bucket)
        fault_site("generate.prefill", {"model": self.model,
                                        "bucket": bucket})
        t0 = self.clock()
        with spans.span("decode", "prefill", model=self.model,
                        bucket=bucket):
            row = self._call(gen, program, tokens, np.int32(Lp - 1),
                             block_ids)
            logits = np.asarray(
                syncs.device_get(row, "generate.prefill"), np.float32)
        if seq.hashes:
            gen.kv.register_prefix(seq.seq_id, seq.hashes)
        self._draft_prefill(seq)
        now = self.clock()
        self._append_token(seq, logits, position=Lp)
        seq.ttft_s = now - seq.enqueued
        seq.last_t = now
        if metrics.metrics_enabled():
            metrics.histogram("generate.ttft_ms").observe(
                seq.ttft_s * 1e3, exemplar=seq.trace_id)
        if events.recording_enabled():
            events.emit("decode", "prefill", model=self.model,
                        bucket=bucket, prompt=Lp,
                        prefill_ms=round((now - t0) * 1e3, 3),
                        trace_id=seq.trace_id)

    # -- shared program-call plumbing --------------------------------------
    @staticmethod
    def _call(entry: GenerativeEntry, program, *operands):
        """Run one arena program against ``entry``'s KV manager: pass the
        current (donated) arena set, store the returned set back, and
        hand the caller whatever payload follows it (logits/row), if
        any. Works for the target and the draft entry alike."""
        kv = entry.kv
        arenas = kv.arenas()
        out = program(entry.params, *arenas, *operands)
        kv.swap(*out[:len(arenas)])
        tail = out[len(arenas):]
        return tail[0] if tail else None

    def _cow_copy(self, entry: GenerativeEntry,
                  pair: Tuple[int, int]) -> None:
        program = entry.program_for("cow", 0)
        self._call(entry, program, np.int32(pair[0]), np.int32(pair[1]))

    def _release_blocks(self, seq: _Seq) -> None:
        """Free every block lease the sequence holds — target arena and,
        when speculation reserved one, the draft arena (both idempotent)."""
        self.gen.kv.free(seq.seq_id)
        if self.draft is not None:
            self.draft.kv.free(seq.seq_id)

    def _fail_seq(self, seq: _Seq, exc: Exception) -> None:
        self._release_blocks(seq)
        self._failed.inc()
        if not seq.future.done():
            seq.future.set_exception(exc)

    # -- chunked / suffix prefill ------------------------------------------
    def _prefill_chunk_step(self, seq: _Seq) -> None:
        """One chunk of ``seq``'s remaining prompt through the chunk
        program. On the final chunk the sequence samples its first token
        (TTFT), registers its prefix blocks, and joins the active set."""
        gen = self.gen
        Lp = int(seq.prompt.size)
        C = gen.chunk_width
        start = seq.prefill_pos
        n_valid = min(C, Lp - start)
        final = start + n_valid >= Lp
        tokens = np.zeros((C,), np.int32)
        tokens[:n_valid] = seq.prompt[start:start + n_valid]
        positions = (start + np.arange(C)).astype(np.int32)
        table_row = gen.kv.block_table(seq.seq_id, gen.table_width)
        program = gen.program_for("chunk", C)
        fault_site("generate.prefill", {"model": self.model, "bucket": C,
                                        "start": start})
        t0 = self.clock()
        try:
            with spans.span("decode", "prefill_chunk", model=self.model,
                            chunk=C, start=start):
                row = self._call(gen, program, tokens, positions,
                                 table_row, np.int32(n_valid))
                if final:
                    logits = np.asarray(
                        syncs.device_get(row, "generate.prefill"),
                        np.float32)
        except Exception as e:
            logger.error("chunk prefill failed for %s: %s", seq.seq_id, e)
            if seq in self._prefilling:
                self._prefilling.remove(seq)
            self._fail_seq(seq, e)
            return
        seq.prefill_pos = start + n_valid
        if not final:
            return
        self._prefilling.remove(seq)
        if seq.hashes:
            gen.kv.register_prefix(seq.seq_id, seq.hashes)
        self._draft_prefill(seq)
        now = self.clock()
        self._append_token(seq, logits, position=Lp)
        seq.ttft_s = now - seq.enqueued
        seq.last_t = now
        if metrics.metrics_enabled():
            metrics.histogram("generate.ttft_ms").observe(
                seq.ttft_s * 1e3, exemplar=seq.trace_id)
        if events.recording_enabled():
            events.emit("decode", "prefill", model=self.model,
                        bucket=C, prompt=Lp, chunked=True,
                        cached_tokens=seq.prefix_hits * gen.block_tokens,
                        prefill_ms=round((now - t0) * 1e3, 3),
                        trace_id=seq.trace_id)
        self.batcher.join(seq)
        if seq.finish:              # eos / budget hit on the first token
            self._finish(seq)

    # -- speculative decoding ----------------------------------------------
    def _draft_prefill(self, seq: _Seq) -> None:
        """Materialize the draft model's KV for the prompt. Failure only
        degrades the sequence to non-speculative decode.

        Mirrors the target's prefix-reuse admission: cached leading
        blocks (shared via the draft ledger's prefix chain) are NOT
        recomputed — only the uncached suffix runs, through the draft's
        chunk program, and a pending copy-on-write resolves before the
        first write, exactly like :meth:`_admit_one` does for the
        target. The legacy whole-prompt prefill scatters EVERY leading
        block, so any reservation with cached blocks must take the
        suffix path."""
        if self.draft is None or not seq.spec_ok:
            return
        d = self.draft
        try:
            Lp = int(seq.prompt.size)
            info = d.kv.reserve_info(seq.seq_id)
            cached = min(int(info["cached_tokens"]), Lp)
            cow = d.kv.take_pending_cow(seq.seq_id)
            if cow is not None:
                self._cow_copy(d, cow)
                d.kv.cow_done(seq.seq_id)
            if cached > 0:
                # suffix-only: recompute from the first uncached
                # position (a FULL hit redoes just the last one)
                C = d.chunk_width
                start = min(cached, Lp - 1)
                while start < Lp:
                    n_valid = min(C, Lp - start)
                    tokens = np.zeros((C,), np.int32)
                    tokens[:n_valid] = seq.prompt[start:start + n_valid]
                    positions = (start + np.arange(C)).astype(np.int32)
                    table_row = d.kv.block_table(seq.seq_id, d.table_width)
                    self._call(d, d.program_for("chunk", C), tokens,
                               positions, table_row, np.int32(n_valid))
                    start += n_valid
            else:
                bucket = bucket_for(Lp, d.prefill_buckets)
                nb = bucket // d.block_tokens
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, :Lp] = seq.prompt
                block_ids = np.asarray(d.kv.blocks_for(seq.seq_id)[:nb],
                                       np.int32)
                program = d.program_for("prefill", bucket)
                self._call(d, program, tokens, np.int32(Lp - 1), block_ids)
            if seq.draft_hashes:
                d.kv.register_prefix(seq.seq_id, seq.draft_hashes)
        except Exception as e:
            logger.warning("draft prefill failed for %s (speculation off "
                           "for this sequence): %s", seq.seq_id, e)
            d.kv.free(seq.seq_id)
            seq.spec_ok = False

    def _draft_propose(self, active: List[_Seq], fed: np.ndarray,
                       drafts: np.ndarray) -> None:
        """Run the draft model ``max(fed) - 1`` single-token decode steps
        over the spec-riding lanes, sampling each proposal with the SAME
        per-(seed, position) sampler the target uses — so a correct draft
        matches the target's token exactly, in greedy AND seeded-sampling
        modes. Lanes whose window is exhausted mask out (reserved-block
        writes), like empty decode lanes."""
        d = self.draft
        B = bucket_for(len(active), d.decode_buckets)
        W = d.table_width
        prev = np.array([seq.generated[-1] for seq in active], np.int64)
        for j in range(drafts.shape[1]):
            tokens = np.zeros((B,), np.int32)
            positions = np.zeros((B,), np.int32)
            tables = np.full((B, W), RESERVED_BLOCK, np.int32)
            seq_lens = np.zeros((B,), np.int32)
            lanes = [i for i, seq in enumerate(active)
                     if j < int(fed[i]) - 1]
            if not lanes:
                return
            for i in lanes:
                seq = active[i]
                tokens[i] = prev[i]
                positions[i] = seq.seq_len - 1 + j
                tables[i] = d.kv.block_table(seq.seq_id, W)
                seq_lens[i] = seq.seq_len + j
            program = d.program_for("decode", B)
            logits = self._call(d, program, tokens, positions, tables,
                                seq_lens)
            rows = np.asarray(
                syncs.device_get(logits, "generate.draft"), np.float32)
            for i in lanes:
                seq = active[i]
                tok = sample_token(rows[i], temperature=seq.temperature,
                                   top_k=seq.top_k, seed=seq.seed,
                                   position=seq.seq_len + j)
                drafts[i, j] = tok
                prev[i] = tok

    def _decode_step_spec(self) -> None:
        """One speculative step: the draft proposes up to ``spec_tokens``
        tokens per lane, the target checks the whole window in ONE verify
        call, and each lane accepts proposals left to right while they
        match what the target's own sampler would have emitted — so the
        output stream is token-identical to plain decode, at up to
        ``spec_width`` tokens per target step. Lanes that cannot
        speculate (draft arena full, window exhausted) ride the same
        program with a one-token window."""
        gen = self.gen
        active = self.batcher.active
        B = bucket_for(len(active), gen.decode_buckets)
        C = gen.spec_width
        W = gen.table_width
        fed = np.ones((len(active),), np.int64)
        for i, seq in enumerate(active):
            remaining = seq.max_new - len(seq.generated)
            if seq.spec_ok and remaining > 1:
                fed[i] = min(C, remaining)
        gamma = int(fed.max()) - 1
        drafts = np.zeros((len(active), max(gamma, 0)), np.int64)
        if gamma > 0:
            self._draft_propose(active, fed, drafts)
        tokens = np.zeros((B, C), np.int32)
        positions = np.zeros((B, C), np.int32)
        tables = np.full((B, W), RESERVED_BLOCK, np.int32)
        n_valid = np.zeros((B,), np.int32)
        for i, seq in enumerate(active):
            f = int(fed[i])
            tokens[i, 0] = seq.generated[-1]
            tokens[i, 1:f] = drafts[i, :f - 1]
            positions[i] = seq.seq_len - 1 + np.arange(C)
            tables[i] = gen.kv.block_table(seq.seq_id, W)
            n_valid[i] = f
        program = gen.program_for("verify", B)
        fault_site("generate.step", {"model": self.model, "batch": B,
                                     "active": len(active)})
        t0 = self.clock()
        with spans.span("decode", "step", model=self.model, batch=B,
                        active=len(active), spec=True):
            logits = self._call(gen, program, tokens, positions, tables,
                                n_valid)
            rows = np.asarray(
                syncs.device_get(logits, "generate.step"), np.float32)
        now = self.clock()
        self.steps += 1
        hot = metrics.metrics_enabled()
        emitted = 0
        for i, seq in enumerate(active):
            f = int(fed[i])
            appended = 0
            matched = 0
            for j in range(f):
                self._append_token(seq, rows[i, j], position=seq.seq_len)
                appended += 1
                if seq.finish:
                    break
                if j < f - 1:
                    if seq.generated[-1] != int(drafts[i, j]):
                        break       # divergence: the window past j is junk
                    matched += 1
            if f > 1:
                seq.spec_proposed += f - 1
                seq.spec_accepted += matched
                self._spec_proposed.inc(f - 1)
                self._spec_accepted.inc(matched)
            emitted += appended
            gap = (now - seq.last_t) / appended
            seq.last_t = now
            seq.itl_s.extend([gap] * appended)
            if hot:
                metrics.histogram("generate.itl_ms").observe(
                    gap * 1e3, exemplar=seq.trace_id)
            if not seq.finish and seq.expired(now):
                seq.finish = "deadline"
            if seq.finish:
                self._finish(seq)
        if events.recording_enabled():
            events.emit("decode", "step", model=self.model, batch=B,
                        active=len(active), tokens=emitted, spec=True,
                        step_ms=round((now - t0) * 1e3, 3))

    def _decode_step(self) -> None:
        gen = self.gen
        active = self.batcher.active
        bucket = bucket_for(len(active), gen.decode_buckets)
        W = gen.table_width
        tokens = np.zeros((bucket,), np.int32)
        positions = np.zeros((bucket,), np.int32)
        tables = np.full((bucket, W), RESERVED_BLOCK, np.int32)
        seq_lens = np.zeros((bucket,), np.int32)
        for i, seq in enumerate(active):
            tokens[i] = seq.generated[-1]
            positions[i] = seq.seq_len - 1      # the fed token's position
            tables[i] = gen.kv.block_table(seq.seq_id, W)
            seq_lens[i] = seq.seq_len
        program = gen.program_for("decode", bucket)
        fault_site("generate.step", {"model": self.model, "batch": bucket,
                                     "active": len(active)})
        t0 = self.clock()
        with spans.span("decode", "step", model=self.model, batch=bucket,
                        active=len(active)):
            logits = self._call(gen, program, tokens, positions, tables,
                                seq_lens)
            rows = np.asarray(
                syncs.device_get(logits, "generate.step"), np.float32)
        now = self.clock()
        self.steps += 1
        hot = metrics.metrics_enabled()
        for i, seq in enumerate(active):
            self._append_token(seq, rows[i], position=seq.seq_len)
            gap = now - seq.last_t
            seq.last_t = now
            seq.itl_s.append(gap)
            if hot:
                metrics.histogram("generate.itl_ms").observe(
                    gap * 1e3, exemplar=seq.trace_id)
            if not seq.finish and seq.expired(now):
                seq.finish = "deadline"     # partial result, not an error
            if seq.finish:
                self._finish(seq)
        if events.recording_enabled():
            events.emit("decode", "step", model=self.model, batch=bucket,
                        active=len(active),
                        step_ms=round((now - t0) * 1e3, 3))

    def _append_token(self, seq: _Seq, logits: np.ndarray,
                      position: int) -> None:
        tok = sample_token(logits, temperature=seq.temperature,
                           top_k=seq.top_k, seed=seq.seed,
                           position=position)
        seq.generated.append(tok)
        if seq.eos_id is not None and tok == seq.eos_id:
            seq.finish = "stop"
        elif len(seq.generated) >= seq.max_new:
            seq.finish = seq.finish or "length"

    def _finish(self, seq: _Seq) -> None:
        self.batcher.leave(seq)
        freed = self.gen.kv.free(seq.seq_id)
        if self.draft is not None:
            self.draft.kv.free(seq.seq_id)
        self._completed.inc()
        now = self.clock()
        if events.recording_enabled():
            itl = seq.itl_s
            events.emit("generate", "request", model=self.model,
                        prompt=int(seq.prompt.size),
                        tokens=len(seq.generated), finish=seq.finish,
                        ttft_ms=round((seq.ttft_s or 0.0) * 1e3, 3),
                        itl_mean_ms=round(sum(itl) / len(itl) * 1e3, 3)
                        if itl else 0.0,
                        itl_max_ms=round(max(itl) * 1e3, 3) if itl
                        else 0.0,
                        total_ms=round((now - seq.enqueued) * 1e3, 3),
                        kv_occupancy=round(self.gen.kv.occupancy(), 4),
                        prefix_hits=seq.prefix_hits,
                        spec_proposed=seq.spec_proposed,
                        spec_accepted=seq.spec_accepted,
                        trace_id=seq.trace_id)
            events.emit("decode", "evict", model=self.model,
                        blocks=freed, trace_id=seq.trace_id)
        seq.future.set_result(seq.result())

    # -- introspection -----------------------------------------------------
    def stats(self) -> Dict[str, float]:
        s = {"admitted": self._admitted.value,
             "shed": self._shed.value,
             "expired": self._expired.value,
             "completed": self._completed.value,
             "failed": self._failed.value,
             "waiting": len(self.batcher),
             "active": len(self.batcher.active),
             "prefilling": len(self._prefilling),
             "prefix_hits": self._prefix_hits.value,
             "prefix_misses": self._prefix_misses.value,
             "cow_copies": self._cow_copies.value,
             "spec_proposed": self._spec_proposed.value,
             "spec_accepted": self._spec_accepted.value,
             "steps": self.steps}
        s.update({f"kv.{k}": v for k, v in self.gen.kv.stats().items()})
        if self.draft is not None:
            s["draft.kv.used_blocks"] = self.draft.kv.used_blocks
            s["draft.kv.free_blocks"] = self.draft.kv.free_blocks
            s["draft_prefix_hits"] = self._draft_prefix_hits.value
        return s
