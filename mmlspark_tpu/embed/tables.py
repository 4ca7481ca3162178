"""Row-sharded embedding tables: fused sharded lookup + sparse update.

The scale problem this module exists for: recommender tables (users,
items, ads) are the one model component that grows with the BUSINESS,
not the architecture — 10^8 rows x 64 dims does not fit one chip, and
never will. So tables shard along the ``tensor`` mesh axis by ROW
(``parallel/sharding.py::embedding_table_sharding``: chip t holds rows
``[t*R/T, (t+1)*R/T)``), and the lookup/update paths are written so no
device ever materializes a full table, a full gather, or a dense
gradient for rows it does not own.

The fused lookup is ONE ``shard_map`` program per (batch, slots) shape:

1. **bucketize** — every device holds the full id block for its batch
   shard (ids are replicated over ``tensor``); it sorts the flat ids by
   owning shard (``owner = id // rows_per_shard``) and packs each
   shard's requests into a fixed-capacity bucket row;
2. **all-to-all** the request buckets over ``tensor`` — device t now
   holds every shard's requests for the rows *t* owns;
3. **local gather** — one ``table_shard[requests]`` per device, rows
   it physically holds, no cross-device indexing;
4. **all-to-all** the gathered rows back, un-permute into the original
   id order;
5. **segment-sum** the weighted multi-hot bags on device — the output
   is (batch, dim), sharded over the data axes like any activation.

Every step is static-shaped (bucket capacity = the id block size), so
one XLA program serves every batch of that shape — no retrace, no
host-side indirection, and the arithmetic per id is EXACTLY the
unsharded reference's (row fetch then the same segment-sum), which is
what makes the sharded path bit-identical to
:func:`bag_lookup_reference` on the same inputs.

The backward pass never builds a dense dLoss/dTable on one device
either, and it never MOVES one: :func:`sparse_table_grads` all-gathers
the (ids, weighted cotangents) over the data axes — O(batch) bytes —
and scatter-adds each bag cotangent into the owning shard's rows
(``.at[rows].add`` lowers to ``lax.scatter-add``). The gradient is
born with the table's own sharding and replicated over data without a
dense O(table) psum, so the optimizer update stays model-parallel end
to end.
:func:`make_bag_lookup` packages both directions as a ``custom_vjp``
so ``DistributedTrainer``'s plain ``jax.grad`` — donation, metrics
ring and all — trains through the fused path unchanged.

Lint Rule 17 makes this file the ONLY home for embedding
gather/scatter and id-bucketing arithmetic (`# lint: allow-embed`
escapes elsewhere must justify themselves in review).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu.parallel.sharding import (embedding_lookup_specs,
                                            embedding_table_sharding,
                                            tensor_axis_size)
from mmlspark_tpu.utils import config as mmlconfig

# id 0 is the pad slot in every table: lookups still FETCH row 0 (static
# shapes — masking happens via the weight, not the gather), so row 0 is
# reserved and real ids start at 1.
PAD_ID = 0


class EmbeddingTable(NamedTuple):
    """One logical table: ``rows`` ids (including the pad row 0) of
    ``dim`` features. ``rows`` is padded up to the tensor-axis multiple
    at placement time; the pad rows are dead weight that keeps every
    shard the same static shape."""
    name: str
    rows: int
    dim: int

    def padded_rows(self, mesh) -> int:
        t = tensor_axis_size(mesh)
        return -(-self.rows // t) * t

    def logical_bytes(self, dtype=np.float32) -> int:
        return int(self.rows) * int(self.dim) * np.dtype(dtype).itemsize


def _flat_ids(ids: jnp.ndarray) -> jnp.ndarray:
    return ids.reshape(-1).astype(jnp.int32)


def bag_lookup_reference(table: jnp.ndarray, ids: jnp.ndarray,
                         weights: jnp.ndarray) -> jnp.ndarray:
    """Unsharded reference bag lookup: gather + weighted segment-sum.

    The numerics ground truth the fused sharded path must match
    bit-for-bit — same rows fetched, same segment-sum order.
    """
    b, slots = ids.shape
    emb = jnp.take(table, _flat_ids(ids), axis=0)        # (b*slots, dim)
    vals = emb * weights.reshape(-1)[:, None]
    seg = jnp.repeat(jnp.arange(b, dtype=jnp.int32), slots)
    return jax.ops.segment_sum(vals, seg, num_segments=b)


def _bucketize(flat: jnp.ndarray, rows_per_shard: int, t: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort a flat id block by owning shard and pack per-shard request
    buckets. Returns ``(buckets, order, sorted_owner, pos)`` where
    ``buckets[t, c]`` is the c-th local row requested from shard t
    (capacity = the whole block — worst case every id on one shard)."""
    n = flat.shape[0]
    owner = flat // rows_per_shard
    local = flat - owner * rows_per_shard
    order = jnp.argsort(owner, stable=True)
    sorted_owner = owner[order]
    sorted_local = local[order]
    start = jnp.searchsorted(sorted_owner, jnp.arange(t, dtype=flat.dtype))
    pos = jnp.arange(n, dtype=jnp.int32) - start[sorted_owner].astype(jnp.int32)
    buckets = jnp.zeros((t, n), flat.dtype).at[sorted_owner, pos].set(
        sorted_local)
    return buckets, order, sorted_owner, pos


def make_fused_lookup(mesh):
    """The fused sharded bag lookup ``(table, ids, weights) -> bags``
    for this mesh — one shard_map program per input shape. Falls back
    to the reference path when the mesh has no model axis, or when
    ``embed.fused_lookup`` is off (GSPMD partitions the reference
    gather against the sharded table — the numerics-triage escape)."""
    t = tensor_axis_size(mesh)
    if mesh is None or t <= 1 or not mmlconfig.get("embed.fused_lookup"):
        return bag_lookup_reference
    table_spec, ids_spec, out_spec = embedding_lookup_specs(mesh)

    def body(tab, idl, wl):
        rows_per_shard = tab.shape[0]
        b, slots = idl.shape
        flat = _flat_ids(idl)
        buckets, order, sorted_owner, pos = _bucketize(flat, rows_per_shard, t)
        # requests OUT: row j of the result is what device j asked of us
        req = jax.lax.all_to_all(buckets, "tensor", 0, 0, tiled=True)
        got = jnp.take(tab, req, axis=0)              # (t, n, dim) local rows
        # rows BACK: bucket j of the result is what device j answered
        back = jax.lax.all_to_all(got, "tensor", 0, 0, tiled=True)
        semb = back[sorted_owner, pos]                # sorted request order
        emb = jnp.zeros_like(semb).at[order].set(semb)  # original order
        vals = emb * wl.reshape(-1)[:, None]
        seg = jnp.repeat(jnp.arange(b, dtype=jnp.int32), slots)
        return jax.ops.segment_sum(vals, seg, num_segments=b)

    fused = jax.shard_map(body, mesh=mesh,
                          in_specs=(table_spec, ids_spec, ids_spec),
                          out_specs=out_spec, check_vma=False)

    def lookup(table, ids, weights):
        return fused(table, ids.astype(jnp.int32),
                     weights.astype(table.dtype))
    return lookup


def _reference_table_grad(rows: int, ids: jnp.ndarray, weights: jnp.ndarray,
                          grad_bags: jnp.ndarray) -> jnp.ndarray:
    """Unsharded sparse table gradient: scatter-add each bag cotangent
    into the rows its ids touched (dBag/dRow is the weight)."""
    dim = grad_bags.shape[-1]
    b, slots = ids.shape
    vals = (grad_bags[:, None, :] * weights[..., None]).reshape(-1, dim)
    return jnp.zeros((rows, dim), grad_bags.dtype).at[
        _flat_ids(ids)].add(vals)


def make_sparse_grad(mesh):
    """``(table_like, ids, weights, grad_bags) -> grad_table`` with the
    gradient born row-sharded AND the cross-device exchange kept
    SPARSE: the (ids, weighted cotangents) — O(batch) bytes — are
    all-gathered over the data axes, then each device scatter-adds the
    full batch's contributions for the rows it owns. The obvious
    alternative (scatter the local batch shard, psum the dense grad
    over data) moves O(table) bytes per axis per step — for a table
    that by design exceeds a chip, that psum IS the step time."""
    t = tensor_axis_size(mesh)
    if mesh is None or t <= 1 or not mmlconfig.get("embed.fused_lookup"):
        return lambda tab, ids, w, g: _reference_table_grad(
            tab.shape[0], ids, w, g)
    table_spec, ids_spec, _ = embedding_lookup_specs(mesh)
    from mmlspark_tpu.parallel.sharding import active_batch_axes
    data_axes = active_batch_axes(mesh)

    def body(tab, idl, wl, gl):
        rows_per_shard = tab.shape[0]
        dim = gl.shape[-1]
        if data_axes:
            # sparse exchange: every device sees every (id, cotangent)
            # pair; tiled gather along the batch dim keeps global batch
            # order, so the scatter below adds in the reference order
            idl = jax.lax.all_gather(idl, data_axes, axis=0, tiled=True)
            wl = jax.lax.all_gather(wl, data_axes, axis=0, tiled=True)
            gl = jax.lax.all_gather(gl, data_axes, axis=0, tiled=True)
        flat = _flat_ids(idl)
        owner = flat // rows_per_shard
        local = flat - owner * rows_per_shard
        mine = owner == jax.lax.axis_index("tensor")
        vals = (gl[:, None, :] * wl[..., None]).reshape(-1, dim)
        vals = jnp.where(mine[:, None], vals, 0.0)
        rows = jnp.where(mine, local, 0)
        # every data replica scatters the SAME full-batch contributions,
        # so the grad comes out replicated over data with no psum
        return jnp.zeros_like(tab).at[rows].add(vals)   # lax.scatter-add

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(table_spec, ids_spec, ids_spec, ids_spec),
        out_specs=table_spec, check_vma=False)

    def grad_fn(table_like, ids, weights, grad_bags):
        return sharded(table_like, ids.astype(jnp.int32),
                       weights.astype(grad_bags.dtype), grad_bags)
    return grad_fn


def sparse_table_grads(mesh, table: jnp.ndarray, ids: jnp.ndarray,
                       weights: jnp.ndarray,
                       grad_bags: jnp.ndarray) -> jnp.ndarray:
    """One-shot convenience over :func:`make_sparse_grad`."""
    return make_sparse_grad(mesh)(table, ids, weights, grad_bags)


def make_bag_lookup(mesh=None):
    """A DIFFERENTIABLE bag lookup for this mesh: forward is the fused
    all-to-all path (reference path when unsharded), backward is the
    sparse scatter-add gradient — so a flax module calling this trains
    through ``jax.grad``/``DistributedTrainer`` with the table gradient
    computed sparse and sharded, never as a dense dL/dTable matmul.

    ``weights`` are treated as constants (they are pad masks and
    frequency features, not trainables): their cotangent is zero, which
    is what lets the backward pass skip re-materializing the gathered
    rows entirely — the residuals are just ``(ids, weights)``.
    """
    lookup = make_fused_lookup(mesh)
    grad_fn = make_sparse_grad(mesh)

    @jax.custom_vjp
    def bag_lookup(table, ids, weights):
        return lookup(table, ids, weights)

    def fwd(table, ids, weights):
        # the table rides the residuals for its SHAPE only (the sparse
        # grad never reads its values — XLA DCEs the dependency); it is
        # the same buffer the surrounding step already keeps live
        return lookup(table, ids, weights), (table, ids, weights)

    def bwd(res, grad_bags):
        table, ids, weights = res
        grad_table = grad_fn(table, ids, weights, grad_bags)
        zero_ids = np.zeros(ids.shape, jax.dtypes.float0) \
            if jnp.issubdtype(ids.dtype, jnp.integer) \
            else jnp.zeros_like(ids)
        return grad_table, zero_ids, jnp.zeros_like(weights)

    bag_lookup.defvjp(fwd, bwd)
    return bag_lookup


class EmbeddingCollection:
    """A named set of row-sharded tables plus their lookup/update
    machinery, bound to one mesh (or none, for the single-device
    reference).

    Usage::

        coll = EmbeddingCollection([EmbeddingTable("user", 100_000, 64),
                                    EmbeddingTable("item", 200_000, 64)],
                                   mesh=mesh)
        tables = coll.place(coll.init(seed=0))       # sharded residency
        bags = coll.lookup(tables, {"user": (ids, w), "item": (ids2, w2)})
        grads = coll.grads(tables, batch, grad_bags)  # scatter-add, sharded
        tables = coll.sgd_update(tables, grads, lr=0.05)
    """

    def __init__(self, tables: Sequence[EmbeddingTable], mesh=None,
                 dtype=jnp.float32):
        names = [t.name for t in tables]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate table names: {names}")
        self.tables: Dict[str, EmbeddingTable] = {t.name: t for t in tables}
        self.mesh = mesh
        self.dtype = dtype
        self._lookup = make_fused_lookup(mesh)
        self._grad = make_sparse_grad(mesh)

    # -- residency -----------------------------------------------------------
    def init(self, seed: int = 0) -> Dict[str, np.ndarray]:
        """Host-side init (scaled-normal rows, pad row zero), PADDED to
        the mesh's shard multiple — the one set of values every mesh
        shape loads, the way test_mesh2d's host init keeps topologies
        comparable."""
        out: Dict[str, np.ndarray] = {}
        for name, spec in sorted(self.tables.items()):
            rng = np.random.default_rng((seed, hash(name) & 0xFFFF))
            arr = rng.normal(0.0, spec.dim ** -0.5,
                             size=(spec.padded_rows(self.mesh), spec.dim))
            arr = arr.astype(np.dtype(self.dtype))
            arr[PAD_ID] = 0.0
            arr[spec.rows:] = 0.0       # shard-padding rows
            out[name] = arr
        return out

    def place(self, host_tables: Dict[str, np.ndarray]) -> Dict[str, Any]:
        """Host arrays -> mesh placement in ONE hop per table: each chip
        receives only its row shard (``device_put`` against the
        NamedSharding), so a table bigger than one chip's HBM never
        materializes a full copy on any device."""
        if self.mesh is None:
            return {k: jnp.asarray(v) for k, v in host_tables.items()}
        sh = embedding_table_sharding(self.mesh)
        with self.mesh:
            return {k: jax.device_put(v, sh)
                    for k, v in host_tables.items()}

    # -- compute -------------------------------------------------------------
    def lookup(self, tables: Dict[str, Any],
               batch: Dict[str, Tuple[Any, Any]]) -> Dict[str, jnp.ndarray]:
        """Fused sharded bag lookup per table; ``batch`` maps table name
        to ``(ids, weights)`` of shape (b, slots)."""
        return {name: self._lookup(tables[name], ids, w)
                for name, (ids, w) in batch.items()}

    def grads(self, tables: Dict[str, Any],
              batch: Dict[str, Tuple[Any, Any]],
              grad_bags: Dict[str, jnp.ndarray]) -> Dict[str, Any]:
        return {name: self._grad(tables[name], ids, w, grad_bags[name])
                for name, (ids, w) in batch.items()}

    def sgd_update(self, tables: Dict[str, Any], grads: Dict[str, Any],
                   lr: float) -> Dict[str, Any]:
        """The sparse-update half of a train step: row-sharded
        ``table - lr * grad``, shapes and shardings preserved so the
        result re-donates into the next step."""
        return {name: tables[name] - lr * grads[name] for name in tables}

    # -- accounting ----------------------------------------------------------
    def logical_bytes(self) -> int:
        """Bytes of the full (unsharded) tables — the number that must
        EXCEED one chip's budget for the workload to be honest about
        crossing the chip (bench lane's ``crosses_chip``). Byte math for
        DEVICE arrays stays in observability/memory.py (Rule 11); this
        is spec arithmetic over the declared shapes."""
        itemsize = jnp.zeros((), self.dtype).dtype.itemsize
        return sum(t.padded_rows(self.mesh) * t.dim * itemsize
                   for t in self.tables.values())


class RowResidency:
    """Frequency-capped per-row hot pool over one host master table.

    PR 17's eviction is whole-table: the registry LRU drops a model's
    ENTIRE ``kind="table"`` ledger line when the warm set overflows
    ``runtime.device_cache_mb``. That is the right lever when the table
    fits one budget slot, and the wrong one when it doesn't — a table
    that is 10x the budget can still serve from residency because real
    id traffic is Zipfian: a small hot set covers most lookups. This
    pool is the per-row refinement: a bounded pool of hot rows over a
    host master, admitting rows on first touch and evicting the
    COLDEST rows first when full.

    "Frequency-capped": each resident row keeps an access counter
    capped at ``freq_cap``; eviction victims sort by
    ``(capped_frequency, last_touch)`` ascending — cold-and-stale rows
    go first. The cap bounds how long a HISTORICALLY hot row can
    outrank a NEWLY hot one: past ~``freq_cap`` touches every hot row
    looks equally hot and recency breaks the tie, so a shifted working
    set turns the pool over in O(capacity) admissions instead of never
    (the classic uncapped-LFU failure).

    Ledger contract (the PR 17 invariant, kept at row granularity):
    resident bytes are re-published to the process ledger as
    ``kind="table"`` under ``model`` after every admit/evict, and
    :meth:`close` frees the pool and reconciles the line to ZERO.
    Lookups are bit-identical to indexing the master directly — rows
    are admitted by copy, never transformed.
    """

    def __init__(self, model: str, master: np.ndarray,
                 capacity_rows: int, freq_cap: int = 15, ledger=None):
        if capacity_rows <= 0:
            raise ValueError(f"capacity_rows must be > 0, got "
                             f"{capacity_rows}")
        if freq_cap <= 0:
            raise ValueError(f"freq_cap must be > 0, got {freq_cap}")
        from mmlspark_tpu.observability import memory as devmem
        self.model = str(model)
        self._master = master
        self._cap = int(capacity_rows)
        self._freq_cap = int(freq_cap)
        self._ledger = ledger if ledger is not None else devmem.get_ledger()
        self._row_bytes = devmem.nbytes_of(master.shape[1:], master.dtype)
        self._pool = np.zeros((self._cap,) + master.shape[1:], master.dtype)
        self._slot: Dict[int, int] = {}      # id -> pool slot
        self._freq: Dict[int, int] = {}      # id -> capped touch count
        self._touch: Dict[int, int] = {}     # id -> logical tick
        self._free = list(range(self._cap - 1, -1, -1))
        self._tick = 0
        self._closed = False
        self.evictions = 0
        self.misses = 0
        self.hits = 0
        self._charge()

    def _charge(self) -> None:
        self._ledger.set_bytes(self.model, "table",
                               len(self._slot) * self._row_bytes)

    def _evict_cold(self, n: int) -> None:
        # coldest first: lowest capped frequency, then stalest touch —
        # deterministic id tiebreak so two runs evict identically
        victims = sorted(self._slot,
                         key=lambda i: (self._freq[i], self._touch[i], i))
        for rid in victims[:n]:
            self._free.append(self._slot.pop(rid))
            del self._freq[rid], self._touch[rid]
            self.evictions += 1

    def lookup(self, ids: Sequence[int]) -> np.ndarray:
        """Rows for ``ids`` (host-order, bit-identical to
        ``master[ids]``), touching/admitting each id through the pool."""
        if self._closed:
            raise RuntimeError(f"RowResidency {self.model!r} is closed")
        out = np.empty((len(ids),) + self._master.shape[1:],
                       self._master.dtype)
        for j, rid in enumerate(ids):
            rid = int(rid)
            self._tick += 1
            slot = self._slot.get(rid)
            if slot is None:
                self.misses += 1
                if not self._free:
                    self._evict_cold(1)
                slot = self._free.pop()
                self._pool[slot] = self._master[rid]
                self._slot[rid] = slot
                self._freq[rid] = 1
            else:
                self.hits += 1
                self._freq[rid] = min(self._freq[rid] + 1, self._freq_cap)
            self._touch[rid] = self._tick
            out[j] = self._pool[slot]
        self._charge()
        return out

    # -- observability -------------------------------------------------------
    @property
    def resident_rows(self) -> int:
        return len(self._slot)

    def resident_bytes(self) -> int:
        return len(self._slot) * self._row_bytes

    def stats(self) -> Dict[str, int]:
        return {"resident_rows": len(self._slot),
                "capacity_rows": self._cap,
                "resident_bytes": self.resident_bytes(),
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions}

    def close(self) -> None:
        """Free the pool and reconcile the ledger line to ZERO — same
        close contract as a registry eviction, at row granularity."""
        if self._closed:
            return
        self._closed = True
        self._slot.clear()
        self._freq.clear()
        self._touch.clear()
        self._free = list(range(self._cap - 1, -1, -1))
        self._charge()
