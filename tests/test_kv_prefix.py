"""Shared-prefix block ledger (serve/kvcache.py): refcounts, the prefix
index, copy-on-write, eviction, and the conservation fuzz.

Pure host-side ledger tests — no device programs, no lanes. The two
properties the fuzz at the bottom guards (the ISSUE's acceptance bar):

- **No block is ever written while refcount > 1.** The only sanctioned
  write path is :meth:`KVCacheManager.prepare_write`; whenever it grants
  an in-place write the block's refcount must be exactly 1, and whenever
  the block is shared it must come back as a copy-on-write pair.
- **Free-list conservation.** At every step each leasable block is in
  exactly one of {free, cached, refcounted} (``check_conservation``).

One device test beside them: the arena as an attention
(:class:`CacheView`) against plain attention over the same rows.
"""
import numpy as np
import pytest

from mmlspark_tpu.serve.kvcache import (
    RESERVED_BLOCK, CacheView, KVCacheManager, blocks_needed,
    prefix_block_hashes,
)


def _kv(num_blocks=16, block_tokens=8):
    return KVCacheManager(layers=2, heads=2, head_dim=4,
                          num_blocks=num_blocks, block_tokens=block_tokens)


def _hashes(prompt, bt=8, model="m"):
    return prefix_block_hashes(model, "float32", prompt, bt)


# -- chained hashing ---------------------------------------------------------

def test_prefix_hashes_cover_full_blocks_only():
    assert _hashes([1] * 7) == []                  # no full block
    assert len(_hashes([1] * 8)) == 1
    assert len(_hashes([1] * 17)) == 2             # trailing partial dropped
    # the partial tail never changes the full blocks' hashes
    assert _hashes([1] * 17) == _hashes([1] * 16)


def test_prefix_hashes_are_chained_not_content_only():
    a = _hashes(list(range(16)))
    b = _hashes(list(range(8, 24)))
    # block [8..15] appears in both prompts but after different prefixes:
    # its KV depends on the whole prefix, so the hashes MUST differ
    assert a[1] != b[0]
    # and the chain seed separates model / dtype / block size
    assert _hashes([1] * 8, model="m") != _hashes([1] * 8, model="other")
    assert (prefix_block_hashes("m", "float32", [1] * 8, 8)
            != prefix_block_hashes("m", "int8", [1] * 8, 8))


# -- sharing through try_reserve --------------------------------------------

def test_registered_prefix_is_shared_not_reprefilled():
    kv = _kv()
    prompt = list(range(16))                       # 2 full blocks
    h = _hashes(prompt)
    a = kv.try_reserve("a", 24, prefix_hashes=h, prompt_tokens=16)
    assert kv.reserve_info("a")["hits"] == 0       # cold: nothing indexed
    kv.register_prefix("a", h)
    b = kv.try_reserve("b", 24, prefix_hashes=h, prompt_tokens=16)
    info = kv.reserve_info("b")
    assert info["hits"] == 2 and info["cached_tokens"] == 16
    assert b[0] == a[0]                            # block 0 shared outright
    assert kv.block_refcount(a[0]) == 2
    # FULL hit: the final matched block is CoW'd, not shared writable
    src, dst = info["pending_cow"]
    assert src == a[1] and dst == b[1] and dst != src
    # a holds one share, b pinned it once as the copy source -> 2
    assert kv.block_refcount(src) == 2
    kv.cow_done("b")
    assert kv.block_refcount(src) == 1             # pin released after copy
    assert kv.cow_copies == 1
    assert kv.check_conservation()


def test_partial_hit_shares_leading_blocks_only():
    kv = _kv()
    base = list(range(16))
    h = _hashes(base)
    kv.try_reserve("a", 24, prefix_hashes=h, prompt_tokens=16)
    kv.register_prefix("a", h)
    longer = base + [99] * 8                       # 3 full blocks, 2 match
    h2 = _hashes(longer)
    assert h2[:2] == h
    kv.try_reserve("b", 32, prefix_hashes=h2, prompt_tokens=24)
    info = kv.reserve_info("b")
    assert info["hits"] == 2 and info["misses"] == 1
    assert info["pending_cow"] is None             # not a full hit: block 1
    a_blocks, b_blocks = kv.blocks_for("a"), kv.blocks_for("b")
    assert b_blocks[:2] == a_blocks[:2]            # is shared READ-ONLY
    assert kv.block_refcount(a_blocks[1]) == 2
    assert kv.check_conservation()


def test_freed_prefix_blocks_park_cached_and_still_hit():
    kv = _kv(num_blocks=8)
    h = _hashes(list(range(16)))
    kv.try_reserve("a", 16, prefix_hashes=h, prompt_tokens=16)
    kv.register_prefix("a", h)
    idle = kv.free_blocks
    kv.free("a")
    assert kv.free_blocks == idle + 2              # cached counts reclaimable
    assert kv.cached_blocks == 2                   # but holds live content
    kv.try_reserve("b", 24, prefix_hashes=h, prompt_tokens=16)
    assert kv.reserve_info("b")["hits"] == 2       # hit survives the free
    assert kv.cached_blocks == 0                   # bumped back to leased
    assert kv.check_conservation()


def test_eviction_reclaims_only_refcount_zero_lru_first():
    kv = _kv(num_blocks=6, block_tokens=8)         # 5 leasable
    h1, h2 = _hashes([1] * 8), _hashes([2] * 8)
    kv.try_reserve("a", 8, prefix_hashes=h1, prompt_tokens=8)
    kv.register_prefix("a", h1)
    kv.try_reserve("b", 8, prefix_hashes=h2, prompt_tokens=8)
    kv.register_prefix("b", h2)
    kv.free("a")                                   # a's block: cached (LRU)
    kv.free("b")                                   # b's block: cached
    assert kv.cached_blocks == 2 and kv.free_blocks == 5
    # demand 4 fresh blocks: 3 truly free + the LRU cached one (a's)
    assert kv.try_reserve("c", 32) is not None
    assert kv.prefix_evictions == 1
    kv.free("c")
    assert kv.try_reserve("d", 8, prefix_hashes=h2, prompt_tokens=8) \
        is not None
    # b's block survived (MRU) -> still a full hit; a's was evicted
    assert kv.reserve_info("d")["hits"] == 1
    assert kv.check_conservation()


def test_reserve_never_evicts_blocks_it_matched():
    kv = _kv(num_blocks=7, block_tokens=8)         # 6 leasable
    h = _hashes(list(range(16)))
    kv.try_reserve("a", 16, prefix_hashes=h, prompt_tokens=16)
    kv.register_prefix("a", h)
    kv.free("a")                                   # both blocks cached
    hx = _hashes([7] * 8)
    kv.try_reserve("x", 8, prefix_hashes=hx, prompt_tokens=8)
    kv.register_prefix("x", hx)
    kv.free("x")                                   # a third cached block
    # full hit wants 1 shared + 4 fresh; only 3 truly free, so one
    # cached block MUST be evicted — and it must be x's, never one of
    # the blocks this very reservation matched
    got = kv.try_reserve("b", 40, prefix_hashes=h, prompt_tokens=16)
    assert got is not None and len(got) == 5
    assert kv.reserve_info("b")["hits"] == 2       # matched set untouched
    assert kv.prefix_evictions == 1
    kv.free("b")
    kv.try_reserve("y", 8, prefix_hashes=hx, prompt_tokens=8)
    assert kv.reserve_info("y")["hits"] == 0       # x's block was the victim
    assert kv.check_conservation()


def test_oversubscribed_reserve_sheds_cleanly():
    kv = _kv(num_blocks=4, block_tokens=8)         # 3 leasable
    h = _hashes(list(range(16)))
    kv.try_reserve("a", 16, prefix_hashes=h, prompt_tokens=16)
    kv.register_prefix("a", h)
    snap = kv.stats()
    assert kv.try_reserve("b", 32, prefix_hashes=h,
                          prompt_tokens=16) is None   # needs 4 > 3
    after = kv.stats()
    assert after == snap                           # shed mutated NOTHING
    assert kv.check_conservation()


# -- the write barrier -------------------------------------------------------

def test_prepare_write_in_place_deindexes_refcount_one():
    kv = _kv()
    h = _hashes([1] * 8)
    kv.try_reserve("a", 16, prefix_hashes=h, prompt_tokens=8)
    kv.register_prefix("a", h)
    blocks = kv.blocks_for("a")
    assert kv.prepare_write("a", 0) is None        # sole holder: in place
    kv.free("a")
    # the write de-indexed it: content diverged, so no future hits
    kv.try_reserve("b", 8, prefix_hashes=h, prompt_tokens=8)
    assert kv.reserve_info("b")["hits"] == 0
    assert blocks[0] not in kv.blocks_for("b") or kv.cached_blocks == 0
    assert kv.check_conservation()


def test_prepare_write_cows_shared_block():
    kv = _kv()
    base = list(range(16))
    h = _hashes(base)
    kv.try_reserve("a", 24, prefix_hashes=h, prompt_tokens=16)
    kv.register_prefix("a", h)
    kv.try_reserve("b", 32, prefix_hashes=_hashes(base + [9] * 8),
                   prompt_tokens=24)               # partial: shares 2 blocks
    shared = kv.blocks_for("b")[1]
    assert kv.block_refcount(shared) == 2
    pair = kv.prepare_write("b", 1)
    assert pair is not None and pair[0] == shared
    assert kv.blocks_for("b")[1] == pair[1]        # lease rewired to dst
    assert kv.block_refcount(shared) == 1          # a keeps its copy
    assert kv.block_refcount(pair[1]) == 1
    assert kv.blocks_for("a")[1] == shared         # a untouched
    assert kv.cow_copies == 1
    assert kv.check_conservation()


def test_free_unpins_pending_cow_source():
    kv = _kv()
    h = _hashes(list(range(16)))
    kv.try_reserve("a", 24, prefix_hashes=h, prompt_tokens=16)
    kv.register_prefix("a", h)
    kv.try_reserve("b", 24, prefix_hashes=h, prompt_tokens=16)
    src, _dst = kv.reserve_info("b")["pending_cow"]
    assert kv.block_refcount(src) == 2
    kv.free("b")                                   # died before the copy
    assert kv.block_refcount(src) == 1             # pin released with it
    assert kv.check_conservation()


# -- conservation fuzz -------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_refcount_cow_conservation_fuzz(seed):
    """Seeded random join/diverge/finish/kill schedule. At EVERY step:
    conservation holds, the scratch block is never leased, any in-place
    write grant has refcount exactly 1, and any CoW pair leaves both
    sides at refcount >= 1 with the lease rewired."""
    rng = np.random.default_rng(seed)
    bt = 4
    kv = KVCacheManager(layers=1, heads=1, head_dim=2, num_blocks=12,
                        block_tokens=bt)
    prompts = [list(rng.integers(0, 50, size=n))
               for n in (4, 8, 8, 12, 6)]          # overlapping hash chains
    live = {}
    next_id = 0
    for _ in range(400):
        op = rng.integers(0, 10)
        if op < 4 or not live:                     # join
            p = prompts[int(rng.integers(0, len(prompts)))]
            h = prefix_block_hashes("m", "float32", p, bt)
            sid = f"s{next_id}"
            tokens = len(p) + int(rng.integers(1, 9))
            got = kv.try_reserve(sid, tokens, prefix_hashes=h,
                                 prompt_tokens=len(p))
            if got is not None:
                assert RESERVED_BLOCK not in got
                assert len(got) == blocks_needed(tokens, bt)
                next_id += 1
                live[sid] = got
                cow = kv.take_pending_cow(sid)
                if cow is not None:
                    assert kv.block_refcount(cow[0]) >= 1  # src pinned
                    kv.cow_done(sid)
                kv.register_prefix(sid, h)
        elif op < 7:                               # diverge: write a block
            sid = list(live)[int(rng.integers(0, len(live)))]
            blocks = kv.blocks_for(sid)
            bi = int(rng.integers(0, len(blocks)))
            before = kv.block_refcount(blocks[bi])
            try:
                pair = kv.prepare_write(sid, bi)
            except RuntimeError:
                # CoW wanted a fresh block and the arena is saturated;
                # the raise must be clean (nothing mutated)
                assert kv.check_conservation()
                continue
            if pair is None:
                # in-place grant: the block was exclusively ours
                assert before == 1
                assert kv.block_refcount(blocks[bi]) == 1
            else:
                assert before > 1                  # shared -> forced CoW
                src, dst = pair
                assert kv.blocks_for(sid)[bi] == dst
                assert kv.block_refcount(src) >= 1
                assert kv.block_refcount(dst) == 1
            live[sid] = kv.blocks_for(sid)
        else:                                      # finish / mid-flight kill
            sid = list(live)[int(rng.integers(0, len(live)))]
            assert kv.free(sid) == len(live.pop(sid))
            assert kv.free(sid) == 0               # idempotent (kill path)
        assert kv.check_conservation(), "block leaked or double-owned"
        assert kv.used_blocks + kv.free_blocks == kv.leasable_blocks
    for sid in list(live):
        kv.free(sid)
    assert kv.used_blocks == 0
    assert kv.check_conservation()


# -- the arena as an attention -----------------------------------------------

@pytest.mark.parametrize("kv_dtype,tol", [(None, 1e-5), ("int8", 5e-2)])
def test_cache_view_matches_plain_attention_over_the_same_rows(kv_dtype,
                                                               tol):
    """Rows written through a block table, a window at a time, and read
    back as context give what plain causal attention gives over the same
    rows; a row that is not valid lands in the scratch block only."""
    import jax.numpy as jnp
    from mmlspark_tpu.parallel.sequence import _reference_attention
    layers, heads, hd, bt, B, W = 2, 2, 4, 8, 2, 3
    kv = KVCacheManager(layers=layers, heads=heads, head_dim=hd,
                        num_blocks=16, block_tokens=bt, kv_dtype=kv_dtype)
    tables = np.stack([kv.block_table(f"s{b}", W) for b in range(B)
                       if kv.try_reserve(f"s{b}", 11)])
    assert RESERVED_BLOCK in tables[:, 2] and tables[:, :2].all()
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(layers, B, 12, heads, hd)),
                           jnp.float32) for _ in range(3))
    arenas, outs = kv.arenas(), []
    # positions 0..7, then 8..10 in a window of four whose last row is pad
    for lo, hi, n_valid in ((0, 8, 8), (8, 12, 3)):
        positions = jnp.broadcast_to(jnp.arange(lo, hi), (B, hi - lo))
        valid = jnp.broadcast_to(jnp.arange(hi - lo) < n_valid,
                                 positions.shape)
        view = CacheView(arenas, jnp.asarray(tables), positions, valid)
        outs.append(jnp.stack([
            view(q[i, :, lo:hi], k[i, :, lo:hi], v[i, :, lo:hi])
            for i in range(layers)])[:, :, :n_valid])
        arenas = view.arenas()
    assert len(arenas) == (4 if kv_dtype else 2)
    want = jnp.stack([
        _reference_attention(q[i, :, :11], k[i, :, :11], v[i, :, :11], True)
        for i in range(layers)])
    np.testing.assert_allclose(np.concatenate(outs, axis=2), want,
                               atol=tol, rtol=tol)
    # the pad row (position 11) went to the scratch block, not to the
    # sequences' own: their second block holds rows 8..10 and nothing else
    keys = np.asarray(arenas[0])
    second = keys[:, tables[:, 1]]
    assert second[:, :, :3].any(axis=(-2, -1)).all()
    assert not second[:, :, 3:].any()
    assert keys[:, RESERVED_BLOCK, 3].any()
    view(q[0, :, 8:], k[0, :, 8:], v[0, :, 8:])     # one call too many
    with pytest.raises(ValueError, match="3 times"):
        view.arenas()
