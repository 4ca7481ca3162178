"""``keye_vl2`` at a toy size against the plain reference
(``benchmark/references/keye_vl2.py``), and what it brought: keys chosen for
each query by a learned indexer (``ops/sparse_attention``: scores, the exact
choice, the core over the chosen keys, the indexer's own loss), the choice's
Pallas call and the flash kernels under a selection (interpreted), a second
loss whose gradient reaches other leaves than the language model's. float32
on the CPU.

Tolerances: both sides compute in float32 on one backend, so they differ
only by the order of additions: 1e-5 relative on logits and losses, 1e-4 on
gradients; the kernels in interpret mode against the masked dense product
2e-5 absolute on unit normal inputs. What has to be exact is exact: the
chosen sets index for index, a tie's lower index, the zero gradient each
loss leaves the other's leaves, the mask a kernel writes against the rule's
statement.
"""
import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark.references import keye_vl2 as ref  # noqa: E402
from benchmark.references import sdar_moe  # noqa: E402
from mmlspark_tpu.models.zoo import build_model  # noqa: E402
from mmlspark_tpu.models.zoo.moe import DroplessMoe  # noqa: E402
from mmlspark_tpu.models.zoo.parts import (  # noqa: E402
    GroupedAttention, plain_frequencies)
from mmlspark_tpu.observability import metrics as obsmetrics  # noqa: E402
from mmlspark_tpu.ops import pallas_attention as pa  # noqa: E402
from mmlspark_tpu.ops import pallas_select  # noqa: E402
from mmlspark_tpu.ops import sparse_attention as sparse  # noqa: E402
from mmlspark_tpu.train.lm_loss import next_token_loss  # noqa: E402

TOP_K = 8
CFG = dict(hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, head_dim=8, moe_intermediate_size=16,
           num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
           rope_theta=1e7, rope_scaling={"mrope_section": [1, 1, 2]},
           rms_norm_eps=1e-6, vocab_size=96,
           sa_config=dict(indexer_num_heads=2, indexer_head_dim=8,
                          indexer_num_kv_heads=1, topk=TOP_K),
           program={"zoo_args": {"dtype": jnp.float32, "gate_grad": False}},
           deployment={"num_experts_published": 8, "experts_first": 0})
ROWS = 2


def _module(length, cfg=CFG):
    return build_model("keye_vl2", **ref.zoo_args(cfg, length))["module"]


def _tokens(length, seed=5):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 96, size=(ROWS, length)).astype(np.int32))


def _away(path, v):
    """Scales off 1, biases off 0 and routers' scores apart, so that none
    is a factor a wrong wiring could hide behind."""
    name = jax.tree_util.keystr(path)
    if "scale" in name or "bias" in name:
        return v + jnp.linspace(-0.5, 0.5, v.size).reshape(v.shape)
    if "router']['kernel" in name:
        return 8.0 * v
    return v


@pytest.fixture(scope="module")
def params():
    return jax.tree_util.tree_map_with_path(
        _away, ref.init_params(CFG, jax.random.PRNGKey(7)))


def _program(module, params, tokens):
    """((loss, parts), gradient), the logits and the sown selections."""
    def loss(p):
        out = module.apply(p, tokens, hidden=True)
        return next_token_loss(out, p["params"]["lm_head"]["kernel"], tokens,
                               chunk=16, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, (logits, sown) = jax.jit(lambda p: (
            jax.value_and_grad(loss, has_aux=True)(p),
            module.apply(p, tokens, mutable=["intermediates"])))(params)
    return got, logits, [
        np.asarray(sown["intermediates"][f"block{i}"]["attn"]["selection"][0])
        for i in range(CFG["num_hidden_layers"])]


def _reference(params, tokens, cfg=CFG, mask=None):
    def loss(p):
        parts = [ref.sequence_loss(cfg, None, mask, ROWS, p, tokens[b])
                 for b in range(ROWS)]
        return sum(part for part, _ in parts), [aux for _, aux in parts]
    with jax.default_matmul_precision("highest"):
        ((total, aux), grads), logits = jax.jit(lambda p: (
            jax.value_and_grad(loss, has_aux=True)(p), jnp.stack([
                ref.logits(cfg, p, tokens[b], mask=mask)
                for b in range(ROWS)])))(params)
    return total, aux, grads, logits


def _close(got, want, rtol=1e-4):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=rtol, atol=rtol * float(jnp.abs(w).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


# ------------------------------------------------- the model on the path
@pytest.mark.parametrize("length", [6, 24], ids=["shorter", "longer"])
def test_losses_logits_gradients_and_choices_match_the_reference(
        params, length):
    """Rows shorter than ``k`` (every causal key is chosen) and longer (16
    of 24 queries have more past than they may keep)."""
    tokens = _tokens(length)
    ((loss, parts), grads), logits, chosen = _program(
        _module(length), params, tokens)
    total, aux, want, want_logits = _reference(params, tokens)
    np.testing.assert_allclose(loss, total, rtol=1e-5)
    np.testing.assert_allclose(parts["loss.main"],
                               sum(a[0] for a in aux), rtol=1e-5)
    np.testing.assert_allclose(parts["loss.indexer"],
                               sum(a[1] for a in aux), rtol=1e-5)
    assert float(parts["loss.indexer"]) > 0
    np.testing.assert_allclose(logits, want_logits, rtol=1e-5, atol=1e-5)
    _close(grads, want)
    for layer, got in enumerate(chosen):
        for b in range(ROWS):
            assert np.array_equal(got[b], np.asarray(ref.unpack(
                aux[b][3][layer], length)))
            assert got[b].sum(-1).tolist() == [
                min(TOP_K, t + 1) for t in range(length)]
            assert not np.triu(got[b], 1).any()


def test_reference_tree_is_the_programs_tree(params):
    shapes = jax.eval_shape(_module(24).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 24), jnp.int32))
    assert jax.tree_util.tree_structure(shapes) \
        == jax.tree_util.tree_structure(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(shapes),
                            jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape, jax.tree_util.keystr(path)


def test_the_published_configuration_counts_its_parameters():
    """Three counts agree: the configuration file's, the reference's
    ``param_shapes`` and the program's own tree at the cell's size (shapes
    only, nothing is allocated); and the published model's from the same
    function."""
    cfg = json.loads((ROOT / "benchmark/configs/keye-vl-2.0-30b-a3b.json")
                     .read_text())
    here = ref.parameters(cfg)
    assert here == 465_391_104
    assert "465,391,104" in cfg["deployment"]["parameters_here"]
    module = build_model(cfg["program"]["zoo"],
                         **ref.zoo_args(cfg, 16384))["module"]
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 512), jnp.int32))
    assert sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes)) == here
    want = ref.param_shapes(cfg)
    assert jax.tree_util.tree_structure(shapes) \
        == jax.tree_util.tree_structure(want, is_leaf=ref._is_shape)
    published = dict(cfg, num_hidden_layers=48, num_experts=128,
                     vocab_size=151936)
    assert ref.parameters(published) == 30_640_656_384
    assert "30,640,656,384" in cfg["deployment"]["parameters_published"]
    # the cell's arithmetic: the chosen pairs of a row of 16,384
    assert ref.selected_pairs(16384, 2048) == 31_458_304
    assert abs(ref.selected_pairs(16384, 2048) / ref.causal_pairs(16384)
               - 0.2344) < 1e-4
    assert set(cfg["reduced"]) == set(cfg["reduced_from"]) == {
        "num_hidden_layers", "num_experts", "num_local_experts",
        "vocab_size"}


def test_each_loss_reaches_its_own_leaves_and_no_other(params):
    """The indexer's four leaves take EXACTLY zero gradient from the
    language-model loss and every other leaf exactly zero from the
    indexer's (the reference's sum of the two is the program's, leaf for
    leaf: the parity test)."""
    tokens = _tokens(12)
    module = _module(12)

    def parts(p):
        out = module.apply(p, tokens, hidden=True)
        aux = next_token_loss(out, p["params"]["lm_head"]["kernel"], tokens,
                              chunk=16, dtype=jnp.float32)[1]
        return jnp.stack([aux["loss.main"], aux["loss.indexer"]])

    def both(f):        # the two parts' gradients, one program
        return jax.jit(lambda p: tuple(
            jax.grad(lambda p, i=i: f(p)[i])(p) for i in range(2)))(params)
    for main, indexer in (both(parts),):
        seen = {True: 0, False: 0}
        for (path, m), i in zip(jax.tree_util.tree_leaves_with_path(main),
                                jax.tree_util.tree_leaves(indexer)):
            name = jax.tree_util.keystr(path)
            mine, other = (i, m) if ref.is_indexer_leaf(name) else (m, i)
            assert not np.asarray(other).any(), name
            if "router" not in name:        # the gate is frozen
                assert np.asarray(mine).any(), name
            seen[ref.is_indexer_leaf(name)] += 1
        assert seen[True] == 5 * CFG["num_hidden_layers"]   # bias and scale
        assert seen[False] > seen[True]


# ------------------------------------------------------------ the choice
def _scores(seed, L, t, B=1, grid=4.0):
    s = np.random.default_rng(seed).normal(size=(B, L // t, L, t))
    return jnp.asarray(np.round(s * grid) / grid, jnp.float32)


def test_a_tie_goes_to_the_lower_index_in_program_and_reference():
    """Scores on a coarse grid, so that the threshold is shared by many
    keys; zeros of both signs are one score."""
    L, k = 32, 5
    scores = _scores(0, L, L, grid=1.0)
    scores = scores.at[0, 0, 3].set(-0.0).at[0, 0, 7].set(0.0)
    rows = np.asarray(scores)[0, 0].T                   # [t, s]
    got = np.asarray(sparse._topk_mask_xla(scores, k))[0, 0].T != 0
    want, _ = ref.choose(jnp.asarray(rows), 0, k)
    assert np.array_equal(got, np.asarray(want))
    ties = 0
    for t in range(L):
        order = sorted(range(t + 1), key=lambda s: (-rows[t, s], s))
        kept = sorted(order[:min(k, t + 1)])
        assert np.nonzero(got[t])[0].tolist() == kept
        last = rows[t, order[min(k, t + 1) - 1]]
        ties += sum(rows[t, s] == last for s in range(t + 1)) > 1
    assert ties > L // 2        # the rule was exercised


@pytest.mark.parametrize("L,t,k,chunk", [
    (1024, 256, 100, 512), (512, 128, 512, 128), (256, 128, 1, 256)])
def test_the_choices_kernel_writes_the_rules_mask(L, t, k, chunk):
    """``pallas_select.topk_mask`` (interpreted) against the rule's
    statement, bit for bit: ties, both zeros, ``k`` from 1 to the whole
    row, several chunks and tiles, two rows."""
    scores = _scores(1, L, t, B=2)
    scores = scores.at[0, :, :, :5].set(0.0).at[1, 1].set(-0.0)
    assert pallas_select.supports(scores.shape, chunk)
    got = np.asarray(pallas_select.topk_mask(scores, k, chunk))
    want = np.asarray(sparse._topk_mask_xla(scores, k))
    assert np.array_equal(got, want)
    assert got.dtype == np.int8 and got.sum() == 2 * ref.selected_pairs(L, k)


def test_what_the_choices_kernel_takes():
    assert pallas_select.supports((1, 32, 16384, 512))
    assert not pallas_select.supports((1, 1, 24, 24))       # no whole lanes
    assert not pallas_select.supports((1, 2, 16384, 512))   # not a square
    assert not pallas_select.supports((1, 32, 65536, 2048))  # past VMEM
    with pytest.raises(ValueError, match="supports"):
        pallas_select.topk_mask(jnp.zeros((1, 1, 24, 24)), 4)


@pytest.mark.parametrize("t,chunk", [(8, 16), (16, 16), (8, 32)])
def test_scores_and_loss_in_steps_are_the_whole_rows(t, chunk, monkeypatch):
    """``indexer_scores`` and ``indexer_loss`` walk (query tile, key chunk)
    steps in one loop (``_steps``) and join a tile's chunks after it: a
    chunk of two tiles, of one, and of the whole row, against the same
    calls with the whole row as ONE step; value and the three gradients."""
    monkeypatch.setattr(sparse, "KEY_CHUNK", chunk)
    B, L, Hi, di, H, G, d, k = 2, 32, 2, 8, 4, 2, 8, 6
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    q_i = jax.random.normal(keys[0], (B, L, Hi, di))
    k_i = jax.random.normal(keys[1], (B, L, di))
    w = jax.random.normal(keys[2], (B, L, Hi))
    q = jax.random.normal(keys[3], (B, L, H, d))
    kv = jax.random.normal(keys[4], (B, L, G, d))
    assert sparse._steps(L, t)[0] == chunk

    def square(x):          # the pair layout -> (B, query, key)
        return x.transpose(0, 1, 3, 2).reshape(B, L, L)
    causal = np.tril(np.ones((L, L), bool))
    scores = jax.jit(sparse.indexer_scores, static_argnums=3)
    whole = square(scores(q_i, k_i, w, L))
    tiled = square(scores(q_i, k_i, w, t))
    np.testing.assert_allclose(np.where(causal, tiled, 0.0),
                               np.where(causal, whole, 0.0), rtol=1e-6,
                               atol=1e-6)

    @functools.partial(jax.jit, static_argnums=0)
    def loss(tile):
        mask = sparse._topk_mask_xla(
            sparse.indexer_scores(q_i, k_i, w, tile), k)
        lse = sparse._selected_xla(q, kv, kv, mask)[1]
        return jax.value_and_grad(
            lambda *x: sparse.indexer_loss(*x, q, kv, lse, mask),
            argnums=(0, 1, 2))(q_i, k_i, w)
    (got, grads), (want, want_grads) = loss(t), loss(L)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, x in zip(grads, want_grads):
        np.testing.assert_allclose(g, x, rtol=1e-4, atol=1e-6)


# -------------------------------------------------------------- the core
def _qkv(L, H=4, G=2, d=32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (1, L, H, d)),
            jax.random.normal(keys[1], (1, L, G, d)),
            jax.random.normal(keys[2], (1, L, G, d)),
            jax.random.normal(keys[3], (1, L, H, d)))


def test_selected_flash_matches_the_masked_dense_product():
    """Both flash kernels with a selection as an operand (interpreted),
    forward, log-sum-exps and the three gradients, against XLA's masked
    dense softmax; rows that keep no key of their first tiles are among
    them (``k`` small against the tile)."""
    L, t = 1024, 512
    q, k, v, do = _qkv(L)
    mask = sparse._topk_mask_xla(_scores(2, L, t, grid=64.0), 40)

    def run(core):
        def f(q, k, v):
            out, lse = core(q, k, v)
            return (out * do).sum(), (out, lse)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))(q, k, v)
    (_, (out, lse)), grads = run(lambda q, k, v: sparse.selected_core(
        q, k, v, mask, use_flash="require"))
    (_, (want, want_lse)), want_grads = run(
        lambda q, k, v: sparse._selected_xla(q, k, v, mask))
    np.testing.assert_allclose(out, want, atol=2e-5)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, atol=5e-5)


def test_a_selection_carries_its_own_names_and_moves_no_other_call():
    q, k, v, _ = (x.astype(jnp.bfloat16) for x in _qkv(512, H=2, G=2))
    mask = sparse._topk_mask_xla(_scores(3, 512, 512), 64)

    def text(f):
        return jax.jit(jax.grad(lambda q, k, v: f(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))).lower(q, k, v).as_text(
                debug_info=True)
    chosen = text(lambda q, k, v: pa.selected_attention(q, k, v, mask)[0])
    plain = text(lambda q, k, v: pa.flash_attention(q, k, v, True))
    assert "selected_attention_fwd" in chosen
    assert "selected_attention_bwd" in chosen
    assert "long_attention_bwd" not in chosen
    assert "selected_attention" not in plain and "long_attention_bwd" in plain
    assert pa.supports_selected((1, 16384, 32, 128), 512, 2)
    assert not pa.supports_selected((1, 16384, 32, 128), 384, 2)
    assert not pa.supports_selected((1, 65536, 32, 128), 512, 2)


def test_a_shape_no_kernel_takes_is_refused_or_counted(monkeypatch):
    q, k, v, _ = _qkv(24, d=8)
    mask = sparse._topk_mask_xla(_scores(4, 24, 24), 8)
    with pytest.raises(ValueError, match="require"):
        sparse.selected_core(q, k, v, mask, use_flash="require")
    monkeypatch.setattr(sparse.sequence, "_on_chip", lambda: True)
    before = {n: obsmetrics.counter(n).value for n in (
        "sparse_attention.fallbacks", "sparse_attention.core_calls.xla",
        "sparse_attention.select_calls.xla")}
    sparse.selected_core(q, k, v, mask)
    sparse.select(_scores(4, 24, 24), 8)
    assert obsmetrics.counter("sparse_attention.fallbacks").value \
        == before["sparse_attention.fallbacks"] + 2
    assert obsmetrics.counter("sparse_attention.core_calls.xla").value \
        == before["sparse_attention.core_calls.xla"] + 1
    assert obsmetrics.counter("sparse_attention.select_calls.xla").value \
        == before["sparse_attention.select_calls.xla"] + 1


def test_with_every_key_chosen_the_layer_is_sdars_under_a_causal_mask():
    """``top_k >= L``: the shared block, tied to the new one. The same
    weights under ``GroupedAttention`` without an indexer and a plain
    causal call give the same rows."""
    L, freqs = 24, plain_frequencies(8, 1e7)
    x = jax.random.normal(jax.random.PRNGKey(1), (ROWS, L, 32))
    kw = dict(dim=32, heads=4, kv_heads=2, head_dim=8, dtype=jnp.float32,
              qk_norm_eps=1e-6, norm_heads=True, rotary_freqs=freqs)
    chosen = GroupedAttention(indexer=(2, 8, L, freqs), **kw)
    p = chosen.init(jax.random.PRNGKey(2), x)
    plain = {"params": {n: w for n, w in p["params"].items()
                        if n != "indexer"}}
    (y, stats), want = chosen.apply(p, x), GroupedAttention(**kw).apply(
        plain, x)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    counts = stats["counts"]
    assert float(counts["sparse_attention.selected_pairs"]) \
        == float(counts["sparse_attention.causal_pairs"]) == ROWS * 300


def test_an_indexer_needs_the_whole_causal_half():
    x = jnp.zeros((1, 16, 32))
    for kw in (dict(window=4), dict(block_diffusion=4),
               dict(attention_fn=lambda *a, **k: None)):
        part = GroupedAttention(32, 4, 2, 8, dtype=jnp.float32,
                                indexer=(2, 8, 4, None), **kw)
        with pytest.raises(ValueError, match="indexer"):
            part.init(jax.random.PRNGKey(0), x)


# ------------------------------------------------------------- positions
def test_the_sectioned_turn_on_three_equal_streams_is_the_plain_turn():
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 3, 16))
    pos = jnp.arange(24) * 3 + 1
    plain = sdar_moe._rotary_at(x, 1e7, pos)
    got = ref.mrope(x, 1e7, jnp.broadcast_to(pos, (3, 24)), (2, 3, 3))
    np.testing.assert_allclose(got, plain, rtol=1e-6, atol=1e-6)
    # streams that differ turn their own frequencies alone
    moved = ref.mrope(x, 1e7, jnp.stack([pos, pos + 5, pos]), (2, 3, 3))
    same = np.isclose(moved, plain, atol=1e-6).all((0, 1))
    assert same[[0, 1, 5, 6, 7, 8, 9, 13, 14, 15]].all()
    assert not same[[2, 3, 4, 10, 11, 12]].any()
    with pytest.raises(ValueError, match="mrope_section"):
        ref.mrope(x, 1e7, jnp.broadcast_to(pos, (3, 24)), (2, 3, 4))


# ------------------------------------------------------------- the share
def _layer(held, first, **kw):
    return DroplessMoe(32, 16, 8, 4, experts_held=(held, first),
                       dtype=jnp.float32, scores="softmax", **kw)


def _share(p, first, count):
    ffn = dict(p["params"])
    for name in ("experts_gate", "experts_up", "experts_down"):
        ffn[name] = ffn[name][first:first + count]
    return {"params": ffn}


def test_eight_shares_add_up_to_the_uncut_layer():
    """The deployment's layout at toy widths: 16 softmax-routed experts,
    four a token, EIGHT chips with two each and no shared expert. What
    every chip computes alike (the mixer with its indexer, the norms, the
    router) is counted once; the shares' routed parts sum to the uncut
    layer as the reference computes it."""
    whole = _layer(16, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    p = whole.init(jax.random.PRNGKey(3), x)
    p["params"]["router"]["kernel"] = 8.0 * p["params"]["router"]["kernel"]
    d = dict(ref.dims(CFG), experts=16, held=16, first=0, top_k=4,
             gate_grad=True)
    want = jax.vmap(lambda row: ref._experts(
        d, jnp.einsum, p["params"], row)[0])(x)
    total, slots = 0.0, 0
    for first in range(0, 16, 2):
        y, stats = _layer(2, first).apply(_share(p, first, 2), x)
        total, slots = total + y, slots + int(stats["slots_here"])
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-6)
    assert slots == 2 * 16 * 4
    np.testing.assert_allclose(whole.apply(p, x)[0], want, rtol=1e-5,
                               atol=1e-6)


# ----------------------------------------------------------------- counts
def test_the_work_counts_the_chosen_pairs():
    L, k = 40, 8
    pairs = sum(min(k, t + 1) for t in range(L))
    assert ref.selected_pairs(L, k) == pairs
    assert ref.causal_pairs(L) == L * (L + 1) // 2
    call = {"rows": 3, "len": L, "heads": 4, "kv_heads": 2, "head_dim": 16,
            "top_k": k}
    flops, nbytes = ref.selected_fwd_cost(call)
    assert flops == 3 * pairs * 4 * 16 * 4
    assert nbytes == 3 * L * 16 * 2 * (2 * 4 + 2 * 2)
    back, back_bytes = ref.selected_bwd_cost(call)
    assert back == 2.5 * flops and back_bytes == nbytes * 7 / 4
    assert ref.select_cost({"rows": 3, "len": L}) == (
        0.0, 3 * L * (L + 1) // 2 * 4)
    cfg = json.loads((ROOT / "benchmark/configs/keye-vl-2.0-30b-a3b.json")
                     .read_text())
    parts = ref._fwd_flops_per_item(cfg, 16384)
    assert parts["core"] == 4 * 32 * 128 * 31_458_304
    assert parts["indexer_scores"] == 2 * 16 * 64 * ref.causal_pairs(16384)
    layer = sum(parts[n] for n in (
        "projections", "indexer_projections", "routed", "core",
        "indexer_scores"))
    assert ref.train_flops_per_item(cfg, 16384) == pytest.approx(
        3 * (4 * layer + parts["head"]) + 4 * parts["target"])
    assert 0.45 < (parts["core"] + parts["indexer_scores"]) / layer < 0.50
