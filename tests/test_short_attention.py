"""The short-sequence attention kernel (``ops/pallas_attention.py``) and
the one place that picks it (``parallel/sequence.full_attention``).

On the CPU the kernel runs in Pallas' interpret mode, which fills what a
block reads outside its array with NaN: a padded row or key column that
leaked into any result would show as a NaN. The last tests compile the
real Mosaic kernel for a described (not attached) v5e, which is what
catches a lowering the interpreter accepts and the chip refuses.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mmlspark_tpu.models.zoo import build_model
from mmlspark_tpu.observability import metrics as obsmetrics
from mmlspark_tpu.ops import pallas_attention
from mmlspark_tpu.parallel import sequence
from mmlspark_tpu.parallel.sequence import full_attention

VIT_B = (2, 197, 12, 64)
VIT_TINY = (2, 65, 3, 64)
ALIGNED = (1, 128, 4, 64)
reference = functools.partial(full_attention, use_flash="never")


def _qkvw(shape, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(k, shape, jnp.float32).astype(dtype)
            for k in keys]


def _rel(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _out_and_grads(fn, q, k, v, w):
    def loss(q, k, v):
        return (fn(q, k, v).astype(jnp.float32)
                * w.astype(jnp.float32)).sum()
    return fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("shape", [VIT_B, VIT_TINY, ALIGNED],
                         ids=["vit_b16", "vit_tiny", "aligned128"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["f32", "bf16"])
def test_forward_and_grad_match_the_reference(dtype, tol, shape, causal):
    q, k, v, w = _qkvw(shape, dtype)
    out, grads = _out_and_grads(
        lambda q, k, v: pallas_attention.short_attention(q, k, v, causal),
        q, k, v, w)
    ref_out, ref_grads = _out_and_grads(
        functools.partial(reference, causal=causal), q, k, v, w)
    assert out.dtype == dtype and out.shape == shape
    assert _rel(out, ref_out) < tol
    for got, want in zip(grads, ref_grads):
        assert got.dtype == dtype
        assert _rel(got, want) < tol


@pytest.mark.parametrize("shape", [VIT_B, VIT_TINY, (1, 130, 8, 16)],
                         ids=["197", "65", "130x16"])
def test_padded_rows_and_key_columns_give_nothing(shape):
    """L is padded to 256 (128, 256) inside; the interpreter reads NaN
    there. A padded key column with any probability, or a padded query
    row with any weight in dk / dv, would make the result NaN; a finite
    result that equals the unpadded reference to float32 rounding has
    given them exactly none."""
    assert pallas_attention._padded_len(shape[1]) > shape[1]
    q, k, v, w = _qkvw(shape, jnp.float32, seed=3)
    out, grads = _out_and_grads(
        lambda q, k, v: pallas_attention.short_attention(q, k, v, False),
        q, k, v, w)
    ref_out, ref_grads = _out_and_grads(
        functools.partial(reference, causal=False), q, k, v, w)
    for got, want in zip((out,) + grads, (ref_out,) + ref_grads):
        assert np.isfinite(np.asarray(got)).all()
        assert _rel(got, want) < 2e-6


def test_which_shapes_fit_one_block():
    assert pallas_attention.supports_short((128, 197, 12, 64))
    assert pallas_attention.supports_short((8, 65, 3, 64))
    assert pallas_attention.supports_short((8, 40, 4, 16), itemsize=4)
    # too long for one block, a head dim that does not divide the lanes
    assert not pallas_attention.supports_short((1, 512, 12, 64))
    assert not pallas_attention.supports_short((1, 197, 12, 80))
    # the flash kernel's lengths stay the flash kernel's
    assert pallas_attention.supports((1, 512, 8, 64))
    assert not pallas_attention.supports((1, 197, 12, 64))


def _counts():
    return {name: obsmetrics.counter(name).value for name in (
        "attention.fused_calls.short", "attention.fused_calls.flash",
        "attention.fused_calls.reference", "attention.flash_fallbacks")}


def _delta(before):
    return {k.rsplit(".", 1)[-1]: int(v - before[k])
            for k, v in _counts().items()}


def test_counters_say_which_implementation_each_trace_took(monkeypatch):
    q, k, v, _ = _qkvw(VIT_TINY, jnp.bfloat16)
    before = _counts()
    full_attention(q, k, v, causal=False)            # the CPU: reference
    assert _delta(before) == {"short": 0, "flash": 0, "reference": 1,
                              "flash_fallbacks": 0}
    before = _counts()
    full_attention(q, k, v, causal=False, use_flash="require")
    assert _delta(before) == {"short": 1, "flash": 0, "reference": 0,
                              "flash_fallbacks": 0}
    # as on a chip: the shape decides, and only what neither kernel takes
    # counts as a fallback
    monkeypatch.setattr(sequence, "_on_chip", lambda: True)
    before = _counts()
    full_attention(q, k, v, causal=True)
    assert _delta(before) == {"short": 1, "flash": 0, "reference": 0,
                              "flash_fallbacks": 0}
    odd = _qkvw((1, 197, 2, 80), jnp.bfloat16)[:3]
    before = _counts()
    full_attention(*odd, causal=False)
    assert _delta(before) == {"short": 0, "flash": 0, "reference": 1,
                              "flash_fallbacks": 1}
    with pytest.raises(ValueError, match="no fused kernel"):
        full_attention(*odd, causal=False, use_flash="require")
    long = _qkvw((1, 512, 2, 64), jnp.bfloat16)[:3]
    before = _counts()
    full_attention(*long, causal=True)
    assert _delta(before) == {"short": 0, "flash": 1, "reference": 0,
                              "flash_fallbacks": 0}


def _tiny_vit(**kw):
    return build_model("vit_tiny", num_classes=10, **kw)["module"]


def test_vit_parameter_tree_is_flax_multi_head_attention(rng):
    module = _tiny_vit()
    x = jnp.asarray(rng.standard_normal((2, 32, 32, 3)), jnp.float32)
    params = module.init(jax.random.PRNGKey(0), x)["params"]
    attn = jax.tree_util.tree_map(lambda a: a.shape, params["block0"]["attn"])
    head = {"kernel": (192, 3, 64), "bias": (3, 64)}
    assert attn == {"query": head, "key": head, "value": head,
                    "out": {"kernel": (3, 64, 192), "bias": (192,)}}


def test_vit_default_path_equals_the_reference_hook(monkeypatch, rng):
    """The same parameter tree through the new default (as on a chip: the
    short kernel, 4 blocks) and through ``attention_fn=`` the reference:
    logits and every parameter's gradient."""
    x = jnp.asarray(rng.standard_normal((2, 32, 32, 3)), jnp.float32)
    labels = jnp.asarray([1, 7])
    default = _tiny_vit(dtype=jnp.float32)
    hooked = _tiny_vit(dtype=jnp.float32, attention_fn=reference)
    params = default.init(jax.random.PRNGKey(0), x)

    def run(module):
        def loss(p):
            logits = module.apply(p, x)
            return -jax.nn.log_softmax(logits)[jnp.arange(2), labels].mean()
        return module.apply(params, x), jax.grad(loss)(params)

    monkeypatch.setattr(sequence, "_on_chip", lambda: True)
    before = _counts()
    logits, grads = run(default)
    # two traces (the logits, the gradient) of four blocks
    assert _delta(before)["short"] == 8 and _delta(before)["reference"] == 0
    before = _counts()
    ref_logits, ref_grads = run(hooked)
    assert _delta(before)["short"] == 0
    assert _rel(logits, ref_logits) < 1e-5
    flat, ref_flat = (jax.tree_util.tree_leaves_with_path(g)
                      for g in (grads, ref_grads))
    assert [p for p, _ in flat] == [p for p, _ in ref_flat]
    # the key bias moves every score of a row alike, so its gradient is
    # rounding noise on both sides: a leaf is held to the typical leaf's
    # norm where its own is smaller
    floor = float(np.median([np.linalg.norm(w) for _, w in ref_flat]))
    for (path, got), (_, want) in zip(flat, ref_flat):
        gap = np.linalg.norm(np.asarray(got) - np.asarray(want))
        assert gap < 1e-4 * max(float(np.linalg.norm(want)), floor), \
            jax.tree_util.keystr(path)


def _data_mesh(devices):
    return Mesh(np.array(devices), ("data",))


def test_default_path_on_a_mesh_runs_on_each_devices_own_rows(monkeypatch):
    """Under ``with mesh:`` the kernel call is shard_mapped over the batch
    axis: the lowered program holds a manual computation over ``data``
    and the compiled one no all-gather; a batch the mesh does not divide
    takes the reference and is counted."""
    monkeypatch.setattr(sequence, "_on_chip", lambda: True)
    mesh = _data_mesh(jax.devices()[:4])
    rows = NamedSharding(mesh, P("data"))
    q, k, v, _ = (jax.device_put(x, rows) for x in _qkvw(
        (8,) + VIT_TINY[1:], jnp.float32))
    fn = jax.jit(functools.partial(full_attention, causal=False))
    with mesh:
        lowered = fn.lower(q, k, v)
        out = fn(q, k, v)
    assert re.search(r"manual_computation|shard_map", lowered.as_text())
    assert "all-gather" not in lowered.compile().as_text()
    assert out.sharding.is_equivalent_to(rows, out.ndim)
    assert _rel(out, reference(q, k, v, causal=False)) < 2e-6
    before = _counts()
    with mesh:
        jax.jit(functools.partial(full_attention, causal=False)).lower(
            *(x[:6] for x in (q, k, v)))
    assert _delta(before) == {"short": 0, "flash": 0, "reference": 1,
                              "flash_fallbacks": 1}


# -- the real lowering, compiled for a described v5e (no chip needed) -------

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def as_on_chip(monkeypatch):
    monkeypatch.setattr(sequence, "_on_chip", lambda: True)
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_mosaic_compiles_the_kernel_at_vit_b16_shape(topo, as_on_chip,
                                                    causal):
    from jax.sharding import SingleDeviceSharding
    x = jax.ShapeDtypeStruct((128, 197, 12, 64), jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))

    def grads(q, k, v, w):
        return jax.grad(lambda q, k, v: (
            full_attention(q, k, v, causal=causal).astype(jnp.float32)
            * w).sum(), argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(x, x, x, x).compile().as_text()
    assert len(re.findall(r"%short_attention_fwd\S* = ", text)) == 1
    assert len(re.findall(r"%short_attention_bwd\S* = ", text)) == 1
    assert text.count("tpu_custom_call") == 2
    assert "[128,12,197,197]" not in text


def test_vit_step_on_four_chips_keeps_the_kernel_on_each_chips_rows(
        topo, as_on_chip):
    """``vit-b16-train-dp4``'s construction at ``vit_tiny`` size: the
    module built with no ``attention_fn``, the step jitted under ``with
    mesh:``. A bare Mosaic call there is refused or all-gathered."""
    mesh = _data_mesh(topo.devices[:4])
    rows, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    module = _tiny_vit()
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3), jnp.bfloat16)))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        shapes)
    images = jax.ShapeDtypeStruct((64, 32, 32, 3), jnp.bfloat16,
                                  sharding=rows)

    def step(p, x):
        return jax.grad(lambda p: module.apply(p, x).astype(
            jnp.float32).sum())(p)

    with mesh:
        text = jax.jit(step).lower(params, images).compile().as_text()
    assert text.count("tpu_custom_call") == 8        # 4 blocks, fwd + bwd
    assert "all-gather" not in text
    assert re.search(r"bf16\[16,65,192\]\S* custom-call\(|"
                     r"\(bf16\[16,65,192\]", text)    # 64 rows / 4 chips


def _benchmark_pattern(metric):
    import json
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "metrics",
                           metric + ".json")) as f:
        return re.compile(json.load(f)["args"]["pattern"])


@pytest.mark.parametrize("shape,dtype", [
    ((2, 4096, 20, 256), jnp.bfloat16),     # glm-4.7-flash-train-ep8share
    ((1, 8192, 8, 64), jnp.bfloat16),       # chip_smoke, the longctx lane
    ((1, 16384, 2, 64), jnp.float32),       # the edge of supports()
    ((1, 512, 2, 8), jnp.float32),          # its narrowest head
    ((4, 8192, 32, 64), jnp.bfloat16),      # lfm2-24b-a2b-train-ep8share-8k
    ((1, 8192, 30, 128), jnp.bfloat16),     # olmo-hybrid-7b-train-8k: AT
                                            # PR 50's cap, L x d = 2^20
], ids=["d256-bf16", "d64-bf16", "d64-f32-edge", "d8-f32", "lfm2-d64-bf16",
        "olmo-d128-bf16"])
def test_mosaic_compiles_the_flash_kernel_and_its_backward(
        topo, as_on_chip, shape, dtype):
    """The flash kernel forward and backward through Mosaic for a v5e, and
    the names the benchmark's readers find them by: the accepted patterns
    of ``kernel.flash_attention_ms`` / ``kernel.flash_fwd_roofline`` must
    match the FORWARD's instruction (a single-result custom call with
    `flash` in its name: the log-sum-exps ride in its one output) and must
    not match the backward's, which ``kernel.flash_bwd_ms`` finds."""
    from jax.sharding import SingleDeviceSharding
    assert pallas_attention.supports(shape)
    x = jax.ShapeDtypeStruct(shape, dtype,
                             sharding=SingleDeviceSharding(topo.devices[0]))

    def grads(q, k, v, w):
        return jax.grad(lambda q, k, v: (
            full_attention(q, k, v, causal=True).astype(jnp.float32)
            * w).sum(), argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(x, x, x, x).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 2
    forward = [_benchmark_pattern(m) for m in (
        "kernel.flash_attention_ms", "kernel.flash_fwd_roofline")]
    backward = _benchmark_pattern("kernel.flash_bwd_ms")
    hits = [[bool(rx.search(c)) for rx in forward + [backward]]
            for c in calls]
    assert sorted(hits) == [[False, False, True], [True, True, False]]
    # nothing score-sized outside the two calls: no L x L array, and no
    # loop (the jnp scans this kernel replaced wrote a f32[B, L, H, 256]
    # block of scores, probabilities, dp and ds to HBM each turn)
    b, L, h, _ = shape
    assert f"[{b},{h},{L},{L}]" not in text
    assert " while(" not in text


@pytest.mark.parametrize("shape,window", [
    ((2, 8192, 64, 128), 512),      # laguna-xs.2-train-ep8share-8k: AT the
                                    # cap up to PR 50
    ((1, 4096, 4, 64), 1280),       # a tile clear between edge and diagonal
    ((1, 1024, 2, 128), 100),       # a window under a tile
], ids=["laguna-d128-W512", "d64-W1280", "d128-W100"])
def test_mosaic_compiles_the_windowed_kernels_under_names_of_their_own(
        topo, as_on_chip, shape, window):
    """A band's forward and backward through Mosaic for a v5e, and the
    names they carry: the accepted patterns of the CAUSAL calls
    (``kernel.flash_attention_ms`` / ``kernel.flash_fwd_roofline`` /
    ``kernel.flash_bwd_ms``) must match neither, the ``attn.window_*``
    patterns each its own, so that a cell with both kinds of layer reads
    one shape of call under each name."""
    from jax.sharding import SingleDeviceSharding
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))

    def grads(q, k, v, w):
        return jax.grad(lambda q, k, v: (
            full_attention(q, k, v, causal=True, window=window).astype(
                jnp.float32) * w).sum(), argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(x, x, x, x).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 2
    causal = [_benchmark_pattern(m) for m in (
        "kernel.flash_attention_ms", "kernel.flash_fwd_roofline",
        "kernel.flash_bwd_ms")]
    assert not [c for c in calls for rx in causal if rx.search(c)]
    mine = [_benchmark_pattern(m) for m in (
        "attn.window_fwd_ms", "attn.window_fwd_roofline",
        "attn.window_bwd_ms", "attn.window_bwd_roofline")]
    hits = [[bool(rx.search(c)) for rx in mine] for c in calls]
    assert sorted(hits) == [[False, False, True, True],
                            [True, True, False, False]]
    b, L, h, _ = shape
    assert f"[{b},{h},{L},{L}]" not in text
    assert " while(" not in text


@pytest.mark.parametrize("width", [64, 256], ids=["d64", "d256"])
def test_a_recomputed_decoder_block_runs_the_flash_forward_once(
        topo, as_on_chip, width):
    """The gradient of a small ``glm4_moe_lite`` (two blocks and the MTP
    module's, each under ``nn.remat``) compiled for a v5e: ONE forward
    flash call a block, where a block that kept only its input ran two
    (the second to rebuild the backward's residuals), and one backward
    call a block; both still found by the benchmark's accepted patterns."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    module = build_model(
        "glm4_moe_lite_tiny", depth=2, max_len=512, nope=width * 3 // 4,
        rope=width // 4, v_dim=width, dtype=jnp.bfloat16)["module"]
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 512), jnp.int32)))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        shapes)
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one)

    def grads(p, x):
        def loss(p):
            out = module.apply(p, x, hidden=True)
            return out["hidden"].sum() + out["mtp_hidden"].sum()
        return jax.grad(loss)(p)

    text = jax.jit(grads).lower(params, tokens).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    blocks = 3
    for metric in ("kernel.flash_attention_ms", "kernel.flash_fwd_roofline",
                   "kernel.flash_bwd_ms"):
        rx = _benchmark_pattern(metric)
        assert sum(bool(rx.search(c)) for c in calls) == blocks, metric
    # and no further forward call that the patterns would not find
    assert sum("flash" in c.split(" = ")[0] for c in calls) == blocks


@pytest.mark.parametrize("key_heads", [16, 32], ids=["grouped", "one_to_one"])
def test_mosaic_compiles_the_delta_rules_chunk_calls(topo, as_on_chip,
                                                     key_heads):
    """The gated delta rule, forward and backward, at the shape of
    benchmark cell ``qwen3-next-train-ep16share`` through Mosaic for a
    v5e: the four Pallas calls of ``ops/pallas_delta_rule.py`` under the
    names ``linattn.chunk_kernel_ms`` finds them by and no accepted
    pattern does, and the walk still the two ``while``s (the scan and its
    transpose) that ``linattn.delta_rule_ms`` finds by their state."""
    from jax.sharding import SingleDeviceSharding
    from mmlspark_tpu.ops import linear_attention as la
    one = SingleDeviceSharding(topo.devices[0])
    B, L, Hv, d = 2, 4096, 32, 128

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def grads(q, k, v, g, beta, w):
        return jax.value_and_grad(lambda *a: (la.gated_delta_rule(
            *a, dtype=jnp.bfloat16) * w).sum(), argnums=(0, 1, 2, 3, 4))(
                q, k, v, g, beta)

    text = jax.jit(grads).lower(
        s((B, L, key_heads, d)), s((B, L, key_heads, d)),
        s((B, L, Hv, d), jnp.bfloat16), s((B, L, Hv)), s((B, L, Hv)),
        s((B, L, Hv, d))).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    names = sorted(re.match(r"(?:ROOT )?%([a-z_]+)", c).group(1)
                   for c in calls)
    assert names == ["delta_chunk_bwd", "delta_chunk_fwd",
                     "delta_chunk_out", "delta_chunk_out_bwd"]
    mine = _benchmark_pattern("linattn.chunk_kernel_ms")
    assert all(mine.search(c) for c in calls)
    for metric in ("kernel.flash_attention_ms", "kernel.flash_fwd_roofline",
                   "kernel.flash_bwd_ms", "kernel.attention_ms",
                   "linattn.delta_rule_ms", "ssm.state_walk_ms"):
        assert not any(_benchmark_pattern(metric).search(c) for c in calls)
    walk = _benchmark_pattern("linattn.delta_rule_ms")
    assert sum(bool(walk.search(line.strip()))
               for line in text.splitlines()) == 2
    # no float32 head-major copy of q, k or v, and no repeated key heads
    assert f"f32[{B},{Hv},{L // 64},64,{d}]" not in text


def test_mosaic_compiles_the_vector_decay_rules_chunk_calls(topo,
                                                            as_on_chip):
    """The delta rule under a decay a key channel, forward and backward,
    at the shape of benchmark cell ``kimi-linear-48b-a3b-train-ep32share-
    16k`` through Mosaic for a v5e: the four Pallas calls of
    ``ops/pallas_kda.py`` (sublane rolls, six masked products a tile, the
    triangular inverse) under the names ``kda.chunk_kernel_ms`` finds them
    by and no accepted pattern does, and the walk the two ``while``s (the
    scan and its transpose) that ``kda.state_walk_ms`` finds by the state
    they carry first."""
    from jax.sharding import SingleDeviceSharding
    from mmlspark_tpu.ops import linear_attention as la
    one = SingleDeviceSharding(topo.devices[0])
    B, L, H, d = 1, 16384, 32, 128

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def grads(q, k, v, g, beta, w):
        return jax.value_and_grad(lambda *a: (la.gated_delta_rule(
            *a, dtype=jnp.bfloat16) * w).sum(), argnums=(0, 1, 2, 3, 4))(
                q, k, v, g, beta)

    text = jax.jit(grads).lower(
        s((B, L, H, d)), s((B, L, H, d)), s((B, L, H, d), jnp.bfloat16),
        s((B, L, H, d)), s((B, L, H)), s((B, L, H, d))).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    names = sorted(re.match(r"(?:ROOT )?%([a-z_]+)", c).group(1)
                   for c in calls)
    assert names == ["kda_chunk_bwd", "kda_chunk_fwd", "kda_chunk_out",
                     "kda_chunk_out_bwd"]
    mine = _benchmark_pattern("kda.chunk_kernel_ms")
    assert all(mine.search(c) for c in calls)
    for metric in ("kernel.flash_attention_ms", "kernel.flash_fwd_roofline",
                   "kernel.flash_bwd_ms", "kernel.attention_ms",
                   "linattn.chunk_kernel_ms", "linattn.delta_rule_ms",
                   "linattn.state_walk_ms", "ssm.state_walk_ms",
                   "ssm.chunk_kernel_ms"):
        assert not any(_benchmark_pattern(metric).search(c) for c in calls)
    walk = _benchmark_pattern("kda.state_walk_ms")
    assert sum(bool(walk.search(line.strip()))
               for line in text.splitlines()) == 2
    # no float32 head-major copy of q, k, v or the decay
    assert f"f32[{B},{H},{L // 64},64,{d}]" not in text


def test_mosaic_compiles_the_flash_kernels_at_two_head_widths(topo,
                                                              as_on_chip):
    """The latent layer's call of cell ``kimi-linear-48b-a3b-train-
    ep32share-16k``, keys of 192 over values of 128 at 16,384 tokens in
    bfloat16 (K + V of a (batch, head) 12 MiB in VMEM, lanes padded), forward
    and backward through Mosaic for a v5e under the causal calls' names, no
    operand padded to the other's width; float32 at this shape is past
    ``supports``' bytes and is refused."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, 16384, 32, 192), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16, sharding=one)
    assert pallas_attention.supports(q.shape, v_dim=128, itemsize=2)
    assert not pallas_attention.supports(q.shape, v_dim=128, itemsize=4)

    def grads(q, k, v, w):
        return jax.grad(lambda q, k, v: (
            full_attention(q, k, v, causal=True).astype(jnp.float32)
            * w).sum(), argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(grads).lower(q, q, v, v).compile()
    text = compiled.as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 2
    forward = [_benchmark_pattern(m) for m in (
        "kernel.flash_attention_ms", "mla.flash_fwd_roofline")]
    backward = _benchmark_pattern("kernel.flash_bwd_ms")
    hits = [[bool(rx.search(c)) for rx in forward + [backward]]
            for c in calls]
    assert sorted(hits) == [[False, False, True], [True, True, False]]
    assert "[1,32,16384,16384]" not in text and " while(" not in text
    # the values stay 128 wide into the call: nothing of (.., 16384, 192)
    # but q, k and their gradients
    assert "bf16[1,32,16384,128]" in text


def test_mosaic_compiles_the_state_space_rules_chunk_calls(topo, as_on_chip):
    """Mamba-2's rule, forward and backward, at the shape of benchmark
    cell ``granite-4.0-h-micro-train-8k`` through Mosaic for a v5e: the
    four Pallas calls of ``ops/pallas_ssd.py`` under the names
    ``ssm.chunk_kernel_ms`` finds them by and no accepted pattern does,
    and the walk still the two ``while``s (the scan and its transpose) that
    ``ssm.state_walk_ms`` finds by the state they carry first."""
    from jax.sharding import SingleDeviceSharding
    from mmlspark_tpu.ops import linear_attention as la
    one = SingleDeviceSharding(topo.devices[0])
    B, L, H, P_, N = 1, 8192, 64, 64, 128

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def grads(x, dt, A, Bm, Cm, w):
        return jax.value_and_grad(lambda *a: (la.ssd(
            *a, dtype=jnp.bfloat16) * w).sum(), argnums=(0, 1, 2, 3, 4))(
                x, dt, A, Bm, Cm)

    text = jax.jit(grads).lower(
        s((B, L, H, P_), jnp.bfloat16), s((B, L, H)), s((H,)),
        s((B, L, 1, N), jnp.bfloat16), s((B, L, 1, N), jnp.bfloat16),
        s((B, L, H, P_))).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    names = sorted(re.match(r"(?:ROOT )?%([a-z_]+)", c).group(1)
                   for c in calls)
    assert names == ["ssd_chunk_bwd", "ssd_chunk_fwd", "ssd_chunk_out",
                     "ssd_chunk_out_bwd"]
    mine = _benchmark_pattern("ssm.chunk_kernel_ms")
    assert all(mine.search(c) for c in calls)
    for metric in ("kernel.flash_attention_ms", "kernel.flash_fwd_roofline",
                   "kernel.flash_bwd_ms", "kernel.attention_ms",
                   "linattn.chunk_kernel_ms", "linattn.delta_rule_ms",
                   "ssm.state_walk_ms"):
        assert not any(_benchmark_pattern(metric).search(c) for c in calls)
    walk = _benchmark_pattern("ssm.state_walk_ms")
    assert sum(bool(walk.search(line.strip()))
               for line in text.splitlines()) == 2
    # neither the masked scores nor a float32 head-major copy of x in HBM
    assert f"[{H},{L // 256},256,256]" not in text
    assert f"f32[{B},{L // 256},256,{H},{P_}]" not in text


@pytest.mark.parametrize("shape,n,normed", [
    ((4, 8192, 32, 128), 64, True),     # sdar-30b-a3b-train-ep8share-4k, q
    ((4, 8192, 4, 128), 64, True),      # and k
    ((2, 8192, 48, 128), 64, False),    # laguna-xs.2-train-ep8share-8k,
    ((2, 8192, 64, 128), 32, False),    # sliding q and full q (half a head)
    ((2, 8192, 8, 128), 32, False),     # and k
], ids=["sdar-q", "sdar-k", "laguna-sliding-q", "laguna-full-q", "laguna-k"])
def test_mosaic_compiles_a_heads_norm_and_turn_under_names_of_their_own(
        topo, as_on_chip, shape, n, normed):
    """``ops/pallas_head_norm_turn.py`` forward and backward at the cells'
    shapes through Mosaic for a v5e: two calls, found by
    ``attn.norm_turn_ms`` and by no other reader's pattern, and no float32
    array of the rows' size outside them (the norm's and the turn's
    temporaries are what the calls take out of the program)."""
    from jax.sharding import SingleDeviceSharding
    from mmlspark_tpu.ops import pallas_head_norm_turn as hnt
    assert hnt.supports(shape, n)
    one = SingleDeviceSharding(topo.devices[0])
    B, L, H, d = shape

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    eps = 1e-6 if normed else None

    def grads(y, cos, sin, scale, ct):
        out, pull = jax.vjp(lambda y, scale: hnt.head_norm_turn(
            y, cos, sin, n, scale, eps), y, scale)
        return out, pull(ct)

    text = jax.jit(grads).lower(
        s(shape, jnp.bfloat16), s((L, d)), s((L, d)),
        s((d,)) if normed else None, s(shape, jnp.bfloat16)
    ).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    names = sorted(re.match(r"(?:ROOT )?%([a-z_]+)", c).group(1)
                   for c in calls)
    assert names == ["head_norm_turn_bwd", "head_norm_turn_fwd"]
    mine = _benchmark_pattern("attn.norm_turn_ms")
    assert all(mine.search(c) for c in calls)
    metrics = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "metrics")
    others = [m[:-5] for m in sorted(os.listdir(metrics))
              if m != "attn.norm_turn_ms.json" and "pattern" in open(
                  os.path.join(metrics, m)).read()]
    assert len(others) > 10
    for metric in others:
        assert not any(_benchmark_pattern(metric).search(c) for c in calls), \
            metric
    assert f"f32[{B},{L},{H},{d}]" not in text
    assert f"f32[{B},{L},{H * d}]" not in text


@pytest.mark.parametrize("norm,scale", [
    (True, 128 ** -0.5), (True, 1.0), (False, 1.0)], ids=["q", "k", "v"])
def test_mosaic_compiles_the_convolution_silu_and_norm_under_names_of_their_own(
        topo, as_on_chip, norm, scale):
    """``ops/pallas_conv_norm.py`` forward and backward at
    ``kimi-linear-48b-a3b-train-ep32share-16k``'s three projections through
    Mosaic for a v5e: two calls, found by ``kda.conv_norm_ms`` and by no
    other reader's pattern; of float32 arrays of the rows' size the program
    holds the normed rows the chunk call reads and their cotangent, and
    nothing for v: the padded copy, the mix and the norm's broadcast column
    are what the calls take out of it."""
    from jax.sharding import SingleDeviceSharding
    from mmlspark_tpu.ops import linear_attention as la
    from mmlspark_tpu.ops import pallas_conv_norm as pcn
    B, L, H, d, W = 1, 16384, 32, 128, 4
    assert pcn.supports((B, L, H, d), W, jnp.bfloat16)
    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def grads(y, taps, ct):
        out, pull = jax.vjp(
            lambda y, taps: la.conv_silu_norm(y, taps, H, norm, scale),
            y, taps)
        return out, pull(ct)

    text = jax.jit(grads).lower(
        s((B, L, H * d), jnp.bfloat16), s((W, H * d)),
        s((B, L, H * d), jnp.float32 if norm else jnp.bfloat16)
    ).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    names = sorted(re.match(r"(?:ROOT )?%([a-z_]+)", c).group(1)
                   for c in calls)
    assert names == ["conv_silu_norm_bwd", "conv_silu_norm_fwd"]
    mine = _benchmark_pattern("kda.conv_norm_ms")
    assert all(mine.search(c) for c in calls)
    metrics = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "metrics")
    others = [m[:-5] for m in sorted(os.listdir(metrics))
              if m != "kda.conv_norm_ms.json" and "pattern" in open(
                  os.path.join(metrics, m)).read()]
    assert len(others) > 10
    for metric in others:
        assert not any(_benchmark_pattern(metric).search(c) for c in calls), \
            metric
    # no padded copy of the rows, in either type
    assert f"[{B},{L + W - 1},{H * d}]" not in text
    # float32 rows: the two the calls hand over where they norm, none for v
    assert len(re.findall(rf"f32\[{B},{L},{H * d}\]\S* parameter", text)) \
        == (1 if norm else 0)
    assert f"f32[{B},{L},{H},{d}]" not in text


def test_mosaic_compiles_the_choice_and_the_selected_core_under_names_of_their_own(
        topo, as_on_chip):
    """``keye-vl2-30b-a3b-train-ep8share-16k``'s three new calls through
    Mosaic for a v5e at the cell's shape: the choice
    (``ops/pallas_select.topk_mask``: a tile's 32 MiB of ordered integers in
    VMEM) and the flash kernels with a selection as an operand, forward and
    backward; each found by its own metric's pattern and by no other
    reader's, and no array a (query, key) pair but the mask outside
    them."""
    from jax.sharding import SingleDeviceSharding
    from mmlspark_tpu.ops import sparse_attention as sparse
    B, L, H, G, d, t = 1, 16384, 32, 4, 128, 512
    assert sparse.tile_of(L) == t
    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def grads(scores, q, k, v, ct):
        mask = sparse.select(scores, 2048)
        (out, _), pull = jax.vjp(lambda q, k, v: sparse.selected_core(
            q, k, v, mask), q, k, v)
        return mask, out, pull((ct, jnp.zeros((B, H, L), jnp.float32)))

    text = jax.jit(grads).lower(
        s((B, L // t, L, t), jnp.float32), s((B, L, H, d)),
        s((B, L, G, d)), s((B, L, G, d)), s((B, L, H, d))
    ).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    by_name = {re.match(r"(?:ROOT )?%([a-z_]+)", c).group(1): c
               for c in calls}
    assert sorted(by_name) == ["selected_attention_bwd",
                               "selected_attention_fwd", "topk_mask"]
    readers = {"topk_mask": "sparseattn.select_ms",
               "selected_attention_fwd": "sparseattn.core_fwd_ms",
               "selected_attention_bwd": "sparseattn.core_bwd_ms"}
    metrics = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "metrics")
    patterned = [m[:-5] for m in sorted(os.listdir(metrics))
                 if "pattern" in open(os.path.join(metrics, m)).read()]
    assert len(patterned) > 15
    for name, call in by_name.items():
        hits = {m for m in patterned if _benchmark_pattern(m).search(call)}
        assert hits == {readers[name],
                        readers[name].replace("_ms", "_roofline")}, name
    assert f"f32[{B},{H},{L},{L}]" not in text
    assert f"[{B},{G},{H // G},{L},{L}]" not in text
