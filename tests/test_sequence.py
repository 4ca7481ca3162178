"""Sequence/context parallelism tests on the 8-device virtual mesh.

Numerical parity of ring/Ulysses attention against single-device softmax
attention, gradients through shard_map, and an end-to-end sequence-parallel
LM training step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
from mmlspark_tpu.parallel.sequence import (
    full_attention, make_attention_fn, ring_attention, ulysses_attention,
)

B, L, H, D = 2, 16, 4, 8


@pytest.fixture(scope="module")
def seq_mesh():
    return make_mesh(MeshSpec(data=2, seq=4))


@pytest.fixture
def qkv():
    rng = np.random.default_rng(0)
    return tuple(jnp.asarray(rng.normal(0, 1, (B, L, H, D)).astype(np.float32))
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full(seq_mesh, qkv, causal):
    q, k, v = qkv
    expected = full_attention(q, k, v, causal=causal)
    with seq_mesh:
        got = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, mesh=seq_mesh, causal=causal))(q, k, v)
    assert np.allclose(np.asarray(expected), np.asarray(got), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(seq_mesh, qkv, causal):
    q, k, v = qkv
    expected = full_attention(q, k, v, causal=causal)
    with seq_mesh:
        got = jax.jit(lambda q, k, v: ulysses_attention(
            q, k, v, mesh=seq_mesh, causal=causal))(q, k, v)
    assert np.allclose(np.asarray(expected), np.asarray(got), atol=1e-5)


def test_ring_gradients_match_full(seq_mesh, qkv):
    q, k, v = qkv

    def loss_full(q, k, v):
        return (full_attention(q, k, v, causal=True) ** 2).sum()

    def loss_ring(q, k, v):
        return (ring_attention(q, k, v, mesh=seq_mesh, causal=True) ** 2).sum()

    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    with seq_mesh:
        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_full, g_ring):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_ring_trivial_seq_axis_falls_back(qkv):
    mesh = make_mesh(MeshSpec(data=8))  # |seq| == 1
    q, k, v = qkv
    out = ring_attention(q, k, v, mesh=mesh, causal=True)
    assert np.allclose(np.asarray(out),
                       np.asarray(full_attention(q, k, v, True)), atol=1e-6)


def test_ulysses_rejects_indivisible_heads(seq_mesh):
    q = jnp.zeros((1, 16, 3, 4))  # 3 heads, |seq|=4
    with pytest.raises(ValueError):
        ulysses_attention(q, q, q, mesh=seq_mesh)


def test_make_attention_fn_auto(seq_mesh):
    fn = make_attention_fn(seq_mesh, "auto")
    assert fn.func is ring_attention
    assert make_attention_fn(None, "auto") is full_attention
    with pytest.raises(ValueError):
        make_attention_fn(seq_mesh, "bogus")


# ---------------------------------------------------------------------------
def test_lm_ring_parity_and_training_step(seq_mesh):
    """TransformerLM: ring-attention logits == full-attention logits on the
    same params, and one sharded training step runs end to end."""
    import optax
    from mmlspark_tpu.models.zoo import build_model
    from mmlspark_tpu.parallel.trainer import DistributedTrainer

    vocab, seqlen = 64, 32
    full_spec = build_model("transformer_lm_tiny", vocab=vocab, max_len=seqlen)
    ring_spec = build_model(
        "transformer_lm_tiny", vocab=vocab, max_len=seqlen,
        attention_fn=make_attention_fn(seq_mesh, "ring"))
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, vocab, (4, seqlen), dtype=np.int32))

    params = full_spec["module"].init(jax.random.PRNGKey(0), tokens)
    logits_full = full_spec["module"].apply(params, tokens)
    with seq_mesh:
        logits_ring = jax.jit(
            lambda p, t: ring_spec["module"].apply(p, t))(params, tokens)
    assert np.allclose(np.asarray(logits_full), np.asarray(logits_ring),
                       atol=2e-4)

    # one full sharded training step (dp x sp) with next-token loss
    module = ring_spec["module"]

    def loss_fn(params, batch, rng):
        logits = module.apply(params, batch["tokens"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], batch["tokens"][:, 1:]).mean()

    trainer = DistributedTrainer(loss_fn, optax.adamw(1e-3), mesh=seq_mesh,
                                 seq_axis="seq")
    state = trainer.init(
        lambda: module.init(jax.random.PRNGKey(0), tokens))
    batch = trainer.put_batch(
        {"tokens": rng.integers(0, vocab, (4, seqlen), dtype=np.int32)})
    state, metrics = trainer.train_step(state, batch, jax.random.PRNGKey(1))
    assert np.isfinite(float(metrics["loss"]))
    assert int(jax.device_get(state["step"])) == 1


def test_lm_tensor_and_seq_parallel_compose():
    """tp x sp x dp on one mesh: step compiles and runs."""
    import optax
    from mmlspark_tpu.models.zoo import build_model
    from mmlspark_tpu.parallel.trainer import DistributedTrainer

    mesh = make_mesh(MeshSpec(data=2, seq=2, tensor=2))
    spec = build_model("transformer_lm_tiny", vocab=64, max_len=16,
                       attention_fn=make_attention_fn(mesh, "ring"))
    module = spec["module"]
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, 64, (4, 16), dtype=np.int32)

    def loss_fn(params, batch, rng):
        logits = module.apply(params, batch["tokens"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], batch["tokens"][:, 1:]).mean()

    trainer = DistributedTrainer(loss_fn, optax.sgd(1e-2), mesh=mesh,
                                 seq_axis="seq")
    state = trainer.init(
        lambda: module.init(jax.random.PRNGKey(0), jnp.asarray(tokens)))
    # tensor rules hit the qkv/mlp kernels: verify at least one param is
    # actually sharded over `tensor`
    shardings = trainer.state_sharding_spec()
    leaves = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec"))
    assert any("tensor" in str(s.spec) for s in leaves)
    state, metrics = trainer.train_step(
        state, trainer.put_batch({"tokens": tokens}), jax.random.PRNGKey(3))
    assert np.isfinite(float(metrics["loss"]))


def test_ring_bf16_stays_close_to_fp32_reference(seq_mesh):
    # accumulators are fp32 even for bf16 inputs: drift vs the fp32 full
    # reference must stay at bf16-rounding scale, not compound per ring step
    rng = np.random.default_rng(5)
    q32, k32, v32 = (jnp.asarray(rng.normal(0, 1, (B, L, H, D)).astype(np.float32))
                     for _ in range(3))
    expected = full_attention(q32, k32, v32, causal=True)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q32, k32, v32))
    with seq_mesh:
        got = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, mesh=seq_mesh, causal=True))(qb, kb, vb)
    assert got.dtype == jnp.bfloat16
    assert np.abs(np.asarray(got, np.float32) - np.asarray(expected)).max() < 0.05


def test_lm_scores_through_jax_model():
    # input_dtype="int32" must flow through the JaxModel scoring path
    from mmlspark_tpu.models.jax_model import JaxModel
    from mmlspark_tpu import Frame
    rng = np.random.default_rng(6)
    tokens = rng.integers(0, 64, (6, 16)).astype(np.float64)  # frame stores f64
    f = Frame.from_dict({"tokens": tokens})
    m = JaxModel(inputCol="tokens", outputCol="logits", miniBatchSize=4)
    m.set_model("transformer_lm_tiny", vocab=64, max_len=16)
    out = m.transform(f)
    assert np.isfinite(np.asarray(out.column("logits"))).all()


# -- fused flash attention kernel (ops/pallas_attention.py) ------------------

def test_flash_attention_matches_reference():
    """Pallas flash kernel (interpret mode on CPU) vs the jnp reference:
    same online-softmax answer, causal and bidirectional, f32 and bf16.
    Tolerance is the bf16-operand matmul rounding both paths share."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops.pallas_attention import flash_attention, supports
    from mmlspark_tpu.parallel.sequence import full_attention

    rng = np.random.default_rng(0)
    B, L, H, D = 2, 256, 3, 64
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, L, H, D)).astype(np.float32))
               for _ in range(3))
    for causal in (False, True):
        ref = np.asarray(full_attention(q, k, v, causal, use_flash="never"))
        got = np.asarray(flash_attention(q, k, v, causal=causal))
        np.testing.assert_allclose(got, ref, atol=8e-3, rtol=1e-2)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = np.asarray(full_attention(qb, kb, vb, True,
                                    use_flash="never")).astype(np.float32)
    got = np.asarray(flash_attention(qb, kb, vb, causal=True)).astype(
        np.float32)
    np.testing.assert_allclose(got, ref, atol=4e-2, rtol=4e-2)


def test_flash_attention_support_gate():
    """Ragged lengths (ViT's 197 tokens) and short sequences are not the
    flash kernel's: ``full_attention`` asks ``supports_short`` next
    (tests/test_short_attention.py) instead of failing block divisibility."""
    from mmlspark_tpu.ops.pallas_attention import supports
    assert supports((2, 512, 4, 64))
    assert supports((1, 1024, 8, 128))
    assert not supports((2, 197, 4, 64))    # ragged
    assert not supports((2, 256, 4, 64))    # < 2 blocks
    assert not supports((2, 512, 4, 63))    # lane-hostile head dim


def test_flash_attention_vjp_matches_reference():
    """flash_attention is differentiable (custom VJP with a blockwise
    O(L*block)-memory backward); grads match the jnp reference path."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.pallas_attention import flash_attention
    from mmlspark_tpu.parallel.sequence import full_attention

    rng = np.random.default_rng(3)
    B, L, H, D = 1, 512, 2, 32
    q, k, v, w = (jnp.asarray(rng.normal(0, 1, (B, L, H, D))
                              .astype(np.float32)) for _ in range(4))
    for causal in (False, True):
        g_ref = jax.grad(lambda *a: (full_attention(
            *a, causal, use_flash="never") * w).sum(), argnums=(0, 1, 2))(
            q, k, v)
        g_fla = jax.grad(lambda *a: (flash_attention(
            *a, causal=causal) * w).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_fla):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=3e-2, rtol=2e-2)
