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


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("length,blocks", [(2048, (256, 256)),
                                           (768, (768, 128))],
                         ids=["L2048", "L768-bq768-bk128"])
@pytest.mark.parametrize("width", [64, 128, 256])
def test_flash_forward_matches_reference(width, length, blocks, causal,
                                         dtype, tol):
    """The forward alone against the plain reference, at the head widths
    the benchmark's cells run. At 2,048 tokens the tile rule leaves a
    query block with K tiles of both kinds under ``causal``: wholly before
    its first row (the loop, no mask) and crossed by the diagonal (the
    tiles after it, each with its constant mask). At 768 a caller's 768 x
    128 become 768 x 256 (nothing larger divides the length): the
    diagonal crosses three K tiles of the one query block. bfloat16 under
    the bound the backward's tests use, float32 as tight as they are."""
    from mmlspark_tpu.ops import pallas_attention

    bq, bk = pallas_attention._fwd_tiles(*blocks, length, width)
    assert length % bq == 0 and bq % bk == 0
    if length == 2048:
        assert length // bq >= 2 and bq > pallas_attention.BLOCK_Q
    else:
        assert (bq, bk) == (768, 256)
    q, k, v, _ = _flash_qkvw((2, length, 2, width), dtype, seed=width + 1)
    got = pallas_attention.flash_attention(q, k, v, causal, *blocks)
    assert got.dtype == dtype and got.shape == q.shape
    # the plain softmax in float32, on the inputs as they are rounded
    want = full_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                          causal, use_flash="never")
    assert _rel(got, want) <= tol


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_forward_multiplies_in_the_input_dtype(dtype):
    """Read from the kernel's jaxpr: both products of a tile take their
    operands in the input dtype and accumulate in float32, in the loop
    without a mask and in each tile the diagonal crosses. The test that
    fails if an upcast of q, k, v or the probabilities comes back."""
    from mmlspark_tpu.ops import pallas_attention

    def dots(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)

    q, k, v, _ = _flash_qkvw((1, 1024, 1, 64), dtype, seed=2)
    closed = jax.make_jaxpr(lambda *a: pallas_attention._flash_forward(
        *a, causal=True, save_lse=True))(q, k, v)
    found = [(tuple(x.aval.dtype for x in eqn.invars), eqn.outvars[0].aval)
             for eqn in dots(closed.jaxpr)
             if eqn.outvars[0].aval.ndim == 2]
    # the loop's tile and the crossed ones, two products each; the
    # log-sum-exps' unpacking outside the kernel multiplies nothing
    bq, bk = pallas_attention._fwd_tiles(256, 256, 1024, 64)
    assert len(found) == 2 * (1 + bq // bk)
    for operands, out in found:
        assert operands == (jnp.dtype(dtype), jnp.dtype(dtype))
        assert out.dtype == jnp.float32


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


# The Pallas backward (``_flash_bwd_kernel``), from the log-sum-exps the
# forward saves. Interpret mode on the CPU, small shapes. B and H above 1
# with distinct values, so that a slipped index map cannot pass; L = 512 is
# two blocks and L = 768 three, so that under ``causal`` a K block meets a
# query block it skips, one the diagonal crosses and one it takes whole.

def _flash_qkvw(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(0, 1, shape).astype(np.float32)).astype(
        dtype) for _ in range(4)]


def _rel(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("length", [512, 768])
@pytest.mark.parametrize("width", [32, 64, 128])
def test_flash_attention_grads_match_reference(width, length, causal,
                                               dtype, tol):
    """bf16 under the kind of bound ``chip_smoke.BF16_REL_TOL`` is: the
    relative Frobenius error of each gradient."""
    from mmlspark_tpu.ops.pallas_attention import flash_attention

    q, k, v, w = _flash_qkvw((2, length, 2, width), dtype, seed=width)
    w = w.astype(jnp.float32)

    def grads(attention):
        return jax.grad(lambda *a: (attention(*a).astype(jnp.float32)
                                    * w).sum(), argnums=(0, 1, 2))(q, k, v)

    want = grads(lambda *a: full_attention(*a, causal, use_flash="never"))
    got = grads(lambda *a: flash_attention(*a, causal))
    for g, r in zip(got, want):
        assert g.shape == r.shape and g.dtype == r.dtype
        assert _rel(g, r) <= tol


@pytest.mark.parametrize("blocks", [(128, 768), (768, 128), (128, 128)],
                         ids=["bq128-bk768", "bq768-bk128", "bq128-bk128"])
def test_flash_attention_grads_with_unequal_blocks(blocks):
    """The backward's tiles are the caller's blocks grown towards 512
    (``_bwd_tile``): 256 x 768, a K block that covers three query blocks,
    768 x 256, the reverse, so that the diagonal crosses more than one
    block of a row of pairs; and the forward's log-sum-exps, saved by rows
    of 128, read by rows of 256."""
    from mmlspark_tpu.ops.pallas_attention import flash_attention

    q, k, v, w = _flash_qkvw((2, 768, 2, 16), jnp.float32, seed=7)
    want = jax.grad(lambda *a: (full_attention(
        *a, True, use_flash="never") * w).sum(), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(lambda *a: (flash_attention(
        *a, True, *blocks) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert _rel(g, r) <= 2e-5


def _row_logsumexp(q, k, causal):
    """float32 (B, H, L) from the plain scores."""
    length, width = q.shape[1], q.shape[3]
    scores = jnp.einsum("blhd,bkhd->bhlk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(width)
    if causal:
        scores = jnp.where(jnp.arange(length)[None, :]
                           > jnp.arange(length)[:, None], -jnp.inf, scores)
    return jax.nn.logsumexp(scores, axis=-1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("length", [512, 1024])
@pytest.mark.parametrize("width", [16, 64, 256, 512])
def test_flash_forward_saves_the_rows_logsumexp(width, length, causal,
                                                dtype):
    """What ``_flash_fwd_rule`` keeps for the backward: float32, by the
    CALLER's rows of 256 whatever tile the forward ran, and exact in bf16
    too (three addends carry each through the bf16 output), for head
    widths that take several lines a query block, one line, and half of
    one."""
    from mmlspark_tpu.ops import pallas_attention

    q, k, v, _ = _flash_qkvw((2, length, 3, width), dtype, seed=11)
    out, lse = pallas_attention._flash_forward(q, k, v, causal,
                                               save_lse=True)
    assert lse.dtype == jnp.float32
    assert lse.shape == (2, 3, length // 256, 256)
    np.testing.assert_allclose(np.asarray(lse).reshape(2, 3, length),
                               np.asarray(_row_logsumexp(q, k, causal)),
                               atol=2e-5, rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(out, np.float32), np.asarray(
            pallas_attention.flash_attention(q, k, v, causal), np.float32))


# (L, head width) of the one flash call each language-model cell of the
# benchmark makes a block: glm-4.7-flash and qwen3-next, granite-4.0-h and
# lfm2, olmo-hybrid (benchmark/configs/*.json with their traffic files)
_CELL_CALLS = [(4096, 256), (8192, 64), (8192, 128)]


@pytest.mark.parametrize("length,width", _CELL_CALLS,
                         ids=[f"L{n}-d{d}" for n, d in _CELL_CALLS])
def test_flash_forward_tiles_from_the_shape(length, width, monkeypatch):
    """The tile rule at the shapes the five configurations run: tiles that
    divide the length and are no smaller than the caller's, a VMEM count
    inside what the call asks of Mosaic, and the log-sum-exps the
    caller's own 256 x 256 would have given, in the same rows."""
    from mmlspark_tpu.ops import pallas_attention as pa

    dtype = jnp.bfloat16
    bq, bk = pa._fwd_tiles(pa.BLOCK_Q, pa.BLOCK_K, length, width)
    assert bq >= pa.BLOCK_Q and bk >= pa.BLOCK_K
    assert length % bq == 0 and bq % bk == 0
    rows = bq + pa._lse_rows(bq, width, dtype)[1]
    need = pa._flash_fwd_vmem_bytes(length, width, bq, bk, rows, 2)
    assert need <= pa._vmem_limit(need) <= pa._VMEM_CAP

    q, k, v, _ = _flash_qkvw((1, length, 1, width), dtype, seed=length)
    _, lse = pa._flash_forward(q, k, v, True, save_lse=True)
    assert lse.shape == (1, 1, length // 256, 256)
    monkeypatch.setattr(pa, "_fwd_tiles", lambda bq, bk, *_: (bq, bk))
    _, as_called = pa._flash_forward.__wrapped__(q, k, v, True,
                                                 save_lse=True)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(as_called),
                               atol=2e-5, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(lse).reshape(1, 1, length),
        np.asarray(_row_logsumexp(q, k, True)), atol=2e-5, rtol=1e-6)


def test_flash_backward_counts_its_traces():
    from mmlspark_tpu.observability import metrics as obsmetrics
    from mmlspark_tpu.ops.pallas_attention import flash_attention

    q, k, v, w = _flash_qkvw((1, 512, 1, 8), jnp.float32, seed=5)
    counter = obsmetrics.counter("attention.flash_bwd_calls.pallas")
    before = counter.value
    flash_attention(q, k, v, True)                  # no backward, no count
    assert counter.value == before
    jax.grad(lambda q: (flash_attention(q, k, v, True) * w).sum())(q)
    assert counter.value == before + 1
