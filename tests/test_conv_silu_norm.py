"""A projection's causal convolution, ``silu`` and a head's L2 norm as one
Pallas pass (``ops/pallas_conv_norm.py``, interpreted on the CPU) against
the form that was there and that other shapes still take:
``linear_attention.causal_conv1d`` then ``silu`` then ``l2_normalize`` then
the scale, float32 through all four from the same rows.

Tolerances. Both sides are float32 from the rows to the one rounding, in
the same order of arithmetic but for a head's sum of squares and for what
the compiler contracts into a fused multiply-add: a few units in float32's
last place OF THE TERMS. Rows written in bfloat16 (no norm) therefore
differ by at most one unit in bfloat16's last place, at a rounding boundary
(under 1% of the elements). Rows written in float32 (normed) are held to
two units in the last place of the head's LENGTH, ``scale``: where a mix's
taps cancel the element itself is small and a unit in ITS last place means
nothing. The derivative is written in the rows' type: 1e-2 of the norm of
the float32 reference's for bfloat16 rows, 1e-5 for float32 ones; the taps'
gradient is float32 sums on both sides, 1e-5.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from mmlspark_tpu.models.zoo import build_model  # noqa: E402
from mmlspark_tpu.models.zoo import parts  # noqa: E402
from mmlspark_tpu.observability import metrics as obsmetrics  # noqa: E402
from mmlspark_tpu.ops import linear_attention as la  # noqa: E402
from mmlspark_tpu.ops import pallas_conv_norm as pcn  # noqa: E402

f32, bf16 = jnp.float32, jnp.bfloat16

# name: (B, L, H, d), taps, rows' type, normed, scale, (row tile, rows in
# flight, a tile's lanes)
CASES = {
    # kimi's three: q normed and scaled, k normed, v neither; two tiles
    "kimi-q": ((2, 64, 2, 128), 4, bf16, True, 128 ** -0.5, (32, 16, 1024)),
    "kimi-k": ((2, 64, 2, 128), 4, bf16, True, 1.0, (32, 16, 1024)),
    "kimi-v": ((2, 64, 2, 128), 4, bf16, False, 1.0, (32, 16, 1024)),
    # three tiles: a middle one has a tile on either side
    "three-tiles": ((1, 96, 2, 128), 4, bf16, True, 128 ** -0.5,
                    (32, 16, 1024)),
    "three-tiles-v": ((1, 96, 1, 128), 4, bf16, False, 1.0, (32, 32, 1024)),
    # one tile walked whole, and one in four chunks
    "one-tile": ((2, 32, 2, 128), 4, bf16, True, 1.0, (32, 32, 1024)),
    "four-chunks": ((1, 64, 1, 128), 4, bf16, True, 1.0, (64, 16, 1024)),
    # a row's heads over two programs' lanes, and three heads in one
    "two-lane-tiles": ((1, 64, 4, 128), 4, bf16, True, 128 ** -0.5,
                       (32, 16, 256)),
    "three-heads": ((2, 64, 3, 128), 4, bf16, True, 1.0, (32, 16, 1024)),
    # heads of two registers, two taps, float32 rows
    "heads-256": ((1, 64, 2, 256), 2, f32, True, 256 ** -0.5, (32, 16, 1024)),
    "float32-rows": ((2, 64, 2, 128), 4, f32, False, 1.0, (32, 16, 1024)),
    "nine-taps": ((1, 64, 1, 128), 9, bf16, True, 1.0, (32, 16, 1024)),
}


def _operands(case, monkeypatch):
    shape, width, dtype, norm, scale, tile = CASES[case]
    for name, value in zip(("ROWS", "CHUNK", "WIDTH"), tile):
        monkeypatch.setattr(pcn, name, value)
    B, L, H, d = shape
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    y = (2.0 * jax.random.normal(ks[0], (B, L, H * d))).astype(dtype)
    taps = 0.5 * jax.random.normal(ks[1], (width, H * d))
    ct = jax.random.normal(ks[2], (B, L, H * d)).astype(
        f32 if norm else dtype)
    return y, taps, ct, H, norm, scale


def _reference(y, taps, heads, norm, scale):
    """The three functions of ``ops/linear_attention.py`` and the scale,
    float32 from the rows on."""
    B, L, C = y.shape
    x = jax.nn.silu(la.causal_conv1d(y.astype(f32), taps))
    if norm:
        x = (la.l2_normalize(x.reshape(B, L, heads, -1)) * scale).reshape(
            B, L, C)
    return x


def _kernel(y, taps, heads, norm, scale):
    assert pcn.supports(y.shape[:2] + (heads, y.shape[2] // heads),
                        taps.shape[0], y.dtype)
    return pcn.conv_silu_norm(y, taps, heads, norm, scale)


def _ulps(got, want):
    """The largest distance in units of the last of ``want``'s eight
    bfloat16 places."""
    got, want = (np.asarray(a.astype(f32), np.float64) for a in (got, want))
    unit = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
    return float(np.max(np.abs(got - want) / unit))


def _gap(a, b):
    return float(jnp.linalg.norm(a.astype(f32) - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("case", CASES)
def test_forward_is_the_references_to_one_unit_in_the_last_place(
        case, monkeypatch):
    y, taps, _, heads, norm, scale = _operands(case, monkeypatch)
    got = _kernel(y, taps, heads, norm, scale)
    want = _reference(y, taps, heads, norm, scale)
    assert got.shape == y.shape
    if norm:
        # float32 out: two units in the last place of a head's length
        assert got.dtype == f32
        assert float(jnp.max(jnp.abs(got - want))) <= 2 * 2.0 ** -23 * scale
    elif y.dtype == f32:
        assert got.dtype == f32
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    else:
        assert got.dtype == bf16
        assert _ulps(got, want.astype(bf16)) <= 1.0
        assert float(jnp.mean(got != want.astype(bf16))) < 0.01


@pytest.mark.parametrize("case", CASES)
def test_derivative_is_the_float32_references(case, monkeypatch):
    y, taps, ct, heads, norm, scale = _operands(case, monkeypatch)
    _, pull = jax.vjp(lambda y, t: _kernel(y, t, heads, norm, scale),
                      y, taps)
    _, want = jax.vjp(lambda y, t: _reference(y, t, heads, norm, scale),
                      y.astype(f32), taps)
    dy, dw = pull(ct)
    dy_ref, dw_ref = want(ct.astype(f32))
    assert dy.dtype == y.dtype and dw.dtype == f32
    assert dw.shape == taps.shape
    assert _gap(dy, dy_ref) < (1e-5 if y.dtype == f32 else 1e-2)
    assert _gap(dw, dw_ref) < 1e-5


@pytest.mark.parametrize("norm", [True, False], ids=["normed", "plain"])
def test_a_row_starts_from_zeros_and_sees_no_other_row(norm, monkeypatch):
    """Batch row ``b``'s first positions see ``W - 1`` zeros and nothing of
    row ``b - 1``'s last positions, and row ``b - 1``'s last derivatives
    nothing of row ``b``'s first cotangents: the two rows of one call are
    bit for bit the rows of two calls of one row, though the blocks before
    and after a tile are then read from inside the same array."""
    y, taps, ct, heads, _, scale = _operands(
        "kimi-q" if norm else "kimi-v", monkeypatch)
    # what the neighbouring row would leak is large
    y = y.at[0, -8:].multiply(50.0).at[1, :8].multiply(50.0)

    def run(y, ct):
        out, pull = jax.vjp(
            lambda y, t: _kernel(y, t, heads, norm, scale), y, taps)
        return (out,) + pull(ct)
    both = run(y, ct)
    for b in range(2):
        alone = run(y[b:b + 1], ct[b:b + 1])
        np.testing.assert_array_equal(both[0][b], alone[0][0])
        np.testing.assert_array_equal(both[1][b], alone[1][0])
    # and the first output is the first row's own under the last tap
    m = y[:, 0].astype(f32) * taps[-1]
    first = m * jax.nn.sigmoid(m)
    if norm:
        first = (la.l2_normalize(first.reshape(2, heads, -1))
                 * scale).reshape(2, -1)
    np.testing.assert_allclose(both[0][:, 0].astype(f32), first,
                               rtol=1e-2 if not norm else 1e-5, atol=1e-6)


@pytest.mark.parametrize("shape,width,dtype,takes", [
    ((1, 16384, 32, 128), 4, bf16, True),       # kimi
    ((2, 4096, 16, 128), 4, bf16, True),        # qwen's key heads
    ((1, 1024, 4, 256), 2, f32, True),          # heads of two registers
    ((1, 8192, 32, 96), 4, bf16, False),        # olmo: no whole register
    ((1, 8192, 32, 64), 4, bf16, False),        # two heads a register
    ((2, 64, 2, 8), 4, f32, False),             # the tiny presets' heads
    ((1, 16384 + 64, 32, 128), 4, bf16, False),     # a ragged row
    ((1, 256, 32, 128), 4, bf16, False),        # a row shorter than a tile
    ((1, 1024, 4, 128), 1, bf16, False),        # no convolution at all
    ((1, 1024, 4, 128), 10, bf16, False),       # more rows before a tile
    ((1, 1024, 4, 128), 2, bf16, True),         # than a register holds
    ((1, 1024, 4, 128), 4, jnp.float16, False),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_supports_reads_the_shape_alone(shape, width, dtype, takes):
    assert pcn.supports(shape, width, dtype) is takes


def _calls():
    return {k: obsmetrics.counter(
        f"linear_attention.conv_norm_calls.{k}").value
            for k in ("pallas", "xla")}


@pytest.mark.parametrize("head_dim,length,form", [
    (128, 64, "pallas"),        # kimi's heads, rows of two tiles
    (128, 48, "xla"),           # rows that are no whole tiles
    (8, 64, "xla"),             # the tiny presets' heads
], ids=["kimi", "ragged", "tiny"])
def test_the_layer_takes_the_form_its_shape_says_and_counts_it(
        head_dim, length, form, monkeypatch):
    """``KimiDeltaAttention`` end to end: q, k and v each count under the
    form they took, and the layer's output and gradients are those of the
    same module with ``supports`` patched to decline (XLA's form)."""
    monkeypatch.setattr(pcn, "ROWS", 32)
    monkeypatch.setattr(pcn, "CHUNK", 16)
    module = parts.KimiDeltaAttention(32, 2, head_dim, 4, 1e-5, 16, bf16)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, length, 32), bf16)
    params = module.init(jax.random.PRNGKey(1), x)

    def run(p):
        return jax.value_and_grad(lambda p: jnp.sum(
            module.apply(p, x).astype(f32) ** 2))(p)
    before = _calls()
    got = run(params)
    after = _calls()
    assert {k: after[k] - before[k] for k in after} == {
        form: 3, "xla" if form == "pallas" else "pallas": 0}
    monkeypatch.setattr(pcn, "supports", lambda *a: False)
    want = run(params)
    assert _calls()["xla"] - after["xla"] == 3
    if form == "xla":
        jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)
        return
    np.testing.assert_allclose(got[0], want[0], rtol=1e-2)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree_util.tree_leaves(want[1])):
        assert float(jnp.linalg.norm(g - w)) \
            < 3e-2 * float(jnp.linalg.norm(w)), jax.tree_util.keystr(path)


def test_what_a_recomputed_block_makes_again(monkeypatch):
    """``kimi_linear`` lets the projections go (``DELTA_NET_QKVZ``), so a
    block's backward pass makes each projection and its pass again: a
    layer's three calls twice in the forward direction, once backward."""
    from tests.test_glm4_moe_lite import _pallas_calls
    monkeypatch.setattr(pcn, "ROWS", 32)
    monkeypatch.setattr(pcn, "CHUNK", 16)
    module = build_model(
        "kimi_linear_tiny", kda_layers=(1, 2), full_attn_layers=(),
        linear_head_dim=128, chunk=64, dtype=bf16)["module"]
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 64), 0, 64)
    params = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(1),
                                                tokens))
    calls = _pallas_calls(jax.make_jaxpr(jax.grad(lambda p: jnp.sum(
        module.apply(p, tokens, hidden=True)["hidden"].astype(f32))))(
            params).jaxpr)
    assert (calls.count("conv_silu_norm_fwd"),
            calls.count("conv_silu_norm_bwd")) == (12, 6)


def test_a_two_layer_kimi_linear_with_heads_of_128_trains_as_with_xlas_form(
        monkeypatch):
    """``kimi_linear`` with bfloat16 rows and heads of 128, two KDA layers
    under ``nn.remat``, three steps of plain gradient descent: the losses
    and the parameters with the kernel against the same module with
    ``supports`` patched to decline. XLA's form multiplies the rows by the
    taps in bfloat16 and rounds the mix there; the pass keeps float32 to
    its one rounding, so the two differ as two bfloat16 programs do: 5% of
    what three steps moved a leaf, beside float32's own last place of the
    leaf (the decay's gate hardly moves at this init)."""
    monkeypatch.setattr(pcn, "ROWS", 32)
    monkeypatch.setattr(pcn, "CHUNK", 16)
    module = build_model(
        "kimi_linear_tiny", kda_layers=(1, 2), full_attn_layers=(),
        linear_head_dim=128, chunk=64, dtype=bf16)["module"]
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 96)
    start = module.init(jax.random.PRNGKey(1), tokens)

    def run():
        @jax.jit
        def step(p):
            loss, g = jax.value_and_grad(lambda p: jnp.mean(
                module.apply(p, tokens, hidden=True)["hidden"].astype(f32)
                ** 2))(p)
            return loss, jax.tree_util.tree_map(
                lambda a, b: a - 0.05 * b.astype(a.dtype), p, g)
        p, losses = start, []
        for _ in range(3):
            loss, p = step(p)
            losses.append(float(loss))
        return losses, p
    before = _calls()
    got = run()
    assert _calls()["pallas"] - before["pallas"] >= 6
    monkeypatch.setattr(pcn, "supports", lambda *a: False)
    want = run()
    assert got[0][2] < got[0][0]
    np.testing.assert_allclose(got[0], want[0], rtol=1e-2)
    moved = jax.tree_util.tree_map(lambda a, b: a - b, want[1], start)
    for (path, g), w, m in zip(
            jax.tree_util.tree_leaves_with_path(got[1]),
            jax.tree_util.tree_leaves(want[1]),
            jax.tree_util.tree_leaves(moved)):
        assert float(jnp.linalg.norm((g - w).astype(f32))) \
            <= 5e-2 * float(jnp.linalg.norm(m.astype(f32))) \
            + 1e-6 * float(jnp.linalg.norm(w.astype(f32))), \
            jax.tree_util.keystr(path)


def test_on_a_mesh_each_device_makes_its_own_rows_and_heads(monkeypatch):
    """Under ``with mesh:`` the call is shard_mapped over the batch and the
    tensor axis, each device's taps those of its own heads, and the taps'
    gradient is summed over the batch axis; a batch the mesh does not
    divide keeps XLA's form."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "tensor"))
    y, taps, ct, heads, norm, scale = _operands("kimi-q", monkeypatch)

    def run():
        def f(y, taps):
            return jnp.sum(la.conv_silu_norm(y, taps, heads, norm, scale)
                           * ct)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(y, taps)
    want = run()
    before = _calls()
    with mesh:
        rows = jax.device_put(y, NamedSharding(mesh, P("data")))
        lowered = jax.jit(lambda y: la.conv_silu_norm(
            y, taps, heads, norm, scale)).lower(rows)
        got = run()
        assert _calls()["pallas"] - before["pallas"] == 2
        la.conv_silu_norm(jnp.concatenate([y, y[:1]]), taps, heads, norm,
                          scale)
        assert _calls()["xla"] - before["xla"] == 1
    text = lowered.compile().as_text()
    assert "all-gather" not in text and "conv_silu_norm_fwd" in text
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    np.testing.assert_array_equal(got[1][0], want[1][0])
    np.testing.assert_allclose(got[1][1], want[1][1], rtol=1e-5, atol=1e-6)
