"""A head's RMS norm and its rotary turn as one Pallas pass
(``ops/pallas_head_norm_turn.py``, interpreted on the CPU) against the form
that was there and that other shapes still take: ``parts.RMSNorm`` then
``parts.rotary``, float32 through both.

Tolerances. The forward pass rounds once, where the reference rounds, and
is float32 up to there on both sides: the two differ by the order of a
head's 128 additions and by where the norm's factor and scale multiply (the
kernel makes ``r (x (w cos) + rot(x) (rot(w) sin))``, the reference ``((x r)
w) cos + pair((x r) w) sin``), a few units in float32's last place, which
moves a bfloat16 result by one unit in ITS last place where the float32
value lies at a rounding boundary (under 1% of the elements), and by no
more. The derivative is written in the rows' type: 1e-2 of the norm of the
float32 reference's (bfloat16 rounds to 2^-9 an element); the scale's
gradient is float32 sums on both sides, 1e-2 asked and 1e-5 met. float32
rows: 1e-5 throughout.
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
from mmlspark_tpu.ops import pallas_head_norm_turn as hnt  # noqa: E402

f32, bf16 = jnp.float32, jnp.bfloat16
EPS = 1e-6
YARN = parts.yarn_frequencies(64, 5e5, 32.0, 4096)

# name: (B, L, H, d), frequencies, factor, normed, rows' type, (row tile,
# rows in flight), positions repeating over the row's halves
CASES = {
    # sdar: every q head of 128 normed, the whole head turned, both copies
    # of a token at its position
    "sdar-q": ((2, 64, 4, 128), parts.plain_frequencies(128, 1e6), 1.0,
               True, bf16, None, True),
    "sdar-k": ((2, 64, 1, 128), parts.plain_frequencies(128, 1e6), 1.0,
               True, bf16, None, True),
    # laguna's sliding layers: bfloat16 rows, no norm, the whole head
    "laguna-sliding": ((1, 48, 3, 128), parts.plain_frequencies(128, 1e4),
                       1.0, False, bf16, None, False),
    # and its full ones: half the head by YaRN's frequencies and factor
    "laguna-yarn": ((1, 48, 2, 128), YARN, 1.2079, False, bf16, None,
                    False),
    "laguna-yarn-k": ((2, 32, 1, 128), YARN, 1.2079, False, bf16, None,
                      False),
    # one head, one batch row, frequencies for a quarter of the head
    "one-head": ((1, 32, 1, 128), parts.plain_frequencies(32, 1e4), 1.0,
                 True, bf16, None, False),
    "normed-half-turn": ((2, 32, 2, 128), YARN, 1.2079, True, bf16, None,
                         True),
    # rows that are no multiple of the tile: the last tile hangs over
    "ragged-normed": ((2, 48, 2, 128), parts.plain_frequencies(128, 1e6),
                      1.0, True, bf16, (32, 64), False),
    "ragged-turn": ((1, 48, 2, 128), parts.plain_frequencies(64, 1e4), 1.0,
                    False, bf16, (32, 64), False),
    # and walked in chunks: the scale's gradient leaves the last chunks out
    "ragged-in-chunks": ((2, 80, 2, 128), parts.plain_frequencies(128, 1e6),
                         1.0, True, bf16, (64, 16), False),
    "several-tiles": ((1, 64, 2, 128), parts.plain_frequencies(128, 1e6),
                      1.0, True, bf16, (32, 16), True),
    "yarn-in-chunks": ((1, 64, 3, 128), YARN, 1.2079, False, bf16, (32, 16),
                       False),
    "float32-rows": ((1, 32, 2, 128), parts.plain_frequencies(128, 1e6),
                     1.0, True, f32, None, False),
}


def _operands(case, monkeypatch):
    shape, freqs, factor, normed, dtype, tile, halves = CASES[case]
    if tile is not None:
        monkeypatch.setattr(hnt, "ROWS", tile[0])
        monkeypatch.setattr(hnt, "CHUNK", tile[1])
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    y = (2.0 * jax.random.normal(ks[0], shape)).astype(dtype)
    ct = jax.random.normal(ks[1], shape).astype(dtype)
    scale = 1.0 + 0.3 * jax.random.normal(ks[2], shape[-1:]) if normed \
        else None
    L = shape[1]
    positions = jnp.arange(L, dtype=f32) % (L // 2) if halves else None
    return y, ct, scale, freqs, factor, positions


def _reference(y, scale, freqs, factor, positions):
    """``RMSNorm`` (its arithmetic, at a given scale) then ``rotary``."""
    if scale is not None:
        y = y.astype(f32)
        y = y * jax.lax.rsqrt(
            jnp.mean(jnp.square(y), -1, keepdims=True) + EPS) * scale
    return parts.rotary(y, freqs, factor, positions)


def _kernel(y, scale, freqs, factor, positions):
    assert hnt.supports(y.shape, len(freqs))
    cos, sin = parts.rotary_tables(y.shape[1], y.shape[-1], freqs, factor,
                                   positions)
    return hnt.head_norm_turn(y, cos, sin, len(freqs), scale,
                              None if scale is None else EPS)


def _ulps(got, want):
    """The largest distance in units of the last of ``want``'s eight
    bfloat16 places."""
    got, want = (np.asarray(a.astype(f32), np.float64) for a in (got, want))
    unit = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
    return float(np.max(np.abs(got - want) / unit))


@pytest.mark.parametrize("case", CASES)
def test_forward_is_the_references_to_one_unit_in_the_last_place(
        case, monkeypatch):
    y, _, scale, *turn = _operands(case, monkeypatch)
    got = _kernel(y, scale, *turn)
    want = _reference(y, scale, *turn).astype(y.dtype)
    assert got.dtype == y.dtype and got.shape == y.shape
    if y.dtype == f32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    else:
        assert _ulps(got, want) <= 1.0
        # and nearly everywhere not even that
        assert float(jnp.mean(got != want)) < 0.01


@pytest.mark.parametrize("case", CASES)
def test_derivative_is_the_float32_references(case, monkeypatch):
    y, ct, scale, *turn = _operands(case, monkeypatch)
    _, pull = jax.vjp(lambda y, s: _kernel(y, s, *turn), y, scale)
    _, want = jax.vjp(lambda y, s: _reference(y, s, *turn), y.astype(f32),
                      scale)
    tol = 1e-5 if y.dtype == f32 else 1e-2

    def gap(a, b):
        return float(jnp.linalg.norm(a.astype(f32) - b)
                     / jnp.linalg.norm(b))
    dy, dw = pull(ct)
    dy_ref, dw_ref = want(ct.astype(f32))
    assert dy.dtype == y.dtype
    assert gap(dy, dy_ref) < tol
    if scale is None:
        assert dw is None
    else:
        assert dw.dtype == f32 and gap(dw, dw_ref) < 1e-5


@pytest.mark.parametrize("shape,n,takes", [
    ((4, 8192, 32, 128), 64, True),     # sdar
    ((2, 8192, 48, 128), 64, True),     # laguna, sliding
    ((2, 8192, 64, 128), 32, True),     # laguna, full (YaRN on half)
    ((4, 8192, 32, 64), 32, False),     # lfm2: two heads a register
    ((2, 64, 4, 8), 4, False),          # the tiny presets' heads
    ((2, 64, 4, 16), 8, False),
    ((2, 64, 4, 32), 16, False),
    ((2, 64, 4, 256), 32, False),       # glm's and qwen's heads are
    ((2, 64, 4, 128), 0, False),        # other parts'; no turn at all
    ((2, 40, 4, 128), 64, False),       # half a register of bfloat16 rows
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_supports_reads_the_shape_alone(shape, n, takes):
    assert hnt.supports(shape, n) is takes


def _calls():
    return {k: obsmetrics.counter(f"attn.norm_turn_calls.{k}").value
            for k in ("pallas", "xla")}


def _attention(head_dim, **kw):
    return parts.GroupedAttention(
        64, 4, 2, head_dim, None, bf16, None, **kw)


@pytest.mark.parametrize("head_dim,kw,form", [
    (128, dict(qk_norm_eps=EPS, norm_heads=True,
               rotary_freqs=parts.plain_frequencies(128, 1e6),
               block_diffusion=4), "pallas"),                   # sdar
    (128, dict(rotary_freqs=YARN, rotary_factor=1.2079), "pallas"),
    (64, dict(qk_norm_eps=EPS, norm_heads=True,
              rotary_freqs=parts.plain_frequencies(64, 1e6)), "xla"),
    (16, dict(qk_norm_eps=EPS, norm_heads=True,
              rotary_freqs=parts.plain_frequencies(16, 1e6)), "xla"),
    (128, dict(), "xla"),                       # granite: nothing to make
    (128, dict(qk_norm_eps=EPS), "xla"),        # olmo: the whole projection
], ids=["sdar", "laguna", "lfm2", "tiny", "granite", "olmo"])
def test_the_layer_takes_the_form_its_shape_says_and_counts_it(
        head_dim, kw, form, monkeypatch):
    """``GroupedAttention`` end to end: q and k each count under the form
    they took, and the layer's output and gradients are those of the same
    module with ``supports`` patched to decline (XLA's form)."""
    module = _attention(head_dim, **kw)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64), bf16)
    params = module.init(jax.random.PRNGKey(1), x)
    if "qk_norm_eps" in kw:     # scales that are not all one
        params = jax.tree_util.tree_map_with_path(
            lambda p, a: a * jnp.linspace(0.5, 1.5, a.size).reshape(a.shape)
            if "norm" in jax.tree_util.keystr(p) else a, params)

    def run(p):
        return jax.value_and_grad(lambda p: jnp.sum(
            module.apply(p, x).astype(f32) ** 2))(p)
    before = _calls()
    got = run(params)
    after = _calls()
    assert {k: after[k] - before[k] for k in after} == {
        form: 2, "xla" if form == "pallas" else "pallas": 0}
    monkeypatch.setattr(hnt, "supports", lambda *a: False)
    want = run(params)
    assert _calls()["xla"] - after["xla"] == 2
    if form == "xla":
        jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)
        return
    np.testing.assert_allclose(got[0], want[0], rtol=5e-3)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree_util.tree_leaves(want[1])):
        assert float(jnp.linalg.norm(g - w)) \
            < 2e-2 * float(jnp.linalg.norm(w)), jax.tree_util.keystr(path)


@pytest.mark.parametrize("model,kw,forward,backward", [
    # a head's norm reads the projection's rows in its derivative: they are
    # what the block keeps, and q and k are made again from them
    ("sdar_moe_tiny", dict(heads=2, kv_heads=1, head_dim=128), 8, 4),
    # the turn alone is undone without them: the TURNED rows are kept and
    # no layer turns twice
    ("laguna_tiny", dict(head_dim=128), 8, 8),
], ids=["sdar", "laguna"])
def test_what_a_recomputed_block_keeps_of_q_and_k(model, kw, forward,
                                                  backward):
    from tests.test_glm4_moe_lite import _pallas_calls
    module = build_model(model, dtype=bf16, **kw)["module"]
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 64), 0, 64)
    params = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(1),
                                                tokens))
    calls = _pallas_calls(jax.make_jaxpr(jax.grad(lambda p: jnp.sum(
        module.apply(p, tokens, hidden=True)["hidden"].astype(f32))))(
            params).jaxpr)
    assert (calls.count("head_norm_turn_fwd"),
            calls.count("head_norm_turn_bwd")) == (forward, backward)


def test_a_two_layer_sdar_moe_with_heads_of_128_trains_as_with_xlas_form(
        monkeypatch):
    """``sdar_moe`` with bfloat16 rows and heads of 128, two layers under
    ``nn.remat``: the loss and every gradient with the kernel against the
    same module with ``supports`` patched to decline. Both round the same
    rows at the same places; what differs is a unit in the last place of
    some q and k elements, through two layers of bfloat16 products."""
    module = build_model(
        "sdar_moe_tiny", dim=64, heads=2, kv_heads=1, head_dim=128,
        dtype=bf16)["module"]
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 96)
    params = module.init(jax.random.PRNGKey(1), tokens)

    def run():
        return jax.jit(jax.value_and_grad(lambda p: jnp.mean(
            module.apply(p, tokens, hidden=True)["hidden"].astype(f32)
            ** 2)))(params)
    before = _calls()
    got = run()
    assert _calls()["pallas"] - before["pallas"] >= 4
    monkeypatch.setattr(hnt, "supports", lambda *a: False)
    want = run()
    np.testing.assert_allclose(got[0], want[0], rtol=1e-2)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree_util.tree_leaves(want[1])):
        assert float(jnp.linalg.norm(g.astype(f32) - w.astype(f32))) \
            <= 5e-2 * float(jnp.linalg.norm(w.astype(f32))) + 1e-12, \
            jax.tree_util.keystr(path)


def test_on_a_mesh_each_device_makes_its_own_rows_and_heads(monkeypatch):
    """Under ``with mesh:`` the call is shard_mapped over the batch and
    the tensor axis, the tables and the scale whole on every device, and
    the scale's gradient is summed over both; a batch the mesh does not
    divide keeps XLA's form."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "tensor"))
    y, ct, scale, *turn = _operands("sdar-q", monkeypatch)
    cos, sin = parts.rotary_tables(y.shape[1], 128, *turn)

    def run(fused):
        def f(y, scale):
            return jnp.sum(fused(y, cos, sin, scale, EPS).astype(f32)
                           * ct.astype(f32))
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(y, scale)
    want = run(parts._norm_turn(y.shape, 64))
    with mesh:
        fused = parts._norm_turn(y.shape, 64)
        rows = jax.device_put(y, NamedSharding(mesh, P("data")))
        lowered = jax.jit(lambda y: fused(y, cos, sin, scale, EPS)).lower(
            rows)
        got = run(fused)
        assert parts._norm_turn((3,) + y.shape[1:], 64) is None
    assert "all-gather" not in lowered.compile().as_text()
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    np.testing.assert_array_equal(got[1][0], want[1][0])
    np.testing.assert_allclose(got[1][1], want[1][1], rtol=1e-5)
