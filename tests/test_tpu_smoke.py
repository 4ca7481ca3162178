"""Real-accelerator smoke suite (`pytest -m tpu`, via `./tools/runme
testtpu` which sets MMLSPARK_TEST_TPU=1 so conftest keeps the ambient
backend).

The reference gated its native-dependent suites behind LinuxOnly
(``CNTKModelSuite.scala:19``); the analogue here is a small lane that runs
the judged paths on the REAL chip — JaxModel scoring against the committed
golden activations, one DeepClassifier fit, and the Pallas kernels compiled
by Mosaic rather than the CPU interpreter — catching backend-specific
regressions the virtual CPU mesh cannot.
"""
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402

pytestmark = [
    pytest.mark.tpu,
    pytest.mark.skipif(jax.default_backend() == "cpu",
                       reason="needs a real accelerator backend "
                              "(run via ./tools/runme testtpu)"),
]

FIXTURES = os.path.join(os.path.dirname(__file__), "data", "pretrained")


def test_pretrained_scoring_matches_cpu_golden():
    """Backend parity: the committed golden activations were computed on
    CPU; the chip must reproduce them through the full downloader +
    featurizer path (fused uint8 wire + device resize + normalization)."""
    import tempfile
    from mmlspark_tpu.core.frame import Frame
    from mmlspark_tpu.core.schema import ColumnSchema, DType, ImageValue
    from mmlspark_tpu.image.featurizer import ImageFeaturizer
    from mmlspark_tpu.models.convert import (
        from_flax_msgpack, import_pretrained,
    )
    from mmlspark_tpu.models.downloader import LocalRepo, ModelDownloader

    g = np.load(os.path.join(FIXTURES, "golden.npz"))
    repo = LocalRepo(tempfile.mkdtemp())
    import_pretrained(
        repo, "resnet20-synthetic", "resnet20_cifar",
        from_flax_msgpack(os.path.join(FIXTURES,
                                       "resnet20_synthetic.msgpack")),
        input_mean=[127.5], input_std=[127.5], num_classes=4)

    imgs = np.empty(len(g["images"]), dtype=object)
    for i, im in enumerate(g["images"]):
        imgs[i] = ImageValue(path=f"mem://{i}", data=np.ascontiguousarray(im))
    frame = Frame.from_dict({"i": np.arange(len(imgs))})
    frame = frame.with_column_values(ColumnSchema("image", DType.IMAGE), imgs)

    fz = ImageFeaturizer(inputCol="image", outputCol="features",
                         cutOutputLayers=1, miniBatchSize=8)
    fz.set_model_from_downloader(ModelDownloader(repo), "resnet20-synthetic")
    feats = np.asarray(fz.transform(frame).column("features"))
    np.testing.assert_allclose(feats, g["pool"], rtol=5e-2, atol=5e-2)


def test_deep_classifier_one_epoch_on_chip():
    from mmlspark_tpu.core.frame import Frame
    from mmlspark_tpu.train.deep import DeepClassifier

    rng = np.random.default_rng(0)
    X = rng.normal(size=(256, 8)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.int64)
    frame = Frame.from_dict({"features": X, "label": y})
    learner = DeepClassifier(architecture="mlp_tabular",
                             architectureArgs={"hidden": [16]},
                             batchSize=64, epochs=3, learningRate=1e-2)
    learner.set_params(featuresCol="features", labelCol="label")
    model = learner.fit(frame)
    assert np.isfinite(float(model._state["final_loss"]))
    pred = np.asarray(model.transform(frame).column("prediction"))
    assert (pred == y).mean() > 0.8


def test_compute_dtype_bf16_scoring_on_chip():
    """computeDtype='bfloat16' on the real MXU: embeddings must stay close
    to the fp32 path and the column must emit float32 (the bf16 wire is an
    implementation detail the user never sees)."""
    from mmlspark_tpu.core.frame import Frame
    from mmlspark_tpu.models.jax_model import JaxModel

    rng = np.random.default_rng(7)
    f = Frame.from_dict(
        {"img": rng.normal(0, 1, (32, 32 * 32 * 3)).astype(np.float32)},
        num_partitions=2)
    outs = {}
    for cdt in ("float32", "bfloat16"):
        m = JaxModel(inputCol="img", outputCol="o", miniBatchSize=16,
                     computeDtype=cdt)
        m.set_model("resnet20_cifar", num_classes=10, seed=0)
        col = np.asarray(m.transform(f).column("o"))
        assert col.dtype == np.float32
        outs[cdt] = col
    scale = np.abs(outs["float32"]).max()
    np.testing.assert_allclose(outs["bfloat16"], outs["float32"],
                               atol=0.05 * scale)


def test_pallas_fused_normalize_matches_numpy():
    """The REAL Mosaic-compiled kernel (interpret=False off-CPU) must match
    the numpy reference bit-tight."""
    from mmlspark_tpu.ops.pallas_preprocess import make_preprocess_fn

    rng = np.random.default_rng(1)
    shape = (16, 16, 3)
    n = int(np.prod(shape))
    u8 = rng.integers(0, 256, size=(12, n), dtype=np.uint8)
    mean, std = (125.3, 123.0, 113.9), (63.0, 62.1, 66.7)
    pre = make_preprocess_fn(shape, mean=mean, std=std, out_dtype=np.float32)
    got = np.asarray(jax.jit(pre)(u8))
    want = ((u8.reshape((-1,) + shape).astype(np.float32)
             - np.asarray(mean, np.float32))
            / np.asarray(std, np.float32))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_pallas_fused_crop_resize_normalize_compiles_under_mosaic():
    """The single-kernel crop+resize+normalize (two MXU matmuls + VPU
    requantize/normalize) must compile under Mosaic on the real chip and
    match the host ops pipeline to one uint8 quantum."""
    from mmlspark_tpu.image import ops
    from mmlspark_tpu.ops.pallas_preprocess import make_fused_preprocess_fn
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    B, HS, WS, C = 8, 64, 64, 3
    u8 = rng.integers(0, 256, (B, HS, WS, C), dtype=np.uint8)
    mean, std = (125.3, 123.0, 113.9), (63.0, 62.1, 66.7)
    host = np.stack([
        (ops.resize(ops.center_crop(im, 56, 56), 32, 32).astype(np.float32)
         - mean) / std
        for im in u8])
    pre = make_fused_preprocess_fn((HS, WS, C), resize=(32, 32),
                                   crop=(56, 56), mean=mean, std=std)
    got = np.asarray(pre(jnp.asarray(u8.reshape(B, -1))))
    inner = (slice(None), slice(1, -1), slice(1, -1))
    np.testing.assert_allclose(got[inner], host[inner], atol=1.01 / 62.0)


@pytest.mark.parametrize("shape,dtype", [
    ((2, 512, 4, 64), "float32"),
    ((4, 8192, 32, 64), "bfloat16"),        # lfm2-24b-a2b-train-ep8share-8k
    ((1, 8192, 30, 128), "bfloat16"),       # olmo-hybrid-7b-train-8k
    ((2, 4096, 20, 256), "bfloat16"),       # glm-4.7-flash-train-ep8share
], ids=["small-f32", "lfm2", "olmo", "glm"])
def test_pallas_flash_attention_compiles_under_mosaic(shape, dtype):
    """The fused flash-attention kernel must compile under Mosaic on the
    real chip, at the shapes the benchmark's cells call it with, and match
    the jnp reference path (on the first row's first two heads where the
    whole call's L x L scores would not fit)."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops.pallas_attention import flash_attention
    from mmlspark_tpu.parallel.sequence import full_attention

    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(0, 1, shape).astype(np.float32)
                           ).astype(dtype) for _ in range(3))
    part = (slice(0, 1), slice(None), slice(0, 2))
    for causal in (False, True):
        got = np.asarray(jax.device_get(
            flash_attention(q, k, v, causal=causal)[part]), np.float32)
        ref = np.asarray(jax.device_get(full_attention(
            q[part], k[part], v[part], causal, use_flash="never")),
            np.float32)
        assert chip_smoke._rel_err(got, ref) <= chip_smoke.BF16_REL_TOL


@pytest.mark.parametrize("kernel_check", chip_smoke.KERNEL_CHECKS,
                         ids=lambda f: f.__name__)
def test_pallas_kernels_at_bench_width(kernel_check):
    """The shapes ``bench.py`` runs — fused_normalize at (128, 224*224*3)
    and (256, 32*32*3), the 256->224 fused crop at batch 32, causal flash
    at B1 L8192 H8 D64 bf16 forward and backward plus the fp32 case on the
    edge of ``supports`` — through ``chip_smoke``'s own checks: numerics
    against the reference AND a Mosaic call in the lowered program."""
    recorded = {}
    kernel_check(chip_smoke.FULL, False,
                 lambda name, *facts, **_: recorded.setdefault(name, facts))
    # the sharded check has nothing to do on a one-chip host
    assert recorded or kernel_check is chip_smoke.kernel_flash_sharded


def test_device_resize_matches_host_within_one_gray_level():
    from mmlspark_tpu.image import ops
    from mmlspark_tpu.ops.pallas_preprocess import device_resize_bilinear
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    u8 = rng.integers(0, 256, size=(4, 40, 24, 3), dtype=np.uint8)
    host = np.stack([ops.resize(im, 16, 16) for im in u8]).astype(int)
    dev = np.asarray(jnp.clip(jnp.round(device_resize_bilinear(
        jnp.asarray(u8, jnp.float32), 16, 16)), 0, 255)).astype(int)
    assert np.abs(host - dev).max() <= 1
