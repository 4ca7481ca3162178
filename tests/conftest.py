"""Test env: force CPU with 8 virtual devices BEFORE jax is imported.

This is the TPU-translation of the reference's `local[*]` SparkSession fixture
(``core/test/base/src/main/scala/TestBase.scala:26-155``): multi-chip behavior
made testable on one box via a fake device mesh.

The REAL-accelerator lane (`./tools/runme testtpu`, the reference's
LinuxOnly native-suite idea) sets ``MMLSPARK_TEST_TPU=1`` to keep the
ambient backend (the attached TPU chip) and runs only ``-m tpu`` smoke
tests against it.
"""
import os

TPU_LANE = os.environ.get("MMLSPARK_TEST_TPU") == "1"

if not TPU_LANE:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    # tests own their compile caches (tmp_path): a directory inherited
    # from the environment would outrank every per-test one
    # (compile_cache.cache_dir) and carry entries between tests
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    os.environ.pop("MMLSPARK_TPU_RUNTIME_COMPILE_CACHE_DIR", None)

import jax  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_sessionstart(session):
    if TPU_LANE:
        # the env var is only meaningful paired with the -m tpu lane; a
        # full suite on the ambient backend would fail confusingly at
        # every mesh-shape assumption, so refuse up front
        marker = (session.config.getoption("-m") or "").strip()
        # the expression must imply the tpu mark: it selects a plain
        # tpu-marked item AND rejects an item carrying every mark BUT tpu
        try:
            from _pytest.mark.expression import Expression
            expr = Expression.compile(marker)
            selects_tpu = (expr.evaluate(lambda name: name == "tpu")
                           and not expr.evaluate(lambda name: name != "tpu"))
        except Exception:
            import re
            selects_tpu = ("tpu" in re.findall(r"\w+", marker)
                           and "not tpu" not in marker and "or" not in marker)
        assert selects_tpu, (
            "MMLSPARK_TEST_TPU=1 runs the real-accelerator smoke lane "
            "only: add -m tpu (or use ./tools/runme testtpu), or unset "
            "the variable for the virtual-CPU-mesh suite")
        # a chip lane that finds no chip is a failure, not a wall of skips
        assert jax.default_backend() == "tpu", (
            f"MMLSPARK_TEST_TPU=1 but jax initialized "
            f"{jax.default_backend()!r}: no TPU reachable from this process")
        return
    assert jax.default_backend() == "cpu"
    assert jax.device_count() == 8, (
        f"expected 8 virtual CPU devices, got {jax.device_count()}")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def make_basic_frame():
    """Tiny inline frame, counterpart of the reference's makeBasicDF
    (TestBase.scala:126-137)."""
    from mmlspark_tpu import Frame
    return Frame.from_dict({
        "numbers": [0, 1, 2, 3],
        "words": ["guitars", "drums", "bass", "keys"],
        "more": ["apples", "oranges", "grapes", "pears"],
        "values": [1.5, 2.5, 3.5, 4.5],
    })


@pytest.fixture
def basic_frame():
    return make_basic_frame()
