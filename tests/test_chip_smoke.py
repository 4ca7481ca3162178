"""chip_smoke.py, rehearsed on the CPU (tier-1).

The real run needs the chip and goes through the chip tool; what can be
held here is everything that is not a device number: every leg runs end to
end at toy sizes (same entry points, same checks, kernels in the Pallas
interpreter), the script refuses any backend but a TPU, and the compile
cache goes where ``JAX_COMPILATION_CACHE_DIR`` says.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402

from mmlspark_tpu import compile_cache  # noqa: E402
from mmlspark_tpu.observability import metrics  # noqa: E402
from mmlspark_tpu.utils import config  # noqa: E402

# toy stand-ins for every full-width shape; the flash shapes are the
# smallest the kernel supports (two 256-row query blocks)
TOY = chip_smoke.Sizes(
    train_model="vit_tiny",
    train_model_args=(("num_classes", 10), ("image_size", 32)),
    train_image=32, train_batch=16, train_rows=32, train_steps=3,
    score_model="resnet20_cifar", score_model_args=(("num_classes", 10),),
    score_max_batch=8, score_requests=(1, 3, 7),
    lm_model="transformer_lm_tiny", lm_max_seq_len=64, lm_block_tokens=8,
    lm_prompts=(3, 9, 17, 30), lm_new_tokens=8,
    normalize=((12, (16, 16, 3)),), crop=(2, 32, 24),
    flash_bf16=(1, 512, 2, 16), flash_bf16_d256=(2, 512, 2, 32),
    flash_fp32=(1, 512, 1, 16),
    short_bf16=(2, 37, 3, 16), gated_delta=(2, 40, 3, 16),
    gated_delta_chunk=16, gated_delta_wide=(1, 40, 3, 8, 16),
    head_norm_turn=(((2, 48, 2, 128), 64, True), ((1, 48, 2, 128), 16, False)))


@pytest.fixture(autouse=True)
def _clean_slate():
    metrics.get_registry().reset()
    config.unset("runtime.compile_cache_dir")
    yield
    metrics.get_registry().reset()
    config.unset("runtime.compile_cache_dir")


_DRIVER = """
import json, sys
sys.path[:0] = [{repo!r}, {tests!r}]
import chip_smoke
from test_chip_smoke import TOY
chip_smoke.emit(chip_smoke.run(TOY, rehearsal=True))
"""


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One rehearsal for the module, in its OWN process: the smoke turns
    on jax's persistent compile cache, which jax binds to one directory
    for the life of a process — left bound in the test process, every
    later test would compile through it. The cache is placed from outside
    (JAX_COMPILATION_CACHE_DIR) the way the driver may."""
    cache = tmp_path_factory.mktemp("placed_from_outside")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    code = _DRIVER.format(repo=str(REPO), tests=str(REPO / "tests"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=str(REPO))
    assert proc.returncode == 0, proc.stderr[-4000:]
    report, verdict = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    # the LAST line is what the driver parses, strictly: these keys only
    assert verdict == {"ok": True, "device": report["device"]}
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    return report, cache


def test_every_leg_runs_at_toy_size_on_the_cpu(rehearsal):
    result, _ = rehearsal
    assert result["ok"] is True
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    assert set(result["versions"]) >= {"jax", "jaxlib", "libtpu"}

    legs = result["legs"]
    assert set(legs) == {"trainer", "server", "kernels"}
    for leg in legs.values():
        assert leg["compile_s"] >= 0 and leg["steady_s"] >= 0

    t = legs["trainer"]
    assert t["devices"] == 8 and t["batch_rows_per_device"] == 2
    assert t["sync_points_per_step"] == 0 and t["steady_compiles"] == 0

    s = legs["server"]
    assert "device 0 only" in s["placement"]
    assert s["score"]["buckets"] == [1, 4, 8]
    assert s["score"]["steady_compiles"] == 0
    assert s["generate"]["steady_compiles"] == 0
    assert s["generate"]["token_agreement"] > 0.9   # fp32 model on the CPU
    # the serve leg went through the AOT seam: a cold cache stores programs
    assert s["aot_cache"]["stores"] > 0 and s["aot_cache"]["misses"] > 0

    k = legs["kernels"]
    assert k["mosaic_lowering_proven"] is False     # interpreted here
    assert {"flash_fwd_bf16", "flash_fwd_fp32", "flash_bwd_bf16",
            "flash_bwd_bf16_d256", "gated_delta_bf16",
            "gated_delta_bf16_key_heads", "gated_delta_bf16_wide",
            "short_fwd_bwd_bf16", "head_norm_turn_d128_n64_normed",
            "head_norm_turn_d128_n16",
            "flash_fwd_bf16_sharded_x8"} <= set(k["kernels"])
    # head width 16, chunk 16: not the Pallas calls' shape
    assert k["kernels"]["gated_delta_bf16"]["path"] == "xla"
    assert k["kernels"]["gated_delta_bf16_key_heads"]["key_heads"] == 1
    assert k["kernels"]["gated_delta_bf16_wide"]["path"] == "xla"
    assert k["kernels"]["gated_delta_bf16_wide"]["shape"] == [1, 40, 3, 16]


def test_smoke_writes_only_where_the_environment_placed_the_cache(rehearsal):
    result, cache = rehearsal
    assert result["cache"]["dir"] == str(cache)
    assert result["cache"]["from_env"] is True
    assert result["cache"]["xla_misses"] > 0        # jax's own cache, there
    assert any(cache.iterdir())
    assert (cache / "aot").is_dir()                 # and the AOT entries
    assert not (REPO / ".jax_cache").exists()


def test_script_refuses_a_backend_that_is_not_a_tpu():
    """``python chip_smoke.py`` takes no switches; on a host whose jax
    comes up on the CPU it must exit non-zero, say which backend it found,
    and print no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300, cwd=str(REPO))
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_script_alone_without_the_package_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo there is no program to drive: non-zero, no result."""
    (tmp_path / "chip_smoke.py").write_bytes(
        (REPO / "chip_smoke.py").read_bytes())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_cache_goes_where_the_environment_says(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, the smoke's default directory
    is ignored and nothing updates jax's cache dir in code."""
    import jax

    env_dir = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (updates.append(name), real_update(name, val)))
    default = str(tmp_path / "checkout" / ".jax_cache")
    assert compile_cache.enable(default) == env_dir
    assert compile_cache.cache_dir() == env_dir
    assert "jax_compilation_cache_dir" not in updates
    assert not os.path.exists(default)
