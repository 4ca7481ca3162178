"""Toy-size cells for the CPU rehearsals: the real configuration, traffic
and metric files with only sizes changed, and a stand-in device whose
peaks are the v5e's (so that the readers' arithmetic runs)."""
from __future__ import annotations

import copy
import os
from typing import Any, Dict

from benchmark.harness import spec

VIT = dict(patch_size=4, hidden_size=192, mlp_dim=768, num_heads=3,
           num_layers=4, image_size=32, num_classes=10)


def device() -> Dict[str, Any]:
    import jax
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": 1,
            "devices": jax.devices()[:1],
            "peaks": {"bf16_tflops": 197.0, "hbm_gbps": 819.0,
                      "hbm_gb": 16.0}}


def train_cell() -> spec.Cell:
    cell = copy.deepcopy(spec.load_cell("vit-b16-train"))
    cell.config.update(VIT)
    cell.config["program"] = {"zoo": "vit_tiny",
                              "zoo_args": {"num_classes": 10},
                              "pixel_mean_std": 127.5}
    # bf16 against float32 at this size, on the CPU: sound runs read 1e-3
    # (loss), 7e-3 and 1e-2 (norm gaps) and 0.018 (the gradient's relative
    # difference); the fp8 control reads 0.09 to 0.12 on the last
    cell.config["limits"] = {"loss_rel_gap": 0.01, "grad_norm_gap": 0.03,
                             "grad_rel_diff": 0.045,
                             "delta_norm_gap": 0.3}
    cell.traffic.update(batch_per_chip=8, resident_batches=4,
                        segment_steps=4, reference_block_rows=4,
                        trace_seconds=1)
    return cell


def run(cell: spec.Cell, tmp_path, *, seed: int = 2 ** 31 + 77,
        seconds: float = 2.0, traced: bool = False) -> Dict[str, Any]:
    import time
    from benchmark.harness import main
    return main.run_cell(cell, seed=seed, seconds=seconds, traced=traced,
                         device=device(), process_start=time.perf_counter(),
                         trace_dir=os.path.join(str(tmp_path), "trace"))
