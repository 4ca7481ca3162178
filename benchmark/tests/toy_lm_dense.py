"""Toy-size cell of the ``train_lm_dense`` runner for the CPU rehearsals:
the real configuration, traffic and metric files of
``granite-4.0-h-micro-train-8k`` with only sizes changed (``toy.py`` has
the stand-in device and ``run``)."""
from __future__ import annotations

import copy

from benchmark.harness import spec

CELL = "granite-4.0-h-micro-train-8k"
GRANITE = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
               shared_intermediate_size=96, num_hidden_layers=4,
               layer_types=["mamba", "mamba", "attention", "mamba"],
               attention_multiplier=0.125, vocab_size=128)


# bf16 against float32 at this size, on the CPU, over six seeds (those of
# test_control_lm_dense.py among them): sound runs read at most 4.9e-6
# (losses), 0.0043 and 0.0063 (norm gaps) and 0.0061 (the gradient's
# relative difference); the fp8 control reads 0.030 to 0.031 on the
# gradient, 0.0086 to 0.024 and 0.028 to 0.048 on the norm gaps and 4e-6
# to 5e-5 on the losses (which precision hardly moves)
LIMITS = {"loss_rel_gap": 0.002, "grad_norm_gap": 0.04,
          "grad_rel_diff": 0.014, "delta_norm_gap": 0.014}


def cell() -> spec.Cell:
    c = copy.deepcopy(spec.load_cell(CELL))
    c.config.update(GRANITE)
    c.config["program"].update(loss_chunk=16, chunk=8)
    c.config["limits"] = dict(LIMITS)
    c.traffic.update(batch_per_chip=2, tokens_per_row=32,
                     resident_batches=4, segment_steps=4, trace_seconds=1)
    return c
