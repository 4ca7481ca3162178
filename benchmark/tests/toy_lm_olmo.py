"""Toy-size cell of ``olmo-hybrid-7b-train-8k`` for the CPU rehearsals:
the real configuration, traffic and metric files with only sizes changed
(``toy.py`` has the stand-in device and ``run``). The head widths keep the
published 1 : 2 ratio and stay under what the chunk kernels take, so the
toy walks XLA's batched form as every toy does."""
from __future__ import annotations

import copy

from benchmark.harness import spec

CELL = "olmo-hybrid-7b-train-8k"
OLMO = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            linear_num_key_heads=4, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=16,
            intermediate_size=96, num_hidden_layers=4,
            layer_types=["linear_attention", "linear_attention",
                         "linear_attention", "full_attention"],
            vocab_size=128)


# bf16 against float32 at this size, on the CPU, over six seeds (those of
# test_control_lm_olmo.py among them): the readings are in that file's
# docstring
LIMITS = {"loss_rel_gap": 0.002, "grad_norm_gap": 0.04,
          "grad_rel_diff": 0.05, "delta_norm_gap": 0.06}


def cell() -> spec.Cell:
    c = copy.deepcopy(spec.load_cell(CELL))
    c.config.update(OLMO)
    c.config["program"].update(loss_chunk=16, chunk=8)
    c.config["limits"] = dict(LIMITS)
    c.traffic.update(batch_per_chip=2, tokens_per_row=32,
                     resident_batches=4, segment_steps=4, trace_seconds=1)
    return c
