"""Toy-size cell of ``sdar-30b-a3b-train-ep8share-4k`` for the CPU
rehearsals: the real configuration, traffic and metric files with only
sizes changed (``toy.py`` has the stand-in device and ``run``). The four
equal layers, the block length, the noise, the frozen gate and the share (4
of 8 experts, from the third on) are the cell's own."""
from __future__ import annotations

import copy

from benchmark.harness import spec

CELL = "sdar-30b-a3b-train-ep8share-4k"
SDAR = dict(hidden_size=64, head_dim=16, num_attention_heads=4,
            num_key_value_heads=2, moe_intermediate_size=32, num_experts=4,
            num_experts_per_tok=2, vocab_size=128)

# bf16 against float32 at this size, on the CPU, over six seeds (those of
# test_control_lm_sdar.py among them): the readings are in that file's
# docstring
LIMITS = {"loss_rel_gap": 0.002, "grad_norm_gap": 0.3,
          "grad_rel_diff": 0.45, "delta_norm_gap": 0.025,
          "routing_flip_share": 0.045, "routing_flip_margin": 0.007}


def cell() -> spec.Cell:
    c = copy.deepcopy(spec.load_cell(CELL))
    c.config.update(SDAR)
    c.config["deployment"].update(num_experts_published=8, experts_first=2)
    c.config["program"].update(loss_chunk=16)
    c.config["limits"] = dict(LIMITS)
    c.traffic.update(batch_per_chip=2, tokens_per_row=32,
                     resident_batches=4, segment_steps=4, trace_seconds=1)
    return c
