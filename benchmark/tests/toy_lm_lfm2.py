"""Toy-size cell of ``lfm2-24b-a2b-train-ep8share-8k`` for the CPU
rehearsals: the real configuration, traffic and metric files with only
sizes changed (``toy.py`` has the stand-in device and ``run``). The layer
list, the one leading dense layer, the frozen gate and the share (4 of 8
experts, from the third on) are the cell's own."""
from __future__ import annotations

import copy

from benchmark.harness import spec

CELL = "lfm2-24b-a2b-train-ep8share-8k"
LFM2 = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=96, moe_intermediate_size=32, num_experts=4,
            num_experts_per_tok=2, vocab_size=128)

# bf16 against float32 at this size, on the CPU, over six seeds (those of
# test_control_lm_lfm2.py among them): the readings are in that file's
# docstring
LIMITS = {"loss_rel_gap": 0.002, "grad_norm_gap": 0.05,
          "grad_rel_diff": 0.03, "delta_norm_gap": 0.02,
          "routing_flip_share": 0.008, "routing_flip_margin": 0.0015}


def cell() -> spec.Cell:
    c = copy.deepcopy(spec.load_cell(CELL))
    c.config.update(LFM2)
    c.config["deployment"].update(num_experts_published=8, experts_first=2)
    c.config["program"].update(loss_chunk=16)
    c.config["limits"] = dict(LIMITS)
    c.traffic.update(batch_per_chip=2, tokens_per_row=32,
                     resident_batches=4, segment_steps=4, trace_seconds=1)
    return c
