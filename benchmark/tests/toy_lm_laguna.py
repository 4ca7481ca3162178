"""Toy-size cell of ``laguna-xs.2-train-ep8share-8k`` for the CPU
rehearsals: the real configuration, traffic and metric files with only
sizes changed (``toy.py`` has the stand-in device and ``run``). The three
lists (kinds of mixer, heads a layer, kinds of feed-forward part), both
rotary rules, the frozen gate and the share (4 of 8 experts, from the
third on) are the cell's own; the window is cut with the row, so that the
band still ends inside it."""
from __future__ import annotations

import copy

from benchmark.harness import spec

CELL = "laguna-xs.2-train-ep8share-8k"
LAGUNA = dict(hidden_size=64, head_dim=16, num_key_value_heads=2,
              num_attention_heads=2,
              num_attention_heads_per_layer=[2, 4, 4, 4, 2],
              sliding_window=8, intermediate_size=96,
              moe_intermediate_size=16, shared_expert_intermediate_size=16,
              num_experts=4, num_experts_per_tok=2, vocab_size=128)

# bf16 against float32 at this size, on the CPU, over six seeds (those of
# test_control_lm_laguna.py among them): the readings are in that file's
# docstring
LIMITS = {"loss_rel_gap": 0.002, "grad_norm_gap": 0.05,
          "grad_rel_diff": 0.045, "delta_norm_gap": 0.02,
          "routing_flip_share": 0.013, "routing_flip_margin": 0.02}


def cell() -> spec.Cell:
    c = copy.deepcopy(spec.load_cell(CELL))
    c.config.update(LAGUNA)
    c.config["deployment"].update(num_experts_published=8, experts_first=2)
    c.config["program"].update(loss_chunk=16)
    c.config["limits"] = dict(LIMITS)
    c.traffic.update(batch_per_chip=2, tokens_per_row=32,
                     resident_batches=4, segment_steps=4, trace_seconds=1)
    return c
