"""Toy-size cell of the ``train_lm_family`` runner for the CPU rehearsals:
the real configuration, traffic and metric files of
``qwen3-next-train-ep16share`` with only sizes changed (``toy.py`` has the
stand-in device and ``run``)."""
from __future__ import annotations

import copy

from benchmark.harness import spec

CELL = "qwen3-next-train-ep16share"
QWEN = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=32, linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=16,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            num_experts=4, num_experts_per_tok=2, num_hidden_layers=4,
            vocab_size=128)


def cell() -> spec.Cell:
    c = copy.deepcopy(spec.load_cell(CELL))
    c.config.update(QWEN)
    c.config["deployment"].update(num_experts_published=8, experts_first=2)
    c.config["program"].update(loss_chunk=16, chunk=8)
    # bf16 against float32 at this size, on the CPU, over six seeds (those
    # of test_control_lm_family.py among them): sound runs read at most
    # 8.4e-5 (losses), 0.018 and 0.0069 (norm gaps), 0.0159 (the
    # gradient's relative difference), 0.0059 of the choices flipped at a
    # margin of 0.00014; the fp8 control reads 0.186 to 0.191 on the
    # gradient, 0.064 to 0.144 and 0.016 to 0.027 on the norm gaps, and
    # 0.037 to 0.055 of the choices flipped, at margins of 0.0046 to 0.0096
    c.config["limits"] = {
        "loss_rel_gap": 0.002, "grad_norm_gap": 0.04,
        "grad_rel_diff": 0.05, "delta_norm_gap": 0.012,
        "routing_flip_share": 0.015, "routing_flip_margin": 0.001}
    c.traffic.update(batch_per_chip=2, tokens_per_row=32,
                     resident_batches=4, segment_steps=4, trace_seconds=1)
    return c
