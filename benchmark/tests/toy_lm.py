"""Toy-size cell of the ``train_lm`` runner for the CPU rehearsals: the
real configuration, traffic and metric files with only sizes changed
(``toy.py`` has the stand-in device and ``run``)."""
from __future__ import annotations

import copy

from benchmark.harness import spec

GLM = dict(hidden_size=64, num_attention_heads=2, q_lora_rank=32,
           kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
           v_head_dim=32, intermediate_size=128, moe_intermediate_size=32,
           n_routed_experts=4, num_experts_per_tok=2, num_hidden_layers=3,
           vocab_size=128)


def train_lm_cell() -> spec.Cell:
    cell = copy.deepcopy(spec.load_cell("glm-4.7-flash-train-ep8share"))
    cell.config.update(GLM)
    cell.config["deployment"].update(n_routed_experts_published=8,
                                     experts_first=2)
    cell.config["program"]["loss_chunk"] = 16
    # bf16 against float32 at this size, on the CPU, over six seeds (those
    # of test_control_lm.py among them): sound runs read at most 9e-5
    # (losses), 0.020 and 0.005 (norm gaps), 0.0148 (the gradient's
    # relative difference), 0.008 of the choices flipped at a margin of
    # 0.0005; the fp8 control reads 0.086 to 0.088 on the gradient and
    # 0.026 to 0.057 of the choices flipped, at margins of 0.005 to 0.008
    cell.config["limits"] = {
        "loss_rel_gap": 0.002, "grad_norm_gap": 0.04,
        "grad_rel_diff": 0.035, "delta_norm_gap": 0.04,
        "routing_flip_share": 0.02, "routing_flip_margin": 0.002}
    cell.traffic.update(batch_per_chip=2, tokens_per_row=32,
                        resident_batches=4, segment_steps=4,
                        trace_seconds=1)
    return cell
