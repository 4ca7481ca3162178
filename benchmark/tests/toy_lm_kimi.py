"""Toy-size cell of ``kimi-linear-48b-a3b-train-ep32share-16k`` for the CPU
rehearsals: the real configuration, traffic and metric files with only
sizes changed (``toy.py`` has the stand-in device and ``run``). The two
layer lists (KDA, KDA, KDA, latent, KDA), the leading dense layer, keys
wider than values in the latent layer, the frozen gate and the share (4 of
8 experts, from the third on) are the cell's own; the delta rule's chunk is
cut with the row."""
from __future__ import annotations

import copy

from benchmark.harness import spec

CELL = "kimi-linear-48b-a3b-train-ep32share-16k"
KIMI = dict(hidden_size=64, num_attention_heads=2, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=96, moe_intermediate_size=16, num_experts=4,
            num_experts_per_token=2, vocab_size=128)

# bf16 against float32 at this size, on the CPU, over six seeds (those of
# test_control_lm_kimi.py among them): the readings are in that file's
# docstring
LIMITS = {"loss_rel_gap": 0.002, "grad_norm_gap": 0.05,
          "grad_rel_diff": 0.07, "delta_norm_gap": 0.02,
          "routing_flip_share": 0.02, "routing_flip_margin": 0.002}


def cell() -> spec.Cell:
    c = copy.deepcopy(spec.load_cell(CELL))
    c.config.update(KIMI)
    c.config["linear_attn_config"].update(num_heads=2, head_dim=16)
    c.config["deployment"].update(num_experts_published=8, experts_first=2)
    c.config["program"].update(loss_chunk=16, chunk=8)
    c.config["limits"] = dict(LIMITS)
    c.traffic.update(batch_per_chip=2, tokens_per_row=32,
                     resident_batches=4, segment_steps=4, trace_seconds=1)
    return c
