"""Toy-size cell of ``keye-vl2-30b-a3b-train-ep8share-16k`` for the CPU
rehearsals: the real configuration, traffic and metric files with only
sizes changed (``toy.py`` has the stand-in device and ``run``). The four
equal layers, the indexer (two heads of 16 over one key head, the top 8 of
a row of 32: three quarters of its queries have more past than they may
keep), its own loss, the frozen gate and the share (4 of 8 experts, from
the third on) are the cell's own; the sectioned turn deals 8 frequencies to
its three streams 2, 3, 3."""
from __future__ import annotations

import copy

from benchmark.harness import spec

CELL = "keye-vl2-30b-a3b-train-ep8share-16k"
KEYE = dict(hidden_size=64, head_dim=16, num_attention_heads=4,
            num_key_value_heads=2, moe_intermediate_size=32, num_experts=4,
            num_local_experts=4, num_experts_per_tok=2, vocab_size=128)

# bf16 against float32 at this size, on the CPU, six seeds (those of
# test_control_lm_keye.py among them), the reference's step 0 following the
# program's selection as the runner has it. Sound runs read at most 0.0043
# (losses, whole), 0.0028 (main) and 0.015 (the indexer's part: 8 kept keys
# of 32 at bfloat16), 0.015 to 0.138 and 0.017 to 0.023 (norm gaps), 0.065
# to 0.116 (the gradient's relative difference), up to 0.016 of the
# routing's choices flipped at margins up to 0.0018, 0.011 to 0.027 of the
# selection's pairs flipped EITHER way (a pair the program keeps and the
# reference does not, or the other way round, over the pairs a query must
# keep; the count itself exact: selection_pairs_gap 0) at margins up to
# 0.0016; the indexer's leaves alone 0.047 to 0.26 on the gradient. (Each
# side choosing for itself, before the reference followed: 0.36 to 0.66 on
# the gradient, 0.031 to 0.070 on the routing: one flipped key of 8
# rewrites a query.) The fp8 control (three seeds) reads 0.185 to 0.223 on
# the selection's flips at margins of 0.027 to 0.048, 0.89 to 0.99 on the
# gradient (0.48 to 0.52 on the indexer's leaves), 0.098 to 0.121 on the
# routing's flips (five it must fail, and fails on every seed); the causal
# control (every causal key chosen) reads 1.316 on the selection's flips
# and on the count (176 pairs over the 136 a row must keep) at margins of
# 0.096 to 0.135, 0.175 to 0.212 on the whole loss and 0.58 to 0.69 on the
# indexer's part at steps 1 and 2, and NOTHING on step 0's loss and
# gradient: followed, its selection is the reference's own computation.
LIMITS = {"loss_rel_gap": 0.03, "loss_main_rel_gap": 0.03,
          "grad_norm_gap": 0.3,
          "grad_rel_diff": 0.3, "delta_norm_gap": 0.06,
          "routing_flip_share": 0.04, "routing_flip_margin": 0.007,
          "selection_flip_share": 0.07, "selection_flip_margin": 0.007,
          "selection_pairs_gap": 0, "grad_rel_diff_indexer": 0.35}


def cell() -> spec.Cell:
    c = copy.deepcopy(spec.load_cell(CELL))
    c.config.update(KEYE)
    c.config["sa_config"].update(indexer_head_dim=16, indexer_num_heads=2,
                                 topk=8)
    c.config["rope_scaling"].update(mrope_section=[2, 3, 3])
    c.config["deployment"].update(num_experts_published=8, experts_first=2)
    c.config["program"].update(loss_chunk=16)
    c.config["limits"] = dict(LIMITS)
    c.traffic.update(batch_per_chip=2, tokens_per_row=32,
                     resident_batches=4, segment_steps=4, trace_seconds=1)
    return c
