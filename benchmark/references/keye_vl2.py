"""Plain reference: Keye-VL-2.0-30B-A3B's language model (``keye_vl2``),
causal next-token training with DeepSeek Sparse Attention's second loss,
float32.

Written from the published ``config.json`` (huggingface.co/Kwai-Keye/
Keye-VL-2.0-30B-A3B, ``model_type: KeyeVL2``: the language model's keys and
``sa_config``), DeepSeek-V3.2-Exp's report (arXiv:2512.02556) and its
published ``inference/model.py`` ``Indexer`` in straightforward
``jax.numpy``: no kernels, no flax, nothing imported from the program (what
belongs to no family comes from the sibling references: the plain-scale
norm, seeded weights by shape, per-leaf norms, the half-split rotary turn,
the softmax-routed layer over the held experts, the control's rounding).
Pre-norm decoder, no biases but the indexer's LayerNorm's, every RMSNorm
with a plain scale. With ``h`` the attention half's normed input ``(L,
2048)``:

- main projections: ``q = turn(norm(h W_q))`` on 32 heads, ``k =
  turn(norm(h W_k))``, ``v = h W_v`` on 4, of 128; an RMS norm over EACH
  head's 128 channels of q and of k (one scale of 128 shared by the
  heads); the turn is ``mrope``: the published ``mrope_section`` ``[16, 24,
  24]`` deals the 64 frequencies of a head to three position streams (time,
  height, width); on token ids the three are the token's index, and the
  sectioned turn IS the plain one (``tests/test_keye_vl2.py`` holds the two
  equal);
- indexer, on ``x = stop_gradient(h)``: ``qI = turn_I(x W_qI)`` (16 heads
  of 64), ``kI = turn_I(LayerNorm(x W_kI))`` (one head of 64; scale and
  bias, eps 1e-6), ``w = (x W_w) 16^-1/2 64^-1/2``; ``I[t, s] = sum_j w[t,
  j] relu(qI[t, j] . kI[s])`` for ``s <= t``; ``turn_I`` the plain turn
  over all 64 channels at the model's theta;
- selection: ``S_t`` = the ``min(2048, t + 1)`` largest ``I[t, s]`` over
  ``s <= t`` by ``lax.top_k`` (a tie to the lower ``s``; the two zeros one
  score); no gradient passes through it;
- core: ``o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, h //
  8] 128^-1/2) v[s, h // 8]``, a masked dense softmax one query head at a
  time, ``QUERY_BLOCK`` queries at a time; through ``W_o``;
- the indexer's loss, a layer: ``p[t, s] = stop_gradient(mean_h
  softmax_{S_t}(...)[h, s])``, ``L_I = (1 / L) sum_t sum_{s in S_t} p (log
  p - log softmax_{S_t}(I[t, .])[s])``, terms with ``p = 0`` counting 0;
- feed-forward half: ``sdar_moe``'s, by import (softmax over all 128, top
  8 over their sum, the 16 experts held here in a dense loop, no shared
  expert, the gate without gradient where the configuration says so);
- a final norm; ``loss = L_LM + sum over layers of L_I``, ``L_LM`` the mean
  next-token cross-entropy over the rows that have a target.

By construction ``W_qI``, ``W_kI``, the LayerNorm and ``W_w`` take gradient
from ``L_I`` alone and every other leaf from ``L_LM`` alone.

``quant`` rounds both operands of every matrix multiplication through a
lower precision (``kimi_linear._fp8``: written out in float32 arithmetic),
and ``mask="causal"`` leaves the selection out (every causal key is
chosen): the two controls that the comparison deciding ``correct`` has to
fail. ``observe`` (the layers' ``(L, L)`` bool selections, ``[t, s]``, as
bits: ``pack``) is another selection to hold this one's against: per layer
the pairs on which the two differ, EITHER way (one it keeps and this
reference does not, one this reference keeps and it does not: a subset of
the right keys has none of the first kind), over the ``min(k, t + 1)`` a
query must keep; how far from this reference's last chosen score the worst
of them lay; and how many pairs it keeps beside how many it must. With
``follow`` the reference then RUNS over that selection, for the FIRST
GRADIENT's sake (step 0's loss and routing follow with it; steps 1 and 2
choose for themselves, and their losses are what holds a program's
selection to this reference's beside the flips and the count). It has to,
for the gradient to mean anything: with seeded weights an indexer's
ranking says nothing of a key's weight in the main softmax, so a key that
two precisions rank on either side of the 2,048th is as likely as any to
be the one a query attends to, and ONE such flip rewrites that query's
output. On the chip,
each side choosing for itself, 0.3% of layer 0's pairs flipped (margins of
0.02: rounding) and from there 6%, 24% and 45% of the next layers' at
margins of 1.8 to 4.0, 55% of the last layer's routed choices with them,
and a first gradient 1.2 times its own norm away, though every loss agreed
to 4e-4 (PERF.md section 6, PR 53, call A). A trained indexer ranks by that
weight, and its near-ties are keys that hardly matter. Following takes the
discrete choice out of what is compared after it, and holds the choice
itself, layer by layer at the same state, to the flips and their margin.

**What is read of the configuration file**: ``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``moe_intermediate_size``, ``num_experts`` (HELD here),
``deployment.num_experts_published`` / ``.experts_first``,
``num_experts_per_tok``, ``norm_topk_prob`` (must be true), ``rope_theta``,
``rope_scaling.mrope_section``, ``rms_norm_eps``, ``vocab_size``,
``sa_config`` (``indexer_num_heads``, ``indexer_head_dim``, ``topk``;
``indexer_num_kv_heads`` must be 1) and
``program.zoo_args`` (``gate_grad``); the runner reads ``program.zoo`` /
``.loss_chunk``, ``optimizer`` and ``limits``.

Also here, because the benchmark keeps them: what the runner asks a family
for (``zoo_args``, ``routed_blocks``, ``selecting_blocks``,
``leaf_names``, ``leaf_class``, ``kernel_calls``, ``LOSS_PARTS``,
``AUX``), and the operations and bytes the new calls need
(``selected_fwd_cost`` / ``_bwd_cost``, ``select_cost``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from benchmark.references.glm47_flash import (  # noqa: F401
    _is_shape, _rms, leaf_norms)
from benchmark.references.kimi_linear import _products
from benchmark.references.sdar_moe import (
    QK_NORM_SCALE_INIT, _experts, _rotary_at)

INIT_STD = 0.02
QUERY_BLOCK = 256       # queries whose (H_I, L) scores are alive at once
ROW_BLOCK = 2048        # rows of logits alive at once
LAYER_NORM_EPS = 1e-6
LOSS_PARTS = ("main", "indexer")    # the loss's parts, beside the whole
AUX = ("loss.main", "loss.indexer", "moe.slots_here",
       "moe.load_max_over_mean", "moe.overflow_layers",
       "sparse_attention.selected_pairs", "sparse_attention.causal_pairs")
# what a block's recomputation keeps: a choice is made once
_KEEP = jax.checkpoint_policies.save_only_these_names("selection")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    if not cfg["norm_topk_prob"]:
        raise ValueError("this reference is the published layer: the chosen "
                         "scores over their sum")
    dep, sa = cfg["deployment"], cfg["sa_config"]
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError("this reference is the published indexer: one key "
                         "head")
    args = cfg["program"].get("zoo_args", {})
    return {
        "dim": int(cfg["hidden_size"]), "depth": int(cfg["num_hidden_layers"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head": int(cfg["head_dim"]),
        "index_heads": int(sa["indexer_num_heads"]),
        "index_head": int(sa["indexer_head_dim"]),
        "index_top_k": int(sa["topk"]),
        "sections": tuple(int(n) for n in
                          cfg["rope_scaling"]["mrope_section"]),
        "expert": int(cfg["moe_intermediate_size"]),
        "experts": int(dep["num_experts_published"]),
        "held": int(cfg["num_experts"]), "first": int(dep["experts_first"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "gate_grad": bool(args.get("gate_grad", True)),
        "vocab": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
    }


# ----------------------------------------------- what the runner asks for
def zoo_args(cfg: Dict[str, Any], length: int) -> Dict[str, Any]:
    """The configuration's published keys as the zoo entry's arguments;
    ``program.zoo_args`` (``gate_grad``) passes as it is."""
    d = dims(cfg)
    return dict(
        vocab=d["vocab"], dim=d["dim"], depth=d["depth"], heads=d["heads"],
        kv_heads=d["kv_heads"], head_dim=d["head"],
        index_heads=d["index_heads"], index_head_dim=d["index_head"],
        index_top_k=d["index_top_k"], expert_hidden=d["expert"],
        num_experts=d["experts"], top_k=d["top_k"],
        experts_held=(d["held"], d["first"]), theta=d["theta"],
        eps=d["eps"], max_len=length, **cfg["program"].get("zoo_args", {}))


def routed_blocks(cfg: Dict[str, Any]) -> List[str]:
    """The blocks with a routed layer (all), in the order ``routing`` has."""
    return [f"block{i}" for i in range(dims(cfg)["depth"])]


def selecting_blocks(cfg: Dict[str, Any]) -> List[str]:
    """The blocks whose mixer chooses its keys (all), in the order
    ``selection`` has."""
    return routed_blocks(cfg)


def selected_pairs(length: int, top_k: int) -> int:
    """(query, key) pairs a row of ``length`` keeps: ``min(k, t + 1)`` a
    query."""
    full = min(top_k, length)
    return full * (full + 1) // 2 + (length - full) * full


def causal_pairs(length: int) -> int:
    return length * (length + 1) // 2


def kernel_calls(cfg: Dict[str, Any], rows: int, length: int,
                 slots: float) -> Dict[str, Dict[str, Any]]:
    """Shapes of the kernels' work: one call of the selected core (under a
    key for either pass), one call of the choice, and one step's grouped
    products (in ``glm47_flash``'s keys, so that its cost function reads
    them)."""
    d = dims(cfg)
    call = {"rows": rows, "len": length, "heads": d["heads"],
            "kv_heads": d["kv_heads"], "head_dim": d["head"],
            "top_k": d["index_top_k"]}
    return {
        "selected_fwd": call, "selected_bwd": call,
        "topk_mask": {"rows": rows, "len": length},
        "expert_matmul": {"slots": slots, "dim": d["dim"],
                          "width": d["expert"], "held": d["held"],
                          "layers": d["depth"]}}


# ------------------------------------------------------------------ weights
def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree (``models/zoo/decoder.keye_vl2``), leaf
    shapes only."""
    d = dims(cfg)
    dim, H, G, hd = d["dim"], d["heads"], d["kv_heads"], d["head"]
    Hi, di = d["index_heads"], d["index_head"]
    block = {
        "norm1": {"scale": (dim,)},
        "attn": {"attn_query": {"kernel": (dim, H * hd)},
                 "attn_key": {"kernel": (dim, G * hd)},
                 "attn_value": {"kernel": (dim, G * hd)},
                 "query_norm": {"scale": (hd,)}, "key_norm": {"scale": (hd,)},
                 "indexer": {"index_query": {"kernel": (dim, Hi * di)},
                             "index_key": {"kernel": (dim, di)},
                             "index_key_norm": {"scale": (di,),
                                                "bias": (di,)},
                             "index_weight": (dim, Hi)},
                 "attn_out": {"kernel": (H * hd, dim)}},
        "norm2": {"scale": (dim,)},
        "ffn": {"router": {"kernel": (dim, d["experts"])},
                "experts_gate": (d["held"], dim, d["expert"]),
                "experts_up": (d["held"], dim, d["expert"]),
                "experts_down": (d["held"], d["expert"], dim)}}
    p = {"token_embedding": {"embedding": (d["vocab"], dim)},
         "final_norm": {"scale": (dim,)},
         "lm_head": {"kernel": (dim, d["vocab"])}}
    for i in range(d["depth"]):
        p[f"block{i}"] = block
    return {"params": p}


def parameters(cfg: Dict[str, Any]) -> int:
    """How many parameters the cut holds (the file's ``parameters_here``)."""
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=_is_shape))


def is_indexer_leaf(name: str) -> bool:
    """Whether a leaf (by its ``keystr`` path) is one of an indexer's four."""
    return "'indexer'" in name


def leaf_names(cfg: Dict[str, Any]) -> List[str]:
    """The leaves' ``keystr`` paths in the tree's order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    return [jax.tree_util.keystr(path) for path, _ in flat]


def leaf_class(name: str) -> str:
    """The class the comparison reads a leaf's gradient under: an
    ``indexer``'s four (gradient from ``L_I`` alone), the main
    ``attention``'s projections and head norms, the routed layer's
    ``experts`` (the frozen gate's router among them: no gradient), the two
    ``tables``, the blocks' and the final ``norms``."""
    if is_indexer_leaf(name):
        return "indexer"
    for part, kind in (("'attn'", "attention"), ("'ffn'", "experts"),
                       ("'token_embedding'", "tables"),
                       ("'lm_head'", "tables")):
        if part in name:
            return kind
    return "norms"


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Weights from a PRNG key in the program's tree layout, float32:
    matrices (the indexer's three among them), expert banks and both tables
    normal(0, 0.02); norm scales 1, but the two head norms'
    ``QK_NORM_SCALE_INIT`` (``sdar_moe``'s, and for its reason); the
    LayerNorm's bias 0. Leaves of one shape are drawn in one call and dealt
    out in the tree's order, from XLA's own bit generator ("rbg": as
    ``glm47_flash.init_params``, and for its reasons). The key is an
    argument, never a constant of the program."""
    key = jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg")
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    names = [jax.tree_util.keystr(path) for path, _ in flat]
    by_shape: Dict[Tuple[int, ...], List[int]] = {}
    for i, n in enumerate(names):
        if "scale" not in n and "bias" not in n:
            by_shape.setdefault(flat[i][1], []).append(i)
    leaves: List[Any] = [None] * len(flat)
    for j, (shape, where) in enumerate(by_shape.items()):
        draws = INIT_STD * jax.random.normal(
            jax.random.fold_in(key, j), (len(where),) + shape, jnp.float32)
        for n, i in enumerate(where):
            leaves[i] = draws[n]
    for i, (_, shape) in enumerate(flat):
        if leaves[i] is None:
            head_norm = "['query_norm']" in names[i] \
                or "['key_norm']" in names[i]
            leaves[i] = jnp.full(
                shape, 0.0 if "bias" in names[i] else
                QK_NORM_SCALE_INIT if head_norm else 1.0, jnp.float32)
    return jax.tree_util.tree_unflatten(tree, leaves)


# ------------------------------------------------------------------ forward
def mrope(x, theta: float, positions, sections) -> jax.Array:
    """x (N, H, R) turned by THREE position streams ``positions`` (3, N):
    frequency ``i`` of the ``R / 2`` (``theta**(-2i/R)``) takes its angle
    from the stream ``sections`` deals it to (the first ``sections[0]``
    frequencies from stream 0, the next ``sections[1]`` from stream 1, the
    rest from stream 2), pairing ``(i, i + R/2)``."""
    R = x.shape[-1]
    if sum(sections) != R // 2:
        raise ValueError(f"mrope_section {sections} over {R // 2} "
                         "frequencies")
    inv = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    stream = np.repeat(np.arange(len(sections)), sections)
    ang = (positions.astype(jnp.float32)[stream].T * inv)[:, None, :]
    x1, x2 = x[..., :R // 2], x[..., R // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _layer_norm(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LAYER_NORM_EPS) * p["scale"] \
        + p["bias"]


def indexer_scores(d, mm, p, x, positions):
    """x (N, dim) DETACHED -> ``(qI (N, H_I, d_I), kI (N, d_I), w (N,
    H_I))``: a block of queries' scores against all keys are
    ``block_scores`` of these."""
    N, Hi, di = x.shape[0], d["index_heads"], d["index_head"]
    q = mm("ld,dk->lk", x, p["index_query"]["kernel"]).reshape(N, Hi, di)
    k = _layer_norm(mm("ld,dk->lk", x, p["index_key"]["kernel"]),
                    p["index_key_norm"])
    w = mm("ld,dj->lj", x, p["index_weight"]) * (Hi ** -0.5 * di ** -0.5)
    return (_rotary_at(q, d["theta"], positions),
            _rotary_at(k[:, None, :], d["theta"], positions)[:, 0], w)


def block_scores(mm, q_i, k_i, w):
    """``I`` (queries, keys) of a block of queries."""
    return jnp.sum(w[:, :, None] * jax.nn.relu(
        mm("tjd,sd->tjs", q_i, k_i)), 1)


def choose(scores, first, top_k: int):
    """The ``min(top_k, t + 1)`` largest of each row's causal scores by
    ``lax.top_k`` -> ``(chosen (rows, L) bool, ranked (rows, L))``; row
    ``r`` is query ``first + r``; ``ranked`` the scores as they were
    ranked (the future at ``-inf``)."""
    rows, L = scores.shape
    t = first + jnp.arange(rows)
    ranked = jnp.where(jnp.arange(L)[None, :] <= t[:, None],
                       jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    _, idx = jax.lax.top_k(ranked, min(top_k, L))
    real = jnp.arange(idx.shape[1])[None, :] <= t[:, None]
    chosen = jnp.zeros((rows, L), bool).at[
        jnp.arange(rows)[:, None], idx].max(real)
    return chosen, ranked


def pack(chosen):
    """A selection ``(..., L)`` bool as bits, ``(..., ceil(L / 8))`` uint8:
    how selections are handed in, kept and handed out (a byte a pair is 268
    MB a layer at a row of 16,384)."""
    return jnp.packbits(chosen, axis=-1)


def unpack(packed, length: int):
    return jnp.unpackbits(packed, axis=-1, count=length).astype(bool)


def _attention(d, mm, top_k, p, x, positions, observe, follow):
    """-> (the mixer's output (L, dim), ``L_I``, this layer's selection (L,
    L) as bits, (pairs on which ``observe`` (bits too) and this reference's
    own choice differ, EITHER way; pairs of ``observe``; pairs of this
    reference's, ``min(k, t + 1)`` a query; how far from this reference's
    last chosen score the widest of the differing pairs lay)). With
    ``follow`` (a bool, traced or not) the core and the loss run over
    ``observe``'s selection, after this reference's own was made and held
    against it."""
    L, H, G, hd = x.shape[0], d["heads"], d["kv_heads"], d["head"]
    three = jnp.broadcast_to(positions, (3, L))
    q = mm("ld,dk->lk", x, p["attn_query"]["kernel"]).reshape(L, H, hd)
    k = mm("ld,dk->lk", x, p["attn_key"]["kernel"]).reshape(L, G, hd)
    v = mm("ld,dk->lk", x, p["attn_value"]["kernel"]).reshape(L, G, hd)
    q = mrope(_rms(q, p["query_norm"], d["eps"]), d["theta"], three,
              d["sections"])
    k = mrope(_rms(k, p["key_norm"], d["eps"]), d["theta"], three,
              d["sections"])
    q_i, k_i, w = indexer_scores(d, mm, p["indexer"],
                                 jax.lax.stop_gradient(x), positions)
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    scale = 1.0 / np.sqrt(hd)
    rows = min(QUERY_BLOCK, L)
    if L % rows:
        raise ValueError(f"a row of {L} tokens in query blocks of {rows}")

    def block(args):
        q_b, qi_b, w_b, first, seen = args
        scores = block_scores(mm, qi_b, k_i, w_b)
        own, ranked = choose(jax.lax.stop_gradient(scores), first, top_k)
        seen = unpack(seen, L)
        # kept across the backward pass a BIT a pair
        kept = checkpoint_name(pack(jnp.where(follow, seen, own)),
                               "selection")
        chosen = unpack(kept, L)

        def head(total, args):          # one query head: (rows, L) scores
            q_h, group = args
            s = jnp.where(chosen, mm("qk,nk->qn", q_h * scale, k[group]),
                          -jnp.inf)
            prob = jax.nn.softmax(s, axis=-1)
            return total + jax.lax.stop_gradient(prob), \
                mm("qn,nk->qk", prob, v[group])
        total, o = jax.lax.scan(
            jax.checkpoint(head), jnp.zeros((rows, L), jnp.float32),
            (q_b.transpose(1, 0, 2), jnp.arange(H) // (H // G)))
        target = total / H
        logq = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), -1)
        live = chosen & (target > 0)
        kl = jnp.sum(jnp.where(live, target * (
            jnp.log(jnp.where(live, target, 1.0))
            - jnp.where(live, logq, 0.0)), 0.0))
        # both ways: a pair ``observe`` keeps and this reference does not,
        # and one this reference keeps and ``observe`` does not (a subset
        # of the chosen keys has no pair of the first kind)
        last = jnp.min(jnp.where(own, ranked, jnp.inf), -1)
        differ = seen != own
        held = (jnp.sum(differ), jnp.sum(seen), jnp.sum(own), jnp.max(
            jnp.where(differ, jnp.abs(last[:, None] - ranked), 0.0)))
        return o.transpose(1, 0, 2).reshape(rows, H * hd), kl, kept, held
    n = L // rows
    o, kl, chosen, (flips, pairs, must, margin) = jax.lax.map(
        jax.checkpoint(block, policy=_KEEP), (
            q.reshape(n, rows, H, hd), q_i.reshape(n, rows, *q_i.shape[1:]),
            w.reshape(n, rows, -1), jnp.arange(0, L, rows),
            observe.reshape(n, rows, -1)))
    return (mm("lk,kd->ld", o.reshape(L, H * hd), p["attn_out"]["kernel"]),
            jnp.sum(kl) / L, chosen.reshape(L, -1),
            (jnp.sum(flips), jnp.sum(pairs), jnp.sum(must), jnp.max(margin)))


def _routed(d, mm, p, x):
    """``sdar_moe._experts`` on ``ROW_BLOCK`` rows at a time (a token's
    routed part is its own), each block recomputed in the backward pass:
    the loop over the held experts then keeps a block's running sum an
    expert, not the row's."""
    L = x.shape[0]
    rows = min(ROW_BLOCK, L)
    if L % rows:
        return _experts(d, mm, p, x)
    y, (choice, scores) = jax.lax.map(
        jax.checkpoint(lambda r: _experts(d, mm, p, r)),
        x.reshape(L // rows, rows, -1))
    return y.reshape(L, -1), (choice.reshape(L, -1), scores.reshape(L, -1))


def _block(d, mm, top_k, positions, follow, p, x, observe):
    y, aux, chosen, held = _attention(
        d, mm, top_k, p["attn"], _rms(x, p["norm1"], d["eps"]), positions,
        observe, follow)
    h = x + y
    y, routing = _routed(d, mm, p["ffn"], _rms(h, p["norm2"], d["eps"]))
    return h + y, (aux, routing, chosen, held)


def hidden_rows(cfg: Dict[str, Any], mm, params: Dict[str, Any],
                tokens: jax.Array, observe: Optional[jax.Array] = None,
                mask: Optional[str] = None, follow=False) -> Dict[str, Any]:
    """One sequence ``tokens`` (L,) -> the normed rows the head reads
    (``hidden`` (L, dim)), the layers' ``L_I`` summed (``indexer``), and per
    layer in order the ``routing``, the ``selection`` (``pack`` of the (L,
    L) bool ``[t, s]``) and what ``observe`` ((depth, L, ceil(L / 8))
    bits, another selection; None: nothing) read against it
    (``observed``); with ``follow`` every layer then RUNS over
    ``observe``'s selection (its own is still made, and held against it).
    ``mask="causal"`` is the control without a selection: every causal key
    chosen. Blocks are
    recomputed in the backward pass, but for their choice."""
    d = dims(cfg)
    p, L = params["params"], tokens.shape[0]
    top_k = L if mask == "causal" else d["index_top_k"]
    if observe is None:
        observe = jnp.zeros((d["depth"], L, -(-L // 8)), jnp.uint8)
    x = p["token_embedding"]["embedding"][tokens]
    out: Dict[str, Any] = {"indexer": 0.0, "routing": [], "selection": [],
                           "observed": []}
    for i in range(d["depth"]):
        x, (aux, routing, chosen, held) = jax.checkpoint(functools.partial(
            _block, d, mm, top_k, jnp.arange(L), follow), policy=_KEEP)(
                p[f"block{i}"], x, observe[i])
        out["indexer"] = out["indexer"] + aux
        out["routing"].append(routing)
        out["selection"].append(chosen)
        out["observed"].append(held)
    out["hidden"] = _rms(x, p["final_norm"], d["eps"])
    return out


def logits(cfg, params, tokens, quant=None, mask=None):
    """(L, vocab) float32 logits of one sequence (the tests' entry)."""
    mm = _products(quant)
    return mm("ld,dv->lv", hidden_rows(cfg, mm, params, tokens,
                                       mask=mask)["hidden"],
              params["params"]["lm_head"]["kernel"])


def sequence_loss(cfg, quant, mask, rows, params, tokens, observe=None,
                  follow=False, keep=True):
    """One sequence's part of the batch loss over ``rows`` sequences:
    ``(part, (main, indexer, routing, selection, observed))``, already over
    the batch's count, so that the parts of a batch add up to its loss;
    without ``keep`` the selections (a byte a pair and layer) stay
    inside."""
    L = tokens.shape[0]
    mm = _products(quant)
    out = hidden_rows(cfg, mm, params, tokens, observe, mask, follow)
    kernel = params["params"]["lm_head"]["kernel"]
    targets = jnp.roll(tokens, -1)
    counted = (jnp.arange(L) < L - 1).astype(jnp.float32)

    def nll(args):
        h, t, w = args
        logp = jax.nn.log_softmax(mm("ld,dv->lv", h, kernel), -1)
        return -jnp.sum(w * jnp.take_along_axis(logp, t[:, None], 1)[:, 0])
    n = L // ROW_BLOCK if L % ROW_BLOCK == 0 and L > ROW_BLOCK else 1
    main = jnp.sum(jax.lax.map(jax.checkpoint(nll), (
        out["hidden"].reshape(n, L // n, -1), targets.reshape(n, -1),
        counted.reshape(n, -1)))) / (rows * (L - 1))
    indexer = out["indexer"] / rows
    return main + indexer, (main, indexer, out["routing"],
                            out["selection"] if keep else (),
                            out["observed"])


# ----------------------------------------------------------------- training
def train_reference(cfg: Dict[str, Any], seed: int, tokens: np.ndarray, *,
                    steps: int, optimizer: Dict[str, Any],
                    quant: Optional[str] = None, mask: Optional[str] = None,
                    observe=None, keep_selection: bool = False
                    ) -> Dict[str, Any]:
    """Follow the first ``steps`` AdamW steps from the seeded weights on
    ``tokens[s]`` (``(rows, L)`` int32, one batch per step), float32 at the
    highest matmul precision, one sequence at a time with the gradients
    summed; decay on leaves of two and more dimensions. The cut's 465M
    parameters are 7.4 GB of weights, gradient and moments; a block of
    ``QUERY_BLOCK`` queries' scores against the row's keys have the rest.

    Returns what ``sdar_moe.train_reference`` returns, for the runner's
    ``compare``: per step the loss (``losses``, ``main`` and ``indexer``;
    ``mtp`` is empty), the first gradient (leaves on the host, and their
    norms), the per-leaf norm of the parameters' change, step 0's routing
    per layer (``choice`` (rows * L, K), ``ranked`` (rows * L, E)), and
    ``timing`` in seconds; and of step 0's selections: with ``observe``
    (per sequence ``pack`` of ``(depth, L, L)`` bool: the program's)
    ``selection_flips`` per layer ``(pairs on which observe and this
    reference differ, either way, over the pairs a query must keep; the
    widest margin at one of them)`` and ``selection_pairs`` per layer
    ``(observe's pairs, the min(k, t + 1) a query this reference kept)``, and
    step 0 then FOLLOWS ``observe``: each layer's core and loss run over
    the handed selection, so that the step's loss and gradient are held
    against the same discrete choice (the module's docstring says why);
    steps 1 and 2 choose for themselves; with
    ``keep_selection`` the reference's own, ``selection`` per sequence in
    that layout, left on the device.
    """
    import time
    lr, b1, b2 = (float(optimizer[k]) for k in
                  ("learning_rate", "beta1", "beta2"))
    eps, decay = float(optimizer["eps"]), float(optimizer["weight_decay"])
    rows = tokens.shape[1]
    clock = {"init": 0.0, "first_sequence": 0.0, "other_sequences": 0.0,
             "fetch": 0.0, "update": 0.0}

    def timed(key, t0):
        clock[key] += time.perf_counter() - t0

    with jax.default_matmul_precision("highest"):
        key = jax.random.PRNGKey(seed)
        init = jax.jit(lambda k: init_params(cfg, k))
        t0 = time.perf_counter()
        params = init(key)
        # everything on this reference's one device, and said so: an array
        # that is committed, or that carries its program's mesh in its type
        # (a handed selection does), passes that on through the gradient
        # and the update into the parameters, and step 1 would build every
        # program of step 0 a second time
        here = jax.sharding.SingleDeviceSharding(next(iter(
            params["params"]["final_norm"]["scale"].devices())))
        params = jax.block_until_ready(jax.device_put(params, here))
        timed("init", t0)

        def add_grad(p, acc, toks, seen, follow):
            (part, aux), g = jax.value_and_grad(functools.partial(
                sequence_loss, cfg, quant, mask, rows, keep=keep_selection),
                has_aux=True)(p, toks, seen, follow)
            return (part, aux), g if acc is None \
                else jax.tree_util.tree_map(jnp.add, acc, g)
        grad_first = jax.jit(lambda p, toks, seen, follow: add_grad(
            p, None, toks, seen, follow))
        grad_seq = jax.jit(add_grad, donate_argnums=(1,))
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
        L, depth = tokens.shape[2], dims(cfg)["depth"]
        seen = [jnp.zeros((depth, L, -(-L // 8)), jnp.uint8)] * rows
        if observe is not None:
            seen = [jax.device_put(x, here) for x in observe]

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def adamw(params, m, v, g, t):
            def leaf(p, m, v, g):
                m = b1 * m + (1 - b1) * g
                v = b2 * v + (1 - b2) * g * g
                step = (m / (1 - b1 ** t)) / (
                    jnp.sqrt(v / (1 - b2 ** t)) + eps)
                if p.ndim >= 2:
                    step = step + decay * p
                return p - lr * step, m, v
            out = jax.tree_util.tree_map(leaf, params, m, v, g)
            return tuple(jax.tree_util.tree_map(
                lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
                for i in range(3))

        m = v = None
        out: Dict[str, Any] = {"losses": [], "main": [], "indexer": [],
                               "mtp": []}
        for s in range(steps):
            total, main, indexer, grads = 0.0, 0.0, 0.0, None
            routing, selection, observed = [], [], []
            for b in range(rows):
                t0 = time.perf_counter()
                toks = jnp.asarray(tokens[s][b])
                # step 0 runs over the selection it was handed, if any
                follow = jnp.asarray(observe is not None and s == 0)
                (part, aux), grads = grad_first(
                    params, toks, seen[b], follow) if b == 0 \
                    else grad_seq(params, grads, toks, seen[b], follow)
                total += float(part)
                main += float(aux[0])
                indexer += float(aux[1])
                timed("other_sequences" if s or b else "first_sequence", t0)
                if s == 0:
                    routing.append(aux[2])
                    observed.append(aux[4])
                    if keep_selection:
                        selection.append(jnp.stack(aux[3]))
                del aux
            out["losses"].append(total)
            out["main"].append(main)
            out["indexer"].append(indexer)
            if s == 0:
                per_seq = [[(np.asarray(c), np.asarray(r)) for c, r in seq]
                           for seq in routing]
                flips = np.asarray(jax.device_get(observed), np.float64)
                if keep_selection:
                    out["selection"] = selection
                out["grad_norms"] = {k: float(n) for k, n in
                                     jax.jit(leaf_norms)(grads).items()}
                t0 = time.perf_counter()
                out["first_grad"] = [np.asarray(x) for x in jax.device_get(
                    jax.tree_util.tree_leaves(grads))]
                timed("fetch", t0)
            del routing, selection, observed
            t0 = time.perf_counter()
            if m is None:
                m, v = zeros(params), zeros(params)
            params, m, v = adamw(params, m, v, grads, float(s + 1))
            del grads
            jax.block_until_ready(params)
            timed("update", t0)
        del m, v
        moved = jax.jit(lambda a, b: leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, a, b)))(params, init(key))
        out["delta_norms"] = {k: float(n) for k, n in moved.items()}
    out["routing"] = [    # per layer, the batch's rows in order
        {"choice": np.concatenate([seq[i][0] for seq in per_seq]),
         "ranked": np.concatenate([seq[i][1] for seq in per_seq])}
        for i in range(len(per_seq[0]))]
    if observe is not None:
        # flips: (sequence, layer, (differing, observe's, own, margin))
        out["selection_flips"] = [
            (float(flips[:, i, 0].sum() / flips[:, i, 2].sum()),
             float(flips[:, i, 3].max())) for i in range(flips.shape[1])]
        out["selection_pairs"] = [
            (int(flips[:, i, 1].sum()), int(flips[:, i, 2].sum()))
            for i in range(flips.shape[1])]
    out["timing"] = {k: round(t, 3) for k, t in clock.items()}
    return out


# ---------------------------------------------- work, from shapes alone
def _fwd_flops_per_item(cfg: Dict[str, Any], length: int) -> Dict[str, float]:
    d = dims(cfg)
    dim, H, G, hd = d["dim"], d["heads"], d["kv_heads"], d["head"]
    Hi, di = d["index_heads"], d["index_head"]
    chosen = selected_pairs(length, d["index_top_k"])
    projections = length * 2.0 * dim * (2 * H * hd + 2 * G * hd)
    indexer_proj = length * 2.0 * dim * (Hi * di + di + Hi)
    routed = length * (2.0 * dim * d["experts"] + d["top_k"] * d["held"]
                       / d["experts"] * 2.0 * 3 * dim * d["expert"])
    return {
        "projections": projections, "indexer_projections": indexer_proj,
        "routed": routed, "core": 2.0 * 2 * H * hd * chosen,
        "indexer_scores": 2.0 * Hi * di * causal_pairs(length),
        # the loss's target: the main scores again on the chosen pairs,
        # forward alone (a constant of the loss)
        "target": 2.0 * H * hd * chosen,
        "head": length * 2.0 * dim * d["vocab"]}


def train_flops_per_item(cfg: Dict[str, Any], length: int = 16384) -> float:
    """Matrix-multiplication and attention FLOPs that one item (a row of
    ``length`` tokens) REQUIRES, forward and backward (backward = 2 x
    forward; nothing recomputed counts): the projections, the indexer's
    three, the router and the EXPECTED routed work of the experts held
    here, the two attention products over the CHOSEN pairs (``min(k, t +
    1)`` a query, not the causal half a dense-masked core computes), the
    indexer's scores over the CAUSAL pairs (every one is ranked), the
    head; and once, forward alone, the main scores again on the chosen
    pairs, which the indexer's loss reads as a constant. The choice itself
    is no product. From shapes alone."""
    f = _fwd_flops_per_item(cfg, length)
    depth = dims(cfg)["depth"]
    layer = f["projections"] + f["indexer_projections"] + f["routed"] \
        + f["core"] + f["indexer_scores"]
    return 3.0 * (depth * layer + f["head"]) + depth * f["target"]


def _core_pairs(call: Dict[str, Any]) -> float:
    return float(call["rows"]) * selected_pairs(
        int(call["len"]), int(call["top_k"]))


def selected_fwd_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One forward call of the selected core on ``rows`` rows of ``len``:
    the two products over the CHOSEN pairs (``rows x pairs x H x D x 4``),
    and q and o at the query heads, k and v at the key/value heads once
    through HBM in bfloat16. The mask's bytes are the form's, not the
    work's."""
    heads, kv, hd = (float(call[k]) for k in
                     ("heads", "kv_heads", "head_dim"))
    return _core_pairs(call) * heads * hd * 4.0, \
        float(call["rows"]) * float(call["len"]) * hd * 2.0 \
        * (2.0 * heads + 2.0 * kv)


def selected_bwd_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One backward call: five products over the chosen pairs (2.5 times
    the forward's FLOPs), and q, k, v and the cotangent read and dq, dk, dv
    written once in bfloat16."""
    flops, nbytes = selected_fwd_cost(call)
    return 2.5 * flops, nbytes * 7.0 / 4.0


def select_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One call of the choice: no product is required of it, and its
    scores, the causal pairs' in float32, are read once."""
    return 0.0, float(call["rows"]) * causal_pairs(int(call["len"])) * 4.0
