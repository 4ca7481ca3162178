"""Plain reference: Laguna-XS.2 (``laguna``), training, float32.

Written from the published ``config.json`` (huggingface.co/poolside/
Laguna-XS.2, ``model_type: laguna``) in straightforward ``jax.numpy``: no
kernels, no flax, nothing imported from the program (five helpers that
belong to no family come from a sibling reference). Pre-norm decoder, no
biases, every RMSNorm with a plain scale, ``D`` = ``hidden_size``:

- ``h_0 = E[token]``; layer ``l``: ``h <- h + Attn_l(norm1(h))``, ``h <- h
  + F_l(norm2(h))``; the mixer's kind read off ``layer_types[l]``, its
  query heads off ``num_attention_heads_per_layer[l]``, the feed-forward
  part off ``mlp_layer_types[l]``: three published lists;
- ``Attn_l``: ``q = x W_q`` on ``H_l`` heads (48 in a full layer, 64 in a
  sliding one), ``k``, ``v`` on 8, of 128; no norm on q or k; positions by
  the layer's kind: ``full_attention`` turns the first 64 dimensions of a
  head (``partial_rotary_factor`` 0.5) by YaRN's frequencies (theta
  500,000, factor 64, original 4,096, beta 64 / 1) with cos and sin times
  the attention factor 1.41589, the other 64 pass; ``sliding_attention``
  turns all 128 by plain rotary at theta 10,000; half-split pairing.
  Causal softmax of ``128^-1/2 q k^T``, in a sliding layer over the keys
  ``0 <= i - j < 512`` alone, query head ``h`` reading key/value head ``h
  // (H_l / 8)``, one query head at a time; ``g = sigmoid(x W_g)`` (``W_g``
  ``D x H_l``: ``gating``), ``o_h <- g_h o_h``; through ``W_o``;
- ``F_l``, ``dense``: ``W_2 (silu(W_1 u) * W_3 u)``, 8,192 wide.
  ``sparse``: ``s = sigmoid(u W_r)`` over all 256; the choice is the top 8
  of ``s + b``; weights ``s`` at the chosen over their sum, times
  ``moe_routed_scaling_factor`` 2.5, on the experts' OUTPUT
  (``moe_apply_router_weight_on_input`` false); ``y = sum_k w_k E_k(u) +
  E_shared(u)``, every ``E`` a SwiGLU of 512, the shared one ungated. Only
  the experts held here (32 of 256: this chip's share of an 8-way expert-
  parallel layer) add their part, in a dense loop over them, under the
  router's full 256-wide choice; a slot whose expert is held elsewhere
  adds nothing;
- a final norm; ``logits = h W_head`` (untied); next-token cross-entropy.

Departures and forms set here (the configuration file lists each under
``assumed``, with the evidence): the gate is a head's scalar; the router
is DeepSeek-V3's rule; ``b`` = 0 and never updated; no norm on q or k;
the window's edge; ``rotate_half`` pairing; YaRN's ramp as
``transformers``' ``_compute_yarn_parameters``; **the gate of the routed
layers takes no gradient** where the configuration says so
(``program.zoo_args.gate_grad`` false: ``lfm2_moe``'s reference has the
argument); matrices, banks and tables normal(0, 0.02), norm scales 1; a
packed row is one document; AdamW.

``quant`` rounds both operands of every matrix multiplication through a
lower precision: the control that the comparison deciding ``correct`` has
to fail. ``window=False`` (``hidden_rows``, ``sequence_loss``) runs the
sliding layers as plain causal ones: the control of the band itself.

**What is read of the configuration file**: ``hidden_size``,
``num_hidden_layers``, ``layer_types``, ``mlp_layer_types`` and
``num_attention_heads_per_layer`` (as long), ``num_key_value_heads``,
``head_dim``, ``sliding_window``, ``rope_parameters`` (both kinds, whole),
``gating`` (must be true), ``intermediate_size``,
``moe_intermediate_size``, ``shared_expert_intermediate_size``,
``num_experts`` (HELD here), ``deployment.num_experts_published`` /
``.experts_first``, ``num_experts_per_tok``,
``moe_routed_scaling_factor``, ``moe_apply_router_weight_on_input`` (must
be false), ``tie_word_embeddings`` (must be false), ``rms_norm_eps``,
``vocab_size`` and ``program.zoo_args``; the runner reads ``program.zoo``
/ ``.loss_chunk``, ``optimizer`` and ``limits``.

Also here, because the benchmark keeps them: what the runner asks a family
for (``zoo_args``, ``routed_blocks``, ``kernel_calls``, ``LOSS_PARTS``,
``AUX``), and the operations and bytes a band's two kernels need
(``window_fwd_cost``, ``window_bwd_cost``).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# what is no family's own: the control's rounding of matmul operands, the
# SwiGLU part, the plain-scale norm, per-leaf norms (the sibling
# reference's; nothing of the program's)
from benchmark.references.glm47_flash import (  # noqa: F401
    _is_shape, _products, _rms, _swiglu, leaf_norms)

INIT_STD = 0.02
KINDS = ("full_attention", "sliding_attention")
LOSS_PARTS = ("main",)          # the heads of the loss, beside the whole
AUX = ("loss.main", "moe.slots_here", "moe.load_max_over_mean",
       "moe.overflow_layers")   # the ring's scalars beside the loss


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    depth = int(cfg["num_hidden_layers"])
    kinds, feeds = tuple(cfg["layer_types"]), tuple(cfg["mlp_layer_types"])
    heads = tuple(int(h) for h in cfg["num_attention_heads_per_layer"])
    if not (len(kinds) == len(feeds) == len(heads) == depth) \
            or set(kinds) - set(KINDS) or set(feeds) - {"dense", "sparse"}:
        raise ValueError(
            f"layer_types {kinds!r}, mlp_layer_types {feeds!r} and "
            f"num_attention_heads_per_layer {heads!r} against {depth} layers")
    if not cfg["gating"] or cfg["tie_word_embeddings"] \
            or cfg["moe_apply_router_weight_on_input"] \
            or cfg["attention_bias"]:
        raise ValueError("this reference is the published layer: a gate a "
                         "head, untied tables, the routing weight on the "
                         "experts' output, no biases")
    dep, rope = cfg["deployment"], cfg["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    if full["rope_type"] != "yarn" or sliding["rope_type"] != "default":
        raise ValueError("full layers turn by yarn, sliding ones by default")
    hd = int(cfg["head_dim"])
    return {
        "dim": int(cfg["hidden_size"]), "kinds": kinds, "feeds": feeds,
        "heads": heads, "kv_heads": int(cfg["num_key_value_heads"]),
        "head": hd, "window": int(cfg["sliding_window"]),
        "full_width": int(hd * float(full["partial_rotary_factor"])),
        "full_theta": float(full["rope_theta"]),
        "yarn": {k: float(full[k]) for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "attention_factor")},
        "sliding_width": int(hd * float(sliding["partial_rotary_factor"])),
        "sliding_theta": float(sliding["rope_theta"]),
        "mlp": int(cfg["intermediate_size"]),
        "expert": int(cfg["moe_intermediate_size"]),
        "shared": int(cfg["shared_expert_intermediate_size"]),
        "experts": int(dep["num_experts_published"]),
        "held": int(cfg["num_experts"]), "first": int(dep["experts_first"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "scaling": float(cfg["moe_routed_scaling_factor"]),
        "gate_grad": bool(
            cfg["program"].get("zoo_args", {}).get("gate_grad", True)),
        "vocab": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
    }


def _routed(d: Dict[str, Any], index: int) -> bool:
    return d["feeds"][index] == "sparse"


# ----------------------------------------------- what the runner asks for
def zoo_args(cfg: Dict[str, Any], length: int) -> Dict[str, Any]:
    """The configuration's published keys as the zoo entry's arguments;
    ``program.zoo_args`` (``gate_grad``) passes as it is."""
    d = dims(cfg)
    y = d["yarn"]
    return dict(
        vocab=d["vocab"], dim=d["dim"], layer_types=d["kinds"],
        heads_per_layer=d["heads"], mlp_layer_types=d["feeds"],
        kv_heads=d["kv_heads"], head_dim=d["head"], window=d["window"],
        mlp_hidden=d["mlp"], expert_hidden=d["expert"],
        shared_hidden=d["shared"], num_experts=d["experts"],
        top_k=d["top_k"], experts_held=(d["held"], d["first"]),
        scaling=d["scaling"], full_theta=d["full_theta"],
        full_rotary_fraction=d["full_width"] / d["head"],
        yarn_factor=y["factor"],
        yarn_original=int(y["original_max_position_embeddings"]),
        yarn_beta_fast=y["beta_fast"], yarn_beta_slow=y["beta_slow"],
        yarn_attention_factor=y["attention_factor"],
        window_theta=d["sliding_theta"], eps=d["eps"], max_len=length,
        **cfg["program"].get("zoo_args", {}))


def routed_blocks(cfg: Dict[str, Any]) -> List[str]:
    """The blocks with a routed layer, in the order ``routing`` has."""
    d = dims(cfg)
    return [f"block{i}" for i in range(len(d["kinds"])) if _routed(d, i)]


def _heads_of(d, kind: str) -> int:
    """The query heads of the layers of one kind (one count a kind in the
    published list; a cut that mixed them would have no one call shape)."""
    counts = {h for k, h in zip(d["kinds"], d["heads"]) if k == kind}
    if len(counts) > 1:
        raise ValueError(f"{kind} layers of {sorted(counts)} query heads")
    return counts.pop() if counts else 0


def kernel_calls(cfg: Dict[str, Any], rows: int, length: int,
                 slots: float) -> Dict[str, Dict[str, Any]]:
    """Shapes of the kernels' work: one forward call of the flash kernel
    (a FULL layer's: the causal half), one step's grouped products (both
    in ``glm47_flash``'s keys, so that its cost functions read them), one
    forward and one backward call of a band's kernels (a sliding
    layer's)."""
    d = dims(cfg)
    band = {"rows": rows, "len": length,
            "heads": _heads_of(d, "sliding_attention"),
            "head_dim": d["head"], "window": d["window"]}
    return {
        "flash_fwd": {"rows": rows, "len": length,
                      "heads": _heads_of(d, "full_attention"),
                      "head_dim": d["head"]},
        "expert_matmul": {"slots": slots, "dim": d["dim"],
                          "width": d["expert"], "held": d["held"],
                          "layers": len(routed_blocks(cfg))},
        "window_fwd": band, "window_bwd": band}


# ------------------------------------------------------------------ weights
def _block_shapes(d, index: int) -> Dict[str, Any]:
    dim, H, G, hd = d["dim"], d["heads"][index], d["kv_heads"], d["head"]
    attn = {"attn_query": {"kernel": (dim, H * hd)},
            "attn_key": {"kernel": (dim, G * hd)},
            "attn_value": {"kernel": (dim, G * hd)},
            "attn_head_gate": {"kernel": (dim, H)},
            "attn_out": {"kernel": (H * hd, dim)}}

    def swiglu(width):
        return {"mlp_gate": {"kernel": (dim, width)},
                "mlp_up": {"kernel": (dim, width)},
                "mlp_down": {"kernel": (width, dim)}}
    if _routed(d, index):
        ffn = {"router": {"kernel": (dim, d["experts"])},
               "router_bias": (d["experts"],),
               "experts_gate": (d["held"], dim, d["expert"]),
               "experts_up": (d["held"], dim, d["expert"]),
               "experts_down": (d["held"], d["expert"], dim),
               "shared": swiglu(d["shared"])}
    else:
        ffn = swiglu(d["mlp"])
    return {"norm1": {"scale": (dim,)}, "attn": attn,
            "norm2": {"scale": (dim,)}, "ffn": ffn}


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree (``models/zoo/decoder.laguna``), leaf
    shapes only."""
    d = dims(cfg)
    p = {"token_embedding": {"embedding": (d["vocab"], d["dim"])},
         "final_norm": {"scale": (d["dim"],)},
         "lm_head": {"kernel": (d["dim"], d["vocab"])}}
    for i in range(len(d["kinds"])):
        p[f"block{i}"] = _block_shapes(d, i)
    return {"params": p}


def parameters(cfg: Dict[str, Any]) -> int:
    """How many parameters the cut holds (the file's ``parameters_here``)."""
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=_is_shape))


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Weights from a PRNG key in the program's tree layout, float32:
    matrices, expert banks and tables normal(0, 0.02); norm scales 1; the
    router's bias 0. Leaves of one shape are drawn in one call and dealt
    out in the tree's order, from XLA's own bit generator ("rbg": as
    ``glm47_flash.init_params``, and for its reasons). The key is an
    argument, never a constant of the program."""
    key = jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg")
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    names = [jax.tree_util.keystr(path) for path, _ in flat]
    by_shape: Dict[Tuple[int, ...], List[int]] = {}
    for i, n in enumerate(names):
        if "scale" not in n and "router_bias" not in n:
            by_shape.setdefault(flat[i][1], []).append(i)
    leaves: List[Any] = [None] * len(flat)
    for j, (shape, where) in enumerate(by_shape.items()):
        draws = INIT_STD * jax.random.normal(
            jax.random.fold_in(key, j), (len(where),) + shape, jnp.float32)
        for n, i in enumerate(where):
            leaves[i] = draws[n]
    for i, (_, shape) in enumerate(flat):
        if leaves[i] is None:
            leaves[i] = (jnp.ones if "scale" in names[i] else jnp.zeros)(
                shape, jnp.float32)
    return jax.tree_util.tree_unflatten(tree, leaves)


# ------------------------------------------------------------------ forward
def yarn_inv_freq(width: int, theta: float, yarn: Dict[str, float]):
    """The ``width / 2`` inverse frequencies of YaRN (arXiv:2309.00071) as
    ``transformers``' ``_compute_yarn_parameters`` makes them, float64: a
    pair that turns more than ``beta_fast`` times over the original
    positions keeps plain rotary's frequency, one that turns fewer than
    ``beta_slow`` times turns ``factor`` times slower, a linear ramp over
    the pairs' indices between."""
    original = yarn["original_max_position_embeddings"]

    def pair(turns):            # the pair that turns so often over them
        return width * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair(yarn["beta_fast"])), 0)
    high = min(math.ceil(pair(yarn["beta_slow"])), width - 1)
    if low == high:
        high += 0.001
    plain = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    ramp = np.clip((np.arange(width // 2) - low) / (high - low), 0.0, 1.0)
    return plain / yarn["factor"] * ramp + plain * (1.0 - ramp)


def _turn(x, inv_freq, factor: float):
    """x (L, H, R), all R dimensions turning: position l turns the pair
    (i, i + R/2) by l * inv_freq[i]; cos and sin times ``factor``."""
    L, R = x.shape[0], x.shape[-1]
    ang = jnp.arange(L, dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :R // 2], x[..., R // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _positions(d, kind: str, x):
    if kind == "sliding_attention":
        width, factor = d["sliding_width"], 1.0
        inv = d["sliding_theta"] ** (
            -np.arange(0, width, 2, dtype=np.float64) / width)
    else:
        width, factor = d["full_width"], d["yarn"]["attention_factor"]
        inv = yarn_inv_freq(width, d["full_theta"], d["yarn"])
    return jnp.concatenate(
        [_turn(x[..., :width], inv, factor), x[..., width:]], -1)


def _attention(d, mm, kind: str, window: bool, p, x):
    L, G, hd = x.shape[0], d["kv_heads"], d["head"]
    H = p["attn_query"]["kernel"].shape[1] // hd
    q = mm("ld,dk->lk", x, p["attn_query"]["kernel"]).reshape(L, H, hd)
    k = mm("ld,dk->lk", x, p["attn_key"]["kernel"]).reshape(L, G, hd)
    v = mm("ld,dk->lk", x, p["attn_value"]["kernel"]).reshape(L, G, hd)
    q, k = _positions(d, kind, q), _positions(d, kind, k)
    scale = 1.0 / np.sqrt(hd)
    ahead = jnp.arange(L)[:, None] - jnp.arange(L)[None, :]   # i - j
    unseen = ahead < 0
    if kind == "sliding_attention" and window:
        unseen = unseen | (ahead >= d["window"])
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    def head(args):             # one query head at a time: (L, L) scores
        q_h, group = args
        s = jnp.where(unseen, -jnp.inf,
                      mm("qk,nk->qn", q_h * scale, k[group]))
        return mm("qn,nk->qk", jax.nn.softmax(s, axis=-1), v[group])
    o = jax.lax.map(jax.checkpoint(head), (
        q.transpose(1, 0, 2), jnp.arange(H) // (H // G)))
    gate = jax.nn.sigmoid(mm("ld,dh->lh", x, p["attn_head_gate"]["kernel"]))
    o = o.transpose(1, 0, 2) * gate[:, :, None]
    return mm("lk,kd->ld", o.reshape(L, H * hd), p["attn_out"]["kernel"])


def _experts(d, mm, p, x):
    """-> (y, routing): routing = (choice (L, K), scores + bias (L, E))."""
    s = jax.nn.sigmoid(mm("ld,de->le", x, p["router"]["kernel"]))
    ranked = s + p["router_bias"]
    choice = jax.lax.top_k(ranked, d["top_k"])[1]
    gate = jnp.take_along_axis(s, choice, axis=-1)
    gate = gate / gate.sum(-1, keepdims=True) * d["scaling"]
    if not d["gate_grad"]:
        gate = jax.lax.stop_gradient(gate)

    def part(bank):                     # dense: every token, no dispatch
        e, w_gate, w_up, w_down = bank
        w = jnp.sum(jnp.where(choice == d["first"] + e, gate, 0.0), -1)
        h = jax.nn.silu(mm("ld,dm->lm", x, w_gate)) \
            * mm("ld,dm->lm", x, w_up)
        return w[:, None] * mm("lm,md->ld", h, w_down)
    # the sum is carried OUTSIDE the recomputed part, whose backward pass
    # then keeps no (L, dim) carry an expert (32 of them held)
    y, _ = jax.lax.scan(
        lambda y, bank: (y + jax.checkpoint(part)(bank), None),
        jnp.zeros_like(x), (
            jnp.arange(d["held"]), p["experts_gate"], p["experts_up"],
            p["experts_down"]))
    return y + _swiglu(mm, p["shared"], x), (choice, ranked)


def _block(d, mm, index: int, window: bool, p, x):
    h = x + _attention(d, mm, d["kinds"][index], window, p["attn"],
                       _rms(x, p["norm1"], d["eps"]))
    z = _rms(h, p["norm2"], d["eps"])
    if not _routed(d, index):
        return h + _swiglu(mm, p["ffn"], z), None
    y, routing = _experts(d, mm, p["ffn"], z)
    return h + y, routing


def hidden_rows(cfg: Dict[str, Any], mm, params: Dict[str, Any],
                tokens: jax.Array, window: bool = True) -> Dict[str, Any]:
    """One sequence ``tokens`` (L,) -> the normed rows the head reads
    (``hidden`` (L, dim)) and the routing of every routed layer in order.
    Blocks are recomputed in the backward pass (that changes no value)."""
    d = dims(cfg)
    p = params["params"]
    x = p["token_embedding"]["embedding"][tokens]
    routings = []
    for i in range(len(d["kinds"])):
        x, routing = jax.checkpoint(functools.partial(
            _block, d, mm, i, window))(p[f"block{i}"], x)
        if _routed(d, i):
            routings.append(routing)
    return {"hidden": _rms(x, p["final_norm"], d["eps"]),
            "routing": routings}


def logits(cfg, params, tokens, quant=None, window: bool = True):
    """One sequence (L,) -> the untied head's (L, vocab) float32 logits."""
    mm = _products(quant)
    return mm("ld,dv->lv",
              hidden_rows(cfg, mm, params, tokens, window)["hidden"],
              params["params"]["lm_head"]["kernel"])


def sequence_loss(cfg, quant, rows, params, tokens, window: bool = True):
    """One sequence's part of the batch loss over ``rows`` sequences:
    ``(part, routing)``, already over the batch's count of targets, so that
    the parts of a batch add up to its loss."""
    L = tokens.shape[0]
    mm = _products(quant)
    out = hidden_rows(cfg, mm, params, tokens, window)
    logp = jax.nn.log_softmax(mm(
        "ld,dv->lv", out["hidden"],
        params["params"]["lm_head"]["kernel"]), -1)
    picked = jnp.take_along_axis(
        logp, jnp.roll(tokens, -1)[:, None], axis=1)[:, 0]
    nll = -jnp.sum(jnp.where(jnp.arange(L) < L - 1, picked, 0.0))
    return nll / (rows * (L - 1)), out["routing"]


# ----------------------------------------------------------------- training
def train_reference(cfg: Dict[str, Any], seed: int, tokens: np.ndarray, *,
                    steps: int, optimizer: Dict[str, Any],
                    quant: Optional[str] = None,
                    window: bool = True) -> Dict[str, Any]:
    """Follow the first ``steps`` AdamW steps from the seeded weights on
    ``tokens[s]`` (``(rows, L)`` int32, one batch per step), float32 at
    the highest matmul precision, one sequence at a time with the
    gradients summed; decay on leaves of two and more dimensions. The
    cut's 692M parameters are 11.1 GB of weights, gradient and moments;
    one sequence's float32 activations, a block at a time, have the rest
    (the 32 held experts' loop keeps no carry an expert: ``_experts``).

    Returns what ``lfm2_moe.train_reference`` returns, for the runner's
    ``compare``: per step the loss (``losses`` and ``main``; ``mtp`` is
    empty, there is no such head), the first gradient (leaves on the host,
    and their norms), the per-leaf norm of the parameters' change, step 0's
    routing per routed layer (``choice`` (rows * L, K), ``ranked`` (rows *
    L, E)), and ``timing`` in seconds.
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
        params = jax.block_until_ready(init(key))
        timed("init", t0)

        def add_grad(p, acc, toks):
            (part, routing), g = jax.value_and_grad(functools.partial(
                sequence_loss, cfg, quant, rows, window=window),
                has_aux=True)(p, toks)
            return (part, routing), jax.tree_util.tree_map(jnp.add, acc, g)
        grad_seq = jax.jit(add_grad, donate_argnums=(1,))
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))

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
        out: Dict[str, Any] = {"losses": [], "main": [], "mtp": []}
        for s in range(steps):
            total, grads, routing = 0.0, zeros(params), []
            for b in range(rows):
                t0 = time.perf_counter()
                (part, r), grads = grad_seq(
                    params, grads, jnp.asarray(tokens[s][b]))
                total += float(part)
                timed("other_sequences" if s or b else "first_sequence", t0)
                routing.append(r)
            out["losses"].append(total)
            out["main"].append(total)
            if s == 0:
                per_seq = [[(np.asarray(c), np.asarray(r)) for c, r in seq]
                           for seq in routing]
                out["grad_norms"] = {k: float(n) for k, n in
                                     jax.jit(leaf_norms)(grads).items()}
                t0 = time.perf_counter()
                out["first_grad"] = [np.asarray(x) for x in jax.device_get(
                    jax.tree_util.tree_leaves(grads))]
                timed("fetch", t0)
            del routing
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
    out["routing"] = [    # per routed layer, the batch's rows in order
        {"choice": np.concatenate([seq[i][0] for seq in per_seq]),
         "ranked": np.concatenate([seq[i][1] for seq in per_seq])}
        for i in range(len(per_seq[0]))]
    out["timing"] = {k: round(t, 3) for k, t in clock.items()}
    return out


# ---------------------------------------------- work, from shapes alone
def _band_pairs(length: float, window: float) -> float:
    """How many (query, key) pairs a band of ``window`` holds in a row of
    ``length``: ``L W - W (W - 1) / 2`` (the first ``W - 1`` queries see
    fewer keys than ``W``); the causal half's ``L (L + 1) / 2`` where the
    window covers the row."""
    w = min(window, length)
    return length * w - w * (w - 1.0) / 2.0


def _fwd_flops_per_token(cfg: Dict[str, Any],
                         length: int) -> Dict[str, float]:
    d = dims(cfg)
    dim, G, hd = d["dim"], d["kv_heads"], d["head"]
    mlp = 2.0 * 3 * dim * d["mlp"]
    routed = 2.0 * dim * d["experts"] + 2.0 * 3 * dim * d["shared"] \
        + d["top_k"] * d["held"] / d["experts"] * 2.0 * 3 * dim * d["expert"]
    out = {"projections": 0.0, "full": 0.0, "band": 0.0, "dense": 0.0,
           "routed": 0.0, "head": 2.0 * dim * d["vocab"]}
    for i, (kind, H) in enumerate(zip(d["kinds"], d["heads"])):
        out["projections"] += 2.0 * dim * (2 * H * hd + 2 * G * hd + H)
        # q.k^T and p.v over the keys a query sees on average: half the row
        # in a full layer (as the sibling references count it), the BAND
        # in a sliding one
        if kind == "full_attention":
            out["full"] += 2.0 * length / 2.0 * H * 2 * hd
        else:
            out["band"] += 2.0 * _band_pairs(length, d["window"]) / length \
                * H * 2 * hd
        out["routed" if _routed(d, i) else "dense"] += \
            routed if _routed(d, i) else mlp
    out["total"] = sum(out.values())
    return out


def train_flops_per_item(cfg: Dict[str, Any], length: int = 8192) -> float:
    """Matrix-multiplication and attention FLOPs that one packed row of
    ``length`` tokens requires, forward and backward (backward = 2 x
    forward; nothing recomputed counts): the projections and the gate, the
    causal half of the two attention products in a full layer and the BAND
    of them in a sliding one, the dense part's three matrices, the router,
    the shared expert, the EXPECTED routed work of the experts held here
    (``top_k * held / experts`` slots a token) and the head (the
    embedding's gather is no product). From shapes alone."""
    return 3.0 * length * _fwd_flops_per_token(cfg, length)["total"]


def _band_io(call: Dict[str, Any], arrays: float) -> float:
    return float(call["rows"]) * arrays * float(call["len"]) \
        * float(call["heads"]) * float(call["head_dim"]) * 2.0


def window_fwd_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One forward call of a band's attention on ``rows`` sequences of
    ``len`` tokens, ``heads`` x ``head_dim``: the two products over the
    band (``2 x 2 x (L W - W (W - 1) / 2) x H x D`` a row), and q, k, v
    read and o written once in bfloat16."""
    pairs = _band_pairs(float(call["len"]), float(call["window"]))
    return float(call["rows"]) * 4.0 * pairs * float(call["heads"]) \
        * float(call["head_dim"]), _band_io(call, 4.0)


def window_bwd_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One backward call: five products over the band where the forward
    has two (the scores again, dP, dV, dK, dQ: 2.5 times its FLOPs), and
    q, k, v and the cotangent read, dq, dk, dv written once in bfloat16."""
    flops, _ = window_fwd_cost(call)
    return 2.5 * flops, _band_io(call, 7.0)
