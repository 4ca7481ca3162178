"""Plain reference: GLM-4.7-Flash (``glm4_moe_lite``), training, float32.

Written from the published description (huggingface.co/zai-org/
GLM-4.7-Flash ``config.json``; the layers are DeepSeek-V3's, arXiv
2412.19437) in straightforward ``jax.numpy``: no kernels, no flax, nothing
imported from the program. Pre-norm decoder (RMSNorm, no biases):

- block: ``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``;
- MLA, expanded form: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> heads
  x (no-position | rotary); ``[c_kv | k_r] = x W_kva``, ``c_kv =
  RMSNorm(c_kv)``, ``[k_nope | v] = c_kv W_kvb``; rotary positions on the
  query's rotary slice and on the ONE rotary key all heads share; causal
  softmax of ``q k^T / sqrt(nope + rope)`` times ``v``, through ``W_o``;
- layer 0: SwiGLU ``W_down(silu(W_gate x) * W_up x)``;
- expert layers: ``s = sigmoid(x W_r)`` over all 64, the top 4 of ``s + b``
  chosen, weights ``s[chosen] / sum * 1.8``, plus the shared expert. Only
  the experts held here (8 of 64: this chip's share of an 8-way expert
  parallel layer) add their part, in a dense loop over them; a slot whose
  expert is held elsewhere adds nothing;
- multi-token prediction, depth 1: ``W_eh [RMSNorm(h_i) ; RMSNorm(Emb(
  t_{i+1}))]`` through one more block, the final norm and the shared head,
  predicting ``t_{i+2}``; ``loss = CE_main + 0.3 CE_mtp``.

Departures from the published description and sizes set here (the
configuration file lists each under ``assumed``): rotary pairs are
``(i, i + 32)`` (half split); ``W_eh`` reads the trunk's state first; the
MTP weight 0.3; ``b`` = 0 and never updated; AdamW; weights normal(0,
0.02); a packed row is one document; the final norm is shared by both
heads. Blocks are recomputed in the backward pass (``jax.checkpoint``) so
that 4,096 x 4,096 float32 scores of six blocks need not live at once:
recomputation changes no value.

``quant`` rounds both operands of every matrix multiplication through a
lower precision: the control that the comparison deciding ``correct`` has
to fail.

Also here, because the benchmark keeps them: the operations and bytes the
new kernels need, from shapes (``flash_fwd_cost``, ``expert_matmul_cost``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MTP_WEIGHT = 0.3
INIT_STD = 0.02


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    dep = cfg["deployment"]
    return {
        "dim": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "q_rank": int(cfg["q_lora_rank"]), "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]), "v": int(cfg["v_head_dim"]),
        "mlp": int(cfg["intermediate_size"]),
        "expert": int(cfg["moe_intermediate_size"]),
        "experts": int(dep["n_routed_experts_published"]),
        "held": int(cfg["n_routed_experts"]),
        "first": int(dep["experts_first"]),
        "shared": int(cfg["n_shared_experts"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "scaling": float(cfg["routed_scaling_factor"]),
        "depth": int(cfg["num_hidden_layers"]),
        "dense": int(cfg["first_k_dense_replace"]),
        "mtp": int(cfg["num_nextn_predict_layers"]),
        "vocab": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
    }


# ------------------------------------------------------------------ weights
def _block_shapes(d, routed: bool) -> Dict[str, Any]:
    dim, H = d["dim"], d["heads"]
    attn = {
        "attn_query_a": {"kernel": (dim, d["q_rank"])},
        "query_norm": {"scale": (d["q_rank"],)},
        "attn_query_b": {"kernel": (d["q_rank"], H * (d["nope"] + d["rope"]))},
        "attn_key_value_a": {"kernel": (dim, d["kv_rank"] + d["rope"])},
        "key_value_norm": {"scale": (d["kv_rank"],)},
        "attn_key_value_b": {"kernel": (d["kv_rank"],
                                        H * (d["nope"] + d["v"]))},
        "attn_out": {"kernel": (H * d["v"], dim)},
    }

    def swiglu(width):
        return {"mlp_gate": {"kernel": (dim, width)},
                "mlp_up": {"kernel": (dim, width)},
                "mlp_down": {"kernel": (width, dim)}}
    if routed:
        ffn = {"router": {"kernel": (dim, d["experts"])},
               "router_bias": (d["experts"],),
               "experts_gate": (d["held"], dim, d["expert"]),
               "experts_up": (d["held"], dim, d["expert"]),
               "experts_down": (d["held"], d["expert"], dim)}
        if d["shared"]:
            ffn["shared"] = swiglu(d["shared"] * d["expert"])
    else:
        ffn = swiglu(d["mlp"])
    return {"norm1": {"scale": (dim,)}, "attn": attn,
            "norm2": {"scale": (dim,)}, "ffn": ffn}


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree (``models/zoo/decoder.Glm4MoeLite``),
    leaf shapes only."""
    d = dims(cfg)
    p = {"token_embedding": {"embedding": (d["vocab"], d["dim"])},
         "final_norm": {"scale": (d["dim"],)},
         "lm_head": {"kernel": (d["dim"], d["vocab"])}}
    for i in range(d["depth"]):
        p[f"block{i}"] = _block_shapes(d, routed=i >= d["dense"])
    if d["mtp"]:
        p["mtp_hnorm"] = {"scale": (d["dim"],)}
        p["mtp_enorm"] = {"scale": (d["dim"],)}
        p["mtp_eh_proj"] = {"kernel": (2 * d["dim"], d["dim"])}
        p["mtp_block"] = _block_shapes(d, routed=True)
    return {"params": p}


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Weights from a PRNG key in the program's tree layout, float32:
    matrices, expert banks and the embedding normal(0, 0.02), norm scales
    1, the router's bias 0. Leaves of one shape (the blocks repeat) are
    drawn in one call and dealt out in the tree's order, from XLA's own
    bit generator (jax's "rbg" keys: the chip compiles it in 6 s where
    sixty threefry draws of this size take 22 to 30 s each time the
    program is built; the values differ between backends, which nothing
    here relies on: program and reference draw on the same device). The
    key is an argument, never a constant of the program: every seed runs
    the same compiled code."""
    key = jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg")
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    names = [jax.tree_util.keystr(path) for path, _ in flat]
    drawn = [i for i, n in enumerate(names)
             if "scale" not in n and "router_bias" not in n]
    by_shape: Dict[Tuple[int, ...], List[int]] = {}
    for i in drawn:
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
def _rounder(quant: Optional[str]):
    """Round a matmul operand through a lower precision, straight through
    (the backward pass sees the rounded operands, its cotangents stay
    float32: cast through fp8 they would underflow to zero)."""
    if quant is None:
        return lambda x: x
    dtype = {"fp8": jnp.float8_e4m3fn, "bf16": jnp.bfloat16}.get(quant)
    if dtype is None:
        raise ValueError(f"unknown control precision {quant!r}")
    return lambda x: x + jax.lax.stop_gradient(
        x.astype(dtype).astype(jnp.float32) - x)


def _rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _rotary(x, theta):
    """x (L, H, R): position l turns the pair (i, i + R/2) by
    l * theta**(-2i/R)."""
    L, R = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :R // 2], x[..., R // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _mla(d, mm, p, x):
    L, H = x.shape[0], d["heads"]
    cq = _rms(mm("ld,dr->lr", x, p["attn_query_a"]["kernel"]),
              p["query_norm"], d["eps"])
    q = mm("lr,rk->lk", cq, p["attn_query_b"]["kernel"]).reshape(
        L, H, d["nope"] + d["rope"])
    kva = mm("ld,dr->lr", x, p["attn_key_value_a"]["kernel"])
    ckv = _rms(kva[:, :d["kv_rank"]], p["key_value_norm"], d["eps"])
    kv = mm("lr,rk->lk", ckv, p["attn_key_value_b"]["kernel"]).reshape(
        L, H, d["nope"] + d["v"])
    q = jnp.concatenate([q[..., :d["nope"]],
                         _rotary(q[..., d["nope"]:], d["theta"])], -1)
    k_r = _rotary(kva[:, None, d["kv_rank"]:], d["theta"])
    k = jnp.concatenate([kv[..., :d["nope"]],
                         jnp.broadcast_to(k_r, (L, H, d["rope"]))], -1)
    scale = 1.0 / np.sqrt(d["nope"] + d["rope"])
    future = jnp.arange(L)[None, :] > jnp.arange(L)[:, None]

    def head(qkv):              # one head at a time: (L, L) scores
        q_h, k_h, v_h = qkv
        s = jnp.where(future, -jnp.inf, mm("qk,nk->qn", q_h * scale, k_h))
        return mm("qn,nk->qk", jax.nn.softmax(s, axis=-1), v_h)
    o = jax.lax.map(jax.checkpoint(head), tuple(
        t.transpose(1, 0, 2) for t in (q, k, kv[..., d["nope"]:])))
    return mm("lk,kd->ld", o.transpose(1, 0, 2).reshape(L, H * d["v"]),
              p["attn_out"]["kernel"])


def _swiglu(mm, p, x):
    h = jax.nn.silu(mm("ld,dm->lm", x, p["mlp_gate"]["kernel"])) \
        * mm("ld,dm->lm", x, p["mlp_up"]["kernel"])
    return mm("lm,md->ld", h, p["mlp_down"]["kernel"])


def _experts(d, mm, p, x):
    """-> (y, routing): routing = (choice (L, K), scores + bias (L, E))."""
    s = jax.nn.sigmoid(mm("ld,de->le", x, p["router"]["kernel"]))
    ranked = s + p["router_bias"]
    choice = jax.lax.top_k(ranked, d["top_k"])[1]
    gate = jnp.take_along_axis(s, choice, axis=-1)
    gate = gate / gate.sum(-1, keepdims=True) * d["scaling"]

    def one(y, bank):                          # dense: every token, no dispatch
        e, w_gate, w_up, w_down = bank
        w = jnp.sum(jnp.where(choice == d["first"] + e, gate, 0.0), -1)
        h = jax.nn.silu(mm("ld,dm->lm", x, w_gate)) \
            * mm("ld,dm->lm", x, w_up)
        return y + w[:, None] * mm("lm,md->ld", h, w_down), None
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x), (
        jnp.arange(d["held"]), p["experts_gate"], p["experts_up"],
        p["experts_down"]))
    if d["shared"]:
        y = y + _swiglu(mm, p["shared"], x)
    return y, (choice, ranked)


def _block(d, mm, routed, p, x):
    h = x + _mla(d, mm, p["attn"], _rms(x, p["norm1"], d["eps"]))
    z = _rms(h, p["norm2"], d["eps"])
    if not routed:
        return h + _swiglu(mm, p["ffn"], z), None
    y, routing = _experts(d, mm, p["ffn"], z)
    return h + y, routing


def _products(quant: Optional[str]):
    """``einsum`` with both operands through the rounder."""
    r = _rounder(quant)
    return lambda eq, a, b: jnp.einsum(eq, r(a), r(b))


def hidden_rows(cfg: Dict[str, Any], mm, params: Dict[str, Any],
                tokens: jax.Array) -> Dict[str, Any]:
    """One sequence ``tokens`` (L,) -> the normed rows each head reads
    (``hidden``, ``mtp_hidden``: (L, dim)) and the routing of every routed
    layer in order (the MTP block's last). ``mm`` is ``_products(quant)``."""
    d = dims(cfg)
    p = params["params"]
    routings: List[Any] = []

    def run(name, routed, x):
        x, routing = jax.checkpoint(
            functools.partial(_block, d, mm, routed))(p[name], x)
        if routed:
            routings.append(routing)
        return x

    emb = p["token_embedding"]["embedding"]
    x = emb[tokens]
    for i in range(d["depth"]):
        x = run(f"block{i}", i >= d["dense"], x)
    out = {"hidden": _rms(x, p["final_norm"], d["eps"])}
    if d["mtp"]:
        z = jnp.concatenate(
            [_rms(x, p["mtp_hnorm"], d["eps"]),
             _rms(emb[jnp.roll(tokens, -1)], p["mtp_enorm"], d["eps"])], -1)
        z = mm("lk,kd->ld", z, p["mtp_eh_proj"]["kernel"])
        z = run("mtp_block", True, z)
        out["mtp_hidden"] = _rms(z, p["final_norm"], d["eps"])
    out["routing"] = routings
    return out


def logits(cfg, params, tokens, quant=None):
    """One sequence (L,) -> the main head's (L, vocab) float32 logits."""
    mm = _products(quant)
    return mm("ld,dv->lv", hidden_rows(cfg, mm, params, tokens)["hidden"],
              params["params"]["lm_head"]["kernel"])


def _nll_sum(mm, hidden, kernel, tokens, ahead):
    """Sum over the rows that have a target ``ahead`` tokens on."""
    L = tokens.shape[0]
    logp = jax.nn.log_softmax(mm("ld,dv->lv", hidden, kernel), axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.roll(tokens, -ahead)[:, None], axis=1)[:, 0]
    return -jnp.sum(jnp.where(jnp.arange(L) < L - ahead, picked, 0.0))


def sequence_loss(cfg, quant, rows, params, tokens):
    """One sequence's part of the batch loss over ``rows`` sequences:
    ``(part, (main, mtp, routing))``, each already over the batch's count
    of targets, so that the parts of a batch add up to its loss."""
    L = tokens.shape[0]
    mm = _products(quant)
    out = hidden_rows(cfg, mm, params, tokens)
    kernel = params["params"]["lm_head"]["kernel"]
    main = _nll_sum(mm, out["hidden"], kernel, tokens, 1) / (rows * (L - 1))
    mtp = jnp.zeros(())
    if "mtp_hidden" in out:
        mtp = _nll_sum(mm, out["mtp_hidden"], kernel, tokens, 2) \
            / (rows * (L - 2))
    return main + MTP_WEIGHT * mtp, (main, mtp, out["routing"])


# ----------------------------------------------------------------- training
def leaf_norms(tree) -> Dict[str, jax.Array]:
    flat = jax.tree_util.tree_leaves_with_path(tree)
    return {jax.tree_util.keystr(path): jnp.sqrt(
        jnp.sum(jnp.square(leaf.astype(jnp.float32))))
        for path, leaf in flat}


def _decayed(leaf) -> bool:
    return leaf.ndim >= 2          # matrices, banks, tables; not scales


def train_reference(cfg: Dict[str, Any], seed: int, tokens: np.ndarray, *,
                    steps: int, optimizer: Dict[str, Any],
                    quant: Optional[str] = None) -> Dict[str, Any]:
    """Follow the first ``steps`` AdamW steps from the seeded weights on
    ``tokens[s]`` (``(rows, L)`` int32, one batch per step), float32 at
    the highest matmul precision, one sequence at a time with the
    gradients summed. Weights, AdamW's two moments and the gradient live
    on the device (16 B a parameter, as in the program) beside one
    sequence's activations; one program computes a sequence's gradient.

    Returns per step the loss and its two parts, the first gradient (its
    leaves on the host, and their norms), the per-leaf norm of the
    parameters' change after the last step, step 0's routing per routed
    layer (``choice`` (rows * L, K) and ``ranked`` (rows * L, E), the
    scores the choice was the top of), and ``timing`` in seconds.
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
            (part, aux), g = jax.value_and_grad(functools.partial(
                sequence_loss, cfg, quant, rows), has_aux=True)(p, toks)
            return (part, aux), jax.tree_util.tree_map(jnp.add, acc, g)
        grad_seq = jax.jit(add_grad, donate_argnums=(1,))
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def adamw(params, m, v, g, t):
            def leaf(p, m, v, g):
                m = b1 * m + (1 - b1) * g
                v = b2 * v + (1 - b2) * g * g
                step = (m / (1 - b1 ** t)) / (
                    jnp.sqrt(v / (1 - b2 ** t)) + eps)
                if _decayed(p):
                    step = step + decay * p
                return p - lr * step, m, v
            out = jax.tree_util.tree_map(leaf, params, m, v, g)
            return tuple(jax.tree_util.tree_map(
                lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
                for i in range(3))

        m = v = None
        out: Dict[str, Any] = {"losses": [], "main": [], "mtp": []}
        for s in range(steps):
            # the batch's loss and gradient: the sum of its sequences' parts
            total, grads, routing = np.zeros(3), zeros(params), []
            for b in range(rows):
                t0 = time.perf_counter()
                (part, (main, mtp, r)), grads = grad_seq(
                    params, grads, jnp.asarray(tokens[s][b]))
                total += [float(part), float(main), float(mtp)]
                timed("other_sequences" if s or b else "first_sequence", t0)
                routing.append(r)
            out["losses"].append(total[0])
            out["main"].append(total[1])
            out["mtp"].append(total[2])
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
def _fwd_flops_per_token(cfg: Dict[str, Any], length: int) -> Dict[str, float]:
    d = dims(cfg)
    dim, H = d["dim"], d["heads"]
    qk = d["nope"] + d["rope"]
    mla = 2.0 * (dim * d["q_rank"] + d["q_rank"] * H * qk
                 + dim * (d["kv_rank"] + d["rope"])
                 + d["kv_rank"] * H * (d["nope"] + d["v"])
                 + H * d["v"] * dim)
    # causal: a query sees half the keys on average; q.k^T and p.v
    attention = 2.0 * length / 2.0 * H * (qk + d["v"])
    expert = 2.0 * 3 * dim * d["expert"]
    routed = (2.0 * dim * d["experts"] + d["shared"] * expert
              # the expected slots a token sends to the experts held here
              + d["top_k"] * d["held"] / d["experts"] * expert)
    blocks = d["dense"] * (mla + attention + 2.0 * 3 * dim * d["mlp"]) \
        + (d["depth"] - d["dense"]) * (mla + attention + routed)
    heads = 2.0 * dim * d["vocab"]
    if d["mtp"]:
        blocks += 2.0 * 2 * dim * dim + mla + attention + routed
        heads *= 2
    layers = d["depth"] + d["mtp"]
    return {"total": blocks + heads, "attention": layers * attention}


def train_flops_per_item(cfg: Dict[str, Any], length: int = 4096) -> float:
    """Matrix-multiplication and attention FLOPs that one packed row of
    ``length`` tokens requires, forward and backward (backward = 2 x
    forward; nothing recomputed counts): the projections, the causal half
    of the two attention products, the dense and shared feed-forward
    layers, the router, the EXPECTED routed work of the experts held here
    (``top_k * held / experts`` slots a token), the MTP module and both
    heads. From shapes alone."""
    return 3.0 * length * _fwd_flops_per_token(cfg, length)["total"]


def flash_fwd_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One forward call of causal attention on ``rows`` sequences of
    ``len`` tokens, ``heads`` x ``head_dim``: the two products over the
    causal half (2 x 2 x L^2/2 x H x D a row), and q, k, v read and o
    written once in bfloat16."""
    rows, L = float(call["rows"]), float(call["len"])
    hd = float(call["heads"]) * float(call["head_dim"])
    return rows * 2.0 * 2.0 * L * L / 2.0 * hd, rows * 4.0 * L * hd * 2.0


def expert_matmul_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One training step's grouped products over the experts held here:
    gate, up and down forward (6 x dim x width FLOPs a slot) and their two
    backward products each (12 more), ``slots`` being the token-slots the
    routers sent here over all routed layers; every bank read in bfloat16
    forward and for the input gradient and its gradient written once, and
    a slot's rows read or written at each of the nine products."""
    slots, dim, width = (float(call[k]) for k in ("slots", "dim", "width"))
    banks = float(call["layers"]) * float(call["held"]) * 3.0 * dim * width
    return 18.0 * slots * dim * width, \
        3.0 * banks * 2.0 + 9.0 * slots * (dim + width) * 2.0
