"""Plain reference: LFM2-24B-A2B (``lfm2_moe``), training, float32.

Written from the published ``config.json`` (huggingface.co/LiquidAI/
LFM2-24B-A2B, ``model_type: lfm2_moe``) in straightforward ``jax.numpy``:
no kernels, no flax, nothing imported from the program (six helpers that
belong to no family come from a sibling reference). Pre-norm decoder, no
biases, every RMSNorm with a plain scale, ``D`` = ``hidden_size``:

- ``h_0 = E[token]``; layer ``l``: ``h <- h + op_l(norm1(h))``, ``h <- h +
  ffn_l(norm2(h))``; ``op_l`` read off ``layer_types[l]``, ``ffn_l`` off
  ``l < num_dense_layers``: the two kinds vary independently;
- ``conv``, the gated short convolution: ``[B | C | x] = u W_in`` (``D ->
  3 D``); ``z = B * x``; ``c_t = k_0 z_{t-2} + k_1 z_{t-1} + k_2 z_t``
  (depthwise, causal, ``conv_L_cache`` = 3 taps written out as three
  shifted products, zeros before the row's start, no bias, NO activation);
  ``y = (C * c) W_out``;
- ``full_attention``: ``q`` on 32 heads, ``k``, ``v`` on 8, of 64; an RMS
  norm over EACH head's 64 channels of q and of k (one scale of 64 shared
  by the heads); rotary positions on all 64, half-split pairing, theta
  1e6; causal softmax of ``64^-1/2 q k^T`` with query head ``h`` reading
  key/value head ``h // 4``, one query head at a time; through ``W_o``;
- the feed-forward part, ``l < num_dense_layers``: ``W_2 (silu(W_1 u) * W_3
  u)``, 11,776 wide. Else the routed layer: ``s = sigmoid(u W_r)`` over
  all 64; the choice is the top 4 of ``s + b`` (``use_expert_bias``);
  weights ``s`` at the chosen over ``(their sum + 1e-6)``
  (``norm_topk_prob``), times ``routed_scaling_factor`` = 1; ``y = sum_k
  w_k E_k(u)``, ``E`` a SwiGLU of 1,536; no shared expert. Only the
  experts held here (8 of 64: this chip's share of an 8-way expert-
  parallel layer) add their part, in a dense loop over them, under the
  router's full 64-wide choice; a slot whose expert is held elsewhere adds
  nothing;
- a final norm; ``logits = h E^T`` with the SAME table ``E``; next-token
  cross-entropy.

Departures and sizes set here (the configuration file lists each under
``assumed``): the tied table; head width 64 = 2048 / 32; the per-head norm
with one shared scale; rotary pairs ``(i, i + 32)``; columns of ``W_in``
in ``[B | C | x]`` order; ``b`` = 0 and never updated; **the gate takes no
gradient** where the configuration says so (``program.zoo_args.gate_grad``
false: the weights ``w_k`` are constants of the backward pass, so the
router's kernel and the tokens get no gradient through the scores; a
share of the experts trained without its exchange cannot compute the
scores' true gradient, which needs all four chosen experts' outputs);
matrices, banks, the table and the taps normal(0, 0.02), norm scales 1; a
packed row is one document; AdamW.

``quant`` rounds both operands of every matrix multiplication through a
lower precision: the control that the comparison deciding ``correct`` has
to fail.

**What is read of the configuration file**: ``hidden_size``,
``num_hidden_layers`` and ``layer_types`` (as long), ``num_dense_layers``,
``intermediate_size``, ``moe_intermediate_size``, ``num_experts`` (HELD
here), ``deployment.num_experts_published`` / ``.experts_first``,
``num_experts_per_tok``, ``routed_scaling_factor``, ``norm_topk_prob``,
``use_expert_bias``, ``num_attention_heads``, ``num_key_value_heads``,
``rope_parameters.rope_theta``, ``norm_eps``, ``conv_L_cache``,
``conv_bias`` (must be false), ``vocab_size`` and ``program.zoo_args``;
the runner reads ``program.zoo`` / ``.loss_chunk``, ``optimizer`` and
``limits``.

Also here, because the benchmark keeps them: what the runner asks a family
for (``zoo_args``, ``routed_blocks``, ``head_kernel``, ``kernel_calls``,
``LOSS_PARTS``, ``AUX``), and the operations and bytes the gated
convolution needs between its two projections (``short_conv_cost``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# what is no family's own: the control's rounding of matmul operands, the
# SwiGLU part, the plain-scale norm, whole-head rotary positions, per-leaf
# norms (the sibling reference's; nothing of the program's)
from benchmark.references.glm47_flash import (  # noqa: F401
    _is_shape, _products, _rms, _rotary, _swiglu, leaf_norms)

INIT_STD = 0.02
WEIGHT_EPS = 1e-6               # in the routing weights' denominator
LOSS_PARTS = ("main",)          # the heads of the loss, beside the whole
AUX = ("loss.main", "moe.slots_here", "moe.load_max_over_mean",
       "moe.overflow_layers")   # the ring's scalars beside the loss


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != int(cfg["num_hidden_layers"]) \
            or set(kinds) - {"conv", "full_attention"}:
        raise ValueError(f"layer_types {kinds!r} against "
                         f"{cfg['num_hidden_layers']} layers of 'conv' or "
                         "'full_attention'")
    if cfg["conv_bias"] or not cfg["norm_topk_prob"] \
            or not cfg["use_expert_bias"]:
        raise ValueError("this reference is the published layer: no bias on "
                         "the convolution, normalised top-k weights, a "
                         "router bias")
    dep = cfg["deployment"]
    dim, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "dim": dim, "kinds": kinds, "dense": int(cfg["num_dense_layers"]),
        "heads": heads, "kv_heads": int(cfg["num_key_value_heads"]),
        "head": dim // heads, "taps": int(cfg["conv_L_cache"]),
        "mlp": int(cfg["intermediate_size"]),
        "expert": int(cfg["moe_intermediate_size"]),
        "experts": int(dep["num_experts_published"]),
        "held": int(cfg["num_experts"]), "first": int(dep["experts_first"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "scaling": float(cfg["routed_scaling_factor"]),
        "gate_grad": bool(
            cfg["program"].get("zoo_args", {}).get("gate_grad", True)),
        "vocab": int(cfg["vocab_size"]), "eps": float(cfg["norm_eps"]),
        "theta": float(cfg["rope_parameters"]["rope_theta"]),
    }


def _routed(d: Dict[str, Any], index: int) -> bool:
    return index >= d["dense"]


# ----------------------------------------------- what the runner asks for
def zoo_args(cfg: Dict[str, Any], length: int) -> Dict[str, Any]:
    """The configuration's published keys as the zoo entry's arguments;
    ``program.zoo_args`` (``gate_grad``) passes as it is."""
    d = dims(cfg)
    return dict(
        vocab=d["vocab"], dim=d["dim"], layer_types=d["kinds"],
        heads=d["heads"], kv_heads=d["kv_heads"], head_dim=d["head"],
        mlp_hidden=d["mlp"], expert_hidden=d["expert"],
        num_experts=d["experts"], top_k=d["top_k"],
        experts_held=(d["held"], d["first"]), dense_layers=d["dense"],
        conv_taps=d["taps"], scaling=d["scaling"], weight_eps=WEIGHT_EPS,
        theta=d["theta"], eps=d["eps"], max_len=length,
        **cfg["program"].get("zoo_args", {}))


def routed_blocks(cfg: Dict[str, Any]) -> List[str]:
    """The blocks with a routed layer, in the order ``routing`` has."""
    d = dims(cfg)
    return [f"block{i}" for i in range(len(d["kinds"])) if _routed(d, i)]


def head_kernel(params: Dict[str, Any]) -> jax.Array:
    """The output matrix ``(dim, vocab)`` the chunked loss reads: the one
    table, transposed."""
    return params["params"]["token_embedding"]["embedding"].T


def kernel_calls(cfg: Dict[str, Any], rows: int, length: int,
                 slots: float) -> Dict[str, Dict[str, Any]]:
    """Shapes of the kernels' work: one forward call of the flash kernel,
    one step's grouped products (both in ``glm47_flash``'s keys, so that
    its cost functions read them), one step's gated convolutions."""
    d = dims(cfg)
    return {
        "flash_fwd": {"rows": rows, "len": length, "heads": d["heads"],
                      "head_dim": d["head"]},
        "expert_matmul": {"slots": slots, "dim": d["dim"],
                          "width": d["expert"], "held": d["held"],
                          "layers": len(routed_blocks(cfg))},
        "short_conv": {"rows": rows, "len": length, "dim": d["dim"],
                       "taps": d["taps"],
                       "layers": d["kinds"].count("conv")}}


# ------------------------------------------------------------------ weights
def _block_shapes(d, index: int) -> Dict[str, Any]:
    dim = d["dim"]
    if d["kinds"][index] == "conv":
        attn = {"attn_in": {"kernel": (dim, 3 * dim)},
                "conv_kernel": (d["taps"], dim),
                "attn_out": {"kernel": (dim, dim)}}
    else:
        H, G, hd = d["heads"], d["kv_heads"], d["head"]
        attn = {"attn_query": {"kernel": (dim, H * hd)},
                "attn_key": {"kernel": (dim, G * hd)},
                "attn_value": {"kernel": (dim, G * hd)},
                "query_norm": {"scale": (hd,)}, "key_norm": {"scale": (hd,)},
                "attn_out": {"kernel": (H * hd, dim)}}
    if _routed(d, index):
        ffn = {"router": {"kernel": (dim, d["experts"])},
               "router_bias": (d["experts"],),
               "experts_gate": (d["held"], dim, d["expert"]),
               "experts_up": (d["held"], dim, d["expert"]),
               "experts_down": (d["held"], d["expert"], dim)}
    else:
        ffn = {"mlp_gate": {"kernel": (dim, d["mlp"])},
               "mlp_up": {"kernel": (dim, d["mlp"])},
               "mlp_down": {"kernel": (d["mlp"], dim)}}
    return {"norm1": {"scale": (dim,)}, "attn": attn,
            "norm2": {"scale": (dim,)}, "ffn": ffn}


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree (``models/zoo/decoder.Lfm2Moe``), leaf
    shapes only. One table: there is no ``lm_head``."""
    d = dims(cfg)
    p = {"token_embedding": {"embedding": (d["vocab"], d["dim"])},
         "final_norm": {"scale": (d["dim"],)}}
    for i in range(len(d["kinds"])):
        p[f"block{i}"] = _block_shapes(d, i)
    return {"params": p}


def parameters(cfg: Dict[str, Any]) -> int:
    """How many parameters the cut holds (the file's ``parameters_here``)."""
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=_is_shape))


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Weights from a PRNG key in the program's tree layout, float32:
    matrices, expert banks, the table and the taps normal(0, 0.02); norm
    scales 1; the router's bias 0. Leaves of one shape are drawn in one
    call and dealt out in the tree's order, from XLA's own bit generator
    ("rbg": as ``glm47_flash.init_params``, and for its reasons). The key
    is an argument, never a constant of the program."""
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
def _short_conv(d, mm, p, u):
    """``u`` (L, D) -> (L, D): the three taps as three shifted products."""
    L, dim = u.shape
    bcx = mm("ld,dk->lk", u, p["attn_in"]["kernel"])
    B, C, x = bcx[:, :dim], bcx[:, dim:2 * dim], bcx[:, 2 * dim:]
    z = B * x
    k = p["conv_kernel"]
    if k.shape[0] != 3:
        raise ValueError(f"{k.shape[0]} taps: the published layer has 3")
    none = jnp.zeros((1, dim), z.dtype)
    z1 = jnp.concatenate([none, z[:-1]])            # z_{t-1}
    z2 = jnp.concatenate([none, none, z[:-2]])      # z_{t-2}
    c = k[0] * z2 + k[1] * z1 + k[2] * z
    return mm("lk,kd->ld", C * c, p["attn_out"]["kernel"])


def _attention(d, mm, p, x):
    L, H, G, hd = x.shape[0], d["heads"], d["kv_heads"], d["head"]
    q = mm("ld,dk->lk", x, p["attn_query"]["kernel"]).reshape(L, H, hd)
    k = mm("ld,dk->lk", x, p["attn_key"]["kernel"]).reshape(L, G, hd)
    v = mm("ld,dk->lk", x, p["attn_value"]["kernel"]).reshape(L, G, hd)
    q = _rotary(_rms(q, p["query_norm"], d["eps"]), d["theta"])
    k = _rotary(_rms(k, p["key_norm"], d["eps"]), d["theta"])
    scale = 1.0 / np.sqrt(hd)
    future = jnp.arange(L)[None, :] > jnp.arange(L)[:, None]
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    def head(args):             # one query head at a time: (L, L) scores
        q_h, group = args
        s = jnp.where(future, -jnp.inf,
                      mm("qk,nk->qn", q_h * scale, k[group]))
        return mm("qn,nk->qk", jax.nn.softmax(s, axis=-1), v[group])
    o = jax.lax.map(jax.checkpoint(head), (
        q.transpose(1, 0, 2), jnp.arange(H) // (H // G)))
    return mm("lk,kd->ld", o.transpose(1, 0, 2).reshape(L, H * hd),
              p["attn_out"]["kernel"])


def _experts(d, mm, p, x):
    """-> (y, routing): routing = (choice (L, K), scores + bias (L, E))."""
    s = jax.nn.sigmoid(mm("ld,de->le", x, p["router"]["kernel"]))
    ranked = s + p["router_bias"]
    choice = jax.lax.top_k(ranked, d["top_k"])[1]
    gate = jnp.take_along_axis(s, choice, axis=-1)
    gate = gate / (gate.sum(-1, keepdims=True) + WEIGHT_EPS) * d["scaling"]
    if not d["gate_grad"]:
        gate = jax.lax.stop_gradient(gate)

    def one(y, bank):                          # dense: every token, no dispatch
        e, w_gate, w_up, w_down = bank
        w = jnp.sum(jnp.where(choice == d["first"] + e, gate, 0.0), -1)
        h = jax.nn.silu(mm("ld,dm->lm", x, w_gate)) \
            * mm("ld,dm->lm", x, w_up)
        return y + w[:, None] * mm("lm,md->ld", h, w_down), None
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x), (
        jnp.arange(d["held"]), p["experts_gate"], p["experts_up"],
        p["experts_down"]))
    return y, (choice, ranked)


def _block(d, mm, index, p, x):
    mixer = _short_conv if d["kinds"][index] == "conv" else _attention
    h = x + mixer(d, mm, p["attn"], _rms(x, p["norm1"], d["eps"]))
    z = _rms(h, p["norm2"], d["eps"])
    if not _routed(d, index):
        return h + _swiglu(mm, p["ffn"], z), None
    y, routing = _experts(d, mm, p["ffn"], z)
    return h + y, routing


def hidden_rows(cfg: Dict[str, Any], mm, params: Dict[str, Any],
                tokens: jax.Array) -> Dict[str, Any]:
    """One sequence ``tokens`` (L,) -> the normed rows the head reads
    (``hidden`` (L, dim)) and the routing of every routed layer in order.
    Blocks are recomputed in the backward pass (that changes no value)."""
    d = dims(cfg)
    p = params["params"]
    x = p["token_embedding"]["embedding"][tokens]
    routings = []
    for i in range(len(d["kinds"])):
        x, routing = jax.checkpoint(functools.partial(_block, d, mm, i))(
            p[f"block{i}"], x)
        if _routed(d, i):
            routings.append(routing)
    return {"hidden": _rms(x, p["final_norm"], d["eps"]),
            "routing": routings}


def logits(cfg, params, tokens, quant=None):
    """One sequence (L,) -> the tied head's (L, vocab) float32 logits."""
    mm = _products(quant)
    return mm("ld,vd->lv", hidden_rows(cfg, mm, params, tokens)["hidden"],
              params["params"]["token_embedding"]["embedding"])


def sequence_loss(cfg, quant, rows, params, tokens):
    """One sequence's part of the batch loss over ``rows`` sequences:
    ``(part, routing)``, already over the batch's count of targets, so that
    the parts of a batch add up to its loss."""
    L = tokens.shape[0]
    mm = _products(quant)
    out = hidden_rows(cfg, mm, params, tokens)
    logp = jax.nn.log_softmax(mm(
        "ld,vd->lv", out["hidden"],
        params["params"]["token_embedding"]["embedding"]), -1)
    picked = jnp.take_along_axis(
        logp, jnp.roll(tokens, -1)[:, None], axis=1)[:, 0]
    nll = -jnp.sum(jnp.where(jnp.arange(L) < L - 1, picked, 0.0))
    return nll / (rows * (L - 1)), out["routing"]


# ----------------------------------------------------------------- training
def train_reference(cfg: Dict[str, Any], seed: int, tokens: np.ndarray, *,
                    steps: int, optimizer: Dict[str, Any],
                    quant: Optional[str] = None) -> Dict[str, Any]:
    """Follow the first ``steps`` AdamW steps from the seeded weights on
    ``tokens[s]`` (``(rows, L)`` int32, one batch per step), float32 at
    the highest matmul precision, one sequence at a time with the
    gradients summed; decay on leaves of two and more dimensions. The
    cut's 469M parameters are 7.5 GB of weights, gradient and moments,
    which leaves one sequence's float32 activations their room: the
    gradient is whole on the device, no walk by halves is needed.

    Returns what ``qwen3_next.train_reference`` returns, for the runner's
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
                sequence_loss, cfg, quant, rows), has_aux=True)(p, toks)
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
def _fwd_flops_per_token(cfg: Dict[str, Any], length: int) -> Dict[str, float]:
    d = dims(cfg)
    dim = d["dim"]
    conv = 2.0 * (dim * 3 * dim + d["taps"] * dim + dim * dim)
    H, G, hd = d["heads"], d["kv_heads"], d["head"]
    # causal: a query sees half the keys on average; q.k^T and p.v
    attention = 2.0 * (2 * dim * H * hd + 2 * dim * G * hd) \
        + 2.0 * length / 2.0 * H * 2 * hd
    mlp = 2.0 * 3 * dim * d["mlp"]
    routed = 2.0 * dim * d["experts"] \
        + d["top_k"] * d["held"] / d["experts"] * 2.0 * 3 * dim * d["expert"]
    total = 2.0 * dim * d["vocab"]
    for i, kind in enumerate(d["kinds"]):
        total += (conv if kind == "conv" else attention) \
            + (routed if _routed(d, i) else mlp)
    return {"conv": conv, "attention": attention, "mlp": mlp,
            "routed": routed, "head": 2.0 * dim * d["vocab"], "total": total}


def train_flops_per_item(cfg: Dict[str, Any], length: int = 8192) -> float:
    """Matrix-multiplication, convolution and attention FLOPs that one
    packed row of ``length`` tokens requires, forward and backward
    (backward = 2 x forward; nothing recomputed counts): the projections,
    the three taps, the causal half of the two attention products, the
    dense part's three matrices, the router, the EXPECTED routed work of
    the experts held here (``top_k * held / experts`` slots a token) and
    the head (the embedding's gather is no product). From shapes alone."""
    return 3.0 * length * _fwd_flops_per_token(cfg, length)["total"]


def short_conv_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One training step's REQUIRED work between the gated convolution's
    two projections (the scope ``short_conv/gate_conv``), whatever
    implements it, a token and layer in bfloat16 rows of ``dim``. Forward:
    ``[B | C | x]`` read (3 rows), ``C * c`` written (1). Once more for
    the block's recomputation, whose ``C * c`` the output projection's
    weight gradient reads. Backward: ``B``, ``C``, ``x`` and the cotangent
    of ``C * c`` read (4), the three cotangents written (3); ``c`` is made
    again on the way, and the taps' gradient is a reduction of what is
    read anyway. 15 rows of 2 x dim bytes, which is the bound; FLOPs: a
    multiply a gate and two a tap forward (2 + 2 x taps a channel), three
    times that over the three passes."""
    tokens = float(call["layers"]) * float(call["rows"]) * float(call["len"])
    dim, taps = float(call["dim"]), float(call["taps"])
    return tokens * 3.0 * (2.0 + 2.0 * taps) * dim, tokens * 15.0 * dim * 2.0
