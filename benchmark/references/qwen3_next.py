"""Plain reference: Qwen3-Next-80B-A3B (``qwen3_next``), training, float32.

Written from the published ``config.json`` (huggingface.co/Qwen/
Qwen3-Next-80B-A3B-Instruct; the linear layers are Gated DeltaNet, Yang et
al. 2024, arXiv:2412.06464) in straightforward ``jax.numpy``: no kernels,
no flax, nothing imported from the program (four helpers that belong to
no family come from the sibling reference). Pre-norm decoder, no biases,
every RMSNorm with scale ``1 + w`` (``w`` = 0 at init) but the gated one:

- block ``l``: ``h = x + mixer_l(norm(x))``, ``y = h + moe(norm(h))``; the
  mixer is softmax attention when ``(l + 1) % 4 == 0``, else Gated DeltaNet;
- Gated DeltaNet: ``[q | k | v | z] = x W_qkvz``, ``[b | a] = x W_ba``;
  ``[q | k | v]`` through a causal depthwise convolution of width 4 (three
  zeros before the row) and ``silu``; ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)``; q and k L2-normalised over the head,
  q times ``dk^-1/2``, key head ``h // 2`` serving value head ``h``; per
  value head, TOKEN BY TOKEN: ``S <- exp(g_t) S``, ``u = beta_t (v_t - S^T
  k_t)``, ``S <- S + k_t u^T``, ``o_t = S^T q_t`` (a scan over chunks of
  tokens whose inner loop is rematerialised, so that 4,096 steps of a 2 MB
  state fit; that changes no value); ``o <- rmsnorm(o) w_n silu(z)`` over
  each head; through ``W_o``;
- gated attention: ``[q | gate] = x W_q`` (halves per head), ``k``, ``v``
  on 2 heads; RMSNorm (``1 + w``) over every q and k head; rotary positions
  on the first quarter of the head; causal softmax of ``q k^T / sqrt(d)``
  with query head ``h`` reading key/value head ``h // 8``; ``o <- o
  sigmoid(gate)``; through ``W_o``;
- expert layer: ``p = softmax(x W_r)`` over all 512, the top 10, weights
  ``p[chosen] / sum``; plus ``sigmoid(x w_g) shared(x)``. Only the experts
  held here (32 of 512: this chip's share of a 16-way expert-parallel
  layer) add their part, in a dense loop over them; a slot whose expert is
  held elsewhere adds nothing;
- final norm, untied head, next-token cross-entropy. No multi-token-
  prediction module: the catalogued ``config.json`` has no key for it.

Departures and sizes set here (the configuration file lists each under
``assumed``): no MTP; ``A_log = log(U(1e-3, 16))``, ``dt_bias`` = 1,
matrices, banks, tables and the convolution normal(0, 0.02); columns of
``W_qkvz`` in ``[q | k | v | z]`` order, head-major; rotary pairs ``(i, i +
32)`` of the first 64; a packed row is one document; AdamW.

``quant`` rounds both operands of every matrix multiplication (the delta
rule's products among them) through a lower precision: the control that
the comparison deciding ``correct`` has to fail.

Also here, because the benchmark keeps them: what the runner asks a family
for (``zoo_args``, ``routed_blocks``, ``kernel_calls``, ``LOSS_PARTS``),
and the operations and bytes of the delta rule's walk
(``delta_rule_cost``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# what is no family's own: the control's rounding of matmul operands, the
# SwiGLU part, per-leaf norms (the sibling reference's; nothing of the
# program's)
from benchmark.references.glm47_flash import (  # noqa: F401
    _is_shape, _products, _swiglu, leaf_norms)

INIT_STD = 0.02
CHUNK = 64
LOSS_PARTS = ("main",)          # the heads of the loss, beside the whole
AUX = ("loss.main", "moe.slots_here", "moe.load_max_over_mean")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    dep = cfg["deployment"]
    head = int(cfg["head_dim"])
    return {
        "dim": int(cfg["hidden_size"]), "depth": int(cfg["num_hidden_layers"]),
        "interval": int(cfg["full_attention_interval"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]), "head": head,
        "rotary": int(head * float(cfg["partial_rotary_factor"])),
        "lk_heads": int(cfg["linear_num_key_heads"]),
        "lv_heads": int(cfg["linear_num_value_heads"]),
        "lk": int(cfg["linear_key_head_dim"]),
        "lv": int(cfg["linear_value_head_dim"]),
        "conv": int(cfg["linear_conv_kernel_dim"]),
        "expert": int(cfg["moe_intermediate_size"]),
        "shared": int(cfg["shared_expert_intermediate_size"]),
        "experts": int(dep["num_experts_published"]),
        "held": int(cfg["num_experts"]), "first": int(dep["experts_first"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "vocab": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
    }


def softmax_layer(d: Dict[str, Any], index: int) -> bool:
    return (index + 1) % d["interval"] == 0


# ----------------------------------------------- what the runner asks for
def zoo_args(cfg: Dict[str, Any], length: int) -> Dict[str, Any]:
    """The configuration's published keys as the zoo entry's arguments."""
    d = dims(cfg)
    return dict(
        vocab=d["vocab"], dim=d["dim"], depth=d["depth"], heads=d["heads"],
        kv_heads=d["kv_heads"], head_dim=d["head"],
        rotary_fraction=float(cfg["partial_rotary_factor"]),
        linear_key_heads=d["lk_heads"], linear_value_heads=d["lv_heads"],
        linear_key_dim=d["lk"], linear_value_dim=d["lv"],
        conv_width=d["conv"], expert_hidden=d["expert"],
        shared_hidden=d["shared"], num_experts=d["experts"],
        top_k=d["top_k"], experts_held=(d["held"], d["first"]),
        attention_interval=d["interval"], theta=d["theta"], eps=d["eps"],
        chunk=_chunk(cfg), max_len=length,
        **cfg["program"].get("zoo_args", {}))


def _chunk(cfg: Dict[str, Any]) -> int:
    return int(cfg["program"].get("chunk", CHUNK))


def routed_blocks(cfg: Dict[str, Any]) -> List[str]:
    """The blocks with a routed layer, in the order ``routing`` has."""
    return [f"block{i}" for i in range(int(cfg["num_hidden_layers"]))]


def kernel_calls(cfg: Dict[str, Any], rows: int, length: int,
                 slots: float) -> Dict[str, Dict[str, Any]]:
    """Shapes of the kernels' work: one forward call of the flash kernel,
    one step's grouped products, one step's walks of the delta rule."""
    d = dims(cfg)
    linear = sum(not softmax_layer(d, i) for i in range(d["depth"]))
    return {
        "flash_fwd": {"rows": rows, "len": length, "heads": d["heads"],
                      "head_dim": d["head"]},
        "expert_matmul": {"slots": slots, "dim": d["dim"],
                          "width": d["expert"], "held": d["held"],
                          "layers": d["depth"]},
        "delta_rule": {"rows": rows, "len": length, "heads": d["lv_heads"],
                       "key_dim": d["lk"], "value_dim": d["lv"],
                       "chunk": _chunk(cfg),
                       "layers": linear}}


# ------------------------------------------------------------------ weights
def _block_shapes(d, index: int) -> Dict[str, Any]:
    dim = d["dim"]
    if softmax_layer(d, index):
        H, G, hd = d["heads"], d["kv_heads"], d["head"]
        attn = {"attn_query_gate": {"kernel": (dim, H * 2 * hd)},
                "attn_key": {"kernel": (dim, G * hd)},
                "attn_value": {"kernel": (dim, G * hd)},
                "query_norm": {"scale": (hd,)}, "key_norm": {"scale": (hd,)},
                "attn_out": {"kernel": (H * hd, dim)}}
    else:
        qk, vv = d["lk_heads"] * d["lk"], d["lv_heads"] * d["lv"]
        attn = {"attn_qkvz": {"kernel": (dim, 2 * qk + 2 * vv)},
                "attn_ba": {"kernel": (dim, 2 * d["lv_heads"])},
                "conv_kernel": (d["conv"], 2 * qk + vv),
                "A_log": (d["lv_heads"],), "dt_bias": (d["lv_heads"],),
                "gate_norm": {"scale": (d["lv"],)},
                "attn_out": {"kernel": (vv, dim)}}
    ffn = {"router": {"kernel": (dim, d["experts"])},
           "experts_gate": (d["held"], dim, d["expert"]),
           "experts_up": (d["held"], dim, d["expert"]),
           "experts_down": (d["held"], d["expert"], dim),
           "shared": {"mlp_gate": {"kernel": (dim, d["shared"])},
                      "mlp_up": {"kernel": (dim, d["shared"])},
                      "mlp_down": {"kernel": (d["shared"], dim)}},
           "shared_gate": {"kernel": (dim, 1)}}
    return {"norm1": {"scale": (dim,)}, "attn": attn,
            "norm2": {"scale": (dim,)}, "ffn": ffn}


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree (``models/zoo/decoder.Qwen3Next``),
    leaf shapes only."""
    d = dims(cfg)
    p = {"token_embedding": {"embedding": (d["vocab"], d["dim"])},
         "final_norm": {"scale": (d["dim"],)},
         "lm_head": {"kernel": (d["dim"], d["vocab"])}}
    for i in range(d["depth"]):
        p[f"block{i}"] = _block_shapes(d, i)
    return {"params": p}


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Weights from a PRNG key in the program's tree layout, float32:
    matrices, expert banks, tables and the convolution normal(0, 0.02);
    ``1 + w`` norms ``w`` = 0, the gated norm's scale 1, ``dt_bias`` 1,
    ``A_log = log(U(1e-3, 16))``. Leaves of one shape are drawn in one call
    and dealt out in the tree's order, from XLA's own bit generator
    ("rbg": as ``glm47_flash.init_params``, and for its reasons). The key
    is an argument, never a constant of the program."""
    key = jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg")
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    names = [jax.tree_util.keystr(path) for path, _ in flat]
    fixed = ("scale", "dt_bias", "A_log")
    drawn = [i for i, n in enumerate(names)
             if not any(f in n for f in fixed)]
    by_shape: Dict[Tuple[int, ...], List[int]] = {}
    for i in drawn:
        by_shape.setdefault(flat[i][1], []).append(i)
    leaves: List[Any] = [None] * len(flat)
    for j, (shape, where) in enumerate(by_shape.items()):
        draws = INIT_STD * jax.random.normal(
            jax.random.fold_in(key, j), (len(where),) + shape, jnp.float32)
        for n, i in enumerate(where):
            leaves[i] = draws[n]
    decays = [i for i, n in enumerate(names) if "A_log" in n]
    a = jax.random.uniform(
        jax.random.fold_in(key, len(by_shape)),
        (len(decays),) + flat[decays[0]][1], jnp.float32, 1e-3, 16.0)
    for n, i in enumerate(decays):
        leaves[i] = jnp.log(a[n])
    for i, (_, shape) in enumerate(flat):
        if leaves[i] is None:
            ones = "gate_norm" in names[i] or "dt_bias" in names[i]
            leaves[i] = (jnp.ones if ones else jnp.zeros)(shape, jnp.float32)
    return jax.tree_util.tree_unflatten(tree, leaves)


# ------------------------------------------------------------------ forward
def _rms(x, p, eps, offset=True):
    scale = 1.0 + p["scale"] if offset else p["scale"]
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotary(x, theta, width):
    """x (L, H, D): position l turns the pair (i, i + width/2) of the
    first ``width`` dimensions by l * theta**(-2i/width); the rest pass."""
    L = x.shape[0]
    inv = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :width // 2], x[..., width // 2:width]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang),
                            x[..., width:]], -1)


def _attention(d, mm, p, x):
    L, H, G, hd = x.shape[0], d["heads"], d["kv_heads"], d["head"]
    qg = mm("ld,dk->lk", x, p["attn_query_gate"]["kernel"]).reshape(
        L, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = mm("ld,dk->lk", x, p["attn_key"]["kernel"]).reshape(L, G, hd)
    v = mm("ld,dk->lk", x, p["attn_value"]["kernel"]).reshape(L, G, hd)
    q = _rotary(_rms(q, p["query_norm"], d["eps"]), d["theta"], d["rotary"])
    k = _rotary(_rms(k, p["key_norm"], d["eps"]), d["theta"], d["rotary"])
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
    o = o.transpose(1, 0, 2) * jax.nn.sigmoid(gate)
    return mm("lk,kd->ld", o.reshape(L, H * hd), p["attn_out"]["kernel"])


def _conv(x, kernel):
    """Causal depthwise: y_t = sum_j kernel[j] x_{t - (W-1) + j}."""
    width, L = kernel.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    return sum(xp[j:j + L] * kernel[j] for j in range(width))


def _delta_rule(mm, q, k, v, g, beta, block: int = CHUNK):
    """Token by token. q, k (L, H, dk), v (L, H, dv), g, beta (L, H)."""
    L, H, dk = q.shape
    pad = -L % block
    xs = tuple(jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
        (-1, block) + x.shape[1:]) for x in (q, k, v, g, beta))

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[:, None, None] * S
        u = b_t[:, None] * (v_t - mm("hkv,hk->hv", S, k_t))
        S = S + mm("hk,hv->hkv", k_t, u)
        return S, mm("hkv,hk->hv", S, q_t)

    @jax.checkpoint
    def tokens(S, x):
        return jax.lax.scan(token, S, x)
    _, o = jax.lax.scan(tokens, jnp.zeros((H, dk, v.shape[-1])), xs)
    return o.reshape((-1,) + o.shape[2:])[:L]


def _delta_net(d, mm, p, x):
    L = x.shape[0]
    Hk, Hv, dk, dv = d["lk_heads"], d["lv_heads"], d["lk"], d["lv"]
    qkvz = mm("ld,dk->lk", x, p["attn_qkvz"]["kernel"])
    ba = mm("ld,dk->lk", x, p["attn_ba"]["kernel"])
    mixed = jax.nn.silu(_conv(qkvz[:, :2 * Hk * dk + Hv * dv],
                              p["conv_kernel"]))
    z = qkvz[:, 2 * Hk * dk + Hv * dv:].reshape(L, Hv, dv)
    q = mixed[:, :Hk * dk].reshape(L, Hk, dk)
    k = mixed[:, Hk * dk:2 * Hk * dk].reshape(L, Hk, dk)
    v = mixed[:, 2 * Hk * dk:].reshape(L, Hv, dv)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, Hv:] + p["dt_bias"])

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    serves = jnp.arange(Hv) // (Hv // Hk)      # value head -> its key head
    q, k = unit(q)[:, serves] / np.sqrt(dk), unit(k)[:, serves]
    o = _delta_rule(mm, q, k, v, g, beta)
    o = _rms(o, p["gate_norm"], d["eps"], offset=False) * jax.nn.silu(z)
    return mm("lk,kd->ld", o.reshape(L, Hv * dv), p["attn_out"]["kernel"])


def _experts(d, mm, p, x):
    """-> (y, routing): routing = (choice (L, K), probabilities (L, E))."""
    ranked = jax.nn.softmax(mm("ld,de->le", x, p["router"]["kernel"]), -1)
    gate, choice = jax.lax.top_k(ranked, d["top_k"])
    gate = gate / gate.sum(-1, keepdims=True)

    def one(y, bank):                          # dense: every token, no dispatch
        e, w_gate, w_up, w_down = bank
        w = jnp.sum(jnp.where(choice == d["first"] + e, gate, 0.0), -1)
        h = jax.nn.silu(mm("ld,dm->lm", x, w_gate)) \
            * mm("ld,dm->lm", x, w_up)
        return y + w[:, None] * mm("lm,md->ld", h, w_down), None
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x), (
        jnp.arange(d["held"]), p["experts_gate"], p["experts_up"],
        p["experts_down"]))
    shared = jax.nn.sigmoid(mm("ld,do->lo", x, p["shared_gate"]["kernel"])) \
        * _swiglu(mm, p["shared"], x)
    return y + shared, (choice, ranked)


def _block(d, mm, index, p, x):
    mixer = _attention if softmax_layer(d, index) else _delta_net
    h = x + mixer(d, mm, p["attn"], _rms(x, p["norm1"], d["eps"]))
    y, routing = _experts(d, mm, p["ffn"], _rms(h, p["norm2"], d["eps"]))
    return h + y, routing


def hidden_rows(cfg: Dict[str, Any], mm, params: Dict[str, Any],
                tokens: jax.Array) -> Dict[str, Any]:
    """One sequence ``tokens`` (L,) -> the normed rows the head reads
    (``hidden`` (L, dim)) and the routing of every layer in order."""
    d = dims(cfg)
    p = params["params"]
    x = p["token_embedding"]["embedding"][tokens]
    routings = []
    for i in range(d["depth"]):
        x, routing = jax.checkpoint(functools.partial(_block, d, mm, i))(
            p[f"block{i}"], x)
        routings.append(routing)
    return {"hidden": _rms(x, p["final_norm"], d["eps"]),
            "routing": routings}


def logits(cfg, params, tokens, quant=None):
    """One sequence (L,) -> the head's (L, vocab) float32 logits."""
    mm = _products(quant)
    return mm("ld,dv->lv", hidden_rows(cfg, mm, params, tokens)["hidden"],
              params["params"]["lm_head"]["kernel"])


def sequence_loss(cfg, quant, rows, params, tokens):
    """One sequence's part of the batch loss over ``rows`` sequences:
    ``(part, routing)``, already over the batch's count of targets, so that
    the parts of a batch add up to its loss."""
    L = tokens.shape[0]
    mm = _products(quant)
    out = hidden_rows(cfg, mm, params, tokens)
    logp = jax.nn.log_softmax(mm(
        "ld,dv->lv", out["hidden"], params["params"]["lm_head"]["kernel"]), -1)
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
    gradients summed; decay on leaves of two and more dimensions.

    Returns what ``glm47_flash.train_reference`` returns, for the runner's
    ``compare``: per step the loss (``losses`` and ``main``; ``mtp`` is
    empty, there is no such head), the first gradient (leaves on the host,
    and their norms), the per-leaf norm of the parameters' change, step 0's
    routing per layer (``choice`` (rows * L, K), ``ranked`` (rows * L, E)),
    and ``timing`` in seconds.
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
    out["routing"] = [    # per layer, the batch's rows in order
        {"choice": np.concatenate([seq[i][0] for seq in per_seq]),
         "ranked": np.concatenate([seq[i][1] for seq in per_seq])}
        for i in range(len(per_seq[0]))]
    out["timing"] = {k: round(t, 3) for k, t in clock.items()}
    return out


# ---------------------------------------------- work, from shapes alone
def delta_rule_flops_per_token(d: Dict[str, Any], chunk: int = CHUNK
                               ) -> Dict[str, float]:
    """Forward FLOPs a token of ONE Gated DeltaNet layer's delta rule in
    its chunked form, all value heads: inside a chunk ``K K^T``, ``Q K^T``,
    ``T (beta exp(G) K)`` and ``(Q K^T * D) U`` (2 x chunk x 128 each), ``T
    (beta V)`` (2 x chunk x 128) and making ``T`` (counted as one more
    chunk x chunk x chunk product, 2 x chunk x chunk: forward substitution's
    own count is a third of it); against the state ``W S``, ``Q S`` and
    ``K^T U`` (2 x 128 x 128 each). ``walk`` is the part the scan from
    chunk to chunk holds (``W S`` and ``K^T U``)."""
    H, dk, dv = d["lv_heads"], d["lk"], d["lv"]
    inside = 2.0 * chunk * (3 * dk + 2 * dv + chunk)
    walk = 2.0 * 2 * dk * dv
    return {"total": H * (inside + walk + 2.0 * dk * dv), "walk": H * walk}


def _fwd_flops_per_token(cfg: Dict[str, Any], length: int) -> Dict[str, float]:
    d = dims(cfg)
    dim = d["dim"]
    qk, vv = d["lk_heads"] * d["lk"], d["lv_heads"] * d["lv"]
    delta_net = 2.0 * (dim * (2 * qk + 2 * vv) + dim * 2 * d["lv_heads"]
                       + d["conv"] * (2 * qk + vv) + vv * dim) \
        + delta_rule_flops_per_token(d)["total"]
    H, G, hd = d["heads"], d["kv_heads"], d["head"]
    # causal: a query sees half the keys on average; q.k^T and p.v
    attention = 2.0 * (dim * H * 2 * hd + 2 * dim * G * hd + H * hd * dim) \
        + 2.0 * length / 2.0 * H * 2 * hd
    routed = 2.0 * dim * d["experts"] + 2.0 * 3 * dim * d["shared"] \
        + 2.0 * dim \
        + d["top_k"] * d["held"] / d["experts"] * 2.0 * 3 * dim * d["expert"]
    soft = sum(softmax_layer(d, i) for i in range(d["depth"]))
    return {"delta_net": delta_net, "attention": attention, "routed": routed,
            "head": 2.0 * dim * d["vocab"],
            "total": (d["depth"] - soft) * delta_net + soft * attention
            + d["depth"] * routed + 2.0 * dim * d["vocab"]}


def train_flops_per_item(cfg: Dict[str, Any], length: int = 4096) -> float:
    """Matrix-multiplication, attention and delta-rule FLOPs that one
    packed row of ``length`` tokens requires, forward and backward
    (backward = 2 x forward; nothing recomputed counts): the projections,
    the convolution, the chunked delta rule's products at chunk 64, the
    causal half of the two attention products, the router, the gated
    shared expert, the EXPECTED routed work of the experts held here
    (``top_k * held / experts`` slots a token) and the head. From shapes
    alone."""
    return 3.0 * length * _fwd_flops_per_token(cfg, length)["total"]


def delta_rule_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One training step's walks of the state from chunk to chunk (the
    scan under the scope ``gated_delta_rule``, which is what the device
    trace can name): forward ``W S`` and ``K^T U`` for every chunk, head,
    row and layer (4 x chunk x dk x dv FLOPs a chunk) and twice that for
    the backward pass, recomputation not counted; and the bytes those
    three passes cannot avoid: forward reads ``W`` and ``exp(G_C - G) K``
    (bfloat16), ``U_0`` (float32) and writes ``U`` and the chunk's
    starting state (float32); the backward pass reads ``W``, ``exp(G_C -
    G) K``, ``U`` and the starting state again with the cotangents of the
    last two, and writes the gradients of ``W``, ``exp(G_C - G) K`` and
    ``U_0`` in float32."""
    chunks = float(call["layers"]) * float(call["rows"]) \
        * float(call["heads"]) * float(call["len"]) / float(call["chunk"])
    C, dk, dv = (float(call[k]) for k in ("chunk", "key_dim", "value_dim"))
    forward = 2 * C * dk * 2 + 2 * C * dv * 4 + dk * dv * 4
    backward = 2 * C * dk * 2 + (C * dv + dk * dv) * 4 \
        + (C * dv + dk * dv) * 4 + (2 * C * dk + C * dv) * 4
    return chunks * 3.0 * 4.0 * C * dk * dv, chunks * (forward + backward)
