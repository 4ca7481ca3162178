"""Plain reference: SDAR-30B-A3B-Chat (``sdar_moe``), block-diffusion
training, float32.

Written from the published ``config.json`` (huggingface.co/JetLM/
SDAR-30B-A3B-Chat, ``model_type: sdar_moe``), SDAR (arXiv:2510.06303) and
the block-diffusion objective it trains by (BD3-LM, arXiv:2503.09573; the
linear schedule of MDLM, arXiv:2406.07524) in straightforward
``jax.numpy``: no kernels, no flax, nothing imported from the program (five
helpers that belong to no family come from a sibling reference). Pre-norm
decoder, no biases, every RMSNorm with a plain scale:

- a row is ``x = [x_t | x_0]``, ``2 L`` ids: ``x_0`` the ``L`` clean
  tokens, ``x_t`` their noised copy. Block ``b`` is positions ``b B .. b B
  + B - 1``; it draws ``t_b ~ U(eps, 1)`` and each of its tokens becomes
  the mask token independently with probability ``t_b`` (``noise``).
  Position ids are ``[0..L-1 | 0..L-1]``; ``h_0 = E[x]``;
- layer ``l``, all alike: ``h <- h + Attn(norm1(h))``, ``h <- h +
  F(norm2(h))``;
- ``Attn``: ``q`` on 32 heads, ``k``, ``v`` on 4, of 128; an RMS norm over
  EACH head's 128 channels of q and of k (one scale of 128 shared by the
  heads); rotary positions on all 128, half-split pairing, theta 1e6, at
  the position ids above; softmax of ``128^-1/2 q k^T`` under the
  block-diffusion mask with query head ``h`` reading key/value head ``h //
  8``, one query head at a time; through ``W_o``. The mask
  (``block_diffusion_seen``), with ``half(i)`` 0 for the noised copy and
  ``blk(i) = (i mod L) // B``: query ``i`` sees key ``j`` iff both are
  noised and ``blk(j) = blk(i)``, or ``i`` is noised, ``j`` clean and
  ``blk(j) < blk(i)``, or both are clean and ``blk(j) <= blk(i)``;
- ``F``: ``p = softmax(u W_r)`` over all 128; the choice its top 8;
  weights ``p`` at the chosen over their sum (``norm_topk_prob``); ``y =
  sum_k w_k E_k(u)``, ``E`` a SwiGLU of 768; no shared expert. Only the
  experts held here (16 of 128: this chip's share of an 8-way
  expert-parallel layer) add their part, in a dense loop over them, under
  the router's full 128-wide choice; a slot whose expert is held
  elsewhere adds nothing;
- a final norm; for the NOISED positions ``i < L`` only, ``loss = (1 /
  (rows L)) sum_i w_i CE(n_i W_head, x_0[i])``: no shift, ``w_i = 1 /
  t_blk(i)`` where ``x_t[i]`` is the mask token and 0 elsewhere. The clean
  half's last-layer output reaches no loss (its keys and values do).

Departures and sizes set here (the configuration file lists each under
``assumed``): block length 4; the linear schedule with ``eps`` 1e-3; the
unshifted target; the per-head norm with one shared scale (Qwen3-MoE's
block); the mask token is the last row of the vocabulary slice and clean
ids lie below it; **the gate takes no gradient** where the configuration
says so (``program.zoo_args.gate_grad`` false, as ``lfm2_moe`` and for its
reason); matrices, banks and both tables normal(0, 0.02), norm scales 1
but the two head norms', which start at 2.25 (``QK_NORM_SCALE_INIT``); a
packed row is one document; AdamW.

``quant`` rounds both operands of every matrix multiplication through a
lower precision, and ``mask="causal"`` puts a plain causal mask over the
``2 L`` positions in the block-diffusion mask's place: the two controls
that the comparison deciding ``correct`` has to fail.

**What is read of the configuration file**: ``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``moe_intermediate_size``, ``num_experts`` (HELD here),
``deployment.num_experts_published`` / ``.experts_first``,
``num_experts_per_tok``, ``norm_topk_prob`` (must be true),
``rope_theta``, ``rms_norm_eps``, ``vocab_size`` and
``program.zoo_args`` (``block_length``, ``gate_grad``); the runner reads
``program.zoo`` / ``.loss_chunk``, ``optimizer`` and ``limits``.

Also here, because the benchmark keeps them: what the runner asks a family
for (``zoo_args``, ``routed_blocks``, ``kernel_calls``, ``LOSS_PARTS``,
``AUX``, ``noise``, ``mask_token``), and the operations and bytes the two
masked attention calls need (``block_diffusion_fwd_cost`` / ``_bwd_cost``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# what is no family's own: the control's rounding of matmul operands, the
# plain-scale norm, per-leaf norms, seeded weights by shape (the sibling
# references'; nothing of the program's)
from benchmark.references.glm47_flash import (  # noqa: F401
    _is_shape, _products, _rms, leaf_norms)

INIT_STD = 0.02
# what the two head norms' scales start at (every other norm's at 1): the
# temperature of the attention logits. Unit-normed q and k of 128 give
# logits of unit variance, a near-uniform softmax over thousands of keys;
# four such layers in a row average every position's state into its row's
# mean, and from the second layer on every position of a row picks the same
# 8 experts (the configuration's ``assumed.init`` has the measurements). A
# trained checkpoint's learned scales sharpen the logits; 2.25 (logits of
# standard deviation 5) stands in: on the chip 1.5 still left a seed's load
# uneven and 2.25 evens it; no more than that, because bfloat16 against
# float32 reads wider the sharper the softmax is.
QK_NORM_SCALE_INIT = 2.25
LOSS_PARTS = ("main",)          # the heads of the loss, beside the whole
AUX = ("loss.main", "diffusion.masked_share", "moe.slots_here",
       "moe.load_max_over_mean", "moe.overflow_layers")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    if not cfg["norm_topk_prob"]:
        raise ValueError("this reference is the published layer: the chosen "
                         "scores over their sum")
    dep = cfg["deployment"]
    args = cfg["program"].get("zoo_args", {})
    return {
        "dim": int(cfg["hidden_size"]), "depth": int(cfg["num_hidden_layers"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head": int(cfg["head_dim"]),
        "expert": int(cfg["moe_intermediate_size"]),
        "experts": int(dep["num_experts_published"]),
        "held": int(cfg["num_experts"]), "first": int(dep["experts_first"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "gate_grad": bool(args.get("gate_grad", True)),
        "block": int(args["block_length"]),
        "vocab": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
    }


# ----------------------------------------------- what the runner asks for
def zoo_args(cfg: Dict[str, Any], length: int) -> Dict[str, Any]:
    """The configuration's published keys as the zoo entry's arguments, for
    rows of ``length`` clean tokens (the module sees ``2 x length``);
    ``program.zoo_args`` (``gate_grad``, ``block_length``) passes as it
    is."""
    d = dims(cfg)
    return dict(
        vocab=d["vocab"], dim=d["dim"], depth=d["depth"], heads=d["heads"],
        kv_heads=d["kv_heads"], head_dim=d["head"],
        expert_hidden=d["expert"], num_experts=d["experts"],
        top_k=d["top_k"], experts_held=(d["held"], d["first"]),
        theta=d["theta"], eps=d["eps"], max_len=2 * length,
        **cfg["program"].get("zoo_args", {}))


def routed_blocks(cfg: Dict[str, Any]) -> List[str]:
    """The blocks with a routed layer (all), in the order ``routing`` has."""
    return [f"block{i}" for i in range(dims(cfg)["depth"])]


def mask_token(cfg: Dict[str, Any]) -> int:
    """The mask token's row: the last of the vocabulary slice held here.
    Clean ids are drawn below it."""
    return int(cfg["vocab_size"]) - 1


def noise(seed: int, tokens: np.ndarray, block_length: int, eps: float,
          mask: int) -> Tuple[np.ndarray, np.ndarray]:
    """The collator's noise for clean rows ``tokens`` ``(N, L)``, from the
    seed alone: every block of ``block_length`` positions of every row
    draws ``t ~ U(eps, 1)``, each of its tokens becomes ``mask``
    independently with probability ``t``. Returns ``(noised (N, L) int32,
    weight (N, L) float32)``, the weight ``1 / t`` at a masked position and
    0 elsewhere. The program's runner and the reference call this one
    function, so both see the same noise."""
    n, length = tokens.shape
    if length % block_length:
        raise ValueError(f"rows of {length} in blocks of {block_length}")
    rng = np.random.default_rng([int(seed), 0x5DA2])
    t = rng.uniform(eps, 1.0, size=(n, length // block_length))
    t = np.repeat(t, block_length, axis=1)
    masked = rng.random((n, length)) < t
    return (np.where(masked, mask, tokens).astype(np.int32),
            np.where(masked, 1.0 / t, 0.0).astype(np.float32))


def kernel_calls(cfg: Dict[str, Any], rows: int, length: int,
                 slots: float) -> Dict[str, Dict[str, Any]]:
    """Shapes of the kernels' work: one call of the masked attention
    (``rows`` rows of ``length`` clean positions and as many noised), under
    a key for either pass, and one step's grouped products (in
    ``glm47_flash``'s keys, so that its cost function reads them)."""
    d = dims(cfg)
    call = {"rows": rows, "len": length, "block": d["block"],
            "heads": d["heads"], "head_dim": d["head"]}
    return {
        "block_diffusion_fwd": call, "block_diffusion_bwd": call,
        "expert_matmul": {"slots": slots, "dim": d["dim"],
                          "width": d["expert"], "held": d["held"],
                          "layers": d["depth"]}}


# ------------------------------------------------------------------ weights
def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree (``models/zoo/decoder.sdar_moe``), leaf
    shapes only."""
    d = dims(cfg)
    dim, H, G, hd = d["dim"], d["heads"], d["kv_heads"], d["head"]
    block = {
        "norm1": {"scale": (dim,)},
        "attn": {"attn_query": {"kernel": (dim, H * hd)},
                 "attn_key": {"kernel": (dim, G * hd)},
                 "attn_value": {"kernel": (dim, G * hd)},
                 "query_norm": {"scale": (hd,)}, "key_norm": {"scale": (hd,)},
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


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Weights from a PRNG key in the program's tree layout, float32:
    matrices, expert banks and both tables normal(0, 0.02); norm scales 1,
    but the two head norms' ``QK_NORM_SCALE_INIT``. Leaves of one shape
    are drawn in one call and dealt out in the tree's order, from XLA's own
    bit generator ("rbg": as ``glm47_flash.init_params``, and for its
    reasons). The key is an argument, never a constant of the program."""
    key = jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg")
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    names = [jax.tree_util.keystr(path) for path, _ in flat]
    by_shape: Dict[Tuple[int, ...], List[int]] = {}
    for i, n in enumerate(names):
        if "scale" not in n:
            by_shape.setdefault(flat[i][1], []).append(i)
    leaves: List[Any] = [None] * len(flat)
    for j, (shape, where) in enumerate(by_shape.items()):
        draws = INIT_STD * jax.random.normal(
            jax.random.fold_in(key, j), (len(where),) + shape, jnp.float32)
        for n, i in enumerate(where):
            leaves[i] = draws[n]
    for i, (_, shape) in enumerate(flat):
        if leaves[i] is None:
            head_norm = "query_norm" in names[i] or "key_norm" in names[i]
            leaves[i] = jnp.full(
                shape, QK_NORM_SCALE_INIT if head_norm else 1.0, jnp.float32)
    return jax.tree_util.tree_unflatten(tree, leaves)


# ------------------------------------------------------------------ forward
def block_diffusion_seen(length: int, block: int) -> jax.Array:
    """``(2 L, 2 L)`` bool: query ``i`` (rows) sees key ``j`` (columns) of
    a row ``[noised | clean]``, written out from the three cases."""
    i = jnp.arange(2 * length)
    clean, blk = i >= length, (i % length) // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    within = ~q_clean & ~k_clean & (k_blk == q_blk)
    before = ~q_clean & k_clean & (k_blk < q_blk)
    upto = q_clean & k_clean & (k_blk <= q_blk)
    return within | before | upto


def causal_seen(n: int) -> jax.Array:
    return jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]


def _rotary_at(x, theta, positions):
    """x (N, H, R): row ``n`` turns the pair (i, i + R/2) by
    ``positions[n] * theta**(-2i/R)``."""
    R = x.shape[-1]
    inv = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :R // 2], x[..., R // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _attention(d, mm, p, x, positions, seen):
    N, H, G, hd = x.shape[0], d["heads"], d["kv_heads"], d["head"]
    q = mm("ld,dk->lk", x, p["attn_query"]["kernel"]).reshape(N, H, hd)
    k = mm("ld,dk->lk", x, p["attn_key"]["kernel"]).reshape(N, G, hd)
    v = mm("ld,dk->lk", x, p["attn_value"]["kernel"]).reshape(N, G, hd)
    q = _rotary_at(_rms(q, p["query_norm"], d["eps"]), d["theta"], positions)
    k = _rotary_at(_rms(k, p["key_norm"], d["eps"]), d["theta"], positions)
    scale = 1.0 / np.sqrt(hd)
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    def head(args):             # one query head at a time: (N, N) scores
        q_h, group = args
        s = jnp.where(seen, mm("qk,nk->qn", q_h * scale, k[group]), -jnp.inf)
        return mm("qn,nk->qk", jax.nn.softmax(s, axis=-1), v[group])
    o = jax.lax.map(jax.checkpoint(head), (
        q.transpose(1, 0, 2), jnp.arange(H) // (H // G)))
    return mm("lk,kd->ld", o.transpose(1, 0, 2).reshape(N, H * hd),
              p["attn_out"]["kernel"])


def _experts(d, mm, p, x):
    """-> (y, routing): routing = (choice (N, K), softmax scores (N, E))."""
    s = jax.nn.softmax(mm("ld,de->le", x, p["router"]["kernel"]), axis=-1)
    choice = jax.lax.top_k(s, d["top_k"])[1]
    gate = jnp.take_along_axis(s, choice, axis=-1)
    gate = gate / gate.sum(-1, keepdims=True)
    if not d["gate_grad"]:
        gate = jax.lax.stop_gradient(gate)

    def one(y, bank):                   # dense: every token, no dispatch
        e, w_gate, w_up, w_down = bank
        w = jnp.sum(jnp.where(choice == d["first"] + e, gate, 0.0), -1)
        h = jax.nn.silu(mm("ld,dm->lm", x, w_gate)) \
            * mm("ld,dm->lm", x, w_up)
        return y + w[:, None] * mm("lm,md->ld", h, w_down), None
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x), (
        jnp.arange(d["held"]), p["experts_gate"], p["experts_up"],
        p["experts_down"]))
    return y, (choice, s)


def _block(d, mm, positions, seen, p, x):
    h = x + _attention(d, mm, p["attn"], _rms(x, p["norm1"], d["eps"]),
                       positions, seen)
    y, routing = _experts(d, mm, p["ffn"], _rms(h, p["norm2"], d["eps"]))
    return h + y, routing


def hidden_rows(cfg: Dict[str, Any], mm, params: Dict[str, Any],
                ids: jax.Array, positions: jax.Array,
                seen: jax.Array) -> Dict[str, Any]:
    """One row of ``ids`` (N,) at ``positions`` (N,) under the mask
    ``seen`` (N, N) -> the normed rows the head reads (``hidden`` (N,
    dim)) and the routing of every layer in order. A block-diffusion row
    is ``ids = [x_t | x_0]``, ``positions = [0..L-1 | 0..L-1]``, ``seen =
    block_diffusion_seen(L, B)``; a plain causal one ``arange`` and
    ``causal_seen``. Blocks are recomputed in the backward pass (that
    changes no value)."""
    d = dims(cfg)
    p = params["params"]
    x = p["token_embedding"]["embedding"][ids]
    routings = []
    for i in range(d["depth"]):
        x, routing = jax.checkpoint(functools.partial(
            _block, d, mm, positions, seen))(p[f"block{i}"], x)
        routings.append(routing)
    return {"hidden": _rms(x, p["final_norm"], d["eps"]),
            "routing": routings}


def diffusion_rows(cfg, mm, params, tokens, noised, mask=None):
    """``hidden_rows`` of the row ``[noised | tokens]``; ``mask="causal"``
    is the control's plain causal mask over the ``2 L`` positions."""
    L = tokens.shape[0]
    seen = causal_seen(2 * L) if mask == "causal" \
        else block_diffusion_seen(L, dims(cfg)["block"])
    return hidden_rows(cfg, mm, params, jnp.concatenate([noised, tokens]),
                       jnp.tile(jnp.arange(L), 2), seen)


def sequence_loss(cfg, quant, mask, rows, params, tokens, noised, weight):
    """One row's part of the batch loss over ``rows`` rows: ``(part,
    routing)``, already over the batch's count of positions, so that the
    parts of a batch add up to its loss. The noised half's logits alone,
    the clean token at the same position as the target, the weights as
    given."""
    L = tokens.shape[0]
    mm = _products(quant)
    out = diffusion_rows(cfg, mm, params, tokens, noised, mask)
    logp = jax.nn.log_softmax(mm(
        "ld,dv->lv", out["hidden"][:L], params["params"]["lm_head"]["kernel"]),
        -1)
    picked = jnp.take_along_axis(logp, tokens[:, None], axis=1)[:, 0]
    return -jnp.sum(weight * picked) / (rows * L), out["routing"]


# ----------------------------------------------------------------- training
def train_reference(cfg: Dict[str, Any], seed: int, tokens: np.ndarray,
                    noised: np.ndarray, weight: np.ndarray, *, steps: int,
                    optimizer: Dict[str, Any], quant: Optional[str] = None,
                    mask: Optional[str] = None) -> Dict[str, Any]:
    """Follow the first ``steps`` AdamW steps from the seeded weights on
    ``tokens[s]``, ``noised[s]`` (``(rows, L)`` int32) and ``weight[s]``
    (float32), one batch per step, the SAME noise the program was given;
    float32 at the highest matmul precision, one row at a time with the
    gradients summed; decay on leaves of two and more dimensions. The
    cut's 456M parameters are 7.3 GB of weights, gradient and moments,
    which leaves one row's float32 activations their room (a head's ``(2
    L, 2 L)`` scores at a time).

    Returns what ``lfm2_moe.train_reference`` returns, for the runner's
    ``compare``: per step the loss (``losses`` and ``main``; ``mtp`` is
    empty), the first gradient (leaves on the host, and their norms), the
    per-leaf norm of the parameters' change, step 0's routing per layer
    (``choice`` (rows * 2 L, K), ``ranked`` (rows * 2 L, E)), and
    ``timing`` in seconds.
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

        def add_grad(p, acc, *row):
            (part, routing), g = jax.value_and_grad(functools.partial(
                sequence_loss, cfg, quant, mask, rows), has_aux=True)(p, *row)
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
                    params, grads, jnp.asarray(tokens[s][b]),
                    jnp.asarray(noised[s][b]), jnp.asarray(weight[s][b]))
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
def live_pairs(length: int, block: int) -> int:
    """(query, key) pairs the block-diffusion mask leaves live on a row of
    ``length`` clean positions and as many noised: ``L^2 + L B`` of the
    ``(2 L)^2``, half of them a noised query's and half a clean one's."""
    return length * length + length * block


def _fwd_flops_per_item(cfg: Dict[str, Any], length: int) -> Dict[str, float]:
    d = dims(cfg)
    dim, H, G, hd = d["dim"], d["heads"], d["kv_heads"], d["head"]
    q_o = 2.0 * 2 * dim * H * hd            # a position: W_q and W_o
    k_v = 2.0 * 2 * dim * G * hd
    routed = 2.0 * dim * d["experts"] \
        + d["top_k"] * d["held"] / d["experts"] * 2.0 * 3 * dim * d["expert"]
    core = 2.0 * 2 * H * hd * live_pairs(length, d["block"])    # a row
    # every layer but the last: both halves through everything. The last:
    # the clean half's keys and values alone (its queries, its W_o and its
    # feed-forward part reach no loss), and the noised queries' pairs
    whole = 2 * length * (q_o + k_v + routed) + core
    last = length * (q_o + k_v + routed) + length * k_v + core / 2.0
    head = length * 2.0 * dim * d["vocab"]
    return {"layer": whole, "last_layer": last, "core": core, "head": head,
            "total": (d["depth"] - 1) * whole + last + head}


def train_flops_per_item(cfg: Dict[str, Any], length: int = 4096) -> float:
    """Matrix-multiplication and attention FLOPs that one item requires
    (a row of ``length`` clean tokens: ``2 x length`` positions through
    the layers), forward and backward (backward = 2 x forward; nothing
    recomputed counts), of what the loss DEPENDS on: the projections and
    the router on both halves, the two attention products over the LIVE
    pairs ``L^2 + L B`` and not the ``(2 L)^2`` of the square, the
    EXPECTED routed work of the experts held here (``top_k * held /
    experts`` slots a position), the head on the noised half alone; in the
    last layer the clean half counts its keys' and values' projections
    and nothing else, since its output reaches no loss. The embedding's
    gather is no product. From shapes alone."""
    return 3.0 * _fwd_flops_per_item(cfg, length)["total"]


def block_diffusion_fwd_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One forward call of the masked attention on ``rows`` rows of
    ``len`` clean positions and as many noised, ``heads`` x ``head_dim``:
    the two products over the live pairs (``rows x 4 x (L^2 + L B) x H x
    D``), and q, k, v read and o written once in bfloat16 (``2 L``
    positions each)."""
    rows, L = float(call["rows"]), int(call["len"])
    hd = float(call["heads"]) * float(call["head_dim"])
    return rows * 4.0 * live_pairs(L, int(call["block"])) * hd, \
        rows * 4.0 * 2.0 * L * hd * 2.0


def block_diffusion_bwd_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One backward call: five products over the live pairs (the scores
    again, dP, dV, dK, dQ: 2.5 times the forward's FLOPs), and q, k, v and
    the cotangent read and dq, dk, dv written once in bfloat16."""
    flops, nbytes = block_diffusion_fwd_cost(call)
    return 2.5 * flops, nbytes * 7.0 / 4.0
