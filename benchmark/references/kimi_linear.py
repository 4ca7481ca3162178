"""Plain reference: Kimi-Linear-48B-A3B-Instruct (``kimi_linear``),
training, float32.

Written from the published ``config.json`` (huggingface.co/moonshotai/
Kimi-Linear-48B-A3B-Instruct, ``model_type: kimi_linear``) and the paper
("Kimi Linear: An Expressive, Efficient Attention Architecture",
arXiv:2510.26692) in straightforward ``jax.numpy``: no kernels, no flax,
nothing imported from the program (helpers that belong to no family come
from the sibling references). Pre-norm decoder, no biases, every RMSNorm
with a plain scale, ``D`` = ``hidden_size`` 2304. Layers are numbered from 1
in ``linear_attn_config``:

- ``h_0 = E[token]``; layer ``l``: ``h <- h + Mix_l(norm1(h))``, ``h <- h +
  F_l(norm2(h))``; ``Mix_l`` is Kimi Delta Attention where ``l`` is in
  ``kda_layers`` and latent attention where it is in ``full_attn_layers``;
- **KDA** (``num_heads`` 32 of ``head_dim`` 128 for keys and values): ``q =
  silu(conv_q(x W_q))``, and so ``k``, ``v``: three matrices, three causal
  depthwise convolutions of ``short_conv_kernel_size`` 4 (zeros before the
  row); q and k L2-normalised over the head (eps 1e-6 inside the root), q
  times ``128^-1/2``; the decay ``g_t = -exp(A_log_h) softplus((x_t W_fa)
  W_fb + dt_bias)``, (H, 128) a token, every entry <= 0; ``beta_t =
  sigmoid(x_t W_b)`` a head; per head a state ``S`` (128, 128), zero at the
  row's start, TOKEN BY TOKEN (``kda_rule``)::

      S   <- Diag(exp(g_t)) S              # row c of S times exp(g_t[c])
      u_t  = beta_t (v_t - S^T k_t)
      S   <- S + k_t u_t^T
      o_t  = S^T q_t

  ``o <- rmsnorm(o) w_n sigmoid((x W_ga) W_gb)`` over each head; through
  ``W_o``;
- **latent attention without positions** (``q_lora_rank`` null,
  ``mla_use_nope`` true): ``q = x W_q`` on 32 heads of 192; ``[c | k_s] =
  x W_kva`` (512 + 64); ``[k_n | v] = rmsnorm(c) W_kvb`` on 32 heads of 128
  + 128; key head ``h`` is ``[k_n,h | k_s]``, the 64 shared channels the
  same for every head and NOT turned (``rope_theta`` is carried and
  unused); causal softmax of ``192^-1/2 q k^T``, one head and one block of
  queries at a time so that the 16,384 x 16,384 scores fit; through
  ``W_o`` (4096 x 2304);
- ``F_l``: a dense SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; after them DeepSeek-V3's routed layer
  (``laguna``'s and ``glm47_flash``'s, by import): ``s = sigmoid(u W_r)``
  over all 256, the choice the top 8 of ``s + b``, weights ``s`` at the
  chosen over their sum times ``routed_scaling_factor`` on the experts'
  OUTPUT, plus one ungated shared SwiGLU; only the experts held here add
  their part, in a dense loop, under the router's full choice;
- a final norm; ``logits = h W_head`` (untied); next-token cross-entropy.

Departures and forms set here (the configuration file lists each under
``assumed``): ``b`` = 0 and never updated; the routed layers' gate takes
no gradient where ``program.zoo_args.gate_grad`` is false; the init; the
column orders; a packed row is one document; no MTP; AdamW.

``quant`` rounds both operands of every matrix multiplication through a
lower precision: the control that the comparison deciding ``correct`` has
to fail.

**What is read of the configuration file**: ``hidden_size``,
``linear_attn_config`` (whole), ``num_hidden_layers`` (must be the two
lists' length), ``num_attention_heads``, ``q_lora_rank`` (must be null),
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``mla_use_nope`` (must be true), ``intermediate_size``,
``first_k_dense_replace``, ``moe_layer_freq`` (must be 1),
``moe_intermediate_size``, ``num_shared_experts``, ``num_experts`` (HELD
here), ``deployment.num_experts_published`` / ``.experts_first``,
``num_experts_per_token``, ``routed_scaling_factor``,
``moe_router_activation_func`` (must be sigmoid), ``moe_renormalize`` (must
be true), ``num_expert_group`` / ``topk_group`` (must be 1),
``num_nextn_predict_layers`` (must be 0), ``tie_word_embeddings`` (must be
false), ``rms_norm_eps``, ``vocab_size`` and ``program.zoo_args`` /
``.chunk``; the runner reads ``program.zoo`` / ``.loss_chunk``,
``optimizer`` and ``limits``.

Also here, because the benchmark keeps them: what the runner asks a family
for (``zoo_args``, ``routed_blocks``, ``kernel_calls``, ``LOSS_PARTS``,
``AUX``) and the operations and bytes of this family's kernels
(``kda_chunk_cost``, ``kda_walk_cost``, ``flash_fwd_cost`` at two head
widths; ``expert_matmul_cost`` is ``glm47_flash``'s, by import, where
``moe.expert_matmul_roofline`` reads it).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# what is no family's own: the SwiGLU part, the plain-scale norm, per-leaf
# norms, the causal depthwise
# convolution, DeepSeek-V3's routed layer over the experts held here (the
# sibling references'; nothing of the program's)
from benchmark.references.glm47_flash import (  # noqa: F401
    _is_shape, _rms, _swiglu, expert_matmul_cost, leaf_norms)
from benchmark.references.laguna import _experts
from benchmark.references.qwen3_next import _conv

INIT_STD = 0.02
CHUNK = 64
# what is alive at once, so that a row of 16,384 tokens fits beside the
# weights, the gradient and AdamW's moments (9.64 GB): a mixer's heads in
# groups, a head's queries in blocks, the rows of the dense part and of the
# loss in blocks; none changes a value
HEAD_GROUP = 8
QUERY_BLOCK = 2048
ROW_BLOCK = 2048
LOSS_PARTS = ("main",)          # the heads of the loss, beside the whole
AUX = ("loss.main", "moe.slots_here", "moe.load_max_over_mean",
       "moe.overflow_layers")   # the ring's scalars beside the loss


def _fp8(x):
    """``x`` at the nearest float8_e4m3fn value (3 explicit mantissa bits,
    normal exponents -6 to 8, the subnormal grid of 2^-9 below them, 448
    the largest; ties to even), computed in float32 ARITHMETIC, straight
    through (the backward pass sees the rounded operands, its cotangents
    stay float32). No narrow dtype appears: the sibling references'
    ``astype(float8_e4m3fn).astype(float32)`` read, on the chip at this
    cell's size, 0.0 flipped choices in the first two routed layers where
    the same code reads 35% on the CPU (PERF.md section 6, PR 51): a pair
    of converts is the compiler's to widen or drop, a rounding written out
    is not. Equal to the dtype's own rounding value for value on the CPU
    (``tests/test_kimi_linear.py``)."""
    a = jnp.abs(x)
    step = jnp.ldexp(jnp.float32(1.0),
                     jnp.clip(jnp.frexp(a)[1] - 1, -6, 8) - 3)
    near = jnp.sign(x) * jnp.minimum(jnp.round(a / step) * step, 448.0)
    return x + jax.lax.stop_gradient(near - x)


def _products(quant: Optional[str]):
    """``einsum``, with both operands through ``_fp8`` under the control's
    precision "fp8"."""
    if quant is None:
        return jnp.einsum
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    return lambda eq, a, b: jnp.einsum(eq, _fp8(a), _fp8(b))


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    lin = cfg["linear_attn_config"]
    kda = tuple(int(l) for l in lin["kda_layers"])
    full = tuple(int(l) for l in lin["full_attn_layers"])
    depth = int(cfg["num_hidden_layers"])
    if set(kda) & set(full) \
            or set(kda) | set(full) != set(range(1, depth + 1)):
        raise ValueError(f"kda_layers {kda!r} and full_attn_layers {full!r} "
                         f"against {depth} layers numbered from 1")
    if cfg["q_lora_rank"] is not None or not cfg["mla_use_nope"] \
            or cfg["tie_word_embeddings"] \
            or cfg["moe_router_activation_func"] != "sigmoid" \
            or not cfg["moe_renormalize"] or int(cfg["moe_layer_freq"]) != 1 \
            or int(cfg["num_expert_group"]) != 1 \
            or int(cfg["topk_group"]) != 1 \
            or int(cfg["num_nextn_predict_layers"]):
        raise ValueError(
            "this reference is the published layer: no query rank, no "
            "positions, untied tables, one group of sigmoid-routed experts "
            "with renormalised weights in every layer past the dense ones, "
            "no MTP module")
    dep = cfg["deployment"]
    return {
        "dim": int(cfg["hidden_size"]), "depth": depth,
        "kinds": tuple("kda" if l in kda else "mla"
                       for l in range(1, depth + 1)),
        "kda_layers": kda, "full_attn_layers": full,
        "lin_heads": int(lin["num_heads"]), "lin_head": int(lin["head_dim"]),
        "conv": int(lin["short_conv_kernel_size"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]), "v": int(cfg["v_head_dim"]),
        "mlp": int(cfg["intermediate_size"]),
        "dense_layers": int(cfg["first_k_dense_replace"]),
        "expert": int(cfg["moe_intermediate_size"]),
        "shared": int(cfg["num_shared_experts"])
        * int(cfg["moe_intermediate_size"]),
        "experts": int(dep["num_experts_published"]),
        "held": int(cfg["num_experts"]), "first": int(dep["experts_first"]),
        "top_k": int(cfg["num_experts_per_token"]),
        "scaling": float(cfg["routed_scaling_factor"]),
        "gate_grad": bool(
            cfg["program"].get("zoo_args", {}).get("gate_grad", True)),
        "vocab": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
    }


def _routed(d: Dict[str, Any], index: int) -> bool:
    return index >= d["dense_layers"]


# ----------------------------------------------- what the runner asks for
def zoo_args(cfg: Dict[str, Any], length: int) -> Dict[str, Any]:
    """The configuration's published keys as the zoo entry's arguments;
    ``program.zoo_args`` (``gate_grad``) passes as it is, ``program.chunk``
    is the delta rule's chunk."""
    d = dims(cfg)
    return dict(
        vocab=d["vocab"], dim=d["dim"], kda_layers=d["kda_layers"],
        full_attn_layers=d["full_attn_layers"], heads=d["heads"],
        kv_rank=d["kv_rank"], nope=d["nope"], rope=d["rope"], v_dim=d["v"],
        linear_heads=d["lin_heads"], linear_head_dim=d["lin_head"],
        conv_width=d["conv"], mlp_hidden=d["mlp"],
        expert_hidden=d["expert"], shared_hidden=d["shared"],
        num_experts=d["experts"], top_k=d["top_k"],
        experts_held=(d["held"], d["first"]), scaling=d["scaling"],
        dense_layers=d["dense_layers"], eps=d["eps"], chunk=_chunk(cfg),
        max_len=length, **cfg["program"].get("zoo_args", {}))


def _chunk(cfg: Dict[str, Any]) -> int:
    return int(cfg["program"].get("chunk", CHUNK))


def routed_blocks(cfg: Dict[str, Any]) -> List[str]:
    """The blocks with a routed layer, in the order ``routing`` has."""
    d = dims(cfg)
    return [f"block{i}" for i in range(d["depth"]) if _routed(d, i)]


def kernel_calls(cfg: Dict[str, Any], rows: int, length: int,
                 slots: float) -> Dict[str, Dict[str, Any]]:
    """Shapes of the kernels' work: one forward call of the flash kernel
    at its two head widths, one step's grouped products
    (``glm47_flash``'s keys), one step's chunk calls and walks of the
    delta rule under a decay a key channel."""
    d = dims(cfg)
    rule = {"rows": rows, "len": length, "heads": d["lin_heads"],
            "key_dim": d["lin_head"], "value_dim": d["lin_head"],
            "chunk": _chunk(cfg), "layers": d["kinds"].count("kda")}
    return {
        "flash_fwd": {"rows": rows, "len": length, "heads": d["heads"],
                      "key_dim": d["nope"] + d["rope"], "value_dim": d["v"]},
        "expert_matmul": {"slots": slots, "dim": d["dim"],
                          "width": d["expert"], "held": d["held"],
                          "layers": len(routed_blocks(cfg))},
        "kda_chunk": rule, "kda_walk": rule}


# ------------------------------------------------------------------ weights
def _mixer_shapes(d, kind: str) -> Dict[str, Any]:
    dim = d["dim"]
    if kind == "mla":
        H = d["heads"]
        return {"attn_query": {"kernel": (dim, H * (d["nope"] + d["rope"]))},
                "attn_key_value_a": {
                    "kernel": (dim, d["kv_rank"] + d["rope"])},
                "key_value_norm": {"scale": (d["kv_rank"],)},
                "attn_key_value_b": {
                    "kernel": (d["kv_rank"], H * (d["nope"] + d["v"]))},
                "attn_out": {"kernel": (H * d["v"], dim)}}
    H, hd = d["lin_heads"], d["lin_head"]
    wide = H * hd
    return {"attn_query": {"kernel": (dim, wide)},
            "attn_key": {"kernel": (dim, wide)},
            "attn_value": {"kernel": (dim, wide)},
            "conv_query": (d["conv"], wide), "conv_key": (d["conv"], wide),
            "conv_value": (d["conv"], wide),
            "attn_decay_a": {"kernel": (dim, hd)},
            "attn_decay_b": {"kernel": (hd, wide)},
            "dt_bias": (wide,), "A_log": (H,),
            "attn_beta": {"kernel": (dim, H)},
            "attn_gate_a": {"kernel": (dim, hd)},
            "attn_gate_b": {"kernel": (hd, wide)},
            "gate_norm": {"scale": (hd,)},
            "attn_out": {"kernel": (wide, dim)}}


def _block_shapes(d, index: int) -> Dict[str, Any]:
    dim = d["dim"]

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
    return {"norm1": {"scale": (dim,)},
            "attn": _mixer_shapes(d, d["kinds"][index]),
            "norm2": {"scale": (dim,)}, "ffn": ffn}


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree (``models/zoo/decoder.kimi_linear``),
    leaf shapes only."""
    d = dims(cfg)
    p = {"token_embedding": {"embedding": (d["vocab"], d["dim"])},
         "final_norm": {"scale": (d["dim"],)},
         "lm_head": {"kernel": (d["dim"], d["vocab"])}}
    for i in range(d["depth"]):
        p[f"block{i}"] = _block_shapes(d, i)
    return {"params": p}


def parameters(cfg: Dict[str, Any]) -> int:
    """How many parameters the cut holds (the file's ``parameters_here``)."""
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=_is_shape))


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Weights from a PRNG key in the program's tree layout, float32:
    matrices, expert banks, tables and the convolutions normal(0, 0.02);
    norm scales 1; the router's bias 0; ``dt_bias`` 1 and ``A_log =
    log(U(1e-3, 16))`` (``qwen3_next``'s: the decays span weak to total).
    Leaves of one shape are drawn in one call and dealt out in the tree's
    order, from XLA's own bit generator ("rbg": as
    ``glm47_flash.init_params``, and for its reasons). The key is an
    argument, never a constant of the program."""
    key = jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg")
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    names = [jax.tree_util.keystr(path) for path, _ in flat]
    fixed = ("scale", "dt_bias", "A_log", "router_bias")
    by_shape: Dict[Tuple[int, ...], List[int]] = {}
    for i, n in enumerate(names):
        if not any(f in n for f in fixed):
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
            zero = "router_bias" in names[i]
            leaves[i] = (jnp.zeros if zero else jnp.ones)(shape, jnp.float32)
    return jax.tree_util.tree_unflatten(tree, leaves)


# ------------------------------------------------------------------ forward
def kda_rule(mm, q, k, v, g, beta, block: int = CHUNK):
    """Token by token. q, k, g (L, H, dk), v (L, H, dv), beta (L, H); the
    state (H, dk, dv) is zero at the row's start and row ``c`` of it decays
    by ``exp(g_t[c])``. The inner loop over a block of tokens is
    recomputed in the backward pass: a state a block is kept."""
    L, H, dk = q.shape
    pad = -L % block
    xs = tuple(jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
        (-1, block) + x.shape[1:]) for x in (q, k, v, g, beta))

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[:, :, None] * S
        u = b_t[:, None] * (v_t - mm("hkv,hk->hv", S, k_t))
        S = S + mm("hk,hv->hkv", k_t, u)
        return S, mm("hkv,hk->hv", S, q_t)

    @jax.checkpoint
    def tokens(S, x):
        return jax.lax.scan(token, S, x)
    _, o = jax.lax.scan(tokens, jnp.zeros((H, dk, v.shape[-1])), xs)
    return o.reshape((-1,) + o.shape[2:])[:L]


def _groups(kernel, groups: int, axis: int):
    """A projection's columns (``axis`` 1) or rows (0), head-major, as
    ``groups`` groups of whole heads on a leading axis."""
    if axis:
        return kernel.reshape(kernel.shape[0], groups, -1).transpose(1, 0, 2)
    return kernel.reshape(groups, -1, kernel.shape[1])


def _summed(part, x, banks):
    """``sum_g part(x, bank_g)`` over the leading axis of ``banks``, one
    group at a time: heads do not see each other before ``W_o``, so a mixer
    is the sum of its groups of heads' parts, and a group's activations are
    all that is alive at once. The sum is carried OUTSIDE the recomputed
    part (``laguna._experts``' way): its backward keeps no carry a group."""
    y, _ = jax.lax.scan(
        lambda y, bank: (y + jax.checkpoint(part)(x, bank), None),
        jnp.zeros_like(x), banks)
    return y


def _kda(d, mm, p, x):
    """KDA, ``HEAD_GROUP`` heads at a time (every step but ``W_fa`` and
    ``W_ga``, which all heads share, is a head's own)."""
    L, H, hd = x.shape[0], d["lin_heads"], d["lin_head"]
    G = max(1, H // HEAD_GROUP)
    Hg = H // G
    fa = mm("ld,dr->lr", x, p["attn_decay_a"]["kernel"])
    ga = mm("ld,dr->lr", x, p["attn_gate_a"]["kernel"])

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    def part(x, w):
        def mixed(name):
            return jax.nn.silu(_conv(mm("ld,dk->lk", x, w[name]),
                                     w["conv_" + name])).reshape(L, Hg, hd)
        q, k, v = mixed("query"), mixed("key"), mixed("value")
        g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
            mm("lr,rk->lk", fa, w["decay_b"]).reshape(L, Hg, hd)
            + w["dt_bias"].reshape(Hg, hd))
        beta = jax.nn.sigmoid(mm("ld,dh->lh", x, w["beta"]))
        o = kda_rule(mm, unit(q) / np.sqrt(hd), unit(k), v, g, beta)
        o = _rms(o, p["gate_norm"], d["eps"]) * jax.nn.sigmoid(
            mm("lr,rk->lk", ga, w["gate_b"]).reshape(L, Hg, hd))
        return mm("lk,kd->ld", o.reshape(L, Hg * hd), w["out"])
    banks = {name: _groups(p[f"attn_{name}"]["kernel"], G, 1)
             for name in ("query", "key", "value", "beta")}
    banks.update({"conv_" + name: _groups(p["conv_" + name], G, 1)
                  for name in ("query", "key", "value")})
    banks.update(decay_b=_groups(p["attn_decay_b"]["kernel"], G, 1),
                 gate_b=_groups(p["attn_gate_b"]["kernel"], G, 1),
                 dt_bias=p["dt_bias"].reshape(G, -1),
                 A_log=p["A_log"].reshape(G, -1),
                 out=_groups(p["attn_out"]["kernel"], G, 0))
    return _summed(part, x, banks)


def _mla(d, mm, p, x):
    """Latent attention, ``HEAD_GROUP`` heads at a time, then one head,
    then one block of its queries: (QUERY_BLOCK, L) scores are alive."""
    L, H = x.shape[0], d["heads"]
    wide = d["nope"] + d["rope"]
    G = max(1, H // HEAD_GROUP)
    Hg = H // G
    kva = mm("ld,dr->lr", x, p["attn_key_value_a"]["kernel"])
    ckv = _rms(kva[:, :d["kv_rank"]], p["key_value_norm"], d["eps"])
    # the 64 shared channels: one row a token for every head, not turned
    k_s = kva[:, d["kv_rank"]:]
    scale = 1.0 / np.sqrt(wide)
    rows = min(QUERY_BLOCK, L)
    if L % rows:
        raise ValueError(f"a row of {L} tokens in query blocks of {rows}")
    keys = jnp.arange(L)[None, :]

    def head(qkv):              # one head, then one block of its queries
        q_h, k_h, v_h = qkv

        def block(args):
            q_b, first = args
            s = jnp.where(keys > first + jnp.arange(rows)[:, None], -jnp.inf,
                          mm("qk,nk->qn", q_b * scale, k_h))
            return mm("qn,nk->qk", jax.nn.softmax(s, axis=-1), v_h)
        return jax.lax.map(jax.checkpoint(block), (
            q_h.reshape(L // rows, rows, wide),
            jnp.arange(0, L, rows))).reshape(L, d["v"])

    def part(x, w):
        q = mm("ld,dk->lk", x, w["query"]).reshape(L, Hg, wide)
        kv = mm("lr,rk->lk", ckv, w["key_value_b"]).reshape(
            L, Hg, d["nope"] + d["v"])
        k = jnp.concatenate([kv[..., :d["nope"]], jnp.broadcast_to(
            k_s[:, None], (L, Hg, d["rope"]))], -1)
        o = jax.lax.map(jax.checkpoint(head), tuple(
            t.transpose(1, 0, 2) for t in (q, k, kv[..., d["nope"]:])))
        return mm("lk,kd->ld", o.transpose(1, 0, 2).reshape(L, Hg * d["v"]),
                  w["out"])
    return _summed(part, x, {
        "query": _groups(p["attn_query"]["kernel"], G, 1),
        "key_value_b": _groups(p["attn_key_value_b"]["kernel"], G, 1),
        "out": _groups(p["attn_out"]["kernel"], G, 0)})


def _by_rows(f, x):
    """``f`` on ``ROW_BLOCK`` rows of ``x`` at a time (a part that treats
    every row alone), recomputed in the backward pass."""
    L = x.shape[0]
    rows = min(ROW_BLOCK, L)
    if L % rows:
        return f(x)
    return jax.lax.map(jax.checkpoint(f), x.reshape(
        L // rows, rows, -1)).reshape(L, -1)


def _block(d, mm, index: int, p, x):
    mixer = _kda if d["kinds"][index] == "kda" else _mla
    h = x + mixer(d, mm, p["attn"], _rms(x, p["norm1"], d["eps"]))
    z = _rms(h, p["norm2"], d["eps"])
    if not _routed(d, index):
        return h + _by_rows(lambda rows: _swiglu(mm, p["ffn"], rows), z), None
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
    for i in range(d["depth"]):
        x, routing = jax.checkpoint(functools.partial(_block, d, mm, i))(
            p[f"block{i}"], x)
        if _routed(d, i):
            routings.append(routing)
    return {"hidden": _rms(x, p["final_norm"], d["eps"]),
            "routing": routings}


def logits(cfg, params, tokens, quant=None):
    """One sequence (L,) -> the untied head's (L, vocab) float32 logits."""
    mm = _products(quant)
    return mm("ld,dv->lv", hidden_rows(cfg, mm, params, tokens)["hidden"],
              params["params"]["lm_head"]["kernel"])


def sequence_loss(cfg, quant, rows, params, tokens):
    """One sequence's part of the batch loss over ``rows`` sequences:
    ``(part, routing)``, already over the batch's count of targets, so that
    the parts of a batch add up to its loss. The logits are made
    ``ROW_BLOCK`` rows at a time."""
    L = tokens.shape[0]
    mm = _products(quant)
    out = hidden_rows(cfg, mm, params, tokens)
    kernel = params["params"]["lm_head"]["kernel"]
    targets = jnp.roll(tokens, -1)
    counted = (jnp.arange(L) < L - 1).astype(jnp.float32)

    def nll(args):
        h, t, w = args
        logp = jax.nn.log_softmax(mm("ld,dv->lv", h, kernel), -1)
        return -jnp.sum(w * jnp.take_along_axis(logp, t[:, None], 1)[:, 0])
    n = L // ROW_BLOCK if L % ROW_BLOCK == 0 and L > ROW_BLOCK else 1
    total = jnp.sum(jax.lax.map(jax.checkpoint(nll), (
        out["hidden"].reshape(n, L // n, -1), targets.reshape(n, -1),
        counted.reshape(n, -1))))
    return total / (rows * (L - 1)), out["routing"]


# ----------------------------------------------------------------- training
def train_reference(cfg: Dict[str, Any], seed: int, tokens: np.ndarray, *,
                    steps: int, optimizer: Dict[str, Any],
                    quant: Optional[str] = None) -> Dict[str, Any]:
    """Follow the first ``steps`` AdamW steps from the seeded weights on
    ``tokens[s]`` (``(rows, L)`` int32, one batch per step), float32 at
    the highest matmul precision, one sequence at a time with the
    gradients summed; decay on leaves of two and more dimensions. The
    cut's 602M parameters are 9.6 GB of weights, gradient and moments; one
    sequence's float32 activations, a group of heads and a block of rows
    at a time (3.9 GB compiled for a described v5e), have the rest.

    Returns what ``laguna.train_reference`` returns, for the runner's
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

        # a step's first sequence MAKES the gradient and the others add to
        # it: one row a step (the cell's) holds no accumulator beside it
        grad_first = jax.jit(jax.value_and_grad(functools.partial(
            sequence_loss, cfg, quant, rows), has_aux=True))

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
            total, grads, routing = 0.0, None, []
            for b in range(rows):
                t0 = time.perf_counter()
                toks = jnp.asarray(tokens[s][b])
                (part, r), grads = grad_first(params, toks) if b == 0 \
                    else grad_seq(params, grads, toks)
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
def kda_rule_flops_per_token(d: Dict[str, Any], chunk: int = CHUNK
                             ) -> Dict[str, float]:
    """Forward FLOPs a token of ONE KDA layer's delta rule in its chunked
    form, all heads: ``qwen3_next.delta_rule_flops_per_token``'s count at
    these widths. A decay a key channel changes no product's size: the
    decay lies inside ``K K^T`` and ``Q K^T`` as a scaling of their
    operands, and a program that splits either product into several to
    keep its exponents sound computes them more than once, which is no
    required work. ``walk`` is the part the scan from chunk to chunk
    holds (``W S`` and ``K^T U``)."""
    H, dk, dv = d["lin_heads"], d["lin_head"], d["lin_head"]
    inside = 2.0 * chunk * (3 * dk + 2 * dv + chunk)
    walk = 2.0 * 2 * dk * dv
    return {"total": H * (inside + walk + 2.0 * dk * dv), "walk": H * walk}


def _fwd_flops_per_token(cfg: Dict[str, Any],
                         length: int) -> Dict[str, float]:
    d = dims(cfg)
    dim, H = d["dim"], d["heads"]
    wide = d["lin_heads"] * d["lin_head"]
    keys, values = d["nope"] + d["rope"], d["v"]
    kda = 2.0 * (dim * 3 * wide + d["conv"] * 3 * wide
                 + 2 * (dim * d["lin_head"] + d["lin_head"] * wide)
                 + dim * d["lin_heads"] + wide * dim) \
        + kda_rule_flops_per_token(d, _chunk(cfg))["total"]
    # causal: a query sees half the keys on average; q.k^T over the keys'
    # width and p.v over the values'
    mla = 2.0 * (dim * H * keys + dim * (d["kv_rank"] + d["rope"])
                 + d["kv_rank"] * H * (d["nope"] + values)
                 + H * values * dim) \
        + 2.0 * length / 2.0 * H * (keys + values)
    mlp = 2.0 * 3 * dim * d["mlp"]
    routed = 2.0 * dim * d["experts"] + 2.0 * 3 * dim * d["shared"] \
        + d["top_k"] * d["held"] / d["experts"] * 2.0 * 3 * dim * d["expert"]
    out = {"kda": kda * d["kinds"].count("kda"),
           "mla": mla * d["kinds"].count("mla"),
           "dense": mlp * d["dense_layers"],
           "routed": routed * (d["depth"] - d["dense_layers"]),
           "head": 2.0 * dim * d["vocab"]}
    out["total"] = sum(out.values())
    return out


def train_flops_per_item(cfg: Dict[str, Any], length: int = 16384) -> float:
    """Matrix-multiplication, attention and delta-rule FLOPs that one
    packed row of ``length`` tokens requires, forward and backward
    (backward = 2 x forward; nothing recomputed counts): the projections
    and the low-rank gates, the convolutions, the chunked delta rule's
    products at the configured chunk, the causal half of the latent
    layers' two attention products at their own widths (192 and 128), the
    dense part, the router, the shared expert, the EXPECTED routed work of
    the experts held here (``top_k * held / experts`` slots a token) and
    the head. From shapes alone, whatever a program pads or splits."""
    return 3.0 * length * _fwd_flops_per_token(cfg, length)["total"]


def _rule_chunks(call: Dict[str, Any]) -> float:
    return float(call["layers"]) * float(call["rows"]) \
        * float(call["heads"]) * float(call["len"]) / float(call["chunk"])


def kda_chunk_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One training step's chunk-local half of the delta rule under a
    decay a key channel (what the calls named ``kda_chunk_*`` do),
    recomputation not counted. FLOPs a chunk and head are
    ``olmo_hybrid.delta_chunk_cost``'s: ``K K^T``, ``Q K^T``, ``T (beta
    e^G K)`` (2 C^2 dk each), ``T (beta V)`` and ``P U`` (2 C^2 dv each),
    making ``T`` (2 C^3) and ``(e^G Q) S_0`` (2 C dk dv); twice that
    backward; a product split by level to keep its exponents sound is
    counted once. Bytes a chunk and head that no schedule avoids: forward
    q, k and the decay's running sum (float32, C dk each), v (bfloat16)
    and beta read; ``W``, ``Kd``, ``qe``, ``P`` (bfloat16) and ``U_0``
    (float32) written, then ``qe``, ``P``, the chunk's starting state and
    ``U`` (float32) read and ``O`` (float32) written; backward every one of
    those read again with the cotangents of what was written, and the
    gradients of what was read written."""
    C, dk, dv = (float(call[k]) for k in ("chunk", "key_dim", "value_dim"))
    flops = 2.0 * C * C * (3 * dk + 2 * dv + C) + 2.0 * C * dk * dv
    rows_in = 3 * C * dk * 4 + C * dv * 2 + C * 4       # q, k, G, v, beta
    tiles = 3 * C * dk * 2 + C * dv * 4 + C * C * 2     # W, Kd, qe, U0, P
    walked = dk * dv * 4 + C * dv * 4                   # S0, U
    out = C * dv * 4                                    # O
    forward = rows_in + tiles + (C * dk * 2 + C * C * 2) + walked + out
    return _rule_chunks(call) * 3.0 * flops, \
        _rule_chunks(call) * 3.0 * forward


def kda_walk_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One training step's walks of the state from chunk to chunk (the
    scan under the scope ``kda_state_walk``): ``qwen3_next.delta_rule_cost``
    with the chunk's decay a vector of ``dk`` float32 where it was one
    number: forward ``W S`` and ``K^T U`` (4 C dk dv FLOPs a chunk) and
    twice that backward, recomputation not counted; forward reads ``W`` and
    ``Kd`` (bfloat16), ``U_0`` and the decay (float32) and writes ``U`` and
    the chunk's starting state (float32); the backward pass reads ``W``,
    ``Kd``, ``U``, the decay and the starting state again with the
    cotangents of ``U`` and the state, and writes the gradients of ``W``,
    ``Kd``, ``U_0`` and the decay in float32."""
    C, dk, dv = (float(call[k]) for k in ("chunk", "key_dim", "value_dim"))
    forward = 2 * C * dk * 2 + 2 * C * dv * 4 + dk * dv * 4 + dk * 4
    backward = 2 * C * dk * 2 + (C * dv + dk * dv) * 4 + dk * 4 \
        + (C * dv + dk * dv) * 4 + (2 * C * dk + C * dv + dk) * 4
    return _rule_chunks(call) * 3.0 * 4.0 * C * dk * dv, \
        _rule_chunks(call) * (forward + backward)


def flash_fwd_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One forward call of causal attention on ``rows`` sequences of
    ``len`` tokens with ``heads`` heads whose keys are ``key_dim`` wide
    and whose values ``value_dim``: ``q k^T`` over the keys' width and ``p
    v`` over the values', each over the causal half (2 x L^2/2 x H x (dk +
    dv) a row), and q, k (``key_dim``) and v, o (``value_dim``) through
    HBM once in bfloat16."""
    rows, L, H = (float(call[k]) for k in ("rows", "len", "heads"))
    wide = float(call["key_dim"]) + float(call["value_dim"])
    return rows * 2.0 * L * L / 2.0 * H * wide, rows * 2.0 * L * H * wide * 2.0
