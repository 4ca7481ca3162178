"""Plain reference: Olmo-Hybrid-7B (``olmo_hybrid``), training, float32.

Written from the published ``config.json`` (huggingface.co/allenai/
Olmo-Hybrid-7B, ``model_type: olmo_hybrid``; the linear layers are Gated
DeltaNet, Yang et al. 2024, arXiv:2412.06464, in flash-linear-attention's
layout) in straightforward ``jax.numpy``: no kernels, no flax, nothing
imported from the program (helpers that belong to no family, and the
delta rule's counts, which are functions of its two head widths, come from
sibling references). No biases, every RMSNorm with a plain scale, ``D`` =
``hidden_size``:

- ``h_0 = E[token]``;
- layer ``l``: ``h <- h + norm1(mixer_l(h))``, ``h <- h + norm2(mlp(h))``:
  the norm on each half's OUTPUT, none on its input (OLMo 2's wiring);
  ``mixer_l`` read off ``layer_types[l]``;
- ``linear_attention``, Gated DeltaNet: ``[q | k | v | z] = x W_qkvz``,
  ``[b | a] = x W_ba``; ``[q | k | v]`` through a causal depthwise
  convolution of width 4 (three zeros before the row) and ``silu``; ``beta
  = 2 sigmoid(b)`` (``linear_allow_neg_eigval``: a token's transition ``I -
  beta k k^T`` has the eigenvalue ``1 - beta`` in (-1, 1)), ``g =
  -exp(A_log) softplus(a + dt_bias)``; q and k L2-normalised over the head
  (eps 1e-6 inside the root), q times ``dk^-1/2``; as many key as value
  heads; per head, TOKEN BY TOKEN with a state ``S`` of 96 x 192: ``S <-
  exp(g_t) S``, ``u = beta_t (v_t - S^T k_t)``, ``S <- S + k_t u^T``, ``o_t
  = S^T q_t`` (a scan over blocks of 64 tokens whose inner loop is
  rematerialised; that changes no value); ``o <- rmsnorm(o) w_n silu(z)``
  over each head; through ``W_o``;
- ``full_attention``: ``q``, ``k``, ``v`` on 30 heads of 128; an RMS norm
  over all 3,840 channels of ``q`` and of ``k`` before the split into
  heads; causal softmax of ``128^-1/2 q k^T``, one head at a time; through
  ``W_o``. No positions (``rope_parameters.rope_theta`` is null), no gate;
- the feed-forward part of every layer: ``W_down (silu(x W_gate) * x
  W_up)``;
- a final norm, an untied head, next-token cross-entropy.

Departures and sizes set here (the configuration file lists each under
``assumed``): the output-norm wiring, the norm on q and k over the whole
projection, no positions and the head width 128 are the OLMo 2 / OLMo 3
family's, the config has no key for them; columns of ``W_qkvz`` in ``[q |
k | v | z]`` order, head-major (a checkpoint's four matrices side by
side); ``A_log = log(U(1e-3, 16))``, ``dt_bias`` = 1, norm scales 1,
matrices, tables and the convolution normal(0, 0.02); a packed row is one
document; AdamW.

``quant`` rounds both operands of every matrix multiplication (the delta
rule's products among them) through a lower precision: the control that
the comparison deciding ``correct`` has to fail.

``train_reference`` walks the layers as ``granite_hybrid``'s does, and BY
HALVES: four float32 copies of 928.9M parameters would be 14.86 GB of a
16.9 GB chip, and the three that stay (weights and AdamW's two moments)
are 11.15 GB, so the gradient is never whole on the device and no vjp
holds more than one half of one block: forward, layer by layer, keeping
each layer's input; backward from the loss, layer by layer: the mixer
half's output again, the feed-forward half's ``jax.vjp`` (four ``[8192,
11008]`` float32 arrays, 1.44 GB), then the mixer half's, each half's
gradient going into its AdamW update (and, at the first step, to the host)
before the next is made. Everything stays on the device; the moments do
not wait on the host. The values are those of ``jax.grad`` of
``sequence_loss`` (``tests/test_olmo_hybrid.py`` holds the two together).

**What is read of the configuration file**: ``hidden_size``,
``num_hidden_layers`` and ``layer_types`` (as long),
``num_attention_heads``, ``num_key_value_heads`` (equal),
``linear_num_key_heads``, ``linear_num_value_heads`` (equal),
``linear_key_head_dim``, ``linear_value_head_dim``,
``linear_conv_kernel_dim``, ``linear_allow_neg_eigval``,
``intermediate_size``, ``vocab_size``, ``rms_norm_eps``,
``program.chunk`` and ``program.zoo_args``; the runner reads
``program.zoo`` / ``.loss_chunk``, ``optimizer`` and ``limits``.

Also here, because the benchmark keeps them: what the runner asks a family
for (``zoo_args``, ``routed_blocks``, ``kernel_calls``, ``LOSS_PARTS``,
``AUX``), and the operations and bytes of the delta rule's walk
(``delta_rule_cost``, ``qwen3_next``'s at these widths) and of its
chunk-local half (``delta_chunk_cost``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# what is no family's own: the control's rounding of matmul operands, the
# SwiGLU part, per-leaf norms; and the delta rule token by token, its
# convolution and its counts, which know two head widths and no family
from benchmark.references.glm47_flash import (  # noqa: F401
    _is_shape, _products, _swiglu, leaf_norms)
from benchmark.references.qwen3_next import (  # noqa: F401
    _conv, _delta_rule, delta_rule_cost, delta_rule_flops_per_token)

INIT_STD = 0.02
CHUNK = 64
LOSS_PARTS = ("main",)          # the heads of the loss, beside the whole
AUX = ("loss.main",)            # the ring's scalars beside the loss
KINDS = ("linear_attention", "full_attention")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != int(cfg["num_hidden_layers"]) or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types {kinds!r} against "
                         f"{cfg['num_hidden_layers']} layers of {KINDS}")
    dim, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    lin = int(cfg["linear_num_value_heads"])
    if int(cfg["num_key_value_heads"]) != heads \
            or int(cfg["linear_num_key_heads"]) != lin:
        raise ValueError("as many key/value as query heads, and as many "
                         "key as value heads, is what this reference covers")
    return {
        "dim": dim, "kinds": kinds, "heads": heads, "head": dim // heads,
        "lk_heads": lin, "lv_heads": lin,
        "lk": int(cfg["linear_key_head_dim"]),
        "lv": int(cfg["linear_value_head_dim"]),
        "conv": int(cfg["linear_conv_kernel_dim"]),
        "beta_scale": 2.0 if cfg["linear_allow_neg_eigval"] else 1.0,
        "mlp": int(cfg["intermediate_size"]),
        "vocab": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
    }


# ----------------------------------------------- what the runner asks for
def zoo_args(cfg: Dict[str, Any], length: int) -> Dict[str, Any]:
    """The configuration's published keys as the zoo entry's arguments."""
    d = dims(cfg)
    return dict(
        vocab=d["vocab"], dim=d["dim"], layer_types=d["kinds"],
        heads=d["heads"], head_dim=d["head"],
        linear_key_heads=d["lk_heads"], linear_value_heads=d["lv_heads"],
        linear_key_dim=d["lk"], linear_value_dim=d["lv"],
        conv_width=d["conv"], mlp_hidden=d["mlp"], eps=d["eps"],
        chunk=_chunk(cfg), max_len=length,
        **cfg["program"].get("zoo_args", {}))


def _chunk(cfg: Dict[str, Any]) -> int:
    return int(cfg["program"].get("chunk", CHUNK))


def routed_blocks(cfg: Dict[str, Any]) -> List[str]:
    """The blocks with a routed layer: none, the model is dense."""
    return []


def kernel_calls(cfg: Dict[str, Any], rows: int, length: int,
                 slots: float = 0.0) -> Dict[str, Dict[str, Any]]:
    """Shapes of the kernels' work: one forward call of the flash kernel,
    one step's delta rule (its walk, and its chunk-local half)."""
    d = dims(cfg)
    rule = {"rows": rows, "len": length, "heads": d["lv_heads"],
            "key_dim": d["lk"], "value_dim": d["lv"], "chunk": _chunk(cfg),
            "layers": d["kinds"].count("linear_attention")}
    return {
        "flash_fwd": {"rows": rows, "len": length, "heads": d["heads"],
                      "head_dim": d["head"]},
        "delta_rule": rule, "delta_chunk": rule}


# ------------------------------------------------------------------ weights
def _mixer_shapes(d, kind: str) -> Dict[str, Any]:
    dim = d["dim"]
    if kind == "full_attention":
        return {"attn_query": {"kernel": (dim, dim)},
                "attn_key": {"kernel": (dim, dim)},
                "attn_value": {"kernel": (dim, dim)},
                "query_norm": {"scale": (dim,)},
                "key_norm": {"scale": (dim,)},
                "attn_out": {"kernel": (dim, dim)}}
    qk, vv = d["lk_heads"] * d["lk"], d["lv_heads"] * d["lv"]
    return {"attn_qkvz": {"kernel": (dim, 2 * qk + 2 * vv)},
            "attn_ba": {"kernel": (dim, 2 * d["lv_heads"])},
            "conv_kernel": (d["conv"], 2 * qk + vv),
            "A_log": (d["lv_heads"],), "dt_bias": (d["lv_heads"],),
            "gate_norm": {"scale": (d["lv"],)},
            "attn_out": {"kernel": (vv, dim)}}


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree (``models/zoo/decoder.OlmoHybrid``),
    leaf shapes only."""
    d = dims(cfg)
    dim = d["dim"]
    p = {"token_embedding": {"embedding": (d["vocab"], dim)},
         "final_norm": {"scale": (dim,)},
         "lm_head": {"kernel": (dim, d["vocab"])}}
    for i, kind in enumerate(d["kinds"]):
        p[f"block{i}"] = {
            "norm1": {"scale": (dim,)}, "attn": _mixer_shapes(d, kind),
            "norm2": {"scale": (dim,)},
            "ffn": {"mlp_gate": {"kernel": (dim, d["mlp"])},
                    "mlp_up": {"kernel": (dim, d["mlp"])},
                    "mlp_down": {"kernel": (d["mlp"], dim)}}}
    return {"params": p}


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Weights from a PRNG key in the program's tree layout, float32:
    matrices, tables and the convolution normal(0, 0.02); every norm's
    scale and ``dt_bias`` 1; ``A_log = log(U(1e-3, 16))``. Leaves of one
    shape are drawn in one call and dealt out in the tree's order, from
    XLA's own bit generator ("rbg": as ``glm47_flash.init_params``, and
    for its reasons). The key is an argument, never a constant of the
    program."""
    key = jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg")
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    names = [jax.tree_util.keystr(path) for path, _ in flat]
    fixed = ("scale", "dt_bias", "A_log")
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
            leaves[i] = jnp.ones(shape, jnp.float32)
    return jax.tree_util.tree_unflatten(tree, leaves)


# ------------------------------------------------------------------ forward
def _rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _delta_inputs(d, mm, p, x):
    """x (L, dim) -> q, k (L, H, dk), v, z (L, H, dv), g, beta (L, H)."""
    L = x.shape[0]
    H, dk, dv = d["lv_heads"], d["lk"], d["lv"]
    qkvz = mm("ld,dk->lk", x, p["attn_qkvz"]["kernel"])
    ba = mm("ld,dk->lk", x, p["attn_ba"]["kernel"])
    mixed = jax.nn.silu(_conv(qkvz[:, :2 * H * dk + H * dv],
                              p["conv_kernel"]))
    z = qkvz[:, 2 * H * dk + H * dv:].reshape(L, H, dv)
    q = mixed[:, :H * dk].reshape(L, H, dk)
    k = mixed[:, H * dk:2 * H * dk].reshape(L, H, dk)
    v = mixed[:, 2 * H * dk:].reshape(L, H, dv)
    beta = d["beta_scale"] * jax.nn.sigmoid(ba[:, :H])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, H:] + p["dt_bias"])

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    return unit(q) / np.sqrt(dk), unit(k), v, g, beta, z


def _delta_output(d, mm, p, o, z):
    L = o.shape[0]
    o = _rms(o, p["gate_norm"], d["eps"]) * jax.nn.silu(z)
    return mm("lk,kd->ld", o.reshape(L, -1), p["attn_out"]["kernel"])


def _delta_net(d, mm, p, x):
    """The layer in three stages rematerialised apart (that changes no
    value): a vjp of the whole at 8,192 x 3840 float32 holds 5 GB, which
    the weights and the moments leave no room for."""
    q, k, v, g, beta, z = jax.checkpoint(
        functools.partial(_delta_inputs, d, mm))(p, x)
    o = jax.checkpoint(functools.partial(_delta_rule, mm))(q, k, v, g, beta)
    return jax.checkpoint(functools.partial(_delta_output, d, mm))(p, o, z)


def _attention(d, mm, p, x):
    L, H, hd = x.shape[0], d["heads"], d["head"]
    q = _rms(mm("ld,dk->lk", x, p["attn_query"]["kernel"]),
             p["query_norm"], d["eps"]).reshape(L, H, hd)
    k = _rms(mm("ld,dk->lk", x, p["attn_key"]["kernel"]),
             p["key_norm"], d["eps"]).reshape(L, H, hd)
    v = mm("ld,dk->lk", x, p["attn_value"]["kernel"]).reshape(L, H, hd)
    future = jnp.arange(L)[None, :] > jnp.arange(L)[:, None]

    def head(args):             # one head at a time: (L, L) scores
        q_h, k_h, v_h = args
        s = jnp.where(future, -jnp.inf,
                      mm("qk,nk->qn", q_h / np.sqrt(hd), k_h))
        return mm("qn,nk->qk", jax.nn.softmax(s, axis=-1), v_h)
    o = jax.lax.map(jax.checkpoint(head), tuple(
        t.transpose(1, 0, 2) for t in (q, k, v)))
    return mm("lk,kd->ld", o.transpose(1, 0, 2).reshape(L, H * hd),
              p["attn_out"]["kernel"])


def _mix(d, mm, kind, p, x):
    """The mixer half: ``x + norm1(mixer(x))``; ``p`` holds ``norm1`` and
    ``attn``."""
    mixer = _delta_net if kind == "linear_attention" else _attention
    return x + _rms(mixer(d, mm, p["attn"], x), p["norm1"], d["eps"])


def _feed(d, mm, p, h):
    """The feed-forward half: ``h + norm2(mlp(h))``; ``p`` holds ``norm2``
    and ``ffn``."""
    return h + _rms(_swiglu(mm, p["ffn"], h), p["norm2"], d["eps"])


def _block(d, mm, kind, p, x):
    """One layer on one sequence (L, dim); its halves rematerialised apart
    (that changes no value)."""
    h = jax.checkpoint(functools.partial(_mix, d, mm, kind))(p, x)
    return jax.checkpoint(functools.partial(_feed, d, mm))(p, h)


def _logits(d, mm, head, norm, x):
    return mm("ld,dv->lv", _rms(x, norm, d["eps"]), head)


def hidden_rows(cfg: Dict[str, Any], mm, params: Dict[str, Any],
                tokens: jax.Array) -> jax.Array:
    """One sequence ``tokens`` (L,) -> the residual stream after the last
    layer, (L, dim), before the final norm."""
    d = dims(cfg)
    p = params["params"]
    x = p["token_embedding"]["embedding"][tokens]
    for i, kind in enumerate(d["kinds"]):
        x = _block(d, mm, kind, p[f"block{i}"], x)
    return x


def logits(cfg, params, tokens, quant=None):
    """One sequence (L,) -> the head's (L, vocab) float32 logits."""
    mm = _products(quant)
    p = params["params"]
    return _logits(dims(cfg), mm, p["lm_head"]["kernel"], p["final_norm"],
                   hidden_rows(cfg, mm, params, tokens))


def _nll(logits_, tokens, targets: int):
    """Sum of next-token losses of one sequence over ``targets``."""
    L = tokens.shape[0]
    logp = jax.nn.log_softmax(logits_, -1)
    picked = jnp.take_along_axis(
        logp, jnp.roll(tokens, -1)[:, None], axis=1)[:, 0]
    return -jnp.sum(jnp.where(jnp.arange(L) < L - 1, picked, 0.0)) / targets


def sequence_loss(cfg, quant, rows, params, tokens):
    """One sequence's part of the batch loss over ``rows`` sequences,
    already over the batch's count of targets, so that the parts of a
    batch add up to its loss."""
    return _nll(logits(cfg, params, tokens, quant), tokens,
                rows * (tokens.shape[0] - 1))


# ----------------------------------------------------------------- training
_MIX_LEAVES, _FEED_LEAVES = ("norm1", "attn"), ("norm2", "ffn")


def train_reference(cfg: Dict[str, Any], seed: int, tokens: np.ndarray, *,
                    steps: int, optimizer: Dict[str, Any],
                    quant: Optional[str] = None) -> Dict[str, Any]:
    """Follow the first ``steps`` AdamW steps from the seeded weights on
    ``tokens[s]`` (``(rows, L)`` int32, one batch per step), float32 at
    the highest matmul precision, the layers walked one by one and each by
    halves (module docstring); decay on leaves of two and more dimensions.

    Returns what ``granite_hybrid.train_reference`` returns: per step the
    loss (``losses`` and ``main``; ``mtp`` is empty, there is no such
    head), the first gradient (leaves on the host in the program's tree
    order, and their norms), the per-leaf norm of the parameters' change,
    no ``routing``, and ``timing`` in seconds.
    """
    import time
    lr, b1, b2 = (float(optimizer[k]) for k in
                  ("learning_rate", "beta1", "beta2"))
    eps, decay = float(optimizer["eps"]), float(optimizer["weight_decay"])
    d, mm = dims(cfg), _products(quant)
    rows, L = tokens.shape[1:]
    clock = {"init": 0.0, "first_step": 0.0, "other_steps": 0.0,
             "fetch": 0.0}

    def over_rows(f):           # a half's function, every row of the batch
        return jax.vmap(f, in_axes=(None, 0))

    def half(block, names):     # the leaves of a block that a half reads
        return {k: block[k] for k in names}

    mixes = {kind: over_rows(functools.partial(_mix, d, mm, kind))
             for kind in set(d["kinds"])}
    feed = over_rows(functools.partial(_feed, d, mm))
    mix_forward = {kind: jax.jit(f) for kind, f in mixes.items()}
    feed_forward = jax.jit(feed)

    def back(f):
        return jax.jit(lambda p, x, dy: jax.vjp(f, p, x)[1](dy),   # (dp, dx)
                       donate_argnums=(2,))
    mix_backward = {kind: back(f) for kind, f in mixes.items()}
    feed_backward = back(feed)

    @functools.partial(jax.jit, donate_argnums=(2,))
    def head(kernel, norm, x, toks):
        def loss(kernel, norm, x):
            return jnp.sum(jax.vmap(lambda x_, t: _nll(
                _logits(d, mm, kernel, norm, x_), t, rows * (L - 1)))(
                    x, toks))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(kernel, norm, x)

    @jax.jit
    def embed_back(table, toks, dx):
        return jax.vjp(lambda t: t[toks], table)[1](dx)[0]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adamw(params, m, v, g, t):
        def leaf(p, m, v, g):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
            if p.ndim >= 2:
                step = step + decay * p
            return p - lr * step, m, v
        out = jax.tree_util.tree_map(leaf, params, m, v, g)
        return tuple(jax.tree_util.tree_map(
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
            for i in range(3))
    norms = jax.jit(leaf_norms)

    with jax.default_matmul_precision("highest"):
        key = jax.random.PRNGKey(seed)
        init = jax.jit(lambda k: init_params(cfg, k))
        t0 = time.perf_counter()
        p = jax.block_until_ready(init(key))["params"]
        clock["init"] = time.perf_counter() - t0
        zeros = jax.jit(lambda tree: jax.tree_util.tree_map(
            jnp.zeros_like, tree))
        m, v = zeros(p), zeros(p)
        out: Dict[str, Any] = {"losses": [], "main": [], "mtp": []}
        first: Dict[str, Any] = {}          # the first gradient, by part

        def update(name, g, s):
            """A part's gradient (a top-level name, or some leaves of a
            block): kept for the comparison at the first step (norms on
            the device, leaves on the host), then into its AdamW update."""
            if s == 0:
                t0 = time.perf_counter()
                first.setdefault(name, []).append(
                    (norms(g), jax.device_get(g)))
                clock["fetch"] += time.perf_counter() - t0
            part = [{k: tree[name][k] for k in g} for tree in (p, m, v)]
            for tree, new in zip((p, m, v), adamw(*part, g, float(s + 1))):
                tree[name].update(new)

        for s in range(steps):
            t0 = time.perf_counter()
            toks = jnp.asarray(tokens[s])
            table = p["token_embedding"]["embedding"]
            xs = [table[toks]]
            for i, kind in enumerate(d["kinds"]):
                blk = p[f"block{i}"]
                xs.append(feed_forward(half(blk, _FEED_LEAVES), mix_forward[
                    kind](half(blk, _MIX_LEAVES), xs[-1])))
            loss, (dkernel, dnorm, dx) = head(
                p["lm_head"]["kernel"], p["final_norm"], xs.pop(), toks)
            update("lm_head", {"kernel": dkernel}, s)
            update("final_norm", dnorm, s)
            del dkernel
            for i in reversed(range(len(d["kinds"]))):
                kind, blk, x = d["kinds"][i], p[f"block{i}"], xs.pop()
                mix = half(blk, _MIX_LEAVES)
                g, dx = feed_backward(half(blk, _FEED_LEAVES),
                                      mix_forward[kind](mix, x), dx)
                update(f"block{i}", g, s)
                g, dx = mix_backward[kind](mix, x, dx)
                update(f"block{i}", g, s)
                del g, mix
            update("token_embedding",
                   {"embedding": embed_back(table, toks, dx)}, s)
            del table, dx
            out["losses"].append(float(loss))
            out["main"].append(float(loss))
            jax.block_until_ready(p)
            clock["other_steps" if s else "first_step"] += \
                time.perf_counter() - t0
        del m, v
        # a block's two halves came apart: one tree again, its leaves and
        # their norms in the whole tree's order
        grads = {name: {k: g for _, part in parts for k, g in part.items()}
                 for name, parts in first.items()}
        norm_of = {name: {k: n for nrm, _ in parts for k, n in nrm.items()}
                   for name, parts in first.items()}
        out["first_grad"] = [np.asarray(x) for x in
                             jax.tree_util.tree_leaves({"params": grads})]
        out["grad_norms"] = {
            f"['params']['{name}']{jax.tree_util.keystr(path)}":
                float(norm_of[name][jax.tree_util.keystr(path)])
            for name in sorted(grads) for path, _ in
            jax.tree_util.tree_leaves_with_path(grads[name])}
        moved = jax.jit(lambda a, b: leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, a, b)))({"params": p}, init(key))
        out["delta_norms"] = {k: float(n) for k, n in moved.items()}
    out["routing"] = []
    out["timing"] = {k: round(t, 3) for k, t in clock.items()}
    return out


# ---------------------------------------------- work, from shapes alone
def _fwd_flops_per_token(cfg: Dict[str, Any], length: int) -> Dict[str, float]:
    d = dims(cfg)
    dim = d["dim"]
    qk, vv = d["lk_heads"] * d["lk"], d["lv_heads"] * d["lv"]
    delta_net = 2.0 * (dim * (2 * qk + 2 * vv) + dim * 2 * d["lv_heads"]
                       + d["conv"] * (2 * qk + vv) + vv * dim) \
        + delta_rule_flops_per_token(d, _chunk(cfg))["total"]
    H, hd = d["heads"], d["head"]
    # causal: a query sees half the keys on average; q.k^T and p.v
    attention = 2.0 * 4 * dim * H * hd + 2.0 * length / 2.0 * H * 2 * hd
    mlp = 2.0 * 3 * dim * d["mlp"]
    count = {k: d["kinds"].count(k) for k in KINDS}
    return {"delta_net": delta_net, "attention": attention, "mlp": mlp,
            "head": 2.0 * dim * d["vocab"],
            "total": count["linear_attention"] * (delta_net + mlp)
            + count["full_attention"] * (attention + mlp)
            + 2.0 * dim * d["vocab"]}


def train_flops_per_item(cfg: Dict[str, Any], length: int = 8192) -> float:
    """Matrix-multiplication, attention and delta-rule FLOPs that one
    packed row of ``length`` tokens requires, forward and backward
    (backward = 2 x forward; nothing recomputed counts): the projections,
    the convolution, the chunked delta rule's products at chunk 64 and the
    published 96 x 192 (whatever a program pads), the causal half of the
    two attention products, the feed-forward part's three matrices and
    the head (the embedding's gather is no product). From shapes alone."""
    return 3.0 * length * _fwd_flops_per_token(cfg, length)["total"]


def delta_chunk_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One training step's chunk-local half of the delta rule (everything
    but the walk: what the calls named ``delta_chunk_*`` do, which is what
    the device trace can name), recomputation not counted, at the
    published head widths whatever a program pads. FLOPs a chunk and head,
    forward: ``K K^T``, ``Q K^T``, ``T (beta e^G K)`` (2 C^2 dk each),
    ``T (beta V)`` and ``P U`` (2 C^2 dv each), making ``T`` (2 C^3) and
    the output's ``(e^G Q) S_0`` (2 C dk dv): ``delta_rule_flops_per_token``'s
    total without its walk; twice that backward. Bytes a chunk and head
    that no schedule avoids: forward q, k (float32, as the L2 norm leaves
    them), v (bfloat16), g, beta read; ``W``, ``Kd``, ``qe``, ``P``
    (bfloat16) and ``U_0`` (float32) written, then ``qe``, ``P``, the
    chunk's starting state and ``U`` (float32) read and ``O`` (float32)
    written; backward every one of those read again with the cotangents of
    what was written, and the gradients of what was read written."""
    chunks = float(call["layers"]) * float(call["rows"]) \
        * float(call["heads"]) * float(call["len"]) / float(call["chunk"])
    C, dk, dv = (float(call[k]) for k in ("chunk", "key_dim", "value_dim"))
    flops = 2.0 * C * C * (3 * dk + 2 * dv + C) + 2.0 * C * dk * dv
    rows_in = 2 * C * dk * 4 + C * dv * 2 + 2 * C * 4       # q, k, v, g, beta
    tiles = 3 * C * dk * 2 + C * dv * 4 + C * C * 2         # W, Kd, qe, U0, P
    walked = dk * dv * 4 + C * dv * 4                       # S0, U
    out = C * dv * 4                                        # O
    forward = rows_in + tiles + (C * dk * 2 + C * C * 2) + walked + out
    return chunks * 3.0 * flops, chunks * 3.0 * forward
