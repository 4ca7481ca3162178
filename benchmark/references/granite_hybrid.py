"""Plain reference: Granite 4.0-H Micro (``granite_hybrid``), training,
float32.

Written from the published ``config.json`` (huggingface.co/ibm-granite/
granite-4.0-h-micro, ``model_type: granitemoehybrid`` with
``num_local_experts`` 0; the state-space layers are Mamba-2, Dao and Gu
2024, arXiv:2405.21060) in straightforward ``jax.numpy``: no kernels, no
flax, nothing imported from the program (four helpers that belong to no
family come from a sibling reference). Pre-norm decoder, no biases but the
convolution's, every RMSNorm with a plain scale, ``D`` = ``hidden_size``:

- ``h_0 = embedding_multiplier x E[token]``;
- layer ``l``: ``h <- h + r mixer_l(norm1(h))``, ``h <- h + r mlp(norm2(h))``
  with ``r`` = ``residual_multiplier`` and ``mixer_l`` read off
  ``layer_types[l]``;
- ``mamba``, the Mamba-2 mixer: ``[z | xBC | dt] = u W_in`` (widths ``H P``
  | ``H P + 2 G N`` | ``H``); ``xBC <- silu(conv(xBC) + b)``, a causal
  depthwise convolution of width 4 (three zeros before the row); ``[x | B |
  C] = xBC`` with ``x`` (L, H, P) and ``B``, ``C`` (L, G, N), head ``h``
  reading group ``h // (H / G)`` (``G`` = 1: every head reads the one ``B``
  and ``C``); ``dt = softplus(dt + dt_bias)`` (no clamp), ``A =
  -exp(A_log)``; per head, TOKEN BY TOKEN: ``S <- exp(dt_t A) S + dt_t B_t
  x_t^T``, ``y_t = S^T C_t + D_skip x_t`` (a scan over blocks of tokens
  whose inner loop is rematerialised, so that 8,192 steps of a 2 MB state
  fit; that changes no value); ``y <- rmsnorm(y silu(z)) w_n`` with the
  gate first and the mean square over all ``H P`` channels; through
  ``W_out``;
- ``attention``: ``q`` on 32 heads, ``k``, ``v`` on 8; causal softmax of
  ``attention_multiplier x q k^T`` (NOT ``head^-1/2``) with query head
  ``h`` reading key/value head ``h // 4``, one query head at a time;
  through ``W_o``. No positions (``position_embedding_type: nope``), no
  norms on q or k, no gate;
- the feed-forward part of every layer (``shared_mlp``): ``W_2 (silu(a) *
  b)`` with ``[a | b] = u W_1``, the two halves of ``W_1`` held as two
  matrices (``mlp_gate``, ``mlp_up``);
- a final norm; ``logits = (h E^T) / logits_scaling`` with the SAME table
  ``E`` (``tie_word_embeddings``); next-token cross-entropy.

Departures and sizes set here (the configuration file lists each under
``assumed``): no positions in attention; ``A_log = log(U(1, 16))``,
``dt_bias`` the inverse softplus of ``exp(U(log 1e-3, log 1e-1))`` floored
at 1e-4, ``D_skip`` = 1, matrices, the table, the convolution and its bias
normal(0, 0.02); columns of ``W_in`` in ``[z | x | B | C | dt]`` order,
head-major; a packed row is one document; AdamW.

``quant`` rounds both operands of every matrix multiplication (the
state's update and read-out among them) through a lower precision: the
control that the comparison deciding ``correct`` has to fail.

``train_reference`` walks the layers: four float32 copies of 772M
parameters (weights, gradient, AdamW's two moments) are 12.35 GB and leave
a 16 GB chip no room for a layer's float32 activations, so the gradient is
never whole on the device. Forward, layer by layer, keeping each layer's
input; backward from the loss, layer by layer (``jax.vjp`` of that one
layer, its two halves under ``jax.checkpoint``), each layer's gradient
going into its AdamW update (and, at the first step, to the host) before
the next layer's is made. Everything stays on the device; the table's
gradient waits from the head, its first source, for the embedding's
gather, its second. The values are those of ``jax.grad`` of
``sequence_loss`` (``tests/test_granite_hybrid.py`` holds the two
together).

**What is read of the configuration file** (``runners/README.md`` is not
this PR's to edit): ``hidden_size``, ``num_hidden_layers`` and
``layer_types`` (as long), ``num_attention_heads``,
``num_key_value_heads``, ``mamba_n_heads``, ``mamba_d_head``,
``mamba_d_state``, ``mamba_n_groups``, ``mamba_d_conv``,
``shared_intermediate_size``, ``embedding_multiplier``,
``attention_multiplier``, ``residual_multiplier``, ``logits_scaling``,
``vocab_size``, ``rms_norm_eps``, ``program.chunk`` and
``program.zoo_args``; the runner reads ``program.zoo`` / ``.loss_chunk``,
``optimizer`` and ``limits``. The head width of attention is ``hidden_size
/ num_attention_heads`` (the config has no key for it).

Also here, because the benchmark keeps them: what the runner asks a family
for (``zoo_args``, ``routed_blocks``, ``head_kernel``, ``kernel_calls``,
``LOSS_PARTS``, ``AUX``), and the operations and bytes of the state's walk
from chunk to chunk (``ssd_walk_cost``).
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
CHUNK = 256
TIME_STEP = (1e-3, 0.1, 1e-4)   # dt at init: min, max, floor
LOSS_PARTS = ("main",)          # the heads of the loss, beside the whole
AUX = ("loss.main",)            # the ring's scalars beside the loss


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != int(cfg["num_hidden_layers"]) \
            or set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {kinds!r} against "
                         f"{cfg['num_hidden_layers']} layers of 'mamba' or "
                         "'attention'")
    dim, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "dim": dim, "kinds": kinds, "heads": heads,
        "kv_heads": int(cfg["num_key_value_heads"]), "head": dim // heads,
        "m_heads": int(cfg["mamba_n_heads"]),
        "m_head": int(cfg["mamba_d_head"]),
        "state": int(cfg["mamba_d_state"]),
        "groups": int(cfg["mamba_n_groups"]),
        "conv": int(cfg["mamba_d_conv"]),
        "mlp": int(cfg["shared_intermediate_size"]),
        "embed_mult": float(cfg["embedding_multiplier"]),
        "attn_mult": float(cfg["attention_multiplier"]),
        "resid_mult": float(cfg["residual_multiplier"]),
        "logits_scaling": float(cfg["logits_scaling"]),
        "vocab": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
    }


# ----------------------------------------------- what the runner asks for
def zoo_args(cfg: Dict[str, Any], length: int) -> Dict[str, Any]:
    """The configuration's published keys as the zoo entry's arguments."""
    d = dims(cfg)
    return dict(
        vocab=d["vocab"], dim=d["dim"], layer_types=d["kinds"],
        heads=d["heads"], kv_heads=d["kv_heads"], head_dim=d["head"],
        mamba_heads=d["m_heads"], mamba_head_dim=d["m_head"],
        state=d["state"], groups=d["groups"], conv_width=d["conv"],
        mlp_hidden=d["mlp"], embedding_multiplier=d["embed_mult"],
        attention_multiplier=d["attn_mult"],
        residual_multiplier=d["resid_mult"],
        logits_scaling=d["logits_scaling"], eps=d["eps"], chunk=_chunk(cfg),
        max_len=length,
        **cfg["program"].get("zoo_args", {}))


def _chunk(cfg: Dict[str, Any]) -> int:
    return int(cfg["program"].get("chunk", CHUNK))


def routed_blocks(cfg: Dict[str, Any]) -> List[str]:
    """The blocks with a routed layer: none, the model is dense."""
    return []


def head_kernel(params: Dict[str, Any]) -> jax.Array:
    """The output matrix ``(dim, vocab)`` the chunked loss reads: the one
    table, transposed."""
    return params["params"]["token_embedding"]["embedding"].T


def kernel_calls(cfg: Dict[str, Any], rows: int, length: int,
                 slots: float = 0.0) -> Dict[str, Dict[str, Any]]:
    """Shapes of the kernels' work: one forward call of the flash kernel,
    one step's walks of the state (``slots`` is a routed family's)."""
    d = dims(cfg)
    return {
        "flash_fwd": {"rows": rows, "len": length, "heads": d["heads"],
                      "head_dim": d["head"]},
        "ssd_walk": {"rows": rows, "len": length, "heads": d["m_heads"],
                     "state": d["state"], "head_dim": d["m_head"],
                     "chunk": _chunk(cfg),
                     "layers": d["kinds"].count("mamba")}}


# ------------------------------------------------------------------ weights
def _mixer_shapes(d, kind: str) -> Dict[str, Any]:
    dim = d["dim"]
    if kind == "mamba":
        inner = d["m_heads"] * d["m_head"]
        mixed = inner + 2 * d["groups"] * d["state"]
        return {"attn_gate_value_key_query_dt": {
                    "kernel": (dim, inner + mixed + d["m_heads"])},
                "conv_kernel": (d["conv"], mixed), "conv_bias": (mixed,),
                "A_log": (d["m_heads"],), "dt_bias": (d["m_heads"],),
                "D_skip": (d["m_heads"],), "gate_norm": {"scale": (inner,)},
                "attn_out": {"kernel": (inner, dim)}}
    H, G, hd = d["heads"], d["kv_heads"], d["head"]
    return {"attn_query": {"kernel": (dim, H * hd)},
            "attn_key": {"kernel": (dim, G * hd)},
            "attn_value": {"kernel": (dim, G * hd)},
            "attn_out": {"kernel": (H * hd, dim)}}


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree (``models/zoo/decoder.GraniteHybrid``),
    leaf shapes only. One table: there is no ``lm_head``."""
    d = dims(cfg)
    dim = d["dim"]
    p = {"token_embedding": {"embedding": (d["vocab"], dim)},
         "final_norm": {"scale": (dim,)}}
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
    matrices, the table, the convolution and its bias normal(0, 0.02);
    norm scales and ``D_skip`` 1; ``A_log = log(U(1, 16))``; ``dt_bias``
    the inverse softplus of ``exp(U(log 1e-3, log 1e-1))`` floored at
    1e-4. Leaves of one shape are drawn in one call and dealt out in the
    tree's order, from XLA's own bit generator ("rbg": as
    ``glm47_flash.init_params``, and for its reasons). The key is an
    argument, never a constant of the program."""
    key = jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg")
    flat, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    names = [jax.tree_util.keystr(path) for path, _ in flat]
    fixed = ("scale", "D_skip", "A_log", "dt_bias")
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
    lo, hi, floor = TIME_STEP
    for word, salt in (("A_log", 0), ("dt_bias", 1)):
        where = [i for i, n in enumerate(names) if word in n]
        u = jax.random.uniform(
            jax.random.fold_in(key, len(by_shape) + salt),
            (len(where),) + flat[where[0]][1], jnp.float32)
        for n, i in enumerate(where):
            if word == "A_log":
                leaves[i] = jnp.log(1.0 + 15.0 * u[n])
            else:
                dt = jnp.maximum(floor, jnp.exp(
                    np.log(lo) + u[n] * (np.log(hi) - np.log(lo))))
                leaves[i] = dt + jnp.log(-jnp.expm1(-dt))
    for i, (_, shape) in enumerate(flat):
        if leaves[i] is None:
            leaves[i] = jnp.ones(shape, jnp.float32)
    return jax.tree_util.tree_unflatten(tree, leaves)


# ------------------------------------------------------------------ forward
def _rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _conv(x, kernel, bias):
    """Causal depthwise: y_t = sum_j kernel[j] x_{t - (W-1) + j} + bias."""
    width, L = kernel.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    return sum(xp[j:j + L] * kernel[j] for j in range(width)) + bias


def _ssd(mm, x, dt, A, B, C, block: int = 64):
    """Token by token. x (L, H, P), dt (L, H), A (H,), B, C (L, H, N)
    (already one a head); returns (L, H, P) without the skip."""
    L, H, P = x.shape
    pad = -L % block
    xs = tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (-1, block) + a.shape[1:]) for a in (x, dt, B, C))

    def token(S, t):
        x_t, dt_t, B_t, C_t = t
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + mm("hn,hp->hnp", B_t, dt_t[:, None] * x_t)
        return S, mm("hnp,hn->hp", S, C_t)

    @jax.checkpoint
    def tokens(S, t):
        return jax.lax.scan(token, S, t)
    _, y = jax.lax.scan(tokens, jnp.zeros((H, B.shape[-1], P)), xs)
    return y.reshape((-1,) + y.shape[2:])[:L]


def _mamba(d, mm, p, u):
    L = u.shape[0]
    H, P, N, G = d["m_heads"], d["m_head"], d["state"], d["groups"]
    inner, mixed = H * P, H * P + 2 * G * N
    zxbcdt = mm("ld,dk->lk", u, p["attn_gate_value_key_query_dt"]["kernel"])
    z = zxbcdt[:, :inner]
    xbc = jax.nn.silu(_conv(zxbcdt[:, inner:inner + mixed],
                            p["conv_kernel"], p["conv_bias"]))
    dt = jax.nn.softplus(zxbcdt[:, inner + mixed:] + p["dt_bias"])
    x = xbc[:, :inner].reshape(L, H, P)
    serves = jnp.arange(H) // (H // G)          # head -> its group
    B = xbc[:, inner:inner + G * N].reshape(L, G, N)[:, serves]
    C = xbc[:, inner + G * N:].reshape(L, G, N)[:, serves]
    y = _ssd(mm, x, dt, -jnp.exp(p["A_log"]), B, C) \
        + p["D_skip"][:, None] * x
    y = _rms(y.reshape(L, inner) * jax.nn.silu(z), p["gate_norm"], d["eps"])
    return mm("lk,kd->ld", y, p["attn_out"]["kernel"])


def _attention(d, mm, p, x):
    L, H, G, hd = x.shape[0], d["heads"], d["kv_heads"], d["head"]
    q = mm("ld,dk->lk", x, p["attn_query"]["kernel"]).reshape(L, H, hd)
    k = mm("ld,dk->lk", x, p["attn_key"]["kernel"]).reshape(L, G, hd)
    v = mm("ld,dk->lk", x, p["attn_value"]["kernel"]).reshape(L, G, hd)
    future = jnp.arange(L)[None, :] > jnp.arange(L)[:, None]
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    def head(args):             # one query head at a time: (L, L) scores
        q_h, group = args
        s = jnp.where(future, -jnp.inf,
                      d["attn_mult"] * mm("qk,nk->qn", q_h, k[group]))
        return mm("qn,nk->qk", jax.nn.softmax(s, axis=-1), v[group])
    o = jax.lax.map(jax.checkpoint(head), (
        q.transpose(1, 0, 2), jnp.arange(H) // (H // G)))
    return mm("lk,kd->ld", o.transpose(1, 0, 2).reshape(L, H * hd),
              p["attn_out"]["kernel"])


def _mix(d, mm, kind, p, x):
    mixer = _mamba if kind == "mamba" else _attention
    return x + d["resid_mult"] * mixer(
        d, mm, p["attn"], _rms(x, p["norm1"], d["eps"]))


def _feed(d, mm, p, h):
    return h + d["resid_mult"] * _swiglu(
        mm, p["ffn"], _rms(h, p["norm2"], d["eps"]))


def _block(d, mm, kind, p, x):
    """One layer on one sequence (L, dim); its halves rematerialised apart
    (that changes no value)."""
    h = jax.checkpoint(functools.partial(_mix, d, mm, kind))(p, x)
    return jax.checkpoint(functools.partial(_feed, d, mm))(p, h)


def _embed(d, table, tokens):
    return d["embed_mult"] * table[tokens]


def _logits(d, mm, table, norm, x):
    return mm("ld,vd->lv", _rms(x, norm, d["eps"]), table) \
        / d["logits_scaling"]


def hidden_rows(cfg: Dict[str, Any], mm, params: Dict[str, Any],
                tokens: jax.Array) -> jax.Array:
    """One sequence ``tokens`` (L,) -> the residual stream after the last
    layer, (L, dim), before the final norm."""
    d = dims(cfg)
    p = params["params"]
    x = _embed(d, p["token_embedding"]["embedding"], tokens)
    for i, kind in enumerate(d["kinds"]):
        x = _block(d, mm, kind, p[f"block{i}"], x)
    return x


def logits(cfg, params, tokens, quant=None):
    """One sequence (L,) -> the tied head's (L, vocab) float32 logits."""
    mm = _products(quant)
    p = params["params"]
    return _logits(dims(cfg), mm, p["token_embedding"]["embedding"],
                   p["final_norm"], hidden_rows(cfg, mm, params, tokens))


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
def train_reference(cfg: Dict[str, Any], seed: int, tokens: np.ndarray, *,
                    steps: int, optimizer: Dict[str, Any],
                    quant: Optional[str] = None) -> Dict[str, Any]:
    """Follow the first ``steps`` AdamW steps from the seeded weights on
    ``tokens[s]`` (``(rows, L)`` int32, one batch per step), float32 at
    the highest matmul precision, the layers walked one by one (module
    docstring); decay on leaves of two and more dimensions.

    Returns what ``qwen3_next.train_reference`` returns but the routing:
    per step the loss (``losses`` and ``main``; ``mtp`` is empty, there is
    no such head), the first gradient (leaves on the host in the program's
    tree order, and their norms), the per-leaf norm of the parameters'
    change, and ``timing`` in seconds.
    """
    import time
    lr, b1, b2 = (float(optimizer[k]) for k in
                  ("learning_rate", "beta1", "beta2"))
    eps, decay = float(optimizer["eps"]), float(optimizer["weight_decay"])
    d, mm = dims(cfg), _products(quant)
    rows, L = tokens.shape[1:]
    clock = {"init": 0.0, "first_step": 0.0, "other_steps": 0.0,
             "fetch": 0.0}

    def over_rows(f):           # a layer's function, every row of the batch
        return jax.vmap(f, in_axes=(None, 0))

    blocks = {kind: over_rows(functools.partial(_block, d, mm, kind))
              for kind in set(d["kinds"])}
    forward = {kind: jax.jit(f) for kind, f in blocks.items()}

    def back(kind):
        def f(p, x, dy):
            return jax.vjp(blocks[kind], p, x)[1](dy)      # (dp, dx)
        return jax.jit(f, donate_argnums=(2,))
    backward = {kind: back(kind) for kind in blocks}
    embed = jax.jit(functools.partial(_embed, d))

    @functools.partial(jax.jit, donate_argnums=(2,))
    def head(table, norm, x, toks):
        def loss(table, norm, x):
            return jnp.sum(jax.vmap(lambda x_, t: _nll(
                _logits(d, mm, table, norm, x_), t, rows * (L - 1)))(x, toks))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(table, norm, x)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def embed_back(dtable, table, toks, dx):
        return dtable + jax.vjp(lambda t: _embed(d, t, toks), table)[1](dx)[0]

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
            """A part's gradient: kept for the comparison at the first
            step (norms on the device, leaves on the host), then into its
            AdamW update."""
            if s == 0:
                t0 = time.perf_counter()
                first[name] = (norms(g), jax.device_get(g))
                clock["fetch"] += time.perf_counter() - t0
            p[name], m[name], v[name] = adamw(
                p[name], m[name], v[name], g, float(s + 1))

        for s in range(steps):
            t0 = time.perf_counter()
            toks = jnp.asarray(tokens[s])
            table = p["token_embedding"]["embedding"]
            xs = [embed(table, toks)]
            for i, kind in enumerate(d["kinds"]):
                xs.append(forward[kind](p[f"block{i}"], xs[-1]))
            loss, (dtable, dnorm, dx) = head(
                table, p["final_norm"], xs.pop(), toks)
            update("final_norm", dnorm, s)
            for i in reversed(range(len(d["kinds"]))):
                g, dx = backward[d["kinds"][i]](p[f"block{i}"], xs.pop(), dx)
                update(f"block{i}", g, s)
            update("token_embedding",
                   {"embedding": embed_back(dtable, table, toks, dx)}, s)
            del table, dtable, dx
            out["losses"].append(float(loss))
            out["main"].append(float(loss))
            jax.block_until_ready(p)
            clock["other_steps" if s else "first_step"] += \
                time.perf_counter() - t0
        del m, v
        grads = {"params": {k: g for k, (_, g) in first.items()}}
        out["first_grad"] = [np.asarray(x)
                             for x in jax.tree_util.tree_leaves(grads)]
        out["grad_norms"] = {       # the whole tree's leaf order
            f"['params']['{name}']{leaf}": float(n)
            for name in sorted(first) for leaf, n in first[name][0].items()}
        moved = jax.jit(lambda a, b: leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, a, b)))({"params": p}, init(key))
        out["delta_norms"] = {k: float(n) for k, n in moved.items()}
    out["routing"] = []
    out["timing"] = {k: round(t, 3) for k, t in clock.items()}
    return out


# ---------------------------------------------- work, from shapes alone
def ssd_flops_per_token(d: Dict[str, Any], chunk: int = CHUNK
                        ) -> Dict[str, float]:
    """Forward FLOPs a token of ONE Mamba-2 layer's rule in its chunked
    form: ``C B^T`` a GROUP (2 x chunk x N), and a head ``((C B^T) * M) (dt
    x)`` (2 x chunk x P), ``B^T (decayed dt x)`` and ``C S_0`` (2 x N x P
    each); whole chunk-by-chunk blocks, as ``qwen3_next``'s count of its
    rule (the block on the diagonal is the algorithm's own, not a mask a
    kernel could skip). ``walk`` is what the scan from chunk to chunk
    holds: a multiply and an add an element of the state a chunk."""
    H, P, N, G = d["m_heads"], d["m_head"], d["state"], d["groups"]
    return {"total": G * 2.0 * chunk * N + H * 2.0 * (chunk * P + 2 * N * P),
            "walk": H * 2.0 * N * P / chunk}


def _fwd_flops_per_token(cfg: Dict[str, Any], length: int) -> Dict[str, float]:
    d = dims(cfg)
    dim = d["dim"]
    inner = d["m_heads"] * d["m_head"]
    mixed = inner + 2 * d["groups"] * d["state"]
    mamba = 2.0 * (dim * (inner + mixed + d["m_heads"]) + d["conv"] * mixed
                   + inner * dim) + ssd_flops_per_token(d, _chunk(cfg))["total"]
    H, G, hd = d["heads"], d["kv_heads"], d["head"]
    # causal: a query sees half the keys on average; q.k^T and p.v
    attention = 2.0 * (2 * dim * H * hd + 2 * dim * G * hd) \
        + 2.0 * length / 2.0 * H * 2 * hd
    mlp = 2.0 * 3 * dim * d["mlp"]
    count = {k: d["kinds"].count(k) for k in ("mamba", "attention")}
    return {"mamba": mamba, "attention": attention, "mlp": mlp,
            "head": 2.0 * dim * d["vocab"],
            "total": count["mamba"] * (mamba + mlp)
            + count["attention"] * (attention + mlp)
            + 2.0 * dim * d["vocab"]}


def train_flops_per_item(cfg: Dict[str, Any], length: int = 8192) -> float:
    """Matrix-multiplication, attention and state-space FLOPs that one
    packed row of ``length`` tokens requires, forward and backward
    (backward = 2 x forward; nothing recomputed counts): the projections,
    the convolution, the chunked rule's products at the configuration's
    chunk, the causal half of the two attention products, the
    feed-forward part's three matrices and the head (the embedding's
    gather is no product). From shapes alone."""
    return 3.0 * length * _fwd_flops_per_token(cfg, length)["total"]


def ssd_walk_cost(call: Dict[str, Any]) -> Tuple[float, float]:
    """One training step's walks of the state from chunk to chunk (the
    scan under the scope ``ssd_scan``, which is what the device trace can
    name), recomputation not counted. A chunk's addend ``Z_c`` is made
    outside the walk, by a batched product; the walk is ``S <- exp(G_C) S +
    Z_c`` and hands out the state the chunk starts from. Forward, per chunk
    and head: 2 x N x P FLOPs; ``Z_c`` read and the starting state written
    (float32; the carried state stays on the chip). Backward, the scan's
    transpose: ``dS <- exp(G_C) dS + dS_0`` and the decay's gradient ``sum(dS
    * S_0)``, 4 x N x P FLOPs; the starting state and its cotangent read,
    ``dZ_c`` written. Five float32 states a chunk through HBM, which is
    the bound."""
    chunks = float(call["layers"]) * float(call["rows"]) \
        * float(call["heads"]) * float(call["len"]) / float(call["chunk"])
    state = float(call["state"]) * float(call["head_dim"])
    return chunks * 6.0 * state, chunks * 5.0 * state * 4.0
