"""Plain reference: Vision Transformer (Dosovitskiy et al. 2020), float32.

Written from the published equations in straightforward ``jax.numpy``: no
kernels, no flax, nothing imported from the program. Pre-norm encoder:
patchify (a matrix multiplication over 16x16x3 patches), [CLS] token,
learned positions, ``depth`` blocks of multi-head attention and a GELU MLP,
final LayerNorm, linear head on the [CLS] row, softmax cross-entropy.

Departures from the paper, all to follow the program's block
(``mmlspark_tpu/models/zoo/vit.py``): LayerNorm epsilon 1e-6 and the tanh
approximation of GELU (the defaults of flax, which google-research's own
code uses too); no dropout; no hidden "representation" layer.

The weights are the benchmark's own, made from the seed by
:func:`init_params` in the tree layout the program's module reads, so the
same values go to the program and to this reference. ``quant`` rounds both
operands of every matrix multiplication through a lower precision: it is
the control that the comparison deciding ``correct`` has to fail.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-6


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    p = int(cfg["patch_size"])
    side = int(cfg["image_size"])
    return {"patch": p, "side": side, "chan": int(cfg["num_channels"]),
            "dim": int(cfg["hidden_size"]), "depth": int(cfg["num_layers"]),
            "heads": int(cfg["num_heads"]), "mlp": int(cfg["mlp_dim"]),
            "classes": int(cfg["num_classes"]),
            "tokens": (side // p) ** 2 + 1}


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Weights from a PRNG key in the program's tree layout, float32 (the
    trainer keeps float32 master weights and computes in bfloat16). Kernels
    and positions are normal(0, 0.02), biases 0, norms 1/0, [CLS] 0. The
    key is an argument, never a constant of the program: every seed runs
    the same compiled code."""
    d = dims(cfg)
    dim, heads, hd = d["dim"], d["heads"], d["dim"] // d["heads"]
    names = ["patch", "pos", "head"] + [
        f"b{i}.{k}" for i in range(d["depth"])
        for k in ("q", "k", "v", "o", "up", "down")]
    keys = dict(zip(names, jax.random.split(key, len(names))))

    def normal(name, shape):
        return 0.02 * jax.random.normal(keys[name], shape, jnp.float32)

    def norm():
        return {"scale": jnp.ones((dim,), jnp.float32),
                "bias": jnp.zeros((dim,), jnp.float32)}

    p: Dict[str, Any] = {
        "patch_embedding": {
            "kernel": normal("patch", (d["patch"], d["patch"], d["chan"],
                                       dim)),
            "bias": jnp.zeros((dim,), jnp.float32)},
        "cls": jnp.zeros((1, 1, dim), jnp.float32),
        "pos_embedding": normal("pos", (1, d["tokens"], dim)),
        "final_norm": norm(),
        "head": {"kernel": normal("head", (dim, d["classes"])),
                 "bias": jnp.zeros((d["classes"],), jnp.float32)},
    }
    for i in range(d["depth"]):
        attn = {n: {"kernel": normal(f"b{i}.{n[0]}", (dim, heads, hd)),
                    "bias": jnp.zeros((heads, hd), jnp.float32)}
                for n in ("query", "key", "value")}
        attn["out"] = {"kernel": normal(f"b{i}.o", (heads, hd, dim)),
                       "bias": jnp.zeros((dim,), jnp.float32)}
        p[f"block{i}"] = {
            "norm1": norm(), "attn": attn, "norm2": norm(),
            "mlp": {"mlp_up": {"kernel": normal(f"b{i}.up", (dim, d["mlp"])),
                               "bias": jnp.zeros((d["mlp"],), jnp.float32)},
                    "mlp_down": {"kernel": normal(f"b{i}.down",
                                                  (d["mlp"], dim)),
                                 "bias": jnp.zeros((dim,), jnp.float32)}}}
    return {"params": p}


def _rounder(quant: Optional[str]):
    """Round a matmul operand through a lower precision. The backward pass
    sees the rounded operands but its cotangents stay float32 (a straight-
    through rounding): cast back through fp8 they would all underflow to
    zero, and the control would fail for a reason no PR would ship."""
    if quant is None:
        return lambda x: x
    dtype = {"fp8": jnp.float8_e4m3fn, "bf16": jnp.bfloat16}.get(quant)
    if dtype is None:
        raise ValueError(f"unknown control precision {quant!r}")
    return lambda x: x + jax.lax.stop_gradient(
        x.astype(dtype).astype(jnp.float32) - x)


def _layer_norm(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def logits(cfg: Dict[str, Any], params: Dict[str, Any], images: jax.Array,
           quant: Optional[str] = None) -> jax.Array:
    """images (B, side, side, chan) float32, already normalized ->
    (B, classes) float32."""
    d = dims(cfg)
    r = _rounder(quant)
    p = params["params"]
    B = images.shape[0]
    g, ps, heads = d["side"] // d["patch"], d["patch"], d["heads"]
    hd = d["dim"] // heads

    def mm(eq, a, b):
        return jnp.einsum(eq, r(a), r(b))

    x = images.reshape(B, g, ps, g, ps, d["chan"]).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(B, g * g, ps * ps * d["chan"])
    x = mm("bnk,kd->bnd", x, p["patch_embedding"]["kernel"].reshape(
        -1, d["dim"])) + p["patch_embedding"]["bias"]
    x = jnp.concatenate([jnp.broadcast_to(p["cls"], (B, 1, d["dim"])), x], 1)
    x = x + p["pos_embedding"]
    for i in range(d["depth"]):
        blk = p[f"block{i}"]
        a = blk["attn"]
        y = _layer_norm(x, blk["norm1"])
        q = mm("bnd,dhk->bnhk", y, a["query"]["kernel"]) + a["query"]["bias"]
        k = mm("bnd,dhk->bnhk", y, a["key"]["kernel"]) + a["key"]["bias"]
        v = mm("bnd,dhk->bnhk", y, a["value"]["kernel"]) + a["value"]["bias"]
        s = mm("bqhk,bnhk->bhqn", q / np.sqrt(hd), k)
        w = jax.nn.softmax(s, axis=-1)
        o = mm("bhqn,bnhk->bqhk", w, v)
        x = x + mm("bqhk,hkd->bqd", o, a["out"]["kernel"]) + a["out"]["bias"]
        y = _layer_norm(x, blk["norm2"])
        m = blk["mlp"]
        h = _gelu(mm("bnd,dm->bnm", y, m["mlp_up"]["kernel"])
                  + m["mlp_up"]["bias"])
        x = x + mm("bnm,md->bnd", h, m["mlp_down"]["kernel"]) \
            + m["mlp_down"]["bias"]
    x = _layer_norm(x, p["final_norm"])[:, 0]
    return mm("bd,dc->bc", x, p["head"]["kernel"]) + p["head"]["bias"]


def _loss_sum(cfg, quant, mean, std, params, u8, labels):
    d = dims(cfg)
    x = (u8.astype(jnp.float32) - mean) / std
    x = x.reshape((-1, d["side"], d["side"], d["chan"]))
    lg = logits(cfg, params, x, quant)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1).sum()


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """{path: Frobenius norm} of every leaf, in float32."""
    flat = jax.tree_util.tree_leaves_with_path(tree)
    return {jax.tree_util.keystr(path): jnp.sqrt(
        jnp.sum(jnp.square(leaf.astype(jnp.float32))))
        for path, leaf in flat}


def train_reference(cfg: Dict[str, Any], seed: int, images_u8: np.ndarray,
                    labels: np.ndarray, *, steps: int, lr: float,
                    momentum: float, mean: float, std: float,
                    block_rows: int, quant: Optional[str] = None
                    ) -> Dict[str, Any]:
    """Follow the first ``steps`` SGD-with-momentum steps from the seeded
    weights, on ``images_u8[s]`` / ``labels[s]`` (one batch per step), in
    float32 at the highest matmul precision, ``block_rows`` rows at a time
    so that the activations fit beside whatever else the device holds.

    Returns the loss of each step, the first gradient (its leaves on the
    host, and their norms), and the per-leaf norm of the parameters' change
    after the last step.
    """
    with jax.default_matmul_precision("highest"):
        params0 = jax.jit(lambda key: init_params(cfg, key))(
            jax.random.PRNGKey(seed))
        grad_block = jax.jit(jax.value_and_grad(functools.partial(
            _loss_sum, cfg, quant, mean, std)))

        @jax.jit
        def update(params, trace, grads):
            trace = jax.tree_util.tree_map(
                lambda t, g: g + momentum * t, trace, grads)
            params = jax.tree_util.tree_map(
                lambda p, t: p - lr * t, params, trace)
            return params, trace

        add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
        norms = jax.jit(leaf_norms)
        delta = jax.jit(lambda a, b: leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, a, b)))

        params = params0
        trace = jax.tree_util.tree_map(jnp.zeros_like, params0)
        losses, first_grad = [], None
        for s in range(steps):
            rows = images_u8[s].shape[0]
            total, grads = 0.0, None
            for lo in range(0, rows, block_rows):
                hi = min(lo + block_rows, rows)
                l, g = grad_block(params, images_u8[s][lo:hi],
                                  labels[s][lo:hi])
                total += float(l)
                grads = g if grads is None else add(grads, g)
            grads = jax.tree_util.tree_map(lambda g: g / rows, grads)
            losses.append(total / rows)
            if s == 0:
                first_grad = {k: float(v) for k, v in norms(grads).items()}
                first_leaves = [np.asarray(g) for g in jax.device_get(
                    jax.tree_util.tree_leaves(grads))]
            params, trace = update(params, trace, grads)
        moved = {k: float(v) for k, v in delta(params, params0).items()}
    return {"losses": losses, "grad_norms": first_grad,
            "first_grad": first_leaves, "delta_norms": moved}


def train_flops_per_item(cfg: Dict[str, Any]) -> float:
    """Matrix-multiplication FLOPs that one image's forward and backward
    passes require (backward = 2x forward; nothing recomputed counts):
    patch embedding, q/k/v/out projections, the two attention products,
    the MLP and the head. From shapes alone."""
    d = dims(cfg)
    n, dim, mlp = d["tokens"], d["dim"], d["mlp"]
    patches = n - 1
    fwd = 2.0 * patches * (d["patch"] ** 2 * d["chan"]) * dim
    per_block = (4 * 2.0 * n * dim * dim          # q, k, v, out
                 + 2 * 2.0 * n * n * dim          # q.k^T and p.v
                 + 2 * 2.0 * n * dim * mlp)       # mlp up, down
    fwd += d["depth"] * per_block + 2.0 * dim * d["classes"]
    return 3.0 * fwd
