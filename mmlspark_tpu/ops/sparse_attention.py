"""Attention over keys that a learned indexer chooses for each query
(DeepSeek Sparse Attention, DeepSeek-V3.2-Exp's report, arXiv:2512.02556):
the indexer's scores, the exact choice of the ``k`` best of a query's past,
the softmax core over the chosen keys, and the loss the indexer learns by.

For one row of ``L`` positions, ``H_I`` indexer heads of ``d_I`` over ONE
indexer key head, and a main attention of ``H`` query heads over ``G``
key/value heads of ``d``:

- **scores** (``indexer_scores``): ``I[t, s] = sum_j w[t, j] relu(qI[t, j]
  . kI[s])``, the products' operands in the callers' dtype, float32 from
  their accumulation on;
- **choice** (``select``): ``S_t`` = the ``min(k, t + 1)`` largest ``I[t,
  s]`` over ``s <= t``, a tie to the lower ``s`` (``lax.top_k``'s rule), as
  a MASK; no gradient passes through it;
- **core** (``selected_core``): ``o[t, h] = sum_{s in S_t} softmax_{s in
  S_t}(q[t, h] . k[s, g(h)] d^-1/2) v[s, g(h)]``, with each row's
  log-sum-exp;
- **the indexer's loss** (``indexer_loss``): ``p[t, s]`` = the mean over
  the ``H`` heads of the core's probabilities (``_target``, from the
  core's log-sum-exps; a constant of the loss), ``L_I = (1 / L)
  sum_t sum_{s in S_t} p[t, s] (log p[t, s] - log softmax_{S_t}(I[t,
  .])[s])``: the KL divergence from the indexer's distribution over the
  chosen keys to the main attention's.

**One layout** for everything that is a number a (query, key) pair: ``(B,
L / t, L, t)``, ``[b, i, s, j]`` the pair of query ``i t + j`` and key
``s``: keys in rows, a query tile a slab (``tile``: 512 where the row is
whole tiles of it, else the row). It is what the flash kernels read a
selection by as it lies (``pallas_attention.selected_attention``: they hold
their score tile keys-in-rows) and what the choice's kernel walks
(``pallas_select.topk_mask``: a count over a column of keys is a sum along
the sublanes); XLA writes it from the products at no cost.

Executors, picked from the shapes as ``full_attention`` picks its kernel
(every trace counts): the choice is ``pallas_select.topk_mask`` (bisection
on a score's bits in VMEM; ``sparse_attention.select_calls.pallas``) or
``_topk_mask_xla`` (``lax.top_k`` and a scatter, the statement of the rule;
``.xla``); the core is the flash kernels with the mask as an operand
(``sparse_attention.core_calls.pallas``) or a masked dense softmax
(``.xla``). On an accelerator a trace that takes an XLA form counts under
``sparse_attention.fallbacks`` as well. Scores, ``p`` and the loss are
XLA's, a query tile against a chunk of ``KEY_CHUNK`` keys at a time and
only the chunks that can hold a tile's past, all in ONE loop of one body
(``_steps``); the loss makes a step's scores again and recomputes each step
in the backward pass, so that no float a pair outlives the choice.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu.observability import metrics as obsmetrics
from mmlspark_tpu.parallel import sequence

KEY_CHUNK = 2048


def _wanted(use: str = "auto") -> bool:
    """Whether a caller's ``use_flash`` asks for a Pallas form here: always
    under "require", on an accelerator under "auto"."""
    return use == "require" or (use == "auto" and sequence._on_chip())


def _one_device() -> bool:
    """Not under a mesh of several devices, where a bare Mosaic call would
    be refused or gathered: these calls have no per-device form yet
    (``parallel/sequence.on_own_rows`` maps one result, the core has
    two)."""
    return sequence._mesh_to_map() is None


def tile_of(L: int) -> int:
    """The query tile of the pair layout for rows of ``L``."""
    from mmlspark_tpu.ops.pallas_attention import SELECTED_TILE
    return SELECTED_TILE if L % SELECTED_TILE == 0 else L


def _steps(L: int, t: int):
    """Every (query tile, key chunk) of a row of ``L`` that holds a causal
    pair -> ``(chunk, tiles, firsts)``: the chunk's extent in keys
    (``KEY_CHUNK`` where the row is whole chunks of it, else a tile's),
    and per step its query tile and its first key, the tiles in order.
    ONE loop over them walks a row's causal pairs at ``(n + 1) / (2 n)``
    of the square's work, ``n`` the chunks a row (0.5625 at 16,384), and
    is one body in the program: a loop an extent would be as many bodies
    as extents at every call site of every layer, and a step program's
    entry in a compile cache grows with them (PERF.md section 6, PR
    53)."""
    e = KEY_CHUNK if L % KEY_CHUNK == 0 and KEY_CHUNK % t == 0 else t
    steps = [(i, c * e) for i in range(L // t)
             for c in range(-(-(i + 1) * t // e))]
    return e, *(np.array(x, np.int32) for x in zip(*steps))


def _rows(x, first, count: int):
    """``count`` positions of ``x`` (B, L, ...) from ``first`` (traced)."""
    return jax.lax.dynamic_slice_in_dim(x, first, count, axis=1)


def _scores(k_i, q, w):
    """A query tile's scores against the keys ``k_i`` (B, keys, d_I): ``q``
    (B, t, H_I, d_I), ``w`` (B, t, H_I) -> (B, keys, t) float32."""
    s = jnp.einsum("bsd,btjd->bjst", k_i, q,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w.swapaxes(1, 2)[:, :, None, :], 1)


def indexer_scores(q_i: jax.Array, k_i: jax.Array, w: jax.Array,
                   t: int) -> jax.Array:
    """``q_i`` (B, L, H_I, d_I), ``k_i`` (B, L, d_I), ``w`` (B, L, H_I)
    float32 -> the scores in the pair layout, float32, a query tile
    against a key chunk at a time (``_steps``). A pair whose key lies past
    its query's tile's last chunk reads 0 (the choice looks at no such
    pair)."""
    B, L = q_i.shape[:2]
    e, tiles, firsts = _steps(L, t)

    def step(out, at):
        i, s = at
        got = _scores(_rows(k_i, s, e), _rows(q_i, i * t, t),
                      _rows(w, i * t, t))
        return jax.lax.dynamic_update_slice(
            out, got[:, None], (0, i, s, 0)), None
    with jax.named_scope("indexer_scores"):
        return jax.lax.scan(step, jnp.zeros((B, L // t, L, t), jnp.float32),
                            (tiles, firsts))[0]


def _topk_mask_xla(scores: jax.Array, k: int) -> jax.Array:
    """The rule of the choice, stated with ``lax.top_k``: the first
    ``min(k, q + 1)`` of query ``q``'s causal scores in ``top_k``'s order
    (descending, a tie to the lower index), scattered into a mask."""
    B, n, L, t = scores.shape
    rows = scores.transpose(0, 1, 3, 2).reshape(B, L, L)
    q = jnp.arange(L)
    # the two zeros are one score, whatever order a backend's top_k has
    rows = jnp.where(q[None, :] <= q[:, None],
                     jnp.where(rows == 0, 0.0, rows), -jnp.inf)
    _, idx = jax.lax.top_k(rows, min(k, L))
    real = jnp.arange(idx.shape[-1])[None, :] <= q[:, None]
    mask = jnp.zeros((B, L, L), jnp.int8).at[
        jnp.arange(B)[:, None, None], q[None, :, None], idx].max(
            jnp.broadcast_to(real, idx.shape).astype(jnp.int8))
    return mask.reshape(B, n, t, L).transpose(0, 1, 3, 2)


def select(scores: jax.Array, k: int) -> jax.Array:
    """The choice as an int8 mask in the pair layout (module docstring);
    ``scores`` carries no gradient to it."""
    from mmlspark_tpu.ops import pallas_select
    scores = jax.lax.stop_gradient(scores)
    with jax.named_scope("indexer_select"):
        if _wanted() and _one_device() \
                and pallas_select.supports(scores.shape):
            obsmetrics.counter("sparse_attention.select_calls.pallas").inc()
            return pallas_select.topk_mask(scores, k)
        if _wanted():
            obsmetrics.counter("sparse_attention.fallbacks").inc()
        obsmetrics.counter("sparse_attention.select_calls.xla").inc()
        return _topk_mask_xla(scores, k)


def _selected_xla(q, k, v, mask):
    """The core as a masked dense softmax: operands in their dtype, float32
    from the accumulation on, as ``sequence._reference_attention``."""
    B, L, H, d = q.shape
    G = k.shape[2]
    seen = mask.transpose(0, 2, 1, 3).reshape(B, 1, 1, L, L) != 0   # [s, t]
    s = jnp.einsum("bsgd,btgrd->bgrst", k, q.reshape(B, L, G, H // G, d),
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    s = jnp.where(seen, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=3)                       # (B, G, R, L)
    p = jnp.exp(s - lse[:, :, :, None, :])
    o = jnp.einsum("bgrst,bsgd->btgrd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, L, H, d).astype(q.dtype), lse.reshape(B, H, L)


def selected_core(q: jax.Array, k: jax.Array, v: jax.Array,
                  mask: jax.Array, use_flash: str = "auto"
                  ) -> Tuple[jax.Array, jax.Array]:
    """``q`` (B, L, H, d), ``k`` and ``v`` (B, L, G, d), ``mask`` in the
    pair layout -> ``(output (B, L, H, d), log-sum-exps (B, H, L)
    float32)``. ``use_flash`` as ``full_attention``'s: "require" is the
    kernels or a ValueError (interpreted on the CPU)."""
    from mmlspark_tpu.ops import pallas_attention
    B, L, H, d = q.shape
    G, t = k.shape[2], mask.shape[3]
    if _wanted(use_flash):
        if _one_device() and pallas_attention.supports_selected(
                q.shape, t, q.dtype.itemsize):
            obsmetrics.counter("sparse_attention.core_calls.pallas").inc()
            # each key/value head repeated to the query heads it serves, as
            # GroupedAttention's causal call has them
            return pallas_attention.selected_attention(
                q, *(jnp.repeat(x, H // G, axis=2) for x in (k, v)), mask)
        if use_flash == "require":
            raise ValueError(f"use_flash='require': the flash kernels take "
                             f"no selection at q shape {tuple(q.shape)}, "
                             f"tile {t}")
        obsmetrics.counter("sparse_attention.fallbacks").inc()
    obsmetrics.counter("sparse_attention.core_calls.xla").inc()
    return _selected_xla(q, k, v, mask)


def _target(k, q, lse, chosen):
    """A query tile's ``p`` (module docstring): ``k`` (B, keys, G, d), ``q``
    (B, t, H, d), ``lse`` (B, t, H) -> (B, keys, t) float32, 0 off
    ``chosen``; a key/value group's heads at a time (a loop, not ``G``
    copies of its body in the program)."""
    B, t, H, d = q.shape
    G = k.shape[2]

    def group(total, x):
        k, q, lse = x           # (B, keys, d), (B, t, R, d), (B, t, R)
        z = jnp.einsum("bsd,btrd->brst", k, q,
                       preferred_element_type=jnp.float32) / np.sqrt(d)
        return total + jnp.sum(jnp.exp(
            z - lse.transpose(0, 2, 1)[:, :, None, :]), 1), None
    total, _ = jax.lax.scan(
        group, jnp.zeros(chosen.shape, jnp.float32),
        (k.transpose(2, 0, 1, 3),
         q.reshape(B, t, G, H // G, d).transpose(2, 0, 1, 3, 4),
         lse.reshape(B, t, G, H // G).transpose(2, 0, 1, 3)))
    return jnp.where(chosen, total / H, 0.0)


def indexer_loss(q_i: jax.Array, k_i: jax.Array, w: jax.Array,
                 q: jax.Array, k: jax.Array, lse: jax.Array,
                 mask: jax.Array) -> jax.Array:
    """``L_I`` (module docstring), the mean over the ``B`` rows, from the
    indexer's operands (``indexer_scores``'s), the main attention's ``q``
    (B, L, H, d) and ``k`` (B, L, G, d), the core's log-sum-exps (B, H, L)
    and the mask. A query tile against a key chunk at a time (``_steps``),
    each step recomputed in the backward pass: its scores again (the
    choice's are not kept: a float a pair), its target ``p`` from ``q``,
    ``k`` and ``lse`` under ``stop_gradient`` (scope ``indexer_target``),
    and its part of the KL terms, those with ``p = 0`` counting 0. With
    ``I`` the scores and ``Z_t`` the log of the sum of ``exp I[t, s]``
    over ALL of query ``t``'s chosen keys, a query's terms are ``sum_s p
    (log p - I) + Z_t sum_s p``: a step returns its two sums and its
    part of ``Z_t`` (a peak and the sum under it), and a tile's steps are
    joined after the loop. The gradient reaches ``q_i``, ``k_i`` and
    ``w`` alone."""
    B, L = q.shape[:2]
    t = mask.shape[3]
    e, tiles, firsts = _steps(L, t)
    q, k, lse = (jax.lax.stop_gradient(x) for x in (q, k, lse))
    lse = lse.transpose(0, 2, 1)

    def step(at):
        i, s = at
        chosen = jax.lax.dynamic_slice(
            mask, (0, i, s, 0), (B, 1, e, t))[:, 0] != 0
        with jax.named_scope("indexer_target"):
            p = _target(_rows(k, s, e), _rows(q, i * t, t),
                        _rows(lse, i * t, t), chosen)
        z = jnp.where(chosen, _scores(
            _rows(k_i, s, e), _rows(q_i, i * t, t), _rows(w, i * t, t)),
            -jnp.inf)
        # a shift, not a term: any finite one gives the same sum
        peak = jax.lax.stop_gradient(jnp.max(z, axis=1))
        peak = jnp.where(jnp.isfinite(peak), peak, -1e30)
        live = chosen & (p > 0)
        terms = jnp.sum(jnp.where(
            live, p * (jnp.log(jnp.where(live, p, 1.0))
                       - jnp.where(live, z, 0.0)), 0.0), axis=1)
        return terms, jnp.sum(p, axis=1), peak, \
            jnp.sum(jnp.exp(z - peak[:, None, :]), axis=1)
    terms, mass, peak, under = jax.lax.map(jax.checkpoint(step),
                                           (tiles, firsts))   # (steps, B, t)
    n = L // t
    top = jax.ops.segment_max(peak, tiles, n)
    under = under * jnp.exp(peak - top[tiles])
    log_sum = top + jnp.log(
        jax.ops.segment_sum(under, tiles, n))  # lint: allow-embed
    mass = jax.ops.segment_sum(mass, tiles, n)  # lint: allow-embed
    return (jnp.sum(terms) + jnp.sum(mass * log_sum)) / (B * L)


def pair_counts(mask: jax.Array) -> Tuple[jax.Array, float]:
    """(pairs the mask keeps, float32; the causal pairs of its rows)."""
    B, _, L, _ = mask.shape
    return jnp.sum(mask.astype(jnp.int32)).astype(jnp.float32), \
        B * L * (L + 1) / 2.0
