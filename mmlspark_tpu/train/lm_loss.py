"""The losses of a decoder LM without the whole logits in memory: next
token, and masked diffusion over a row's noised copy.

``(tokens, vocab)`` float32 logits and their gradient are the largest
arrays of an LM step (8,192 x 19,360 x 4 B = 634 MB each, per head) and
nothing needs them at once: ``chunked_cross_entropy`` walks the rows in
chunks, and each chunk's logits are recomputed in the backward pass
(``jax.checkpoint``), so one chunk's worth lives at a time.

``next_token_loss`` puts the targets in place: the main head's row ``i``
predicts token ``i + 1``; a multi-token-prediction head's row ``i``
(DeepSeek-V3 section 2.2, depth 1) predicts token ``i + 2`` through the
same output matrix. Rows that have no target count nothing. No document
mask: a packed row is one sequence. A term the model made itself
(``out["aux_loss"]``: the loss a learned indexer is trained by) is added.

``masked_diffusion_loss`` is block diffusion's (BD3-LM, arXiv:2503.09573;
the linear schedule of MDLM, arXiv:2406.07524): of a row ``[noised copy |
clean copy]`` the NOISED half's row ``i`` predicts the clean token AT
``i``, no shift, and counts ``weights[i]``: ``1 / t`` of its block where
the noised copy holds the mask token, 0 elsewhere. The clean half's rows
reach no loss.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def chunked_cross_entropy(hidden: jax.Array, kernel: jax.Array,
                          targets: jax.Array, weights: jax.Array,
                          chunk: int = 2048,
                          dtype=jnp.bfloat16) -> jax.Array:
    """Sum over rows of ``weights * -log softmax(hidden @ kernel)[target]``.

    ``hidden`` (N, D), ``kernel`` (D, V), ``targets`` and ``weights`` (N,).
    The product runs in ``dtype`` with float32 accumulation; the softmax
    and the sum are float32.
    """
    n = hidden.shape[0]
    chunk = min(chunk, n)
    pad = -n % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        weights = jnp.pad(weights, (0, pad))
    w = kernel.astype(dtype)

    @jax.checkpoint
    def one(h, t, m):
        logits = jnp.dot(h.astype(dtype), w,
                         preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * m)

    def body(total, xs):
        return total + one(*xs), None

    rows = (hidden.reshape(-1, chunk, hidden.shape[-1]),
            targets.reshape(-1, chunk), weights.reshape(-1, chunk))
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), rows)
    return total


def next_token_loss(out: Dict[str, jax.Array], kernel: jax.Array,
                    tokens: jax.Array, *, mtp_weight: float = 0.3,
                    chunk: int = 2048, dtype=jnp.bfloat16
                    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``(loss, {"loss.main", "loss.mtp"})`` from a model's
    ``{"hidden", "mtp_hidden"?}`` rows (``(B, L, D)``, already normed),
    the output matrix and the ``(B, L)`` tokens they came from; ``loss =
    main + mtp_weight * mtp``, each a mean over the rows that have a
    target. A model with a loss of its own beside the language model's
    (``out["aux_loss"]``: ``keye_vl2``'s indexers', whose leaves the other
    terms do not reach and which reaches no other leaf) has it added with
    coefficient 1 and reported as ``loss.indexer``."""
    B, L = tokens.shape
    pos = jnp.arange(L)

    def head(hidden, ahead):
        with jax.named_scope("lm_loss"):
            valid = jnp.broadcast_to(pos < L - ahead, (B, L))
            total = chunked_cross_entropy(
                hidden.reshape(B * L, -1), kernel,
                jnp.roll(tokens, -ahead, axis=1).reshape(B * L),
                valid.reshape(B * L).astype(jnp.float32), chunk, dtype)
            return total / (B * (L - ahead))

    main = head(out["hidden"], 1)
    mtp: Optional[jax.Array] = None
    if "mtp_hidden" in out:
        mtp = head(out["mtp_hidden"], 2)
    loss = main if mtp is None else main + mtp_weight * mtp
    aux = {"loss.main": main}
    if mtp is not None:
        aux["loss.mtp"] = mtp
    if "aux_loss" in out:
        loss = loss + out["aux_loss"]
        aux["loss.indexer"] = out["aux_loss"]
    return loss, aux


def masked_diffusion_loss(out: Dict[str, jax.Array], kernel: jax.Array,
                          targets: jax.Array, weights: jax.Array, *,
                          chunk: int = 2048, dtype=jnp.bfloat16
                          ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``(loss, {"loss.main", "diffusion.masked_share"})`` from a model's
    ``{"hidden"}`` rows over ``[noised | clean]`` (``(B, 2 L, D)``, already
    normed), the output matrix, the ``(B, L)`` clean tokens and the ``(B,
    L)`` float32 weights of the noised positions: ``loss = sum_i w_i
    CE(hidden_i W, target_i) / (B L)`` over the noised half alone.
    ``diffusion.masked_share`` is the share of noised positions that carry
    a weight."""
    B, L = targets.shape
    if out["hidden"].shape[1] != 2 * L:
        raise ValueError(f"hidden rows of {out['hidden'].shape[1]} for "
                         f"{L} clean tokens: [noised | clean] is {2 * L}")
    with jax.named_scope("lm_loss"):
        total = chunked_cross_entropy(
            out["hidden"][:, :L].reshape(B * L, -1), kernel,
            targets.reshape(B * L),
            weights.reshape(B * L).astype(jnp.float32), chunk, dtype)
        loss = total / (B * L)
    return loss, {"loss.main": loss, "diffusion.masked_share": jnp.mean(
        (weights > 0).astype(jnp.float32))}
