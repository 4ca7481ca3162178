"""Mixture-of-Experts: top-k routed FFN sharded over the ``expert`` axis.

The one parallelism family SURVEY.md §2.6 lists that the reference era never
had — built the TPU way (GShard/Switch style):

- the router is a tiny fp32 Dense; each token picks its top-k experts;
- in ``MoeMlp`` (and in it alone: ``DroplessMoe`` below sorts its slots,
  gathers rows and scatter-adds them) dispatch/combine are EINSUMS against
  one-hot capacity tensors — no gather/scatter, so the whole layer stays
  MXU-shaped and XLA lowers the token movement to an all-to-all over the
  ``expert`` mesh axis (the sharding rules place the leading E dim of
  ``experts_up``/``experts_down`` on ``expert``,
  ``parallel/sharding.DEFAULT_RULES``);
- per-expert capacity C = ceil(capacity_factor * S * k / E); overflow
  tokens fall through the residual (standard GShard drop policy);
- the load-balancing auxiliary loss (Shazeer et al.: E * mean_e(frac
  tokens routed to e) . mean_e(router prob of e)) is sown under
  ``("losses", "moe_aux")`` for the trainer to add.

``transformer_lm_moe`` swaps the dense MLP of every other decoder block
for this layer (via TransformerLM's pluggable block/ffn factories) — the flagship composition: ring/Ulysses attention over ``seq``,
tensor-parallel projections, expert-parallel FFNs, all in one jitted step.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from mmlspark_tpu.models.zoo import register_model
from mmlspark_tpu.observability import metrics as obsmetrics


class MoeMlp(nn.Module):
    dim: int
    num_experts: int = 8
    expert_hidden: Optional[int] = None   # default 4*dim
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        """x (B, L, D) -> (B, L, D); sows the aux loss under losses/moe_aux."""
        B, L, D = x.shape
        E, K = self.num_experts, self.top_k
        H = self.expert_hidden or 4 * D
        S = B * L
        C = max(1, math.ceil(self.capacity_factor * S * K / E))
        xf = x.reshape(S, D)

        # Router in fp32: tiny matmul, numerically owns the gating decision.
        logits = nn.Dense(E, dtype=jnp.float32, param_dtype=jnp.float32,
                          name="router")(xf.astype(jnp.float32))   # (S, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, K)              # (S, K)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        # Position of each (token, choice) within its expert's capacity:
        # choices fill expert slots in (choice-priority, token-order) —
        # first every token's 1st choice, then 2nd choices, like GShard.
        # Counting is int32: an fp32 cumsum loses exactness past 2^24
        # token-choices, silently colliding capacity slots at long context.
        onehot_i = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)    # (S, K, E)
        flat = onehot_i.transpose(1, 0, 2).reshape(K * S, E)       # (K*S, E)
        pos_flat = jnp.cumsum(flat, axis=0) - flat                 # slots used
        position = (pos_flat.reshape(K, S, E).transpose(1, 0, 2)
                    * onehot_i).sum(-1)                            # (S, K)
        keep = (position < C) & (onehot_i.sum(-1) > 0)             # (S, K)
        onehot = onehot_i.astype(jnp.float32)

        # dispatch (S, K, E, C) collapsed over K -> (S, E, C)
        cap_onehot = jax.nn.one_hot(position, C, dtype=jnp.float32)
        dispatch = jnp.einsum("ske,skc->sec",
                              onehot * keep[..., None], cap_onehot)
        combine = jnp.einsum("ske,skc->sec",
                             onehot * (gate_vals * keep)[..., None],
                             cap_onehot)

        w_up = self.param("experts_up", nn.initializers.lecun_normal(),
                          (E, D, H), jnp.float32).astype(self.dtype)
        w_down = self.param("experts_down", nn.initializers.lecun_normal(),
                            (E, H, D), jnp.float32).astype(self.dtype)
        # all-to-all happens here under GSPMD: xe is expert-sharded, xf is
        # batch-sharded
        xe = jnp.einsum("sec,sd->ecd", dispatch.astype(self.dtype), xf)
        h = nn.gelu(jnp.einsum("ecd,edh->ech", xe, w_up))
        ye = jnp.einsum("ech,ehd->ecd", h, w_down)
        y = jnp.einsum("sec,ecd->sd", combine.astype(self.dtype), ye)

        # Load-balancing aux loss (fp32, scheme-standard scale E). Sown only
        # outside init so the 'losses' collection never leaks into the
        # trainable param tree (the optimizer must not "train" a buffer).
        if not self.is_initializing():
            frac_routed = (onehot[:, 0, :]).mean(axis=0)  # 1st-choice share
            mean_prob = probs.mean(axis=0)
            aux = E * jnp.sum(frac_routed * mean_prob)
            self.sow("losses", "moe_aux", aux)
        return y.reshape(B, L, D)


# ---------------------------------------------------------------------------
# Dropless routing over the experts held here.
#
# Token-slots (token, choice) are sorted by expert, the held experts' slots
# lying first and group by group, and gate/up/down run as grouped matrix
# products over those groups (``jax.lax.ragged_dot``; on a TPU XLA lowers it
# to a grouped Mosaic matmul that walks only the tiles the groups cover).
# Every array between the sort and the token-order sum has ``R`` rows, the
# smallest rung that holds the step's slots on a short ladder of static
# sizes the layer's shapes give (``_ladder``), not the ``S*K`` slots there
# are: the first ``R`` entries of the sort name the rows, one gather brings
# them from token order (``_rows_of_tokens``), and each returns as an addend
# of its token's row (``_sum_by_token``, a scatter-add); the two are each
# other's transpose, so the backward pass moves ``R`` rows too and no
# ``(S*K, D)`` array is made. The rung is a device scalar's choice
# (``lax.switch``), and the last rung is ``S*K``: no slot is dropped at any
# load.

# the ladder's rungs below ``S*K``: the expert-order buffers hold this many
# times the slots an even router sends to the held experts, rounded up to
# whole tiles of rows. Gathers, scatter-adds and element-wise passes pay for
# every row of a rung, filler included (0.4 us a row a layer at width 2,048
# on a v5e), so the lowest lies close over a steady load; a router trained
# on one chip's share climbs past it, and past four times it late in a
# window (PERF.md section 6, PRs 31 and 41)
_RUNGS_OVER_EVEN_LOAD = (1.25, 4)
_ROWS_TILE = 512


def _ladder(slots: int, held: int, experts: int) -> Tuple[int, ...]:
    """The sizes the expert-order buffers may take, ascending, the last one
    all ``slots``; one rung where the layer holds every expert."""
    rungs = {slots}
    for over in _RUNGS_OVER_EVEN_LOAD:
        tiles = math.ceil(over * slots * held / (experts * _ROWS_TILE))
        rungs.add(min(slots, tiles * _ROWS_TILE))
    return tuple(sorted(rungs))


def _sum_by_token(tokens, rows, token):
    """``(R, D)`` rows -> float32 ``(tokens, D)``: row ``r`` is an addend
    of ``out[token[r]]``. XLA's scatter-add, which sorts the ids itself (on
    a v5e 1.9 ms for 20,480 float32 rows of 2,048; PERF.md section 6, PR
    31, has the forms that lost to it); its transpose is the gather
    ``g[token]``."""
    # no embedding table's bag: an expert layer's rows, one order of
    # addition on every path (the dense-loop tests hold it to 1e-5)
    return jax.ops.segment_sum(  # lint: allow-embed
        rows.astype(jnp.float32), token, num_segments=tokens)


def _at_choice(s, choice):
    """``s (S, E)`` at ``choice (S, K)`` along the expert axis: ``out[t, k]
    = s[t, choice[t, k]]``, read as a comparison against that axis and a
    sum over it. One addend a slot is not zero, so the result is the
    gather's to the bit; so is the gradient, the same comparison times the
    cotangent summed over ``K`` (a token's choices are distinct: one
    addend an expert). XLA fuses the ``K`` sums into one pass over ``s`` at
    the VPU's width; its ``gather`` walks the slots one by one, about 10 ns
    each on a v5e whatever the bytes (20.5 ms of a 622 ms step at 32,768
    tokens x 8). A choice at a time over ``(S, E)``, not one comparison
    over ``(S, K, E)``: the bits are the same, but around that form XLA
    arranges the routed conditionals otherwise, for 0.3 GB more
    temporaries and slower grouped products (PERF.md section 6, PR 48)."""
    experts = jax.lax.broadcasted_iota(jnp.int32, (1, s.shape[-1]), 1)
    return jnp.stack(
        [jnp.where(choice[:, k:k + 1] == experts, s, 0).sum(-1)
         for k in range(choice.shape[-1])], axis=-1)


def _slots_by_expert(local, held):
    """int32 ``(held,)``: how many of ``local (S, K)`` (a token's choices,
    counted from the first held expert) name each held expert. The same
    comparison, a choice at a time, summed over the tokens: exact, and
    one fused pass where a ``bincount`` of the ``S*K`` slots is a
    scatter-add of a scalar a slot, walked like the gather (18 ms of that
    step)."""
    experts = jax.lax.broadcasted_iota(jnp.int32, (1, held), 1)
    return sum((local[:, k:k + 1] == experts).sum(0, dtype=jnp.int32)
               for k in range(local.shape[-1]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rows_of_tokens(tokens, x, token, live):
    """``(tokens, D)`` -> ``(R, D)``: row ``r`` is ``x[token[r]]``; the
    transpose of ``_sum_by_token``, and it of this. A row that is not
    ``live`` lies past the groups of the products that read this, which
    leave whatever they find in its cotangent: it adds nothing to the
    token's."""
    return x[token]


def _rows_of_tokens_fwd(tokens, x, token, live):
    return x[token], (token, live)


def _rows_of_tokens_bwd(tokens, res, g):
    token, live = res
    g = jnp.where(live[:, None], g, 0)
    return _sum_by_token(tokens, g, token).astype(g.dtype), None, None


_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


def _grouped(x, w, sizes):
    obsmetrics.counter("moe.grouped_calls.ragged_dot").inc()
    return jax.lax.ragged_dot(x, w, sizes,
                              preferred_element_type=jnp.float32)


def _routed_rows(rows, xf, gate, w_gate, w_up, w_down, order, sizes):
    """The held experts' part of the layer's sum, float32 ``(S, D)``, over
    expert-order buffers of ``rows`` rows: exact while no more than ``rows``
    slots are routed here. ``xf (S, D)`` and the weights in the products'
    dtype, ``gate (S, K)`` float32, ``order`` the slots sorted by expert
    (slot ``s*K + k`` is token ``s``'s choice ``k``), ``sizes`` the held
    experts' groups."""
    tokens, top_k = gate.shape
    with jax.named_scope("moe_dispatch"):
        slot = order[:rows]               # the held slots, then filler
        token = slot // top_k
        live = jnp.arange(rows) < sizes.sum()
        xs = _rows_of_tokens(tokens, xf, token, live)
    with jax.named_scope("moe_experts"):
        h = nn.silu(_grouped(xs, w_gate, sizes)) * _grouped(xs, w_up, sizes)
        ys = _grouped(h.astype(xs.dtype), w_down, sizes)
    with jax.named_scope("moe_combine"):
        # rows past the groups hold whatever the product left there
        ys = jnp.where(live[:, None], ys, 0)
        weight = gate.reshape(tokens * top_k).at[slot].get(
            unique_indices=True, mode="promise_in_bounds")
        return _sum_by_token(tokens, ys * weight[:, None], token)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _routed(rungs, rung, xf, gate, w_gate, w_up, w_down, order, sizes):
    """``_routed_rows`` at ``rungs[rung]`` rows, ``rung`` a device scalar:
    the smallest rung that holds the step's slots. Differentiated as a
    whole: the backward pass takes the same rung and runs its forward
    again, so neither pass hands the other an array of a size it did not
    run (``lax.switch``'s own derivative keeps every branch's residuals)."""
    return jax.lax.switch(
        rung, [functools.partial(_routed_rows, rows) for rows in rungs],
        xf, gate, w_gate, w_up, w_down, order, sizes)


def _routed_fwd(rungs, rung, *operands):
    return _routed(rungs, rung, *operands), (rung, operands)


def _routed_bwd(rungs, kept, g):
    def pull(rows):
        def back(g, *operands):
            moved, ids = operands[:5], operands[5:]     # order, sizes
            return jax.vjp(lambda *m: _routed_rows(rows, *m, *ids),
                           *moved)[1](g)
        return back
    rung, operands = kept
    moved = jax.lax.switch(rung, [pull(rows) for rows in rungs], g,
                           *operands)
    return (None, *moved, None, None)


_routed.defvjp(_routed_fwd, _routed_bwd)


class DroplessMoe(nn.Module):
    """Routed experts, with a shared expert or (``shared=None``) without,
    no capacity and no dropped token. One layer, one dispatch, one set of
    grouped products; how the scores are made and whether the shared
    expert is gated are arguments.

    ``scores="sigmoid"`` (DeepSeek-V3's layer; ``topk_method: noaux_tc``
    with one group): ``s = sigmoid(x W_r)`` in float32 over ALL
    ``num_experts``; the choice is the top ``top_k`` of ``s + b``
    (``router_bias``: no gradient reaches it, it only moves the choice).
    ``scores="softmax"``: ``s = softmax(x W_r)`` in float32 over all of
    them, the choice its top ``top_k``, no bias. Either way the weights are
    ``s`` at the chosen over ``(their sum + weight_eps)``, times
    ``scaling`` (``weight_eps`` 0 in DeepSeek-V3's and Qwen3-Next's layer,
    1e-6 in LFM2's). ``s`` at the chosen is read by ``_at_choice``, a
    comparison against the expert axis and a sum over it, not a gather:
    the same bits, and XLA's gather of scalars costs about 10 ns each on
    a v5e whatever their bytes.
    ``gate_grad=False`` makes the weights, and the scores behind them,
    constants of the backward pass (``jax.lax.stop_gradient``): no
    gradient reaches the router's kernel, and none reaches the tokens
    through the scores; the forward pass is the same. It is what a SHARE
    trained without its exchange can honestly compute: the scores' true
    gradient needs the outputs of all ``top_k`` chosen experts, of which a
    chip that holds ``held`` of ``num_experts`` has only its own, so a
    router trained on that part of the sum alone is pulled onto the held
    experts and the share's load climbs step by step (PERF.md section 6,
    PR 40). With the exchange of a routed layer across chips (ROADMAP
    Queue 2 A5) the argument goes back to ``True``.
    ``shared_gate`` puts the shared expert behind a sigmoid of one more
    output of the token: ``sigmoid(x w_g) * shared(x)``.
    ``experts_held = (count, first)``
    says which experts' weights live here: the layer computes their part of
    the sum, and a slot whose expert is held elsewhere adds nothing (its
    part is that chip's to add). ``shared(name)`` makes the shared expert, a
    dense feed-forward part every chip computes alike.

    A layer that holds a share of the experts sizes its expert-order
    buffers by the smallest rung that holds the step's slots (``_ladder``:
    a few static sizes, the last all ``tokens x top_k`` rows) and runs the
    same path at every rung: the choice is a device scalar's.

    Returns ``(y, stats)``: ``slots_here`` (slots routed to held experts),
    ``rows`` (the rows of the rung this call ran at), ``overflowed`` (1 if
    that was all ``tokens x top_k`` of a ladder with smaller rungs, else
    0) and ``load_max_over_mean`` (the fullest held expert over their
    mean).
    Sows the choice under ``("intermediates", "router_choice")``.
    """
    dim: int
    num_experts: int
    expert_hidden: int
    top_k: int
    experts_held: Optional[Tuple[int, int]] = None
    scaling: float = 1.0
    shared: Optional[Callable[[str], nn.Module]] = None
    dtype: Any = jnp.bfloat16
    scores: str = "sigmoid"
    shared_gate: bool = False
    weight_eps: float = 0.0
    gate_grad: bool = True

    @nn.compact
    def __call__(self, x):
        B, L, D = x.shape
        E, K, H = self.num_experts, self.top_k, self.expert_hidden
        held, first = self.experts_held or (E, 0)
        if not (0 <= first and first + held <= E):
            raise ValueError(f"experts_held {(held, first)} of {E} experts")
        if self.scores not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown scores {self.scores!r}")
        S = B * L
        xf = x.reshape(S, D)
        init = nn.initializers.normal(0.02)

        with jax.named_scope("moe_router"):
            logits = nn.Dense(
                E, use_bias=False, dtype=jnp.float32,
                param_dtype=jnp.float32, kernel_init=init, name="router")(
                    xf.astype(jnp.float32))
            if self.scores == "sigmoid":
                s = jax.nn.sigmoid(logits)
                ranked = s + self.param(
                    "router_bias", nn.initializers.zeros, (E,), jnp.float32)
            else:
                s = ranked = jax.nn.softmax(logits, axis=-1)
            _, choice = jax.lax.top_k(ranked, K)     # indices: no gradient
            gate = _at_choice(s, choice)
            total = gate.sum(-1, keepdims=True)
            if self.weight_eps:
                total = total + self.weight_eps
            gate = gate / total * self.scaling
            if not self.gate_grad:
                gate = jax.lax.stop_gradient(gate)
            self.sow("intermediates", "router_choice", choice)

        with jax.named_scope("moe_dispatch"):
            local = choice - first
            here = (local >= 0) & (local < held)
            # expert order: held experts by index, then everything else
            key = jnp.where(here, local, held).reshape(S * K)
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            sizes = _slots_by_expert(local, held)
            slots_here = sizes.sum()
            # the smallest rung that holds them: how many of the rungs
            # below the last they exceed
            rungs = _ladder(S * K, held, E)
            rung = jnp.sum(slots_here > jnp.asarray(rungs[:-1], jnp.int32))

        # the outer scope names what ``_routed_rows``'s own three leave
        # out: the weights' casts and the choice of the rung
        with jax.named_scope("moe_experts"):
            w_gate, w_up, w_down = (
                self.param(name, init, shape, jnp.float32).astype(self.dtype)
                for name, shape in (("experts_gate", (held, D, H)),
                                    ("experts_up", (held, D, H)),
                                    ("experts_down", (held, H, D))))
            operands = (xf.astype(self.dtype), gate, w_gate, w_up, w_down,
                        order, sizes)
            if len(rungs) == 1:  # buffers of every slot: no conditional
                y = _routed_rows(S * K, *operands)
            else:
                y = _routed(rungs, rung, *operands)

        with jax.named_scope("moe_combine"):
            if self.shared is not None:
                side = self.shared("shared")(xf).astype(jnp.float32)
                if self.shared_gate:
                    side = side * jax.nn.sigmoid(nn.Dense(
                        1, use_bias=False, dtype=jnp.float32,
                        param_dtype=jnp.float32, kernel_init=init,
                        name="shared_gate")(xf.astype(jnp.float32)))
                y = y + side

        load = sizes.astype(jnp.float32)
        stats = {"slots_here": slots_here,
                 "rows": jnp.asarray(rungs, jnp.int32)[rung],
                 "overflowed": ((rung > 0) & (rung == len(rungs) - 1)
                                ).astype(jnp.int32),
                 "load_max_over_mean": load.max() / jnp.maximum(
                     load.mean(), 1e-9)}
        return y.reshape(B, L, D).astype(self.dtype), stats


def _moe_lm(vocab, dim, depth, heads, max_len, num_experts, top_k,
            capacity_factor, dtype, attention_fn):
    """TransformerLM whose odd blocks swap the dense MLP for MoeMlp via the
    pluggable block/ffn factories — zero duplication of the attention half
    or the embedding/tied-head trunk (``zoo/transformer.py``)."""
    from mmlspark_tpu.models.zoo.transformer import DecoderBlock, TransformerLM

    def block_factory(i, name):
        ffn = None
        if i % 2 == 1:
            def ffn(fname):
                return MoeMlp(dim, num_experts=num_experts, top_k=top_k,
                              capacity_factor=capacity_factor, dtype=dtype,
                              name=fname)
        return DecoderBlock(dim, heads, dtype=dtype,
                            attention_fn=attention_fn, ffn_factory=ffn,
                            name=name)

    return TransformerLM(vocab=vocab, dim=dim, depth=depth, heads=heads,
                         max_len=max_len, dtype=dtype,
                         attention_fn=attention_fn,
                         block_factory=block_factory)


def moe_aux_loss(variables) -> jnp.ndarray:
    """Sum of every sown moe_aux term in a ``mutable=['losses']`` pass."""
    losses = variables.get("losses", {})
    total = jnp.zeros((), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(losses):
        total = total + jnp.sum(leaf)
    return total


@register_model("transformer_lm_moe")
def transformer_lm_moe(vocab: int = 32000, dim: int = 512, depth: int = 6,
                       heads: int = 8, max_len: int = 2048,
                       num_experts: int = 8, top_k: int = 2,
                       capacity_factor: float = 1.25,
                       dtype=jnp.bfloat16, attention_fn=None):
    return dict(
        module=_moe_lm(vocab, dim, depth, heads, max_len, num_experts,
                       top_k, capacity_factor, dtype, attention_fn),
        input_shape=(max_len,), input_dtype="int32",
        feature_layer="hidden", feature_dim=dim,
        layer_names=["hidden", "logits"],
        # decoder blocks use the (q, k, v, causal) attention contract, so
        # the ring/Ulysses kernels can be swapped in for seq-parallel runs
        seq_attention=True,
    )


@register_model("transformer_lm_moe_tiny")
def transformer_lm_moe_tiny(vocab: int = 256, dim: int = 64, depth: int = 2,
                            heads: int = 4, max_len: int = 128,
                            num_experts: int = 4, top_k: int = 2,
                            capacity_factor: float = 2.0,
                            dtype=jnp.float32, attention_fn=None):
    """Test-scale MoE LM (fp32; generous capacity so tiny batches route)."""
    return dict(
        module=_moe_lm(vocab, dim, depth, heads, max_len, num_experts,
                       top_k, capacity_factor, dtype, attention_fn),
        input_shape=(max_len,), input_dtype="int32",
        feature_layer="hidden", feature_dim=dim,
        layer_names=["hidden", "logits"],
        # decoder blocks use the (q, k, v, causal) attention contract, so
        # the ring/Ulysses kernels can be swapped in for seq-parallel runs
        seq_attention=True,
    )
