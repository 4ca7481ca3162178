"""Kimi Delta Attention's chunk-local half as Pallas TPU calls: the gated
delta rule of ``ops/pallas_delta_rule.py`` with the decay a VECTOR over the
key channels (KDA, arXiv:2510.26692), at heads of 128 x 128 with as many
key as value heads.

``ops/linear_attention.py``'s module docstring has the mathematics. With a
decay a channel the chunk's products hold the decay INSIDE the contraction,
``sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])``: no matrix ``D`` multiplies ``K
K^T`` afterwards. The calls split the causal triangle of a chunk by the
HIGHEST BIT in which row ``i`` and column ``j`` differ: at level ``s`` (1,
2, ..., 32) the pairs with ``i`` in the upper and ``j`` in the lower half of
one block of ``2 s`` rows, and with ``R`` = ``G`` at the upper half's first
row::

    exp(G_i - G_j) = exp(G_i - R) exp(R - G_j),      both exponents <= 0

so a level is ONE matrix product of two scaled operands, ``(k * exp(G -
R)) (k * exp(R' - G))^T`` under the level's mask (``R`` and ``R'`` a row's
own reference: ``G`` gathered at block starts by rolls along the sublanes),
six products for ``K K^T`` and six for ``Q K^T`` a tile, whatever the
decays: none is a quotient of two exponentials. The diagonal is ``q_i .
k_i``.

A program works on pairs of chunks as ``pallas_delta_rule``'s does, and the
grid, the block specs, the triangular inverse and the layout of what the
walk and the output read are that module's own:

- ``kda_chunk_fwd``: in go q, k (float32), v and ``G`` (the running sum of
  the decay inside each chunk, float32, made by XLA: one cumulative sum a
  layer) as ``(B, L, H * 128)`` rows and ``beta`` as lanes; out go ``W = T
  (beta e^G K)``, ``Kd = e^(G_C - G) K``, ``qe = e^G Q``, ``P`` in the
  products' operand type and ``U_0 = T (beta V)`` in float32, under
  ``pallas_delta_rule``'s checkpoint name ``DELTA_CHUNK_TILES``.
- ``kda_chunk_bwd`` (``jax.custom_vjp``): makes the tiles again and takes
  the five cotangents back to q, k, v, ``G`` and beta; the gathers'
  transposes are rolls the other way, from the coarsest level down.
- ``kda_chunk_out`` / ``kda_chunk_out_bwd``: ``O = qe S_0 + P U`` and its
  transpose are ``pallas_delta_rule``'s kernels as they are (the decay is
  in ``qe`` and ``P`` already), under names of their own so that a trace
  tells the two rules' calls apart.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mmlspark_tpu.ops import pallas_delta_rule as pdr
from mmlspark_tpu.ops.pallas_delta_rule import (
    CHUNK, PAIR, _NT, _TN, _chunks_of, _col, _dot, _dot32, _f32, _pair_rows,
    _pairs_loop, _row, _to_chunks)

WIDTH = 128
_LEVELS = (1, 2, 4, 8, 16, 32)
_FWD_NAME = "kda_chunk_fwd"
_BWD_NAME = "kda_chunk_bwd"
_OUT_NAME = "kda_chunk_out"
_OUT_BWD_NAME = "kda_chunk_out_bwd"


def supports(chunk: int, key_heads: int, value_heads: int, dk: int,
             dv: int) -> bool:
    """Shapes the calls take: chunks of 64, heads of 128 x 128, as many
    key as value heads."""
    return chunk == CHUNK and key_heads == value_heads \
        and dk == WIDTH and dv == WIDTH


def _roll(x, shift: int):
    """``jnp.roll(x, shift, 0)`` of a tile: row ``i`` gets row ``i -
    shift``."""
    return pltpu.roll(x, shift % x.shape[0], 0)


def _gathers(G, row):
    """Per level ``s``: ``G`` at the first row of each row's block of ``s``
    rows, (R, dk) float32. ``F_1 = G``; a row in the upper half of its
    block of ``2 s`` takes the lower half's value, ``s`` rows up."""
    out, F = [], G
    for s in _LEVELS:
        out.append(F)
        F = jnp.where((row & s) != 0, _roll(F, s), F)
    return out


def _decays(G, F, s: int, row):
    """Level ``s``'s two decays, (R, dk): a row's own to its block's first
    row, ``exp(G - F_s)``, and from the NEXT block's first row back to it,
    ``exp(F_s[. + s] - G)`` (1 in a chunk's last block, which has none:
    those rows are under no level's mask)."""
    inside = (row & (CHUNK - 1)) < CHUNK - s
    return jnp.exp(G - F), jnp.exp(jnp.where(inside, _roll(F, -s) - G, 0.0))


def _level_mask(m, s: int):
    """Pairs (i, j), i > j, whose highest differing bit is ``s``: the same
    block of ``2 s`` rows (so the same chunk), ``i`` in its upper half."""
    return (((m["r"] ^ m["c"]) >> (s.bit_length() - 1)) == 1) \
        & (m["r"] > m["c"])


def _tiles(q, k, v, G, beta_row, dtype, m, row):
    """The tiles of a pair of chunks from their rows: q, k, G (R, dk)
    float32, v (R, dv), beta (1, R); ``m`` = ``pallas_delta_rule._masks(R)``
    and ``row`` the row index as (R, dk)."""
    q, k, v = q.astype(_f32), k.astype(_f32), v.astype(_f32)
    beta = _col(beta_row, m["eye"])
    F = _gathers(G, row)
    kk = jnp.zeros((q.shape[0],) * 2, _f32)
    qk = jnp.where(m["eye"], jnp.sum(
        q.astype(dtype).astype(_f32) * k.astype(dtype).astype(_f32),
        axis=1, keepdims=True), 0.0)
    for s, F_s in zip(_LEVELS, F):
        lhs, rhs = _decays(G, F_s, s, row)
        kr = (k * rhs).astype(dtype)
        mask = _level_mask(m, s)
        kk += jnp.where(mask, _dot((k * lhs).astype(dtype), kr, _NT), 0.0)
        qk += jnp.where(mask, _dot((q * lhs).astype(dtype), kr, _NT), 0.0)
    T = pdr.inv_unit_lower_tile(
        jnp.where(m["below"], beta * kk, 0.0), m["r"], m["c"])
    eG = jnp.exp(G)
    last = jnp.where(row < CHUNK, G[CHUNK - 1:CHUNK], G[PAIR - 1:PAIR])
    return dict(
        q=q, k=k, v=v, beta=beta, F=F, kk=kk, qk=qk, T=T,
        Tc=T.astype(dtype), eG=eG, E=jnp.exp(last - G),
        kb=(k * (beta * eG)).astype(dtype), vb=(v * beta).astype(dtype))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                w_ref, u0_ref, kd_ref, qe_ref, p_ref, *, dtype):
    m = pdr._masks(PAIR)
    row = jax.lax.broadcasted_iota(jnp.int32, (PAIR, q_ref.shape[-1]), 0)

    def pair(p):
        rows = _pair_rows(p)
        t = _tiles(q_ref[rows, :], k_ref[rows, :], v_ref[rows, :],
                   g_ref[rows, :], beta_ref[pl.ds(p, 1), :], dtype, m, row)
        _to_chunks(w_ref, p, _dot(t["Tc"], t["kb"]))
        _to_chunks(u0_ref, p, _dot(t["Tc"], t["vb"]))
        _to_chunks(kd_ref, p, t["k"] * t["E"])
        _to_chunks(qe_ref, p, t["q"] * t["eG"])
        p_ref[p] = t["qk"].astype(p_ref.dtype)

    _pairs_loop(p_ref.shape[0], pair)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                dw_ref, du0_ref, dkd_ref, dqe_ref, dp_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, *, dtype):
    m = pdr._masks(PAIR)
    row = jax.lax.broadcasted_iota(jnp.int32, (PAIR, q_ref.shape[-1]), 0)

    def pair(p):
        rows, one = _pair_rows(p), (pl.ds(p, 1), slice(None))
        G = g_ref[rows, :]
        t = _tiles(q_ref[rows, :], k_ref[rows, :], v_ref[rows, :], G,
                   beta_ref[one], dtype, m, row)
        q, k, v, beta, eG, E, T, Tc = (t[n] for n in (
            "q", "k", "v", "beta", "eG", "E", "T", "Tc"))
        dW = _chunks_of(dw_ref, p).astype(dtype)
        dU0 = _chunks_of(du0_ref, p).astype(dtype)
        dKd = _chunks_of(dkd_ref, p).astype(_f32)
        dqe = _chunks_of(dqe_ref, p).astype(_f32)
        dP = dp_ref[p].astype(_f32)

        # W = T kb, U0 = T vb; A = beta_i KK_ij -> T = (I + A)^-1
        dT = _dot(dW, t["kb"], _NT) + _dot(dU0, t["vb"], _NT)
        dkb = _dot(Tc, dW, _TN)
        dvb = _dot(Tc, dU0, _TN)
        dA = jnp.where(m["below"], -_dot32(_dot32(T, dT, _TN), T, _NT), 0.0)
        dbeta = jnp.sum(dA * t["kk"], axis=1, keepdims=True)
        dKK = dA * beta

        # the diagonal of P, q_i . k_i; kb = k beta e^G, qe = q e^G, Kd = k E
        diag = jnp.sum(jnp.where(m["eye"], dP, 0.0), axis=1, keepdims=True)
        kbg, kdg = dkb * k * (beta * eG), dKd * k * E
        dbeta += jnp.sum(dkb * k * eG + dvb * v, axis=1, keepdims=True)
        dq = dqe * eG + diag * k
        dk = dkb * (beta * eG) + dKd * E + diag * q
        # E's G_C is the chunk's last row
        at_last = (row & (CHUNK - 1)) == CHUNK - 1
        dG = kbg + dqe * q * eG - kdg + jnp.where(
            at_last, jnp.where(
                row < CHUNK, jnp.sum(kdg[:CHUNK], axis=0, keepdims=True),
                jnp.sum(kdg[CHUNK:], axis=0, keepdims=True)), 0.0)

        # the levels, coarsest first: what reaches F_2s goes on to F_s
        dF = jnp.zeros_like(G)
        for s, F_s in reversed(list(zip(_LEVELS, t["F"]))):
            lhs, rhs = _decays(G, F_s, s, row)
            kl, ql, kr = k * lhs, q * lhs, k * rhs
            mask = _level_mask(m, s)
            Mk = jnp.where(mask, dKK, 0.0).astype(dtype)
            Mq = jnp.where(mask, dP, 0.0).astype(dtype)
            krc = kr.astype(dtype)
            dkl, dql = _dot(Mk, krc), _dot(Mq, krc)
            dkr = _dot(Mk, kl.astype(dtype), _TN) \
                + _dot(Mq, ql.astype(dtype), _TN)
            dq += dql * lhs
            dk += dkl * lhs + dkr * rhs
            dEl, dEr = dkl * kl + dql * ql, dkr * kr
            dG += dEl - dEr
            # F_2s = F_s, or in a block's upper half F_s from s rows up
            dF = jnp.where((row & s) == 0, dF + _roll(dF, -s), 0.0) \
                - dEl + _roll(dEr, s)
        dG += dF

        dq_ref[rows, :] = dq
        dk_ref[rows, :] = dk
        dv_ref[rows, :] = (dvb * beta).astype(dv_ref.dtype)
        dg_ref[rows, :] = dG
        dbeta_ref[one] = _row(dbeta, m["eye"])

    _pairs_loop(dp_ref.shape[0], pair)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _forward(q, k, v, G, beta, dtype):
    B, H, N = q.shape[0], beta.shape[1], q.shape[1] // CHUNK
    s = pdr._specs(B, N, H, H)

    def chunks(dt):
        return jax.ShapeDtypeStruct((N, B, H, CHUNK, WIDTH), dt)
    rows = s["rows"](WIDTH)
    return pdr._call(
        functools.partial(_fwd_kernel, dtype=dtype), _FWD_NAME, s["grid"],
        [rows, rows, rows, rows, s["scalars"]],
        pdr._tile_specs(s, WIDTH, WIDTH),
        [chunks(dtype), chunks(_f32), chunks(dtype), chunks(dtype),
         jax.ShapeDtypeStruct((N // 2, B, H, PAIR, PAIR), dtype)],
        "parallel", q, k, v, G, beta)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _backward(q, k, v, G, beta, cts, dtype):
    H = beta.shape[1]
    s = pdr._specs(q.shape[0], q.shape[1] // CHUNK, H, H)
    rows = s["rows"](WIDTH)
    ins = [rows, rows, rows, rows, s["scalars"]]
    return pdr._call(
        functools.partial(_bwd_kernel, dtype=dtype), _BWD_NAME, s["grid"],
        ins + pdr._tile_specs(s, WIDTH, WIDTH), ins,
        [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, G, beta)],
        "parallel", q, k, v, G, beta, *cts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def kda_chunk(q: jax.Array, k: jax.Array, v: jax.Array, G: jax.Array,
              beta: jax.Array, dtype: Any):
    """The chunk-local tiles of the delta rule under a decay a key channel.
    ``q``, ``k`` and ``G`` (B, L, H * 128) float32: ``q`` already scaled,
    ``G`` the running sum of the log decay inside each chunk of 64; ``v``
    (B, L, H * 128); ``beta`` (B, H, L / 128, 128) float32; ``L =
    pallas_delta_rule.padded_length(L)``. Returns what
    ``pallas_delta_rule.delta_chunk`` returns, in its layout."""
    return tuple(_forward(q, k, v, G, beta, jnp.dtype(dtype)))


def _fwd_rule(q, k, v, G, beta, dtype):
    tiles = tuple(checkpoint_name(t, pdr.DELTA_CHUNK_TILES)
                  for t in kda_chunk(q, k, v, G, beta, dtype))
    return tiles, (q, k, v, G, beta)


def _bwd_rule(dtype, res, cts):
    return tuple(_backward(*res, tuple(cts), jnp.dtype(dtype)))


kda_chunk.defvjp(_fwd_rule, _bwd_rule)

kda_chunk_out = pdr.chunk_out_call(_OUT_NAME, _OUT_BWD_NAME)
