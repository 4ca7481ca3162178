"""Fused uint8 -> normalized-float image preprocessing as a Pallas TPU kernel.

The reference ran resize/crop/normalize per-row through OpenCV JNI on CPUs
(``ImageTransformer.scala``); the BASELINE.json north star asks for this
rewritten as a Pallas kernel fused ahead of the model's first layer.

Why it wins on TPU:
- host->HBM transfer moves uint8 (4x less PCIe/DMA traffic than fp32);
- the uint8->float cast + mean/std normalize runs on the VPU out of VMEM,
  emitting bfloat16 straight into the model's first conv — the fp32 image
  tensor never round-trips through HBM;
- one elementwise pass, batched over the grid, no per-row Python.

Layout note: images are flattened to (B, H*W*C) so the lane dimension is a
multiple of 128 (HWC C=3 alone would waste the VPU lanes); the per-channel
mean/std are pre-tiled host-side into length-N vectors.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from mmlspark_tpu.parallel.sharding import map_batch_shards


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _normalize_kernel(u8_ref, mean_ref, inv_std_ref, out_ref):
    # Mosaic has no direct uint8->float cast; hop through int32.
    x = u8_ref[:].astype(jnp.int32).astype(jnp.float32)
    out_ref[:] = ((x - mean_ref[:]) * inv_std_ref[:]).astype(out_ref.dtype)


_BLOCK_B = 8  # sublane tiling requires batch blocks divisible by 8


def _normalize_call(u8_flat: jax.Array, mean_vec: jax.Array,
                    inv_std_vec: jax.Array, out_dtype) -> jax.Array:
    """(B, N) uint8 -> (B, N) normalized: the pallas_call over ONE
    device's rows."""
    b, n = u8_flat.shape
    bp = ((b + _BLOCK_B - 1) // _BLOCK_B) * _BLOCK_B
    if bp != b:
        u8_flat = jnp.pad(u8_flat, ((0, bp - b), (0, 0)))
    vmem = pl.ANY if _interpret() else pltpu.VMEM
    out = pl.pallas_call(
        _normalize_kernel,
        grid=(bp // _BLOCK_B,),
        in_specs=[
            pl.BlockSpec((_BLOCK_B, n), lambda i: (i, 0), memory_space=vmem),
            pl.BlockSpec((_BLOCK_B, n), lambda i: (0, 0), memory_space=vmem),
            pl.BlockSpec((_BLOCK_B, n), lambda i: (0, 0), memory_space=vmem),
        ],
        out_specs=pl.BlockSpec((_BLOCK_B, n), lambda i: (i, 0),
                               memory_space=vmem),
        out_shape=jax.ShapeDtypeStruct((bp, n), out_dtype),
        interpret=_interpret(),
    )(u8_flat,
      jnp.broadcast_to(mean_vec[None, :], (_BLOCK_B, n)),
      jnp.broadcast_to(inv_std_vec[None, :], (_BLOCK_B, n)))
    return out[:b]


@functools.partial(jax.jit,
                   static_argnames=("image_shape", "out_dtype", "mesh"))
def fused_normalize(u8_flat: jax.Array, mean_vec: jax.Array,
                    inv_std_vec: jax.Array,
                    image_shape: Tuple[int, int, int],
                    out_dtype=jnp.bfloat16,
                    mesh: Optional[Mesh] = None) -> jax.Array:
    """(B, N) uint8 -> (B, H, W, C) normalized out_dtype; N = H*W*C.

    A Mosaic kernel is an opaque custom call to the SPMD partitioner:
    inside a multi-device jit it would all-gather the batch and run the
    whole thing on every chip. With ``mesh``, the call is shard_mapped
    over the mesh's batch axes so each device normalizes its own rows."""
    call = map_batch_shards(
        functools.partial(_normalize_call, out_dtype=out_dtype), mesh,
        batched=(True, False, False))
    out = call(u8_flat, mean_vec, inv_std_vec)
    return out.reshape((u8_flat.shape[0],) + tuple(image_shape))


def make_preprocess_fn(image_shape: Tuple[int, int, int],
                       mean: Sequence[float] = (127.5, 127.5, 127.5),
                       std: Sequence[float] = (127.5, 127.5, 127.5),
                       out_dtype=jnp.bfloat16,
                       mesh: Optional[Mesh] = None):
    """Returns fn(u8_flat (B, N)) -> (B, H, W, C) normalized activations.

    Compose inside the SAME jit as the model forward so the normalized
    activations feed the first conv without an HBM round trip:

        pre = make_preprocess_fn((32, 32, 3))
        @jax.jit
        def forward(params, u8):
            return module.apply(params, pre(u8))

    Inside a multi-device jit (a ``DistributedTrainer`` loss) pass the
    trainer's ``mesh`` so every chip normalizes its own batch rows (see
    :func:`fused_normalize`).
    """
    h, w, c = image_shape
    n = h * w * c
    mean_vec = jnp.asarray(np.tile(np.asarray(mean, np.float32), h * w))
    inv_std_vec = jnp.asarray(
        np.tile(1.0 / np.asarray(std, np.float32), h * w))
    if mean_vec.shape[0] != n:
        raise ValueError(f"mean length {len(mean)} does not tile into {n}")

    def preprocess(u8_flat: jax.Array) -> jax.Array:
        if u8_flat.dtype != jnp.uint8:
            u8_flat = u8_flat.astype(jnp.uint8)
        return fused_normalize(u8_flat, mean_vec, inv_std_vec,
                               (h, w, c), out_dtype, mesh)
    return preprocess


def _sampling_matrix(src: int, dst: int, crop_off: float = 0.0,
                     crop_size: Optional[int] = None) -> np.ndarray:
    """(dst, src) bilinear sampling matrix, half-pixel centers with edge
    clamp — identical convention to the host path (``image/ops.py
    _resize_stack``). An optional crop window folds INTO the matrix: crop
    + resize is just a shifted/scaled sampling grid, so the fused kernel
    gets both for the price of one matmul."""
    size = src if crop_size is None else crop_size
    s = crop_off + (np.arange(dst) + 0.5) * size / dst - 0.5
    i0 = np.clip(np.floor(s).astype(np.int64), 0, src - 1)
    i1 = np.clip(i0 + 1, 0, src - 1)
    frac = np.clip(s - i0, 0.0, 1.0).astype(np.float32)
    m = np.zeros((dst, src), np.float32)
    m[np.arange(dst), i0] += 1.0 - frac
    m[np.arange(dst), i1] += frac
    return m


def _crop_resize_norm_kernel(u8_ref, ry_ref, rxc_ref, mean_ref, istd_ref,
                             out_ref):
    """One image per grid step: cast (VPU) -> H-resize matmul (MXU) ->
    W-resize matmul (MXU) -> requantize + normalize (VPU), all out of
    VMEM. The W-axis matrix is pre-expanded channel-blockwise
    (kron(Rx, I_C)) so both resizes are plain 2-D matmuls — no gathers,
    no transposes, nothing Mosaic has to emulate."""
    # full-f32 matmul precision: default TPU dot rounds operands to bf16,
    # which perturbs resampled pixels by up to +-2 uint8 quanta and breaks
    # parity with the host resize
    x = u8_ref[0].astype(jnp.int32).astype(jnp.float32)      # (Hs, Ws*C)
    y = jax.lax.dot(ry_ref[:], x,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)       # (Hd, Ws*C)
    z = jax.lax.dot(y, rxc_ref[:],
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)       # (Hd, WdC_pad)
    # re-quantize exactly like the host resize (clip+rint back to uint8
    # range) so fused and host routes score identical images identically
    z = jnp.clip(jnp.round(z), 0.0, 255.0)
    out_ref[0] = ((z - mean_ref[:]) * istd_ref[:]).astype(out_ref.dtype)


def _pad128(n: int) -> int:
    return ((n + 127) // 128) * 128


@functools.partial(jax.jit, static_argnames=("src_hw", "dst_hw", "channels",
                                             "out_dtype"))
def _fused_crop_resize_normalize(u8: jax.Array, ry: jax.Array, rxc: jax.Array,
                                 mean2d: jax.Array, istd2d: jax.Array,
                                 src_hw: Tuple[int, int],
                                 dst_hw: Tuple[int, int], channels: int,
                                 out_dtype=jnp.float32) -> jax.Array:
    b = u8.shape[0]
    hs, ws = src_hw
    hd, wd = dst_hw
    wsc = ws * channels
    wdc_pad = rxc.shape[1]
    vmem = pl.ANY if _interpret() else pltpu.VMEM
    out = pl.pallas_call(
        _crop_resize_norm_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hs, wsc), lambda i: (i, 0, 0),
                         memory_space=vmem),
            pl.BlockSpec((hd, hs), lambda i: (0, 0), memory_space=vmem),
            pl.BlockSpec((wsc, wdc_pad), lambda i: (0, 0),
                         memory_space=vmem),
            pl.BlockSpec((hd, wdc_pad), lambda i: (0, 0), memory_space=vmem),
            pl.BlockSpec((hd, wdc_pad), lambda i: (0, 0), memory_space=vmem),
        ],
        out_specs=pl.BlockSpec((1, hd, wdc_pad), lambda i: (i, 0, 0),
                               memory_space=vmem),
        out_shape=jax.ShapeDtypeStruct((b, hd, wdc_pad), out_dtype),
        interpret=_interpret(),
    )(u8, ry, rxc, mean2d, istd2d)
    return out[:, :, :wd * channels].reshape(b, hd, wd, channels)


def make_fused_preprocess_fn(src_shape: Tuple[int, int, int],
                             resize: Optional[Tuple[int, int]] = None,
                             crop: Optional[Tuple[int, int]] = None,
                             mean: Sequence[float] = (0.0,),
                             std: Sequence[float] = (1.0,),
                             out_dtype=jnp.float32):
    """The complete SURVEY §7 preprocess as ONE Pallas kernel: uint8 in,
    center-crop + bilinear-resize + normalize, model-ready activations
    out — the OpenCV pipeline the reference ran per-row on CPUs
    (``ImageTransformer.scala:33-153``), fused ahead of the first layer.

    ``fn(u8 (B, Hs*Ws*C) or (B, Hs, Ws, C)) -> (B, Hd, Wd, C)``.
    ``crop`` is a center-crop (h, w) applied BEFORE ``resize`` (either may
    be None); per-channel ``mean``/``std`` normalize after the host-parity
    requantize. Compose inside the model's jit; pass
    ``out_dtype=jnp.bfloat16`` to feed the first conv in bf16."""
    hs, ws, c = (int(v) for v in src_shape)
    ch, cw = (int(v) for v in crop) if crop else (hs, ws)
    if ch > hs or cw > ws:
        raise ValueError(f"crop {crop} exceeds source {src_shape}")
    hd, wd = (int(v) for v in resize) if resize else (ch, cw)
    # integer floor offsets, matching ops.center_crop's slicing — a
    # fractional offset would blend adjacent pixels instead of cropping
    off_h, off_w = float((hs - ch) // 2), float((ws - cw) // 2)
    ry = _sampling_matrix(hs, hd, off_h, ch)
    rx = _sampling_matrix(ws, wd, off_w, cw)
    wdc_pad = _pad128(wd * c)
    # kron(Rx^T, I_C) with lane padding: column (w*c + k) resamples
    # channel k at output position w
    rxc = np.zeros((ws * c, wdc_pad), np.float32)
    for k in range(c):
        rxc[np.ix_(np.arange(ws) * c + k, np.arange(wd) * c + k)] = rx.T
    mean_row = np.zeros((wdc_pad,), np.float32)
    istd_row = np.zeros((wdc_pad,), np.float32)
    mean_row[:wd * c] = np.tile(np.broadcast_to(
        np.asarray(mean, np.float32), (c,)), wd)
    istd_row[:wd * c] = np.tile(1.0 / np.broadcast_to(
        np.asarray(std, np.float32), (c,)), wd)
    ry_d = jnp.asarray(ry)
    rxc_d = jnp.asarray(rxc)
    mean2d = jnp.asarray(np.broadcast_to(mean_row, (hd, wdc_pad)))
    istd2d = jnp.asarray(np.broadcast_to(istd_row, (hd, wdc_pad)))

    def preprocess(u8: jax.Array) -> jax.Array:
        if u8.dtype != jnp.uint8:
            u8 = u8.astype(jnp.uint8)
        u8 = u8.reshape(u8.shape[0], hs, ws * c)
        return _fused_crop_resize_normalize(
            u8, ry_d, rxc_d, mean2d, istd2d, (hs, ws), (hd, wd), c,
            out_dtype)
    return preprocess


def device_resize_bilinear(x: jax.Array, height: int, width: int) -> jax.Array:
    """On-device bilinear resize of (B, H, W, C) float images, half-pixel
    centers with edge clamp — the SAME convention as the host path
    (``image/ops.py _resize_stack``), so fusing the resize into a scoring
    jit is a pure acceleration, not a semantic change. (``jax.image.resize``
    would anti-alias on downscale and diverge from the OpenCV-style host
    numbers.) Gather indices/weights are compile-time constants; the lerp is
    two taken-row blends per axis, fused by XLA."""
    b, h, w = x.shape[:3]
    if (h, w) == (height, width):
        return x

    def plan(src, dst):
        s = (np.arange(dst) + 0.5) * src / dst - 0.5
        i0 = np.clip(np.floor(s).astype(np.int64), 0, src - 1)
        i1 = np.clip(i0 + 1, 0, src - 1)
        frac = np.clip(s - i0, 0.0, 1.0).astype(np.float32)
        return jnp.asarray(i0), jnp.asarray(i1), jnp.asarray(frac)

    y0, y1, wy = plan(h, height)
    x0, x1, wx = plan(w, width)
    wy = wy[None, :, None, None]
    wx = wx[None, None, :, None]
    r0 = jnp.take(x, y0, axis=1)
    r1 = jnp.take(x, y1, axis=1)
    rows = r0 * (1 - wy) + r1 * wy
    c0 = jnp.take(rows, x0, axis=2)
    c1 = jnp.take(rows, x1, axis=2)
    return c0 * (1 - wx) + c1 * wx
