"""Gradient compression: int8 error-feedback all-reduce (distributed-opt trick).

Structure = quantized reduce-scatter (all_to_all of int8 segments) → local
dequant-sum → requantize → int8 all-gather.  Per-device wire bytes ≈ 2N·1B
versus a ring fp32 all-reduce's ≈ 8N·1B → ~4× ICI saving on the gradient
exchange.  int8 rounding of the *contribution* is absorbed by per-device
error feedback (the residual is carried to the next step, so the accumulated
update is unbiased); the post-reduction requantization error is shared and
bounded by 1/127 of the segment max.

Usage (inside shard_map with the data axis bound):
    g_sync, err = compressed_psum_mean(g_local, err, axis_name="data")
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 256  # per-scale quantization group


def _quantize_rows(x: jnp.ndarray):
    """x: [R, M] fp32, M % CHUNK == 0 → (int8 [R, M], scales [R, M/CHUNK])."""
    R, M = x.shape
    xp = x.reshape(R, M // CHUNK, CHUNK)
    scale = jnp.maximum(jnp.max(jnp.abs(xp), axis=-1), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xp / scale[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(R, M), scale.astype(jnp.float32)


def _dequantize_rows(q: jnp.ndarray, scale: jnp.ndarray):
    R, M = q.shape
    x = q.astype(jnp.float32).reshape(R, M // CHUNK, CHUNK) * scale[..., None]
    return x.reshape(R, M)


def compressed_psum_mean(g: jnp.ndarray, err: jnp.ndarray, axis_name: str):
    """Error-feedback int8 mean-reduce over ``axis_name`` (shard_map only).

    Returns (mean gradient, new local error residual)."""
    D = jax.lax.axis_size(axis_name)
    n = g.size
    flat = g.reshape(-1).astype(jnp.float32) + err.reshape(-1)
    seg = -(-n // (D * CHUNK)) * CHUNK  # segment length, CHUNK-aligned
    pad = D * seg - n
    flat_p = jnp.pad(flat, (0, pad)).reshape(D, seg)

    # quantize my contribution, remember what was actually sent (EF residual)
    q, s = _quantize_rows(flat_p)
    new_err = (flat_p - _dequantize_rows(q, s)).reshape(-1)[:n]

    # reduce-scatter: device i ends up owning segment i from every peer
    q_x = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0, tiled=True)
    s_x = jax.lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0, tiled=True)
    own = _dequantize_rows(q_x.reshape(D, seg), s_x.reshape(D, -1)).sum(axis=0) / D  # [seg]

    # requantize the reduced segment and all-gather it back
    q2, s2 = _quantize_rows(own[None, :])
    q_all = jax.lax.all_gather(q2[0], axis_name)  # [D, seg] int8
    s_all = jax.lax.all_gather(s2[0], axis_name)  # [D, seg/CHUNK]
    mean = _dequantize_rows(q_all, s_all).reshape(-1)[:n]
    return mean.reshape(g.shape), new_err.reshape(g.shape)


def uncompressed_psum_mean(g: jnp.ndarray, axis_name: str):
    d = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    return jax.lax.psum(g.astype(jnp.float32), axis_name) / d
