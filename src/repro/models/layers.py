"""Shared neural-net layers. Every matmul routes through ``dense`` below,
which applies the approximate-multiplier pipeline when configured — this is
how the paper's technique becomes a first-class, model-wide feature."""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.approx import ApproxConfig, QWeight, approx_dense

__all__ = [
    "dense",
    "init_dense",
    "rms_norm",
    "layer_norm",
    "rotary",
    "apply_rope",
    "apply_m_rope",
    "sinusoidal_at",
    "sinusoidal_positions",
    "truncated_normal_init",
]


def truncated_normal_init(key, shape, scale: float = 1.0, dtype=jnp.float32):
    fan_in = shape[0] if len(shape) >= 2 else max(shape[0], 1)
    std = scale / np.sqrt(fan_in)
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def init_dense(key, d_in: int, d_out: int, scale: float = 1.0) -> jax.Array:
    return truncated_normal_init(key, (d_in, d_out), scale)


@jax.named_scope("dense")
def dense(x: jax.Array, w, cfg: ApproxConfig) -> jax.Array:
    """x (..., K) @ w (K, N) under the configured multiplier semantics:
    ``cfg.mode`` selects float, exact-quant, LUT, low-rank or the Pallas
    kernel (the serving engine's ``exact``/``approx`` execution modes resolve
    to these). ``w`` may be a frozen ``QWeight`` (serving path). Runs under
    the ``dense`` name scope, so every operation of a projection carries it
    in its HLO ``op_name``, whatever the mode."""
    if isinstance(w, QWeight):
        return approx_dense(x, w, cfg).astype(x.dtype)
    if cfg.mode == "float":
        return jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))
    return approx_dense(x, w, cfg).astype(x.dtype)


@jax.named_scope("norm")
def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * gamma.astype(x.dtype)


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma + beta).astype(x.dtype)


def rotary(positions: jax.Array, dim: int, theta: float = 10000.0) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables, (..., dim//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def _rope_rotate(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope(q, k, positions, theta: float = 10000.0):
    """q/k: (B, S, H, hd); positions: (B, S)."""
    hd = q.shape[-1]
    cos, sin = rotary(positions, hd, theta)          # (B, S, hd/2)
    cos = cos[:, :, None, :].astype(q.dtype)
    sin = sin[:, :, None, :].astype(q.dtype)
    return _rope_rotate(q, cos, sin), _rope_rotate(k, cos, sin)


def apply_m_rope(
    q, k, positions_thw: jax.Array, sections: Sequence[int], theta: float = 1000000.0
):
    """Qwen2-VL multimodal RoPE: ``positions_thw`` (B, 3, S) temporal/height/
    width position ids; ``sections`` split head_dim//2 into 3 groups, each
    rotated by its own position stream."""
    hd = q.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    cos_parts, sin_parts = [], []
    start = 0
    for i, sec in enumerate(sections):
        inv = 1.0 / (
            theta ** (jnp.arange(start, start + sec, dtype=jnp.float32) * 2.0 / hd)
        )
        ang = positions_thw[:, i, :].astype(jnp.float32)[..., None] * inv
        cos_parts.append(jnp.cos(ang))
        sin_parts.append(jnp.sin(ang))
        start += sec
    cos = jnp.concatenate(cos_parts, axis=-1)[:, :, None, :].astype(q.dtype)
    sin = jnp.concatenate(sin_parts, axis=-1)[:, :, None, :].astype(q.dtype)
    return _rope_rotate(q, cos, sin), _rope_rotate(k, cos, sin)


def sinusoidal_at(positions: jax.Array, dim: int) -> jax.Array:
    """(...,) int positions -> (..., dim) sinusoidal embeddings (jnp-native,
    never a compile-time constant)."""
    inv = 1.0 / (10000 ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv        # (..., dim/2)
    out = jnp.stack([jnp.sin(ang), jnp.cos(ang)], axis=-1)      # (..., dim/2, 2)
    return out.reshape(*positions.shape, dim)


def sinusoidal_positions(seq_len: int, dim: int, offset: int = 0) -> jax.Array:
    return sinusoidal_at(jnp.arange(offset, offset + seq_len), dim)
