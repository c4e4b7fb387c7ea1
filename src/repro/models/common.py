"""Shared layer primitives: norms, RoPE, initializers."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def rmsnorm(x: jax.Array, scale: Optional[jax.Array], eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    if scale is not None:
        x = x * scale.astype(jnp.float32)
    return x.astype(dtype)


def layernorm(x: jax.Array, scale: Optional[jax.Array], bias: Optional[jax.Array],
              eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        x = x * scale.astype(jnp.float32)
    if bias is not None:
        x = x + bias.astype(jnp.float32)
    return x.astype(dtype)


def apply_norm(kind: str, x: jax.Array, params: Optional[dict],
               eps: float = 1e-6) -> jax.Array:
    """Dispatch on the config's norm kind; ``eps`` is the RMSNorm's
    (``ModelConfig.norm_eps``).

    ``nonparametric_ln`` (olmo, arXiv:2402.00838) is LayerNorm with no
    learned scale/bias — params is None.
    """
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"] if params else None, eps)
    if kind == "layernorm":
        return layernorm(x, params["scale"] if params else None,
                         params.get("bias") if params else None)
    if kind == "nonparametric_ln":
        return layernorm(x, None, None)
    raise ValueError(f"unknown norm {kind!r}")


def norm_param(kind: str, dim: int, dtype=jnp.float32) -> Optional[dict]:
    if kind == "rmsnorm":
        return {"scale": jnp.ones((dim,), dtype)}
    if kind == "layernorm":
        return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}
    if kind == "nonparametric_ln":
        return None
    raise ValueError(kind)


def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(head_dim, theta), dtype=jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    angles = angles[..., None, :]                              # broadcast over heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


def dense_init(key: jax.Array, shape, scale: float = 0.02, dtype=jnp.float32) -> jax.Array:
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
