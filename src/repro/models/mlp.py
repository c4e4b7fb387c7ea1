"""Feed-forward layers: SwiGLU, GELU and ReLU^2 MLPs."""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro.models.common import dense_init
from repro.parallel.constraints import BATCH, MODEL, constrain


def init_mlp(key, d_model: int, d_ff: int, kind: str, dtype=jnp.float32) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    out_scale = 0.02 / math.sqrt(2.0)
    if kind == "swiglu":
        return {
            "wi": dense_init(k1, (d_model, d_ff), dtype=dtype),
            "wg": dense_init(k2, (d_model, d_ff), dtype=dtype),
            "wo": dense_init(k3, (d_ff, d_model), scale=out_scale, dtype=dtype),
        }
    if kind in ("gelu", "relu2"):
        return {
            "wi": dense_init(k1, (d_model, d_ff), dtype=dtype),
            "wo": dense_init(k3, (d_ff, d_model), scale=out_scale, dtype=dtype),
        }
    raise ValueError(f"unknown mlp kind {kind!r}")


def activation(kind: str, h: jax.Array) -> jax.Array:
    """The non-gated MLPs' activation: GELU, or ReLU^2 (``relu(h)^2``,
    Primer / Nemotron-H)."""
    if kind == "relu2":
        return jnp.square(jax.nn.relu(h))
    return jax.nn.gelu(h)


def mlp_forward(params: Dict, x: jax.Array, kind: str) -> jax.Array:
    if kind == "swiglu":
        h = jnp.einsum("bsd,df->bsf", x, params["wi"].astype(x.dtype))
        g = jnp.einsum("bsd,df->bsf", x, params["wg"].astype(x.dtype))
        h = jax.nn.silu(g) * constrain(h, BATCH, None, MODEL)
    else:
        h = jnp.einsum("bsd,df->bsf", x, params["wi"].astype(x.dtype))
        h = activation(kind, constrain(h, BATCH, None, MODEL))
    return constrain(
        jnp.einsum("bsf,fd->bsd", h, params["wo"].astype(x.dtype)),
        BATCH, None, None)
