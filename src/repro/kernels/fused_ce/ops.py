"""Public fused-CE op: padding + masking around the kernel."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.fused_ce.ce import BT, fused_ce_stats


def fused_cross_entropy(hidden: jax.Array, head: jax.Array,
                        labels: jax.Array, interpret: Optional[bool] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """Token-level CE without materializing logits.

    hidden: (T, d); head: (d, V); labels: (T,) int32, < 0 = ignore.
    Returns (sum loss, token count) — same contract as
    ``models.model.chunked_cross_entropy`` on flattened inputs.
    """
    t = hidden.shape[0]
    pad = (-t) % BT
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad), constant_values=-1)
    safe = jnp.maximum(labels, 0)
    lse, pick = fused_ce_stats(hidden, head, safe, interpret=interpret)
    mask = (labels >= 0).astype(jnp.float32)
    loss = jnp.sum((lse[:, 0] - pick[:, 0]) * mask)
    return loss, jnp.sum(mask)
