"""Public sliding-window attention op: padding + head layout around the kernel."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.swa_attention.swa import BKV, BQ, swa_flash


def swa_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  window: int = 0, interpret: Optional[bool] = None) -> jax.Array:
    """Causal (optionally sliding-window) attention.

    q, k, v: (B, S, H, D) — kv heads already repeated to H (GQA handled by
    the caller).  Returns (B, S, H, D).
    """
    b, s, h, d = q.shape
    skv = k.shape[1]

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    qf, kf, vf = to_bh(q), to_bh(k), to_bh(v)
    pq = (-s) % BQ
    pk = (-skv) % BKV
    if pq:
        qf = jnp.pad(qf, ((0, 0), (0, pq), (0, 0)))
    if pk:
        kf = jnp.pad(kf, ((0, 0), (0, pk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pk), (0, 0)))
    out = swa_flash(qf, kf, vf, window=window, seq_kv=skv,
                    interpret=interpret)
    out = out[:, :s]
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
