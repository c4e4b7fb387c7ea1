"""Sliding-window flash attention kernel (Pallas TPU).

Online-softmax attention with an explicit sliding window: query block i
visits only the kv blocks inside its window, so work and VMEM are
O(window) per query block instead of O(seq) — the kernel behind the
sub-quadratic ``long_500k`` decode variant and the SWA training path
(h2o-danube, zamba2 shared blocks).

Tiling: grid = (batch*heads, n_q_blocks, n_kv_steps); blocks (BQ, D) for q
and (BKV, D) for k/v live in VMEM; f32 accumulators (m, l, acc) persist in
VMEM scratch across the kv-step dimension (TPU grids iterate the last axis
innermost/sequentially).  MXU-aligned: BQ = BKV = 128, D = head_dim.
For full-causal (window=0) the kv-step count equals the kv block count and
off-diagonal blocks are skipped via ``pl.when``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

BQ = 128
BKV = 128
NEG_INF = -1e30


def _steps(window: int, n_kv: int) -> int:
    """KV blocks each query block must visit."""
    if window <= 0:
        return n_kv
    return min(n_kv, (window + BQ - 1) // BKV + 1)


def _kv_index(q_i, j, steps: int):
    """KV block index for (q block, step): trailing `steps` blocks ending at
    the diagonal; clamped (skipped in-body when negative)."""
    return jnp.maximum(q_i - (steps - 1) + j, 0)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, steps: int, window: int, seq_kv: int, scale: float):
    q_i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_j = q_i - (steps - 1) + j

    @pl.when(kv_j >= 0)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale          # (BQ, D)
        k = k_ref[0].astype(jnp.float32)                  # (BKV, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = q_i * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BKV), 0)
        k_pos = kv_j * BKV + jax.lax.broadcasted_iota(jnp.int32, (BQ, BKV), 1)
        mask = (k_pos <= q_pos) & (k_pos < seq_kv)
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "seq_kv", "interpret"))
def swa_flash(q: jax.Array, k: jax.Array, v: jax.Array, *, window: int,
              seq_kv: int, interpret: Optional[bool] = None) -> jax.Array:
    """q: (BH, Sq, D), k/v: (BH, Skv, D), padded to block multiples.

    Returns (BH, Sq, D).  ``seq_kv`` is the unpadded kv length (masking).
    """
    bh, sq, d = q.shape
    skv = k.shape[1]
    assert sq % BQ == 0 and skv % BKV == 0, (sq, skv)
    nq, nkv = sq // BQ, skv // BKV
    steps = _steps(window, nkv)
    scale = 1.0 / np.sqrt(d)

    kernel = functools.partial(_kernel, steps=steps, window=window,
                               seq_kv=seq_kv, scale=scale)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        grid=(bh, nq, steps),
        in_specs=[
            pl.BlockSpec((1, BQ, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BKV, d),
                         lambda b, i, j, s=steps: (b, _kv_index(i, j, s), 0)),
            pl.BlockSpec((1, BKV, d),
                         lambda b, i, j, s=steps: (b, _kv_index(i, j, s), 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, d), jnp.float32),
        ],
        interpret=default_interpret() if interpret is None else interpret,
    )(q, k, v)
