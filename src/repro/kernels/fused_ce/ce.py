"""Fused cross-entropy kernel (Pallas TPU).

The LM loss is the other memory hot spot besides attention: naive lowering
materializes (tokens, vocab) logits in HBM (yi-9b train: 1M x 64k x 4 B
per step).  This kernel streams vocab TILES through VMEM with an online
logsumexp, so per token only the running (max, sumexp, label-logit)
statistics ever leave the core — the logits matrix never exists in HBM.

Tiling: grid = (token_blocks, vocab_blocks); per step the (BT, d) hidden
tile and the (d, BV) head tile produce a (BT, BV) logit tile on the MXU;
f32 running stats persist in VMEM scratch across the vocab dimension
(innermost, sequential).  MXU-aligned: BT = 128, BV = 512.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

BT = 128
BV = 512
NEG_INF = -1e30


def _kernel(h_ref, w_ref, lab_ref, lse_ref, pick_ref,
            m_ref, l_ref, p_ref, *, n_vocab: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        p_ref[...] = jnp.full_like(p_ref, NEG_INF)

    h = h_ref[...].astype(jnp.float32)               # (BT, d)
    w = w_ref[...].astype(jnp.float32)               # (d, BV)
    logits = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    vpos = j * BV + jax.lax.broadcasted_iota(jnp.int32, (BT, BV), 1)
    valid = vpos < n_vocab
    logits = jnp.where(valid, logits, NEG_INF)

    # online logsumexp
    m_prev = m_ref[...]                              # (BT, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) \
        + jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True)
    m_ref[...] = m_new

    # label-logit pick: the label lands in exactly one vocab tile
    lab = lab_ref[...]                               # (BT, 1) int32
    hit = (vpos == lab) & valid
    p_ref[...] = jnp.maximum(
        p_ref[...], jnp.max(jnp.where(hit, logits, NEG_INF),
                            axis=1, keepdims=True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        lse_ref[...] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        pick_ref[...] = p_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_ce_stats(hidden: jax.Array, head: jax.Array, labels: jax.Array,
                   interpret: Optional[bool] = None):
    """hidden (T, d) x head (d, V), labels (T,) -> (lse (T,1), pick (T,1)).

    T must be a multiple of BT; V is padded internally to BV multiples.
    Negative labels return pick = -inf (masked by the wrapper).
    """
    t, d = hidden.shape
    v = head.shape[1]
    assert t % BT == 0, t
    pv = (-v) % BV
    if pv:
        head = jnp.pad(head, ((0, 0), (0, pv)))
    nv = head.shape[1] // BV
    lab2 = labels.reshape(t, 1)

    kernel = functools.partial(_kernel, n_vocab=v)
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((t, 1), jnp.float32),
            jax.ShapeDtypeStruct((t, 1), jnp.float32),
        ),
        grid=(t // BT, nv),
        in_specs=[
            pl.BlockSpec((BT, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, BV), lambda i, j: (0, j)),
            pl.BlockSpec((BT, 1), lambda i, j: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((BT, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((BT, 1), lambda i, j: (i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((BT, 1), jnp.float32),
            pltpu.VMEM((BT, 1), jnp.float32),
            pltpu.VMEM((BT, 1), jnp.float32),
        ],
        interpret=default_interpret() if interpret is None else interpret,
    )(hidden, head, lab2)
