"""Mamba2 SSD intra-chunk kernel (Pallas TPU).

The SSD chunked algorithm (arXiv:2405.21060) splits the linear recurrence
into: (a) a quadratic attention-like computation INSIDE each fixed-size
chunk plus that chunk's input-state contribution — embarrassingly parallel
over (batch x chunk), all-MXU work; and (b) a tiny sequential recurrence
ACROSS chunks.  This kernel is (a); the wrapper in ops.py runs (b) as a
``lax.scan`` over the per-chunk states and adds the inter-chunk output
term.

Tiling: grid = (batch*nchunks); each step holds one chunk in VMEM:
x (Q, H, P), dt (Q, H), B/C (Q, N).  Q = chunk (128 default), P = head_dim,
N = state_dim — Q x N and Q x Q matmuls are MXU-shaped.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
            y_ref, state_ref, cum_ref):
    x = x_ref[0].astype(jnp.float32)        # (Q, H, P)
    dt = dt_ref[0].astype(jnp.float32)      # (Q, H)
    a = a_ref[...].astype(jnp.float32)      # (H,)
    b = b_ref[0].astype(jnp.float32)        # (Q, N)
    c = c_ref[0].astype(jnp.float32)        # (Q, N)
    q = x.shape[0]

    adt = dt * a[None, :]                   # (Q, H) negative decay steps
    cum = jnp.cumsum(adt, axis=0)           # within-chunk cumulative decay

    # intra-chunk attention-like term
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q, Q)
    seg = cum[:, None, :] - cum[None, :, :]                        # (Q, Q, H)
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    mask = (cols <= rows)[:, :, None]
    decay = jnp.where(mask, jnp.exp(jnp.where(mask, seg, 0.0)), 0.0)
    m = cb[:, :, None] * decay * dt[None, :, :]                    # (Q, Q, H)
    y = jnp.einsum("tsh,shp->thp", m, x)                           # (Q, H, P)

    # chunk input-state contribution: S_c = sum_s exp(total - cum_s) dt_s B_s x_s
    total = cum[-1]                                                # (H,)
    w = jnp.exp(total[None, :] - cum) * dt                         # (Q, H)
    state = jnp.einsum("sh,sn,shp->hpn", w, b, x)                  # (H, P, N)

    y_ref[0] = y
    state_ref[0] = state
    cum_ref[0] = cum


@jax.jit
def ssd_intra_chunk(x: jax.Array, dt: jax.Array, a: jax.Array,
                    b: jax.Array, c: jax.Array):
    """x: (BC, Q, H, P), dt: (BC, Q, H), a: (H,), b/c: (BC, Q, N).

    Interpret-only on every backend: the v5e compiler refuses this kernel
    (no Mosaic lowering of ``cumsum``; 3-D ``(Q, Q, H)`` intermediates).

    Returns (y_intra (BC,Q,H,P) f32, states (BC,H,P,N) f32, cum (BC,Q,H) f32).
    """
    bc, q, h, p = x.shape
    n = b.shape[-1]
    return pl.pallas_call(
        _kernel,
        out_shape=(
            jax.ShapeDtypeStruct((bc, q, h, p), jnp.float32),
            jax.ShapeDtypeStruct((bc, h, p, n), jnp.float32),
            jax.ShapeDtypeStruct((bc, q, h), jnp.float32),
        ),
        grid=(bc,),
        in_specs=[
            pl.BlockSpec((1, q, h, p), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, q, h), lambda i: (i, 0, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
            pl.BlockSpec((1, q, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, q, n), lambda i: (i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, q, h, p), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, h, p, n), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, q, h), lambda i: (i, 0, 0)),
        ),
        interpret=True,
    )(x, dt, a, b, c)
