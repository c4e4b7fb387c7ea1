"""Public SSD op: Pallas intra-chunk kernel + inter-chunk recurrence."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.ssd import ssd_intra_chunk


def ssd_chunked_pallas(x: jax.Array, dt: jax.Array, a: jax.Array,
                       b: jax.Array, c: jax.Array, chunk: int,
                       initial_state: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """Drop-in replacement for ``models.ssm.ssd_chunked`` backed by the
    Pallas kernel.  Shapes as there: x (B,L,H,P), dt (B,L,H), a (H,),
    b/c (B,L,N) -> (y (B,L,H,P), final_state (B,H,P,N))."""
    bs, l, h, p = x.shape
    n = b.shape[-1]
    pad = (-l) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    nc = x.shape[1] // chunk

    xc = x.reshape(bs * nc, chunk, h, p)
    dtc = dt.reshape(bs * nc, chunk, h)
    bc_ = b.reshape(bs * nc, chunk, n)
    cc = c.reshape(bs * nc, chunk, n)

    y_intra, states, cum = ssd_intra_chunk(
        xc, dtc, a.astype(jnp.float32), bc_, cc)
    y_intra = y_intra.reshape(bs, nc, chunk, h, p)
    states = states.reshape(bs, nc, h, p, n)
    cum = cum.reshape(bs, nc, chunk, h)

    # inter-chunk recurrence (tiny, sequential over nc)
    total = cum[:, :, -1, :]                      # (B, nc, H)
    decay_chunk = jnp.exp(total)

    def step(s_prev, inp):
        dc, sc = inp
        return s_prev * dc[:, :, None, None] + sc, s_prev

    s0 = (jnp.zeros((bs, h, p, n), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    final, s_before = jax.lax.scan(
        step, s0, (decay_chunk.transpose(1, 0, 2),
                   states.transpose(1, 0, 2, 3, 4)))
    s_before = s_before.transpose(1, 0, 2, 3, 4)   # (B,nc,H,P,N)

    outw = jnp.exp(cum)
    y_inter = jnp.einsum("bqtn,bqhpn,bqth->bqthp",
                         cc.reshape(bs, nc, chunk, n).astype(jnp.float32),
                         s_before, outw)
    y = (y_intra + y_inter).reshape(bs, nc * chunk, h, p)[:, :l]
    return y.astype(x.dtype), final
