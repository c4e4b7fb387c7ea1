"""On-device content-fingerprint kernel (Pallas TPU).

The paper's checksum-based dedup (§4.6 checkpoint compression, §5.2.1
conditional swap) fingerprints EVERY live device buffer at every context
switch and checkpoint — on TPU this must run at HBM bandwidth on-device so
only the 128-bit digest crosses to the host.

Digest: four uint32 lanes of position-weighted modular sums.  Per-position
weights make the digest permutation-sensitive; per-block partial digests
combine by wrapping addition, so the grid reduction is embarrassingly
parallel.  Block shape (ROWS, 128): last dim matches the TPU lane width,
ROWS*128*4B per block sized well under VMEM.  Each block writes a
lane-dense (8, 128) partial tile, the smallest output block the TPU's
(8, 128) tiling allows.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

ROWS = 256                    # 256 x 128 x 4B = 128 KiB per input block
LANES = 128

# numpy scalars embed as jaxpr literals (Pallas kernels must not capture
# traced constants, and python ints > int32-max overflow weak typing)
P1 = np.uint32(2654435761)    # Knuth multiplicative
P2 = np.uint32(0x9E3779B9)    # golden ratio
P3 = np.uint32(0x85EBCA6B)    # murmur3 c1
P4 = np.uint32(0xC2B2AE35)    # murmur3 c2


def _kernel(x_ref, o_ref):
    """One (ROWS, 128) input block -> one (8, 128) output tile: row k < 4
    holds digest lane k summed over the block's rows, one partial per
    lane position; rows 4-7 are zero padding to the (8, 128) tile."""
    i = pl.program_id(0)
    x = x_ref[...]
    rows = jax.lax.broadcasted_iota(jnp.uint32, (ROWS, LANES), 0)
    lanes = jax.lax.broadcasted_iota(jnp.uint32, (ROWS, LANES), 1)
    base = jnp.uint32(i) * jnp.uint32(ROWS * LANES)
    pos = base + rows * jnp.uint32(LANES) + lanes
    w = pos * P1 + P2
    terms = (x * w, (x ^ P3) * (w ^ P4), (x * x + P4) * w,
             (x + pos) * (pos * P3 + P1))
    # Mosaic reduces no unsigned type: sum the same bits as int32, whose
    # wrapping addition is bit-identical
    for k, t in enumerate(terms):
        t = jax.lax.bitcast_convert_type(t, jnp.int32)
        o_ref[k:k + 1, :] = jnp.sum(t, axis=0, keepdims=True)
    o_ref[4:, :] = jnp.zeros((8 - len(terms), LANES), jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fingerprint_u32(words: jax.Array,
                    interpret: Optional[bool] = None) -> jax.Array:
    """words: (n_blocks*ROWS, LANES) uint32 -> (4,) uint32 digest."""
    n, l = words.shape
    assert l == LANES and n % ROWS == 0, (n, l)
    nblocks = n // ROWS
    partials = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((nblocks * 8, LANES), jnp.int32),
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, LANES), lambda i: (i, 0)),
        interpret=default_interpret() if interpret is None else interpret,
    )(words)
    # wrapping uint32 addition is associative: any summation order gives
    # the digest of the whole array
    partials = jax.lax.bitcast_convert_type(partials, jnp.uint32)
    return jnp.sum(partials.reshape(nblocks, 8, LANES)[:, :4], axis=(0, 2),
                   dtype=jnp.uint32)
