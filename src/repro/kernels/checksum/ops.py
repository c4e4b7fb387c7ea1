"""Public fingerprint op: arbitrary-array content digest via the Pallas kernel."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.checksum.fingerprint import LANES, ROWS, fingerprint_u32


def _as_words(arr: jax.Array) -> jax.Array:
    """Bit-exact view of any array as padded (N, 128) uint32 words."""
    a = jnp.ravel(arr)
    if a.dtype == jnp.bfloat16 or a.dtype == jnp.float16:
        a = a.view(jnp.uint16).astype(jnp.uint32)
    elif a.dtype.itemsize == 4:
        a = a.view(jnp.uint32)
    elif a.dtype.itemsize == 8:
        a = a.view(jnp.uint32)
    elif a.dtype.itemsize == 1:
        a = a.view(jnp.uint8).astype(jnp.uint32)
    else:
        a = a.astype(jnp.float32).view(jnp.uint32)
    block = ROWS * LANES
    pad = (-a.shape[0]) % block
    a = jnp.pad(a, (0, pad))
    return a.reshape(-1, LANES)


def fingerprint(arr: jax.Array, interpret: Optional[bool] = None) -> jax.Array:
    """128-bit content digest of an array, computed on-device.

    Equal contents (same dtype/shape) always produce equal digests;
    distinct contents collide with probability ~2^-128 under the
    position-weighted modular-sum family.
    """
    return fingerprint_u32(_as_words(arr), interpret=interpret)


def digest_hex(arr) -> str:
    """Host-side convenience: hex string of the digest."""
    d = np.asarray(fingerprint(jnp.asarray(arr)))
    return "".join(f"{int(x):08x}" for x in d)
