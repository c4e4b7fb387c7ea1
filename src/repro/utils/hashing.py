"""Host-side content checksums.

The paper's checksum-based dedup (§4.6, §5.2.1) fingerprints device buffers
by content.  On-device fingerprints use the Pallas kernel in
``repro.kernels.checksum``; this module provides the host-side reference
(used for checkpoint chunk addressing and in tests).
"""
from __future__ import annotations

import hashlib
from typing import Any

import numpy as np


def buffer_checksum(arr: Any) -> str:
    """Stable content checksum of an array (dtype+shape+bytes)."""
    a = np.asarray(arr)
    h = hashlib.blake2b(digest_size=16)
    h.update(str(a.dtype).encode())
    h.update(str(a.shape).encode())
    h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def chunk_digest(*parts) -> str:
    """Content address of one checkpoint chunk: blake2b-128 of the
    concatenation of ``parts`` (bytes-like), fed in order without joining
    them.  ``hashlib`` releases the GIL on large buffers, so chunks hash in
    parallel on threads."""
    h = hashlib.blake2b(digest_size=16)
    for part in parts:
        h.update(part)
    return h.hexdigest()
