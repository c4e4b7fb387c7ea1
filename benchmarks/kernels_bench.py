"""Kernel micro-benchmarks.  Off a TPU the kernels run in Pallas interpret
mode (correctness-path cost only); each row says which mode it timed."""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.checksum.ops import fingerprint
from repro.kernels.ssd_scan.ops import ssd_chunked_pallas
from repro.kernels.swa_attention.ops import swa_attention


def _time(fn, n=3) -> float:
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / n


def run() -> List[Dict]:
    key = jax.random.PRNGKey(0)
    rows = []
    mode = f"interpret={default_interpret()}"

    x = jax.random.normal(key, (1 << 20,), jnp.float32)
    t = _time(lambda: fingerprint(x))
    rows.append({"name": "kernel/checksum/4MB", "us_per_call": t * 1e6,
                 "derived": f"GBps={x.nbytes/t/1e9:.2f} {mode}"})

    q = jax.random.normal(key, (1, 512, 4, 64), jnp.float32)
    t = _time(lambda: swa_attention(q, q, q, window=128))
    rows.append({"name": "kernel/swa_attn/512x4x64_w128",
                 "us_per_call": t * 1e6, "derived": mode})

    xs = jax.random.normal(key, (1, 256, 4, 32), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(key, (1, 256, 4)))
    a = -jnp.ones((4,))
    b = jax.random.normal(key, (1, 256, 16))
    t = _time(lambda: ssd_chunked_pallas(xs, dt, a, b, b, 64)[0])
    rows.append({"name": "kernel/ssd_scan/256x4x32",
                 "us_per_call": t * 1e6, "derived": "interpret=True"})
    return rows
