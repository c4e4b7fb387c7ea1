"""Pallas TPU kernels for the framework's compute hot spots.

- ``checksum``      — on-device content fingerprint (the hot loop of the
  paper's checksum-based dedup, §4.6/§5.2.1: every context switch and every
  checkpoint fingerprints all live buffers).
- ``swa_attention`` — sliding-window flash attention (sub-quadratic decode
  for the long_500k shape; also the dense-arch training hot spot).
- ``ssd_scan``      — Mamba2 SSD intra-chunk kernel (ssm/hybrid archs).
- ``fused_ce``      — streaming-vocab cross entropy: online logsumexp over
  vocab tiles so the (tokens, vocab) logits never exist in HBM (the other
  memory hot spot the roofline analysis exposed).

Each kernel directory has: the ``pl.pallas_call`` kernel with explicit
BlockSpec VMEM tiling, ``ops.py`` (jit'd public wrapper), ``ref.py``
(pure-jnp oracle).  ``interpret`` defaults to ``default_interpret()``:
compiled by Mosaic on a TPU, interpreted anywhere else (the CPU tests).
``checksum``, ``swa_attention`` and ``fused_ce`` compile for v5e
(``tests/test_tpu_compile.py``).

``ssd_scan`` is interpret-only: the v5e compiler refuses it (``cumsum``
has no Mosaic lowering, and the kernel builds 3-D ``(Q, Q, H)``
intermediates).  Wiring it in or deleting it is open.
"""
import jax


def default_interpret() -> bool:
    """Pallas interpret mode exactly when JAX's backend is not a TPU."""
    return jax.default_backend() != "tpu"
