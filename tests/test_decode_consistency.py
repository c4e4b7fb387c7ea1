"""Decode-vs-prefill consistency: the incremental (KV/SSM cache) path must
produce the same logits as re-running prefill on the extended prompt."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import decode_step_fn, init_params, prefill_fn
from repro.models.frontend import synth_extra_inputs

# dense, GQA+SWA (ring cache), SSM, hybrid, MoE, enc-dec, VLM
ARCHS = ["olmo-1b", "h2o-danube-3-4b", "mamba2-130m", "zamba2-1.2b",
         "granite-moe-3b-a800m", "whisper-base", "llama-3.2-vision-11b"]


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_prefill(arch, rng_key):
    cfg = get_smoke_config(arch)
    # float32 compute for a tight comparison
    cfg = dataclasses.replace(cfg, dtype="float32")
    b, s = 2, 160 if cfg.sliding_window else 48   # exceed the SWA window
    params = init_params(cfg, rng_key)
    tokens = jax.random.randint(rng_key, (b, s + 1), 0, cfg.vocab_size)
    extras = synth_extra_inputs(cfg, b, rng_key)

    batch_s = {"tokens": tokens[:, :s], **extras}
    batch_s1 = {"tokens": tokens, **extras}

    logits_s, state = jax.jit(
        lambda p, x: prefill_fn(p, x, cfg, cache_len=s + 4))(
        params, batch_s)
    logits_ref, _ = jax.jit(lambda p, x: prefill_fn(p, x, cfg))(
        params, batch_s1)

    # decode the next token from the cache: must match prefill(s+1)
    next_tok = tokens[:, s]
    logits_dec, _ = jax.jit(lambda p, st, t: decode_step_fn(p, st, t, cfg))(
        params, state, next_tok)

    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(logits_ref),
                               rtol=2e-3, atol=2e-3)
