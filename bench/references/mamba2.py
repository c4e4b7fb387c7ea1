"""Plain float32 reference of Mamba-2 (arXiv:2405.21060) as the benchmark
configures it.

Attention-free stack: embedding tied to the output head; per layer an
RMSNorm with scale, then the Mamba-2 block and a residual; a final RMSNorm;
next-token cross entropy averaged over every token of the batch.

The block: ``in_proj`` gives ``[z, x, B, C, dt]`` (one B/C group); a causal
depthwise convolution of width ``conv_width`` with bias, then SiLU, over
``[x, B, C]``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` per head.
The SSM is computed in its quadratic (dual) form, the masked matrix of the
paper's section 3: per head ``y_t = sum_{s<=t} (C_t . B_s)
exp(sum_{s<r<=t} dt_r A) dt_s x_s``, plus ``D x_t``; then ``y * silu(z)``,
an RMSNorm with scale and ``out_proj``.  The program computes the same
sums chunk by chunk; this reference never chunks.

Weights come from the seed by the recipe the configuration states: key
``PRNGKey(seed)`` split 8 ways; the embedding is 0.02-normal from key 0;
the layers' keys are key 2 split per layer, each split 4 ways: ``in_proj``
0.02-normal (key 0), ``conv_w`` 0.1-normal (key 1), ``out_proj``
0.02-normal (key 2); ``conv_b`` 0, ``A_log`` 0, ``D`` 1, ``dt_bias`` -2,
norm scales 1.  RMSNorm eps is 1e-6.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from bench.references.common import F32, Matmul

EPS = 1e-6


def _dims(model: Dict):
    s = model["ssm"]
    d = model["d_model"]
    d_in = s["expand"] * d
    return d, d_in, d_in // s["head_dim"], s["state_dim"], s["head_dim"], s["conv_width"]


def init_params(model: Dict, seed: int) -> Dict:
    d, d_in, nh, n, _, cw = _dims(model)
    v = model["vocab_size"]
    conv_ch = d_in + 2 * n
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    def layer(key):
        k1, k2, k3, _ = jax.random.split(key, 4)
        return {
            "ln1": {"scale": jnp.ones((d,), F32)},
            "ssm": {
                "in_proj": 0.02 * jax.random.normal(k1, (d, 2 * d_in + 2 * n + nh), F32),
                "conv_w": 0.1 * jax.random.normal(k2, (cw, conv_ch), F32),
                "conv_b": jnp.zeros((conv_ch,), F32),
                "A_log": jnp.zeros((nh,), F32),
                "D": jnp.ones((nh,), F32),
                "dt_bias": jnp.full((nh,), -2.0, F32),
                "norm_scale": jnp.ones((d_in,), F32),
                "out_proj": 0.02 * jax.random.normal(k3, (d_in, d), F32),
            },
        }

    init = jax.jit(lambda: {
        "embed": 0.02 * jax.random.normal(ks[0], (v, d), F32),
        "final_norm": {"scale": jnp.ones((d,), F32)},
        "blocks": jax.vmap(layer)(jax.random.split(ks[2], model["num_layers"])),
    })
    return init()


def rms_norm(x: jax.Array, scale: jax.Array) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + EPS) * scale


def ssm_block(x: jax.Array, w: Dict, model: Dict, mm: Matmul) -> jax.Array:
    """x: (rows, seq, d_model) -> (rows, seq, d_model)."""
    d, d_in, nh, n, hp, cw = _dims(model)
    rows, seq, _ = x.shape
    proj = mm("bsd,de->bse", x, w["in_proj"])
    z = proj[..., :d_in]
    xbc = proj[..., d_in:2 * d_in + 2 * n]
    dt = proj[..., 2 * d_in + 2 * n:]
    padded = jnp.pad(xbc, ((0, 0), (cw - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + seq] * w["conv_w"][i] for i in range(cw))
    conv = jax.nn.silu(conv + w["conv_b"])
    xs = conv[..., :d_in].reshape(rows, seq, nh, hp)
    bm = conv[..., d_in:d_in + n]
    cm = conv[..., d_in + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])                 # (b, s, h)
    a = -jnp.exp(w["A_log"])                                # (h,)
    cum = jnp.cumsum(dt * a, axis=1)                        # (b, s, h)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    seg = cum[:, :, None, :] - cum[:, None, :, :]           # (b, t, s, h)
    decay = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
    cb = jnp.einsum("btn,bsn->bts", cm, bm, precision=jax.lax.Precision.HIGHEST)
    mat = cb[..., None] * decay * dt[:, None, :, :]         # (b, t, s, h)
    y = jnp.einsum("btsh,bshp->bthp", mat, xs, precision=jax.lax.Precision.HIGHEST)
    y = y + w["D"][None, None, :, None] * xs
    y = y.reshape(rows, seq, d_in) * jax.nn.silu(z)
    y = rms_norm(y, w["norm_scale"])
    return mm("bse,ed->bsd", y, w["out_proj"])


def loss_sum(params: Dict, tokens: jax.Array, labels: jax.Array, model: Dict,
             mm: Matmul):
    """(sum of next-token cross entropies, token count) over these rows."""
    x = mm.round(params["embed"][tokens])
    layer = jax.checkpoint(
        lambda x, w: x + ssm_block(rms_norm(x, w["ln1"]["scale"]), w["ssm"],
                                   model, mm))

    def body(x, w):
        return layer(x, w), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = rms_norm(x, params["final_norm"]["scale"])
    logits = mm("bsd,vd->bsv", x, params["embed"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - ll), jnp.asarray(labels.size, F32)
