"""Plain float32 reference of OLMo (arXiv:2402.00838) as the benchmark
configures it.

Decoder-only transformer: embedding tied to the output head; per layer a
layer norm without scale or bias, multi-head causal self-attention with
rotary position embedding (rotate-half, theta from the configuration) and
no biases, a residual, a second such norm, a SwiGLU MLP
(``W_o (silu(x W_g) * x W_i)``) and a residual; a final norm; cross
entropy of the next token, averaged over every token of the batch.

Weights come from the seed by the recipe the configuration states: key
``PRNGKey(seed)`` split 8 ways; the embedding is 0.02-normal from key 0;
the layers' keys are key 2 split per layer, each split into an attention
key (split 4: q, k, v, o) and an MLP key (split 3: i, g, o); projections are
0.02-normal, output projections 0.02/sqrt(2)-normal.  Everything is
float32; matmuls run at ``Precision.HIGHEST`` (or in the control's lower
precision, ``common.Matmul``).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.references.common import F32, Matmul


def init_params(model: Dict, seed: int) -> Dict:
    d, h, f, v = (model["d_model"], model["num_heads"], model["d_ff"],
                  model["vocab_size"])
    hd = d // h
    out_scale = 0.02 / math.sqrt(2.0)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    def normal(key, shape, scale=0.02):
        return scale * jax.random.normal(key, shape, F32)

    def layer(key):
        ka, km = jax.random.split(key)
        kq, kk, kv, ko = jax.random.split(ka, 4)
        k1, k2, k3 = jax.random.split(km, 3)
        return {
            "attn": {"wq": normal(kq, (d, h, hd)), "wk": normal(kk, (d, h, hd)),
                     "wv": normal(kv, (d, h, hd)),
                     "wo": normal(ko, (h, hd, d), out_scale)},
            "mlp": {"wi": normal(k1, (d, f)), "wg": normal(k2, (d, f)),
                    "wo": normal(k3, (f, d), out_scale)},
        }

    init = jax.jit(lambda: {
        "embed": normal(ks[0], (v, d)),
        "blocks": jax.vmap(layer)(jax.random.split(ks[2], model["num_layers"])),
    })
    return init()


def layer_norm(x: jax.Array, eps: float = 1e-5) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (rows, seq, heads, head_dim); rotate-half rotary embedding."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer_forward(x: jax.Array, w: Dict, model: Dict, mm: Matmul) -> jax.Array:
    s = x.shape[1]
    hd = model["d_model"] // model["num_heads"]
    h = layer_norm(x)
    q = rope(mm("bsd,dhk->bshk", h, w["attn"]["wq"]), model["rope_theta"])
    k = rope(mm("bsd,dhk->bshk", h, w["attn"]["wk"]), model["rope_theta"])
    v = mm("bsd,dhk->bshk", h, w["attn"]["wv"])
    scores = mm("bqhk,bshk->bhqs", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = mm("bhqs,bshk->bqhk", p, v)
    x = x + mm("bshk,hkd->bsd", o, w["attn"]["wo"])
    h = layer_norm(x)
    a = mm("bsd,df->bsf", h, w["mlp"]["wi"])
    g = mm("bsd,df->bsf", h, w["mlp"]["wg"])
    return x + mm("bsf,fd->bsd", jax.nn.silu(g) * a, w["mlp"]["wo"])


def loss_sum(params: Dict, tokens: jax.Array, labels: jax.Array, model: Dict,
             mm: Matmul):
    """(sum of next-token cross entropies, token count) over these rows."""
    x = mm.round(params["embed"][tokens])

    layer = jax.checkpoint(lambda x, w: layer_forward(x, w, model, mm))

    def body(x, w):
        return layer(x, w), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = layer_norm(x)
    logits = mm("bsd,vd->bsv", x, params["embed"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - ll), jnp.asarray(labels.size, F32)

