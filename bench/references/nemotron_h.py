"""Plain float32 reference of Nemotron-H (NVIDIA-Nemotron-3-Nano-30B-A3B,
arXiv:2504.03624) as the benchmark configures it.

Blocks follow ``layer_pattern``; each is ``x + mixer(RMSNorm(x))`` with
one mixer; then a final RMSNorm, an untied head and next-token cross
entropy averaged over every token of the batch.  RMSNorm eps is 1e-5.

- ``M``, Mamba-2: ``in_proj`` gives ``[z, x, B, C, dt]`` with G groups of
  B and C (head h reads group h // (H / G)); a causal depthwise
  convolution of width ``conv_width`` with bias, then SiLU, over
  ``[x, B, C]``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``.
  The SSM is computed in its quadratic (dual) form, the masked matrix of
  arXiv:2405.21060 section 3, one block of queries at a time against every
  key: ``y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s``
  plus ``D x_t``.  Then ``y * silu(z)``, an RMSNorm over each group of
  ``d_inner / G`` channels with scale, and ``out_proj``.
- ``E``, MoE: router logits over all ``num_experts``; sigmoid scores; the
  top ``top_k`` are chosen (a correction bias of zero added for the choice);
  their weights are the scores over the chosen scores' sum, times
  ``routed_scale``.  Each held expert (experts 0 .. held - 1) is computed
  densely over every token, ``down(relu(up(x))^2)``, times its weight (0
  where not chosen); plus a shared ReLU^2 expert on every token.
- ``*``, attention: grouped-query, causal, no bias, no positional
  embedding; softmax over one block of queries at a time.

Weights come from the seed by the program's recipe: key ``PRNGKey(seed)``
split 8 ways; the embedding 0.02-normal from key 0, the head from key 1;
key 2 split 4 ways, one per mixer kind (mamba, moe, attn, mlp), each split
once per block of that kind.  A Mamba-2 block's key splits 4 ways:
``in_proj`` 0.02-normal (0), ``conv_w`` 0.1-normal (1), ``out_proj``
0.02-normal (2); ``conv_b`` 0, ``A_log`` 0, ``D`` 1, ``dt_bias`` -2.  An
MoE block's key splits 3 ways (router, experts, shared): router 0.02-normal;
the experts' key 3 ways, ``wi`` from 0 and ``wo`` from 2 at 0.02 / sqrt(2);
the shared expert's 3 ways, ``wi`` from 0 and ``wo`` from 2 at
0.02 / sqrt(2).  An attention block's key splits 4 ways (q, k, v, o), o at
0.02 / sqrt(2).  Norm scales 1.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.references.common import F32, HIGHEST, Matmul

EPS = 1e-5
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
QUERY_BLOCK = 256     # queries whose scores are formed at once


def _normal(key, shape, scale=0.02):
    return scale * jax.random.normal(key, shape, F32)


def _ssm_dims(model: Dict):
    s = model["ssm"]
    h, p, g, n = s["num_heads"], s["head_dim"], s["n_groups"], s["state_dim"]
    return h, p, g, n, h * p, s["conv_width"]


def init_params(model: Dict, seed: int) -> Dict:
    d, v, f = model["d_model"], model["vocab_size"], model["d_ff"]
    h, p, g, n, d_in, cw = _ssm_dims(model)
    moe = model["moe"]
    held = moe.get("num_held") or moe["num_experts"]
    nh, kvh, hd = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    out_scale = 0.02 / math.sqrt(2.0)
    conv_ch = d_in + 2 * g * n
    ones = {"scale": jnp.ones((d,), F32)}

    def mamba(key):
        k1, k2, k3, _ = jax.random.split(key, 4)
        return {"ln1": ones, "ssm": {
            "in_proj": _normal(k1, (d, 2 * d_in + 2 * g * n + h)),
            "conv_w": _normal(k2, (cw, conv_ch), 0.1),
            "conv_b": jnp.zeros((conv_ch,), F32),
            "A_log": jnp.zeros((h,), F32),
            "D": jnp.ones((h,), F32),
            "dt_bias": jnp.full((h,), -2.0, F32),
            "norm_scale": jnp.ones((d_in,), F32),
            "out_proj": _normal(k3, (d_in, d)),
        }}

    def moe_block(key):
        kr, ke, ks = jax.random.split(key, 3)
        e0, _, e2 = jax.random.split(ke, 3)
        s0, _, s2 = jax.random.split(ks, 3)
        sf = moe["shared_expert_ff"]
        return {"ln1": ones, "moe": {
            "router": _normal(kr, (d, moe["num_experts"])),
            "wi": _normal(e0, (held, d, f)),
            "wo": _normal(e2, (held, f, d), out_scale),
            "shared": {"wi": _normal(s0, (d, sf)),
                       "wo": _normal(s2, (sf, d), out_scale)},
        }}

    def attn(key):
        kq, kk, kv, ko = jax.random.split(key, 4)
        return {"ln1": ones, "attn": {
            "wq": _normal(kq, (d, nh, hd)), "wk": _normal(kk, (d, kvh, hd)),
            "wv": _normal(kv, (d, kvh, hd)),
            "wo": _normal(ko, (nh, hd, d), out_scale)}}

    init_block = {"mamba": mamba, "moe": moe_block, "attn": attn}
    pattern = model["layer_pattern"]
    assert set(pattern) <= set(KINDS), pattern

    def init():
        ks = jax.random.split(jax.random.PRNGKey(seed), 8)
        kinds = jax.random.split(ks[2], 4)
        blocks = {}
        for i, (letter, kind) in enumerate(KINDS.items()):
            if pattern.count(letter):
                keys = jax.random.split(kinds[i], pattern.count(letter))
                blocks[kind] = jax.vmap(init_block[kind])(keys)
        return {"embed": _normal(ks[0], (v, d)), "head": _normal(ks[1], (d, v)),
                "final_norm": {"scale": jnp.ones((d,), F32)}, "blocks": blocks}

    return jax.jit(init)()


def rms_norm(x: jax.Array, scale: jax.Array) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + EPS) * scale


def _query_blocks(fn, length: int):
    """Run ``fn(start)`` for each block of ``QUERY_BLOCK`` query positions,
    recomputed in the backward pass; outputs concatenated on axis 1."""
    qb = min(QUERY_BLOCK, length)
    assert length % qb == 0, (length, qb)
    starts = jnp.arange(0, length, qb)
    out = jax.lax.map(jax.checkpoint(fn), starts)     # (nq, rows, qb, ...)
    return jnp.moveaxis(out, 0, 1).reshape((out.shape[1], length) + out.shape[3:])


def mamba_block(x: jax.Array, w: Dict, model: Dict, mm: Matmul) -> jax.Array:
    """x: (rows, seq, d_model) -> (rows, seq, d_model)."""
    h, p, g, n, d_in, cw = _ssm_dims(model)
    rows, seq, _ = x.shape
    r = h // g
    proj = mm("bsd,de->bse", x, w["in_proj"])
    z = proj[..., :d_in]
    xbc = proj[..., d_in:2 * d_in + 2 * g * n]
    dt = proj[..., 2 * d_in + 2 * g * n:]
    padded = jnp.pad(xbc, ((0, 0), (cw - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + seq] * w["conv_w"][i] for i in range(cw))
    conv = jax.nn.silu(conv + w["conv_b"])
    xs = conv[..., :d_in].reshape(rows, seq, g, r, p)
    bm = conv[..., d_in:d_in + g * n].reshape(rows, seq, g, n)
    cm = conv[..., d_in + g * n:].reshape(rows, seq, g, n)
    dt = jax.nn.softplus(dt + w["dt_bias"]).reshape(rows, seq, g, r)
    a = -jnp.exp(w["A_log"]).reshape(g, r)
    cum = jnp.cumsum(dt * a, axis=1)                        # (b, s, g, r)
    pos = jnp.arange(seq)
    qb = min(QUERY_BLOCK, seq)

    def queries(t0):
        sl = lambda v: jax.lax.dynamic_slice_in_dim(v, t0, qb, axis=1)
        causal = (t0 + jnp.arange(qb))[:, None] >= pos[None, :]   # (t, s)
        seg = sl(cum)[:, :, None] - cum[:, None]            # (b, t, s, g, r)
        decay = jnp.exp(jnp.where(causal[None, :, :, None, None], seg, -jnp.inf))
        cb = jnp.einsum("btgn,bsgn->btsg", sl(cm), bm, precision=HIGHEST)
        mat = cb[..., None] * decay * dt[:, None]           # (b, t, s, g, r)
        return jnp.einsum("btsgr,bsgrp->btgrp", mat, xs, precision=HIGHEST)

    y = _query_blocks(queries, seq)
    y = y + w["D"].reshape(g, r)[None, None, :, :, None] * xs
    y = y.reshape(rows, seq, d_in) * jax.nn.silu(z)
    y = rms_norm(y.reshape(rows, seq, g, d_in // g),
                 w["norm_scale"].reshape(g, d_in // g)).reshape(rows, seq, d_in)
    return mm("bse,ed->bsd", y, w["out_proj"])


def moe_block(x: jax.Array, w: Dict, model: Dict, mm: Matmul) -> jax.Array:
    moe = model["moe"]
    held = w["wi"].shape[0]
    scores = jax.nn.sigmoid(mm("bsd,de->bse", x, w["router"]))
    top, idx = jax.lax.top_k(scores, moe["top_k"])
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) * moe["routed_scale"]
    # each held expert's weight per token: 0 where the router did not pick it
    weight = jnp.sum(jnp.where(idx[..., None] == jnp.arange(held), top[..., None],
                               0.0), axis=-2)               # (b, s, held)
    up = jnp.square(jax.nn.relu(mm("bsd,edf->bsef", x, w["wi"])))
    out = mm("bsef,efd->bsd", up * weight[..., None], w["wo"])
    sh = jnp.square(jax.nn.relu(mm("bsd,df->bsf", x, w["shared"]["wi"])))
    return out + mm("bsf,fd->bsd", sh, w["shared"]["wo"])


def attn_block(x: jax.Array, w: Dict, model: Dict, mm: Matmul) -> jax.Array:
    rows, seq, _ = x.shape
    nh, kvh, hd = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    q = mm("bsd,dhk->bshk", x, w["wq"]).reshape(rows, seq, kvh, nh // kvh, hd)
    k = mm("bsd,dhk->bshk", x, w["wk"])
    v = mm("bsd,dhk->bshk", x, w["wv"])
    pos = jnp.arange(seq)
    qb = min(QUERY_BLOCK, seq)

    def queries(t0):
        qi = jax.lax.dynamic_slice_in_dim(q, t0, qb, axis=1)
        scores = mm("bqgrk,bsgk->bgrqs", qi, k) / math.sqrt(hd)
        causal = (t0 + jnp.arange(qb))[:, None] >= pos[None, :]
        scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
        return mm("bgrqs,bsgk->bqgrk", jax.nn.softmax(scores, axis=-1), v)

    o = _query_blocks(queries, seq).reshape(rows, seq, nh, hd)
    return mm("bshk,hkd->bsd", o, w["wo"])


MIXERS = {"mamba": ("ssm", mamba_block), "moe": ("moe", moe_block),
          "attn": ("attn", attn_block)}


def loss_sum(params: Dict, tokens: jax.Array, labels: jax.Array, model: Dict,
             mm: Matmul):
    """(sum of next-token cross entropies, token count) over these rows."""
    x = mm.round(params["embed"][tokens])
    seen = dict.fromkeys(KINDS.values(), 0)
    for letter in model["layer_pattern"]:
        kind = KINDS[letter]
        name, mixer = MIXERS[kind]
        i = seen[kind]
        seen[kind] += 1

        def block(x, stack, i=i, name=name, mixer=mixer):
            w = jax.tree_util.tree_map(lambda a: a[i], stack)
            return x + mixer(rms_norm(x, w["ln1"]["scale"]), w[name], model, mm)

        x = jax.checkpoint(block)(x, params["blocks"][kind])
    x = rms_norm(x, params["final_norm"]["scale"])
    logits = mm("bsd,dv->bsv", x, params["head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - ll), jnp.asarray(labels.size, F32)
