"""Attention: GQA, sliding-window, flash-style chunked softmax, KV-cache decode.

Training/prefill attention is a pure-JAX blockwise (flash-style) online
softmax: O(block^2) live memory instead of O(seq^2), which is what lets the
32k-prefill and 4k-train shapes fit per-device HBM at compile time.  The
Pallas kernel in ``repro.kernels.swa_attention`` implements the same
computation for the TPU hot path; this module is also its oracle's basis.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import apply_rope, dense_init
from repro.parallel.constraints import BATCH, MODEL, constrain

NEG_INF = -1e30


def init_attention(key, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, dtype=jnp.float32) -> Dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d_model, num_heads, head_dim), dtype=dtype),
        "wk": dense_init(kk, (d_model, num_kv_heads, head_dim), dtype=dtype),
        "wv": dense_init(kv, (d_model, num_kv_heads, head_dim), dtype=dtype),
        "wo": dense_init(ko, (num_heads, head_dim, d_model),
                         scale=0.02 / math.sqrt(2.0), dtype=dtype),
    }


def _repeat_kv(k: jax.Array, num_heads: int) -> jax.Array:
    """(B, S, KVH, D) -> (B, S, H, D) by repeating kv heads (GQA)."""
    kvh = k.shape[-2]
    if kvh == num_heads:
        return k
    rep = num_heads // kvh
    return jnp.repeat(k, rep, axis=-2)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        *, causal: bool = True, window: int = 0,
                        q_offset: int = 0, block_q: int = 512,
                        block_kv: int = 512) -> jax.Array:
    """Flash-style online-softmax attention.

    q: (B, Sq, H, D); k, v: (B, Skv, H, D) (kv already head-repeated).
    window: 0 = full; >0 = sliding window (query i attends to keys in
    (i - window, i]).  q_offset: absolute position of q[0] relative to k[0]
    (for cross/prefill-continuation use).
    Returns (B, Sq, H, D).
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    scale = 1.0 / math.sqrt(d)

    # few-head models (heads % model-axis != 0) fall back to sequence-
    # parallel attention over query blocks; pick block_q so the number of
    # q blocks matches the model axis exactly (whisper 8H, granite-moe 24H)
    from repro.parallel.constraints import current_mesh
    _mesh = current_mesh()
    _msize = dict(zip(_mesh.axis_names, _mesh.devices.shape)).get("model", 1) \
        if _mesh is not None else 1
    # (only for LONG sequences: under AD/remat the scan-over-sharded-blocks
    # re-gathers — measured a net loss at train_4k, a 52x win at 32k prefill)
    if _msize > 1 and h % _msize != 0 and h < _msize:
        nq0 = -(-sq // block_q)
        if nq0 % _msize != 0 and sq % _msize == 0 and sq // _msize >= 1024:
            block_q = sq // _msize

    # pad to block multiples
    pq = (-sq) % block_q
    pkv = (-skv) % block_kv
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))
    nq, nkv = qp.shape[1] // block_q, kp.shape[1] // block_kv

    # (nq, B, H, bq, D) etc. — pin batch/head sharding through the reshapes
    qb = qp.reshape(b, nq, block_q, h, d).transpose(1, 0, 3, 2, 4) * scale
    kb = kp.reshape(b, nkv, block_kv, h, d).transpose(1, 0, 3, 2, 4)
    vb = vp.reshape(b, nkv, block_kv, h, d).transpose(1, 0, 3, 2, 4)
    from repro.parallel.constraints import current_mesh
    mesh = current_mesh()
    msize = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1) \
        if mesh is not None else 1
    if msize > 1 and h % msize != 0 and nq % msize == 0:
        # few-head models (whisper: 8 heads < 16 shards): sequence-parallel
        # attention — shard QUERY BLOCKS over "model"; each shard scans the
        # full kv for its query blocks.
        qb = constrain(qb, MODEL, BATCH, None, None, None)
        kb = constrain(kb, None, BATCH, None, None, None)
        vb = constrain(vb, None, BATCH, None, None, None)
    else:
        qb = constrain(qb, None, BATCH, MODEL, None, None)
        kb = constrain(kb, None, BATCH, MODEL, None, None)
        vb = constrain(vb, None, BATCH, MODEL, None, None)

    q_pos = jnp.arange(nq * block_q).reshape(nq, block_q) + q_offset
    kv_pos = jnp.arange(nkv * block_kv).reshape(nkv, block_kv)
    kv_valid = kv_pos < skv

    def q_block(carry, xs):
        qi, qpos = xs  # (B,H,bq,D), (bq,)

        def kv_block(acc, ys):
            m, l, o = acc
            ki, vi, kpos, kval = ys
            s = jnp.einsum("bhqd,bhkd->bhqk", qi, ki,
                           preferred_element_type=jnp.float32)
            # additive (bq, bkv) bias instead of a full (b,h,bq,bkv) select:
            # one broadcastable small operand instead of score-sized pred +
            # two score-sized select operands (memory-roofline lever)
            mask = kval[None, :]
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            if window:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            bias = jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)
            s = s + bias[None, None]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            o_new = o * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vi.dtype), vi,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, o_new), None

        init = (constrain(jnp.full((b, h, block_q), NEG_INF, jnp.float32),
                          BATCH, MODEL, None),
                constrain(jnp.zeros((b, h, block_q), jnp.float32),
                          BATCH, MODEL, None),
                constrain(jnp.zeros((b, h, block_q, d), jnp.float32),
                          BATCH, MODEL, None, None))
        (m, l, o), _ = jax.lax.scan(kv_block, init, (kb, vb, kv_pos, kv_valid))
        o = o / jnp.maximum(l, 1e-30)[..., None]
        return carry, o.astype(q.dtype)

    _, ob = jax.lax.scan(q_block, None, (qb, q_pos))
    out = ob.transpose(1, 0, 3, 2, 4).reshape(b, nq * block_q, h, d)
    return out[:, :sq]


def _to_blocks(x: jax.Array, blk: int) -> jax.Array:
    """(B, S, H, D) -> (S/blk, B, H, blk, D), padding S to a multiple."""
    b, s, h, d = x.shape
    x = jnp.pad(x, ((0, 0), (0, (-s) % blk), (0, 0), (0, 0)))
    return x.reshape(b, -1, blk, h, d).transpose(1, 0, 3, 2, 4)


def _from_blocks(x: jax.Array, s: int) -> jax.Array:
    n, b, h, blk, d = x.shape
    return x.transpose(1, 0, 3, 2, 4).reshape(b, n * blk, h, d)[:, :s]


def _score_bias(qpos, kpos, skv: int, causal: bool) -> jax.Array:
    mask = kpos[None, :] < skv
    if causal:
        mask = mask & (kpos[None, :] <= qpos[:, None])
    return jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block: int = 512) -> jax.Array:
    """Online-softmax attention whose backward pass recomputes each block's
    scores from the saved log-sum-exp (FlashAttention, arXiv:2205.14135)
    instead of keeping them: memory O(S * D), not O(S^2), through
    autodiff.  q, k, v: (B, S, H, D), kv heads already repeated; every
    block of keys is visited, masked where causal."""
    return _flash_fwd(q, k, v, causal, block)[0]


def _flash_blocks(q, k, v, block):
    """Scaled query blocks, key and value blocks, and their positions."""
    bq, bk = min(block, q.shape[1]), min(block, k.shape[1])
    qb = _to_blocks(q, bq) * (1.0 / math.sqrt(q.shape[-1]))
    kb, vb = _to_blocks(k, bk), _to_blocks(v, bk)
    q_pos = jnp.arange(qb.shape[0] * bq).reshape(-1, bq)
    kv_pos = jnp.arange(kb.shape[0] * bk).reshape(-1, bk)
    return qb, kb, vb, q_pos, kv_pos


def _flash_fwd(q, k, v, causal, block):
    s, skv = q.shape[1], k.shape[1]
    qb, kb, vb, q_pos, kv_pos = _flash_blocks(q, k, v, block)

    def q_block(_, xs):
        qi, qpos = xs

        def kv_block(acc, ys):
            m, l, o = acc
            kj, vj, kpos = ys
            sc = jnp.einsum("bhqd,bhkd->bhqk", qi, kj,
                            preferred_element_type=jnp.float32)
            sc = sc + _score_bias(qpos, kpos, skv, causal)[None, None]
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            o = o * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32)
            return (m_new, l * corr + jnp.sum(p, axis=-1), o), None

        shape = qi.shape[:-1]
        init = (jnp.full(shape, NEG_INF, jnp.float32),
                jnp.zeros(shape, jnp.float32),
                jnp.zeros(qi.shape, jnp.float32))
        (m, l, o), _ = jax.lax.scan(kv_block, init, (kb, vb, kv_pos))
        return None, ((o / l[..., None]).astype(q.dtype), m + jnp.log(l))

    _, (ob, lse) = jax.lax.scan(q_block, None, (qb, q_pos))
    out = _from_blocks(ob, s)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block, res, g):
    q, k, v, out, lse = res
    s, skv = q.shape[1], k.shape[1]
    qb, kb, vb, q_pos, kv_pos = _flash_blocks(q, k, v, block)
    bq = qb.shape[-2]
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    gb = _to_blocks(g, bq)
    db = _to_blocks(delta[..., None], bq)[..., 0]

    def kv_block(dq, ys):
        kj, vj, kpos = ys

        def q_block(acc, xs):
            dk, dv = acc
            qi, gi, li, di, qpos = xs
            sc = jnp.einsum("bhqd,bhkd->bhqk", qi, kj,
                            preferred_element_type=jnp.float32)
            sc = sc + _score_bias(qpos, kpos, skv, causal)[None, None]
            p = jnp.exp(sc - li[..., None])
            dv = dv + jnp.einsum("bhqk,bhqd->bhkd", p.astype(gi.dtype), gi,
                                 preferred_element_type=jnp.float32)
            dp = jnp.einsum("bhqd,bhkd->bhqk", gi, vj,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - di[..., None])
            dk = dk + jnp.einsum("bhqk,bhqd->bhkd", ds.astype(qi.dtype), qi,
                                 preferred_element_type=jnp.float32)
            dqi = jnp.einsum("bhqk,bhkd->bhqd", ds.astype(kj.dtype), kj,
                             preferred_element_type=jnp.float32)
            return (dk, dv), dqi

        zero = jnp.zeros(kj.shape, jnp.float32)
        (dk, dv), dqs = jax.lax.scan(q_block, (zero, zero),
                                     (qb, gb, lse, db, q_pos))
        return dq + dqs, (dk, dv)

    dq, (dkb, dvb) = jax.lax.scan(
        kv_block, jnp.zeros(qb.shape, jnp.float32), (kb, vb, kv_pos))
    return (_from_blocks(dq * (1.0 / math.sqrt(q.shape[-1])), s).astype(q.dtype),
            _from_blocks(dkb, skv).astype(k.dtype),
            _from_blocks(dvb, skv).astype(v.dtype))


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def attention_forward(params: Dict, x: jax.Array, *, num_heads: int,
                      num_kv_heads: int, rope_theta: float, window: int = 0,
                      positions: Optional[jax.Array] = None,
                      kv: Optional[jax.Array] = None,
                      causal: bool = True, recompute: bool = False) -> jax.Array:
    """Full attention layer (projections + blockwise core).

    kv: optional cross-attention source (B, Skv, d_model); None = self-attn.
    recompute: the core is ``flash_attention``, whose backward recomputes
    the scores (long sequences: no (S, S) residuals); full windows only.
    """
    b, s, _ = x.shape
    src = x if kv is None else kv
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", src, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", src, params["wv"].astype(x.dtype))
    q = constrain(q, BATCH, None, MODEL, None)
    if kv is None and rope_theta > 0:
        if positions is None:
            positions = jnp.arange(s)[None, :]
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    k = constrain(_repeat_kv(k, num_heads), BATCH, None, MODEL, None)
    v = constrain(_repeat_kv(v, num_heads), BATCH, None, MODEL, None)
    # heads not divisible by the model axis (granite-moe: 24H on 16 shards)
    # replicate attention 16x; pad with zero heads to the next multiple —
    # exact (zero v => zero output; sliced off below) and fully sharded
    from repro.parallel.constraints import current_mesh as _cm
    _mesh = _cm()
    _msz = dict(zip(_mesh.axis_names, _mesh.devices.shape)).get("model", 1) \
        if _mesh is not None else 1
    nh = q.shape[2]
    hpad = ((-nh) % _msz) if (_msz > 1 and nh >= _msz) else 0
    if hpad:
        padh = ((0, 0), (0, 0), (0, hpad), (0, 0))
        q = constrain(jnp.pad(q, padh), BATCH, None, MODEL, None)
        k = constrain(jnp.pad(k, padh), BATCH, None, MODEL, None)
        v = constrain(jnp.pad(v, padh), BATCH, None, MODEL, None)
    if recompute:
        assert not window, "flash_attention has no window"
        o = flash_attention(q, k, v, causal and kv is None)
    else:
        o = blockwise_attention(q, k, v, causal=causal and kv is None,
                                window=window)
    if hpad:
        o = o[:, :, :nh]
    o = constrain(o, BATCH, None, MODEL, None)
    return constrain(
        jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype)),
        BATCH, None, None)


# ---------------------------------------------------------------------------
# Decode path (KV cache)
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, cache_len: int, num_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16) -> Dict:
    return {
        "k": jnp.zeros((batch, cache_len, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, num_kv_heads, head_dim), dtype),
    }


def decode_attention(params: Dict, x: jax.Array, cache: Dict, pos: jax.Array,
                     *, num_heads: int, num_kv_heads: int, rope_theta: float,
                     window: int = 0) -> Tuple[jax.Array, Dict]:
    """One-token decode: x (B, 1, d_model), cache holds cache_len positions.

    For sliding-window models the cache is a ring buffer of size window;
    ``pos`` is the absolute position of the new token.
    Returns (out (B,1,d_model), updated cache).
    """
    b, one, _ = x.shape
    assert one == 1
    cache_len = cache["k"].shape[1]
    # decode sharding scheme: batch over data, CACHE LENGTH over model
    # (GQA kv heads are too few to shard 16-way); heads stay replicated and
    # the softmax reduces over model-sharded cache segments.
    q = constrain(jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype)),
                  BATCH, None, None, None)
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if rope_theta > 0:
        p = jnp.full((b, 1), pos)
        q = apply_rope(q, p, rope_theta)
        k = apply_rope(k, p, rope_theta)

    slot = (pos % cache_len) if window else jnp.minimum(pos, cache_len - 1)
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))

    kk = constrain(_repeat_kv(ck.astype(x.dtype), num_heads),
                   BATCH, MODEL, None, None)
    vv = constrain(_repeat_kv(cv.astype(x.dtype), num_heads),
                   BATCH, MODEL, None, None)
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bshk,bthk->bhst", q * scale, kk,
                   preferred_element_type=jnp.float32)  # (B,H,1,cache)
    s = constrain(s, BATCH, None, None, MODEL)
    idx = jnp.arange(cache_len)
    if window:
        # ring buffer: valid slots are those written within the last `window`
        # absolute positions <= pos.
        age = (slot - idx) % cache_len
        valid = (age < jnp.minimum(pos + 1, cache_len))
    else:
        valid = idx <= pos
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhst,bthk->bshk", p, vv)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))
    return out, {"k": ck, "v": cv}


def init_cross_cache(params: Dict, kv_src: jax.Array, *, num_kv_heads: int) -> Dict:
    """Precompute cross-attention K/V from encoder/vision embeddings."""
    k = jnp.einsum("bsd,dhk->bshk", kv_src, params["wk"].astype(kv_src.dtype))
    v = jnp.einsum("bsd,dhk->bshk", kv_src, params["wv"].astype(kv_src.dtype))
    return {"k": k, "v": v}


def decode_cross_attention(params: Dict, x: jax.Array, cross: Dict,
                           *, num_heads: int) -> jax.Array:
    """Cross-attn for decode: full (non-causal) attention over cached cross K/V."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    kk = _repeat_kv(cross["k"].astype(x.dtype), num_heads)
    vv = _repeat_kv(cross["v"].astype(x.dtype), num_heads)
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bshk,bthk->bhst", q * scale, kk,
                   preferred_element_type=jnp.float32)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhst,bthk->bshk", p, vv)
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))
