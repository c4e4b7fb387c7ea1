"""Generic model assembly for all assigned architecture families.

One parameter/function pair covers dense, MoE, SSM, hybrid, audio (enc-dec),
VLM and layer-pattern families, driven entirely by ``ModelConfig``:

- ``init_params``     — parameter pytree (layers stacked for lax.scan)
- ``model_forward``   — training forward -> (loss, metrics)
- ``prefill_fn``      — prompt processing -> (last logits, decode state)
- ``decode_step_fn``  — one-token decode with KV/SSM caches
- ``init_decode_state`` — cache allocation (shape source for dry-runs)

Layers are scanned with stacked weights (small HLO, fast compiles, remat
per block).  Heterogeneous extras (zamba2 shared attention block, VLM
cross-attention every k layers) use GROUP SCANS — an outer scan over
groups of ``every`` layers with the extra block applied once per group —
rather than ``lax.cond``, so the lowered HLO has no conditionals on the
hot path (exact roofline accounting, cheaper compile).  A ``pattern`` model
(Nemotron-H: unlike single-mixer blocks in a fixed order) walks its
pattern in Python over parameters stacked per mixer kind; it trains, and
does not serve.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import mlp as mlp_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.common import apply_norm, dense_init, norm_param, rmsnorm
from repro.parallel.constraints import BATCH, MODEL, constrain

LOSS_CHUNK = 128   # sequence chunk for the memory-bounded CE loss


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_attn_block(cfg: ModelConfig, key, dtype) -> Dict:
    ka, km = jax.random.split(key)
    hd = cfg.resolved_head_dim()
    return {
        "ln1": norm_param(cfg.norm, cfg.d_model, dtype),
        "attn": attn_lib.init_attention(ka, cfg.d_model, cfg.num_heads,
                                        cfg.num_kv_heads, hd, dtype),
        "ln2": norm_param(cfg.norm, cfg.d_model, dtype),
        "mlp": mlp_lib.init_mlp(km, cfg.d_model, cfg.d_ff, cfg.mlp, dtype),
    }


def _init_moe_block(cfg: ModelConfig, key, dtype) -> Dict:
    ka, km = jax.random.split(key)
    hd = cfg.resolved_head_dim()
    return {
        "ln1": norm_param(cfg.norm, cfg.d_model, dtype),
        "attn": attn_lib.init_attention(ka, cfg.d_model, cfg.num_heads,
                                        cfg.num_kv_heads, hd, dtype),
        "ln2": norm_param(cfg.norm, cfg.d_model, dtype),
        "moe": moe_lib.init_moe(km, cfg.d_model, cfg.d_ff, cfg.mlp, cfg.moe, dtype),
    }


def _init_ssm_block(cfg: ModelConfig, key, dtype) -> Dict:
    return {
        "ln1": norm_param(cfg.norm, cfg.d_model, dtype),
        "ssm": ssm_lib.init_ssm(key, cfg.d_model, cfg.ssm, dtype),
    }


def _init_cross_block(cfg: ModelConfig, key, dtype) -> Dict:
    hd = cfg.resolved_head_dim()
    return {
        "ln": norm_param(cfg.norm, cfg.d_model, dtype),
        "attn": attn_lib.init_attention(key, cfg.d_model, cfg.num_heads,
                                        cfg.num_kv_heads, hd, dtype),
        "gate": jnp.zeros((), jnp.float32),   # zero-init cross-attn gate
    }


def _stack_init(fn, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(fn)(keys)


# the mixer of each ``layer_pattern`` letter, which names its blocks' stack
PATTERN_KINDS = {"M": "mamba", "E": "moe", "*": "attn", "-": "mlp"}


def _init_mixer_block(cfg: ModelConfig, kind: str, key, dtype) -> Dict:
    """A pattern model's block: its pre-norm ``ln1`` and one mixer."""
    block = {"ln1": norm_param(cfg.norm, cfg.d_model, dtype)}
    if kind == "mamba":
        block["ssm"] = ssm_lib.init_ssm(key, cfg.d_model, cfg.ssm, dtype)
    elif kind == "moe":
        block["moe"] = moe_lib.init_moe(key, cfg.d_model, cfg.d_ff, cfg.mlp,
                                        cfg.moe, dtype)
    elif kind == "attn":
        block["attn"] = attn_lib.init_attention(
            key, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim(), dtype)
    else:
        block["mlp"] = mlp_lib.init_mlp(key, cfg.d_model, cfg.d_ff, cfg.mlp,
                                        dtype)
    return block


def _init_pattern(cfg: ModelConfig, key, dtype) -> Dict:
    """Blocks stacked per mixer kind, in pattern order within a kind: few
    and large leaves.  ``key`` splits 4 ways, one per kind in
    ``PATTERN_KINDS`` order; a kind's key splits once per block of it."""
    keys = jax.random.split(key, len(PATTERN_KINDS))
    out = {}
    for (letter, kind), k in zip(PATTERN_KINDS.items(), keys):
        n = cfg.layer_pattern.count(letter)
        if n:
            out[kind] = _stack_init(functools.partial(
                _init_mixer_block, cfg, kind, dtype=dtype), k, n)
    return out


def group_layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_groups, layers_per_group, tail_layers) for group-scan archs."""
    if cfg.arch_type == "hybrid":
        every = cfg.attn_every
    elif cfg.arch_type == "vlm":
        every = cfg.vlm.cross_attn_every
    else:
        return (0, 0, cfg.num_layers)
    n = cfg.num_layers // every
    return (n, every, cfg.num_layers - n * every)


def num_shared_attn(cfg: ModelConfig) -> int:
    return group_layout(cfg)[0] if cfg.arch_type == "hybrid" else 0


def num_cross_layers(cfg: ModelConfig) -> int:
    return group_layout(cfg)[0] if cfg.arch_type == "vlm" else 0


def _split_groups(blocks, n: int, per: int):
    """Stacked (L, ...) params -> ((n, per, ...), tail (L-n*per, ...))."""
    grouped = jax.tree_util.tree_map(
        lambda a: a[:n * per].reshape((n, per) + a.shape[1:]), blocks)
    tailb = jax.tree_util.tree_map(lambda a: a[n * per:], blocks)
    return grouped, tailb


def _merge_groups(grouped, tailt):
    flat = jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), grouped)
    return jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b], axis=0), flat, tailt)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32) -> Dict:
    """``key`` splits 8 ways: embedding 0.02-normal from key 0, the untied
    head from key 1, the blocks from key 2 (``_init_pattern`` for a pattern
    model), then the families' extras."""
    ks = jax.random.split(key, 8)
    params: Dict = {
        "embed": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype=dtype),
        "final_norm": norm_param(cfg.norm, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dtype=dtype)

    if cfg.arch_type in ("dense", "vlm", "audio"):
        block_fn = functools.partial(_init_attn_block, cfg, dtype=dtype)
    elif cfg.arch_type == "moe":
        block_fn = functools.partial(_init_moe_block, cfg, dtype=dtype)
    elif cfg.arch_type in ("ssm", "hybrid"):
        block_fn = functools.partial(_init_ssm_block, cfg, dtype=dtype)
    elif cfg.arch_type == "pattern":
        assert len(cfg.layer_pattern) == cfg.num_layers, cfg.layer_pattern
        params["blocks"] = _init_pattern(cfg, ks[2], dtype)
        return params
    else:
        raise ValueError(cfg.arch_type)
    params["blocks"] = _stack_init(block_fn, ks[2], cfg.num_layers)

    if cfg.arch_type == "hybrid":
        # zamba2: ONE shared attention block applied every attn_every layers
        params["shared_attn"] = _init_attn_block(cfg, ks[3], dtype)

    if cfg.arch_type == "vlm":
        params["cross"] = _stack_init(
            functools.partial(_init_cross_block, cfg, dtype=dtype),
            ks[4], num_cross_layers(cfg))
        params["projector"] = dense_init(
            ks[5], (cfg.vlm.image_embed_dim, cfg.d_model), dtype=dtype)

    if cfg.arch_type == "audio":
        enc = cfg.encdec
        params["encoder"] = {
            "blocks": _stack_init(
                functools.partial(_init_attn_block, cfg, dtype=dtype),
                ks[6], enc.encoder_layers),
            "final_norm": norm_param(cfg.norm, cfg.d_model, dtype),
        }
        params["cross"] = _stack_init(
            functools.partial(_init_cross_block, cfg, dtype=dtype),
            ks[7], cfg.num_layers)
    return params


# ---------------------------------------------------------------------------
# Forward building blocks
# ---------------------------------------------------------------------------

def _self_attn(cfg: ModelConfig, block: Dict, x: jax.Array) -> jax.Array:
    h = apply_norm(cfg.norm, x, block["ln1"], cfg.norm_eps)
    h = attn_lib.attention_forward(
        block["attn"], h, num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        rope_theta=cfg.rope_theta, window=cfg.sliding_window)
    return x + h


def _mlp_res(cfg: ModelConfig, block: Dict, x: jax.Array) -> jax.Array:
    h = apply_norm(cfg.norm, x, block["ln2"], cfg.norm_eps)
    return x + mlp_lib.mlp_forward(block["mlp"], h, cfg.mlp)


def _dense_block(cfg: ModelConfig, block: Dict, x: jax.Array) -> jax.Array:
    return _mlp_res(cfg, block, _self_attn(cfg, block, x))


def _moe_block(cfg: ModelConfig, block: Dict, x: jax.Array
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (x, aux loss, rows routed to each held expert)."""
    x = _self_attn(cfg, block, x)
    h = apply_norm(cfg.norm, x, block["ln2"], cfg.norm_eps)
    out, aux, rows = moe_lib.moe_forward(block["moe"], h, cfg.mlp, cfg.moe)
    return x + out, aux, rows


def _ssm_block(cfg: ModelConfig, block: Dict, x: jax.Array) -> jax.Array:
    h = apply_norm(cfg.norm, x, block["ln1"], cfg.norm_eps)
    return x + ssm_lib.ssm_forward(block["ssm"], h, cfg.ssm, cfg.norm_eps)


def _cross_block(cfg: ModelConfig, cblock: Dict, x: jax.Array,
                 kv_src: jax.Array) -> jax.Array:
    h = apply_norm(cfg.norm, x, cblock["ln"], cfg.norm_eps)
    h = attn_lib.attention_forward(
        cblock["attn"], h, num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        rope_theta=0.0, kv=kv_src, causal=False)
    gate = jnp.tanh(cblock["gate"]).astype(x.dtype) if "gate" in cblock else 1.0
    return x + gate * h


def _audio_block(cfg: ModelConfig, block: Dict, cross: Dict, x: jax.Array,
                 cross_src: jax.Array) -> jax.Array:
    h = apply_norm(cfg.norm, x, block["ln1"], cfg.norm_eps)
    h = attn_lib.attention_forward(
        block["attn"], h, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, rope_theta=cfg.rope_theta)
    x = x + h
    x = _cross_block(cfg, cross, x, cross_src)
    return _mlp_res(cfg, block, x)


def _encoder_forward(cfg: ModelConfig, params: Dict, frames: jax.Array) -> jax.Array:
    """Whisper-style bidirectional encoder over stub frame embeddings."""
    enc = params["encoder"]
    pos = jnp.arange(frames.shape[1])
    freqs = jnp.exp(-jnp.arange(0, cfg.d_model, 2) / cfg.d_model * 9.21)
    ang = pos[:, None] * freqs[None, :]
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[None]
    x = frames + pe.astype(frames.dtype)

    def body(x, block):
        h = apply_norm(cfg.norm, x, block["ln1"], cfg.norm_eps)
        h = attn_lib.attention_forward(
            block["attn"], h, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, rope_theta=0.0, causal=False)
        x = x + h
        return _mlp_res(cfg, block, x), None

    x, _ = jax.lax.scan(body, x, enc["blocks"])
    return apply_norm(cfg.norm, x, enc["final_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Training forward
# ---------------------------------------------------------------------------

def _remat_wrapper(remat, policy: str = "full"):
    if not remat:
        return lambda f: f
    if policy == "dots":
        return functools.partial(
            jax.checkpoint, policy=jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint


def _pattern_block(cfg: ModelConfig, kind: str, i: int, stack: Dict,
                   x: jax.Array):
    """Block ``i`` of ``kind``'s stack: ``x + mixer(norm(x))``; returns
    (x, aux loss, rows routed to each held expert or None)."""
    block = jax.tree_util.tree_map(lambda a: a[i], stack)
    h = apply_norm(cfg.norm, x, block["ln1"], cfg.norm_eps)
    aux, rows = jnp.zeros((), jnp.float32), None
    if kind == "mamba":
        h = ssm_lib.ssm_forward(block["ssm"], h, cfg.ssm, cfg.norm_eps)
    elif kind == "moe":
        h, aux, rows = moe_lib.moe_forward(block["moe"], h, cfg.mlp, cfg.moe)
    elif kind == "attn":
        h = attn_lib.attention_forward(
            block["attn"], h, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, rope_theta=cfg.rope_theta,
            recompute=True)
    else:
        h = mlp_lib.mlp_forward(block["mlp"], h, cfg.mlp)
    return x + h, aux, rows


def _pattern_blocks(cfg: ModelConfig, params: Dict, x: jax.Array, ckpt):
    """Walk ``layer_pattern`` in Python, one remat per block; a block takes
    its slice of its kind's stack inside the remat, so no slice of the
    parameters outlives it."""
    aux = jnp.zeros((), jnp.float32)
    rows = []
    seen = dict.fromkeys(PATTERN_KINDS.values(), 0)
    for letter in cfg.layer_pattern:
        kind = PATTERN_KINDS[letter]
        block = ckpt(functools.partial(_pattern_block, cfg, kind, seen[kind]))
        seen[kind] += 1
        x, a, r = block(params["blocks"][kind], x)
        aux = aux + a
        if r is not None:
            rows.append(r)
    return (x, aux, jnp.stack(rows)) if rows else (x, aux)


def _scan_blocks(cfg: ModelConfig, params: Dict, x: jax.Array,
                 cross_src: Optional[jax.Array], remat: bool = True,
                 remat_policy: str = "full"):
    """Run all layers; returns (hidden, aux_loss_sum) and, where the model
    has MoE blocks, a third item: the rows routed to each held expert per
    MoE block, (blocks, held) int32."""
    ckpt = _remat_wrapper(remat, remat_policy)
    aux0 = jnp.zeros((), jnp.float32)

    if cfg.arch_type in ("dense", "ssm"):
        def layer(carry, block):
            x, aux = carry
            x = (_dense_block if cfg.arch_type == "dense" else _ssm_block)(
                cfg, block, x)
            return (x, aux), None
        (x, aux), _ = jax.lax.scan(ckpt(layer), (x, aux0), params["blocks"])
        return x, aux

    if cfg.arch_type == "moe":
        def layer(carry, block):
            x, aux = carry
            x, a, rows = _moe_block(cfg, block, x)
            return (x, aux + a), rows
        (x, aux), rows = jax.lax.scan(ckpt(layer), (x, aux0), params["blocks"])
        return x, aux, rows

    if cfg.arch_type == "pattern":
        return _pattern_blocks(cfg, params, x, ckpt)

    if cfg.arch_type == "audio":
        def layer(carry, inp):
            x, aux = carry
            block, cross = inp
            x = _audio_block(cfg, block, cross, x, cross_src)
            return (x, aux), None
        (x, aux), _ = jax.lax.scan(ckpt(layer), (x, aux0),
                                   (params["blocks"], params["cross"]))
        return x, aux

    # group-scan archs
    n, per, tail = group_layout(cfg)
    grouped, tailb = _split_groups(params["blocks"], n, per)

    if cfg.arch_type == "hybrid":
        sa = params["shared_attn"]

        def group(carry, gblocks):
            x, aux = carry
            def inner(c, blk):
                return _ssm_block(cfg, blk, c), None
            x, _ = jax.lax.scan(inner, x, gblocks)
            x = _mlp_res(cfg, sa, _self_attn(cfg, sa, x))
            return (x, aux), None

        (x, aux), _ = jax.lax.scan(ckpt(group), (x, aux0), grouped)
        if tail:
            def tail_layer(carry, blk):
                x, aux = carry
                return (_ssm_block(cfg, blk, x), aux), None
            (x, aux), _ = jax.lax.scan(ckpt(tail_layer), (x, aux), tailb)
        return x, aux

    if cfg.arch_type == "vlm":
        def group(carry, inp):
            x, aux = carry
            gblocks, cross = inp
            def inner(c, blk):
                return _dense_block(cfg, blk, c), None
            x, _ = jax.lax.scan(inner, x, gblocks)
            x = _cross_block(cfg, cross, x, cross_src)
            return (x, aux), None

        (x, aux), _ = jax.lax.scan(ckpt(group), (x, aux0),
                                   (grouped, params["cross"]))
        assert tail == 0, "vlm layers must divide cross_attn_every"
        return x, aux

    raise ValueError(cfg.arch_type)


def _lm_head(cfg: ModelConfig, params: Dict) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["head"]


def chunked_cross_entropy(hidden: jax.Array, head: jax.Array,
                          labels: jax.Array, chunk: int = LOSS_CHUNK
                          ) -> Tuple[jax.Array, jax.Array]:
    """CE over sequence chunks so (B,S,V) logits are never materialized.

    labels < 0 are ignored.  Returns (sum_loss, token_count).
    """
    b, s, d = hidden.shape
    pad = (-s) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = hidden.shape[1] // chunk
    hc = hidden.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, chunk).transpose(1, 0, 2)

    def body(acc, inp):
        loss_sum, count = acc
        h, l = inp
        logits = jnp.einsum("bsd,dv->bsv", h, head.astype(h.dtype),
                            preferred_element_type=jnp.float32)
        logits = constrain(logits, BATCH, None, MODEL)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(
            logits, jnp.maximum(l, 0)[..., None], axis=-1)[..., 0]
        mask = (l >= 0).astype(jnp.float32)
        loss_sum = loss_sum + jnp.sum((lse - ll) * mask)
        count = count + jnp.sum(mask)
        return (loss_sum, count), None

    (loss_sum, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (hc, lc))
    return loss_sum, count


def model_forward(params: Dict, batch: Dict, cfg: ModelConfig,
                  remat: bool = True,
                  remat_policy: str = "full") -> Tuple[jax.Array, Dict]:
    """Training forward.  batch: tokens (B,S), labels (B,S) and, per family,
    image_embeds (B,N,img_dim) [vlm] or encoder_frames (B,F,d_model) [audio].
    Returns (mean loss, metrics dict)."""
    tokens = batch["tokens"]
    compute_dtype = jnp.dtype(cfg.dtype)
    x = constrain(params["embed"].astype(compute_dtype)[tokens],
                  BATCH, None, None)

    cross_src = None
    if cfg.arch_type == "vlm":
        cross_src = jnp.einsum(
            "bnd,de->bne", batch["image_embeds"].astype(compute_dtype),
            params["projector"].astype(compute_dtype))
    elif cfg.arch_type == "audio":
        cross_src = _encoder_forward(
            cfg, params, batch["encoder_frames"].astype(compute_dtype))

    x, aux, *rows = _scan_blocks(cfg, params, x, cross_src, remat=remat,
                                 remat_policy=remat_policy)
    x = apply_norm(cfg.norm, x, params["final_norm"], cfg.norm_eps)
    loss_sum, count = chunked_cross_entropy(x, _lm_head(cfg, params),
                                            batch["labels"])
    ce = loss_sum / jnp.maximum(count, 1.0)
    loss = ce + aux
    metrics = {"ce": ce, "aux": aux, "tokens": count}
    if rows:
        metrics["moe_rows"] = rows[0]
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------

def cache_length(cfg: ModelConfig, seq_len: int) -> int:
    """KV-cache length: ring buffer of `window` for SWA models, else seq_len."""
    if cfg.sliding_window:
        return min(cfg.sliding_window, seq_len)
    return seq_len


def _no_serving(cfg: ModelConfig) -> None:
    if cfg.arch_type == "pattern":
        raise NotImplementedError("prefill and decode of a pattern model")


def init_decode_state(cfg: ModelConfig, batch: int, seq_len: int,
                      dtype=jnp.bfloat16) -> Dict:
    _no_serving(cfg)
    hd = cfg.resolved_head_dim() if cfg.num_heads else 0
    state: Dict = {"pos": jnp.zeros((), jnp.int32)}
    clen = cache_length(cfg, seq_len)

    if cfg.arch_type in ("dense", "moe", "vlm", "audio"):
        state["kv"] = {
            "k": jnp.zeros((cfg.num_layers, batch, clen, cfg.num_kv_heads, hd), dtype),
            "v": jnp.zeros((cfg.num_layers, batch, clen, cfg.num_kv_heads, hd), dtype),
        }
    if cfg.arch_type in ("ssm", "hybrid"):
        per = ssm_lib.init_ssm_state(batch, cfg.d_model, cfg.ssm)
        state["ssm"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape), per)
    if cfg.arch_type == "hybrid":
        n = num_shared_attn(cfg)
        state["kv"] = {
            "k": jnp.zeros((n, batch, clen, cfg.num_kv_heads, hd), dtype),
            "v": jnp.zeros((n, batch, clen, cfg.num_kv_heads, hd), dtype),
        }
    if cfg.arch_type == "vlm":
        n = num_cross_layers(cfg)
        state["cross_kv"] = {
            "k": jnp.zeros((n, batch, cfg.vlm.num_image_tokens,
                            cfg.num_kv_heads, hd), dtype),
            "v": jnp.zeros((n, batch, cfg.vlm.num_image_tokens,
                            cfg.num_kv_heads, hd), dtype),
        }
    if cfg.arch_type == "audio":
        state["cross_kv"] = {
            "k": jnp.zeros((cfg.num_layers, batch, cfg.encdec.encoder_seq,
                            cfg.num_kv_heads, hd), dtype),
            "v": jnp.zeros((cfg.num_layers, batch, cfg.encdec.encoder_seq,
                            cfg.num_kv_heads, hd), dtype),
        }
    return state


def decode_step_fn(params: Dict, state: Dict, token: jax.Array,
                   cfg: ModelConfig) -> Tuple[jax.Array, Dict]:
    """One decode step.  token: (B,) int32.  Returns (logits (B,V), state)."""
    _no_serving(cfg)
    compute_dtype = jnp.dtype(cfg.dtype)
    pos = state["pos"]
    x = params["embed"].astype(compute_dtype)[token][:, None]  # (B,1,d)
    new_state = dict(state)
    window = cfg.sliding_window

    def attn_decode(block, x, cache):
        h = apply_norm(cfg.norm, x, block["ln1"], cfg.norm_eps)
        h, cache = attn_lib.decode_attention(
            block["attn"], h, cache, pos, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, rope_theta=cfg.rope_theta,
            window=window)
        return x + h, cache

    def cross_decode(cblock, ckv, x):
        h = apply_norm(cfg.norm, x, cblock["ln"], cfg.norm_eps)
        h = attn_lib.decode_cross_attention(
            cblock["attn"], h,
            jax.tree_util.tree_map(lambda a: a.astype(compute_dtype), ckv),
            num_heads=cfg.num_heads)
        gate = jnp.tanh(cblock["gate"]).astype(x.dtype)
        return x + gate * h

    if cfg.arch_type in ("dense", "moe", "audio"):
        def layer(carry, inp):
            x = carry
            if cfg.arch_type == "audio":
                block, cache, cross, ckv = inp
            else:
                block, cache = inp
            x, cache = attn_decode(block, x, cache)
            if cfg.arch_type == "moe":
                h = apply_norm(cfg.norm, x, block["ln2"], cfg.norm_eps)
                out, _, _ = moe_lib.moe_forward(block["moe"], h, cfg.mlp, cfg.moe)
                x = x + out
            elif cfg.arch_type == "audio":
                x = cross_decode(cross, ckv, x)
                x = _mlp_res(cfg, block, x)
            else:
                x = _mlp_res(cfg, block, x)
            return x, cache

        xs = (params["blocks"], state["kv"])
        if cfg.arch_type == "audio":
            xs = (params["blocks"], state["kv"], params["cross"],
                  state["cross_kv"])
        x, new_kv = jax.lax.scan(layer, x, xs)
        new_state["kv"] = new_kv

    elif cfg.arch_type == "vlm":
        n, per, _ = group_layout(cfg)
        grouped_blocks, _ = _split_groups(params["blocks"], n, per)
        grouped_kv = jax.tree_util.tree_map(
            lambda a: a.reshape((n, per) + a.shape[1:]), state["kv"])

        def group(x, inp):
            gblocks, gkv, cross, ckv = inp
            def inner(c, blk_kv):
                blk, cache = blk_kv
                c, cache = attn_decode(blk, c, cache)
                return _mlp_res(cfg, blk, c), cache
            x, new_gkv = jax.lax.scan(inner, x, (gblocks, gkv))
            x = cross_decode(cross, ckv, x)
            return x, new_gkv

        x, new_gkv = jax.lax.scan(
            group, x, (grouped_blocks, grouped_kv, params["cross"],
                       state["cross_kv"]))
        new_state["kv"] = jax.tree_util.tree_map(
            lambda a: a.reshape((n * per,) + a.shape[2:]), new_gkv)

    elif cfg.arch_type == "ssm":
        def layer(x, inp):
            block, sstate = inp
            h = apply_norm(cfg.norm, x, block["ln1"], cfg.norm_eps)
            h, sstate = ssm_lib.ssm_decode_step(block["ssm"], h, sstate, cfg.ssm)
            return x + h, sstate
        x, new_ssm = jax.lax.scan(layer, x, (params["blocks"], state["ssm"]))
        new_state["ssm"] = new_ssm

    elif cfg.arch_type == "hybrid":
        n, per, tail = group_layout(cfg)
        grouped_blocks, tailb = _split_groups(params["blocks"], n, per)
        grouped_ssm = jax.tree_util.tree_map(
            lambda a: a[:n * per].reshape((n, per) + a.shape[1:]), state["ssm"])
        tail_ssm = jax.tree_util.tree_map(lambda a: a[n * per:], state["ssm"])
        sa = params["shared_attn"]

        def ssm_layer(x, inp):
            block, sstate = inp
            h = apply_norm(cfg.norm, x, block["ln1"], cfg.norm_eps)
            h, sstate = ssm_lib.ssm_decode_step(block["ssm"], h, sstate, cfg.ssm)
            return x + h, sstate

        def group(x, inp):
            gblocks, gssm, cache = inp
            x, new_gssm = jax.lax.scan(ssm_layer, x, (gblocks, gssm))
            x, cache = attn_decode(sa, x, cache)
            x = _mlp_res(cfg, sa, x)
            return x, (new_gssm, cache)

        x, (new_gssm, new_kv) = jax.lax.scan(
            group, x, (grouped_blocks, grouped_ssm, state["kv"]))
        if tail:
            x, new_tail = jax.lax.scan(ssm_layer, x, (tailb, tail_ssm))
        else:
            new_tail = tail_ssm
        new_state["ssm"] = _merge_groups(new_gssm, new_tail)
        new_state["kv"] = new_kv
    else:
        raise ValueError(cfg.arch_type)

    x = apply_norm(cfg.norm, x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x,
                        _lm_head(cfg, params).astype(compute_dtype),
                        preferred_element_type=jnp.float32)[:, 0]
    new_state["pos"] = pos + 1
    return logits, new_state


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def _fill_cache(cfg: ModelConfig, block: Dict, h: jax.Array, s: int,
                clen: int, dtype):
    """Compute k/v for all positions; keep the last `clen` in ring layout."""
    k = jnp.einsum("bsd,dhk->bshk", h, block["attn"]["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dhk->bshk", h, block["attn"]["wv"].astype(h.dtype))
    pos_ids = jnp.arange(s)[None, :]
    if cfg.rope_theta > 0:
        k = attn_lib.apply_rope(k, pos_ids, cfg.rope_theta)
    if cfg.sliding_window and s > clen:
        # ring layout: position p lives at slot p % clen; after slicing the
        # last clen positions (s-clen .. s-1), original index i holds
        # position s-clen+i, whose slot is (i + s) % clen -> roll by s%clen.
        k, v = k[:, -clen:], v[:, -clen:]
        roll = s % clen
        k = jnp.roll(k, roll, axis=1)
        v = jnp.roll(v, roll, axis=1)
    elif s < clen:
        padw = clen - s
        k = jnp.pad(k, ((0, 0), (0, padw), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, padw), (0, 0), (0, 0)))
    return k.astype(dtype), v.astype(dtype)


def _ssm_prefill_layer(cfg: ModelConfig, block: Dict, x: jax.Array):
    """One Mamba2 layer over the full prompt, returning its decode state."""
    b, s, _ = x.shape
    sp = cfg.ssm
    d_in = sp.expand * cfg.d_model
    nheads = d_in // sp.head_dim
    nst = sp.state_dim
    h = apply_norm(cfg.norm, x, block["ln1"], cfg.norm_eps)
    proj = jnp.einsum("bld,de->ble", h, block["ssm"]["in_proj"].astype(h.dtype))
    z = proj[..., :d_in]
    xbc = proj[..., d_in:2 * d_in + 2 * nst]
    dt = proj[..., 2 * d_in + 2 * nst:]
    w = block["ssm"]["conv_w"].astype(h.dtype)
    padn = sp.conv_width - 1
    xp = jnp.pad(xbc, ((0, 0), (padn, 0), (0, 0)))
    conv = sum(xp[:, j:j + s] * w[j] for j in range(sp.conv_width))
    conv = jax.nn.silu(conv + block["ssm"]["conv_b"].astype(h.dtype))
    xs = conv[..., :d_in].reshape(b, s, nheads, sp.head_dim)
    bmat = conv[..., d_in:d_in + nst]
    cmat = conv[..., d_in + nst:]
    dtp = jax.nn.softplus(dt.astype(jnp.float32) + block["ssm"]["dt_bias"])
    a = -jnp.exp(block["ssm"]["A_log"])
    y, fstate = ssm_lib.ssd_chunked(xs, dtp, a, bmat, cmat, sp.chunk_size)
    y = y + block["ssm"]["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(b, s, d_in).astype(h.dtype)
    y = y * jax.nn.silu(z)
    y = rmsnorm(y, block["ssm"]["norm_scale"], cfg.norm_eps)
    x = x + jnp.einsum("ble,ed->bld", y, block["ssm"]["out_proj"].astype(h.dtype))
    sstate = {"conv": xp[:, s:], "ssm": fstate}
    return x, sstate


def prefill_fn(params: Dict, batch: Dict, cfg: ModelConfig,
               remat: bool = True,
               cache_len: Optional[int] = None) -> Tuple[jax.Array, Dict]:
    """Process a full prompt; returns (last-token logits (B,V), decode state).

    Caches are filled for subsequent ``decode_step_fn`` calls.  ``cache_len``
    sizes the decode cache (>= prompt length) so generation has headroom;
    default = prompt length (the dry-run convention where decode positions
    stay within seq_len).
    """
    _no_serving(cfg)
    tokens = batch["tokens"]
    b, s = tokens.shape
    target_len = cache_len if cache_len is not None else s
    assert target_len >= s, (target_len, s)
    compute_dtype = jnp.dtype(cfg.dtype)
    x = params["embed"].astype(compute_dtype)[tokens]
    clen = cache_length(cfg, target_len)
    ckpt = jax.checkpoint if remat else (lambda f: f)

    cross_src = None
    if cfg.arch_type == "vlm":
        cross_src = jnp.einsum(
            "bnd,de->bne", batch["image_embeds"].astype(compute_dtype),
            params["projector"].astype(compute_dtype))
    elif cfg.arch_type == "audio":
        cross_src = _encoder_forward(
            cfg, params, batch["encoder_frames"].astype(compute_dtype))

    state = init_decode_state(cfg, b, target_len, dtype=compute_dtype)
    state["pos"] = jnp.asarray(s, jnp.int32)

    fill = functools.partial(_fill_cache, cfg, s=s, clen=clen,
                             dtype=compute_dtype)

    def cross_kv_of(c):
        return attn_lib.init_cross_cache(c["attn"], cross_src,
                                         num_kv_heads=cfg.num_kv_heads)

    if cfg.arch_type in ("dense", "moe", "audio"):
        def layer(x, inp):
            block = inp[0] if isinstance(inp, tuple) else inp
            hn = apply_norm(cfg.norm, x, block["ln1"], cfg.norm_eps)
            kc, vc = fill(block, hn)
            if cfg.arch_type == "moe":
                x, _, _ = _moe_block(cfg, block, x)
            elif cfg.arch_type == "audio":
                x = _audio_block(cfg, block, inp[1], x, cross_src)
            else:
                x = _dense_block(cfg, block, x)
            return x, {"k": kc, "v": vc}

        xs = params["blocks"] if cfg.arch_type != "audio" \
            else (params["blocks"], params["cross"])
        x, kv = jax.lax.scan(ckpt(layer), x, xs)
        state["kv"] = kv
        if cfg.arch_type == "audio":
            ck = jax.vmap(cross_kv_of)(params["cross"])
            state["cross_kv"] = jax.tree_util.tree_map(
                lambda a: a.astype(compute_dtype), ck)

    elif cfg.arch_type == "vlm":
        n, per, _ = group_layout(cfg)
        grouped_blocks, _ = _split_groups(params["blocks"], n, per)

        def group(x, inp):
            gblocks, cross = inp
            def inner(c, blk):
                hn = apply_norm(cfg.norm, c, blk["ln1"], cfg.norm_eps)
                kc, vc = fill(blk, hn)
                return _dense_block(cfg, blk, c), {"k": kc, "v": vc}
            x, gkv = jax.lax.scan(inner, x, gblocks)
            x = _cross_block(cfg, cross, x, cross_src)
            return x, gkv

        x, gkv = jax.lax.scan(ckpt(group), x, (grouped_blocks, params["cross"]))
        state["kv"] = jax.tree_util.tree_map(
            lambda a: a.reshape((n * per,) + a.shape[2:]), gkv)
        ck = jax.vmap(cross_kv_of)(params["cross"])
        state["cross_kv"] = jax.tree_util.tree_map(
            lambda a: a.astype(compute_dtype), ck)

    elif cfg.arch_type == "ssm":
        def layer(x, block):
            return _ssm_prefill_layer(cfg, block, x)
        x, sstates = jax.lax.scan(ckpt(layer), x, params["blocks"])
        state["ssm"] = sstates

    elif cfg.arch_type == "hybrid":
        n, per, tail = group_layout(cfg)
        grouped_blocks, tailb = _split_groups(params["blocks"], n, per)
        sa = params["shared_attn"]

        def group(x, gblocks):
            def inner(c, blk):
                return _ssm_prefill_layer(cfg, blk, c)
            x, gssm = jax.lax.scan(inner, x, gblocks)
            hn = apply_norm(cfg.norm, x, sa["ln1"], cfg.norm_eps)
            kc, vc = fill(sa, hn)
            x = _mlp_res(cfg, sa, _self_attn(cfg, sa, x))
            return x, (gssm, {"k": kc, "v": vc})

        x, (gssm, kv) = jax.lax.scan(ckpt(group), x, grouped_blocks)
        if tail:
            def tail_layer(c, blk):
                return _ssm_prefill_layer(cfg, blk, c)
            x, tssm = jax.lax.scan(ckpt(tail_layer), x, tailb)
        else:
            tssm = jax.tree_util.tree_map(
                lambda a: a[:0], jax.tree_util.tree_map(
                    lambda a: a.reshape((-1,) + a.shape[2:]), gssm))
        state["ssm"] = _merge_groups(gssm, tssm)
        state["kv"] = kv
    else:
        raise ValueError(cfg.arch_type)

    x = apply_norm(cfg.norm, x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, -1],
                        _lm_head(cfg, params).astype(compute_dtype),
                        preferred_element_type=jnp.float32)
    return logits, state


def loss_fn(params: Dict, batch: Dict, cfg: ModelConfig,
            remat: bool = True) -> jax.Array:
    return model_forward(params, batch, cfg, remat=remat)[0]
