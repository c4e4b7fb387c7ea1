"""Mixture-of-Experts layer: a router over every expert and a dropless
dispatch to the experts this chip holds.

The router scores all ``num_experts`` experts and picks ``top_k`` per token
(``MoEConfig.router``: the renormalized top-k of a softmax, or of sigmoid
scores times ``routed_scale``).  The layer holds ``MoEConfig.held``
experts, from expert 0: its share when several chips share the layer.  It
computes their part of the result for every token routed to them; what the
other experts add is the part of the chips that hold them.

Dispatch drops no token, so a token's output does not depend on which
other tokens share its call (a step at splice 2 computes what splice 1
does).  Per call of at most ``TOKEN_CHUNK`` tokens:

- the token-expert assignments are sorted by held expert, the others last;
- the expert FFN runs as a grouped matmul over the sorted rows with the
  group sizes (megablox ``gmm``, whose grid covers the routed rows' tiles
  only; gate and up projections concatenated into one call);
- each assignment gathers its row back, weighted, and a token sums its own.

The rows buffer is sized for every assignment landing here, tokens ×
min(top_k, held); rows past the routed ones are never read by the kernel
and are masked on the way in and out.

Under a mesh with a "model" axis each model shard holds its slice of the
held experts, runs the same dispatch over its batch shard's tokens, and
the partial outputs combine with one ``psum`` over "model".  Outside a
mesh the same code runs as one shard.

The softmax router's load-balance auxiliary loss follows Switch/Mixtral
practice; the sigmoid router has none.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.kernels import default_interpret
from repro.models.common import dense_init
from repro.models.mlp import activation, init_mlp, mlp_forward
from repro.parallel.constraints import BATCH, constrain, current_mesh

TOKEN_CHUNK = 8192   # tokens per dispatch call: bounds the rows buffers
ROW_TILE = 128       # gmm's row tile; each group pads to it
_COL_TILES = (512, 384, 256, 128)


def init_moe(key, d_model: int, d_ff: int, kind: str, moe: MoEConfig,
             dtype=jnp.float32) -> Dict:
    """Key ``key`` splits into (router, experts, shared); the experts' key
    into (wi, wg, wo).  Expert weights are stacked over the held experts:
    (held, d, f) / (held, f, d)."""
    kr, ke, ks = jax.random.split(key, 3)
    e, f = moe.held, d_ff
    out_scale = 0.02 / math.sqrt(2.0)
    keys = jax.random.split(ke, 3)
    params = {
        "router": dense_init(kr, (d_model, moe.num_experts), dtype=dtype),
        "wi": dense_init(keys[0], (e, d_model, f), dtype=dtype),
        "wo": dense_init(keys[2], (e, f, d_model), scale=out_scale, dtype=dtype),
    }
    if kind == "swiglu":
        params["wg"] = dense_init(keys[1], (e, d_model, f), dtype=dtype)
    if moe.shared_expert_ff:
        params["shared"] = init_mlp(ks, d_model, moe.shared_expert_ff, kind, dtype)
    return params


def route(logits: jax.Array, moe: MoEConfig
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """logits (T, E) -> (weights (T,k) f32, expert ids (T,k), aux loss).

    ``softmax``: the top k of the softmax, renormalized, and the Switch
    load-balance loss, E * sum_e f_e * p_e (top-1 fraction times mean
    probability).  ``sigmoid`` (DeepSeek-V3's router at one group, as
    Nemotron-H uses it): the top k of the sigmoid scores (a correction
    bias, which no gradient reaches, would be added for the choice only;
    here it is zero), weighted by their scores over the scores' sum; no
    auxiliary loss.  Both weights are then times ``routed_scale``."""
    if moe.router == "sigmoid":
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        w, idx = jax.lax.top_k(scores, moe.top_k)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return w * moe.routed_scale, idx, jnp.zeros((), jnp.float32)
    if moe.router != "softmax":
        raise ValueError(f"unknown router {moe.router!r}")
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, moe.top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    e = logits.shape[-1]
    top1 = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(jnp.mean(probs, axis=0) * top1)
    if moe.routed_scale != 1.0:
        w = w * moe.routed_scale
    return w, idx, aux


def _col_tile(n: int) -> int:
    """A tile of a column dimension that divides it, else the whole of it."""
    return next((t for t in _COL_TILES if n % t == 0), n)


def gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """gmm's (rows, contracted, output) tiles for a call of these sizes;
    its backward calls ask again with theirs."""
    return ROW_TILE, _col_tile(k), _col_tile(n)


def _gmm(x: jax.Array, w: jax.Array, sizes: jax.Array) -> jax.Array:
    return gmm(x, w.astype(x.dtype), sizes, preferred_element_type=x.dtype,
               tiling=gmm_tiling, interpret=default_interpret())


def held_experts_ffn(xf: jax.Array, idx: jax.Array, weights: jax.Array,
                     wi: jax.Array, wg: Optional[jax.Array], wo: jax.Array,
                     kind: str, offset=0) -> Tuple[jax.Array, jax.Array]:
    """The part of the layer's output that experts ``offset ..
    offset + e`` give, for one call's tokens.

    xf: (T, d); idx, weights: (T, k) global routing; wi/wg: (e, d, f),
    wo: (e, f, d).  Returns (out (T, d), rows routed to each of the e
    experts (e,) int32)."""
    t, d = xf.shape
    k = idx.shape[1]
    e = wi.shape[0]
    n = t * k
    local = idx.reshape(-1) - offset
    held = (local >= 0) & (local < e)
    key = jnp.where(held, local, e)                  # the others sort last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=e + 1)[:e].astype(jnp.int32)
    bound = t * min(k, e)                            # every assignment here
    rows = -(-bound // ROW_TILE) * ROW_TILE
    if rows > n:
        order = jnp.concatenate([order, jnp.zeros(rows - n, order.dtype)])
    sel = order[:rows]
    valid = (jnp.arange(rows) < jnp.sum(sizes))[:, None]

    x_rows = jnp.where(valid, xf[sel // k], 0)
    if kind == "swiglu":
        f = wi.shape[-1]
        hg = _gmm(x_rows, jnp.concatenate([wi, wg], axis=-1), sizes)
        h = jax.nn.silu(hg[:, f:]) * hg[:, :f]
    else:
        h = activation(kind, _gmm(x_rows, wi, sizes))
    y_rows = jnp.where(valid, _gmm(h, wo, sizes), 0)

    # each assignment's row, back in (token, slot) order
    pos = jnp.zeros(n, jnp.int32).at[order[:n]].set(jnp.arange(n, dtype=jnp.int32))
    got = y_rows[jnp.minimum(pos, rows - 1)]
    wk = jnp.where(held, weights.reshape(-1), 0).astype(xf.dtype)
    got = jnp.where(held[:, None], got, 0) * wk[:, None]
    return jnp.sum(got.reshape(t, k, d), axis=1), sizes


def dispatch(xf: jax.Array, idx: jax.Array, weights: jax.Array, wi, wg, wo,
             kind: str, offset=0, token_chunk: int = TOKEN_CHUNK
             ) -> Tuple[jax.Array, jax.Array]:
    """``held_experts_ffn`` over calls of ``token_chunk`` tokens (one call
    where the tokens do not divide evenly), each recomputed in the
    backward pass, so that one call's rows buffers are live at a time."""
    t, d = xf.shape
    if t <= token_chunk or t % token_chunk:
        return held_experts_ffn(xf, idx, weights, wi, wg, wo, kind, offset)
    nc = t // token_chunk

    @jax.checkpoint
    def call(xs):
        return held_experts_ffn(*xs, wi, wg, wo, kind, offset)

    out, sizes = jax.lax.map(call, (xf.reshape(nc, token_chunk, d),
                                    idx.reshape(nc, token_chunk, -1),
                                    weights.reshape(nc, token_chunk, -1)))
    return out.reshape(t, d), jnp.sum(sizes, axis=0)


def moe_forward(params: Dict, x: jax.Array, kind: str, moe: MoEConfig
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar, rows routed to
    each held expert (held,) int32)."""
    b, s, d = x.shape
    t = b * s
    held = moe.held
    xf = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xf, params["router"].astype(x.dtype))
    weights, idx, aux = route(logits, moe)
    weights = weights.astype(x.dtype)
    wi, wo, wg = params["wi"], params["wo"], params.get("wg")

    mesh = current_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh else {}
    m = sizes.get("model", 1)
    batch_axes = tuple(a for a in BATCH if sizes.get(a, 1) > 1)
    n_batch = 1
    for a in batch_axes:
        n_batch *= sizes[a]

    if mesh is not None and m > 1 and t % n_batch == 0:
        # pad the held experts to a multiple of the model axis; the padded
        # slots hold no expert: assignments past ``held`` route nowhere
        pad = (-held) % m
        if pad:
            def padw(w):
                return jnp.pad(w, ((0, pad),) + ((0, 0),) * (w.ndim - 1))
            wi, wo = padw(wi), padw(wo)
            wg = padw(wg) if wg is not None else None
            idx = jnp.where(idx < held, idx, -1)
        wg_in = wg if wg is not None else wi          # unused without a gate
        bspec = batch_axes if len(batch_axes) > 1 else \
            (batch_axes[0] if batch_axes else None)

        def shard_fn(xf_l, idx_l, w_l, wi_l, wg_l, wo_l):
            e_off = jax.lax.axis_index("model") * wi_l.shape[0]
            out, rows = dispatch(xf_l, idx_l, w_l, wi_l,
                                 wg_l if wg is not None else None, wo_l,
                                 kind, e_off)
            if batch_axes:
                rows = jax.lax.psum(rows, batch_axes)
            return jax.lax.psum(out, "model"), rows

        # check_vma off: gmm's pallas_call states no varying mesh axes
        out, rows = jax.shard_map(
            shard_fn, mesh=mesh, check_vma=False,
            in_specs=(P(bspec, None), P(bspec, None), P(bspec, None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=(P(bspec, None), P("model")))(
                xf, idx, weights, wi, wg_in, wo)
        rows = rows[:held]
    else:
        out, rows = dispatch(xf, idx, weights, wi, wg, wo, kind)

    out = constrain(out, BATCH, None)
    if "shared" in params:
        out = out + mlp_forward(params["shared"], xf[None], kind)[0]
    return out.reshape(b, s, d), aux * moe.router_aux_weight, rows
