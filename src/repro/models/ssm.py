"""Mamba2 / SSD (state-space duality) block, arXiv:2405.21060.

Training/prefill uses the chunked SSD algorithm: quadratic attention-like
computation inside fixed-size chunks, linear recurrence across chunks
(``lax.scan``).  Decode is the O(1) recurrent update on (B, H, P, N) state.

This pure-jnp implementation is also the oracle basis for the Pallas
``ssd_scan`` kernel.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.models.common import dense_init, rmsnorm
from repro.parallel.constraints import BATCH, MODEL, constrain


def init_ssm(key, d_model: int, cfg: SSMConfig, dtype=jnp.float32) -> Dict:
    d_in, nheads = cfg.d_inner(d_model), cfg.heads(d_model)
    n = cfg.n_groups * cfg.state_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    conv_ch = d_in + 2 * n
    return {
        # in_proj -> [z (d_in), x (d_in), B (G*N), C (G*N), dt (H)]
        "in_proj": dense_init(k1, (d_model, 2 * d_in + 2 * n + nheads), dtype=dtype),
        "conv_w": dense_init(k2, (cfg.conv_width, conv_ch), scale=0.1, dtype=dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.zeros((nheads,), jnp.float32),       # A = -exp(A_log) = -1
        "D": jnp.ones((nheads,), jnp.float32),
        "dt_bias": jnp.full((nheads,), -2.0, jnp.float32),  # softplus(-2) ~ 0.13
        "norm_scale": jnp.ones((d_in,), dtype),
        "out_proj": dense_init(k3, (d_in, d_model), dtype=dtype),
    }


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, chunk: int,
                initial_state: jax.Array | None = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    x:  (B, L, H, P)   inputs per head
    dt: (B, L, H)      positive step sizes (already softplus'd)
    a:  (H,)           negative decay rates (A = -exp(A_log))
    b:  (B, L, G, N)   input projection of G groups; head h reads group
                       h // (H / G).  (B, L, N) is one group, broadcast
                       over the heads
    c:  (B, L, G, N)   output projection, grouped as b
    Returns (y (B, L, H, P), final_state (B, H, P, N)).

    G groups run as G independent scans over their H / G heads (``vmap``),
    so ``C B^T`` is formed once per group.
    """
    if b.ndim == 4:
        g = b.shape[2]
        if g == 1:
            return ssd_chunked(x, dt, a, b[:, :, 0], c[:, :, 0], chunk,
                               initial_state)
        bs, l, h, p = x.shape
        r = h // g
        s0 = None if initial_state is None else \
            initial_state.reshape(bs, g, r, p, -1)
        y, final = jax.vmap(
            lambda xg, dg, ag, bg, cg, sg: ssd_chunked(xg, dg, ag, bg, cg,
                                                       chunk, sg),
            in_axes=(2, 2, 0, 2, 2, None if s0 is None else 1),
            out_axes=(2, 1))(x.reshape(bs, l, g, r, p), dt.reshape(bs, l, g, r),
                             a.reshape(g, r), b, c, s0)
        return y.reshape(bs, l, h, p), final.reshape(bs, h, p, -1)
    bs, l, h, p = x.shape
    n = b.shape[-1]
    pad = (-l) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad)) + ((0, 0),))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    nc = x.shape[1] // chunk
    # chunked views: (B, nc, Q, ...)
    xq = x.reshape(bs, nc, chunk, h, p)
    dtq = dt.reshape(bs, nc, chunk, h)
    bq = b.reshape(bs, nc, chunk, n)
    cq = c.reshape(bs, nc, chunk, n)

    adt = dtq * a[None, None, None, :]                     # (B,nc,Q,H) decay log-steps
    cum = jnp.cumsum(adt, axis=2)                          # within-chunk cumulative
    total = cum[:, :, -1, :]                               # (B,nc,H)

    # --- intra-chunk (quadratic within chunk) ---
    # M[t,s] = (C_t . B_s) * exp(cum_t - cum_s) * dt_s   for s <= t
    cb = jnp.einsum("bqtn,bqsn->bqts", cq, bq,
                    preferred_element_type=jnp.float32)    # (B,nc,Q,Q)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]    # (B,nc,Q,Q,H) cum_t - cum_s
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(mask[None, None, :, :, None], seg, -jnp.inf))
    m = cb[..., None] * decay * dtq[:, :, None, :, :]      # (B,nc,Q,Q,H)
    y_intra = jnp.einsum("bqtsh,bqshp->bqthp", m, xq.astype(jnp.float32))

    # --- chunk states ---
    # S_c = sum_s exp(total - cum_s) dt_s B_s (x) x_s  -> (B,nc,H,P,N)
    w = jnp.exp(total[:, :, None, :] - cum) * dtq          # (B,nc,Q,H)
    state_c = jnp.einsum("bqsh,bqsn,bqshp->bqhpn",
                         w, bq.astype(jnp.float32), xq.astype(jnp.float32))

    # --- inter-chunk recurrence over chunks ---
    decay_chunk = jnp.exp(total)                           # (B,nc,H)

    def step(s_prev, inp):
        dc, sc = inp                                       # (B,H), (B,H,P,N)
        s_new = s_prev * dc[:, :, None, None] + sc
        return s_new, s_prev

    s0 = (jnp.zeros((bs, h, p, n), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    final, s_before = jax.lax.scan(
        step, s0, (decay_chunk.transpose(1, 0, 2), state_c.transpose(1, 0, 2, 3, 4)))
    s_before = s_before.transpose(1, 0, 2, 3, 4)           # (B,nc,H,P,N) state entering chunk

    # --- inter-chunk contribution ---
    # y_inter[t] = C_t . (exp(cum_t) * S_in)
    outw = jnp.exp(cum)                                    # (B,nc,Q,H)
    y_inter = jnp.einsum("bqtn,bqhpn,bqth->bqthp",
                         cq.astype(jnp.float32), s_before, outw)

    y = (y_intra + y_inter).reshape(bs, nc * chunk, h, p)[:, :l]
    return y.astype(x.dtype), final


def _split_proj(proj: jax.Array, d_in: int, n: int, nheads: int):
    z = proj[..., :d_in]
    xbc = proj[..., d_in:d_in + d_in + 2 * n]
    dt = proj[..., d_in + d_in + 2 * n:]
    assert dt.shape[-1] == nheads
    return z, xbc, dt


def gated_norm(y: jax.Array, z: jax.Array, scale: jax.Array, groups: int,
               eps: float = 1e-6) -> jax.Array:
    """``y * silu(z)``, then an RMSNorm over each of ``groups`` equal
    slices of the channels (Zamba2 / Nemotron-H's grouped gated norm)."""
    y = y * jax.nn.silu(z)
    if groups == 1:
        return rmsnorm(y, scale, eps)
    shape = y.shape
    y = rmsnorm(y.reshape(shape[:-1] + (groups, -1)),
                scale.reshape(groups, -1), eps)
    return y.reshape(shape)


def ssm_forward(params: Dict, xin: jax.Array, cfg: SSMConfig,
                eps: float = 1e-6) -> jax.Array:
    """Full Mamba2 block: in_proj -> conv -> SSD -> gated norm -> out_proj.
    ``eps`` is the gated norm's."""
    bsz, l, d_model = xin.shape
    d_in, nheads = cfg.d_inner(d_model), cfg.heads(d_model)
    g = cfg.n_groups
    n = g * cfg.state_dim

    proj = jnp.einsum("bld,de->ble", xin, params["in_proj"].astype(xin.dtype))
    z, xbc, dt = _split_proj(proj, d_in, n, nheads)

    # causal depthwise conv over (x, B, C) channels
    w = params["conv_w"].astype(xin.dtype)                 # (W, ch)
    pad = cfg.conv_width - 1
    xp = jnp.pad(xbc, ((0, 0), (pad, 0), (0, 0)))
    conv = sum(xp[:, i:i + l] * w[i] for i in range(cfg.conv_width))
    conv = jax.nn.silu(conv + params["conv_b"].astype(xin.dtype))

    xs = constrain(conv[..., :d_in].reshape(bsz, l, nheads, cfg.head_dim),
                   BATCH, None, MODEL, None)
    bmat = conv[..., d_in:d_in + n]
    cmat = conv[..., d_in + n:]
    if g > 1:
        bmat = bmat.reshape(bsz, l, g, cfg.state_dim)
        cmat = cmat.reshape(bsz, l, g, cfg.state_dim)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["A_log"])
    y, _ = ssd_chunked(xs, dt, a, bmat, cmat, cfg.chunk_size)
    y = y + params["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(bsz, l, d_in).astype(xin.dtype)

    y = gated_norm(y, z, params["norm_scale"], g, eps)
    return jnp.einsum("ble,ed->bld", y, params["out_proj"].astype(xin.dtype))


# ---------------------------------------------------------------------------
# Decode path (recurrent state)
# ---------------------------------------------------------------------------

def init_ssm_state(batch: int, d_model: int, cfg: SSMConfig,
                   dtype=jnp.float32) -> Dict:
    d_in = cfg.expand * d_model
    nheads = d_in // cfg.head_dim
    n = cfg.state_dim
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, d_in + 2 * n), dtype),
        "ssm": jnp.zeros((batch, nheads, cfg.head_dim, n), jnp.float32),
    }


def ssm_decode_step(params: Dict, xin: jax.Array, state: Dict, cfg: SSMConfig
                    ) -> Tuple[jax.Array, Dict]:
    """One-token recurrent step.  xin: (B, 1, d_model)."""
    bsz, one, d_model = xin.shape
    d_in = cfg.expand * d_model
    nheads = d_in // cfg.head_dim
    n = cfg.state_dim

    proj = jnp.einsum("bld,de->ble", xin, params["in_proj"].astype(xin.dtype))
    z, xbc, dt = _split_proj(proj[:, 0], d_in, n, nheads)

    # conv ring: state holds previous W-1 inputs
    w = params["conv_w"].astype(xin.dtype)
    hist = jnp.concatenate([state["conv"], xbc[:, None]], axis=1)  # (B, W, ch)
    conv = jnp.einsum("bwc,wc->bc", hist, w)
    conv = jax.nn.silu(conv + params["conv_b"].astype(xin.dtype))
    new_conv_state = hist[:, 1:]

    xs = conv[:, :d_in].reshape(bsz, nheads, cfg.head_dim)
    bmat = conv[:, d_in:d_in + n]
    cmat = conv[:, d_in + n:]

    dtp = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,H)
    a = -jnp.exp(params["A_log"])
    decay = jnp.exp(dtp * a[None, :])                                  # (B,H)
    # h' = decay h + dt * B (x) x
    upd = jnp.einsum("bh,bn,bhp->bhpn", dtp, bmat.astype(jnp.float32),
                     xs.astype(jnp.float32))
    h_new = state["ssm"] * decay[:, :, None, None] + upd
    y = jnp.einsum("bn,bhpn->bhp", cmat.astype(jnp.float32), h_new)
    y = y + params["D"][None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(bsz, d_in).astype(xin.dtype)

    y = y * jax.nn.silu(z)
    y = rmsnorm(y, params["norm_scale"])
    out = jnp.einsum("be,ed->bd", y, params["out_proj"].astype(xin.dtype))
    return out[:, None], {"conv": new_conv_state, "ssm": h_new}
