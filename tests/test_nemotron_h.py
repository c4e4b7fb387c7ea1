"""Nemotron-H's pieces at a small size on the CPU, in float32: the pattern
model against the plain reference, the grouped SSD and gated norm, ReLU^2,
the sigmoid router, and the dropless expert layer (the chip's share adds up
to the uncut layer, no row is dropped, the result does not depend on the
splice)."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.references import nemotron_h as ref  # noqa: E402
from bench.references.common import Matmul  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig, TrainConfig  # noqa: E402
from repro.core.elastic import ElasticRuntime  # noqa: E402
from repro.kernels.ssd_scan.ref import ssd_sequential_ref  # noqa: E402
from repro.models import init_params, model_forward  # noqa: E402
from repro.models import mlp as mlp_lib  # noqa: E402
from repro.models import moe as moe_lib  # noqa: E402
from repro.models.ssm import gated_norm, ssd_chunked  # noqa: E402

MODEL = {
    "name": "nemotron-tiny", "arch_type": "pattern", "layer_pattern": "MEM*E",
    "num_layers": 5, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
    "head_dim": 16, "d_ff": 32, "vocab_size": 128, "rope_theta": 0.0,
    "norm": "rmsnorm", "norm_eps": 1e-5, "mlp": "relu2",
    "tie_embeddings": False, "dtype": "float32",
    "ssm": {"state_dim": 16, "head_dim": 16, "num_heads": 8, "n_groups": 2,
            "chunk_size": 16, "conv_width": 4},
    "moe": {"num_experts": 8, "top_k": 3, "num_held": 4, "router": "sigmoid",
            "routed_scale": 2.5, "shared_expert_ff": 48,
            "router_aux_weight": 0.0},
}


def _cfg(model=MODEL) -> ModelConfig:
    m = dict(model)
    return ModelConfig(**{**m, "ssm": SSMConfig(**m["ssm"]),
                          "moe": MoEConfig(**m["moe"])})


def _tokens(key, b=2, s=64, v=128):
    return jax.random.randint(jax.random.PRNGKey(key), (b, s + 1), 0, v)


def test_published_param_count():
    cfg = get_config("nemotron-3-nano-30b-a3b")
    assert cfg.param_count() == 31_577_937_344
    assert cfg.active_param_count() == 3_227_751_872
    cut = dataclasses.replace(cfg, layer_pattern="MEMEM*E", num_layers=7,
                              vocab_size=16384,
                              moe=dataclasses.replace(cfg.moe, num_held=8))
    assert cut.param_count() == 528_092_736


def test_weights_loss_and_gradients_match_reference():
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(11))
    want = ref.init_params(MODEL, 11)
    flat = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    for path, w in jax.tree_util.tree_flatten_with_path(want)[0]:
        np.testing.assert_allclose(flat[path], w, rtol=1e-6, atol=1e-9)

    tok = _tokens(3)
    batch = {"tokens": tok[:, :-1], "labels": tok[:, 1:]}
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model_forward(p, batch, cfg)[0]))(params)

    def ref_loss(p):
        total, count = ref.loss_sum(p, batch["tokens"], batch["labels"],
                                    MODEL, Matmul())
        return total / count

    want_loss, want_grads = jax.jit(jax.value_and_grad(ref_loss))(want)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    got = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(want_grads)[0]:
        scale = float(jnp.max(jnp.abs(g))) + 1e-12
        err = float(jnp.max(jnp.abs(got[path] - g))) / scale
        assert err < 1e-3, (jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("causal,s,skv,block", [(True, 200, 200, 64),
                                                 (False, 100, 150, 64)])
def test_flash_attention_matches_blockwise(causal, s, skv, block):
    """The attention of the pattern model's 8k blocks: its backward pass
    recomputes the scores, and agrees with autodiff of the blockwise
    softmax (which keeps them)."""
    from repro.models.attention import blockwise_attention, flash_attention
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(ks[0], (2, s, 3, 16))
    k = jax.random.normal(ks[1], (2, skv, 3, 16))
    v = jax.random.normal(ks[2], (2, skv, 3, 16))
    g = jax.random.normal(ks[3], (2, s, 3, 16))
    flash = jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal, block) * g),
        argnums=(0, 1, 2)))
    plain = jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(blockwise_attention(
            q, k, v, causal=causal, block_q=block, block_kv=block) * g),
        argnums=(0, 1, 2)))
    (a, ga), (b, gb) = flash(q, k, v), plain(q, k, v)
    np.testing.assert_allclose(a, b, rtol=1e-5)
    for x, y in zip(ga, gb):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-5)


def _ssd_inputs(g, h=8, n=8, p=4, b=2, length=48, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    x = jax.random.normal(ks[0], (b, length, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, length, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bm = jax.random.normal(ks[3], (b, length, g, n))
    cm = jax.random.normal(ks[4], (b, length, g, n))
    return x, dt, a, bm, cm


def test_grouped_ssd_matches_broadcast_and_recurrence():
    x, dt, a, bm, cm = _ssd_inputs(g=4)
    y, final = ssd_chunked(x, dt, a, bm, cm, 16)
    # every group equal: G = 4 computes what one group broadcast does
    same_b = jnp.broadcast_to(bm[:, :, :1], bm.shape)
    same_c = jnp.broadcast_to(cm[:, :, :1], cm.shape)
    y4, f4 = ssd_chunked(x, dt, a, same_b, same_c, 16)
    y1, f1 = ssd_chunked(x, dt, a, bm[:, :, 0], cm[:, :, 0], 16)
    np.testing.assert_allclose(y4, y1, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(f4, f1, rtol=1e-5, atol=1e-5)
    # against the sequential recurrence, head by head on its group
    r = x.shape[2] // bm.shape[2]
    for head in range(x.shape[2]):
        ys, fs = ssd_sequential_ref(x[:, :, head:head + 1], dt[:, :, head:head + 1],
                                    a[head:head + 1], bm[:, :, head // r],
                                    cm[:, :, head // r])
        np.testing.assert_allclose(y[:, :, head:head + 1], ys, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(final[:, head:head + 1], fs, rtol=1e-4, atol=1e-4)


def test_grouped_gated_norm():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    y = jax.random.normal(ks[0], (2, 5, 24))
    z = jax.random.normal(ks[1], (2, 5, 24))
    scale = jax.random.normal(ks[2], (24,))
    got = gated_norm(y, z, scale, groups=3, eps=1e-5)
    gy = (y * jax.nn.silu(z)).reshape(2, 5, 3, 8)
    want = gy / jnp.sqrt(jnp.mean(gy * gy, axis=-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want.reshape(2, 5, 24) * scale, rtol=1e-5,
                               atol=1e-6)


def test_relu2_mlp():
    params = mlp_lib.init_mlp(jax.random.PRNGKey(2), 16, 24, "relu2")
    assert set(params) == {"wi", "wo"}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 16))
    want = jnp.maximum(x @ params["wi"], 0.0) ** 2 @ params["wo"]
    np.testing.assert_allclose(mlp_lib.mlp_forward(params, x, "relu2"), want,
                               rtol=1e-5, atol=1e-6)


def test_sigmoid_routing_matches_hand_written_top_k():
    logits = jax.random.normal(jax.random.PRNGKey(4), (32, 16))
    moe = MoEConfig(num_experts=16, top_k=6, router="sigmoid", routed_scale=2.5)
    w, idx, aux = moe_lib.route(logits, moe)
    scores = np.asarray(jax.nn.sigmoid(logits))
    for t in range(32):
        pick = np.argsort(-scores[t], kind="stable")[:6]
        assert sorted(pick.tolist()) == sorted(np.asarray(idx[t]).tolist())
        want = scores[t, pick] / scores[t, pick].sum() * 2.5
        got = dict(zip(np.asarray(idx[t]).tolist(), np.asarray(w[t]).tolist()))
        np.testing.assert_allclose([got[e] for e in pick], want, rtol=1e-6)
    assert float(aux) == 0.0


def _layer(held, key=5, e=8, d=16, f=24, k=3):
    moe = MoEConfig(num_experts=e, top_k=k, num_held=held, router="sigmoid",
                    routed_scale=2.5, shared_expert_ff=20)
    return moe, moe_lib.init_moe(jax.random.PRNGKey(key), d, f, "relu2", moe)


def _dense_part(x, idx, w, wi, wo, offset=0):
    """Each expert over every token, times its routing weight (0 where not
    picked): the plain sum the dispatch must give."""
    out = jnp.zeros_like(x)
    for j in range(wi.shape[0]):
        wj = jnp.sum(jnp.where(idx == offset + j, w, 0.0), axis=-1)
        out = out + wj[:, None] * (jnp.maximum(x @ wi[j], 0.0) ** 2 @ wo[j])
    return out


def test_shares_add_up_to_the_uncut_layer():
    moe, params = _layer(held=8)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 16))
    whole, _, rows = moe_lib.moe_forward(params, x, "relu2", moe)
    assert int(jnp.sum(rows)) == 2 * 24 * 3
    xf = x.reshape(-1, 16)
    logits = xf @ params["router"]
    w, idx, _ = moe_lib.route(logits, moe)
    parts = sum(moe_lib.dispatch(xf, idx, w, params["wi"][o:o + 2], None,
                                 params["wo"][o:o + 2], "relu2", o)[0]
                for o in range(0, 8, 2))
    shared = mlp_lib.mlp_forward(params["shared"], x, "relu2").reshape(-1, 16)
    np.testing.assert_allclose(parts + shared, whole.reshape(-1, 16),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(parts, _dense_part(xf, idx, w, params["wi"],
                                                  params["wo"]),
                               rtol=1e-5, atol=1e-6)


def test_dropless_when_every_token_picks_held_experts():
    moe, params = _layer(held=4)
    # the held experts' router columns dominate: every token picks 3 of them
    params["router"] = params["router"].at[:, :4].add(50.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (2, 32, 16)))
    out, _, rows = moe_lib.moe_forward(params, x, "relu2", moe)
    xf = x.reshape(-1, 16)
    w, idx, _ = moe_lib.route(xf @ params["router"], moe)
    assert bool(jnp.all(idx < 4))
    assert int(jnp.sum(rows)) == 64 * 3           # every assignment computed
    want = _dense_part(xf, idx, w, params["wi"], params["wo"]) \
        + mlp_lib.mlp_forward(params["shared"], x, "relu2").reshape(-1, 16)
    np.testing.assert_allclose(out.reshape(-1, 16), want, rtol=1e-5, atol=1e-5)


def test_dispatch_in_chunks_matches_one_call():
    moe, params = _layer(held=4)
    xf = jax.random.normal(jax.random.PRNGKey(8), (64, 16))
    w, idx, _ = moe_lib.route(xf @ params["router"], moe)
    one, rows1 = moe_lib.dispatch(xf, idx, w, params["wi"], None, params["wo"],
                                  "relu2")
    four, rows4 = moe_lib.dispatch(xf, idx, w, params["wi"], None, params["wo"],
                                   "relu2", token_chunk=16)
    np.testing.assert_allclose(four, one, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(rows4, rows1)


def test_splice_invariance_through_resize():
    """ElasticRuntime at splice 1 and after a resize to splice 2 follows
    the same trajectory: dispatch drops nothing, whatever the tokens that
    share a call."""
    cfg = _cfg()
    tcfg = TrainConfig(total_steps=10, warmup_steps=1, learning_rate=1e-3)
    a = ElasticRuntime(cfg, tcfg, 2, 2, 4, 32, seed=3)
    b = ElasticRuntime(cfg, tcfg, 2, 2, 4, 32, seed=3)
    a.run_steps(3)
    b.run_steps(1)
    b.resize(1)
    assert b.splice == 2
    b.run_steps(2)
    for ra, rb in zip(a.history, b.history):
        assert abs(ra["loss"] - rb["loss"]) <= 1e-5 * abs(ra["loss"]), (ra, rb)
    for x, y in zip(jax.tree_util.tree_leaves(a.state["params"]),
                    jax.tree_util.tree_leaves(b.state["params"])):
        np.testing.assert_allclose(x, y, rtol=2e-4, atol=1e-6)
    # the routing counters, summed over the splice's slices, agree too
    assert a.prof.counters == b.prof.counters
    c = a.prof.counters
    assert c["moe.block_steps"] == 3 * 2
    assert 0 < c["moe.rows_peak"] * 4 >= c["moe.rows"] > 0
    assert c["moe.rows"] <= 3 * 2 * (4 * 32) * 3


def test_pattern_does_not_serve():
    from repro.models import init_decode_state
    with pytest.raises(NotImplementedError):
        init_decode_state(_cfg(), 1, 8)
