"""Sharding rules + HLO cost analyzer correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis.hlo_cost import analyze_hlo, parse_module
from repro.launch.mesh import make_mesh
from repro.parallel.constraints import constrain
from repro.parallel.sharding import batch_specs, param_specs

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def _mesh22():
    if jax.device_count() < 4:
        pytest.skip("needs >=4 devices (dryrun-only)")
    return make_mesh((2, 2), ("data", "model"))


def test_param_specs_megatron_convention():
    """Row-parallel down-projections shard the contracted dim over model."""
    import jax
    # build a fake mesh object via make_mesh only when possible; otherwise
    # emulate with a 1x1 mesh and assert replicated specs
    mesh = make_mesh((1, 1), ("data", "model"))
    params = {
        "blocks": {
            "attn": {"wq": jnp.zeros((4, 128, 8, 16)),
                     "wo": jnp.zeros((4, 8, 16, 128))},
            "mlp": {"wi": jnp.zeros((4, 128, 512)),
                    "wo": jnp.zeros((4, 512, 128))},
        },
        "embed": jnp.zeros((1024, 128)),
    }
    specs = param_specs(params, mesh)
    # 1x1 mesh -> everything replicated but specs still well-formed
    flat = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert all(isinstance(s, P) for s in flat)


def test_batch_specs_leading_dim():
    mesh = make_mesh((1, 1), ("data", "model"))
    batch = {"tokens": jnp.zeros((8, 16), jnp.int32)}
    specs = batch_specs(batch, mesh)
    assert isinstance(specs["tokens"], P)


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = constrain(x, "data", "model")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------- HLO cost analyzer
def test_trip_count_multiplication():
    """A scan of N matmuls must count N x the flops of one matmul."""
    n, m = 8, 64

    def one(x, w):
        return x @ w, None

    def scanned(x, ws):
        y, _ = jax.lax.scan(lambda c, w: (c @ w, None), x, ws)
        return y

    x = jnp.zeros((m, m))
    ws = jnp.zeros((n, m, m))
    hlo = jax.jit(scanned).lower(x, ws).compile().as_text()
    cost = analyze_hlo(hlo)
    expected = n * 2 * m * m * m
    assert abs(cost.flops - expected) / expected < 0.05, cost.flops


def test_flops_single_dot():
    a, b, k = 32, 48, 64
    hlo = jax.jit(lambda x, y: x @ y).lower(
        jnp.zeros((a, k)), jnp.zeros((k, b))).compile().as_text()
    cost = analyze_hlo(hlo)
    assert abs(cost.flops - 2 * a * b * k) / (2 * a * b * k) < 0.05


def test_nested_scan_trip_counts():
    m = 16

    def inner(x, ws):
        y, _ = jax.lax.scan(lambda c, w: (c @ w, None), x, ws)
        return y

    def outer(x, ws):
        y, _ = jax.lax.scan(lambda c, w: (inner(c, w), None), x, ws)
        return y

    x = jnp.zeros((m, m))
    ws = jnp.zeros((3, 5, m, m))     # 15 matmuls total
    hlo = jax.jit(outer).lower(x, ws).compile().as_text()
    cost = analyze_hlo(hlo)
    expected = 15 * 2 * m ** 3
    assert abs(cost.flops - expected) / expected < 0.05


def test_parse_module_finds_entry():
    hlo = jax.jit(lambda x: x + 1).lower(jnp.zeros((4,))).compile().as_text()
    comps, entry = parse_module(hlo)
    assert entry is not None and entry in comps
