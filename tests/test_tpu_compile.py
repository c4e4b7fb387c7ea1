"""Compile for a described TPU v5e (no chip attached): what the chip's
compiler refuses, or what does not fit its memory, fails here.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  All compiles stay in this one file, in the test's own
process, with the persistent compilation cache off (an entry written for a
described chip cannot be read back without one).
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.kernels.checksum.ops import fingerprint
from repro.kernels.fused_ce.ce import fused_ce_stats
from repro.kernels.swa_attention.ops import swa_attention
from repro.launch.mesh import make_mesh
from repro.parallel.sharding import batch_specs, param_specs, to_shardings
from repro.training.state import init_train_state
from repro.training.step import build_train_step

GiB = 1 << 30
V5E_HBM = 16 * GiB
HEADROOM = 1 * GiB  # what the runtime reserves and fragmentation costs
# the job chip_smoke.py runs: olmo-1b at published widths, 4 of 16 layers
OLMO = dataclasses.replace(get_config("olmo-1b"), num_layers=4)
BATCH, SEQ = 4, 2048
TCFG = TrainConfig(total_steps=10, warmup_steps=1, learning_rate=1e-3)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    """ShapeDtypeStructs of ``tree`` (arrays or shapes), each leaf placed by
    ``sharding``: one sharding for all, or a matching tree of them."""
    if not isinstance(sharding, (NamedSharding, SingleDeviceSharding)):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, sharding)
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _state_shapes(cfg):
    return jax.eval_shape(
        lambda: init_train_state(cfg, TCFG, jax.random.PRNGKey(0)))


def _batch_shapes():
    tok = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
    return {"tokens": tok, "labels": tok}


def _nbytes(tree):
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("splice", [1, 2])
def test_olmo_step_fits_one_v5e(one_chip, splice):
    """The executor's olmo step at the chip smoke batch fits 16 GiB beside
    the premium mamba2-130m state that shares the chip during a preempt."""
    state = _shapes(_state_shapes(OLMO), one_chip)
    batch = _shapes(_batch_shapes(), one_chip)
    flags = jax.ShapeDtypeStruct((1, 2), jnp.int32, sharding=one_chip)
    step = build_train_step(OLMO, TCFG, splice=splice, with_barrier=True)
    mem = jax.jit(step).lower(state, batch, flags).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    beside = _nbytes(_state_shapes(get_config("mamba2-130m")))
    assert used + beside + HEADROOM < V5E_HBM, (used / GiB, beside / GiB)


def test_sharded_step_has_barrier_allreduce(topo):
    """(2, 2) data x model step with the barrier: compiles for v5e:2x2 and
    psums the (need, ack) payload over the data axis on the chips."""
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    state = _state_shapes(OLMO)
    batch = _batch_shapes()
    st_sh = to_shardings(param_specs(state, mesh), mesh)
    b_sh = to_shardings(batch_specs(batch, mesh), mesh)
    f_sh = NamedSharding(mesh, P("data"))
    flags = jax.ShapeDtypeStruct((2, 2), jnp.int32, sharding=f_sh)
    with mesh:
        step = jax.jit(build_train_step(OLMO, TCFG, with_barrier=True,
                                        mesh=mesh),
                       in_shardings=(st_sh, b_sh, f_sh),
                       out_shardings=(st_sh, None))
        compiled = step.lower(_shapes(state, st_sh), _shapes(batch, b_sh),
                              flags).compile()
    hlo = compiled.as_text()
    # the barrier payload is the step's only int32[2] all-reduce, in two
    # groups of two: one group per model index, across the data axis
    assert re.search(r"= s32\[2\]\{[^}]*\} all-reduce\(.*"
                     r"replica_groups=\{\{\d+,\d+\},\{\d+,\d+\}\}", hlo), \
        [ln for ln in hlo.splitlines() if "all-reduce(" in ln][:8]
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < _nbytes(state)  # state is sharded


def _kernel_hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_fused_ce_compiles_at_olmo_widths(one_chip):
    d, v, t = OLMO.d_model, OLMO.vocab_size, BATCH * SEQ
    hlo = _kernel_hlo(
        lambda h, w, lab: fused_ce_stats(h, w, lab, interpret=False),
        jax.ShapeDtypeStruct((t, d), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((d, v), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((t,), jnp.int32, sharding=one_chip))
    assert "tpu_custom_call" in hlo


def test_swa_attention_compiles_at_h2o_danube_widths(one_chip):
    cfg = get_config("h2o-danube-3-4b")
    hd = cfg.resolved_head_dim()
    assert hd == 120 and cfg.sliding_window == 4096
    x = jax.ShapeDtypeStruct((1, 2 * cfg.sliding_window, cfg.num_heads, hd),
                             jnp.bfloat16, sharding=one_chip)
    hlo = _kernel_hlo(
        lambda q, k, v: swa_attention(q, k, v, window=cfg.sliding_window,
                                      interpret=False), x, x, x)
    assert "tpu_custom_call" in hlo


def test_fingerprint_compiles(one_chip):
    x = jax.ShapeDtypeStruct((1 << 22,), jnp.float32, sharding=one_chip)
    hlo = _kernel_hlo(lambda a: fingerprint(a, interpret=False), x)
    assert "tpu_custom_call" in hlo
