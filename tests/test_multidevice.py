"""Real multi-device execution: the sharded step must match single-device
numerics.  Runs in a subprocess (jax locks the host device count at first
init, so the main test process must stay at 1 device)."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import get_smoke_config
from repro.configs.base import TrainConfig
from repro.launch.mesh import make_mesh
from repro.models.frontend import synth_extra_inputs
from repro.parallel.sharding import batch_specs, param_specs, to_shardings
from repro.training.state import init_train_state
from repro.training.step import build_train_step

cfg = get_smoke_config("olmo-1b")
tcfg = TrainConfig(total_steps=10, warmup_steps=1, learning_rate=1e-3)
key = jax.random.PRNGKey(0)
state = init_train_state(cfg, tcfg, key)
tokens = jax.random.randint(key, (8, 64), 0, cfg.vocab_size)
batch = {"tokens": tokens, "labels": tokens}

# single-device reference
ref_step = jax.jit(build_train_step(cfg, tcfg, splice=1))
ref_state, ref_metrics = ref_step(state, batch)
ref_losses = [float(ref_metrics["loss"])]
ref_state2, m2 = ref_step(ref_state, batch)
ref_losses.append(float(m2["loss"]))

# sharded on a (2, 4) mesh: data x model
mesh = make_mesh((2, 4), ("data", "model"))
st_sh = to_shardings(param_specs(state, mesh), mesh)
b_sh = to_shardings(batch_specs(batch, mesh), mesh)
state_s = jax.device_put(state, st_sh)
batch_s = jax.device_put(batch, b_sh)
with mesh:
    step = jax.jit(build_train_step(cfg, tcfg, splice=1),
                   in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None))
    s1, mm1 = step(state_s, batch_s)
    s2, mm2 = step(s1, batch_s)
losses = [float(mm1["loss"]), float(mm2["loss"])]
print(json.dumps({"ref": ref_losses, "sharded": losses}))
"""


# chip_smoke.py's four-chip path at smoke size on four host devices
CHIP_SMOKE_4 = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util, json
spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
from repro.configs import get_smoke_config
out = cs.run_four_chips(get_smoke_config("olmo-1b"), global_batch=4,
                        seq_len=32)
print(json.dumps(out))
"""


def _run_child(script: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=900,
                          env=env, cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_step_matches_single_device():
    out = _run_child(SCRIPT)
    for a, b in zip(out["ref"], out["sharded"]):
        assert abs(a - b) / abs(a) < 1e-4, out


def test_chip_smoke_four_chip_path():
    """The (2, 2) barrier-carrying step: both data shards' need/ack reach
    the psum, and its losses match the one-device reference."""
    out = _run_child(CHIP_SMOKE_4)
    assert out["payloads"] == [[0, 0], [2, 0], [2, 2]]
