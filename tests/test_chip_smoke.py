"""chip_smoke.py: refuses to run without a TPU, and its one-chip phases run
end to end at smoke size on the CPU (the chip runs them at olmo-1b width)."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from repro.configs import get_smoke_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _ok_lines(stdout: str) -> list:
    out = []
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok"):
                out.append(line)
        except (ValueError, AttributeError):
            pass
    return out


def _run(script: str, cwd: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)  # the script finds src/ on its own
    return subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=300, env=env, cwd=cwd)


def test_fails_without_tpu():
    proc = _run(SCRIPT, ROOT)
    assert proc.returncode != 0
    assert not _ok_lines(proc.stdout), proc.stdout
    assert "needs a TPU" in proc.stderr


def test_fails_without_the_repo(tmp_path):
    lone = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run(str(lone), str(tmp_path))
    assert proc.returncode != 0
    assert not _ok_lines(proc.stdout), proc.stdout


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_at_smoke_size(chip_smoke):
    """Preempt, restore at the checkpointed step (state bit-equal) and a
    splice resize, with losses matching the uninterrupted reference."""
    out = chip_smoke.run_one_chip(get_smoke_config("olmo-1b"),
                                  get_smoke_config("mamba2-130m"),
                                  global_batch=4, seq_len=32)
    assert {"preempt", "restore", "resize"} <= set(out["events"])
    assert out["worst_rel_loss_diff"] <= chip_smoke.LOSS_RTOL
