"""FLOP counts and peak rates of the benchmark, against hand counts."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import flops, peaks, spec  # noqa: E402


def test_dense_hand_count():
    # d 4, 2 heads of 2, ff 8, 1 layer, vocab 10, seq 3
    m = {"d_model": 4, "num_layers": 1, "vocab_size": 10, "num_heads": 2,
         "num_kv_heads": 2, "d_ff": 8, "mlp": "swiglu"}
    qkvo = 4 * (4 * 4)                 # four 4x4 projections
    mlp = 3 * (4 * 8)                  # wi, wg, wo
    attn = 2 * 4 * 2                   # QK^T and PV: 4 wide, (3+1)/2 keys
    head = 4 * 10
    assert flops.dense_causal(m, 3) == 3 * 2 * (qkvo + mlp + attn + head)


def test_ssd_hand_count():
    # d 4, expand 2 -> d_in 8, head_dim 4 -> 2 heads, state 2, chunk 3
    m = {"d_model": 4, "num_layers": 1, "vocab_size": 10,
         "ssm": {"state_dim": 2, "head_dim": 4, "expand": 2,
                 "chunk_size": 3, "conv_width": 4}}
    proj = 4 * (2 * 8 + 2 * 2 + 2) + 8 * 4
    conv = 4 * (8 + 2 * 2)
    pairs = 2                          # (3 + 1) / 2 key positions per query
    ssd = pairs * 2 + pairs * 8 + 2 * (2 * 4 * 2)
    head = 4 * 10
    assert flops.ssd_chunked(m, 16) == 3 * 2 * (proj + conv + ssd + head)


@pytest.mark.parametrize("name,per_token", [
    ("olmo-1b-l4", 2_329_460_736.0),   # the counts PERF.md states
    ("mamba2-130m", 860_746_752.0),
])
def test_config_counts(name, per_token):
    assert flops.per_token(spec.config(name)) == per_token


@pytest.mark.parametrize("entry", spec.benchmark()["configs"],
                         ids=lambda c: c["name"])
def test_every_config_has_its_count_file(entry):
    cfg = spec.config(entry["name"])
    count = spec.counts(cfg["flops"]).per_token
    assert count(cfg["model"], cfg["train"]["seq_len"]) == flops.per_token(cfg)


def test_unknown_count_raises():
    with pytest.raises(KeyError, match="counts/no_such_count.py"):
        spec.counts("no_such_count")


def test_peaks_keyed_by_kind():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
