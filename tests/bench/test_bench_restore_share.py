"""`restore_built_share` reads the store's restore counters: the share of
the restored device bytes that were built anew, and nothing where no
restore ran in the window."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402


def _read(counters):
    return spec.metric_reader("restore_built_share")(
        {"program": {"counters": counters, "counts": {}, "totals": {}}})


@pytest.mark.parametrize("built, total, share", [
    (50, 100, 50.0), (100, 100, 100.0), (1, 3, 100.0 / 3)])
def test_share_of_the_restored_bytes_built(built, total, share):
    got = _read({"restore.bytes": total, "restore.bytes_built": built,
                 "ckpt.bytes": 7})
    assert got == pytest.approx(share)


@pytest.mark.parametrize("counters", [
    {}, {"ckpt.bytes": 100, "ckpt.bytes_new": 50},
    {"restore.bytes": 0, "restore.bytes_built": 0}])
def test_nothing_without_a_restore(counters):
    assert _read(counters) is None


def test_listed_for_the_churn_cell_alone():
    entry = next(m for m in spec.benchmark()["per_layer"]
                 if m["name"] == "restore_built_share")
    assert entry["workloads"] == ["mamba2-130m.churn"]
    assert entry["moves"] == "resume_s" and entry["layer"] == "mechanisms"
