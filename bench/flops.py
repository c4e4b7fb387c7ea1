"""Model FLOPs per trained token.

Training counts three passes' worth of the forward's multiply-adds
(forward, and backward's two products), at 2 FLOPs per multiply-add.  The
recompute of full remat is not counted, nor are elementwise operations
(norms, activations, softmax, exponentials).  A configuration file names
its count under ``flops``: ``bench/counts/<flops>.py`` (``spec.counts``).
"""
from __future__ import annotations

from typing import Dict

from bench import spec

dense_causal = spec.counts("dense_causal").per_token
ssd_chunked = spec.counts("ssd_chunked").per_token


def per_token(config: Dict) -> float:
    count = spec.counts(config["flops"]).per_token
    return count(config["model"], config["train"]["seq_len"])
