"""Model FLOPs per trained token, one function per configuration family.

Training counts three passes' worth of the forward's multiply-adds
(forward, and backward's two products), at 2 FLOPs per multiply-add.  The
recompute of full remat is not counted, nor are elementwise operations
(norms, activations, softmax, exponentials).  A configuration file names
its function under ``flops``.
"""
from __future__ import annotations

from typing import Callable, Dict


def dense_causal(model: Dict, seq_len: int) -> float:
    """Decoder-only transformer with tied embeddings and a SwiGLU or GELU
    MLP.  Attention is counted causal: a query at position i attends to
    i + 1 keys, (seq_len + 1) / 2 on average, for both ``QK^T`` and ``PV``."""
    d, layers, v = model["d_model"], model["num_layers"], model["vocab_size"]
    h, kvh = model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or d // h
    mlp_mats = 3 if model.get("mlp", "swiglu") == "swiglu" else 2
    per_layer = d * h * hd * 2 + d * kvh * hd * 2 + mlp_mats * d * model["d_ff"]
    head = d * v
    attn = 2 * h * hd * (seq_len + 1) / 2       # QK^T and PV per query
    forward = 2 * (layers * (per_layer + attn) + head)
    return 3.0 * forward


def ssd_chunked(model: Dict, seq_len: int) -> float:
    """Mamba-2 (SSD) stack with tied embeddings, counted as the chunked
    SSD algorithm at the configuration's chunk size Q: within a chunk the
    causal half of ``C B^T`` (N per pair) and of the masked matrix times x
    (H*P per pair), (Q + 1) / 2 pairs per token; the chunk states and the
    states' contribution to the output, H*P*N each per token; plus the
    in/out projections and the depthwise convolution."""
    s = model["ssm"]
    d, layers, v = model["d_model"], model["num_layers"], model["vocab_size"]
    d_in = s["expand"] * d
    nh, n, q = d_in // s["head_dim"], s["state_dim"], s["chunk_size"]
    hp = s["head_dim"]
    proj = d * (2 * d_in + 2 * n + nh) + d_in * d
    conv = s["conv_width"] * (d_in + 2 * n)
    pairs = (q + 1) / 2
    ssd = pairs * n + pairs * nh * hp + 2 * nh * hp * n
    forward = 2 * (layers * (proj + conv + ssd) + d * v)
    return 3.0 * forward


FUNCTIONS: Dict[str, Callable[[Dict, int], float]] = {
    "dense_causal": dense_causal,
    "ssd_chunked": ssd_chunked,
}


def per_token(config: Dict) -> float:
    return FUNCTIONS[config["flops"]](config["model"], config["train"]["seq_len"])
