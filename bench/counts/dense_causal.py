"""Decoder-only transformer with tied embeddings and a SwiGLU or GELU MLP."""
from __future__ import annotations

from typing import Dict


def per_token(model: Dict, seq_len: int) -> float:
    """Attention is counted causal: a query at position i attends to i + 1
    keys, (seq_len + 1) / 2 on average, for both ``QK^T`` and ``PV``."""
    d, layers, v = model["d_model"], model["num_layers"], model["vocab_size"]
    h, kvh = model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or d // h
    mlp_mats = 3 if model.get("mlp", "swiglu") == "swiglu" else 2
    per_layer = d * h * hd * 2 + d * kvh * hd * 2 + mlp_mats * d * model["d_ff"]
    head = d * v
    attn = 2 * h * hd * (seq_len + 1) / 2       # QK^T and PV per query
    forward = 2 * (layers * (per_layer + attn) + head)
    return 3.0 * forward
