"""Mamba-2 (SSD) stack with tied embeddings."""
from __future__ import annotations

from typing import Dict


def per_token(model: Dict, seq_len: int) -> float:
    """Counted as the chunked SSD algorithm at the configuration's chunk
    size Q: within a chunk the causal half of ``C B^T`` (N per pair) and of
    the masked matrix times x (H*P per pair), (Q + 1) / 2 pairs per token;
    the chunk states and the states' contribution to the output, H*P*N each
    per token; plus the in/out projections and the depthwise convolution."""
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
