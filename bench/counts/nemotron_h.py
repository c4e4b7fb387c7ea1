"""Nemotron-H: Mamba-2, MoE and attention blocks after ``layer_pattern``,
an untied head; and the operations and bytes of its expert kernel.

Counted as ``flops.py`` states: three passes of the forward's
multiply-adds at 2 FLOPs each.  Mamba-2 blocks as the chunked SSD at the
configuration's chunk (``ssd_chunked.py``, with ``C B^T`` once per group);
attention causal, (seq_len + 1) / 2 keys per query; each MoE block's router
and shared expert on every token and the held experts at the routing's
expected share, ``top_k * held / num_experts`` expert FFNs per token."""
from __future__ import annotations

from typing import Dict, List, Tuple


def _ffn(d: int, f: int, mlp: str) -> int:
    return (3 if mlp == "swiglu" else 2) * d * f


def per_token(model: Dict, seq_len: int) -> float:
    d, v, mlp = model["d_model"], model["vocab_size"], model["mlp"]
    pattern = model["layer_pattern"]
    s, m = model["ssm"], model["moe"]
    h, p, n, g, q = (s["num_heads"], s["head_dim"], s["state_dim"],
                     s["n_groups"], s["chunk_size"])
    d_in, conv_ch = h * p, h * p + 2 * g * n
    pairs = (q + 1) / 2
    mamba = (d * (d_in + conv_ch + h) + d_in * d + s["conv_width"] * conv_ch
             + pairs * g * n + pairs * h * p + 2 * h * p * n)
    nh, kvh, hd = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    attn = 2 * d * hd * (nh + kvh) + 2 * nh * hd * (seq_len + 1) / 2
    held = m.get("num_held") or m["num_experts"]
    moe = (d * m["num_experts"] + _ffn(d, m["shared_expert_ff"], mlp)
           + m["top_k"] * held / m["num_experts"] * _ffn(d, model["d_ff"], mlp))
    per = {"M": mamba, "*": attn, "E": moe, "-": _ffn(d, model["d_ff"], mlp)}
    forward = 2 * (sum(per[c] for c in pattern) + d * v)
    return 3.0 * forward


def gmm_flops(rows: float, k: int, n: int) -> float:
    """One grouped matmul of ``rows`` routed rows, (rows, k) @ (k, n)."""
    return 2.0 * rows * k * n


def gmm_bytes(rows: float, k: int, n: int, groups: int,
              itemsize: int = 2) -> float:
    """The least it moves: its rows in (k wide) and out (n wide) and each
    group's (k, n) weights once, in bfloat16.  The weight gradient's
    transposed product (rows of k and of n in, (groups, k, n) out) moves
    the same."""
    return float(itemsize) * (rows * (k + n) + groups * k * n)


def gmm_passes(model: Dict) -> List[Tuple[int, int]]:
    """(k, n) of each expert-kernel product one MoE block runs per
    training step at full remat: for the up (d -> f; gate and up together
    for SwiGLU) and down (f -> d) products, the forward, its recompute, the
    input gradient and the weight gradient."""
    d, f = model["d_model"], model["d_ff"]
    up = 2 * f if model["mlp"] == "swiglu" else f
    return [(d, up)] * 2 + [(up, d), (d, up)] + [(f, d)] * 2 + [(d, f), (f, d)]


def gmm_roofline_s(model: Dict, rows: float, block_steps: float,
                   peak: Dict[str, float]) -> float:
    """Least time of the window's expert kernels: per MoE block and step,
    each product's larger of operations over the bf16 peak and bytes over
    the HBM bandwidth, at the window's mean rows per block and step."""
    m = model["moe"]
    held = m.get("num_held") or m["num_experts"]
    r = rows / block_steps
    one = sum(max(gmm_flops(r, k, n) / peak["bf16_flops"],
                  gmm_bytes(r, k, n, held) / peak["hbm_bytes_per_s"])
              for k, n in gmm_passes(model))
    return block_steps * one
