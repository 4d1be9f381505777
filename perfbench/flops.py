"""Operations and bytes by shape: what the algorithm needs, not what a
compiler happened to emit. Recomputed operations are never counted.

A multiply-add is 2 operations. Causal attention counts only the visible
half of the score matrix (t*(t+1)/2 pairs of t*t), so that no share of a
roofline can pass 100% by counting masked work.
"""
from __future__ import annotations

from typing import Any, Dict


def matmul_params(conf: Dict[str, Any]) -> Dict[str, int]:
    """Parameters that take part in a matrix multiplication for every token:
    the blocks' weight matrices and the tied head. The embedding lookup, the
    positions, LayerNorm scales and biases do none."""
    d, L, f, V = conf["n_embd"], conf["n_layer"], conf["n_inner"], \
        conf["vocab_size"]
    return {"blocks": L * (4 * d * d + 2 * d * f), "head": V * d}


def attention_forward_flops(t: int, n_head: int, head_dim: int,
                            causal: bool = True) -> float:
    """QK^T and PV of one sequence of t tokens, one layer, all heads."""
    pairs = t * (t + 1) / 2 if causal else t * t
    return 2 * 2 * pairs * head_dim * n_head


def train_flops_per_token(conf: Dict[str, Any], seq: int) -> float:
    """Forward and backward of one token at sequence length `seq`:
    6 x (block + head parameters) + causal attention, backward twice the
    forward. The optimizer's elementwise pass is not counted."""
    mp = matmul_params(conf)
    hd = conf["n_embd"] // conf["n_head"]
    attn = conf["n_layer"] * attention_forward_flops(
        seq, conf["n_head"], hd) / seq
    return 6.0 * (mp["blocks"] + mp["head"]) + 3.0 * attn


def forward_flops(conf: Dict[str, Any], new_tokens: int,
                  attended_pairs: float) -> float:
    """Serving: 2 x matmul parameters for each token processed, plus the
    attention over `attended_pairs` (query, visible key) pairs a layer."""
    mp = matmul_params(conf)
    hd = conf["n_embd"] // conf["n_head"]
    return (2.0 * (mp["blocks"] + mp["head"]) * new_tokens
            + conf["n_layer"] * 4.0 * attended_pairs * hd * conf["n_head"])


def flash_forward_call(rows: int, t: int, head_dim: int,
                       itemsize: int = 2) -> Dict[str, float]:
    """One call of the flash forward kernel on [rows = batch*heads, t,
    head_dim]: causal operations, and the bytes it has to move at the least
    (q, k, v read once, the output written once, the row log-sum-exp)."""
    flops = rows * 4.0 * (t * (t + 1) / 2) * head_dim
    byts = rows * (4.0 * t * head_dim * itemsize + 4.0 * t)
    return {"flops": flops, "bytes": byts}


def least_seconds(flops: float, byts: float, peaks: Dict[str, float]):
    """The roofline's least time and which bound applies."""
    tc = flops / peaks["bf16_flops"]
    tm = byts / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
