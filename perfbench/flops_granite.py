"""Operations and bytes by shape for `granitemoehybrid` without experts:
what the algorithm needs, not what a compiler happened to emit. A
multiply-add is 2 operations; causal attention counts the visible pairs only.
Sizes come from the configuration file's published keys.
"""
from __future__ import annotations

from typing import Any, Dict

from perfbench.reference_granite import dims as _dims


def matmul_params(conf: Dict[str, Any]) -> Dict[str, int]:
    """Parameters that take part in a matrix multiplication for every token,
    by part: the Mamba layers' two projections, the attention layers' four,
    every layer's gated MLP, the tied head. The embedding lookup, norm
    scales, the conv kernel and the per-head scalars do none."""
    m = _dims(conf)
    d = m["d"]
    return {
        "mamba_proj": m["n_mamba"] * (d * m["in"] + m["inner"] * d),
        "attn_proj": m["n_attn"] * (2 * d * d
                                    + 2 * d * m["kv_heads"] * m["hd"]),
        "mlp": m["L"] * 3 * d * m["f"],
        "head": m["V"] * d,
    }


def param_count(conf: Dict[str, Any]) -> int:
    """Every parameter, the tied embedding once."""
    m = _dims(conf)
    mp = matmul_params(conf)
    small = (m["n_mamba"] * (m["conv"] * m["K"] + m["conv"] + 3 * m["H"]
                             + m["inner"])
             + 2 * m["L"] * m["d"] + m["d"])
    return sum(mp.values()) + small


def scan_flops_per_token(conf: Dict[str, Any]) -> float:
    """One step of the recurrence in every Mamba layer: over the H x P x N
    elements of S the decay (1), the input's outer product (1 for delta x B
    taken per row, 1 to add) and the read-out against C (2); the conv's K
    taps a channel."""
    m = _dims(conf)
    return m["n_mamba"] * (5.0 * m["H"] * m["P"] * m["N"]
                           + 2.0 * m["K"] * m["conv"])


def forward_flops(conf: Dict[str, Any], new_tokens: int,
                  attended_pairs: float) -> float:
    """Serving: 2 x matmul parameters and one step of the scan for each
    token processed (prefilled or decoded: the chunked prefill is counted
    as the recurrence it computes, not as the products it is computed by),
    plus the attention layers' scores and values over `attended_pairs`
    (query, visible key) pairs a layer."""
    m = _dims(conf)
    per_token = 2.0 * sum(matmul_params(conf).values()) \
        + scan_flops_per_token(conf)
    return per_token * new_tokens \
        + m["n_attn"] * 4.0 * attended_pairs * m["hd"] * m["heads"]


def state_step_bytes(conf: Dict[str, Any], state_itemsize: int = 4,
                     tail_itemsize: int = 2) -> Dict[str, int]:
    """The least bytes one decode step of the recurrence moves for ONE live
    lane over all Mamba layers: S read once and written once, and the conv
    tail (K - 1 rows of xBC) likewise."""
    m = _dims(conf)
    return {
        "ssm": 2 * m["n_mamba"] * m["H"] * m["P"] * m["N"] * state_itemsize,
        "conv": 2 * m["n_mamba"] * (m["K"] - 1) * m["conv"] * tail_itemsize,
    }


def weight_bytes(conf: Dict[str, Any], itemsize: int = 2) -> int:
    """What a decode tick reads of the weights whatever the lanes: every
    matrix once (the embedding as the head), the small leaves too."""
    return param_count(conf) * itemsize


def kv_bytes_per_token(conf: Dict[str, Any], itemsize: int = 2) -> int:
    """K and V of one position over the attention layers."""
    m = _dims(conf)
    return 2 * m["n_attn"] * m["kv_heads"] * m["hd"] * itemsize
