"""Operations and bytes by shape for `smallthinker` (routed ReGLU experts in
every layer, window layers beside global ones): what the algorithm needs, not
what a compiler happened to emit. A multiply-add is 2 operations; attention
counts the visible pairs only, a window layer's capped at its window. Sizes
come from the configuration file's published keys, for the `n_layer` layers
held here.
"""
from __future__ import annotations

from typing import Any, Dict

from perfbench.reference_smallthinker import dims as _dims


def matmul_params(conf: Dict[str, Any]) -> Dict[str, int]:
    """Parameters that take part in a matrix multiplication for ONE token, by
    part: the four attention projections, the router, the
    `moe_num_active_primary_experts` experts the token is routed to (not the
    64 held), the head. The embedding lookup and the norm scales do none."""
    m = _dims(conf)
    d, qd, kv = m["d"], m["heads"] * m["hd"], m["kv_heads"] * m["hd"]
    return {
        "attn_proj": m["L"] * (d * (qd + 2 * kv) + qd * d),
        "router": m["L"] * d * m["E"],
        "experts_used": m["L"] * m["k"] * 3 * d * m["f"],
        "head": m["V"] * d,
    }


def layer_kinds(conf: Dict[str, Any]) -> Dict[str, int]:
    """How many of the layers held see every position, and how many a
    window."""
    m = _dims(conf)
    window = sum(bool(w) for w in conf["sliding_window_layout"][:m["L"]])
    return {"global": m["L"] - window, "window": window}


def causal_pairs(n: int, window: int = 0) -> float:
    """(query, visible key) pairs of a prefill of n tokens in one layer:
    every s <= t, or with a window only the newest `window` of them."""
    if not window or n <= window:
        return n * (n + 1) / 2.0
    return window * (window + 1) / 2.0 + float(n - window) * window


def attention_flops(conf: Dict[str, Any], layer_pairs: float) -> float:
    """Scores and values over `layer_pairs` (query, visible key) pairs summed
    over the layers (each layer's own count, a window layer's capped)."""
    m = _dims(conf)
    return 4.0 * layer_pairs * m["hd"] * m["heads"]


def forward_flops(conf: Dict[str, Any], new_tokens: int,
                  layer_pairs: float) -> float:
    """Serving: 2 x the parameters a token USES for each token processed
    (prefilled or decoded), plus attention over `layer_pairs`."""
    return 2.0 * sum(matmul_params(conf).values()) * new_tokens \
        + attention_flops(conf, layer_pairs)


def prefill_layer_pairs(conf: Dict[str, Any], n: int) -> float:
    """The pairs of a prefill of n tokens, summed over the layers held."""
    kinds = layer_kinds(conf)
    return kinds["global"] * causal_pairs(n) \
        + kinds["window"] * causal_pairs(n, _dims(conf)["window"])


def expert_bytes(conf: Dict[str, Any], itemsize: int = 2) -> int:
    """One expert's three matrices: the least a product that uses the expert
    reads of it, whatever computes it."""
    m = _dims(conf)
    return 3 * m["d"] * m["f"] * itemsize


def weight_bytes(conf: Dict[str, Any], itemsize: int = 2) -> Dict[str, int]:
    """What a decode tick reads of the weights at the most, by part: every
    expert of every layer, the attention and router matrices, the head."""
    m = _dims(conf)
    mp = matmul_params(conf)
    return {"experts": m["L"] * m["E"] * expert_bytes(conf, itemsize),
            "attention": (mp["attn_proj"] + mp["router"]) * itemsize,
            "head": mp["head"] * itemsize}


def kv_bytes_per_token(conf: Dict[str, Any], itemsize: int = 2) -> int:
    """K and V of one position over the layers held."""
    m = _dims(conf)
    return 2 * m["L"] * m["kv_heads"] * m["hd"] * itemsize
