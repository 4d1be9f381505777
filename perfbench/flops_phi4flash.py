"""Operations and bytes by shape for `phi4flash` (SambaY: Mamba-1 and window
differential attention in a self-decoder, one full attention layer whose
keys and values the cross-decoder reads, gated memory units): what the
algorithm needs, not what a compiler happened to emit. A multiply-add is 2
operations; attention counts the visible pairs only. Sizes come from the
configuration file's published keys and the sizes its `assumed` states.
"""
from __future__ import annotations

from typing import Any, Dict

from perfbench.reference_phi4flash import dims as _dims


def layer_matmul_params(conf: Dict[str, Any]) -> Dict[str, int]:
    """Parameters a token multiplies in ONE layer of each kind (the mixer's
    matrices and the MLP's; norms, biases, the conv, A, D and the lambda
    vectors do none), and the K and V projection alone of an attention
    layer."""
    m = _dims(conf)
    d, hd, di, n, r = m["d"], m["hd"], m["di"], m["N"], m["R"]
    qd, kv = m["heads"] * hd, m["kv_heads"] * hd
    mlp = 3 * d * m["f"]
    return {"mamba": d * 2 * di + di * (r + 2 * n) + r * di + di * d + mlp,
            "attention": d * (qd + 2 * kv) + qd * d + mlp,
            "cross": 2 * d * qd + mlp, "gmu": 2 * d * di + mlp,
            "kv_projection": 2 * d * kv}


def tick_params(conf: Dict[str, Any]) -> int:
    """What one decoded token multiplies: every layer and the tied head."""
    m = _dims(conf)
    lp = layer_matmul_params(conf)
    return (m["n_mamba"] * lp["mamba"] + m["n_attn"] * lp["attention"]
            + m["n_cross"] * lp["cross"] + m["n_gmu"] * lp["gmu"]
            + m["V"] * m["d"])


def admit_params(conf: Dict[str, Any]) -> int:
    """What one admitted prompt token multiplies: the self-decoder (its
    Mamba-1 and window layers, the memory layer) and the full attention
    layer's K and V projection; the layers after it never run over a
    prompt, and an admission makes no logits."""
    m = _dims(conf)
    lp = layer_matmul_params(conf)
    return (m["n_mamba"] * lp["mamba"] + (m["n_attn"] - 1) * lp["attention"]
            + lp["kv_projection"])


def scan_flops(conf: Dict[str, Any]) -> float:
    """The selective scan's operations for one token over the Mamba-1
    layers: a (channel, state) entry's decay product, its exponential
    argument, the input's outer product and its sum, and C's product and
    sum (6), and D x and the gate (3 a channel)."""
    m = _dims(conf)
    return float(m["n_mamba"] * (6 * m["di"] * m["N"] + 3 * m["di"]))


def attention_flops(conf: Dict[str, Any], layer_pairs: float) -> float:
    """Differential attention over `layer_pairs` (query, visible key) pairs
    summed over the layers that attend: a head pair scores two maps of hd
    and reads values of 2 hd with each (2 x (2 hd + 4 hd)), so 6 hd a query
    head."""
    m = _dims(conf)
    return 6.0 * layer_pairs * m["hd"] * m["heads"]


def window_pairs(conf: Dict[str, Any], n: int) -> float:
    """The pairs of a prefill of n tokens in ONE window layer."""
    w = min(n, _dims(conf)["window"])
    return w * (w + 1) / 2.0 + (n - w) * w


def admit_layer_pairs(conf: Dict[str, Any], n: int) -> float:
    """The pairs an admission of n tokens attends, over the window layers
    (the full layer's are computed only by the tick's readers)."""
    return (_dims(conf)["n_attn"] - 1) * window_pairs(conf, n)


def serve_flops(conf: Dict[str, Any], decoded: int, prefilled: int,
                layer_pairs: float) -> float:
    """Serving: 2 x the tick's parameters and a step of the scan for each
    decoded token, 2 x the admission's parameters and a step of the scan
    for each prefilled token, attention over `layer_pairs`."""
    return (2.0 * tick_params(conf) + scan_flops(conf)) * decoded \
        + (2.0 * admit_params(conf) + scan_flops(conf)) * prefilled \
        + attention_flops(conf, layer_pairs)


def scan_bytes(conf: Dict[str, Any], width: int) -> int:
    """The least an admission's scan at a bucket width moves, over the
    Mamba-1 layers: its inputs delta, delta x, B and C and its output y,
    float32, each once."""
    m = _dims(conf)
    return m["n_mamba"] * 4 * width * (3 * m["di"] + 2 * m["N"])


def kv_bytes_per_token(conf: Dict[str, Any], itemsize: int = 2) -> int:
    """K and V of one position in ONE attention layer's buffer."""
    m = _dims(conf)
    return 2 * m["kv_heads"] * m["hd"] * itemsize

