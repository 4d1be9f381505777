"""Operations and bytes by shape for `qwen3_next` (Gated DeltaNet layers
beside gated full attention, routed experts of which this chip holds a
share, a shared expert): what the algorithm needs, not what a compiler
happened to emit. A multiply-add is 2 operations; attention counts the
visible pairs only. Sizes come from the configuration file's published keys,
for the `n_layer` layers held here.
"""
from __future__ import annotations

from typing import Any, Dict

from perfbench.reference_qwen3_next import dims as _dims


def matmul_params(conf: Dict[str, Any]) -> Dict[str, int]:
    """Parameters that take part in a matrix multiplication for ONE token
    on every layer whatever its routing, by part: the GDN and the attention
    projections, the router (all its experts), the shared expert and its
    gate, the head. The routed experts a token reaches are counted by the
    program, pair by pair (`expert_params`); the embedding lookup, the conv
    and the norm scales do none."""
    m = _dims(conf)
    d, hd = m["d"], m["hd"]
    kd, vd = m["Hk"] * m["dk"], m["Hv"] * m["dv"]
    return {
        "gdn_proj": m["n_gdn"] * d * (2 * kd + 2 * vd + 2 * m["Hv"] + vd),
        "attn_proj": m["n_full"] * (d * 2 * m["heads"] * hd
                                    + 2 * d * m["kv_heads"] * hd
                                    + m["heads"] * hd * d),
        "router": m["L"] * d * m["E"],
        "shared": m["L"] * (3 * d * m["fs"] + d),
        "head": m["V"] * d,
    }


def expert_params(conf: Dict[str, Any]) -> int:
    """One routed expert's three matrices: what one (row, expert) pair
    multiplies."""
    m = _dims(conf)
    return 3 * m["d"] * m["f"]


def expert_bytes(conf: Dict[str, Any], itemsize: int = 2) -> int:
    """One expert's three matrices: the least a product that uses the expert
    reads of it, whatever computes it."""
    return expert_params(conf) * itemsize


def used_by_a_token(conf: Dict[str, Any]) -> float:
    """Parameters a token uses here on average were its routing uniform:
    the dense part and top-k x held / routed experts a layer."""
    m = _dims(conf)
    return sum(matmul_params(conf).values()) + m["L"] * m["k"] \
        * m["held"] / m["E"] * expert_params(conf)


def delta_rule_flops(conf: Dict[str, Any]) -> float:
    """The gated delta rule's operations for one token over the GDN layers
    held, in its recurrent form (the least): per value head the decay of S
    (dk dv), S^T k, the outer update and S^T q (2 dk dv each)."""
    m = _dims(conf)
    return float(m["n_gdn"] * m["Hv"] * 7 * m["dk"] * m["dv"])


def delta_rule_chunked_flops(conf: Dict[str, Any], chunk: int = 64) -> float:
    """The same for one token in the chunked form of the admission, chunk
    C: K K^T and Q K^T (2 C dk each), the triangular solve over dk + dv
    columns (C (dk + dv)), the masked product's values (2 C dv), and the
    state's four products W S, Q S, K^T V' and the decay (6 dk dv)."""
    m = _dims(conf)
    dk, dv, c = m["dk"], m["dv"], chunk
    per_head = 4 * c * dk + c * (dk + dv) + 2 * c * dv + 6 * dk * dv
    return float(m["n_gdn"] * m["Hv"] * per_head)


def attention_flops(conf: Dict[str, Any], layer_pairs: float) -> float:
    """Scores and values over `layer_pairs` (query, visible key) pairs summed
    over the full-attention layers."""
    m = _dims(conf)
    return 4.0 * layer_pairs * m["hd"] * m["heads"]


def prefill_layer_pairs(conf: Dict[str, Any], n: int) -> float:
    """The pairs of a prefill of n tokens, summed over the attention layers
    held."""
    return _dims(conf)["n_full"] * n * (n + 1) / 2.0


def forward_flops(conf: Dict[str, Any], new_tokens: int, held_pairs: float,
                  layer_pairs: float) -> float:
    """Serving: for each token processed (prefilled or decoded) 2 x the
    dense parameters and the delta rule's step; 2 x an expert's parameters
    for each (row, held expert) pair; attention over `layer_pairs`."""
    return (2.0 * sum(matmul_params(conf).values())
            + delta_rule_flops(conf)) * new_tokens \
        + 2.0 * expert_params(conf) * held_pairs \
        + attention_flops(conf, layer_pairs)


def gdn_state_bytes(conf: Dict[str, Any]) -> int:
    """One lane's GDN state S over the GDN layers held, float32."""
    m = _dims(conf)
    return m["n_gdn"] * m["Hv"] * m["dk"] * m["dv"] * 4


def weight_bytes(conf: Dict[str, Any], itemsize: int = 2) -> Dict[str, int]:
    """What a decode tick reads of the weights at the most, by part: every
    held expert of every layer, the dense matrices, the head."""
    m = _dims(conf)
    mp = matmul_params(conf)
    return {"experts": m["L"] * m["held"] * expert_bytes(conf, itemsize),
            "dense": (sum(mp.values()) - mp["head"]) * itemsize,
            "head": mp["head"] * itemsize}


def kv_bytes_per_token(conf: Dict[str, Any], itemsize: int = 2) -> int:
    """K and V of one position over the attention layers held."""
    m = _dims(conf)
    return 2 * m["n_full"] * m["kv_heads"] * m["hd"] * itemsize
