"""A serve-only LM block with mixers and feed-forwards of several kinds
under one residual stream, read from four families' published config.json
(``FAMILIES``): Mamba-2 layers beside grouped-query attention
(`granitemoehybrid` without experts, IBM Granite 4.0-H), attention whose
layers differ in their own data, window and rotary positions, over routed
experts (`smallthinker`, PowerInfer SmallThinker), Gated DeltaNet linear
attention beside gated full attention over routed experts and a shared one
(`qwen3_next`, Qwen3-Next), and the SambaY decoder-hybrid-decoder
(`phi4flash`, Phi-4-mini-flash-reasoning: Mamba-1 and window differential
attention, one full layer's keys and values read by cross-attention layers
beside gated memory units). A layer names its mixer (``layer_types``:
``mamba``, ``linear_attention``, ``selective`` (Mamba-1),
``cross_attention``, ``gmu``, or ``attention`` with ``rope`` and ``window``
as that layer's data) and the model its feed-forward (``ffn``: ``gated``
with its activation, or ``experts``); RMS normalisation and no biases but
where the model says otherwise (``layer_norm``, ``attn_bias``), four scalar
multipliers (embedding, residual, attention, logits; 1 where a family has
none), the head tied to the embedding or a matrix of its own. With ``u`` a
layer's normalised input:

  * every layer: ``h += r * Mixer(u)``, ``u = RMSNorm(h)``, then the
    feed-forward on ``x = RMSNorm(h)``: gated, ``h += r * W_down(act(g) *
    v)`` with ``[g | v] = W_up x``; or routed, ``h += r * sum over e in I
    of w_e W_down,e (act(W_gate,e x) * W_up,e x)`` where ``I`` are the
    ``moe_top_k`` largest router logits ``W_r u`` (float32; the router reads
    the layer's normalised input BEFORE the mixer) and ``w`` the softmax
    over the chosen: dropless, every expert held here
    (parallel/expert_parallel.dropless_experts). Logits ``W_head
    RMSNorm(h) / logits_scaling``, ``h0 = embedding_multiplier * E[tok]``.
  * attention: ``q = W_q u`` as ``n_heads`` heads of ``head_dim``, ``k, v``
    as ``n_kv_heads`` heads (KV head j serves query heads g*j .. g*j+g-1),
    no bias; where the layer has ``rope``, rotary positions on q and k over
    the whole head (theta ``rope_theta``, the two halves of a head paired)
    before K is stored; scores ``attention_multiplier * q k^T``, causal,
    and in a layer with a ``window`` w only ``t - w < s <= t``; softmax in
    float32.
  * Mamba-2 (one group): ``[z | xBC | dt] = W_in u``; ``xBC =
    silu(conv(xBC))``, a causal depthwise convolution of width ``ssm_conv``
    with bias; ``xBC -> x [H, P], B [N], C [N]``; ``delta = softplus(dt +
    dt_bias)``, ``A = -exp(A_log)`` per head; ``S_t = exp(delta_t A) S_{t-1}
    + delta_t x_t (outer) B_t``; ``y_t = S_t C_t + D x_t``; ``y =
    RMSNorm(y * silu(z))`` over the whole inner width; output ``W_out y``.

`qwen3_next` (Qwen3-Next) changes the pieces, each a field of the config
that defaults to the other families' behaviour:

  * norms zero-centred (``norm_offset``): ``x / sqrt(mean(x^2) + eps) * (1 +
    w)``, in every norm but the GDN output's, whose scale is a plain ``w``.
  * the feed-forward reads ``x = N_post(h)`` AFTER the mixer, the router too
    (``router_after_mixer``): ``r = W_r x`` over all ``moe_experts`` (float32),
    ``I`` its ``moe_top_k`` largest, weights ``softmax(r[I])``; only experts
    ``moe_first .. moe_first + moe_held - 1`` are held and computed (one
    chip's share of an expert-parallel layer: what the others would add is
    left out), beside a shared expert of width ``shared_expert`` that every
    row takes, ``sigmoid(w_sg . x) W_d,s (silu(W_g,s x) * W_u,s x)``.
  * gated attention (``attn_gate``, ``qk_norm``, ``rope_dims``): ``W_q u``
    viewed ``[H, 2 hd]`` splits a head into ``q | gate``; ``q = N_q(q)``, ``k
    = N_k(W_k u)`` per head over hd; rotary (rotate-half, pairs ``(j, j +
    rope_dims / 2)``) on the first ``rope_dims`` of a head only; causal
    softmax; ``o = W_o (att * sigmoid(gate))``.
  * Gated DeltaNet (``linear_attention``; ``gdn_k_heads`` key heads of
    ``gdn_k_dim``, ``gdn_v_heads`` value heads of ``gdn_v_dim``, r = value
    heads a key head): ``W_qkvz u`` viewed per key head as ``[q | k | v (r
    heads) | z (r heads)]``, ``W_ba u`` per key head as ``[b (r) | a (r)]``;
    ``x = [q | k | v]`` through a causal depthwise conv of ``gdn_conv``, no
    bias, then silu; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
    dt_bias)``; value head h reads key head h // r, ``q^ = q / sqrt(sum q^2
    + 1e-6) / sqrt(dk)``, ``k^ = k / sqrt(sum k^2 + 1e-6)``; per value head
    ``S [dk, dv]`` float32 from 0: ``S <- exp(g_t) S``, ``delta = beta_t (v_t
    - S^T k^_t)``, ``S <- S + k^_t (outer) delta``, ``o_t = S^T q^_t``; ``y =
    RMSNorm_w(o) * silu(z)`` per value head; output ``W_out y``.

`phi4flash` (SambaY) changes these, each a field of the config:

  * LayerNorm ``(x - mean) / sqrt(var + eps) * w + b`` in every norm
    (``layer_norm``), and ``W x + b`` in every attention projection
    (``attn_bias``).
  * differential attention (``diff_attn``): query heads 2i, 2i+1 are pair
    i, KV heads 2j, 2j+1 pair j, pair i reads pair i // 2; ``a1 =
    softmax(q1 k1^T s) [v1 | v2]``, ``a2 = softmax(q2 k2^T s) [v1 | v2]``;
    ``o = RMSNorm(a1 - lambda a2) * (1 - lambda_init)`` over the pair's 2
    hd, ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
    ``lambda_init = 0.8 - 0.6 exp(-0.3 l)`` at layer l. Both maps run in
    the attention kernels that exist: the arena's row read as heads of a
    KV pair side by side, each query head a row of that width zero where
    the other map's keys lie (``_pair_rows``).
  * Mamba-1 (``selective``; inner ``sel_inner``, state ``sel_state``,
    rank ``sel_dt_rank``): ``[x | z] = W_in u``; ``x = silu(conv(x) +
    b)``; ``[dt | B | C] = W_x x``; ``delta = softplus(W_dt dt + b_dt)``;
    ``S_t = exp(delta_t A) * S_{t-1} + B_t (delta_t x_t)`` with ``A =
    -exp(A_log)`` of its own a (state entry, channel), ``S [N, inner]``;
    ``y = (C_t S_t + D x) * silu(z)``; output ``W_out y``. Layer
    ``memory_layer`` keeps y as the memory m.
  * GMU (``gmu``): ``W_out (silu(W_in u) * m)``, m of the same token.
  * cross-attention (``cross_attention``): ``q = W_q u + b``, attending
    the last attention layer's keys and values (one buffer, written by
    that layer, read by all: ``KVGroup.readers``). The admission stops at
    that layer's K and V (``admit_layers``): nothing after it feeds a
    later token's state.

Two computations of the recurrence, one result: the admission prefill runs
it in chunks (``ssm_chunk``; inside a chunk a masked matrix product, between
chunks the carried ``S``), the decode tick one step on the lane's stored
``S`` and its last ``ssm_conv - 1`` rows of ``xBC``. The delta rule the
same way (``gdn_chunked``, ``gdn_step``): inside a chunk of ``gdn_chunk``
with ``G`` the cumulative sum of ``g``, ``A_ij = beta_i e^(G_i - G_j) k^_i .
k^_j`` for ``j < i``, ``T = (I + A)^-1``, ``W = T (beta e^G K^)``, ``U = T
(beta V)``; from the carried ``S0``: ``V' = U - W S0``, ``O = (Q^ e^G) S0 +
tril(Q^ K^T * e^(G_i - G_j)) V'``, ``S1 = e^(G_C) S0 + (K^ e^(G_C - G))^T V'``.
Two of attention too:
the admission attends a whole prompt by blocks of query rows over chunks of
keys (``attention_full``: no ``[H, T, T]`` scores at 8,192 positions, a
window layer reads its band; with bf16 products and Pallas on, one kernel
that writes no score at all, ops/pallas_prefill.py), the tick one query a
lane over the lane's own blocks (ops/pallas_paged.decode_attention: a Pallas
kernel on the chip, serving/paged.chunked_attention elsewhere; a lower bound a
lane in a window layer). And two of the expert layer, by the rows: every expert on
every lane in one batched product in the tick, rows sorted by expert through
grouped products in the admission.

What is held where. Weights once, in the config's compute dtype (bfloat16
under ``dtype_policy="performance"``): no float32 masters, no optimizer
state, so ``fit`` refuses. Leaves are stacked by kind on a leading layer
axis (``params["mamba"]``, ``params["attn"]``, ``params["mlp"]`` or
``params["moe"]``), but for the experts' two matrices, ONE BUFFER A LAYER:
a layer sliced out of a stack is copied for the grouped kernel. Per
request the model tells the paged decoder (``cache_needs``) that it keeps
keys and values for its attention layers only, with ``n_kv_heads`` heads,
in one KV group a distinct window (the layers that see every position, the
layers of each window: a pool and a per-lane table each, a window group's
lane holding only the blocks its window reaches),
and two state leaves a lane where it has Mamba layers: ``ssm`` ``[H, P, N]``
float32 and ``conv``
``[ssm_conv - 1, conv_dim]`` in the compute dtype (where it has GDN layers
``gdn`` ``[Hv, dk, dv]`` float32 and ``gdn_conv`` ``[gdn_conv - 1, 2 Hk dk +
Hv dv]``, where it has Mamba-1 layers ``selective`` ``[N, inner]`` float32
and ``selective_conv`` ``[sel_conv - 1, inner]``), ONE BUFFER A LAYER
(``[lanes, ...]`` each) so that the tick rewrites each in place and nothing
restacks 4 GB of state. They ride in the decoder's arena pytree beside
``k`` and ``v`` and are donated with it. K and V are one buffer an
attention layer too, ``[blocks + 1, block_tokens, n_kv_heads * head_dim]``:
a token's heads side by side in one row, which the chip stores as the
tick's scatter and gather want it (a last dimension of one head of 64 it
stores in another order, and re-laid every buffer twice a tick).

The decoder re-consumes a prompt's last token in the request's first tick
(serving/paged.py), so admission leaves the lane's state as of the token
BEFORE it: ``n_state = keep - 1`` real tokens feed the state, every later
position of the bucket carries ``delta = 0`` and feeds nothing into ``S``
or the conv tail.

Activations: the residual stream, the norms, the router's logits and
weights, the rotary angles, ``delta``, the decay and ``S``
are float32; every matrix product reads its activation in the weights'
dtype and accumulates in float32; ``xBC`` is rounded to the compute dtype
where it leaves the projection, which is what the stored tail holds.

Reference anchor: none in the DL4J 0.4 reference, whose recurrent layers
are LSTM and GRU cells (nn/layers/recurrent/GravesLSTM.java) and which has
neither attention nor experts; provenance
is Gu & Dao (Mamba-1), Ren et al. (SambaY), Sun et al. (YOCO), Ye et al.
(differential attention), Dao & Gu, "Transformers are SSMs" (Mamba-2 and
its chunked dual form),
Su et al. (rotary positions), Beltagy et al. (sliding windows), Gale et
al. (dropless experts), Yang et al., "Gated Delta Networks" (the gated
delta rule and its chunked form) and the published `granitemoehybrid`,
`smallthinker`, `qwen3_next` and `phi4flash` config.json. The plain float32
forms of the same equations are perfbench/reference_granite.py,
perfbench/reference_smallthinker.py, perfbench/reference_qwen3_next.py and
perfbench/reference_phi4flash.py.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.ops import dispatch
from deeplearning4j_tpu.ops.memory import CacheNeeds, KVGroup, StateLeaf

Params = Dict[str, Any]
MAMBA, ATTENTION, LINEAR = "mamba", "attention", "linear_attention"
SELECTIVE, CROSS, GMU = "selective", "cross_attention", "gmu"
KINDS = (MAMBA, ATTENTION, LINEAR, SELECTIVE, CROSS, GMU)
GATED, EXPERTS = "gated", "experts"
# the per-lane recurrent state leaves a model may hold (cache_needs)
STATE_LEAVES = ("ssm", "conv", "gdn", "gdn_conv", "selective",
                "selective_conv")
ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 256
    d_model: int = 64
    layer_types: Tuple[str, ...] = (MAMBA, MAMBA, ATTENTION, MAMBA, MAMBA)
    n_heads: int = 4
    n_kv_heads: int = 2
    d_ff: int = 128
    # the served context: a lane's block table and the smallest arena are
    # sized by it. The model has no position table, so this is a serving
    # limit and not a width.
    max_len: int = 256
    ssm_heads: int = 8
    ssm_head_dim: int = 16
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_chunk: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_eps: float = 1e-5
    dtype_policy: str = "performance"   # "strict": float32 weights (tests)
    # an attention layer's own data, one entry an ATTENTION layer in the
    # model's order (empty: none has it): rotary positions on q and k
    # (theta ``rope_theta``, the two halves of a head paired), and how many
    # of the newest positions it sees, the token's own among them (0: all)
    attn_head_dim: int = 0              # 0: d_model // n_heads
    rope: Tuple[bool, ...] = ()
    rope_theta: float = 10000.0
    window: Tuple[int, ...] = ()
    # the admission's attention multiplies in float32 (True), or reads q and
    # the probabilities in the arena's dtype as K and V are stored (False)
    attn_exact: bool = True
    # the feed-forward of every layer: GATED (``d_ff`` wide, W_up = gate |
    # up) or EXPERTS (``moe_experts`` routed ones of width ``d_ff``, the
    # ``moe_top_k`` largest router logits a token, softmax over the chosen,
    # dropless: parallel/expert_parallel.dropless_experts; the router reads
    # the layer's normalised input before the mixer); ``ffn_act`` gates both
    ffn: str = "gated"
    ffn_act: str = "silu"
    moe_experts: int = 0
    moe_top_k: int = 0
    tie_head: bool = True               # logits against the embedding
    # the routed experts held here, ``moe_held`` from ``moe_first`` (0: all
    # ``moe_experts``); a shared expert of this width beside them (0: none);
    # the router reads the feed-forward's normalised input, after the mixer
    moe_first: int = 0
    moe_held: int = 0
    shared_expert: int = 0
    router_after_mixer: bool = False
    # gated attention: q | gate a head out of W_q, q and k normalised a
    # head, rotary over the first ``rope_dims`` of a head (0: all of it)
    attn_gate: bool = False
    qk_norm: bool = False
    rope_dims: int = 0
    # RMSNorm scales as 1 + w (zero-centred) but the GDN output's
    norm_offset: bool = False
    # Gated DeltaNet (``linear_attention`` layers): key and value heads,
    # their widths, the conv over q | k | v, the admission's chunk
    gdn_k_heads: int = 0
    gdn_v_heads: int = 0
    gdn_k_dim: int = 128
    gdn_v_dim: int = 128
    gdn_conv: int = 4
    gdn_chunk: int = 64
    # phi4flash (SambaY): LayerNorm with a bias in every norm, biases on
    # the attention projections, differential attention (query heads 2i,
    # 2i+1 a pair over KV heads 2j, 2j+1), Mamba-1 (``selective``) layers
    # of inner width ``sel_inner``, state ``sel_state``, a rank
    # ``sel_dt_rank`` step and a conv of ``sel_conv``, the admission's scan
    # in time chunks of ``sel_chunk``; the Mamba-1 layer ``memory_layer``
    # (among all layers) keeps its gated output, which GMU layers gate;
    # cross-attention layers project q alone and read the last attention
    # layer's keys and values, and the admission stops at that projection
    layer_norm: bool = False
    attn_bias: bool = False
    diff_attn: bool = False
    sel_inner: int = 0
    sel_state: int = 16
    sel_dt_rank: int = 0
    sel_conv: int = 4
    sel_chunk: int = 128
    memory_layer: int = -1

    # what the decode pools ask of an expert layer: no row is dropped and a
    # row's output is its own, whoever shares its batch
    moe_dropless = True

    def __post_init__(self):
        bad = [t for t in self.layer_types if t not in KINDS]
        if bad or not self.layer_types:
            raise ValueError(f"layer_types must name one of {KINDS} for "
                             f"every layer, got {bad}")
        kinds = self.layer_types
        last_attention = max((i for i, t in enumerate(kinds)
                              if t == ATTENTION), default=len(kinds))
        if self.n_cross and (
                kinds.index(CROSS) < last_attention
                or self.window_of(self.n_attention - 1)
                in [self.window_of(j) for j in range(self.n_attention - 1)]):
            raise ValueError("cross-attention layers come after the last "
                             "attention layer, which pages alone")
        if self.n_gmu and not (
                0 <= self.memory_layer < kinds.index(GMU)
                and kinds[self.memory_layer] == SELECTIVE):
            raise ValueError("GMU layers come after the Mamba-1 layer "
                             "memory_layer")
        if (not self.attn_head_dim and self.d_model % self.n_heads) \
                or self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"d_model {self.d_model} / n_heads {self.n_heads} / "
                f"n_kv_heads {self.n_kv_heads} do not divide")
        for name in ("rope", "window"):
            if getattr(self, name) and \
                    len(getattr(self, name)) != self.n_attention:
                raise ValueError(f"{name} has one entry an attention layer "
                                 f"({self.n_attention}), or none")
        if self.ffn not in (GATED, EXPERTS) or self.ffn_act not in ACTS:
            raise ValueError(f"ffn {self.ffn!r} / ffn_act {self.ffn_act!r}: "
                             f"one of {(GATED, EXPERTS)} / {sorted(ACTS)}")
        if (self.ffn == EXPERTS) != bool(self.moe_experts) or \
                not 0 <= self.moe_top_k <= self.moe_experts:
            raise ValueError(
                f"ffn {self.ffn!r} with {self.moe_experts} experts, top "
                f"{self.moe_top_k}")
        if self.moe_first < 0 or self.moe_held < 0 or \
                self.moe_first + self.moe_count > self.moe_experts or \
                (self.ffn != EXPERTS and (self.shared_expert
                                          or self.router_after_mixer)):
            raise ValueError(
                f"experts {self.moe_first} .. {self.moe_first} + "
                f"{self.moe_count} of {self.moe_experts} held, a shared "
                f"expert of {self.shared_expert}, ffn {self.ffn!r}")
        if self.n_linear and (not self.gdn_k_heads or self.gdn_v_heads
                              % self.gdn_k_heads):
            raise ValueError(f"{self.gdn_v_heads} GDN value heads over "
                             f"{self.gdn_k_heads} key heads")
        if self.rope_dims % 2 or self.rope_dims > self.head_dim:
            raise ValueError(f"rotary over {self.rope_dims} of a head of "
                             f"{self.head_dim}")
        if self.n_selective and not (self.sel_inner and self.sel_dt_rank):
            raise ValueError("Mamba-1 layers need sel_inner and sel_dt_rank")
        if self.diff_attn and (self.n_heads % 2 or self.n_kv_heads % 2):
            raise ValueError("differential attention pairs its heads")

    @classmethod
    def from_published(cls, conf: Dict[str, Any], *, max_len: int,
                       dtype_policy: str = "performance") -> "HybridConfig":
        """From a published config.json's own keys, by the family its
        ``model_type`` names (``FAMILIES``; `granitemoehybrid` where it
        names none). Whatever of a family this module does not compute is
        refused by the family's reader."""
        kind = conf.get("model_type", "granitemoehybrid")
        if kind not in FAMILIES:
            raise ValueError(f"HybridLM reads no model_type {kind!r} (have "
                             f"{sorted(FAMILIES)})")
        return FAMILIES[kind](cls, conf, int(max_len), dtype_policy)

    # -- sizes ------------------------------------------------------------
    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.dtype_policy == "performance" \
            else jnp.float32

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_mamba(self) -> int:
        return sum(t == MAMBA for t in self.layer_types)

    @property
    def n_linear(self) -> int:
        return sum(t == LINEAR for t in self.layer_types)

    @property
    def n_attention(self) -> int:
        return sum(t == ATTENTION for t in self.layer_types)

    @property
    def n_selective(self) -> int:
        return sum(t == SELECTIVE for t in self.layer_types)

    @property
    def n_cross(self) -> int:
        return sum(t == CROSS for t in self.layer_types)

    @property
    def n_gmu(self) -> int:
        return sum(t == GMU for t in self.layer_types)

    @property
    def attend_shape(self) -> Tuple[int, int, int]:
        """(query rows, KV heads, head width) as the attention kernels see
        them: with differential attention the arena's row read as heads of
        a KV pair side by side, and each query head a row of that width,
        zero where the other map's keys lie (``_pair_rows``)."""
        if self.diff_attn:
            return self.n_heads, self.n_kv_heads // 2, 2 * self.head_dim
        return self.n_heads, self.n_kv_heads, self.head_dim

    def lambda_init(self, g: int) -> float:
        """Differential attention's lambda_init at layer g (among all)."""
        return 0.8 - 0.6 * math.exp(-0.3 * g)

    @property
    def admit_layers(self) -> int:
        """Layers the admission runs whole: with cross-attention layers it
        stops before the last attention layer, of which it projects K and
        V alone (and before everything after it)."""
        if self.n_cross:
            return max(i for i, t in enumerate(self.layer_types)
                       if t == ATTENTION)
        return self.n_layers

    @property
    def moe_count(self) -> int:
        """The routed experts held here."""
        return self.moe_held or self.moe_experts

    @property
    def moe_share(self) -> bool:
        """Only some of the routed experts are held here."""
        return self.moe_count < self.moe_experts

    @property
    def gdn_key_dim(self) -> int:
        return self.gdn_k_heads * self.gdn_k_dim

    @property
    def gdn_value_dim(self) -> int:
        return self.gdn_v_heads * self.gdn_v_dim

    @property
    def gdn_conv_dim(self) -> int:
        """q | k | v, the conv's width."""
        return 2 * self.gdn_key_dim + self.gdn_value_dim

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.d_model // self.n_heads

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    def rope_of(self, j: int) -> bool:
        return bool(self.rope) and self.rope[j]

    def window_of(self, j: int) -> int:
        return self.window[j] if self.window else 0

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_state

    @property
    def in_dim(self) -> int:
        return 2 * self.d_inner + 2 * self.ssm_state + self.ssm_heads

    def runs(self) -> List[Tuple[str, int, int, int]]:
        """Maximal runs of one layer kind: (kind, first layer, first index
        among the layers of that kind, length)."""
        out: List[Tuple[str, int, int, int]] = []
        seen = dict.fromkeys(KINDS, 0)
        for g, kind in enumerate(self.layer_types):
            if out and out[-1][0] == kind:
                out[-1] = out[-1][:3] + (out[-1][3] + 1,)
            else:
                out.append((kind, g, seen[kind], 1))
            seen[kind] += 1
        return out

    # -- what the paged decoder asks (serving/paged.py) -------------------
    def kv_groups(self) -> Tuple[KVGroup, ...]:
        """The attention layers by how they page: one group a distinct
        window (0, every position, first), each with its layers' indices
        among the attention layers. The group of the last attention layer
        is read by the cross-attention layers too (``readers``)."""
        sizes = sorted({self.window_of(j) for j in range(self.n_attention)})
        shared = self.window_of(self.n_attention - 1) if self.n_cross \
            else None
        return tuple(
            KVGroup(tuple(j for j in range(self.n_attention)
                          if self.window_of(j) == w), w,
                    **({"readers": 1 + self.n_cross} if w == shared else {}))
            for w in sizes)

    def cache_needs(self) -> CacheNeeds:
        dt = jnp.dtype(self.compute_dtype).name
        _, kv_heads, head_dim = self.attend_shape
        return CacheNeeds(
            kv_layers=self.n_attention, kv_heads=kv_heads,
            head_dim=head_dim, groups=self.kv_groups(),
            state=((StateLeaf("ssm", self.n_mamba,
                              (self.ssm_heads, self.ssm_head_dim,
                               self.ssm_state), "float32"),
                    StateLeaf("conv", self.n_mamba,
                              (self.ssm_conv - 1, self.conv_dim),
                              jnp.dtype(self.compute_dtype).name))
                   if self.n_mamba else ())
            + ((StateLeaf("gdn", self.n_linear,
                          (self.gdn_v_heads, self.gdn_k_dim, self.gdn_v_dim),
                          "float32"),
                StateLeaf("gdn_conv", self.n_linear,
                          (self.gdn_conv - 1, self.gdn_conv_dim),
                          jnp.dtype(self.compute_dtype).name))
               if self.n_linear else ())
            # Mamba-1's S laid [N, inner] (the state on the sublanes, the
            # channels on the lanes: [inner, 16] would pad 16 to 128)
            + ((StateLeaf("selective", self.n_selective,
                          (self.sel_state, self.sel_inner), "float32"),
                StateLeaf("selective_conv", self.n_selective,
                          (self.sel_conv - 1, self.sel_inner), dt))
               if self.n_selective else ()),
            kv_per_layer=True)

    def paged_decode_step(self, params, arena, tok, pos, tables):
        return paged_decode_step(params, arena, tok, pos, tables, self)

    def paged_admit(self, params, arena, window, write_table, lane):
        return paged_admit(params, arena, window, write_table, lane, self)

    @property
    def moe_rows_per_token(self) -> int:
        """(token, expert) rows a token makes through the expert layers."""
        return self.moe_top_k * self.n_layers if self.ffn == EXPERTS else 0

    def scan_chunks(self, width: int) -> int:
        """Chunks the admission prefill's scan walks at a bucket width (the
        Mamba-1 scan's time chunks, else the delta rule's where the model
        has no Mamba layer)."""
        if self.n_selective:
            return -(-width // self.sel_chunk)
        if not self.n_mamba:
            return -(-width // self.gdn_chunk)
        return width // _chunk_len(self, width)

    def admit_attend(self, width: int) -> str:
        """Which attention the admission runs at a bucket width: ``kernel``
        (ops/pallas_prefill.py) where Pallas is on, the products read bf16
        (not ``attn_exact``) and its tiles divide the width; ``xla`` (the
        by-blocks path of ``attention_full``) otherwise."""
        from deeplearning4j_tpu.ops import pallas_prefill
        from deeplearning4j_tpu.ops.pallas_kernels import pallas_enabled

        rows, kv_heads, head_dim = self.attend_shape
        fits = pallas_prefill.fits(width, rows // kv_heads, head_dim)
        return "kernel" if not self.attn_exact and fits and \
            pallas_enabled() else "xla"


# ---------------------------------------------------------------------------
# the families a published config.json can name
# ---------------------------------------------------------------------------


def _from_granite(cls, conf, max_len, dtype_policy):
    """`granitemoehybrid` without experts: Mamba-2 layers beside grouped-
    query attention, as ``layer_types`` lays them."""
    refused = [
        (conf.get("num_local_experts", 0) != 0, "experts"),
        (conf.get("mamba_n_groups", 1) != 1, "mamba_n_groups != 1"),
        (conf.get("position_embedding_type", "nope") != "nope",
         "positional encoding"),
        (conf.get("normalization_function", "rmsnorm") != "rmsnorm",
         "a norm other than rmsnorm"),
        (conf.get("hidden_act", "silu") != "silu", "hidden_act"),
        (bool(conf.get("attention_bias")), "attention_bias"),
        (bool(conf.get("mamba_proj_bias")), "mamba_proj_bias"),
        (not conf.get("mamba_conv_bias", True), "no mamba_conv_bias"),
        (not conf.get("tie_word_embeddings", True), "an untied head"),
        (conf["mamba_expand"] * conf["hidden_size"]
         != conf["mamba_n_heads"] * conf["mamba_d_head"],
         "mamba_expand x hidden_size != mamba_n_heads x mamba_d_head"),
    ]
    bad = [what for is_bad, what in refused if is_bad]
    if bad:
        raise ValueError("HybridLM does not compute: " + ", ".join(bad))
    return cls(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        layer_types=tuple(conf["layer_types"]),
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], max_len=max_len,
        ssm_heads=conf["mamba_n_heads"],
        ssm_head_dim=conf["mamba_d_head"],
        ssm_state=conf["mamba_d_state"], ssm_conv=conf["mamba_d_conv"],
        ssm_chunk=conf["mamba_chunk_size"],
        embedding_multiplier=float(conf["embedding_multiplier"]),
        residual_multiplier=float(conf["residual_multiplier"]),
        attention_multiplier=float(conf["attention_multiplier"]),
        logits_scaling=float(conf["logits_scaling"]),
        rms_eps=float(conf["rms_norm_eps"]), dtype_policy=dtype_policy)


def _from_smallthinker(cls, conf, max_len, dtype_policy):
    """`smallthinker`: every layer attention over grouped heads and
    routed ReGLU experts; ``rope_layout`` and ``sliding_window_layout``
    say layer by layer which have rotary positions and the window.
    ``n_layer`` (where the file has it) is how many of the
    ``num_hidden_layers`` are held here, from layer 0 on."""
    n = int(conf.get("n_layer", conf["num_hidden_layers"]))
    ropes = conf["rope_layout"][:n]
    windows = conf["sliding_window_layout"][:n]
    refused = [
        (conf.get("rope_scaling") is not None, "rope_scaling"),
        (not conf.get("moe_primary_router_apply_softmax", True),
         "a router without the softmax over the chosen"),
        (len(ropes) != n or len(windows) != n,
         "layouts shorter than the layers held"),
        (max_len > conf["max_position_embeddings"],
         "a served context past max_position_embeddings"),
    ]
    bad = [what for is_bad, what in refused if is_bad]
    if bad:
        raise ValueError("HybridLM does not compute: " + ", ".join(bad))
    return cls(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        layer_types=(ATTENTION,) * n,
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        attn_head_dim=conf["head_dim"],
        d_ff=conf["moe_ffn_hidden_size"], max_len=max_len,
        rope=tuple(bool(r) for r in ropes),
        rope_theta=float(conf["rope_theta"]),
        window=tuple(int(conf["sliding_window_size"]) if w else 0
                     for w in windows),
        attn_exact=False, ffn=EXPERTS, ffn_act="relu",
        moe_experts=conf["moe_num_primary_experts"],
        moe_top_k=conf["moe_num_active_primary_experts"],
        tie_head=bool(conf["tie_word_embeddings"]),
        embedding_multiplier=1.0, residual_multiplier=1.0,
        attention_multiplier=float(conf["head_dim"]) ** -0.5,
        logits_scaling=1.0, rms_eps=float(conf["rms_norm_eps"]),
        dtype_policy=dtype_policy)


def _from_qwen3_next(cls, conf, max_len, dtype_policy):
    """`qwen3_next`: layer i full attention where ``(i + 1) %
    full_attention_interval == 0``, else Gated DeltaNet; every layer's
    feed-forward routed experts beside a shared one. ``n_layer`` (where
    the file has it) is how many of the ``num_hidden_layers`` are held
    here, from layer 0 on; ``num_experts`` how many experts are held,
    from ``expert_first`` on, of the ``router_num_experts`` the router
    scores (both ``num_experts`` where the file names no share)."""
    n = int(conf.get("n_layer", conf["num_hidden_layers"]))
    every = int(conf["full_attention_interval"])
    kinds = tuple(ATTENTION if (i + 1) % every == 0 else LINEAR
                  for i in range(n))
    routed = int(conf.get("router_num_experts", conf["num_experts"]))
    rope_dims = conf["head_dim"] * float(conf["partial_rotary_factor"])
    refused = [
        (conf.get("decoder_sparse_step", 1) != 1
         or bool(conf.get("mlp_only_layers")), "dense feed-forward layers"),
        (conf.get("rope_scaling") is not None, "rope_scaling"),
        (bool(conf.get("use_sliding_window")), "a sliding window"),
        (bool(conf.get("attention_bias")), "attention_bias"),
        (conf.get("hidden_act", "silu") != "silu", "hidden_act"),
        (not conf.get("norm_topk_prob", True),
         "a router without the softmax over the chosen"),
        (n > conf["num_hidden_layers"], "more layers than published"),
        (rope_dims != int(rope_dims) or int(rope_dims) % 2,
         "a rotary share that is no even number of dims"),
        (max_len > conf["max_position_embeddings"],
         "a served context past max_position_embeddings"),
    ]
    bad = [what for is_bad, what in refused if is_bad]
    if bad:
        raise ValueError("HybridLM does not compute: " + ", ".join(bad))
    return cls(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        layer_types=kinds, n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        attn_head_dim=conf["head_dim"],
        d_ff=conf["moe_intermediate_size"], max_len=max_len,
        rope=(True,) * kinds.count(ATTENTION),
        rope_theta=float(conf["rope_theta"]), rope_dims=int(rope_dims),
        attn_exact=False, attn_gate=True, qk_norm=True,
        norm_offset=True, ffn=EXPERTS, ffn_act="silu",
        moe_experts=routed, moe_top_k=conf["num_experts_per_tok"],
        moe_first=int(conf.get("expert_first", 0)),
        moe_held=int(conf["num_experts"]),
        shared_expert=int(conf["shared_expert_intermediate_size"]),
        router_after_mixer=True,
        tie_head=bool(conf["tie_word_embeddings"]),
        gdn_k_heads=conf["linear_num_key_heads"],
        gdn_v_heads=conf["linear_num_value_heads"],
        gdn_k_dim=conf["linear_key_head_dim"],
        gdn_v_dim=conf["linear_value_head_dim"],
        gdn_conv=conf["linear_conv_kernel_dim"],
        embedding_multiplier=1.0, residual_multiplier=1.0,
        attention_multiplier=float(conf["head_dim"]) ** -0.5,
        logits_scaling=1.0, rms_eps=float(conf["rms_norm_eps"]),
        dtype_policy=dtype_policy)


def _from_phi4flash(cls, conf, max_len, dtype_policy):
    """`phi4flash` (SambaY): with n layers, below n // 2 Mamba-1 at even
    indices and differential attention over a window of ``sliding_window``
    at odd ones; layer n // 2 Mamba-1 that keeps its gated output as the
    memory; n // 2 + 1 differential attention over every position, whose
    keys and values every later odd layer reads (cross-attention, q alone);
    the later even layers GMU. LayerNorm with bias throughout, biases on
    the attention projections, no positions, the head tied. The Mamba-1
    sizes the config lacks are read where the file states them (the
    family's: state 16, conv 4, expand 2, a step of rank ceil(d / 16))."""
    n, d = int(conf["num_hidden_layers"]), int(conf["hidden_size"])
    half = n // 2
    kinds = tuple(
        (SELECTIVE if i % 2 == 0 else ATTENTION) if i < half
        else SELECTIVE if i == half else ATTENTION if i == half + 1
        else (GMU if i % 2 == 0 else CROSS) for i in range(n))
    rope = [k for k in conf if k.startswith("rope")
            or k in ("partial_rotary_factor", "position_embedding_type")]
    refused = [
        (bool(rope), "positions (" + ", ".join(sorted(rope)) + ")"),
        (conf.get("mb_per_layer", 2) != 2, "mb_per_layer other than 2"),
        (bool(conf.get("mlp_bias")), "mlp_bias"),
        (bool(conf.get("lm_head_bias")), "lm_head_bias"),
        (conf.get("hidden_act", "silu") != "silu", "hidden_act"),
        (not conf.get("tie_word_embeddings", True), "an untied head"),
        (n % 2 or n < 4, "an odd number of layers, or fewer than 4"),
        (max_len > conf["max_position_embeddings"],
         "a served context past max_position_embeddings"),
    ]
    bad = [what for is_bad, what in refused if is_bad]
    if bad:
        raise ValueError("HybridLM does not compute: " + ", ".join(bad))
    hd = d // conf["num_attention_heads"]
    n_window = sum(k == ATTENTION for k in kinds[:half])
    return cls(
        vocab_size=conf["vocab_size"], d_model=d, layer_types=kinds,
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], max_len=max_len,
        window=(int(conf["sliding_window"]),) * n_window + (0,),
        attn_exact=False, layer_norm=True, attn_bias=True, diff_attn=True,
        sel_inner=int(conf.get("mamba_expand", 2)) * d,
        sel_state=int(conf.get("mamba_d_state", 16)),
        sel_dt_rank=int(conf.get("mamba_dt_rank", math.ceil(d / 16))),
        sel_conv=int(conf.get("mamba_d_conv", 4)), memory_layer=half,
        tie_head=True, embedding_multiplier=1.0, residual_multiplier=1.0,
        attention_multiplier=float(hd) ** -0.5, logits_scaling=1.0,
        rms_eps=float(conf["layer_norm_eps"]), dtype_policy=dtype_policy)


# the families ``HybridConfig.from_published`` reads, by ``model_type``
FAMILIES = {"granitemoehybrid": _from_granite,
            "smallthinker": _from_smallthinker,
            "qwen3_next": _from_qwen3_next,
            "phi4flash": _from_phi4flash}

# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def param_shapes(cfg: HybridConfig) -> Params:
    """Every leaf's shape, stacked by layer kind; the experts' matrices
    (``moe.W_in`` = gate | up, ``moe.W_down``) one buffer a LAYER: the
    grouped product is a kernel call, and a layer sliced out of a stack is
    copied for it (0.75 GB a layer at the served size), where a buffer of
    its own is read in place."""
    d, f, nm, na, L = cfg.d_model, cfg.d_ff, cfg.n_mamba, cfg.n_attention, \
        cfg.n_layers
    kv, qd, e = cfg.n_kv_heads * cfg.head_dim, cfg.q_dim, cfg.moe_experts
    held, fs = cfg.moe_count, cfg.shared_expert
    ffn = {"mlp": {"norm2": (L, d), "W_up": (L, d, 2 * f),
                   "W_down": (L, f, d)}} if cfg.ffn == GATED else \
        {"moe": {"norm2": (L, d), "router": (L, d, e),
                 "W_in": ((held, d, 2 * f),) * L,
                 "W_down": ((held, f, d),) * L,
                 **({"shared_in": (L, d, 2 * fs), "shared_down": (L, fs, d),
                     "shared_gate": (L, d, 1)} if fs else {})}}
    head = {} if cfg.tie_head else {"head": (cfg.vocab_size, d)}
    nl = cfg.n_linear
    gdn = {"gdn": {
        "norm1": (nl, d),
        "W_qkvz": (nl, d, 2 * cfg.gdn_key_dim + 2 * cfg.gdn_value_dim),
        "W_ba": (nl, d, 2 * cfg.gdn_v_heads),
        "conv_w": (nl, cfg.gdn_conv, cfg.gdn_conv_dim),
        "A_log": (nl, cfg.gdn_v_heads), "dt_bias": (nl, cfg.gdn_v_heads),
        "norm_o": (nl, cfg.gdn_v_dim), "W_out": (nl, cfg.gdn_value_dim, d)}} \
        if nl else {}
    gated = {"Wq": (na, d, 2 * qd)} if cfg.attn_gate else {}
    qk = {"q_norm": (na, cfg.head_dim), "k_norm": (na, cfg.head_dim)} \
        if cfg.qk_norm else {}
    mamba = {"mamba": {
        "norm1": (nm, d), "W_in": (nm, d, cfg.in_dim),
        "conv_w": (nm, cfg.ssm_conv, cfg.conv_dim),
        "conv_b": (nm, cfg.conv_dim), "dt_bias": (nm, cfg.ssm_heads),
        "A_log": (nm, cfg.ssm_heads), "D": (nm, cfg.ssm_heads),
        "norm_y": (nm, cfg.d_inner), "W_out": (nm, cfg.d_inner, d)}} \
        if nm else {}
    out = {
        "embed": (cfg.vocab_size, d), "norm_f": (d,), **head, **ffn,
        **mamba, **gdn,
        "attn": {
            "norm1": (na, d), "Wq": (na, d, qd), "Wk": (na, d, kv),
            "Wv": (na, d, kv), "Wo": (na, qd, d), **gated, **qk},
    }
    _sambay_shapes(cfg, out)
    return out


def _sambay_shapes(cfg: HybridConfig, out: Params) -> None:
    """The phi4flash leaves, into ``out``: Mamba-1 (``selective``; A_log
    laid [N, inner] as its state is), the cross-attention layers' (q and o
    alone) and the GMU layers'; a bias beside every norm's scale with
    ``layer_norm``, beside every attention projection with ``attn_bias``,
    and differential attention's lambda vectors and pair norm."""
    d, hd, qd = cfg.d_model, cfg.head_dim, cfg.q_dim
    di, n, r = cfg.sel_inner, cfg.sel_state, cfg.sel_dt_rank
    ns, nc, ng = cfg.n_selective, cfg.n_cross, cfg.n_gmu
    if ns:
        out["selective"] = {
            "norm1": (ns, d), "W_in": (ns, d, 2 * di),
            "conv_w": (ns, cfg.sel_conv, di), "conv_b": (ns, di),
            "W_x": (ns, di, r + 2 * n), "W_dt": (ns, r, di),
            "dt_bias": (ns, di), "A_log": (ns, n, di), "D": (ns, di),
            "W_out": (ns, di, d)}
    if nc:
        out["cross"] = {"norm1": (nc, d), "Wq": (nc, d, qd),
                        "Wo": (nc, qd, d)}
    if ng:
        out["gmu"] = {"norm1": (ng, d), "W_in": (ng, d, di),
                      "W_out": (ng, di, d)}
    kv = cfg.n_kv_heads * hd
    for kind, stack in (("attn", out["attn"]), ("cross", out.get("cross"))):
        if stack is None:
            continue
        layers = stack["norm1"][0]
        if cfg.attn_bias:
            stack["bq"], stack["bo"] = (layers, qd), (layers, d)
            if kind == "attn":
                stack["bk"] = stack["bv"] = (layers, kv)
        if cfg.diff_attn:
            for name in ("lq1", "lk1", "lq2", "lk2"):
                stack[name] = (layers, hd)
            stack["sub"] = (layers, 2 * hd)
    if cfg.layer_norm:
        out["norm_f_b"] = (d,)
        out["mlp"]["norm2_b"] = out["mlp"]["norm2"]
        for kind in ("attn", "selective", "cross", "gmu"):
            if kind in out:
                out[kind]["norm1_b"] = out[kind]["norm1"]


def init_params(cfg: HybridConfig, key) -> Params:
    """Every weight from ``key``, rounded once to the compute dtype.
    Matrices Xavier-normal; norm scales 1; ``A_log = log(uniform(1,
    16))``, ``dt_bias`` the inverse softplus of a step log-uniform in
    [1e-3, 1e-1], ``D = 1``, the conv kernel uniform in +-1/sqrt(width)
    (the Mamba-2 family's convention). A size for tests and examples: a
    benchmark cell's weights are the plain reference's to make, leaf by
    leaf (perfbench/reference_*.py)."""
    shapes = param_shapes(cfg)
    ks = iter(jax.random.split(key, 16))
    f32 = jnp.float32

    def xavier(shape):
        std = np.sqrt(2.0 / (shape[-2] + shape[-1]))
        return jax.random.normal(next(ks), shape, f32) * np.float32(std)

    ones = lambda shape: jnp.ones(shape, f32)
    # a norm's scale: 1, or 0 where it is taken as 1 + w (zero-centred)
    scale = (lambda shape: jnp.zeros(shape, f32)) if cfg.norm_offset \
        else ones
    a = shapes["attn"]
    out = {}
    if "mamba" in shapes:
        m = shapes["mamba"]
        dt = jnp.exp(jax.random.uniform(next(ks), m["dt_bias"], f32,
                                        np.log(1e-3), np.log(1e-1)))
        bound = 1.0 / np.sqrt(cfg.ssm_conv)
    out["embed"] = jax.random.normal(next(ks), shapes["embed"], f32) \
        * np.float32(0.02)
    out["norm_f"] = scale(shapes["norm_f"])
    if "mamba" in shapes:
        out["mamba"] = {
            "norm1": ones(m["norm1"]), "W_in": xavier(m["W_in"]),
            "conv_w": jax.random.uniform(next(ks), m["conv_w"], f32,
                                         -bound, bound),
            "conv_b": jnp.zeros(m["conv_b"], f32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(next(ks), m["A_log"], f32,
                                                1.0, 16.0)),
            "D": ones(m["D"]), "norm_y": ones(m["norm_y"]),
            "W_out": xavier(m["W_out"])}
    out["attn"] = {"norm1": scale(a["norm1"]), "Wq": xavier(a["Wq"]),
                   "Wk": xavier(a["Wk"]), "Wv": xavier(a["Wv"]),
                   "Wo": xavier(a["Wo"])}
    if "mlp" in shapes:
        p = shapes["mlp"]
        out["mlp"] = {"norm2": scale(p["norm2"]), "W_up": xavier(p["W_up"]),
                      "W_down": xavier(p["W_down"])}
    # the leaves of the wider block draw from a stream of their own, so
    # that the others are what they were
    ks = iter(jax.random.split(jax.random.fold_in(key, 1),
                               2 + 2 * cfg.n_layers))
    if "moe" in shapes:
        p = shapes["moe"]
        out["moe"] = {"norm2": scale(p["norm2"]),
                      "router": xavier(p["router"]),
                      "W_in": tuple(xavier(one) for one in p["W_in"]),
                      "W_down": tuple(xavier(one) for one in p["W_down"])}
    if "head" in shapes:
        out["head"] = jax.random.normal(next(ks), shapes["head"], f32) \
            * np.float32(0.02)
    # the qwen3_next leaves: a stream of their own again; the GDN output
    # norm's scale is a plain w
    ks = iter(jax.random.split(jax.random.fold_in(key, 2), 16))
    if cfg.qk_norm:
        out["attn"]["q_norm"] = scale(a["q_norm"])
        out["attn"]["k_norm"] = scale(a["k_norm"])
    if cfg.shared_expert:
        p = shapes["moe"]
        for name in ("shared_in", "shared_down", "shared_gate"):
            out["moe"][name] = xavier(p[name])
    if "gdn" in shapes:
        g = shapes["gdn"]
        dt = jnp.exp(jax.random.uniform(next(ks), g["dt_bias"], f32,
                                        np.log(1e-3), np.log(1e-1)))
        bound = 1.0 / np.sqrt(cfg.gdn_conv)
        out["gdn"] = {
            "norm1": scale(g["norm1"]),
            "W_qkvz": xavier(g["W_qkvz"]), "W_ba": xavier(g["W_ba"]),
            "conv_w": jax.random.uniform(next(ks), g["conv_w"], f32,
                                         -bound, bound),
            "A_log": jnp.log(jax.random.uniform(next(ks), g["A_log"], f32,
                                                1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm_o": ones(g["norm_o"]), "W_out": xavier(g["W_out"])}
    if "selective" in shapes or cfg.layer_norm or cfg.attn_bias \
            or cfg.diff_attn:
        _sambay_init(cfg, shapes, out, jax.random.fold_in(key, 3))
    return jax.tree_util.tree_map(lambda x: x.astype(cfg.compute_dtype), out)


def _sambay_init(cfg: HybridConfig, shapes: Params, out: Params,
                 key) -> None:
    """The phi4flash leaves from their own stream: matrices Xavier-normal,
    norm and bias vectors and lambda vectors normal(0, 0.1) about 1 (a
    scale) or 0, ``A_log = log(1 .. N)`` a channel, ``dt_bias`` the inverse
    softplus of a step log-uniform in [1e-3, 1e-1], ``D = 1``, the conv
    uniform in +-1/sqrt(width)."""
    ks = iter(jax.random.split(key, 64))
    f32 = jnp.float32
    near = lambda shape, mean: mean + 0.1 * jax.random.normal(
        next(ks), shape, f32)

    def leaf(name, shape):
        if name.startswith("W"):
            std = np.sqrt(2.0 / (shape[-2] + shape[-1]))
            return jax.random.normal(next(ks), shape, f32) * np.float32(std)
        if name in ("norm1", "norm2", "sub"):
            return near(shape, 1.0)
        if name == "A_log":
            return jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[1] + 1, dtype=f32))[None, :, None], shape)
        if name == "D":
            return jnp.ones(shape, f32)
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(next(ks), shape, f32,
                                            np.log(1e-3), np.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if name == "conv_w":
            bound = 1.0 / np.sqrt(shape[1])
            return jax.random.uniform(next(ks), shape, f32, -bound, bound)
        return near(shape, 0.0)

    for kind in ("attn", "mlp", "selective", "cross", "gmu"):
        if kind in shapes:
            stack = out.setdefault(kind, {})
            for name, shape in shapes[kind].items():
                if name not in stack:
                    stack[name] = leaf(name, shape)
    if "norm_f_b" in shapes:
        out["norm_f_b"] = near(shapes["norm_f_b"], 0.0)


# ---------------------------------------------------------------------------
# the layer, piece by piece
# ---------------------------------------------------------------------------


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(g)


def _norm(x, g, cfg: HybridConfig, b=None):
    """The model's norm: RMSNorm of scale ``w``, or ``1 + w``
    (``norm_offset``); with ``layer_norm`` LayerNorm, ``(x - mean) /
    sqrt(var + eps) * w + b``."""
    if cfg.layer_norm:
        c = x - jnp.mean(x, axis=-1, keepdims=True)
        return c * lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True)
                             + cfg.rms_eps) * _f32(g) + _f32(b)
    if cfg.norm_offset:
        return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + cfg.rms_eps) * (1.0 + _f32(g))
    return _rms(x, g, cfg.rms_eps)


def _mm(x, w):
    """x [..., n] float32 against w [n, m]: the activation read in the
    weight's dtype, the sum in float32 (HIGHEST touches float32 weights
    only, the strict policy of the tests)."""
    return jnp.matmul(x.astype(w.dtype), w, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _layer(tree, i):
    """Layer i of leaves stacked on a leading layer axis (i static or
    traced: the slice is read where it is used, never restacked)."""
    return jax.tree_util.tree_map(
        lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), tree)


def _mlp(h, fp, cfg: HybridConfig):
    gv = _mm(_norm(h, fp["norm2"], cfg, fp.get("norm2_b")), fp["W_up"])
    g, v = gv[..., :cfg.d_ff], gv[..., cfg.d_ff:]
    return h + cfg.residual_multiplier * _mm(ACTS[cfg.ffn_act](g) * v,
                                             fp["W_down"])


def _router(u, params, g, cfg: HybridConfig):
    """Layer g's router logits [T, E] in float32 over ALL E experts, from
    ``u``: the layer's normalised input BEFORE its mixer (the tensor the
    mixer reads), or, with ``router_after_mixer``, the feed-forward's (which
    ``_ffn`` hands it). None where the feed-forward is gated, and for the
    mixer's input where the router reads after it."""
    if cfg.ffn != EXPERTS:
        return None
    w = lax.dynamic_index_in_dim(params["moe"]["router"], g, keepdims=False)
    return jnp.matmul(u, _f32(w), precision=lax.Precision.HIGHEST)


def _ffn(h, r, params, g, cfg: HybridConfig, scope: str, live=None):
    """Layer g's feed-forward on the stream h: gated, or the routed
    experts on the logits ``r`` that ``_router`` read before the mixer (or
    on its own logits of the normalised input, ``router_after_mixer``),
    experts ``moe_first ..`` of them held here (one chip, no exchange),
    and a shared expert where the model has one. Returns (h, how many held
    experts got a row of a ``live`` row, how many (live row, held expert)
    pairs the rows made; both None where gated)."""
    if cfg.ffn == GATED:
        return _mlp(h, _layer(params["mlp"], g), cfg), None, None
    # imported where a model of this kind is built, not with the module
    from deeplearning4j_tpu.parallel.expert_parallel import dropless_experts

    ep = params["moe"]
    x = _norm(h, lax.dynamic_index_in_dim(ep["norm2"], g, keepdims=False),
              cfg)
    if cfg.router_after_mixer:
        with jax.named_scope(scope + "_route"):
            r = _router(x, params, g, cfg)
    y, hit = dropless_experts(x, r, ep["W_in"][g], ep["W_down"][g],
                              top_k=cfg.moe_top_k, first=cfg.moe_first,
                              act=ACTS[cfg.ffn_act], live=live, scope=scope)
    pairs = None
    if cfg.moe_share:
        with jax.named_scope(scope + "_route"):
            local = lax.top_k(r, cfg.moe_top_k)[1] - cfg.moe_first
            held = (local >= 0) & (local < cfg.moe_count)
            if live is not None:
                held = held & live[:, None]
            pairs = jnp.sum(held, dtype=jnp.int32)
    if cfg.shared_expert:
        fs = cfg.shared_expert
        with jax.named_scope(scope.rsplit(".", 1)[0] + ".shared_expert"):
            gu = _mm(x, ep["shared_in"][g])
            gate = jax.nn.sigmoid(_mm(x, ep["shared_gate"][g]))
            y = y + gate * _mm(ACTS[cfg.ffn_act](gu[..., :fs])
                               * gu[..., fs:], ep["shared_down"][g])
    return h + cfg.residual_multiplier * y, hit, pairs


def _split_in(cfg: HybridConfig, zxbcdt):
    di, cd = cfg.d_inner, cfg.conv_dim
    return (zxbcdt[..., :di],
            zxbcdt[..., di:di + cd].astype(cfg.compute_dtype),
            zxbcdt[..., di + cd:])


def _split_conv(cfg: HybridConfig, xbc):
    di, n = cfg.d_inner, cfg.ssm_state
    x = xbc[..., :di].reshape(xbc.shape[:-1] + (cfg.ssm_heads,
                                               cfg.ssm_head_dim))
    return x, xbc[..., di:di + n], xbc[..., di + n:]


def _mamba_out(y, z, mp, cfg: HybridConfig):
    """Gated norm over the whole inner width, then the out-projection."""
    y = y.reshape(z.shape)
    return _mm(_rms(y * jax.nn.silu(z), mp["norm_y"], cfg.rms_eps),
               mp["W_out"])


def mamba_step(u, ssm, tail, mp, cfg: HybridConfig):
    """One step of the recurrence for every lane: u [S, d] (normalised),
    ssm [S, H, P, N] float32, tail [S, K-1, C] -> (out [S, d], ssm, tail)."""
    z, xbc, dt = _split_in(cfg, _mm(u, mp["W_in"]))
    with jax.named_scope("tick.ssm_conv"):
        win = jnp.concatenate([tail, xbc[:, None, :]], axis=1)  # [S, K, C]
        conv = jnp.sum(_f32(win) * _f32(mp["conv_w"])[None], axis=1) \
            + _f32(mp["conv_b"])
        x, b, c = _split_conv(cfg, jax.nn.silu(conv))
        tail = win[:, 1:]
    with jax.named_scope("tick.ssm_step"):
        delta = jax.nn.softplus(dt + _f32(mp["dt_bias"]))        # [S, H]
        decay = jnp.exp(-delta * jnp.exp(_f32(mp["A_log"])))
        ssm = ssm * decay[:, :, None, None] \
            + (delta[:, :, None] * x)[..., None] * b[:, None, None, :]
        y = jnp.sum(ssm * c[:, None, None, :], axis=-1) \
            + _f32(mp["D"])[None, :, None] * x
    return _mamba_out(y, z, mp, cfg), ssm, tail


def _chunk_len(cfg: HybridConfig, t: int) -> int:
    """The scan's chunk at a sequence of t positions: ``ssm_chunk`` where
    it divides t, else the largest divisor they share (a bucket of 384
    walks three chunks of 128); a shorter sequence is one chunk."""
    return t if t <= cfg.ssm_chunk else math.gcd(t, cfg.ssm_chunk)


def mamba_chunked(u, n_state, mp, cfg: HybridConfig):
    """The recurrence over one sequence in chunks: u [T, d] (normalised),
    ``n_state`` (traced) the positions that feed the state. Returns (out
    [T, d], ssm [H, P, N] and tail [K-1, C] as of position n_state - 1).
    A position at or past ``n_state`` carries delta = 0: no decay, no
    input. Products in float32 at HIGHEST: the state is what every later
    token of the request is computed from."""
    t = u.shape[0]
    k1 = cfg.ssm_conv - 1
    hi = lax.Precision.HIGHEST
    z, xbc, dt = _split_in(cfg, _mm(u, mp["W_in"]))
    padded = jnp.concatenate(
        [jnp.zeros((k1, cfg.conv_dim), xbc.dtype), xbc], axis=0)
    w = _f32(mp["conv_w"])
    conv = sum(_f32(padded[k:k + t]) * w[k] for k in range(cfg.ssm_conv)) \
        + _f32(mp["conv_b"])
    # padded[i] is xbc[i - k1]: rows n_state - k1 .. n_state - 1
    tail = lax.dynamic_slice_in_dim(padded, n_state, k1, axis=0)
    x, b, c = _split_conv(cfg, jax.nn.silu(conv))
    feeds = (jnp.arange(t) < n_state)[:, None]
    delta = jnp.where(feeds, jax.nn.softplus(dt + _f32(mp["dt_bias"])), 0.0)
    a = -delta * jnp.exp(_f32(mp["A_log"]))                  # [T, H] <= 0
    q = _chunk_len(cfg, t)
    nc = t // q
    ch = lambda v: v.reshape((nc, q) + v.shape[1:])
    x, b, c, a = ch(x), ch(b), ch(c), ch(a)
    dx = ch(delta)[..., None] * x                            # [nc, q, H, P]
    cs = jnp.cumsum(a, axis=1)                               # [nc, q, H]
    # inside a chunk: y_t = sum_{s<=t} exp(cs_t - cs_s) (C_t . B_s) dx_s
    seen = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]
    lmat = jnp.exp(jnp.where(seen, cs[:, :, None, :] - cs[:, None, :, :],
                             -jnp.inf))                      # [nc, q, q, H]
    g = jnp.einsum("cqn,ckn->cqk", c, b, precision=hi)
    y = jnp.einsum("cqkh,ckhp->cqhp", g[..., None] * lmat, dx, precision=hi)
    # what a chunk adds to the state at its end, and its whole decay
    s_add = jnp.einsum("ckh,ckhp,ckn->chpn", jnp.exp(cs[:, -1:, :] - cs),
                       dx, b, precision=hi)
    s_dec = jnp.exp(cs[:, -1, :])                            # [nc, H]

    def carry(s, xs):
        add, dec = xs
        return s * dec[:, None, None] + add, s

    s0 = jnp.zeros((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                   jnp.float32)
    ssm, s_in = lax.scan(carry, s0, (s_add, s_dec))
    y = y + jnp.einsum("cqn,chpn,cqh->cqhp", c, s_in, jnp.exp(cs),
                       precision=hi)
    y = y + _f32(mp["D"])[None, None, :, None] * x
    return _mamba_out(y.reshape(t, cfg.d_inner), z, mp, cfg), ssm, tail


def _gdn_in(u, lp, cfg: HybridConfig):
    """The GDN projections of rows u [n, d]: (x [n, conv_dim] = q | k | v
    in the compute dtype, what the conv and its stored tail read; z [n, Hv,
    dv]; b, a [n, Hv]). ``W_qkvz`` is laid a key head at a time, ``[q | k |
    v (r heads) | z (r heads)]``, ``W_ba`` ``[b (r) | a (r)]``."""
    n = u.shape[0]
    hk, dk, dv = cfg.gdn_k_heads, cfg.gdn_k_dim, cfg.gdn_v_dim
    r = cfg.gdn_v_heads // hk
    qkvz = _mm(u, lp["W_qkvz"]).reshape(n, hk, 2 * dk + 2 * r * dv)
    ba = _mm(u, lp["W_ba"]).reshape(n, hk, 2 * r)
    flat = lambda a: a.reshape(n, -1)
    x = jnp.concatenate([flat(qkvz[..., :dk]), flat(qkvz[..., dk:2 * dk]),
                         flat(qkvz[..., 2 * dk:2 * dk + r * dv])], axis=-1)
    z = qkvz[..., 2 * dk + r * dv:].reshape(n, cfg.gdn_v_heads, dv)
    return (x.astype(cfg.compute_dtype), z,
            flat(ba[..., :r]), flat(ba[..., r:]))


def _gdn_qkv(xc, b, a, lp, cfg: HybridConfig):
    """After the conv and silu, xc [n, conv_dim] float32: (q^, k^ [n, Hv,
    dk], each value head reading key head h // r, unit-normalised, q^ over
    sqrt(dk); v [n, Hv, dv]; beta, g [n, Hv] float32)."""
    n = xc.shape[0]
    kd, r = cfg.gdn_key_dim, cfg.gdn_v_heads // cfg.gdn_k_heads
    heads = lambda c: jnp.repeat(c.reshape(n, cfg.gdn_k_heads,
                                           cfg.gdn_k_dim), r, axis=1)
    unit = lambda c: c * lax.rsqrt(jnp.sum(c * c, -1, keepdims=True) + 1e-6)
    q = unit(heads(xc[:, :kd])) * np.float32(cfg.gdn_k_dim ** -0.5)
    k = unit(heads(xc[:, kd:2 * kd]))
    v = xc[:, 2 * kd:].reshape(n, cfg.gdn_v_heads, cfg.gdn_v_dim)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(_f32(lp["A_log"])) * jax.nn.softplus(a + _f32(lp["dt_bias"]))
    return q, k, v, beta, g


def _gdn_out(o, z, lp, cfg: HybridConfig):
    """The gated norm a value head (a plain scale ``w``), then W_out."""
    y = _rms(o, lp["norm_o"], cfg.rms_eps) * jax.nn.silu(z)
    return _mm(y.reshape(o.shape[0], cfg.gdn_value_dim), lp["W_out"])


def gdn_step(u, state, tail, lp, cfg: HybridConfig):
    """One step of the gated delta rule for every lane: u [S, d]
    (normalised), state [S, Hv, dk, dv] float32, tail [S, K-1, conv_dim]
    -> (out [S, d], state, tail). The state's products are elementwise
    sums in float32."""
    x, z, b, a = _gdn_in(u, lp, cfg)
    with jax.named_scope("tick.gdn_conv"):
        win = jnp.concatenate([tail, x[:, None, :]], axis=1)   # [S, K, C]
        xc = jax.nn.silu(jnp.sum(_f32(win) * _f32(lp["conv_w"])[None],
                                 axis=1))
        tail = win[:, 1:]
    with jax.named_scope("tick.gdn_step"):
        q, k, v, beta, g = _gdn_qkv(xc, b, a, lp, cfg)
        state = state * jnp.exp(g)[:, :, None, None]
        delta = beta[..., None] * (v - jnp.sum(state * k[..., None], axis=2))
        state = state + k[..., None] * delta[:, :, None, :]
        o = jnp.sum(state * q[..., None], axis=2)               # [S, Hv, dv]
    return _gdn_out(o, z, lp, cfg), state, tail


def gdn_chunked(u, n_state, lp, cfg: HybridConfig):
    """The gated delta rule over one sequence in chunks of ``gdn_chunk``:
    u [T, d] (normalised), ``n_state`` (traced) the positions that feed the
    state. Returns (out [T, d], state [Hv, dk, dv] and tail [K-1, conv_dim]
    as of position n_state - 1). A position at or past ``n_state`` carries
    g = 0 and beta = 0: it feeds nothing into S. T need not divide into
    chunks: the last is padded with such positions. Inside a chunk the
    products are float32 at HIGHEST and the triangular solve is float32;
    between chunks a scan carries S."""
    t = u.shape[0]
    k1 = cfg.gdn_conv - 1
    hi = lax.Precision.HIGHEST
    x, z, b, a = _gdn_in(u, lp, cfg)
    padded = jnp.concatenate(
        [jnp.zeros((k1, cfg.gdn_conv_dim), x.dtype), x], axis=0)
    w = _f32(lp["conv_w"])
    xc = jax.nn.silu(sum(_f32(padded[i:i + t]) * w[i]
                         for i in range(cfg.gdn_conv)))
    # padded[i] is x[i - k1]: rows n_state - k1 .. n_state - 1
    tail = lax.dynamic_slice_in_dim(padded, n_state, k1, axis=0)
    q, k, v, beta, g = _gdn_qkv(xc, b, a, lp, cfg)
    feeds = (jnp.arange(t) < n_state)[:, None]
    beta, g = jnp.where(feeds, beta, 0.0), jnp.where(feeds, g, 0.0)
    c = cfg.gdn_chunk
    nc = -(-t // c)
    # [nc, Hv, C, ...]: a chunk's rows of one value head together
    ch = lambda m: jnp.pad(m, ((0, nc * c - t),) + ((0, 0),) * (m.ndim - 1)
                           ).reshape((nc, c) + m.shape[1:]).swapaxes(1, 2)
    q, k, v, beta, g = ch(q), ch(k), ch(v), ch(beta), ch(g)
    with jax.named_scope("admit.gdn_scan"):
        gam = jnp.cumsum(g, axis=-1)                          # [nc, Hv, C]
        below = jnp.tril(jnp.ones((c, c), bool), -1)
        upto = jnp.tril(jnp.ones((c, c), bool))
        diff = gam[..., :, None] - gam[..., None, :]
        decay = jnp.exp(jnp.where(upto, diff, -jnp.inf))      # [.., C, C]
        kk = jnp.einsum("nhik,nhjk->nhij", k, k, precision=hi)
        amat = jnp.where(below, beta[..., None] * decay * kk, 0.0)
        eye = jnp.eye(c, dtype=jnp.float32)
        rhs = jnp.concatenate([(beta * jnp.exp(gam))[..., None] * k,
                               beta[..., None] * v], axis=-1)
        wu = jax.scipy.linalg.solve_triangular(
            eye + amat, rhs, lower=True, unit_diagonal=True)
        wk, uv = wu[..., :cfg.gdn_k_dim], wu[..., cfg.gdn_k_dim:]
        qk = jnp.einsum("nhik,nhjk->nhij", q, k, precision=hi) * decay
        qg = q * jnp.exp(gam)[..., None]
        kg = k * jnp.exp(gam[..., -1:] - gam)[..., None]
        last = jnp.exp(gam[..., -1])                          # [nc, Hv]

        def carry(s, xs):
            wk_, uv_, qk_, qg_, kg_, last_ = xs
            vp = uv_ - jnp.einsum("hck,hkv->hcv", wk_, s, precision=hi)
            o = jnp.einsum("hck,hkv->hcv", qg_, s, precision=hi) \
                + jnp.einsum("hij,hjv->hiv", qk_, vp, precision=hi)
            s = last_[:, None, None] * s \
                + jnp.einsum("hck,hcv->hkv", kg_, vp, precision=hi)
            return s, o

        s0 = jnp.zeros((cfg.gdn_v_heads, cfg.gdn_k_dim, cfg.gdn_v_dim),
                       jnp.float32)
        state, o = lax.scan(carry, s0, (wk, uv, qk, qg, kg, last))
    o = o.swapaxes(1, 2).reshape(nc * c, cfg.gdn_v_heads,
                                 cfg.gdn_v_dim)[:t]
    return _gdn_out(o, z, lp, cfg), state, tail


def _rope(x, pos, theta: float, dims: int = 0):
    """Rotary positions over the whole head, the two halves of a head
    paired: x [n, H, hd] float32 at positions pos [n]; with ``dims`` over
    the first ``dims`` of a head alone (their halves paired), the rest as
    it was."""
    if dims and dims < x.shape[-1]:
        return jnp.concatenate([_rope(x[..., :dims], pos, theta),
                                x[..., dims:]], axis=-1)
    half = x.shape[-1] // 2
    inv = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * np.float32(-np.log(theta) / half))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _qkv(u, ap, cfg: HybridConfig, j: int = 0, pos=None):
    """q, k, v of attention layer j (among the attention layers) for rows
    u [n, d] at positions pos [n], and the output's gate [n, H * hd] (None
    without ``attn_gate``): q and k normalised a head where the model says
    so, then rotary where the layer has it."""
    n = u.shape[0]
    gate = None
    if cfg.attn_gate:
        qg = _mm(u, ap["Wq"]).reshape(n, cfg.n_heads, 2 * cfg.head_dim)
        q, gate = qg[..., :cfg.head_dim], \
            qg[..., cfg.head_dim:].reshape(n, cfg.q_dim)
    else:
        q = _q(u, ap, cfg)
    k, v = _kv(u, ap, cfg)
    if cfg.qk_norm:
        q, k = _norm(q, ap["q_norm"], cfg), _norm(k, ap["k_norm"], cfg)
    if cfg.rope_of(j):
        q, k = _rope(q, pos, cfg.rope_theta, cfg.rope_dims), \
            _rope(k, pos, cfg.rope_theta, cfg.rope_dims)
    return q, k, v, gate


def _proj(u, w, b):
    """u W (+ b where the model has attention biases)."""
    out = _mm(u, w)
    return out if b is None else out + _f32(b)


def _q(u, ap, cfg: HybridConfig):
    return _proj(u, ap["Wq"], ap.get("bq")).reshape(
        u.shape[0], cfg.n_heads, cfg.head_dim)


def _kv(u, ap, cfg: HybridConfig):
    n = u.shape[0]
    k = _proj(u, ap["Wk"], ap.get("bk")).reshape(n, cfg.n_kv_heads,
                                                 cfg.head_dim)
    v = _proj(u, ap["Wv"], ap.get("bv")).reshape(n, cfg.n_kv_heads,
                                                 cfg.head_dim)
    return k, v


def _attn_out(att, gate, ap):
    """W_o over the heads' output [n, H * hd], under the gate's sigmoid
    where the layer has one (and its bias where it has one)."""
    if gate is not None:
        att = _f32(att) * jax.nn.sigmoid(gate)
    return _proj(att, ap["Wo"], ap.get("bo"))


def _pair_rows(q):
    """Differential attention's queries as the kernels read them: q [n, H,
    hd], heads 2i and 2i+1 a pair, -> [n, H, 2 hd], row 2i ``[q_2i | 0]``
    and row 2i+1 ``[0 | q_2i+1]``: against a KV pair's row ``[k_2j |
    k_2j+1]`` each scores its own map, and each reads both values."""
    n, h, hd = q.shape
    q = q.reshape(n, h // 2, 2, hd)
    zero = jnp.zeros_like(q[:, :, 0])
    return jnp.stack([jnp.concatenate([q[:, :, 0], zero], -1),
                      jnp.concatenate([zero, q[:, :, 1]], -1)],
                     axis=2).reshape(n, h, 2 * hd)


def _diff_out(att, ap, cfg: HybridConfig, g: int):
    """The pairs' two maps' outputs att [n, H, 2 hd] float32 -> [n, H * hd]:
    ``RMSNorm(a1 - lambda a2) * (1 - lambda_init)`` a pair, with ``lambda =
    exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`` of layer g."""
    n, h, w = att.shape
    a = att.reshape(n, h // 2, 2, w)
    init = np.float32(cfg.lambda_init(g))
    dot = lambda x, y: jnp.sum(_f32(ap[x]) * _f32(ap[y]))
    lam = jnp.exp(dot("lq1", "lk1")) - jnp.exp(dot("lq2", "lk2")) + init
    o = _rms(a[:, :, 0] - lam * a[:, :, 1], ap["sub"], cfg.rms_eps)
    return (o * (1.0 - init)).reshape(n, h // 2 * w)


def _selective_in(u, sp, cfg: HybridConfig):
    """Mamba-1's in-projection of rows u [n, d]: (x [n, inner] in the
    compute dtype, what the conv and its stored tail read; z [n, inner])."""
    xz = _mm(u, sp["W_in"])
    di = cfg.sel_inner
    return xz[..., :di].astype(cfg.compute_dtype), xz[..., di:]


def _selective_proj(xc, sp, cfg: HybridConfig):
    """After the conv and silu, xc [n, inner] float32: (delta [n, inner] =
    softplus(W_dt dt + dt_bias), B [n, N], C [n, N]) from ``[dt | B | C] =
    W_x xc``."""
    r, n = cfg.sel_dt_rank, cfg.sel_state
    dbc = _mm(xc, sp["W_x"])
    delta = jax.nn.softplus(_mm(dbc[:, :r], sp["W_dt"])
                            + _f32(sp["dt_bias"]))
    return delta, dbc[:, r:r + n], dbc[:, r + n:]


def _selective_out(y, xc, z, sp):
    """y + D x, gated by silu(z): the memory; and W_out of it."""
    y = (y + _f32(sp["D"]) * xc) * jax.nn.silu(z)
    return _mm(y, sp["W_out"]), y


def selective_step(u, state, tail, sp, cfg: HybridConfig):
    """One step of Mamba-1 for every lane: u [S, d] (normalised), state
    [S, N, inner] float32, tail [S, K-1, inner] -> (out [S, d], state, tail,
    the gated y [S, inner]). ``S_t = exp(delta_t A) S + (delta_t x_t) B_t``
    a channel and state entry, ``y_t = C_t S_t``: elementwise float32."""
    x, z = _selective_in(u, sp, cfg)
    with jax.named_scope("tick.selective_conv"):
        win = jnp.concatenate([tail, x[:, None, :]], axis=1)   # [S, K, di]
        xc = jax.nn.silu(jnp.sum(_f32(win) * _f32(sp["conv_w"])[None],
                                 axis=1) + _f32(sp["conv_b"]))
        tail = win[:, 1:]
    delta, b, c = _selective_proj(xc, sp, cfg)
    with jax.named_scope("tick.selective_step"):
        a = -jnp.exp(_f32(sp["A_log"]))                         # [N, di]
        state = jnp.exp(delta[:, None, :] * a[None]) * state \
            + b[:, :, None] * (delta * xc)[:, None, :]
        y = jnp.sum(state * c[:, :, None], axis=1)
    out, y = _selective_out(y, xc, z, sp)
    return out, state, tail, y


def selective_chunked(u, n_state, sp, cfg: HybridConfig):
    """Mamba-1 over one sequence: u [T, d] (normalised), ``n_state``
    (traced) the positions that feed the state. Returns (out [T, d], state
    [N, inner] and tail [K-1, inner] as of position n_state - 1, the gated
    y [T, inner]). A position at or past ``n_state`` carries delta = 0: no
    decay, no input. The scan walks time in chunks of ``sel_chunk`` with
    the state carried between them (ops/pallas_selective.selective_scan:
    a Pallas kernel over channel tiles on the chip)."""
    from deeplearning4j_tpu.ops.pallas_selective import selective_scan

    t, k1 = u.shape[0], cfg.sel_conv - 1
    x, z = _selective_in(u, sp, cfg)
    padded = jnp.concatenate([jnp.zeros((k1, cfg.sel_inner), x.dtype), x],
                             axis=0)
    w = _f32(sp["conv_w"])
    xc = jax.nn.silu(sum(_f32(padded[i:i + t]) * w[i]
                         for i in range(cfg.sel_conv)) + _f32(sp["conv_b"]))
    # padded[i] is x[i - k1]: rows n_state - k1 .. n_state - 1
    tail = lax.dynamic_slice_in_dim(padded, n_state, k1, axis=0)
    delta, b, c = _selective_proj(xc, sp, cfg)
    delta = jnp.where((jnp.arange(t) < n_state)[:, None], delta, 0.0)
    with jax.named_scope("admit.selective_scan"):
        y, state = selective_scan(delta, delta * xc, b, c,
                                  -jnp.exp(_f32(sp["A_log"])),
                                  chunk=cfg.sel_chunk)
    out, y = _selective_out(y, xc, z, sp)
    return out, state, tail, y


def _gmu(u, m, gp):
    """The gated memory unit: ``W_out (silu(W_in u) * m)``."""
    return _mm(jax.nn.silu(_mm(u, gp["W_in"])) * m, gp["W_out"])


# one pass's float32 scores [H, rows, keys] at the most, and the keys a pass
# reads at the most: a sequence past them is attended by blocks of query
# rows, each over chunks of keys (both halved from the sequence's length
# until they fit, so they divide it)
SCORE_BYTES = 1 << 29
KEY_CHUNK = 2048
MIN_ROWS = 128


def _attend_tiles(n_heads: int, t: int) -> Tuple[int, int]:
    """(query rows a block, keys a chunk) for a sequence of t positions."""
    keys = t
    while keys > KEY_CHUNK and keys % 2 == 0:
        keys //= 2
    rows = t
    while rows % 2 == 0 and rows > MIN_ROWS and \
            4 * n_heads * rows * keys > SCORE_BYTES:
        rows //= 2
    return rows, keys


def attention_full(u, ap, cfg: HybridConfig, kv_dtype, j: int = 0,
                   g: int = 0):
    """Causal grouped-query attention of attention layer j over one
    sequence u [T, d]; K and V are rounded to the arena's dtype first,
    which is what a later decode step will read. Returns (out [T, d], k, v
    [T, Hkv, hd]).

    By blocks of query rows over chunks of keys, folded into a running
    (max, denominator, accumulator) in float32, where the whole score
    matrix would pass SCORE_BYTES (28 heads at 8,192 positions: 7.5 GB in
    float32): a block walks only the chunks its rows can see, from the one
    position ``start - window + 1`` lies in (a window layer) or the first
    (a global one) to the one its last row lies in, so a window layer reads
    its band and a global layer the causal half. A sequence that fits is
    one block over one chunk: the plain softmax. The products multiply in
    float32 (``attn_exact``), or read q and the probabilities in the
    arena's dtype and sum in float32. Where ``cfg.admit_attend`` says
    ``kernel`` the same arithmetic runs in one Pallas call
    (ops/pallas_prefill.py) that writes no score to HBM."""
    t = u.shape[0]
    window = cfg.window_of(j)
    q, k, v, gate = _qkv(u, ap, cfg, j, jnp.arange(t))
    k, v = k.astype(kv_dtype), v.astype(kv_dtype)
    att = attend_sequence(q, k, v, cfg, kv_dtype, window)
    if cfg.diff_attn:
        return _attn_out(_diff_out(_f32(att).reshape(t, cfg.n_heads, -1),
                                   ap, cfg, g), gate, ap), k, v
    return _attn_out(att, gate, ap), k, v


def attend_sequence(q, k, v, cfg: HybridConfig, kv_dtype, window: int = 0):
    """Causal grouped attention of one sequence: q [T, H, hd], k and v [T,
    Hkv, hd] as stored (``kv_dtype``), scale ``attention_multiplier``, a
    window where one is given -> att [T, H * hd'] (hd' the width the
    kernels see: ``cfg.attend_shape``, a pair's with differential
    attention, whose rows ``_pair_rows`` lays). The kernel where
    ``cfg.admit_attend`` says so, else by blocks (``attention_full``)."""
    t = q.shape[0]
    hq, hk, hd = cfg.attend_shape
    grp = hq // hk
    hi = lax.Precision.HIGHEST
    if cfg.diff_attn:
        q = _pair_rows(q)
        k, v = k.reshape(t, hk, hd), v.reshape(t, hk, hd)
    if cfg.admit_attend(t) == "kernel":
        from deeplearning4j_tpu.ops.pallas_prefill import prefill_attention

        with jax.named_scope("admit.attend"):
            return prefill_attention(
                q.reshape(t, hq * hd).astype(kv_dtype), k.reshape(t, -1),
                v.reshape(t, -1), head_dim=hd,
                scale=cfg.attention_multiplier, window=window)
    # a KV head's products: its g query heads' rows side by side against
    # its keys, [Hkv, g * rows, keys], the keys innermost (with the scores
    # laid "kgts" out of one einsum the chip's compiler put the rows
    # innermost; and a softmax over 8,192 keys at once it took as a window
    # sliding over them, 0.7 s a layer: PERF.md section 6, PR 37)
    q = q.reshape(t, hk, grp, hd).transpose(1, 2, 0, 3)   # [Hkv, g, T, hd]
    if cfg.attn_exact:
        keys, vals = _f32(k), _f32(v)
    else:
        q, keys, vals = q.astype(kv_dtype), k, v
    keys, vals = keys.transpose(1, 0, 2), vals.transpose(1, 0, 2)
    rows, span = _attend_tiles(cfg.n_heads, t)

    def block(start):
        qb = q if rows == t else lax.dynamic_slice_in_dim(q, start, rows, 2)
        qb = qb.reshape(hk, grp * rows, hd)
        at = start + jnp.arange(rows)[:, None]

        def fold(c, carry):
            m, l, acc = carry
            kb = lax.dynamic_slice_in_dim(keys, c * span, span, 1)
            vb = lax.dynamic_slice_in_dim(vals, c * span, span, 1)
            sc = jnp.einsum("kqd,ksd->kqs", qb, kb, precision=hi,
                            preferred_element_type=jnp.float32) \
                * cfg.attention_multiplier
            key_at = c * span + jnp.arange(span)[None]
            see = key_at <= at
            if window:
                see = see & (key_at > at - window)
            sc = jnp.where(see[None, None],
                           sc.reshape(hk, grp, rows, span), -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            # a chunk none of whose keys a row sees leaves its max at -inf
            base = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(sc - base[..., None])
            corr = jnp.exp(m - base)
            l = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "kqs,ksd->kqd",
                p.reshape(hk, grp * rows, span).astype(vb.dtype), vb,
                precision=hi, preferred_element_type=jnp.float32)
            return m_new, l, acc * corr[..., None] \
                + pv.reshape(hk, grp, rows, hd)

        init = (jnp.full((hk, grp, rows), -jnp.inf, jnp.float32),
                jnp.zeros((hk, grp, rows), jnp.float32),
                jnp.zeros((hk, grp, rows, hd), jnp.float32))
        first = jnp.maximum(start - window + 1, 0) // span if window else 0
        _, l, acc = lax.fori_loop(first, (start + rows - 1) // span + 1,
                                  fold, init)
        return (acc / l[..., None]).transpose(2, 0, 1, 3)

    with jax.named_scope("admit.attend"):
        att = block(0) if rows == t else \
            lax.map(block, jnp.arange(0, t, rows))
    return att.reshape(t, hq * hd)


def _embed(params, tok, cfg: HybridConfig):
    return cfg.embedding_multiplier * _f32(params["embed"][tok])


def _head(params, h, cfg: HybridConfig):
    x = _norm(h, params["norm_f"], cfg, params.get("norm_f_b"))
    e = params["embed"] if cfg.tie_head else params["head"]
    return jnp.einsum("...d,vd->...v", x.astype(e.dtype), e,
                      precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32) \
        / cfg.logits_scaling


# ---------------------------------------------------------------------------
# one sequence, whole: the admission prefill and `output`
# ---------------------------------------------------------------------------


def prefill(params, tokens, n_state, cfg: HybridConfig, kv_dtype=None,
            whole: bool = True):
    """One sequence tokens [T] through every layer. Returns the residual
    stream h [T, d], K and V [n_attention, T, Hkv, hd] in ``kv_dtype``, and
    by name the recurrent state as of position ``n_state - 1``: ``ssm``
    [n_mamba, H, P, N] float32 and ``conv`` [n_mamba, K-1, C], ``gdn``
    [n_linear, Hv, dk, dv] float32 and ``gdn_conv`` [n_linear, K-1, C]
    where there are such layers; with a share of the experts held also
    ``moe_pairs``, the (row, held expert) pairs of the prompt's rows.

    Mamba-1 layers give ``selective`` [n_selective, N, inner] float32 and
    ``selective_conv`` [n_selective, K-1, inner]. With cross-attention
    layers and ``whole`` False the prefill stops at the last attention
    layer's K and V (``cfg.admit_layers``): what the layers after it would
    compute feeds no state, and h is then the stream before that layer.

    A model of gated feed-forwards without GDN or Mamba-1 layers runs its
    Mamba layers under a loop that reads each layer's weights out of the
    stacked leaves by index; any other unrolls its layers, as the tick
    does (an expert layer's matrices are buffers of their own)."""
    if kv_dtype is None:
        kv_dtype = cfg.compute_dtype
    t = tokens.shape[0]
    if cfg.ffn == EXPERTS or cfg.n_linear or cfg.n_selective:
        h, ks, vs, state = _prefill_unrolled(params, tokens, n_state, cfg,
                                             kv_dtype, whole)
    else:
        h, ks, vs, state = _prefill_runs(params, tokens, n_state, cfg,
                                         kv_dtype)
    empty = jnp.zeros((0, t, cfg.n_kv_heads, cfg.head_dim), kv_dtype)
    return (h, jnp.stack(ks) if ks else empty,
            jnp.stack(vs) if vs else empty, state)


def _prefill_runs(params, tokens, n_state, cfg: HybridConfig, kv_dtype):
    """`prefill` by runs of one layer kind, the Mamba runs under a loop:
    (h, K list, V list, state by name)."""
    r = cfg.residual_multiplier
    h = _embed(params, tokens, cfg)
    ssm = jnp.zeros((cfg.n_mamba, cfg.ssm_heads, cfg.ssm_head_dim,
                     cfg.ssm_state), jnp.float32)
    conv = jnp.zeros((cfg.n_mamba, cfg.ssm_conv - 1, cfg.conv_dim),
                     cfg.compute_dtype)
    ks, vs = [], []
    for kind, g0, j0, n in cfg.runs():
        if kind == MAMBA:
            def body(i, carry, g0=g0, j0=j0):
                h, ssm, conv = carry
                mp = _layer(params["mamba"], j0 + i)
                with jax.named_scope("admit.ssm_scan"):
                    out, s, tail = mamba_chunked(
                        _norm(h, mp["norm1"], cfg), n_state, mp, cfg)
                h = _mlp(h + r * out, _layer(params["mlp"], g0 + i), cfg)
                return (h, lax.dynamic_update_index_in_dim(ssm, s, j0 + i, 0),
                        lax.dynamic_update_index_in_dim(conv, tail,
                                                        j0 + i, 0))

            h, ssm, conv = lax.fori_loop(0, n, body, (h, ssm, conv))
        else:
            for i in range(n):
                ap = _layer(params["attn"], j0 + i)
                u = _norm(h, ap["norm1"], cfg)
                out, k, v = attention_full(u, ap, cfg, kv_dtype, j0 + i)
                ks.append(k)
                vs.append(v)
                h = _mlp(h + r * out, _layer(params["mlp"], g0 + i), cfg)
    return h, ks, vs, {"ssm": ssm, "conv": conv}


def _before_mixer(u, params, g, cfg: HybridConfig):
    """The router's logits where it reads the mixer's input, else None."""
    return None if cfg.router_after_mixer else _router(u, params, g, cfg)


def _prefill_unrolled(params, tokens, n_state, cfg: HybridConfig, kv_dtype,
                      whole: bool = True):
    """`prefill` a layer at a time: (h, K list, V list, state by name)."""
    t = tokens.shape[0]
    r = cfg.residual_multiplier
    h = _embed(params, tokens, cfg)
    live = jnp.arange(t) <= n_state if cfg.moe_share else None
    seen = dict.fromkeys(KINDS, 0)
    ks, vs, kept = [], [], {"ssm": [], "conv": [], "gdn": [],
                            "gdn_conv": [], "selective": [],
                            "selective_conv": []}
    pairs = jnp.zeros((), jnp.int32)
    memory = None
    for g, kind in enumerate(cfg.layer_types):
        j = seen[kind]
        seen[kind] += 1
        if kind == MAMBA:
            mp = _layer(params["mamba"], j)
            u = _norm(h, mp["norm1"], cfg)
            logits = _before_mixer(u, params, g, cfg)
            with jax.named_scope("admit.ssm_scan"):
                out, s, tail = mamba_chunked(u, n_state, mp, cfg)
            kept["ssm"].append(s)
            kept["conv"].append(tail)
        elif kind == LINEAR:
            lp = _layer(params["gdn"], j)
            u = _norm(h, lp["norm1"], cfg)
            logits = _before_mixer(u, params, g, cfg)
            out, s, tail = gdn_chunked(u, n_state, lp, cfg)
            kept["gdn"].append(s)
            kept["gdn_conv"].append(tail)
        elif kind == SELECTIVE:
            sp = _layer(params["selective"], j)
            u = _norm(h, sp["norm1"], cfg, sp.get("norm1_b"))
            logits = None
            out, s, tail, y = selective_chunked(u, n_state, sp, cfg)
            if g == cfg.memory_layer:
                memory = y
            kept["selective"].append(s)
            kept["selective_conv"].append(tail)
        elif kind == GMU:
            gp = _layer(params["gmu"], j)
            logits = None
            out = _gmu(_norm(h, gp["norm1"], cfg, gp.get("norm1_b")),
                       memory, gp)
        elif kind == CROSS:
            cp = _layer(params["cross"], j)
            u = _norm(h, cp["norm1"], cfg, cp.get("norm1_b"))
            logits = None
            att = attend_sequence(_q(u, cp, cfg), ks[-1], vs[-1], cfg,
                                  kv_dtype)
            out = _attn_out(_diff_out(_f32(att).reshape(t, cfg.n_heads, -1),
                                      cp, cfg, g), None, cp)
        else:
            ap = _layer(params["attn"], j)
            u = _norm(h, ap["norm1"], cfg, ap.get("norm1_b"))
            logits = _before_mixer(u, params, g, cfg)
            if g == cfg.admit_layers and not whole:
                # the admission of a model whose later layers read this
                # one's keys and values: they are all it needs of it
                with jax.named_scope("admit.yoco_kv"):
                    k, v = _kv(u, ap, cfg)
                ks.append(k.astype(kv_dtype))
                vs.append(v.astype(kv_dtype))
                break
            out, k, v = attention_full(u, ap, cfg, kv_dtype, j, g)
            ks.append(k)
            vs.append(v)
        h, _, held = _ffn(h + r * out, logits, params, g, cfg, "admit.moe",
                          live)
        if held is not None:
            pairs = pairs + held
    state = {name: jnp.stack(leaves) for name, leaves in kept.items()
             if leaves}
    if cfg.moe_share:
        state["moe_pairs"] = pairs
    return h, ks, vs, state


def forward(params, tokens, cfg: HybridConfig):
    """tokens [B, T] -> logits [B, T, V] float32 (every position feeds
    the state: no padding)."""
    t = tokens.shape[1]
    one = lambda row: _head(params, prefill(params, row, t, cfg)[0], cfg)
    # the grouped product of the expert layer has no batched form: its
    # rows go one sequence after the other
    return lax.map(one, tokens) if cfg.ffn == EXPERTS \
        else jax.vmap(one)(tokens)


# ---------------------------------------------------------------------------
# the paged decoder's two bodies (serving/paged.py builds the programs)
# ---------------------------------------------------------------------------


def paged_decode_step(params, arena, tok, pos, tables, cfg: HybridConfig):
    """One decode tick: tok, pos [S], tables [S, m] -> (arena, logits [S,
    V]). ``arena`` holds, a buffer an attention layer, k and v ``[blocks +
    1, bt, Hkv * hd]`` (block 0 trash) and, a buffer a Mamba layer, ``ssm``
    ``[S, H, P, N]`` and ``conv`` ``[S, K-1, C]`` indexed by lane. Attention layers scatter
    the token's K and V into (tables[s, pos // bt], pos % bt) and read the
    lane's blocks through ops/pallas_paged.decode_attention, grouped; Mamba
    layers advance EVERY lane's state one step (a dead lane's is never
    read: admission overwrites it whole). The layers are unrolled: each
    state buffer is read once and rewritten in place.

    A model whose attention layers page in several groups (window layers
    beside global ones: ``cfg.kv_groups``) is handed ``tables`` [G, S, m],
    a table a group; a window layer's buffers have its group's blocks, it
    reads through its group's table and sees ``pos - window < t <= pos``.
    With routed experts the tick also returns how many (layer, expert)
    pairs got the row of a live lane (one whose write block is not trash):
    (arena, logits, hit); where only a share of the experts is held, also
    how many (layer, live lane, held expert) triples the routing made:
    (arena, logits, hit, pairs). GDN layers advance every lane's ``gdn``
    and ``gdn_conv`` as Mamba layers do theirs, Mamba-1 layers their
    ``selective`` and ``selective_conv``. A cross-attention layer reads the
    last attention layer's buffers (written by it earlier in the same
    tick) through that layer's table; a GMU layer gates the memory layer's
    output of the same token."""
    from deeplearning4j_tpu.ops.pallas_paged import decode_attention

    s = tok.shape[0]
    bt = arena["k"][0].shape[1]
    r = cfg.residual_multiplier
    h = _embed(params, tok, cfg)
    groups = cfg.kv_groups()
    group_of = {j: gi for gi, grp in enumerate(groups)
                for j in grp.layer_ids}
    by_group = [tables] if len(groups) == 1 else list(tables)
    wbs = [jnp.take_along_axis(t, (pos // bt)[:, None], axis=1)[:, 0]
           for t in by_group]
    off = pos % bt
    live = wbs[0] != 0
    hits = jnp.zeros((), jnp.int32)
    pairs = jnp.zeros((), jnp.int32)
    ak, av = list(arena["k"]), list(arena["v"])
    state = {name: list(arena.get(name, ())) for name in STATE_LEAVES}
    seen = dict.fromkeys(KINDS, 0)
    memory = None
    for g, kind in enumerate(cfg.layer_types):
        j = seen[kind]
        seen[kind] += 1
        if kind == SELECTIVE:
            sp = _layer(params["selective"], j)
            u = _norm(h, sp["norm1"], cfg, sp.get("norm1_b"))
            logits = None
            out, state["selective"][j], state["selective_conv"][j], y = \
                selective_step(u, state["selective"][j],
                               state["selective_conv"][j], sp, cfg)
            if g == cfg.memory_layer:
                memory = y
        elif kind == GMU:
            gp = _layer(params["gmu"], j)
            logits = None
            with jax.named_scope("tick.gmu"):
                out = _gmu(_norm(h, gp["norm1"], cfg, gp.get("norm1_b")),
                           memory, gp)
        elif kind == CROSS:
            cp = _layer(params["cross"], j)
            src = cfg.n_attention - 1
            gi = group_of[src]
            u = _norm(h, cp["norm1"], cfg, cp.get("norm1_b"))
            logits = None
            with jax.named_scope("tick.cross_attn"):
                att = decode_attention(
                    _pair_rows(_q(u, cp, cfg)), ak[src], av[src],
                    by_group[gi], pos, scale=cfg.attention_multiplier)
                out = _attn_out(_diff_out(att, cp, cfg, g), None, cp)
        elif kind == MAMBA:
            mp = _layer(params["mamba"], j)
            u = _norm(h, mp["norm1"], cfg)
            logits = _before_mixer(u, params, g, cfg)
            out, state["ssm"][j], state["conv"][j] = mamba_step(
                u, state["ssm"][j], state["conv"][j], mp, cfg)
        elif kind == LINEAR:
            lp = _layer(params["gdn"], j)
            u = _norm(h, lp["norm1"], cfg)
            logits = _before_mixer(u, params, g, cfg)
            out, state["gdn"][j], state["gdn_conv"][j] = gdn_step(
                u, state["gdn"][j], state["gdn_conv"][j], lp, cfg)
        else:
            ap = _layer(params["attn"], j)
            u = _norm(h, ap["norm1"], cfg, ap.get("norm1_b"))
            logits = _before_mixer(u, params, g, cfg)
            q, k1, v1, gate = _qkv(u, ap, cfg, j, pos)
            gi, window = group_of[j], cfg.window_of(j)
            with jax.named_scope("tick.scatter"):
                ak[j] = ak[j].at[wbs[gi], off].set(
                    k1.reshape(s, -1).astype(ak[j].dtype))
                av[j] = av[j].at[wbs[gi], off].set(
                    v1.reshape(s, -1).astype(av[j].dtype))
            lo = jnp.maximum(pos - (window - 1), 0) if window else None
            if cfg.diff_attn:
                with jax.named_scope("tick.diff_attn"):
                    att = decode_attention(
                        _pair_rows(q), ak[j], av[j], by_group[gi], pos,
                        scale=cfg.attention_multiplier, lo=lo)
                    out = _attn_out(_diff_out(att, ap, cfg, g), gate, ap)
            else:
                att = decode_attention(
                    q, ak[j], av[j], by_group[gi], pos,
                    scale=cfg.attention_multiplier, lo=lo)
                out = _attn_out(att.reshape(s, cfg.q_dim), gate, ap)
        h, hit, held = _ffn(h + r * out, logits, params, g, cfg, "tick.moe",
                            live)
        if hit is not None:
            hits = hits + hit
        if held is not None:
            pairs = pairs + held
    out = {"k": tuple(ak), "v": tuple(av)}
    for name, bufs in state.items():
        if name in arena:
            out[name] = tuple(bufs)
    if cfg.moe_share:
        return out, _head(params, h, cfg), hits, pairs
    if cfg.ffn == EXPERTS:
        return out, _head(params, h, cfg), hits
    return out, _head(params, h, cfg)


def paged_admit(params, arena, window, write_table, lane,
                cfg: HybridConfig, whole: bool = False):
    """The batch-1 admission prefill at a bucket width: window [1, T],
    write_table [m] (shared and beyond-prompt entries point at trash block
    0), lane int32 [2] = (the lane's index, n_state). Scatters the
    prompt's K and V into the lane's private blocks and writes the lane's
    recurrent state as of position n_state - 1 over whatever the lane held.

    With several KV groups write_table is [G, m], a row a group. A window
    group's layers scatter only the ``window / bt + 1`` blocks that the
    positions ``n_state + 1 - window .. n_state`` lie in (what the lane's
    first tick can see: the host gave the group blocks for those alone);
    every other block of the bucket is left where it was computed.

    A model with cross-attention layers stops at the last attention
    layer's K and V (``cfg.admit_layers``; ``whole`` runs every layer, as a
    check that the result is the same).

    Where only a share of the experts is held it returns (arena, the
    prompt's (row, held expert) pairs: one int32), else the arena."""
    t = window.shape[1]
    bt = arena["k"][0].shape[1]
    nb = -(-t // bt)
    with jax.named_scope("admit.prefill"):
        _, ks, vs, state = prefill(params, window[0], lane[1], cfg,
                                   arena["k"][0].dtype, whole)
    groups = cfg.kv_groups()
    tables = write_table[None] if write_table.ndim == 1 else write_table
    with jax.named_scope("admit.scatter"):
        pad = ((0, 0), (0, nb * bt - t), (0, 0), (0, 0))
        blocks = lambda a: jnp.pad(a, pad).reshape(
            cfg.n_attention, nb, bt, cfg.n_kv_heads * cfg.head_dim)
        kb, vb = blocks(ks), blocks(vs)
        ak, av = list(arena["k"]), list(arena["v"])
        for gi, grp in enumerate(groups):
            n, first = nb, 0
            if grp.window and grp.window // bt + 1 < nb:
                n = grp.window // bt + 1
                first = jnp.clip((lane[1] + 1 - grp.window) // bt, 0, nb - n)
            cut = lambda a, ax: a if n == nb else \
                lax.dynamic_slice_in_dim(a, first, n, axis=ax)
            cols = cut(tables[gi, :nb], 0)
            for j in grp.layer_ids:
                ak[j] = ak[j].at[cols].set(cut(kb[j], 0))
                av[j] = av[j].at[cols].set(cut(vb[j], 0))
        out = {"k": tuple(ak), "v": tuple(av)}
        for name in STATE_LEAVES:
            if name in arena:
                out[name] = tuple(buf.at[lane[0]].set(state[name][j])
                                  for j, buf in enumerate(arena[name]))
    if cfg.moe_share:
        # the (row, held expert) pairs of the prompt, for its span
        return out, state["moe_pairs"]
    return out


# ---------------------------------------------------------------------------
# the model object the serving engine loads
# ---------------------------------------------------------------------------


class HybridLM:
    """Serve-only: weights held once in the config's compute dtype, no
    optimizer state. ``ServingEngine(model=HybridLM(...))`` serves it
    through ``/generate`` and ``PagedDecoder`` like a TransformerLM."""

    def __init__(self, cfg: HybridConfig, params: Optional[Params] = None,
                 seed: int = 0) -> None:
        dispatch.enable_compile_cache()
        self.cfg = cfg
        self._run_cfg = cfg     # the decode pools' probe for an LM
        self.mesh = None
        self.params = params if params is not None \
            else jax.jit(lambda k: init_params(cfg, k))(
                jax.random.PRNGKey(seed))
        self._forward = jax.jit(lambda p, toks: forward(p, toks, cfg))

    @classmethod
    def from_state(cls, cfg: HybridConfig, params: Params) -> "HybridLM":
        return cls(cfg, params)

    def fit(self, *_a, **_k):
        raise NotImplementedError(
            "HybridLM is serve-only: it holds its weights once in "
            f"{jnp.dtype(self.cfg.compute_dtype).name} and no optimizer "
            "state; training this model is not implemented")

    fit_batches = fit_iterator = fit

    def logits(self, tokens) -> jax.Array:
        return self._forward(self.params, jnp.asarray(tokens, jnp.int32))

    def output(self, tokens) -> jax.Array:
        return self.logits(tokens)

    def generate(self, *_a, **_k):
        raise NotImplementedError(
            "HybridLM generates through the paged decoder "
            "(ServingEngine /generate with DL4J_TPU_SERVE_KV_BLOCK > 0): "
            "the unpaged sampler, static top_k/top_p filters and the "
            "fixed-slot pool carry no recurrent state")
