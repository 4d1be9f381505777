"""A serve-only LM block with mixers and feed-forwards of several kinds
under one residual stream: Mamba-2 layers beside grouped-query attention
(`granitemoehybrid` without experts, IBM Granite 4.0-H), and attention whose
layers differ in their own data, window and rotary positions, over routed
experts (`smallthinker`, PowerInfer SmallThinker). A layer names its mixer
(``layer_types``: ``mamba``, or ``attention`` with ``rope`` and ``window`` as
that layer's data) and the model its feed-forward (``ffn``: ``gated`` with
its activation, or ``experts``); RMS normalisation throughout, no biases,
four scalar multipliers (embedding, residual, attention, logits; 1 where a
family has none), the head tied to the embedding or a matrix of its own.
With ``u`` a layer's normalised input:

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

Two computations of the recurrence, one result: the admission prefill runs
it in chunks (``ssm_chunk``; inside a chunk a masked matrix product, between
chunks the carried ``S``), the decode tick one step on the lane's stored
``S`` and its last ``ssm_conv - 1`` rows of ``xBC``. Two of attention too:
the admission attends a whole prompt by blocks of query rows over chunks of
keys (``attention_full``: no ``[H, T, T]`` scores at 8,192 positions, a
window layer reads its band; with bf16 products and Pallas on, one kernel
that writes no score at all, ops/pallas_prefill.py), the tick one query a
lane over the lane's blocks (serving/paged.chunked_attention, with a lower
bound a lane in a window layer). And two of the expert layer, by the rows: every expert on
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
``[ssm_conv - 1, conv_dim]`` in the compute dtype, ONE BUFFER A LAYER
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
is Dao & Gu, "Transformers are SSMs" (Mamba-2 and its chunked dual form),
Su et al. (rotary positions), Beltagy et al. (sliding windows), Gale et
al. (dropless experts) and the published `granitemoehybrid` and
`smallthinker` config.json. The plain float32 forms of the same equations
are perfbench/reference_granite.py and perfbench/reference_smallthinker.py.
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
MAMBA, ATTENTION = "mamba", "attention"
GATED, EXPERTS = "gated", "experts"
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

    # what the decode pools ask of an expert layer: no row is dropped and a
    # row's output is its own, whoever shares its batch
    moe_dropless = True

    def __post_init__(self):
        bad = [t for t in self.layer_types if t not in (MAMBA, ATTENTION)]
        if bad or not self.layer_types:
            raise ValueError(f"layer_types must name {MAMBA!r} or "
                             f"{ATTENTION!r} for every layer, got {bad}")
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
        if self.ffn == EXPERTS and self.n_mamba:
            raise ValueError("routed experts beside Mamba layers: not "
                             "computed (the Mamba runs loop over layers "
                             "by index, an expert layer's buffers are its "
                             "own)")
        if (self.ffn == EXPERTS) != bool(self.moe_experts) or \
                not 0 <= self.moe_top_k <= self.moe_experts:
            raise ValueError(
                f"ffn {self.ffn!r} with {self.moe_experts} experts, top "
                f"{self.moe_top_k}")

    @classmethod
    def from_published(cls, conf: Dict[str, Any], *, max_len: int,
                       dtype_policy: str = "performance") -> "HybridConfig":
        """From a published config.json's own keys: `granitemoehybrid`
        (the default) or `smallthinker` (``model_type``). Whatever of a
        family this module does not compute is refused here."""
        if conf.get("model_type") == "smallthinker":
            return cls._from_smallthinker(conf, int(max_len), dtype_policy)
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
            d_ff=conf["intermediate_size"], max_len=int(max_len),
            ssm_heads=conf["mamba_n_heads"],
            ssm_head_dim=conf["mamba_d_head"],
            ssm_state=conf["mamba_d_state"], ssm_conv=conf["mamba_d_conv"],
            ssm_chunk=conf["mamba_chunk_size"],
            embedding_multiplier=float(conf["embedding_multiplier"]),
            residual_multiplier=float(conf["residual_multiplier"]),
            attention_multiplier=float(conf["attention_multiplier"]),
            logits_scaling=float(conf["logits_scaling"]),
            rms_eps=float(conf["rms_norm_eps"]), dtype_policy=dtype_policy)

    @classmethod
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
    def n_attention(self) -> int:
        return self.n_layers - self.n_mamba

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
        seen = {MAMBA: 0, ATTENTION: 0}
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
        among the attention layers."""
        sizes = sorted({self.window_of(j) for j in range(self.n_attention)})
        return tuple(
            KVGroup(tuple(j for j in range(self.n_attention)
                          if self.window_of(j) == w), w) for w in sizes)

    def cache_needs(self) -> CacheNeeds:
        return CacheNeeds(
            kv_layers=self.n_attention, kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, groups=self.kv_groups(),
            state=(StateLeaf("ssm", self.n_mamba,
                             (self.ssm_heads, self.ssm_head_dim,
                              self.ssm_state), "float32"),
                   StateLeaf("conv", self.n_mamba,
                             (self.ssm_conv - 1, self.conv_dim),
                             jnp.dtype(self.compute_dtype).name))
            if self.n_mamba else (),
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
        """Chunks the admission prefill's scan walks at a bucket width."""
        return width // _chunk_len(self, width)

    def admit_attend(self, width: int) -> str:
        """Which attention the admission runs at a bucket width: ``kernel``
        (ops/pallas_prefill.py) where Pallas is on, the products read bf16
        (not ``attn_exact``) and its tiles divide the width; ``xla`` (the
        by-blocks path of ``attention_full``) otherwise."""
        from deeplearning4j_tpu.ops import pallas_prefill
        from deeplearning4j_tpu.ops.pallas_kernels import pallas_enabled

        fits = pallas_prefill.fits(width, self.n_heads // self.n_kv_heads,
                                   self.head_dim)
        return "kernel" if not self.attn_exact and fits and \
            pallas_enabled() else "xla"


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
    ffn = {"mlp": {"norm2": (L, d), "W_up": (L, d, 2 * f),
                   "W_down": (L, f, d)}} if cfg.ffn == GATED else \
        {"moe": {"norm2": (L, d), "router": (L, d, e),
                 "W_in": ((e, d, 2 * f),) * L, "W_down": ((e, f, d),) * L}}
    head = {} if cfg.tie_head else {"head": (cfg.vocab_size, d)}
    mamba = {"mamba": {
        "norm1": (nm, d), "W_in": (nm, d, cfg.in_dim),
        "conv_w": (nm, cfg.ssm_conv, cfg.conv_dim),
        "conv_b": (nm, cfg.conv_dim), "dt_bias": (nm, cfg.ssm_heads),
        "A_log": (nm, cfg.ssm_heads), "D": (nm, cfg.ssm_heads),
        "norm_y": (nm, cfg.d_inner), "W_out": (nm, cfg.d_inner, d)}} \
        if nm else {}
    return {
        "embed": (cfg.vocab_size, d), "norm_f": (d,), **head, **ffn,
        **mamba,
        "attn": {
            "norm1": (na, d), "Wq": (na, d, qd), "Wk": (na, d, kv),
            "Wv": (na, d, kv), "Wo": (na, qd, d)},
    }


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
    a = shapes["attn"]
    out = {}
    if "mamba" in shapes:
        m = shapes["mamba"]
        dt = jnp.exp(jax.random.uniform(next(ks), m["dt_bias"], f32,
                                        np.log(1e-3), np.log(1e-1)))
        bound = 1.0 / np.sqrt(cfg.ssm_conv)
    out["embed"] = jax.random.normal(next(ks), shapes["embed"], f32) \
        * np.float32(0.02)
    out["norm_f"] = ones(shapes["norm_f"])
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
    out["attn"] = {"norm1": ones(a["norm1"]), "Wq": xavier(a["Wq"]),
                   "Wk": xavier(a["Wk"]), "Wv": xavier(a["Wv"]),
                   "Wo": xavier(a["Wo"])}
    if "mlp" in shapes:
        p = shapes["mlp"]
        out["mlp"] = {"norm2": ones(p["norm2"]), "W_up": xavier(p["W_up"]),
                      "W_down": xavier(p["W_down"])}
    # the leaves of the wider block draw from a stream of their own, so
    # that the others are what they were
    ks = iter(jax.random.split(jax.random.fold_in(key, 1),
                               2 + 2 * cfg.n_layers))
    if "moe" in shapes:
        p = shapes["moe"]
        out["moe"] = {"norm2": ones(p["norm2"]),
                      "router": xavier(p["router"]),
                      "W_in": tuple(xavier(one) for one in p["W_in"]),
                      "W_down": tuple(xavier(one) for one in p["W_down"])}
    if "head" in shapes:
        out["head"] = jax.random.normal(next(ks), shapes["head"], f32) \
            * np.float32(0.02)
    return jax.tree_util.tree_map(lambda x: x.astype(cfg.compute_dtype), out)


# ---------------------------------------------------------------------------
# the layer, piece by piece
# ---------------------------------------------------------------------------


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(g)


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
    gv = _mm(_rms(h, fp["norm2"], cfg.rms_eps), fp["W_up"])
    g, v = gv[..., :cfg.d_ff], gv[..., cfg.d_ff:]
    return h + cfg.residual_multiplier * _mm(ACTS[cfg.ffn_act](g) * v,
                                             fp["W_down"])


def _router(u, params, g, cfg: HybridConfig):
    """Layer g's router logits [T, E] in float32, from the layer's
    normalised input ``u`` BEFORE its mixer (the tensor the mixer reads);
    None where the feed-forward is gated."""
    if cfg.ffn != EXPERTS:
        return None
    w = lax.dynamic_index_in_dim(params["moe"]["router"], g, keepdims=False)
    return jnp.matmul(u, _f32(w), precision=lax.Precision.HIGHEST)


def _ffn(h, r, params, g, cfg: HybridConfig, scope: str, live=None):
    """Layer g's feed-forward on the stream h: gated, or the routed
    experts on the logits ``r`` that ``_router`` read before the mixer
    (every expert is held here: one chip, no exchange). Returns (h, how
    many experts got a row of a ``live`` row; None where gated)."""
    if cfg.ffn == GATED:
        return _mlp(h, _layer(params["mlp"], g), cfg), None
    # imported where a model of this kind is built, not with the module
    from deeplearning4j_tpu.parallel.expert_parallel import dropless_experts

    ep = params["moe"]
    x = _rms(h, lax.dynamic_index_in_dim(ep["norm2"], g, keepdims=False),
             cfg.rms_eps)
    y, hit = dropless_experts(x, r, ep["W_in"][g], ep["W_down"][g],
                              top_k=cfg.moe_top_k, act=ACTS[cfg.ffn_act],
                              live=live, scope=scope)
    return h + cfg.residual_multiplier * y, hit


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


def _rope(x, pos, theta: float):
    """Rotary positions over the whole head, the two halves of a head
    paired: x [n, H, hd] float32 at positions pos [n]."""
    half = x.shape[-1] // 2
    inv = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * np.float32(-np.log(theta) / half))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _qkv(u, ap, cfg: HybridConfig, j: int = 0, pos=None):
    """q, k, v of attention layer j (among the attention layers) for rows
    u [n, d] at positions pos [n]: rotary on q and k where the layer has
    it."""
    n = u.shape[0]
    q = _mm(u, ap["Wq"]).reshape(n, cfg.n_heads, cfg.head_dim)
    k = _mm(u, ap["Wk"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
    v = _mm(u, ap["Wv"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
    if cfg.rope_of(j):
        q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    return q, k, v


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


def attention_full(u, ap, cfg: HybridConfig, kv_dtype, j: int = 0):
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
    hk, grp, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim
    hi = lax.Precision.HIGHEST
    window = cfg.window_of(j)
    q, k, v = _qkv(u, ap, cfg, j, jnp.arange(t))
    k, v = k.astype(kv_dtype), v.astype(kv_dtype)
    if cfg.admit_attend(t) == "kernel":
        from deeplearning4j_tpu.ops.pallas_prefill import prefill_attention

        with jax.named_scope("admit.attend"):
            att = prefill_attention(
                q.reshape(t, cfg.q_dim).astype(kv_dtype), k.reshape(t, -1),
                v.reshape(t, -1), head_dim=hd,
                scale=cfg.attention_multiplier, window=window)
        return _mm(att, ap["Wo"]), k, v
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
    return _mm(att.reshape(t, cfg.q_dim), ap["Wo"]), k, v


def _embed(params, tok, cfg: HybridConfig):
    return cfg.embedding_multiplier * _f32(params["embed"][tok])


def _head(params, h, cfg: HybridConfig):
    x = _rms(h, params["norm_f"], cfg.rms_eps)
    e = params["embed"] if cfg.tie_head else params["head"]
    return jnp.einsum("...d,vd->...v", x.astype(e.dtype), e,
                      precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32) \
        / cfg.logits_scaling


# ---------------------------------------------------------------------------
# one sequence, whole: the admission prefill and `output`
# ---------------------------------------------------------------------------


def prefill(params, tokens, n_state, cfg: HybridConfig, kv_dtype=None):
    """One sequence tokens [T] through every layer. Returns the residual
    stream h [T, d], K and V [n_attention, T, Hkv, hd] in ``kv_dtype``, and
    the recurrent state as of position ``n_state - 1``: ssm [n_mamba, H,
    P, N] float32, conv [n_mamba, K-1, C]. Mamba layers run under a loop
    that reads each layer's weights out of the stacked leaves by index."""
    if kv_dtype is None:
        kv_dtype = cfg.compute_dtype
    t = tokens.shape[0]
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
                        _rms(h, mp["norm1"], cfg.rms_eps), n_state, mp, cfg)
                h = _mlp(h + r * out, _layer(params["mlp"], g0 + i), cfg)
                return (h, lax.dynamic_update_index_in_dim(ssm, s, j0 + i, 0),
                        lax.dynamic_update_index_in_dim(conv, tail,
                                                        j0 + i, 0))

            h, ssm, conv = lax.fori_loop(0, n, body, (h, ssm, conv))
        else:
            for i in range(n):
                ap = _layer(params["attn"], j0 + i)
                u = _rms(h, ap["norm1"], cfg.rms_eps)
                logits = _router(u, params, g0 + i, cfg)
                out, k, v = attention_full(u, ap, cfg, kv_dtype, j0 + i)
                ks.append(k)
                vs.append(v)
                h, _ = _ffn(h + r * out, logits, params, g0 + i, cfg,
                            "admit.moe")
    empty = jnp.zeros((0, t, cfg.n_kv_heads, cfg.head_dim), kv_dtype)
    return (h, jnp.stack(ks) if ks else empty,
            jnp.stack(vs) if vs else empty, ssm, conv)


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
    lane's blocks through serving/paged.chunked_attention, grouped; Mamba
    layers advance EVERY lane's state one step (a dead lane's is never
    read: admission overwrites it whole). The layers are unrolled: each
    state buffer is read once and rewritten in place.

    A model whose attention layers page in several groups (window layers
    beside global ones: ``cfg.kv_groups``) is handed ``tables`` [G, S, m],
    a table a group; a window layer's buffers have its group's blocks, it
    reads through its group's table and sees ``pos - window < t <= pos``.
    With routed experts the tick also returns how many (layer, expert)
    pairs got the row of a live lane (one whose write block is not trash):
    (arena, logits, hit)."""
    from deeplearning4j_tpu.serving.paged import chunked_attention

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
    ak, av = list(arena["k"]), list(arena["v"])
    ssm, conv = list(arena.get("ssm", ())), list(arena.get("conv", ()))
    seen = {MAMBA: 0, ATTENTION: 0}
    for g, kind in enumerate(cfg.layer_types):
        j = seen[kind]
        seen[kind] += 1
        if kind == MAMBA:
            mp = _layer(params["mamba"], j)
            logits = None
            out, ssm[j], conv[j] = mamba_step(
                _rms(h, mp["norm1"], cfg.rms_eps), ssm[j], conv[j], mp, cfg)
        else:
            ap = _layer(params["attn"], j)
            u = _rms(h, ap["norm1"], cfg.rms_eps)
            logits = _router(u, params, g, cfg)
            q, k1, v1 = _qkv(u, ap, cfg, j, pos)
            gi, window = group_of[j], cfg.window_of(j)
            with jax.named_scope("tick.scatter"):
                ak[j] = ak[j].at[wbs[gi], off].set(
                    k1.reshape(s, -1).astype(ak[j].dtype))
                av[j] = av[j].at[wbs[gi], off].set(
                    v1.reshape(s, -1).astype(av[j].dtype))
            att = chunked_attention(
                q, ak[j], av[j], by_group[gi], pos,
                scale=cfg.attention_multiplier,
                lo=jnp.maximum(pos - (window - 1), 0) if window else None)
            out = _mm(att.reshape(s, cfg.q_dim), ap["Wo"])
        h, hit = _ffn(h + r * out, logits, params, g, cfg, "tick.moe", live)
        if hit is not None:
            hits = hits + hit
    out = {"k": tuple(ak), "v": tuple(av)}
    if "ssm" in arena:
        out.update(ssm=tuple(ssm), conv=tuple(conv))
    if cfg.ffn == EXPERTS:
        return out, _head(params, h, cfg), hits
    return out, _head(params, h, cfg)


def paged_admit(params, arena, window, write_table, lane,
                cfg: HybridConfig):
    """The batch-1 admission prefill at a bucket width: window [1, T],
    write_table [m] (shared and beyond-prompt entries point at trash block
    0), lane int32 [2] = (the lane's index, n_state). Scatters the
    prompt's K and V into the lane's private blocks and writes the lane's
    recurrent state as of position n_state - 1 over whatever the lane held.

    With several KV groups write_table is [G, m], a row a group. A window
    group's layers scatter only the ``window / bt + 1`` blocks that the
    positions ``n_state + 1 - window .. n_state`` lie in (what the lane's
    first tick can see: the host gave the group blocks for those alone);
    every other block of the bucket is left where it was computed."""
    t = window.shape[1]
    bt = arena["k"][0].shape[1]
    nb = -(-t // bt)
    with jax.named_scope("admit.prefill"):
        _, ks, vs, ssm, conv = prefill(params, window[0], lane[1], cfg,
                                       arena["k"][0].dtype)
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
        if "ssm" in arena:
            out.update(
                ssm=tuple(buf.at[lane[0]].set(ssm[j])
                          for j, buf in enumerate(arena["ssm"])),
                conv=tuple(buf.at[lane[0]].set(conv[j])
                           for j, buf in enumerate(arena["conv"])))
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
