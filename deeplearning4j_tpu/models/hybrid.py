"""A serve-only hybrid LM: Mamba-2 layers beside grouped-query attention.

The published shape is `granitemoehybrid` without experts (IBM Granite
4.0-H): a per-layer pattern of two mixer kinds under one residual stream,
RMS normalisation, a gated SiLU feed-forward without biases, no positional
encoding, four scalar multipliers (embedding, residual, attention, logits).
With ``u`` a layer's normalised input:

  * every layer: ``h += r * Mixer(RMSNorm(h))`` then
    ``h += r * W_down(silu(g) * v)`` with ``[g | v] = W_up RMSNorm(h)``;
    logits ``RMSNorm(h) E^T / logits_scaling``, head tied to the embedding
    ``h0 = embedding_multiplier * E[tok]``.
  * attention: ``q = W_q u`` as ``n_heads`` heads, ``k, v`` as
    ``n_kv_heads`` heads (KV head j serves query heads g*j .. g*j+g-1), no
    bias, no rotary; scores ``attention_multiplier * q k^T``, causal,
    softmax in float32.
  * Mamba-2 (one group): ``[z | xBC | dt] = W_in u``; ``xBC =
    silu(conv(xBC))``, a causal depthwise convolution of width ``ssm_conv``
    with bias; ``xBC -> x [H, P], B [N], C [N]``; ``delta = softplus(dt +
    dt_bias)``, ``A = -exp(A_log)`` per head; ``S_t = exp(delta_t A) S_{t-1}
    + delta_t x_t (outer) B_t``; ``y_t = S_t C_t + D x_t``; ``y =
    RMSNorm(y * silu(z))`` over the whole inner width; output ``W_out y``.

Two computations of the recurrence, one result: the admission prefill runs
it in chunks (``ssm_chunk``; inside a chunk a masked matrix product, between
chunks the carried ``S``), the decode tick one step on the lane's stored
``S`` and its last ``ssm_conv - 1`` rows of ``xBC``.

What is held where. Weights once, in the config's compute dtype (bfloat16
under ``dtype_policy="performance"``): no float32 masters, no optimizer
state, so ``fit`` refuses. Leaves are stacked by kind on a leading layer
axis (``params["mamba"]``, ``params["attn"]``, ``params["mlp"]``). Per
request the model tells the paged decoder (``cache_needs``) that it keeps
keys and values for its attention layers only, with ``n_kv_heads`` heads,
and two state leaves a lane: ``ssm`` ``[H, P, N]`` float32 and ``conv``
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

Activations: the residual stream, the norms, ``delta``, the decay and ``S``
are float32; every matrix product reads its activation in the weights'
dtype and accumulates in float32; ``xBC`` is rounded to the compute dtype
where it leaves the projection, which is what the stored tail holds.

Reference anchor: none in the DL4J 0.4 reference, whose recurrent layers
are LSTM and GRU cells (nn/layers/recurrent/GravesLSTM.java); provenance
is Dao & Gu, "Transformers are SSMs" (Mamba-2 and its chunked dual form)
and the published `granitemoehybrid` config.json. The plain float32 form
of the same equations is perfbench/reference_granite.py.
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
from deeplearning4j_tpu.ops.memory import CacheNeeds, StateLeaf

Params = Dict[str, Any]
MAMBA, ATTENTION = "mamba", "attention"


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
    moe_experts: int = 0                # none: the decode pools ask

    def __post_init__(self):
        bad = [t for t in self.layer_types if t not in (MAMBA, ATTENTION)]
        if bad or not self.layer_types:
            raise ValueError(f"layer_types must name {MAMBA!r} or "
                             f"{ATTENTION!r} for every layer, got {bad}")
        if self.d_model % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"d_model {self.d_model} / n_heads {self.n_heads} / "
                f"n_kv_heads {self.n_kv_heads} do not divide")

    @classmethod
    def from_published(cls, conf: Dict[str, Any], *, max_len: int,
                       dtype_policy: str = "performance") -> "HybridConfig":
        """From a `granitemoehybrid` config.json's own keys. Whatever of
        the family this module does not compute is refused here."""
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
        return self.d_model // self.n_heads

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
    def cache_needs(self) -> CacheNeeds:
        return CacheNeeds(
            kv_layers=self.n_attention, kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            state=(StateLeaf("ssm", self.n_mamba,
                             (self.ssm_heads, self.ssm_head_dim,
                              self.ssm_state), "float32"),
                   StateLeaf("conv", self.n_mamba,
                             (self.ssm_conv - 1, self.conv_dim),
                             jnp.dtype(self.compute_dtype).name)),
            kv_per_layer=True)

    def paged_decode_step(self, params, arena, tok, pos, tables):
        return paged_decode_step(params, arena, tok, pos, tables, self)

    def paged_admit(self, params, arena, window, write_table, lane):
        return paged_admit(params, arena, window, write_table, lane, self)

    def scan_chunks(self, width: int) -> int:
        """Chunks the admission prefill's scan walks at a bucket width."""
        return width // _chunk_len(self, width)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def param_shapes(cfg: HybridConfig) -> Params:
    """Every leaf's shape, stacked by layer kind."""
    d, f, nm, na, L = cfg.d_model, cfg.d_ff, cfg.n_mamba, cfg.n_attention, \
        cfg.n_layers
    kv = cfg.n_kv_heads * cfg.head_dim
    return {
        "embed": (cfg.vocab_size, d), "norm_f": (d,),
        "mamba": {
            "norm1": (nm, d), "W_in": (nm, d, cfg.in_dim),
            "conv_w": (nm, cfg.ssm_conv, cfg.conv_dim),
            "conv_b": (nm, cfg.conv_dim), "dt_bias": (nm, cfg.ssm_heads),
            "A_log": (nm, cfg.ssm_heads), "D": (nm, cfg.ssm_heads),
            "norm_y": (nm, cfg.d_inner), "W_out": (nm, cfg.d_inner, d)},
        "attn": {
            "norm1": (na, d), "Wq": (na, d, d), "Wk": (na, d, kv),
            "Wv": (na, d, kv), "Wo": (na, d, d)},
        "mlp": {"norm2": (L, d), "W_up": (L, d, 2 * f),
                "W_down": (L, f, d)},
    }


def init_params(cfg: HybridConfig, key) -> Params:
    """Every weight from ``key``, rounded once to the compute dtype.
    Matrices Xavier-normal; norm scales 1; ``A_log = log(uniform(1,
    16))``, ``dt_bias`` the inverse softplus of a step log-uniform in
    [1e-3, 1e-1], ``D = 1``, the conv kernel uniform in +-1/sqrt(width)
    (the Mamba-2 family's convention)."""
    shapes = param_shapes(cfg)
    ks = iter(jax.random.split(key, 16))
    f32 = jnp.float32

    def xavier(shape):
        std = np.sqrt(2.0 / (shape[-2] + shape[-1]))
        return jax.random.normal(next(ks), shape, f32) * np.float32(std)

    ones = lambda shape: jnp.ones(shape, f32)
    m, a, p = shapes["mamba"], shapes["attn"], shapes["mlp"]
    dt = jnp.exp(jax.random.uniform(next(ks), m["dt_bias"], f32,
                                    np.log(1e-3), np.log(1e-1)))
    bound = 1.0 / np.sqrt(cfg.ssm_conv)
    out = {
        "embed": jax.random.normal(next(ks), shapes["embed"], f32)
        * np.float32(0.02),
        "norm_f": ones(shapes["norm_f"]),
        "mamba": {
            "norm1": ones(m["norm1"]), "W_in": xavier(m["W_in"]),
            "conv_w": jax.random.uniform(next(ks), m["conv_w"], f32,
                                         -bound, bound),
            "conv_b": jnp.zeros(m["conv_b"], f32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(next(ks), m["A_log"], f32,
                                                1.0, 16.0)),
            "D": ones(m["D"]), "norm_y": ones(m["norm_y"]),
            "W_out": xavier(m["W_out"])},
        "attn": {"norm1": ones(a["norm1"]), "Wq": xavier(a["Wq"]),
                 "Wk": xavier(a["Wk"]), "Wv": xavier(a["Wv"]),
                 "Wo": xavier(a["Wo"])},
        "mlp": {"norm2": ones(p["norm2"]), "W_up": xavier(p["W_up"]),
                "W_down": xavier(p["W_down"])},
    }
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
    return h + cfg.residual_multiplier * _mm(jax.nn.silu(g) * v,
                                             fp["W_down"])


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


def _qkv(u, ap, cfg: HybridConfig):
    n = u.shape[0]
    q = _mm(u, ap["Wq"]).reshape(n, cfg.n_heads, cfg.head_dim)
    k = _mm(u, ap["Wk"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
    v = _mm(u, ap["Wv"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def attention_full(u, ap, cfg: HybridConfig, kv_dtype):
    """Causal grouped-query attention over one sequence u [T, d]; K and V
    are rounded to the arena's dtype first, which is what a later decode
    step will read. Returns (out [T, d], k, v [T, Hkv, hd])."""
    t = u.shape[0]
    hk, grp = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    hi = lax.Precision.HIGHEST
    q, k, v = _qkv(u, ap, cfg)
    k, v = k.astype(kv_dtype), v.astype(kv_dtype)
    q = q.reshape(t, hk, grp, cfg.head_dim)
    sc = jnp.einsum("tkgd,skd->kgts", q, _f32(k), precision=hi) \
        * cfg.attention_multiplier
    sc = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], sc,
                   -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    att = jnp.einsum("kgts,skd->tkgd", p, _f32(v), precision=hi)
    return _mm(att.reshape(t, cfg.d_model), ap["Wo"]), k, v


def _embed(params, tok, cfg: HybridConfig):
    return cfg.embedding_multiplier * _f32(params["embed"][tok])


def _head(params, h, cfg: HybridConfig):
    x = _rms(h, params["norm_f"], cfg.rms_eps)
    e = params["embed"]
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
                out, k, v = attention_full(
                    _rms(h, ap["norm1"], cfg.rms_eps), ap, cfg, kv_dtype)
                ks.append(k)
                vs.append(v)
                h = _mlp(h + r * out, _layer(params["mlp"], g0 + i), cfg)
    empty = jnp.zeros((0, t, cfg.n_kv_heads, cfg.head_dim), kv_dtype)
    return (h, jnp.stack(ks) if ks else empty,
            jnp.stack(vs) if vs else empty, ssm, conv)


def forward(params, tokens, cfg: HybridConfig):
    """tokens [B, T] -> logits [B, T, V] float32 (every position feeds
    the state: no padding)."""
    t = tokens.shape[1]
    one = lambda row: _head(params, prefill(params, row, t, cfg)[0], cfg)
    return jax.vmap(one)(tokens)


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
    state buffer is read once and rewritten in place."""
    from deeplearning4j_tpu.serving.paged import chunked_attention

    s = tok.shape[0]
    bt = arena["k"][0].shape[1]
    r = cfg.residual_multiplier
    h = _embed(params, tok, cfg)
    wb = jnp.take_along_axis(tables, (pos // bt)[:, None], axis=1)[:, 0]
    off = pos % bt
    ak, av = list(arena["k"]), list(arena["v"])
    ssm, conv = list(arena["ssm"]), list(arena["conv"])
    seen = {MAMBA: 0, ATTENTION: 0}
    for g, kind in enumerate(cfg.layer_types):
        j = seen[kind]
        seen[kind] += 1
        if kind == MAMBA:
            mp = _layer(params["mamba"], j)
            out, ssm[j], conv[j] = mamba_step(
                _rms(h, mp["norm1"], cfg.rms_eps), ssm[j], conv[j], mp, cfg)
        else:
            ap = _layer(params["attn"], j)
            q, k1, v1 = _qkv(_rms(h, ap["norm1"], cfg.rms_eps), ap, cfg)
            with jax.named_scope("tick.scatter"):
                ak[j] = ak[j].at[wb, off].set(
                    k1.reshape(s, -1).astype(ak[j].dtype))
                av[j] = av[j].at[wb, off].set(
                    v1.reshape(s, -1).astype(av[j].dtype))
            att = chunked_attention(q, ak[j], av[j], tables, pos,
                                    scale=cfg.attention_multiplier)
            out = _mm(att.reshape(s, cfg.d_model), ap["Wo"])
        h = _mlp(h + r * out, _layer(params["mlp"], g), cfg)
    arena = {"k": tuple(ak), "v": tuple(av), "ssm": tuple(ssm),
             "conv": tuple(conv)}
    return arena, _head(params, h, cfg)


def paged_admit(params, arena, window, write_table, lane,
                cfg: HybridConfig):
    """The batch-1 admission prefill at a bucket width: window [1, T],
    write_table [m] (shared and beyond-prompt entries point at trash block
    0), lane int32 [2] = (the lane's index, n_state). Scatters the
    prompt's K and V into the lane's private blocks and writes the lane's
    recurrent state as of position n_state - 1 over whatever the lane held."""
    t = window.shape[1]
    bt = arena["k"][0].shape[1]
    nb = -(-t // bt)
    with jax.named_scope("admit.prefill"):
        _, ks, vs, ssm, conv = prefill(params, window[0], lane[1], cfg,
                                       arena["k"][0].dtype)
    with jax.named_scope("admit.scatter"):
        pad = ((0, 0), (0, nb * bt - t), (0, 0), (0, 0))
        blocks = lambda a: jnp.pad(a, pad).reshape(
            cfg.n_attention, nb, bt, cfg.n_kv_heads * cfg.head_dim)
        cols = write_table[:nb]
        out = {"k": tuple(buf.at[cols].set(kb)
                          for buf, kb in zip(arena["k"], blocks(ks))),
               "v": tuple(buf.at[cols].set(vb)
                          for buf, vb in zip(arena["v"], blocks(vs))),
               "ssm": tuple(buf.at[lane[0]].set(ssm[j])
                            for j, buf in enumerate(arena["ssm"])),
               "conv": tuple(buf.at[lane[0]].set(conv[j])
                             for j, buf in enumerate(arena["conv"]))}
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
