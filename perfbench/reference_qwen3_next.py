"""The plain reference for `qwen3_next` (Qwen3-Next-80B-A3B): the forward pass
in straightforward float32 `jax.numpy`, matmul precision "highest", one
sequence at a time. No kernel, no cache, no chunks, no batching, no import
from the program. It reads the published key names of the configuration file
and follows the equations below; what the file lists under `assumed` and
`departures` is where the published config is silent or where this leaves
the family's description.

``h`` is the residual stream, float32; layer ``i`` of the ``n_layer`` held
here is layer ``i`` of the model: full attention where ``(i + 1) %
full_attention_interval == 0``, else Gated DeltaNet (GDN). Every layer
``h += Mixer(N_in(h))``, then ``h += MoE(N_post(h))``; logits ``W_head
N_f(h)``, the head its own matrix, no multiplier anywhere.

- Zero-centred RMSNorm (``N_in``, ``N_post``, ``N_f``, the q and k norms):
  ``x / sqrt(mean(x^2) + rms_norm_eps) * (1 + w)``.
- Gated attention (`num_attention_heads` heads over `num_key_value_heads`
  KV heads of `head_dim`, no bias): ``W_q u`` viewed ``[heads, 2 head_dim]``
  splits a head into ``q | gate``; ``k = W_k u``, ``v = W_v u``; ``q =
  N_q(q)``, ``k = N_k(k)`` a head; rotary on the first ``head_dim *
  partial_rotary_factor`` dims of a head, rotate-half (pairs ``(j, j +
  half)``), ``inv_freq_j = rope_theta^(-2j / dims)``, the rest unchanged;
  causal softmax of ``q k / sqrt(head_dim)``, KV head j serving query heads
  ``g j .. g j + g - 1``; ``o = W_o (att * sigmoid(gate))``.
- GDN (`linear_num_key_heads` key heads of `linear_key_head_dim`,
  `linear_num_value_heads` value heads of `linear_value_head_dim`, r value
  heads a key head, no bias): ``W_qkvz u`` viewed per key head as ``[q | k |
  v (r heads) | z (r heads)]``, ``W_ba u`` per key head as ``[b (r) | a
  (r)]``; ``x = [q | k | v]`` (all key heads' q, then k, then v) through a
  causal depthwise conv of `linear_conv_kernel_dim`, no bias, then silu;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; value
  head h reads key head h // r; ``q^ = q / sqrt(sum q^2 + 1e-6) /
  sqrt(dk)``, ``k^ = k / sqrt(sum k^2 + 1e-6)``; per value head ``S [dk,
  dv]`` float32 from 0, one step at a time (a `lax.scan` over time): ``S <-
  exp(g_t) S``, ``delta = beta_t (v_t - S^T k^_t)``, ``S <- S + k^_t
  (outer) delta``, ``o_t = S^T q^_t``; ``y = o / sqrt(mean(o^2) + eps) * w
  * silu(z)`` a value head (a plain scale); output ``W_out y``.
- MoE: ``x = N_post(h)``; router logits ``r = W_r x`` over all
  `router_num_experts` experts, float32; ``I`` its `num_experts_per_tok`
  largest, weights ``softmax(r[I])`` (the published softmax, top-k and
  renormalise, `norm_topk_prob`, is the same thing); ``y = sum over e in
  I held of w_e W_d,e (silu(W_g,e x) * W_u,e x) + sigmoid(w_sg . x)
  W_d,s (silu(W_g,s x) * W_u,s x)``. Only experts ``expert_first ..
  expert_first + num_experts - 1`` are held; what the others would add is
  left out, as in the program. Each held expert is applied to its own
  routed rows alone, so the cost follows the pairs.

`init_params` is also how the benchmark makes the weights it hands to the
program: every weight from the seed, leaf by leaf, rounded ONCE to
`weights_dtype` (bfloat16), in the program's layout (attention and GDN
leaves stacked by kind, the held experts' two matrices one buffer a layer).

Two controls of "how correct is decided", never a run's path: `lowp="fp8"`
rounds both operands of every linear layer (the projections, the experts'
and the shared expert's matrices and its gate, the head) to float8_e4m3
(per-tensor scale), the nearest precision below the bfloat16 the
configuration states for them; the router stays float32 as the
configuration states. `lowp="state_bf16"` rounds every GDN layer's state S
to bfloat16 after every step, where the configuration states float32.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench.reference_smallthinker import _attend, _below_best, _first

Params = Dict[str, Any]
F8_MAX = 448.0   # float8_e4m3fn
LOWP = (None, "fp8", "state_bf16")
HI = lax.Precision.HIGHEST
SCORE_BYTES = 1 << 29      # one block of query rows' scores at the most
CAP = 128                  # an expert's rows are padded to a multiple of it


def dims(conf: Dict[str, Any]) -> Dict[str, int]:
    n = int(conf.get("n_layer", conf["num_hidden_layers"]))
    every = int(conf["full_attention_interval"])
    full = [(i + 1) % every == 0 for i in range(n)]
    return {
        "V": conf["vocab_size"], "d": conf["hidden_size"], "L": n,
        "full": full, "n_full": sum(full), "n_gdn": n - sum(full),
        "heads": conf["num_attention_heads"],
        "kv_heads": conf["num_key_value_heads"], "hd": conf["head_dim"],
        "rope": int(conf["head_dim"] * float(conf["partial_rotary_factor"])),
        "held": conf["num_experts"],
        "E": int(conf.get("router_num_experts", conf["num_experts"])),
        "first": int(conf.get("expert_first", 0)),
        "k": conf["num_experts_per_tok"], "f": conf["moe_intermediate_size"],
        "fs": conf["shared_expert_intermediate_size"],
        "Hk": conf["linear_num_key_heads"], "Hv": conf["linear_num_value_heads"],
        "dk": conf["linear_key_head_dim"], "dv": conf["linear_value_head_dim"],
        "K": conf["linear_conv_kernel_dim"],
    }


def param_count(conf: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by kind, for the layers and the share held here."""
    m = dims(conf)
    d, hd = m["d"], m["hd"]
    kd, vd = m["Hk"] * m["dk"], m["Hv"] * m["dv"]
    gdn = (d * (2 * kd + 2 * vd) + d * 2 * m["Hv"] + m["K"] * (2 * kd + vd)
           + 2 * m["Hv"] + m["dv"] + vd * d)
    attn = (d * 2 * m["heads"] * hd + 2 * d * m["kv_heads"] * hd
            + m["heads"] * hd * d + 2 * hd)
    ffn = d * m["E"] + 3 * d * m["fs"] + d + 2 * d
    expert = 3 * d * m["f"]
    out = {"gdn_mixer": gdn, "attention_mixer": attn, "ffn_outside_experts":
           ffn, "expert": expert,
           "gdn_layer": gdn + ffn + m["held"] * expert,
           "attention_layer": attn + ffn + m["held"] * expert,
           "embedding": m["V"] * d,
           "head": 0 if conf.get("tie_word_embeddings") else m["V"] * d,
           "final_norm": d}
    out["total"] = (m["n_gdn"] * out["gdn_layer"]
                    + m["n_full"] * out["attention_layer"]
                    + out["embedding"] + out["head"] + d)
    return out


def init_params(conf: Dict[str, Any], key,
                residual_gain: float = 1.0) -> Params:
    """Every weight from `key`, in the layout the program's model holds,
    rounded once to `weights_dtype`, LEAF BY LEAF (assumed: the config gives
    no initialisation). Matrices Xavier-normal; the embedding and the head
    normal(0, 0.02); the zero-centred norms' ``w`` normal(0, 0.1), so that
    ``1 + w`` is no constant; the GDN output norm's plain scale 1;
    ``A_log = log U(1, 16)``; ``dt_bias`` the inverse softplus of a step
    log-uniform in [1e-3, 1e-1] (the Mamba family's convention); the conv
    kernel U(+-1/2). `residual_gain` multiplies the matrices that write into
    the residual stream (W_o, W_out, the experts' and the shared expert's
    W_down)."""
    m = dims(conf)
    d, L, na, nl = m["d"], m["L"], m["n_full"], m["n_gdn"]
    hd, f, fs = m["hd"], m["f"], m["fs"]
    kd, vd = m["Hk"] * m["dk"], m["Hv"] * m["dv"]
    dtype = jnp.dtype(conf.get("weights_dtype", "bfloat16"))
    ks = iter(jax.random.split(key, 32 + 2 * L))
    f32 = jnp.float32

    def xavier(shape, gain=1.0):
        std = gain * np.sqrt(2.0 / (shape[-2] + shape[-1]))
        return (jax.random.normal(next(ks), shape, f32)
                * np.float32(std)).astype(dtype)

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, f32)
                * np.float32(std)).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, f32, lo, hi)

    step = jnp.exp(uniform((nl, m["Hv"]), np.log(1e-3), np.log(1e-1)))
    out = {
        "embed": normal((m["V"], d), 0.02), "norm_f": normal((d,), 0.1),
        "attn": {"norm1": normal((na, d), 0.1),
                 "Wq": xavier((na, d, 2 * m["heads"] * hd)),
                 "Wk": xavier((na, d, m["kv_heads"] * hd)),
                 "Wv": xavier((na, d, m["kv_heads"] * hd)),
                 "Wo": xavier((na, m["heads"] * hd, d), residual_gain),
                 "q_norm": normal((na, hd), 0.1),
                 "k_norm": normal((na, hd), 0.1)},
        "gdn": {"norm1": normal((nl, d), 0.1),
                "W_qkvz": xavier((nl, d, 2 * kd + 2 * vd)),
                "W_ba": xavier((nl, d, 2 * m["Hv"])),
                "conv_w": uniform((nl, m["K"], 2 * kd + vd), -0.5,
                                  0.5).astype(dtype),
                "A_log": jnp.log(uniform((nl, m["Hv"]), 1.0,
                                         16.0)).astype(dtype),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
                "norm_o": jnp.ones((nl, m["dv"]), dtype),
                "W_out": xavier((nl, vd, d), residual_gain)},
        "moe": {"norm2": normal((L, d), 0.1), "router": xavier((L, d, m["E"])),
                "W_in": tuple(xavier((m["held"], d, 2 * f))
                              for _ in range(L)),
                "W_down": tuple(xavier((m["held"], f, d), residual_gain)
                                for _ in range(L)),
                "shared_in": xavier((L, d, 2 * fs)),
                "shared_down": xavier((L, fs, d), residual_gain),
                "shared_gate": xavier((L, d, 1))},
    }
    if not conf.get("tie_word_embeddings"):
        out["head"] = normal((m["V"], d), 0.02)
    return out


# ---------------------------------------------------------------------------
# the layers, float32
# ---------------------------------------------------------------------------


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, lowp: Optional[str]):
    w = w.astype(jnp.float32)
    if lowp == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HI)


def _znorm(x, w, eps):
    """Zero-centred RMSNorm: the scale is 1 + w."""
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w.astype(jnp.float32))


def _frozen(conf: Dict[str, Any]):
    """The numbers the jitted layers read, as a hashable static argument."""
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "partial_rotary_factor", "rope_theta", "rms_norm_eps",
            "num_experts_per_tok", "moe_intermediate_size",
            "shared_expert_intermediate_size", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim")
    return tuple((k, conf[k]) for k in keep)


def _rotate(x, theta: float, dims_: int):
    """Rotary positions 0 .. T-1 on the first `dims_` of each head of x [T,
    H, hd], their halves paired; the rest unchanged."""
    t = x.shape[0]
    half = dims_ // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / dims_)
    ang = jnp.asarray(np.arange(t, dtype=np.float64)[:, None] * freq[None],
                      jnp.float32)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:dims_]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., dims_:]], axis=-1)


def _gdn(h, lp, ck, lowp):
    """h [T, d] -> (h + GDN(N_in(h)), S [Hv, dk, dv] after the last
    position); the delta rule one step at a time."""
    conf = dict(ck)
    eps = conf["rms_norm_eps"]
    hk, hv = conf["linear_num_key_heads"], conf["linear_num_value_heads"]
    dk, dv = conf["linear_key_head_dim"], conf["linear_value_head_dim"]
    r, kw = hv // hk, conf["linear_conv_kernel_dim"]
    t = h.shape[0]
    u = _znorm(h, lp["norm1"], eps)
    qkvz = _linear(u, lp["W_qkvz"], lowp).reshape(t, hk, 2 * dk + 2 * r * dv)
    ba = _linear(u, lp["W_ba"], lowp).reshape(t, hk, 2 * r)
    q = qkvz[..., :dk].reshape(t, hk * dk)
    k = qkvz[..., dk:2 * dk].reshape(t, hk * dk)
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(t, hv * dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(t, hv, dv)
    b, a = ba[..., :r].reshape(t, hv), ba[..., r:].reshape(t, hv)
    x = jnp.concatenate([q, k, v], axis=-1)
    padded = jnp.pad(x, ((kw - 1, 0), (0, 0)))
    w = lp["conv_w"].astype(jnp.float32)
    x = jax.nn.silu(sum(padded[i:i + t] * w[i] for i in range(kw)))
    kd = hk * dk
    # value head h reads key head h // r
    per_value = lambda c: jnp.repeat(c.reshape(t, hk, dk), r, axis=1)
    unit = lambda c: c / jnp.sqrt(jnp.sum(c * c, -1, keepdims=True) + 1e-6)
    q = unit(per_value(x[:, :kd])) / np.float32(np.sqrt(dk))
    k = unit(per_value(x[:, kd:2 * kd]))
    v = x[:, 2 * kd:].reshape(t, hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) \
        * jax.nn.softplus(a + lp["dt_bias"].astype(jnp.float32))

    def step(s, inp):
        q_t, k_t, v_t, b_t, g_t = inp
        s = jnp.exp(g_t)[:, None, None] * s
        delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t,
                                                  precision=HI))
        s = s + k_t[:, :, None] * delta[:, None, :]
        if lowp == "state_bf16":
            # not astype there and back: the chip's compiler keeps the
            # excess precision of such a pair and the control would read 0
            s = lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=HI)

    s0 = jnp.zeros((hv, dk, dv), jnp.float32)
    s_last, o = lax.scan(step, s0, (q, k, v, beta, g), unroll=8)
    y = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * lp["norm_o"].astype(jnp.float32) * jax.nn.silu(z)
    return h + _linear(y.reshape(t, hv * dv), lp["W_out"], lowp), s_last


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _gdn_layer(h, lp, ck, lowp):
    return _gdn(h, lp, ck, lowp)[0]


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _gdn_state(h, lp, ck, lowp):
    return _gdn(h, lp, ck, lowp)[1]


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _before(h, ap, ck, lowp):
    """An attention layer up to its products: q (normalised, rotated), k
    (the same), v, and the output's gate."""
    conf = dict(ck)
    t, hd, heads = h.shape[0], conf["head_dim"], conf["num_attention_heads"]
    kv_heads, eps = conf["num_key_value_heads"], conf["rms_norm_eps"]
    rot = int(hd * float(conf["partial_rotary_factor"]))
    u = _znorm(h, ap["norm1"], eps)
    qg = _linear(u, ap["Wq"], lowp).reshape(t, heads, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(t, heads * hd)
    k = _linear(u, ap["Wk"], lowp).reshape(t, kv_heads, hd)
    v = _linear(u, ap["Wv"], lowp).reshape(t, kv_heads, hd)
    q, k = _znorm(q, ap["q_norm"], eps), _znorm(k, ap["k_norm"], eps)
    theta = float(conf["rope_theta"])
    return _rotate(q, theta, rot), _rotate(k, theta, rot), v, gate


@functools.partial(jax.jit, static_argnames=("lowp",))
def _after(h, att, gate, wo, lowp):
    return h + _linear(att * jax.nn.sigmoid(gate), wo, lowp)


@functools.partial(jax.jit, static_argnames=("ck", "k"))
def _route(h, norm2, router, ck, k):
    """The feed-forward's input and its routing: x, the chosen experts
    [T, k] and their weights."""
    conf = dict(ck)
    x = _znorm(h, norm2, conf["rms_norm_eps"])
    r = jnp.matmul(x, router.astype(jnp.float32), precision=HI)
    topv, topi = lax.top_k(r, k)
    return x, topi, jax.nn.softmax(topv, axis=-1)


@functools.partial(jax.jit, static_argnames=("lowp",))
def _experts(h, x, rows, weight, w_in, w_down, lowp):
    """h + the held experts' part: expert e's rows ``rows[e]`` (padding
    rows carry weight 0), through its own matrices, summed back into
    their rows under their weights."""
    f = w_down.shape[1]
    xs = x[rows]                                              # [E, c, d]

    def one(xe, wi, wd):
        gu = _linear(xe, wi, lowp)
        return _linear(jax.nn.silu(gu[:, :f]) * gu[:, f:], wd, lowp)

    out = jax.vmap(one)(xs, w_in, w_down) * weight[..., None]
    return h + jnp.zeros_like(x).at[rows.reshape(-1)].add(
        out.reshape(-1, x.shape[1]))


@functools.partial(jax.jit, static_argnames=("lowp",))
def _shared(h, x, w_in, w_down, gate, lowp):
    fs = w_down.shape[0]
    gu = _linear(x, w_in, lowp)
    y = _linear(jax.nn.silu(gu[:, :fs]) * gu[:, fs:], w_down, lowp)
    return h + jax.nn.sigmoid(_linear(x, gate, lowp)) * y


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _head(h, norm_f, head, ck, lowp):
    conf = dict(ck)
    return _linear(_znorm(h, norm_f, conf["rms_norm_eps"]), head.T, lowp)


def _rows(heads: int, t: int) -> int:
    rows = t
    while rows % 2 == 0 and rows > 64 and 4 * heads * rows * t > SCORE_BYTES:
        rows //= 2
    return rows


def _by_expert(topi: np.ndarray, weight: np.ndarray, n: int, first: int,
               held: int):
    """On the host: each held expert's routed rows among the first n,
    padded with row 0 under weight 0 to a common multiple of CAP ->
    (rows [held, cap] int32, weight [held, cap] float32)."""
    k = topi.shape[1]
    e, t, w = topi[:n].reshape(-1) - first, np.repeat(np.arange(n), k), \
        weight[:n].reshape(-1)
    keep = (e >= 0) & (e < held)
    order = np.argsort(e[keep], kind="stable")
    e, t, w = e[keep][order], t[keep][order], w[keep][order]
    counts = np.bincount(e, minlength=held)
    at = np.arange(e.size) - (np.cumsum(counts) - counts)[e]
    cap = CAP * max(1, -(-int(counts.max(initial=0)) // CAP))
    rows = np.zeros((held, cap), np.int32)
    wts = np.zeros((held, cap), np.float32)
    rows[e, at] = t
    wts[e, at] = w
    return rows, wts


def hidden_one(params: Params, tokens, conf: Dict[str, Any],
               lowp: Optional[str] = None, n: Optional[int] = None):
    """tokens [T] -> the residual stream [T, d] after the last layer held
    here: a Python loop over the layers, each upcasting its own weights.
    `n` is how many of the T are the sequence (the rest padding, which no
    real row sees and which chooses no expert)."""
    if lowp not in LOWP:
        raise ValueError(f"unknown lower precision {lowp!r}")
    ck = _frozen(conf)
    m = dims(conf)
    tokens = jnp.asarray(tokens)
    t = tokens.shape[0]
    n = t if n is None else int(n)
    rows = _rows(m["heads"], t)
    layer = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)
    h = params["embed"][tokens].astype(jnp.float32)
    seen = {"gdn": 0, "attn": 0}
    moe = params["moe"]
    for i in range(m["L"]):
        kind = "attn" if m["full"][i] else "gdn"
        j = seen[kind]
        seen[kind] += 1
        if kind == "gdn":
            h = _gdn_layer(h, layer(params["gdn"], j), ck, lowp)
        else:
            ap = layer(params["attn"], j)
            q, k, v, gate = _before(h, ap, ck, lowp)
            att = jnp.concatenate([_attend(q, k, v, start, rows, 0)
                                   for start in range(0, t, rows)])
            h = _after(h, att, gate, ap["Wo"], lowp)
        x, topi, weight = _route(h, moe["norm2"][i], moe["router"][i], ck,
                                 m["k"])
        picked, wts = _by_expert(np.asarray(topi), np.asarray(weight), n,
                                 m["first"], m["held"])
        h = _experts(h, x, jnp.asarray(picked), jnp.asarray(wts),
                     moe["W_in"][i], moe["W_down"][i], lowp)
        h = _shared(h, x, moe["shared_in"][i], moe["shared_down"][i],
                    moe["shared_gate"][i], lowp)
    return h


def state_one(params: Params, tokens, conf: Dict[str, Any],
              lowp: Optional[str] = None):
    """tokens [T], unpadded -> the first GDN layer's state S [Hv, dk, dv]
    float32 after the last of them: what a decoder's state pool holds for a
    lane of that layer, by the plain scan (and with `lowp="state_bf16"`,
    what it would hold were S kept in bfloat16)."""
    if lowp not in LOWP:
        raise ValueError(f"unknown lower precision {lowp!r}")
    if dims(conf)["full"][0]:
        raise ValueError("the first layer is not a GDN layer")
    h = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["gdn"])
    return _gdn_state(h, lp, _frozen(conf), lowp)


def logits_one(params: Params, tokens, conf: Dict[str, Any],
               lowp: Optional[str] = None, start: int = 0,
               rows: Optional[int] = None, n: Optional[int] = None):
    """tokens [T] -> logits [T, V], or of the `rows` positions from
    `start` alone."""
    h = hidden_one(params, tokens, conf, lowp, n)
    if rows is not None:
        h = lax.dynamic_slice_in_dim(h, start, rows, axis=0)
    head = params["embed"] if conf.get("tie_word_embeddings") \
        else params["head"]
    return _head(h, params["norm_f"], head, _frozen(conf), lowp)


def serve_gaps(conf: Dict[str, Any], params, prompt, served, width: int,
               lowp: Optional[str] = None,
               rows: Optional[int] = None) -> np.ndarray:
    """For one finished greedy request: at each served position, how far
    the served token's float32 reference logit lies below the reference's
    best. With `lowp`, the token judged is not the served one but the one
    the control puts first at that position. `width` is the padded length
    the sequence is read at and `rows` the longest answer (the answer's own
    length is cut on the host, so shapes repeat over a cell's requests)."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)])[:-1]
    n, n_p = seq.size, len(prompt)
    if rows is None:
        rows = len(served)
    if n > width or len(served) > rows or n_p - 1 + rows > width:
        raise ValueError(f"a request of {n_p} + {len(served)} tokens does "
                         f"not fit width {width}, rows {rows}")
    buf = np.zeros((width,), np.int32)
    buf[:n] = seq
    ref = logits_one(params, buf, conf, None, n_p - 1, rows, n)
    if lowp is None:
        judged = np.zeros((rows,), np.int32)
        judged[:len(served)] = served
    else:
        judged = _first(logits_one(params, buf, conf, lowp, n_p - 1, rows,
                                   n))
    return np.asarray(_below_best(ref, jnp.asarray(judged)))[:len(served)]
