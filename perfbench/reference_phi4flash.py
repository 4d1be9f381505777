"""The plain reference for `phi4flash` (Phi-4-mini-flash-reasoning, the
SambaY decoder-hybrid-decoder): the forward pass in straightforward float32
`jax.numpy`, matmul precision "highest", one sequence at a time, EVERY layer
over every position, the cross-decoder included. No kernel, no cache, no
chunks, no batching, no import from the program. It reads the published key
names of the configuration file (and the sizes its `assumed` states under
their own keys) and follows the equations below.

``h`` is the residual stream, float32; ``n`` = `num_hidden_layers`, half =
n // 2. Every layer ``h += Mixer_l(LN_in(h))``, then ``h += W_2 (u *
silu(g))`` with ``[g | u] = W_1 LN_post(h)`` (no bias); ``h0 = E[tok]``;
logits ``LN_f(h) E^T``, tied. ``LN(x) = (x - mean) / sqrt(var + eps) * w +
b``, eps `layer_norm_eps`. No positional encoding anywhere.

Mixers, by layer l:

- l < half, even, and l = half: Mamba-1 (inner ``di = mamba_expand * d``,
  state N `mamba_d_state`, conv `mamba_d_conv`, rank R `mamba_dt_rank`):
  ``[x | z] = W_in u``; ``x = silu(conv(x) + b_conv)`` causal depthwise;
  ``[dt | B | C] = W_x x``; ``delta = softplus(W_dt dt + b_dt)``; ``A =
  -exp(A_log)`` [di, N]; ``S_t = exp(delta_t A) * S_{t-1} + (delta_t x_t)
  (outer) B_t``, a `lax.scan` over time; ``y_t = S_t C_t + D x_t``; ``y =
  y * silu(z)``; output ``W_out y``. Layer half keeps ``y`` as the memory.
- l < half, odd: differential attention over a window of `sliding_window`
  (``t - w < s <= t``); l = half + 1: over every position.
- l > half + 1, even: GMU, ``W_out (silu(W_in u) * m)`` with m the memory of
  the same token; odd: differential cross-attention, q alone, over layer
  half + 1's keys and values, causal.
- Differential attention (heads of ``hd = d / num_attention_heads``; query
  heads 2i, 2i+1 pair i, KV heads 2j, 2j+1 pair j, pair i reading pair i //
  2): ``q = W_q u + b_q``, ``k, v`` likewise; ``a1 = softmax(q1 k1^T / sqrt
  hd) [v1 | v2]``, ``a2 = softmax(q2 k2^T / sqrt hd) [v1 | v2]``; ``o =
  RMSNorm(a1 - lam a2) * w_sub * (1 - lam_init)`` over the pair's 2 hd;
  ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``, ``lam_init = 0.8 -
  0.6 exp(-0.3 l)``; output ``W_o o + b_o``. Rows of queries in blocks,
  a window layer's against the keys of the block's band.

`init_params` is also how the benchmark makes the weights it hands to the
program: every weight from the seed, leaf by leaf, rounded ONCE to
`weights_dtype`, in the program's layout (leaves stacked by layer kind;
``A_log`` laid [N, di]).

Two controls of "how correct is decided", never a run's path: `lowp="fp8"`
rounds both operands of every linear layer (the projections, the MLP, the
GMU, the head) to float8_e4m3 (per-tensor scale), the nearest precision
below the bfloat16 the configuration states; `lowp="state_bf16"` rounds
every Mamba-1 layer's state S to bfloat16 after every step, where the
configuration states float32.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Params = Dict[str, Any]
F8_MAX = 448.0   # float8_e4m3fn
LOWP = (None, "fp8", "state_bf16")
HI = lax.Precision.HIGHEST
SCORE_BYTES = 1 << 29      # one block of query rows' scores at the most


def dims(conf: Dict[str, Any]) -> Dict[str, Any]:
    n, d = conf["num_hidden_layers"], conf["hidden_size"]
    half = n // 2
    kinds = [("mamba" if i % 2 == 0 else "window") if i < half
             else "mamba" if i == half else "full" if i == half + 1
             else ("gmu" if i % 2 == 0 else "cross") for i in range(n)]
    heads = conf["num_attention_heads"]
    return {
        "V": conf["vocab_size"], "d": d, "L": n, "half": half,
        "kinds": kinds, "heads": heads,
        "kv_heads": conf["num_key_value_heads"], "hd": d // heads,
        "f": conf["intermediate_size"], "window": conf["sliding_window"],
        "di": conf.get("mamba_expand", 2) * d,
        "N": conf.get("mamba_d_state", 16), "K": conf.get("mamba_d_conv", 4),
        "R": conf.get("mamba_dt_rank", math.ceil(d / 16)),
        "n_mamba": kinds.count("mamba"),
        "n_attn": kinds.count("window") + kinds.count("full"),
        "n_cross": kinds.count("cross"), "n_gmu": kinds.count("gmu"),
        "eps": conf["layer_norm_eps"],
    }


def param_count(conf: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by layer kind, the tied head counted once."""
    m = dims(conf)
    d, hd, di, n, r = m["d"], m["hd"], m["di"], m["N"], m["R"]
    qd, kv = m["heads"] * hd, m["kv_heads"] * hd
    diff = 4 * hd + 2 * hd
    mamba = (d * 2 * di + m["K"] * di + di + di * (r + 2 * n) + r * di + di
             + n * di + di + di * d)
    attn = d * (qd + 2 * kv) + qd + 2 * kv + qd * d + d + diff
    cross = d * qd + qd + qd * d + d + diff
    gmu = 2 * d * di
    around = 3 * d * m["f"] + 4 * d       # the MLP and the two LayerNorms
    out = {"mamba_mixer": mamba, "attention_mixer": attn,
           "cross_mixer": cross, "gmu_mixer": gmu, "mlp_and_norms": around,
           "mamba_layer": mamba + around, "attention_layer": attn + around,
           "cross_layer": cross + around, "gmu_layer": gmu + around,
           "embedding": m["V"] * d, "final_norm": 2 * d}
    out["layers"] = (m["n_mamba"] * out["mamba_layer"]
                     + m["n_attn"] * out["attention_layer"]
                     + m["n_cross"] * out["cross_layer"]
                     + m["n_gmu"] * out["gmu_layer"])
    out["total"] = out["layers"] + out["embedding"] + out["final_norm"]
    return out


def init_params(conf: Dict[str, Any], key,
                residual_gain: float = 1.0) -> Params:
    """Every weight from `key`, in the layout the program's model holds,
    rounded once to `weights_dtype`, LEAF BY LEAF (assumed: the config gives
    no initialisation). Matrices Xavier-normal; the embedding normal(0,
    0.02); LayerNorm scales, their biases and the pair norms' scales
    normal(0, 0.1) about 1, 0 and 1; the attention biases and lambda
    vectors normal(0, 0.1); ``A_log = log(1 .. N)`` a channel; ``b_dt`` the
    inverse softplus of a step log-uniform in [1e-3, 1e-1]; ``D = 1``; the
    conv kernel U(+-1/2) and its bias 0. `residual_gain` multiplies the
    matrices that write into the residual stream (W_out of Mamba-1 and GMU,
    W_o, the MLP's W_2)."""
    m = dims(conf)
    d, hd, di, n, r, f = m["d"], m["hd"], m["di"], m["N"], m["R"], m["f"]
    qd, kv = m["heads"] * hd, m["kv_heads"] * hd
    L, nm, na, nc, ng = m["L"], m["n_mamba"], m["n_attn"], m["n_cross"], \
        m["n_gmu"]
    dtype = jnp.dtype(conf.get("weights_dtype", "bfloat16"))
    ks = iter(jax.random.split(key, 64))
    f32 = jnp.float32

    def xavier(shape, gain=1.0):
        std = gain * np.sqrt(2.0 / (shape[-2] + shape[-1]))
        return (jax.random.normal(next(ks), shape, f32)
                * np.float32(std)).astype(dtype)

    def near(shape, mean, std=0.1):
        return (mean + jax.random.normal(next(ks), shape, f32)
                * np.float32(std)).astype(dtype)

    def diff(layers):
        return {"lq1": near((layers, hd), 0.0), "lk1": near((layers, hd), 0.0),
                "lq2": near((layers, hd), 0.0), "lk2": near((layers, hd), 0.0),
                "sub": near((layers, 2 * hd), 1.0)}

    step = jnp.exp(jax.random.uniform(next(ks), (nm, di), f32,
                                      np.log(1e-3), np.log(1e-1)))
    return {
        "embed": near((m["V"], d), 0.0, 0.02),
        "norm_f": near((d,), 1.0), "norm_f_b": near((d,), 0.0),
        "mlp": {"norm2": near((L, d), 1.0), "norm2_b": near((L, d), 0.0),
                "W_up": xavier((L, d, 2 * f)),
                "W_down": xavier((L, f, d), residual_gain)},
        "selective": {
            "norm1": near((nm, d), 1.0), "norm1_b": near((nm, d), 0.0),
            "W_in": xavier((nm, d, 2 * di)),
            "conv_w": jax.random.uniform(next(ks), (nm, m["K"], di), f32,
                                         -0.5, 0.5).astype(dtype),
            "conv_b": jnp.zeros((nm, di), dtype),
            "W_x": xavier((nm, di, r + 2 * n)), "W_dt": xavier((nm, r, di)),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=f32))[None, :, None],
                (nm, n, di)).astype(dtype),
            "D": jnp.ones((nm, di), dtype),
            "W_out": xavier((nm, di, d), residual_gain)},
        "attn": {"norm1": near((na, d), 1.0), "norm1_b": near((na, d), 0.0),
                 "Wq": xavier((na, d, qd)), "Wk": xavier((na, d, kv)),
                 "Wv": xavier((na, d, kv)),
                 "Wo": xavier((na, qd, d), residual_gain),
                 "bq": near((na, qd), 0.0), "bk": near((na, kv), 0.0),
                 "bv": near((na, kv), 0.0), "bo": near((na, d), 0.0),
                 **diff(na)},
        "cross": {"norm1": near((nc, d), 1.0), "norm1_b": near((nc, d), 0.0),
                  "Wq": xavier((nc, d, qd)),
                  "Wo": xavier((nc, qd, d), residual_gain),
                  "bq": near((nc, qd), 0.0), "bo": near((nc, d), 0.0),
                  **diff(nc)},
        "gmu": {"norm1": near((ng, d), 1.0), "norm1_b": near((ng, d), 0.0),
                "W_in": xavier((ng, d, di)),
                "W_out": xavier((ng, di, d), residual_gain)},
    }


# ---------------------------------------------------------------------------
# the layers, float32
# ---------------------------------------------------------------------------


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, lowp: Optional[str], b=None):
    w = w.astype(jnp.float32)
    if lowp == "fp8":
        x, w = _fp8(x), _fp8(w)
    y = jnp.matmul(x, w, precision=HI)
    return y if b is None else y + b.astype(jnp.float32)


def _ln(x, w, b, eps):
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c / jnp.sqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32) + b.astype(jnp.float32)


def _frozen(conf: Dict[str, Any]):
    """The numbers the jitted layers read, as a hashable static argument."""
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "layer_norm_eps", "sliding_window",
            "num_hidden_layers")
    m = dims(conf)
    return tuple((k, conf[k]) for k in keep) + tuple(
        (k, m[k]) for k in ("di", "N", "K", "R"))


def _mamba(h, mp, ck, lowp):
    """h [T, d] -> (h + Mamba1(LN(h)), the gated y [T, di], S [di, N] after
    the last position); the recurrence one step at a time."""
    c = dict(ck)
    di, n, r, kw = c["di"], c["N"], c["R"], c["K"]
    f32 = lambda a: a.astype(jnp.float32)
    t = h.shape[0]
    u = _ln(h, mp["norm1"], mp["norm1_b"], c["layer_norm_eps"])
    xz = _linear(u, mp["W_in"], lowp)
    x, z = xz[:, :di], xz[:, di:]
    padded = jnp.pad(x, ((kw - 1, 0), (0, 0)))
    w = f32(mp["conv_w"])
    x = jax.nn.silu(sum(padded[i:i + t] * w[i] for i in range(kw))
                    + f32(mp["conv_b"]))
    dbc = _linear(x, mp["W_x"], lowp)
    delta = jax.nn.softplus(_linear(dbc[:, :r], mp["W_dt"], lowp)
                            + f32(mp["dt_bias"]))               # [T, di]
    b, cc = dbc[:, r:r + n], dbc[:, r + n:]
    a = -jnp.exp(f32(mp["A_log"])).T                            # [di, N]

    def step(s, inp):
        x_t, b_t, c_t, d_t = inp
        s = jnp.exp(d_t[:, None] * a) * s + (d_t * x_t)[:, None] * b_t[None]
        if lowp == "state_bf16":
            # not astype there and back: the chip's compiler keeps the
            # excess precision of such a pair and the control would read 0
            s = lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("cn,n->c", s, c_t, precision=HI)

    s_last, y = lax.scan(step, jnp.zeros((di, n), jnp.float32),
                         (x, b, cc, delta), unroll=8)
    y = (y + f32(mp["D"]) * x) * jax.nn.silu(z)
    return h + _linear(y, mp["W_out"], lowp), y, s_last


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _mamba_layer(h, mp, ck, lowp):
    return _mamba(h, mp, ck, lowp)[:2]


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _mamba_state(h, mp, ck, lowp):
    return _mamba(h, mp, ck, lowp)[2]


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _gmu_layer(h, memory, gp, ck, lowp):
    c = dict(ck)
    u = _ln(h, gp["norm1"], gp["norm1_b"], c["layer_norm_eps"])
    return h + _linear(jax.nn.silu(_linear(u, gp["W_in"], lowp)) * memory,
                       gp["W_out"], lowp)


@functools.partial(jax.jit, static_argnames=("ck", "lowp", "cross"))
def _project(h, ap, ck, lowp, cross):
    """An attention layer's normalised input and its q [T, heads, hd] (and
    k, v [T, kv_heads, hd] where it is not a cross-attention layer)."""
    c = dict(ck)
    t, heads = h.shape[0], c["num_attention_heads"]
    hd = c["hidden_size"] // heads
    u = _ln(h, ap["norm1"], ap["norm1_b"], c["layer_norm_eps"])
    q = _linear(u, ap["Wq"], lowp, ap["bq"]).reshape(t, heads, hd)
    if cross:
        return q, None, None
    kvh = c["num_key_value_heads"]
    k = _linear(u, ap["Wk"], lowp, ap["bk"]).reshape(t, kvh, hd)
    v = _linear(u, ap["Wv"], lowp, ap["bv"]).reshape(t, kvh, hd)
    return q, k, v


@functools.partial(jax.jit, static_argnames=("rows", "window"))
def _diff_block(q, k, v, start, lam, lam_init, sub, eps, rows, window):
    """Rows ``start .. start + rows`` of differential attention: q [T,
    heads, hd], k, v [T, kv_heads, hd] -> o [rows, heads / 2, 2 hd]; every
    key the rows could see scored (all of them, or with a window the rows'
    band), the invisible masked."""
    t, heads, hd = q.shape
    kvh = k.shape[1]
    qb = lax.dynamic_slice_in_dim(q, start, rows, 0).reshape(
        rows, heads // 2, 2, hd)
    first = 0
    if window:
        # keys start - window + 1 .. start + rows - 1, the ones before 0
        # padding that no row sees
        pad = ((window - 1, 0), (0, 0), (0, 0))
        k = lax.dynamic_slice_in_dim(jnp.pad(k, pad), start,
                                     rows + window - 1, 0)
        v = lax.dynamic_slice_in_dim(jnp.pad(v, pad), start,
                                     rows + window - 1, 0)
        first = start - window + 1
    n = k.shape[0]
    # pair i reads KV pair i // 2: its two maps' keys, both values
    kp = jnp.repeat(k.reshape(n, kvh // 2, 2, hd), 2, axis=1)
    vp = jnp.repeat(v.reshape(n, kvh // 2, 2 * hd), 2, axis=1)
    at = start + jnp.arange(rows)[:, None]
    key = first + jnp.arange(n)[None]
    see = (key <= at) & (key >= 0)
    if window:
        see = see & (key > at - window)

    def one(which):
        sc = jnp.einsum("rpd,spd->prs", qb[:, :, which], kp[:, :, which],
                        precision=HI) / np.float32(np.sqrt(hd))
        p = jax.nn.softmax(jnp.where(see[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("prs,spe->rpe", p, vp, precision=HI)

    o = one(0) - lam * one(1)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * sub.astype(jnp.float32)
    return o * (1.0 - lam_init)


@functools.partial(jax.jit, static_argnames=("lowp",))
def _attn_out(h, o, wo, bo, lowp):
    return h + _linear(o.reshape(o.shape[0], -1), wo, lowp, bo)


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _mlp_layer(h, fp, ck, lowp):
    c = dict(ck)
    f = c["intermediate_size"]
    gu = _linear(_ln(h, fp["norm2"], fp["norm2_b"], c["layer_norm_eps"]),
                 fp["W_up"], lowp)
    return h + _linear(gu[:, f:] * jax.nn.silu(gu[:, :f]), fp["W_down"],
                       lowp)


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _head(h, norm_f, norm_f_b, embed, ck, lowp):
    c = dict(ck)
    return _linear(_ln(h, norm_f, norm_f_b, c["layer_norm_eps"]), embed.T,
                   lowp)


def _rows(heads: int, t: int) -> int:
    rows = t
    while rows % 2 == 0 and rows > 64 and 4 * heads * rows * t > SCORE_BYTES:
        rows //= 2
    return rows


def _lam(ap, l: int):
    f32 = lambda a: a.astype(jnp.float32)
    init = 0.8 - 0.6 * math.exp(-0.3 * l)
    return (jnp.exp(jnp.sum(f32(ap["lq1"]) * f32(ap["lk1"])))
            - jnp.exp(jnp.sum(f32(ap["lq2"]) * f32(ap["lk2"]))) + init,
            np.float32(init))


def _differential(h, q, k, v, ap, l, m, window, lowp):
    t = q.shape[0]
    rows = _rows(m["heads"], t)
    lam, init = _lam(ap, l)
    eps = np.float32(m["eps"])
    o = jnp.concatenate([
        _diff_block(q, k, v, start, lam, init, ap["sub"], eps, rows, window)
        for start in range(0, t, rows)])
    return _attn_out(h, o, ap["Wo"], ap["bo"], lowp)


def hidden_one(params: Params, tokens, conf: Dict[str, Any],
               lowp: Optional[str] = None):
    """tokens [T] -> the residual stream [T, d] after the last layer: a
    Python loop over the layers, each upcasting its own weights."""
    if lowp not in LOWP:
        raise ValueError(f"unknown lower precision {lowp!r}")
    ck = _frozen(conf)
    m = dims(conf)
    layer = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)
    h = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    seen = {"mamba": 0, "attn": 0, "cross": 0, "gmu": 0}
    memory = shared = None
    for l, kind in enumerate(m["kinds"]):
        stack = {"window": "attn", "full": "attn"}.get(kind, kind)
        j = seen[stack]
        seen[stack] += 1
        if kind == "mamba":
            h, y = _mamba_layer(h, layer(params["selective"], j), ck, lowp)
            if l == m["half"]:
                memory = y
        elif kind == "gmu":
            h = _gmu_layer(h, memory, layer(params["gmu"], j), ck, lowp)
        elif kind == "cross":
            cp = layer(params["cross"], j)
            q, _, _ = _project(h, cp, ck, lowp, True)
            h = _differential(h, q, shared[0], shared[1], cp, l, m, 0, lowp)
        else:
            ap = layer(params["attn"], j)
            q, k, v = _project(h, ap, ck, lowp, False)
            if kind == "full":
                shared = (k, v)
            h = _differential(h, q, k, v, ap, l, m,
                              m["window"] if kind == "window" else 0, lowp)
        h = _mlp_layer(h, layer(params["mlp"], l), ck, lowp)
    return h


def state_one(params: Params, tokens, conf: Dict[str, Any],
              lowp: Optional[str] = None):
    """tokens [T], unpadded -> the first Mamba-1 layer's state S [di, N]
    float32 after the last of them: what a decoder's state pool holds for
    a lane of that layer (laid [N, di] there), by the plain scan (and with
    `lowp="state_bf16"`, what it would hold were S kept in bfloat16)."""
    if lowp not in LOWP:
        raise ValueError(f"unknown lower precision {lowp!r}")
    h = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    mp = jax.tree_util.tree_map(lambda a: a[0], params["selective"])
    return _mamba_state(h, mp, _frozen(conf), lowp)


def logits_one(params: Params, tokens, conf: Dict[str, Any],
               lowp: Optional[str] = None, start: int = 0,
               rows: Optional[int] = None):
    """tokens [T] -> logits [T, V], or of the `rows` positions from
    `start` alone."""
    h = hidden_one(params, tokens, conf, lowp)
    if rows is not None:
        h = lax.dynamic_slice_in_dim(h, start, rows, axis=0)
    return _head(h, params["norm_f"], params["norm_f_b"], params["embed"],
                 _frozen(conf), lowp)


@jax.jit
def _first(logits):
    return jnp.argmax(logits, axis=-1)


@jax.jit
def _below_best(ref, judged):
    got = jnp.take_along_axis(ref, judged[:, None], axis=-1)[:, 0]
    return ref.max(axis=-1) - got


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
    ref = logits_one(params, buf, conf, None, n_p - 1, rows)
    if lowp is None:
        judged = np.zeros((rows,), np.int32)
        judged[:len(served)] = served
    else:
        judged = _first(logits_one(params, buf, conf, lowp, n_p - 1, rows))
    return np.asarray(_below_best(ref, jnp.asarray(judged)))[:len(served)]
