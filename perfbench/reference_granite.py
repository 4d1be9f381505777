"""The plain reference for `granitemoehybrid` without experts (IBM Granite
4.0-H): the forward pass in straightforward float32 `jax.numpy`, matmul
precision "highest", one sequence at a time. No kernel, no cache, no chunks,
no batching, no import from the program. It reads the published key names of
the configuration file and follows the equations below; what the file's
`departures` list is where it leaves the published description.

With ``u`` a layer's normalised input (RMSNorm, eps `rms_norm_eps`):

- ``h = embedding_multiplier * E[tok]``; no positions of any kind.
- every layer ``h += residual_multiplier * Mixer(RMSNorm(h))``, then
  ``h += residual_multiplier * W_down(silu(g) * v)``, ``[g | v] = W_up
  RMSNorm(h)``; logits ``RMSNorm(h) E^T / logits_scaling``, head tied.
- attention: ``q = W_q u`` as `num_attention_heads` heads, ``k, v`` as
  `num_key_value_heads` heads, KV head j serving query heads g*j..g*j+g-1;
  scores ``attention_multiplier * q k^T``, causal, softmax.
- Mamba-2 (one group): ``[z | xBC | dt] = W_in u``; ``xBC = silu(conv(xBC))``
  causal, depthwise, width `mamba_d_conv`, with bias; ``x [H, P], B [N], C
  [N]``; ``delta = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; ``S_t =
  exp(delta_t A) S_{t-1} + delta_t x_t (outer) B_t``; ``y_t = S_t C_t + D
  x_t``; ``y = RMSNorm(y * silu(z))`` over the whole inner width; ``W_out y``.
  The recurrence is a plain `lax.scan` over time.

`init_params` is also how the benchmark makes the weights it hands to the
program: every weight from the seed, rounded ONCE to the configuration's
`weights_dtype` (bfloat16), leaves stacked by layer kind. The reference
upcasts the same values, a layer at a time in a Python loop over the layers,
so that a layer's float32 copy is all that stands beside the stored weights.

Two controls of "how correct is decided", never a run's path: `lowp="fp8"`
rounds both operands of every linear layer to float8_e4m3 (per-tensor scale),
the nearest precision below the bfloat16 the configuration states for them;
`lowp="state_bf16"` rounds the recurrent state ``S`` to bfloat16 after every
step, where the configuration states float32.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Params = Dict[str, Any]
F8_MAX = 448.0   # float8_e4m3fn
LOWP = (None, "fp8", "state_bf16")


def dims(conf: Dict[str, Any]) -> Dict[str, int]:
    d = conf["hidden_size"]
    h, p, n = conf["mamba_n_heads"], conf["mamba_d_head"], \
        conf["mamba_d_state"]
    inner = h * p
    kinds = conf["layer_types"]
    return {
        "V": conf["vocab_size"], "d": d, "f": conf["intermediate_size"],
        "L": len(kinds), "n_mamba": sum(k == "mamba" for k in kinds),
        "n_attn": sum(k == "attention" for k in kinds),
        "heads": conf["num_attention_heads"],
        "kv_heads": conf["num_key_value_heads"],
        "hd": d // conf["num_attention_heads"],
        "H": h, "P": p, "N": n, "inner": inner, "K": conf["mamba_d_conv"],
        "conv": inner + 2 * n, "in": 2 * inner + 2 * n + h,
    }


def param_count(conf: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by part, tied head counted once."""
    m = dims(conf)
    d, f = m["d"], m["f"]
    mlp = 2 * d + d * 2 * f + f * d      # both block norms ride here
    mamba = (d * m["in"] + m["inner"] * d + m["conv"] * m["K"] + m["conv"]
             + 3 * m["H"] + m["inner"])
    attn = 2 * d * d + 2 * d * m["kv_heads"] * m["hd"]
    return {"mamba_layer": mamba + mlp, "attention_layer": attn + mlp,
            "embedding": m["V"] * d, "final_norm": d,
            "total": (m["n_mamba"] * (mamba + mlp)
                      + m["n_attn"] * (attn + mlp) + m["V"] * d + d)}


def init_params(conf: Dict[str, Any], key,
                residual_gain: float = 1.0) -> Params:
    """Every weight from `key`, in the layout the program's HybridLM holds
    (leaves stacked by layer kind), rounded once to `weights_dtype`.

    Matrices are Xavier-normal, the embedding normal(0, 0.02), norm scales
    1, conv kernel uniform in +-1/sqrt(width) with zero bias, ``A_log =
    log(uniform(1, 16))``, ``dt_bias`` the inverse softplus of a step
    log-uniform in [1e-3, 1e-1], ``D = 1`` (the Mamba-2 family's
    convention; the file's `assumed`). `residual_gain` multiplies the three
    matrices that write into the residual stream (W_out, W_o, W_down): with
    the tied head and `embedding_multiplier` 12 a fresh model at gain 1
    puts its last input token first by several standard deviations of the
    logits, whatever the arithmetic, and a served token then tells nothing;
    at a gain that lets the blocks outweigh the embedding a served token
    depends on the whole computation. Serving cells, whose `correct` reads
    tokens, state theirs."""
    m = dims(conf)
    d, f, nm, na, L = m["d"], m["f"], m["n_mamba"], m["n_attn"], m["L"]
    kv = m["kv_heads"] * m["hd"]
    ks = iter(jax.random.split(key, 16))
    f32 = jnp.float32

    def xavier(shape, gain=1.0):
        std = gain * np.sqrt(2.0 / (shape[-2] + shape[-1]))
        return jax.random.normal(next(ks), shape, f32) * np.float32(std)

    ones = lambda *shape: jnp.ones(shape, f32)
    dt = jnp.exp(jax.random.uniform(next(ks), (nm, m["H"]), f32,
                                    np.log(1e-3), np.log(1e-1)))
    bound = 1.0 / np.sqrt(m["K"])
    out = {
        "embed": jax.random.normal(next(ks), (m["V"], d), f32)
        * np.float32(0.02),
        "norm_f": ones(d),
        "mamba": {
            "norm1": ones(nm, d), "W_in": xavier((nm, d, m["in"])),
            "conv_w": jax.random.uniform(next(ks), (nm, m["K"], m["conv"]),
                                         f32, -bound, bound),
            "conv_b": jnp.zeros((nm, m["conv"]), f32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(next(ks), (nm, m["H"]), f32,
                                                1.0, 16.0)),
            "D": ones(nm, m["H"]), "norm_y": ones(nm, m["inner"]),
            "W_out": xavier((nm, m["inner"], d), residual_gain)},
        "attn": {
            "norm1": ones(na, d), "Wq": xavier((na, d, d)),
            "Wk": xavier((na, d, kv)), "Wv": xavier((na, d, kv)),
            "Wo": xavier((na, d, d), residual_gain)},
        "mlp": {"norm2": ones(L, d), "W_up": xavier((L, d, 2 * f)),
                "W_down": xavier((L, f, d), residual_gain)},
    }
    dtype = jnp.dtype(conf.get("weights_dtype", "bfloat16"))
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), out)


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
    return jnp.matmul(x, w, precision=lax.Precision.HIGHEST)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _frozen(conf: Dict[str, Any]):
    """The configuration's numbers as a hashable static argument."""
    keep = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "mamba_n_heads", "mamba_d_head",
            "mamba_d_state", "mamba_d_conv", "vocab_size",
            "embedding_multiplier", "residual_multiplier",
            "attention_multiplier", "logits_scaling", "rms_norm_eps")
    return tuple((k, conf[k]) for k in keep) \
        + (("layer_types", tuple(conf["layer_types"])),)


def _mamba(h, mp, ck, lowp):
    """h [T, d] -> (h + r * Mamba2(RMSNorm(h)), S after the last
    position); the recurrence one step at a time."""
    conf = dict(ck)
    m = dims(conf)
    f32 = lambda a: a.astype(jnp.float32)
    t = h.shape[0]
    u = _rms(h, mp["norm1"], conf["rms_norm_eps"])
    zxbcdt = _linear(u, mp["W_in"], lowp)
    z = zxbcdt[:, :m["inner"]]
    xbc = zxbcdt[:, m["inner"]:m["inner"] + m["conv"]]
    dt = zxbcdt[:, m["inner"] + m["conv"]:]
    padded = jnp.pad(xbc, ((m["K"] - 1, 0), (0, 0)))
    w = f32(mp["conv_w"])
    conv = sum(padded[k:k + t] * w[k] for k in range(m["K"])) \
        + f32(mp["conv_b"])
    xbc = jax.nn.silu(conv)
    x = xbc[:, :m["inner"]].reshape(t, m["H"], m["P"])
    b = xbc[:, m["inner"]:m["inner"] + m["N"]]
    c = xbc[:, m["inner"] + m["N"]:]
    delta = jax.nn.softplus(dt + f32(mp["dt_bias"]))            # [T, H]
    a = -jnp.exp(f32(mp["A_log"]))                              # [H]
    skip = f32(mp["D"])

    def step(s, inp):
        x_t, b_t, c_t, d_t = inp
        s = jnp.exp(d_t * a)[:, None, None] * s \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        if lowp == "state_bf16":
            # not astype there and back: the chip's compiler keeps the
            # excess precision of such a pair and the control would read 0
            s = lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        y_t = jnp.einsum("hpn,n->hp", s, c_t,
                         precision=lax.Precision.HIGHEST) \
            + skip[:, None] * x_t
        return s, y_t

    s0 = jnp.zeros((m["H"], m["P"], m["N"]), jnp.float32)
    s_last, y = lax.scan(step, s0, (x, b, c, delta))
    y = _rms(y.reshape(t, m["inner"]) * jax.nn.silu(z), mp["norm_y"],
             conf["rms_norm_eps"])
    return (h + conf["residual_multiplier"]
            * _linear(y, mp["W_out"], lowp)), s_last


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _mamba_layer(h, mp, ck, lowp):
    return _mamba(h, mp, ck, lowp)[0]


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _mamba_state(h, mp, ck, lowp):
    return _mamba(h, mp, ck, lowp)[1]


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _attention_layer(h, ap, ck, lowp):
    conf = dict(ck)
    m = dims(conf)
    t = h.shape[0]
    grp = m["heads"] // m["kv_heads"]
    u = _rms(h, ap["norm1"], conf["rms_norm_eps"])
    q = _linear(u, ap["Wq"], lowp).reshape(t, m["kv_heads"], grp, m["hd"])
    k = _linear(u, ap["Wk"], lowp).reshape(t, m["kv_heads"], m["hd"])
    v = _linear(u, ap["Wv"], lowp).reshape(t, m["kv_heads"], m["hd"])
    s = jnp.einsum("tkgd,skd->kgts", q, k, precision=lax.Precision.HIGHEST) \
        * conf["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    att = jnp.einsum("kgts,skd->tkgd", p, v, precision=lax.Precision.HIGHEST)
    return h + conf["residual_multiplier"] * _linear(
        att.reshape(t, m["d"]), ap["Wo"], lowp)


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _mlp_layer(h, fp, ck, lowp):
    conf = dict(ck)
    f = conf["intermediate_size"]
    gv = _linear(_rms(h, fp["norm2"], conf["rms_norm_eps"]), fp["W_up"],
                 lowp)
    return h + conf["residual_multiplier"] * _linear(
        jax.nn.silu(gv[:, :f]) * gv[:, f:], fp["W_down"], lowp)


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _head(h, norm_f, embed, ck, lowp):
    conf = dict(ck)
    x = _rms(h, norm_f, conf["rms_norm_eps"])
    return _linear(x, embed.T, lowp) / conf["logits_scaling"]


def hidden_one(params: Params, tokens, conf: Dict[str, Any],
               lowp: Optional[str] = None):
    """tokens [T] -> the residual stream [T, d] after the last layer: a
    Python loop over the layers, each upcasting its own weights."""
    if lowp not in LOWP:
        raise ValueError(f"unknown lower precision {lowp!r}")
    ck = _frozen(conf)
    layer = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)
    h = conf["embedding_multiplier"] \
        * params["embed"][tokens].astype(jnp.float32)
    seen = {"mamba": 0, "attention": 0}
    for g, kind in enumerate(conf["layer_types"]):
        j = seen[kind]
        seen[kind] += 1
        if kind == "mamba":
            h = _mamba_layer(h, layer(params["mamba"], j), ck, lowp)
        elif kind == "attention":
            h = _attention_layer(h, layer(params["attn"], j), ck, lowp)
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        h = _mlp_layer(h, layer(params["mlp"], g), ck, lowp)
    return h


def state_one(params: Params, tokens, conf: Dict[str, Any],
              lowp: Optional[str] = None):
    """tokens [T], unpadded -> the first Mamba layer's recurrent state S
    [H, P, N] float32 after the last of them: what a decoder's state pool
    holds for a lane of that layer, by the plain scan (and with
    `lowp="state_bf16"`, what it would hold were S kept in bfloat16)."""
    if lowp not in LOWP:
        raise ValueError(f"unknown lower precision {lowp!r}")
    if conf["layer_types"][0] != "mamba":
        raise ValueError("the first layer is not a Mamba layer")
    h = conf["embedding_multiplier"] \
        * params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    mp = jax.tree_util.tree_map(lambda a: a[0], params["mamba"])
    return _mamba_state(h, mp, _frozen(conf), lowp)


def logits_one(params: Params, tokens, conf: Dict[str, Any],
               lowp: Optional[str] = None, start: int = 0,
               rows: Optional[int] = None):
    """tokens [T] -> logits [T, V], or of the `rows` positions from
    `start` alone (one compiled head for every request of a cell)."""
    h = hidden_one(params, jnp.asarray(tokens), conf, lowp)
    if rows is not None:
        h = lax.dynamic_slice_in_dim(h, start, rows, axis=0)
    return _head(h, params["norm_f"], params["embed"], _frozen(conf), lowp)


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
    every sequence is read at and `rows` the longest answer (both fixed a
    cell: every array on the device has the cell's shape and the answer's
    own length is cut on the host, so nothing compiles twice)."""
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
