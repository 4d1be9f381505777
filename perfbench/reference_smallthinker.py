"""The plain reference for `smallthinker` (PowerInfer SmallThinker-21BA3B):
the forward pass in straightforward float32 `jax.numpy`, matmul precision
"highest", one sequence at a time. No kernel, no cache, no batching, no
sorted routing, no import from the program. It reads the published key names
of the configuration file and follows the equations below; what the file
lists under `assumed` and `departures` is where the published config is
silent or where this leaves the family's description.

``h`` is the residual stream, float32; layer ``l`` of the ``n_layer`` held
here is layer ``l`` of the model (`rope_layout[l]`, `sliding_window_layout[l]`).

- ``h0 = E[tok]``, no multiplier. Logits ``W_head RMSNorm(h_L)``, the head
  its own matrix (`tie_word_embeddings` false).
- ``u = RMSNorm(h; g1, rms_norm_eps)``. Router logits ``r = W_r u``, one an
  expert, in float32: the router reads the layer's normalised input BEFORE
  attention, the tensor attention reads (assumed: normalised, not raw).
- attention: ``q = W_q u`` as `num_attention_heads` heads of `head_dim`,
  ``k = W_k u``, ``v = W_v u`` as `num_key_value_heads` heads (KV head j
  serves query heads g*j .. g*j+g-1), no bias (assumed). Where
  ``rope_layout[l] = 1``: rotary positions on q and k over the whole head,
  theta `rope_theta`, the two halves of a head paired (assumed: the family's
  rotate-half form), no scaling. Where ``sliding_window_layout[l] = 1``:
  token t sees s with ``t - sliding_window_size < s <= t`` (assumed: the
  window counts the token itself); else every ``s <= t``. Scores over
  ``sqrt(head_dim)``, softmax. ``h += W_o att``.
- ``x = RMSNorm(h; g2, rms_norm_eps)``. ``I`` = the
  `moe_num_active_primary_experts` largest of ``r``; ``w = softmax(r[I])``
  over the chosen (`moe_primary_router_apply_softmax`; `norm_topk_prob` then
  changes nothing). ``h += sum over e in I of w_e W_down,e (relu(W_gate,e x)
  * W_up,e x)``, width `moe_ffn_hidden_size`, no bias, no shared expert.

The experts are a loop over the experts, each applied to every row under the
row's weight for it (0 where not chosen); attention is taken a block of
query rows at a time against every key, so that a sequence of 8,192
positions fits beside 11 GB of weights. `init_params` is
also how the benchmark makes the weights it hands to the program: every
weight from the seed, leaf by leaf, rounded ONCE to `weights_dtype`
(bfloat16); the experts' matrices one buffer a layer (one float32 copy of
all of them would be 18 GB). The reference upcasts the same values, a layer
(an expert) at a time.

One control of "how correct is decided", never a run's path: `lowp="fp8"`
rounds both operands of every linear layer (the four projections, the
experts' matrices, the head) to float8_e4m3 (per-tensor scale), the nearest
precision below the bfloat16 the configuration states for them. The router
stays float32 in the control as in the configuration.
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
LOWP = (None, "fp8")
HI = lax.Precision.HIGHEST
SCORE_BYTES = 1 << 29      # one block of query rows' scores at the most


def dims(conf: Dict[str, Any]) -> Dict[str, int]:
    return {
        "V": conf["vocab_size"], "d": conf["hidden_size"],
        "L": int(conf.get("n_layer", conf["num_hidden_layers"])),
        "heads": conf["num_attention_heads"],
        "kv_heads": conf["num_key_value_heads"], "hd": conf["head_dim"],
        "E": conf["moe_num_primary_experts"],
        "k": conf["moe_num_active_primary_experts"],
        "f": conf["moe_ffn_hidden_size"],
        "window": conf["sliding_window_size"],
    }


def param_count(conf: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by kind, for the layers held here; the head counted
    beside the embedding where it is a matrix of its own."""
    m = dims(conf)
    d, qd, kv = m["d"], m["heads"] * m["hd"], m["kv_heads"] * m["hd"]
    layer = {"attention": d * (qd + 2 * kv) + qd * d, "router": d * m["E"],
             "norms": 2 * d, "experts": m["E"] * 3 * d * m["f"]}
    tied = bool(conf.get("tie_word_embeddings"))
    out = {k + "_a_layer": v for k, v in layer.items()}
    out["layer"] = sum(layer.values())
    out["embedding"] = m["V"] * d
    out["head"] = 0 if tied else m["V"] * d
    out["final_norm"] = d
    out["total"] = (m["L"] * out["layer"] + out["embedding"] + out["head"]
                    + d)
    return out


def init_params(conf: Dict[str, Any], key,
                residual_gain: float = 1.0) -> Params:
    """Every weight from `key`, in the layout the program's model holds,
    rounded once to `weights_dtype`, LEAF BY LEAF: the attention and router
    leaves stacked on a leading layer axis, the experts' two matrices
    (``W_in`` = gate | up, ``W_down``) one buffer a layer.

    Matrices are Xavier-normal, the embedding and the head normal(0, 0.02),
    norm scales 1 (assumed: the config gives no initialisation).
    `residual_gain` multiplies the matrices that write into the residual
    stream (W_o, W_down), as the other serving references offer it; with an
    untied head and an embedding of 0.02 the blocks outweigh the embedding
    at gain 1 already."""
    m = dims(conf)
    d, L, E, f = m["d"], m["L"], m["E"], m["f"]
    qd, kv = m["heads"] * m["hd"], m["kv_heads"] * m["hd"]
    dtype = jnp.dtype(conf.get("weights_dtype", "bfloat16"))
    ks = iter(jax.random.split(key, 8 + 2 * L))
    f32 = jnp.float32

    def xavier(shape, gain=1.0):
        std = gain * np.sqrt(2.0 / (shape[-2] + shape[-1]))
        return (jax.random.normal(next(ks), shape, f32)
                * np.float32(std)).astype(dtype)

    def normal(shape):
        return (jax.random.normal(next(ks), shape, f32)
                * np.float32(0.02)).astype(dtype)

    ones = lambda *shape: jnp.ones(shape, dtype)
    out = {
        "embed": normal((m["V"], d)), "norm_f": ones(d),
        "attn": {"norm1": ones(L, d), "Wq": xavier((L, d, qd)),
                 "Wk": xavier((L, d, kv)), "Wv": xavier((L, d, kv)),
                 "Wo": xavier((L, qd, d), residual_gain)},
        "moe": {"norm2": ones(L, d), "router": xavier((L, d, E)),
                "W_in": tuple(xavier((E, d, 2 * f)) for _ in range(L)),
                "W_down": tuple(xavier((E, f, d), residual_gain)
                                for _ in range(L))},
    }
    if not conf.get("tie_word_embeddings"):
        out["head"] = normal((m["V"], d))
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


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rotate(x, theta: float):
    """Rotary positions 0 .. T-1 on x [T, H, hd], halves paired."""
    t, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = jnp.asarray(np.arange(t, dtype=np.float64)[:, None] * freq[None],
                      jnp.float32)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _frozen(conf: Dict[str, Any]):
    """The numbers the jitted layers read, as a hashable static argument."""
    keep = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_num_active_primary_experts", "rope_theta", "rms_norm_eps")
    return tuple((k, conf[k]) for k in keep)


@functools.partial(jax.jit, static_argnames=("ck", "lowp", "rope"))
def _before(h, ap, router, ck, lowp, rope):
    """A layer up to its attention's products: the normalised input's
    router logits, and q, k, v (rotated where the layer has rotary)."""
    conf = dict(ck)
    t, hd = h.shape[0], conf["head_dim"]
    u = _rms(h, ap["norm1"], conf["rms_norm_eps"])
    r = jnp.matmul(u, router.astype(jnp.float32), precision=HI)
    q = _linear(u, ap["Wq"], lowp).reshape(t, conf["num_attention_heads"], hd)
    k = _linear(u, ap["Wk"], lowp).reshape(t, conf["num_key_value_heads"], hd)
    v = _linear(u, ap["Wv"], lowp).reshape(t, conf["num_key_value_heads"], hd)
    if rope:
        q, k = _rotate(q, conf["rope_theta"]), _rotate(k, conf["rope_theta"])
    return r, q, k, v


@functools.partial(jax.jit, static_argnames=("rows", "window"))
def _attend(q, k, v, start, rows, window):
    """Query rows start .. start+rows-1 against every key: a KV head's
    query heads' rows side by side, the keys innermost. The softmax is
    written out, its row maximum and its denominator each behind a barrier:
    left to itself the chip's compiler takes a maximum that is broadcast
    back over 8,192 keys as a window sliding over them, and one layer then
    takes a second (PERF.md section 6, PR 37)."""
    t, heads, hd = q.shape
    kv_heads = k.shape[1]
    grp = heads // kv_heads
    qb = lax.dynamic_slice_in_dim(q, start, rows, 0).reshape(
        rows, kv_heads, grp, hd).transpose(1, 2, 0, 3)
    sc = jnp.einsum("kqd,ksd->kqs", qb.reshape(kv_heads, grp * rows, hd),
                    k.transpose(1, 0, 2), precision=HI) \
        / np.float32(np.sqrt(hd))
    at = start + jnp.arange(rows)[:, None]
    s = jnp.arange(t)[None, :]
    see = s <= at
    if window:
        see = see & (s > at - window)
    sc = jnp.where(see[None, None], sc.reshape(kv_heads, grp, rows, t),
                   -jnp.inf)
    e = jnp.exp(sc - lax.optimization_barrier(
        jnp.max(sc, axis=-1, keepdims=True)))
    p = e / lax.optimization_barrier(jnp.sum(e, axis=-1, keepdims=True))
    att = jnp.einsum("kqs,ksd->kqd", p.reshape(kv_heads, grp * rows, t),
                     v.transpose(1, 0, 2), precision=HI)
    return att.reshape(kv_heads, grp, rows, hd).transpose(
        2, 0, 1, 3).reshape(rows, heads * hd)


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _after(h, att, r, n, ap, norm2, ck, lowp):
    """The attention's output into the stream, the experts' input, and each
    row's weight for each expert ([T, E], 0 where not chosen; rows from n
    on, padding, choose none)."""
    conf = dict(ck)
    k = conf["moe_num_active_primary_experts"]
    h = h + _linear(att, ap["Wo"], lowp)
    x = _rms(h, norm2, conf["rms_norm_eps"])
    topv, topi = lax.top_k(r, k)
    w = jax.nn.softmax(topv, axis=-1)
    chosen = jax.nn.one_hot(topi, r.shape[-1], dtype=jnp.float32)  # [T,k,E]
    weight = jnp.einsum("tke,tk->te", chosen, w)
    weight = jnp.where((jnp.arange(h.shape[0]) < n)[:, None], weight, 0.0)
    return h, x, weight


@functools.partial(jax.jit, static_argnames=("lowp",))
def _experts(h, x, weight, w_in, w_down, lowp):
    """h + the experts' part: a loop over the experts, each applied to every
    row and counted under the row's weight for it (0 where the row did not
    choose it): ten times the products the routing needs, and nothing to
    sort, gather or scatter."""
    f = w_down.shape[1]

    def one(e, y):
        gu = _linear(x, w_in[e], lowp)
        out = _linear(jax.nn.relu(gu[:, :f]) * gu[:, f:], w_down[e], lowp)
        return y + out * weight[:, e][:, None]

    return h + lax.fori_loop(0, weight.shape[1], one, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("ck", "lowp"))
def _head(h, norm_f, head, ck, lowp):
    conf = dict(ck)
    return _linear(_rms(h, norm_f, conf["rms_norm_eps"]), head.T, lowp)


def _rows(heads: int, t: int) -> int:
    rows = t
    while rows % 2 == 0 and rows > 64 and 4 * heads * rows * t > SCORE_BYTES:
        rows //= 2
    return rows


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
    for l in range(m["L"]):
        ap = layer(params["attn"], l)
        r, q, k, v = _before(h, ap, params["moe"]["router"][l], ck, lowp,
                             bool(conf["rope_layout"][l]))
        window = m["window"] if conf["sliding_window_layout"][l] else 0
        att = jnp.concatenate([
            _attend(q, k, v, start, rows, window)
            for start in range(0, t, rows)])
        h, x, weight = _after(h, att, r, n, ap, params["moe"]["norm2"][l],
                              ck, lowp)
        h = _experts(h, x, weight, params["moe"]["W_in"][l],
                     params["moe"]["W_down"][l], lowp)
    return h


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
    ref = logits_one(params, buf, conf, None, n_p - 1, rows, n)
    if lowp is None:
        judged = np.zeros((rows,), np.int32)
        judged[:len(served)] = served
    else:
        judged = _first(logits_one(params, buf, conf, lowp, n_p - 1, rows,
                                   n))
    return np.asarray(_below_best(ref, jnp.asarray(judged)))[:len(served)]
