"""The plain reference: the GPT-2-shaped model of the configuration files in
straightforward float32 `jax.numpy`, matmul precision "highest". No kernel,
no cache, no batching, no import from the program.

It follows the configuration file and the block it describes (see each
file's `departures`): learned positions, pre-LayerNorm, full multi-head
causal attention without projection biases, tanh-GELU MLP with biases, final
LayerNorm, head tied to the embedding, mean next-token cross-entropy, Adam.

`init_params` is also how the benchmark makes the weights it hands to the
program: one jitted call from the seed, float32 masters. After the window the
reference makes them again from the same seed; it takes nothing that the
program has touched.

`lowp="fp8"` is the control of "how correct is decided": the same mathematics
with every linear layer's two operands rounded to float8_e4m3 (per-tensor
scale to the format's range), the nearest precision below the bfloat16 the
configurations state. The benchmark's runs never use it.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Params = Dict[str, Any]
F8_MAX = 448.0   # float8_e4m3fn


def dims(conf: Dict[str, Any]) -> Tuple[int, int, int, int, int, int]:
    return (conf["vocab_size"], conf["n_embd"], conf["n_layer"],
            conf["n_head"], conf["n_inner"], conf["n_positions"])


def init_params(conf: Dict[str, Any], key,
                residual_out: str = "scaled") -> Params:
    """Float32 masters in the layout the program's TransformerLM holds:
    block leaves stacked on a leading layer axis.

    `residual_out` is the scale of the two matrices that write into the
    residual stream (Wo, W2). "scaled" is the repo's scheme, 0.02/sqrt(2L):
    a fresh model's residual is then its input embedding, and with the tied
    head it repeats its last token with a margin of some 30 standard
    deviations of the logits, so that no arithmetic could alter a greedy
    token. "xavier" draws them like the other matrices: every block's output
    outweighs the embedding, and a served token depends on the whole
    computation. Serving cells, whose `correct` reads tokens, use it."""
    V, d, L, _, f, T = dims(conf)
    if residual_out not in ("scaled", "xavier"):
        raise ValueError(f"unknown residual_out {residual_out!r}")
    ks = jax.random.split(key, 8)
    normal = lambda k, shape, std: (
        jax.random.normal(k, shape, jnp.float32) * np.float32(std))
    xavier = lambda k, shape: normal(
        k, shape, np.sqrt(2.0 / (shape[-2] + shape[-1])))
    out = ((lambda k, shape: normal(k, shape, 0.02 / np.sqrt(2 * L)))
           if residual_out == "scaled" else xavier)
    ones = lambda *s: jnp.ones(s, jnp.float32)
    zeros = lambda *s: jnp.zeros(s, jnp.float32)
    return {
        "embed": normal(ks[0], (V, d), 0.02),
        "pos": normal(ks[1], (T, d), 0.01),
        "lnf_g": ones(d), "lnf_b": zeros(d),
        "blocks": {
            "ln1_g": ones(L, d), "ln1_b": zeros(L, d),
            "Wq": xavier(ks[2], (L, d, d)), "Wk": xavier(ks[3], (L, d, d)),
            "Wv": xavier(ks[4], (L, d, d)),
            "Wo": out(ks[5], (L, d, d)),
            "ln2_g": ones(L, d), "ln2_b": zeros(L, d),
            "W1": xavier(ks[6], (L, d, f)), "b1": zeros(L, f),
            "W2": out(ks[7], (L, f, d)), "b2": zeros(L, d),
        },
    }


def _fp8(x):
    """Round to float8_e4m3 after scaling the tensor's largest magnitude to
    the format's largest; straight-through for the gradient."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + lax.stop_gradient(q - x)


def _linear(x, w, lowp: Optional[str]):
    if lowp == "fp8":
        x, w = _fp8(x), _fp8(w)
    elif lowp is not None:
        raise ValueError(f"unknown lower precision {lowp!r}")
    return jnp.matmul(x, w, precision=lax.Precision.HIGHEST)


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _block(h, bp, n_head: int, eps: float, lowp):
    """One pre-LN block on h [T, d] (one sequence)."""
    t, d = h.shape
    hd = d // n_head
    x = _ln(h, bp["ln1_g"], bp["ln1_b"], eps)
    split = lambda a: a.reshape(t, n_head, hd).transpose(1, 0, 2)
    q = split(_linear(x, bp["Wq"], lowp))
    k = split(_linear(x, bp["Wk"], lowp))
    v = split(_linear(x, bp["Wv"], lowp))
    s = jnp.einsum("hqd,hkd->hqk", q, k,
                   precision=lax.Precision.HIGHEST) / np.float32(np.sqrt(hd))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("hqk,hkd->hqd", p, v, precision=lax.Precision.HIGHEST)
    h = h + _linear(a.transpose(1, 0, 2).reshape(t, d), bp["Wo"], lowp)
    x = _ln(h, bp["ln2_g"], bp["ln2_b"], eps)
    inner = jax.nn.gelu(_linear(x, bp["W1"], lowp) + bp["b1"],
                        approximate=True)
    return h + _linear(inner, bp["W2"], lowp) + bp["b2"]


def logits_one(params: Params, tokens, conf: Dict[str, Any],
               lowp: Optional[str] = None):
    """tokens [T] -> logits [T, V] for one sequence, layer by layer (each
    block recomputed in the backward pass, so one layer's activations live
    at a time)."""
    eps = conf["layer_norm_epsilon"]
    n_head = conf["n_head"]
    t = tokens.shape[0]
    h = params["embed"][tokens] + params["pos"][:t]

    @jax.checkpoint
    def body(h, bp):
        return _block(h, bp, n_head, eps, lowp), None

    h, _ = lax.scan(body, h, params["blocks"])
    h = _ln(h, params["lnf_g"], params["lnf_b"], eps)
    return _linear(h, params["embed"].T, lowp)


def nll_one(params, tokens, targets, conf, lowp=None):
    logp = jax.nn.log_softmax(logits_one(params, tokens, conf, lowp), -1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0].mean()


def loss_and_grads(params, x, y, conf, lowp=None):
    """Mean loss over the rows of x [N, T] and its gradient, a row at a
    time so that it fits beside the reference's own Adam state."""
    n = x.shape[0]

    def row(carry, xy):
        loss_a, g_a = carry
        loss, g = jax.value_and_grad(nll_one)(params, xy[0], xy[1], conf,
                                              lowp)
        g_a = jax.tree_util.tree_map(lambda a, b: a + b / n, g_a, g)
        return (loss_a + loss / n, g_a), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    (loss, grads), _ = lax.scan(row, (jnp.zeros((), jnp.float32), zero),
                                (x, y))
    return loss, grads


def adam(params, grads, m, v, t, opt: Dict[str, Any]):
    """Plain Adam as the configuration's `optimizer` states it; t counts
    from 1."""
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["eps"], \
        opt["learning_rate"]
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v,
                               grads)
    tf = jnp.asarray(t, jnp.float32)
    corr = jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * corr * m / (jnp.sqrt(v) + eps),
        params, m, v)
    return params, m, v


def leaf_norms(tree) -> Dict[str, Any]:
    """Euclidean norm of every leaf, by a flat name (`blocks.Wq`)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {".".join(str(getattr(p, "key", p)) for p in path):
            jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for path, leaf in flat}


def delta_norms(params, conf, key) -> Dict[str, Any]:
    """Norm of each leaf's change from the weights `init_params` makes for
    `key`, which are made again here and not kept."""
    p0 = init_params(conf, key)
    return leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, params, p0))


@functools.partial(jax.jit, static_argnames=("conf_key", "lowp", "rows"),
                   donate_argnums=(0, 1, 2))
def _train_step(params, m, v, t, x, y, conf_key, lowp, rows):
    conf = dict(conf_key)
    conf["optimizer"] = dict(conf["optimizer"])
    if rows is not None:
        # the planted fault "half of the batch left out, the mean taken
        # over the rest": a control for the comparison, never a run's path
        x, y = x[:rows], y[:rows]
    loss, grads = loss_and_grads(params, x, y, conf, lowp)
    gn = leaf_norms(grads)
    params, m, v = adam(params, grads, m, v, t, conf["optimizer"])
    return params, m, v, loss, gn


def _conf_key(conf: Dict[str, Any]):
    keep = ("vocab_size", "n_embd", "n_layer", "n_head", "n_inner",
            "n_positions", "layer_norm_epsilon")
    opt = tuple(sorted((k, v) for k, v in conf["optimizer"].items()
                       if k != "name"))
    return tuple((k, conf[k]) for k in keep) + (("optimizer", opt),)


def train_reference(conf: Dict[str, Any], key, batches, lowp=None,
                    rows: Optional[int] = None) -> Dict[str, Any]:
    """Follow the first len(batches) optimizer steps from the weights of
    `key`. Returns each step's loss, the per-leaf norms of the first
    gradient, and the per-leaf norms of the parameters' change after the
    last step."""
    ck = _conf_key(conf)
    make = jax.jit(lambda k: init_params(conf, k))
    params = make(key)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first = [], None
    for i, (x, y) in enumerate(batches):
        params, m, v, loss, gn = _train_step(
            params, m, v, i + 1, jnp.asarray(x), jnp.asarray(y), ck, lowp,
            rows)
        losses.append(float(loss))
        if i == 0:
            first = {k: float(a) for k, a in gn.items()}
    change = jax.jit(lambda p, k: delta_norms(p, conf, k))(params, key)
    change = {k: float(a) for k, a in change.items()}
    del params, m, v
    return {"losses": losses, "grad_norms": first, "change_norms": change}


@functools.partial(jax.jit, static_argnames=("conf_key", "lowp"))
def _logits(params, tokens, conf_key, lowp):
    return logits_one(params, tokens, dict(conf_key), lowp)


def serve_gaps(conf: Dict[str, Any], params, prompt, served, width: int,
               lowp: Optional[str] = None) -> np.ndarray:
    """For one finished greedy request: at each served position, how far
    the served token's float32 reference logit lies below the reference's
    best. With `lowp`, the token judged is not the served one but the one
    the lower precision puts first at that position (the control)."""
    ck = tuple(kv for kv in _conf_key(conf) if kv[0] != "optimizer")
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)])[:-1]
    n, n_p = seq.size, len(prompt)
    buf = np.zeros((width,), np.int32)
    buf[:n] = seq
    ref = _logits(params, jnp.asarray(buf), ck, None)[n_p - 1:n]
    if lowp is None:
        judged = jnp.asarray(np.asarray(served, np.int32))
    else:
        judged = jnp.argmax(
            _logits(params, jnp.asarray(buf), ck, lowp)[n_p - 1:n], axis=-1)
    got = jnp.take_along_axis(ref, judged[:, None], axis=-1)[:, 0]
    return np.asarray(ref.max(axis=-1) - got)
