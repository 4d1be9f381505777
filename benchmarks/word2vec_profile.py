#!/usr/bin/env python
"""Word2Vec SGNS device profile: is the epoch scan scatter-bound?

SURVEY section 7 (item 9b): the planned Pallas scatter-add kernel for sparse embedding rows should be
built ONLY if the profile shows the `.at[].add()` scatters dominating the
step; otherwise record the ruling-out. This script measures, on the real
chip, an attribution breakdown of one SGNS minibatch step
(nlp/word2vec.py:_neg_body — gathers, sigmoid math, two scatter-adds):

  full_ms         the real body (gathers + math + scatters)
  no_scatter_ms   ablation: scatters replaced by mathematically-comparable
                  dense reductions feeding the output (keeps the gathers +
                  einsum math; removes only the scatter HLOs)
  gather_ms       gathers alone (rows summed into the output)

scatter cost ~= full - no_scatter. The ablations are PROFILING-ONLY copies
of the body's math (cited inline); the training path is untouched.

Writes W2V_PROFILE.json and a verdict row into PALLAS_BENCH.json
("word2vec"."scatter_profile") so the decision is a committed artifact.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp import word2vec as w2v


def _force(x):
    jax.block_until_ready(x)


def _bench(fn, args, steps=40):
    out = fn(*args)
    _force(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    _force(out)
    return (time.perf_counter() - t0) / steps * 1e3


def main(vocab=50_000, dim=128, batch=2048, k=5):
    rng = np.random.default_rng(0)
    syn0 = jnp.asarray(rng.standard_normal((vocab, dim)) * 0.1, jnp.float32)
    syn1 = jnp.asarray(rng.standard_normal((vocab, dim)) * 0.1, jnp.float32)
    contexts = jnp.asarray(rng.integers(0, vocab, (batch,)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, vocab, (batch, k + 1)), jnp.int32)
    labels = jnp.zeros((batch, k + 1), jnp.float32).at[:, 0].set(1.0)
    live = jnp.ones((batch, k + 1), jnp.float32)
    alpha = jnp.asarray(0.025, jnp.float32)

    full = jax.jit(w2v._neg_body)

    def no_scatter(syn0, syn1neg, contexts, targets, labels, live, alpha):
        # PROFILING ABLATION of nlp/word2vec.py:_neg_body — identical
        # gathers + einsum/sigmoid math; the two .at[].add scatters are
        # replaced by dense sums so the update math still runs and feeds
        # the output, but no scatter HLO is emitted.
        l1 = syn0[contexts]
        s1 = syn1neg[targets]
        dot = jnp.einsum("bd,bkd->bk", l1, s1)
        f = jax.nn.sigmoid(dot)
        base = jnp.where(dot > w2v.MAX_EXP, labels - 1.0,
                         jnp.where(dot < -w2v.MAX_EXP, labels, labels - f))
        g = base * alpha * live
        neu1e = jnp.einsum("bk,bkd->bd", g, s1)
        upd1 = (g[..., None] * l1[:, None, :]).sum(axis=(0, 1))  # (D,)
        upd0 = neu1e.sum(axis=0)                                  # (D,)
        return syn0 + upd0[None, :], syn1neg + upd1[None, :]

    def gathers_only(syn0, syn1neg, contexts, targets, *_):
        l1 = syn0[contexts]
        s1 = syn1neg[targets]
        return l1.sum(), s1.sum()

    args = (syn0, syn1, contexts, targets, labels, live, alpha)
    res = {
        "vocab": vocab, "dim": dim, "batch": batch, "negatives": k,
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "full_ms": round(_bench(full, args), 3),
        "no_scatter_ms": round(_bench(jax.jit(no_scatter), args), 3),
        "gather_ms": round(_bench(jax.jit(gathers_only), args), 3),
    }
    scatter_ms = max(0.0, res["full_ms"] - res["no_scatter_ms"])
    res["scatter_ms_attributed"] = round(scatter_ms, 3)
    res["scatter_fraction"] = round(scatter_ms / max(res["full_ms"], 1e-9),
                                    3)
    if res["scatter_fraction"] >= 0.4:
        res["verdict"] = (
            "SCATTER-BOUND: the .at[].add scatters cost "
            f"{res['scatter_fraction']:.0%} of the step — a pallas "
            "row-scatter-add kernel is justified (SURVEY section 7 item 9b)")
    else:
        res["verdict"] = (
            f"NOT scatter-bound ({res['scatter_fraction']:.0%} of the "
            "step): the pallas scatter-add kernel is ruled out by "
            "measurement; gathers+math dominate and already ride XLA")
    # atomic write: a kill mid-dump must not leave a truncated artifact
    with open("W2V_PROFILE.json.tmp", "w") as f:
        json.dump(res, f, indent=1)
    os.replace("W2V_PROFILE.json.tmp", "W2V_PROFILE.json")
    from deeplearning4j_tpu.ops.kernel_gate import record_win

    record_win("word2vec", "scatter_profile", res)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
