"""Word2Vec scatter-add pre-analysis — CPU-labeled, NON-CHIP numbers.

The on-chip scatter profile (`benchmarks/word2vec_profile.py` ->
W2V_PROFILE.json) needs the chip and has not been taken; this pre-analysis
bounds the question on CPU so that when the profile lands, the kernel
decision is one step, not two.

The question (open since round 1): in the SGNS step (`nlp/word2vec.py
_neg_body` — the jitted redesign of SkipGram.java:214-252's Hogwild
updates), can the two `.at[].add()` scatter-adds into syn0/syn1neg come
to DOMINATE at reference-scale vocabularies (text8: ~71k words at
min_count 5, ~253k unfiltered), justifying a Pallas scatter kernel?

Method (all on forced-CPU jax, interpret-grade evidence only):
  * time the FULL jitted `_neg_body` per vocab size;
  * time a MATH-ONLY variant (identical gathers/sigmoid/einsum math,
    returns the dense update tensors instead of scattering them);
  * time a SCATTER-ONLY jit (the `_mean_scale` count scatter + the two
    row scatter-adds, on precomputed updates);
  * scatter_fraction = 1 - math_only/full  (plus the direct scatter
    timing as a cross-check).

Analytic bound (vocab-independence argument): the scatter's write set is
B*(K+2) rows x D floats REGARDLESS of V — growing the vocab only grows
the TABLE the rows land in (cache pressure on CPU, HBM paging on TPU),
not the update volume. So the scatter fraction is bounded by row-update
traffic vs the gather+einsum math on the same rows, and a vocab sweep
measures pure locality effects. Whatever this says, the DECISION stays
pending the on-chip profile: TPU scatter cost is dominated by dynamic
-update-slice serialization, which CPU numbers cannot see (hence the
loud non-chip label on the artifact).

Writes W2V_SCATTER_PREANALYSIS.json; run from the repo root:
    python benchmarks/word2vec_scatter_preanalysis.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # a CPU analysis by design

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from deeplearning4j_tpu.nlp.word2vec import (  # noqa: E402
    MAX_EXP,
    _mean_scale,
    _neg_body,
)

ARTIFACT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "W2V_SCATTER_PREANALYSIS.json")


def _math_only(syn0, syn1neg, contexts, targets, labels, live, alpha):
    """_neg_body with the scatters REMOVED: identical gathers + sigmoid
    math + einsums, returning the dense per-pair updates instead of
    applying them (kept in lockstep with nlp/word2vec._neg_body:92-116 —
    if the step changes, re-derive this)."""
    l1 = syn0[contexts]
    s1 = syn1neg[targets]
    dot = jnp.einsum("bd,bkd->bk", l1, s1)
    f = jax.nn.sigmoid(dot)
    base = jnp.where(
        dot > MAX_EXP, labels - 1.0,
        jnp.where(dot < -MAX_EXP, labels, labels - f))
    g = base * alpha * live
    neu1e = jnp.einsum("bk,bkd->bd", g, s1)
    return g[..., None] * l1[:, None, :], neu1e


def _scatter_only(syn0, syn1neg, contexts, targets, upd_t, neu1e, live):
    """Just the scatter side: the _mean_scale count scatters + the two
    row scatter-adds, on precomputed update tensors."""
    t_scale = _mean_scale(syn1neg.shape[0], targets, live)
    syn1neg = syn1neg.at[targets].add(t_scale[..., None] * upd_t)
    ctx_live = (live.sum(axis=1) > 0).astype(jnp.float32)
    ctx_scale = _mean_scale(syn0.shape[0], contexts, ctx_live)
    syn0 = syn0.at[contexts].add(ctx_scale[:, None] * neu1e)
    return syn0, syn1neg


def _time(fn, args, reps=5):
    out = fn(*args)
    jax.tree_util.tree_map(
        lambda a: np.asarray(a.reshape(-1)[:1]), out)  # force
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.tree_util.tree_map(lambda a: np.asarray(a.reshape(-1)[:1]), out)
    return (time.perf_counter() - t0) / reps


def _time_donated(fn, tables, rest, reps=5):
    """Time a table-mutating step under the PRODUCTION calling convention:
    syn0/syn1 donated and re-bound each call (nlp/word2vec.py's
    donate_argnums=(0, 1) discipline). Without donation each call COPIES
    both V x D tables, and the 'scatter cost' reads as a table-sized
    memcpy that scales with V — the first (wrong) version of this script
    measured exactly that artifact: 77->99% 'scatter fraction' that was
    really copy fraction."""
    tables = fn(*tables, *rest)  # warm/compile; re-bind donated buffers
    jax.tree_util.tree_map(lambda a: np.asarray(a.reshape(-1)[:1]), tables)
    t0 = time.perf_counter()
    for _ in range(reps):
        tables = fn(*tables, *rest)
    jax.tree_util.tree_map(lambda a: np.asarray(a.reshape(-1)[:1]), tables)
    return (time.perf_counter() - t0) / reps


def run(vocab_sizes=(10_000, 71_000, 253_000), batch=2048, k_neg=5,
        dim=128, reps=5):
    rng = np.random.default_rng(0)
    rows = []
    for v in vocab_sizes:
        syn0 = jnp.asarray(rng.standard_normal((v, dim)), jnp.float32)
        syn1 = jnp.asarray(rng.standard_normal((v, dim)), jnp.float32)
        contexts = jnp.asarray(rng.integers(0, v, batch), jnp.int32)
        targets = jnp.asarray(rng.integers(0, v, (batch, k_neg + 1)),
                              jnp.int32)
        labels = jnp.zeros((batch, k_neg + 1),
                           jnp.float32).at[:, 0].set(1.0)
        live = jnp.ones((batch, k_neg + 1), jnp.float32)
        alpha = jnp.asarray(0.025, jnp.float32)

        # donation matches production (word2vec.py donate_argnums=(0,1)):
        # the tables update in place; un-donated timing would measure a
        # V-scaled table memcpy instead of the scatter
        # graftlint: disable-file=donation-through-dispatch -- this pre-analysis bench deliberately measures the production donation contract (word2vec.py donate_argnums=(0,1)); tables are rebuilt between legs
        full = jax.jit(_neg_body, donate_argnums=(0, 1))
        math = jax.jit(_math_only)
        scat = jax.jit(_scatter_only, donate_argnums=(0, 1))

        t_full = _time_donated(full, (syn0, syn1),
                               (contexts, targets, labels, live, alpha),
                               reps)
        syn0 = jnp.asarray(rng.standard_normal((v, dim)), jnp.float32)
        syn1 = jnp.asarray(rng.standard_normal((v, dim)), jnp.float32)
        t_math = _time(math, (syn0, syn1, contexts, targets, labels, live,
                              alpha), reps)
        upd_t, neu1e = math(syn0, syn1, contexts, targets, labels, live,
                            alpha)
        t_scat = _time_donated(scat, (syn0, syn1),
                               (contexts, targets, upd_t, neu1e, live),
                               reps)
        rows.append({
            "vocab": v, "batch": batch, "negative_k": k_neg, "dim": dim,
            "full_step_ms": round(t_full * 1e3, 3),
            "math_only_ms": round(t_math * 1e3, 3),
            "scatter_only_ms": round(t_scat * 1e3, 3),
            "scatter_fraction_subtractive": round(
                max(0.0, 1.0 - t_math / t_full), 4),
            "scatter_fraction_direct": round(t_scat / t_full, 4),
        })
        print(f"V={v}: full {t_full*1e3:.2f}ms, math {t_math*1e3:.2f}ms, "
              f"scatter {t_scat*1e3:.2f}ms "
              f"(fraction ~{1 - t_math / t_full:.0%})", flush=True)
    return rows


def main():
    rows = run()
    fr = [r["scatter_fraction_subtractive"] for r in rows]
    artifact = {
        "label": "PRE-ANALYSIS on forced-CPU jax — NOT on-chip evidence; "
                 "the kernel decision stays pending W2V_PROFILE.json",
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "generated": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "rows": rows,
        "analysis": {
            "write_volume_vocab_independent": True,
            "note": "scatter writes B*(K+2) rows x D floats regardless of "
                    "V; the vocab sweep isolates table-locality effects. "
                    "On TPU the analogous cost is scatter serialization in "
                    "HBM, invisible to CPU timing — on-chip profile "
                    "required before any kernel work.",
            "cpu_scatter_fraction_range": [min(fr), max(fr)],
        },
    }
    with open(ARTIFACT, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"wrote {ARTIFACT}")
    return artifact


if __name__ == "__main__":
    main()
