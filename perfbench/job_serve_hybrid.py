"""The `serve_hybrid` job: the `serve` job (perfbench/job_serve.py) for a
model that is not the GPT-2-shaped TransformerLM. The configuration file's
`model_type` names the builder: which serve-only model of the program is
built, on weights made by which plain reference, and which reference judges
the tokens served.

Everything around the model is the `serve` job's: the same engine, HTTP
`POST /generate` streamed, the paged decoder, the closed loop of clients, the
warm-up of every prefill shape, the drain. The window's accounting below is
`job_serve.run`'s, line for line, so that `serve_tokens_per_s`, `ttft_p95_ms`,
`gap_p95_ms` and `setup_s` mean here what they mean there, and the readers
get the same `ctx` keys. (PERF.md, Open questions: once `job_serve.run` is
split into build, window and compare, this copy can go.) It adds to
`traced` the decoder's ticks of the traced tail (`ticks`), which the
state-update readers lay beside the device's events, and to `correct` one
number that reads the recurrent state itself (`state_bits_lost`): served
tokens cannot tell a state kept in fewer bits than the configuration states
(PERF.md, "How correct is decided").
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from perfbench import compare, harness, loadgen, trace_reduce, traffic
from perfbench.job_serve import DRAIN_S, _counters, _p95, _warm, \
    reference_width


def _granite(cell: Dict[str, Any]):
    """`granitemoehybrid` without experts: the program's HybridLM, served
    in the configuration's dtype at the cell's served context."""
    from deeplearning4j_tpu.models import hybrid
    from perfbench import reference_granite

    conf = cell["conf"]
    cfg = hybrid.HybridConfig.from_published(
        conf, max_len=int(cell["model"]["max_len"]))
    if np.dtype(cfg.compute_dtype).name != conf["weights_dtype"]:
        raise ValueError(f"the program serves {cfg.compute_dtype}, the "
                         f"configuration states {conf['weights_dtype']}")
    return (lambda params: hybrid.HybridLM.from_state(cfg, params),
            reference_granite, "ssm")


BUILDERS = {"granitemoehybrid": _granite}


def build(cell: Dict[str, Any]):
    """(params -> model, the reference module, the state pool's leaf that
    the configuration holds to float32) for the cell's configuration.
    Imports the program's model first of all, so that a program without it
    fails here, at once."""
    kind = cell["conf"].get("model_type")
    if kind not in BUILDERS:
        raise ValueError(f"job serve_hybrid: no builder for model_type "
                         f"{kind!r} (have {sorted(BUILDERS)})")
    return BUILDERS[kind](cell)


def mantissa_bits_lost(arrays) -> float:
    """How many of float32's 23 mantissa bits the worst of `arrays` leaves
    unused: for an array, the largest k such that 99% of its elements that
    are not 0 have their k lowest bits at 0. Arithmetic in float32 leaves
    none (the lowest bit is set in half of the elements: 0); values that
    were rounded to bfloat16 on the way, or are held in it, read 16, to
    float16 13. Nothing to read is not a number."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def counts(x):
        bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
        live = (bits << 1) != 0
        # the lowest bit set, the 24th at the latest: trailing zeros 0..23
        low = bits | jnp.uint32(1 << 23)
        zeros = lax.population_count((low & (~low + jnp.uint32(1)))
                                     - jnp.uint32(1))
        return jnp.sum(live), jnp.stack(
            [jnp.sum(live & (zeros >= k)) for k in range(1, 24)])

    lost = float("nan")
    for x in arrays:
        n, at_least = (np.asarray(v) for v in counts(x))
        if n:
            held = [k for k in range(1, 24) if at_least[k - 1] >= 0.99 * n]
            lost = np.nanmax([lost, float(max(held, default=0))])
    return float(lost)


def warm_every_eight(port: int, mix: Dict[str, Any], vocab: int, seed: int,
                     t_start: float) -> None:
    """`job_serve._warm` every 8 tokens of prompt length and not every 16:
    the program's ladder of prefill widths (powers of two and one and a
    half times them) has the rung 24 between 16 and 32, which a mix whose
    prompts start at 16 tokens reaches and `_warm` alone steps over; it
    then compiled inside the window. Above 16 every rung is a multiple of
    8."""
    user = mix["user_tokens"]
    for shift in (0, 8):
        _warm(port, dict(mix, user_tokens=dict(user, min=user["min"] + shift)),
              vocab, seed, t_start)


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_start: float, chips: int) -> Dict[str, Any]:
    model_of, reference, state_leaf = build(cell)

    import jax

    from deeplearning4j_tpu.obs import trace as obs_trace
    from deeplearning4j_tpu.serving.engine import ServingEngine

    conf, mix = cell["conf"], cell["mix"]
    vocab = conf["vocab_size"]
    key = harness.seed_key(seed)
    make = jax.jit(lambda k: reference.init_params(
        conf, k, **cell.get("weights", {})))
    lm = model_of(make(key))
    engine = ServingEngine(model=lm, port=0, **cell.get("engine", {})).start()
    try:
        harness.say(f"set-up: engine up at {time.perf_counter() - t_start:.1f} s")
        warm_every_eight(engine.port, mix, vocab, seed, t_start)
        clients = traffic.chat_requests(mix, vocab, seed,
                                        int(cell.get("per_client", 16)))
        # spans of the program's own tracer: on in the traced run only, so
        # that the end-to-end run pays nothing for them
        obs_trace.set_enabled(True if trace else None)
        obs_trace.tracer().clear()
        loop = loadgen.ClosedLoop(engine.port, clients)
        c0 = _counters(engine)
        setup_s = time.perf_counter() - t_start
        t0 = loop.start()
        loop.pump(t0 + seconds, send_new=True)
        t1 = time.perf_counter()
        c1 = _counters(engine)
        ticks = lambda lo, hi: [
            s for s in obs_trace.tracer().spans("serve.batch")
            if s["attrs"].get("kind") == "decode.paged"
            and s["duration_s"] is not None and lo <= s["t_mono"] < hi]
        spans = ticks(t0, t1)
        traced = None
        if trace:
            logdir = harness.trace_dir()
            trace_reduce.start(logdir)
            ta = time.perf_counter()
            loop.pump(ta + float(cell.get("trace_seconds", 3)),
                      send_new=True)
            tb = time.perf_counter()
            traced = {"window_s": tb - ta, "logdir": logdir,
                      "ticks": ticks(ta, tb)}
            trace_reduce.stop()
        loop.pump(time.perf_counter() + DRAIN_S, send_new=False)
        loop.close()
        kv = engine.kv_report()
        # the state pool as the last tick left it: no request is in flight
        state_bits_lost = mantissa_bits_lost(
            buf for pool in engine.state_pools().values()
            for buf in pool.get(state_leaf, ()))
    finally:
        obs_trace.set_enabled(None)
        engine.stop(drain=True)
    peak = harness.memory_peak_bytes(chips)

    # ---- the window, as the clients saw it -------------------------------
    mine = [r for r in loop.requests if t0 <= r.t_send < t1]
    failed = [r for r in mine if not r.ok]
    worst_ms = 1e3 * (seconds + DRAIN_S)
    ttft = [1e3 * (r.token_times[0] - r.t_send)
            if r.ok and r.token_times else worst_ms for r in mine]
    arrivals = prefill_tokens = 0
    gap_ms: List[float] = []
    pairs = 0.0
    for r in loop.requests:
        n_p = len(r.spec["tokens"])
        times = r.token_times
        if times and t0 <= times[0] < t1:
            prefill_tokens += n_p
            pairs += n_p * (n_p + 1) / 2
        for i, t in enumerate(times):
            if t0 <= t < t1:
                arrivals += 1
                pairs += n_p + i
                if i > 0:
                    gap_ms.append(1e3 * (t - times[i - 1]))
    window = {"seconds": t1 - t0, "requests": len(mine),
              "prefill_tokens": prefill_tokens,
              "decode_tokens": arrivals, "attended_pairs": pairs,
              "ttft_p50_ms": float(np.median(ttft)) if ttft else None,
              "gap_p50_ms": float(np.median(gap_ms)) if gap_ms else None,
              "gaps": len(gap_ms)}
    counters = {k: c1[k] - c0[k] for k in c0}

    # ---- correct ----------------------------------------------------------
    del engine, lm
    gc.collect()
    unanswered = [r for r in mine if not r.finished and r.error is None]
    malformed = [r for r in mine if r.ok and (
        len(r.tokens) != r.spec["n_new"]
        or any(not (isinstance(t, int) and 0 <= t < vocab)
               for t in r.tokens))]
    greedy = [r for r in mine if r.ok and r.spec["temperature"] == 0.0
              and r not in malformed]
    gaps: List[float] = []
    sample: List[Any] = []
    if greedy:
        rng = np.random.default_rng([int(seed), 11])
        greedy.sort(key=lambda r: -(len(r.spec["tokens"]) + len(r.tokens)))
        n = min(int(cell.get("compare_requests", 8)), len(greedy))
        sample = [greedy[0]] + [greedy[i] for i in sorted(
            1 + rng.choice(len(greedy) - 1, n - 1, replace=False))] \
            if n > 1 else greedy[:1]
        t_ref = time.perf_counter()
        gaps = reference_gaps(cell, reference, make(key),
                              [(r.spec["tokens"], r.tokens) for r in sample])
        harness.say(f"reference: {len(sample)} requests, {len(gaps)} tokens "
                    f"in {time.perf_counter() - t_ref:.1f} s")
    checks = compare.serve_checks(gaps, len(malformed),
                                  cell.get("limits", {}))
    checks["unanswered"] = {"value": float(len(unanswered)), "limit": 0.0,
                            "ok": not unanswered}
    compare._check(checks, "state_bits_lost", state_bits_lost,
                   cell.get("limits", {}))
    for r in failed[:3]:
        harness.say(f"failed request: {r.error}")
    return {
        "attempted": len(mine), "failed": len(failed),
        "end_to_end": {
            "serve_tokens_per_s": arrivals / (t1 - t0),
            "ttft_p95_ms": _p95(ttft) if ttft else worst_ms,
            "gap_p95_ms": _p95(gap_ms) if gap_ms else worst_ms,
            "setup_s": setup_s,
        },
        "window": window, "counters": counters, "spans": spans,
        "traced": traced, "memory_peak_bytes": peak, "checks": checks,
        "kv": kv, "sample": [(r.spec["tokens"], r.tokens) for r in sample],
    }


def reference_gaps(cell: Dict[str, Any], reference, params, sample,
                   lowp=None) -> List[float]:
    """The reference over each (prompt, served tokens) of `sample`, one
    padded length and one count of answer rows for the whole cell."""
    mix = cell["mix"]
    width = reference_width(mix)
    gaps: List[float] = []
    for prompt, served in sample:
        gaps += reference.serve_gaps(
            cell["conf"], params, prompt, served, width, lowp=lowp,
            rows=mix["output_tokens"]["max"]).tolist()
    return gaps
