"""The `serve` job: HTTP `POST /generate` with `"stream": true` on a
ServingEngine in this process, answered by the paged decoder, under a
closed loop of clients (perfbench/loadgen.py) whose requests come from the
cell's traffic mix and the seed.

Set-up makes the weights from the seed in one jitted call, starts the engine
and sends one short request at every prompt length a prefill shape can start
at, so that every program the window will use is compiled (or found in the
cache) before it. After the window the clients stop sending, every request in
flight is waited for, the engine is stopped and freed, and the reference runs
once over a sample of the finished greedy requests.
"""
from __future__ import annotations

import gc
import re
import time
from typing import Any, Dict, List

import numpy as np

from perfbench import compare, harness, loadgen, reference, trace_reduce, \
    traffic

DRAIN_S = 60.0          # how long past the close an answer is waited for
WARM_S = 1000.0         # a cold first run compiles every shape in warm-up
TICK_SAMPLES = re.compile(
    r"^dl4j_dispatch_(decode_ticks|decode_tokens)(?:_total)?\{[^}]*\}\s+(\S+)",
    re.M)


def _counters(engine) -> Dict[str, float]:
    """The program's own counts: the serving ledger as /metrics gives it,
    and the decoder's tick ledger from the central registry's exposition."""
    from deeplearning4j_tpu.obs import registry as obs_registry

    serving = engine.metrics()["serving"]
    out = {k: float(serving[k]) for k in
           ("prefix_hits", "prefix_lookups", "generated_tokens",
            "preemptions", "errors", "timeouts", "rejected_429")}
    out["decode_ticks"] = out["decode_tokens"] = 0.0
    text = obs_registry.default_registry().render_prometheus()
    for name, value in TICK_SAMPLES.findall(text):
        out[name] += float(value)
    return out


def _warm(port: int, mix: Dict[str, Any], vocab: int, seed: int,
          t_start: float) -> None:
    """One greedy request of one token at every prompt length a prefill
    shape can start at, eight at a time: every program the window uses is
    compiled (or found in the cache) here, in set-up."""
    rng = np.random.default_rng([int(seed), 7])
    specs = [{"tokens": rng.integers(0, vocab, n, dtype=np.int32),
              "n_new": 1, "temperature": 0.0, "seed": 0}
             for n in traffic.warm_lengths(mix)]
    loop = loadgen.ClosedLoop(port, [specs[i::8] for i in range(8)],
                              repeat=False)
    loop.start()
    deadline = time.perf_counter() + WARM_S
    done = 0
    while loop.in_flight and time.perf_counter() < deadline:
        loop.pump(min(deadline, time.perf_counter() + 20.0), send_new=True)
        n = sum(1 for r in loop.requests if r._completed)
        if n != done:
            done = n
            harness.say(f"set-up: {done} of {len(specs)} warm-up requests "
                        f"answered at {time.perf_counter() - t_start:.1f} s")
    hung = loop.in_flight
    loop.close()
    bad = [r.error for r in loop.requests if not r.ok]
    if bad or hung:
        raise RuntimeError(f"warm-up: {hung} requests unanswered after "
                           f"{WARM_S:.0f} s, errors {bad[:3]}")


def reference_width(mix: Dict[str, Any]) -> int:
    """One padded length for every sequence the reference reads: the mix's
    longest prompt and output, rounded up to 64."""
    longest = (mix["system_tokens"] + mix["user_tokens"]["max"]
               + mix["output_tokens"]["max"])
    return -(-longest // 64) * 64


def _p95(values: List[float]) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_start: float, chips: int) -> Dict[str, Any]:
    import jax

    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from deeplearning4j_tpu.obs import trace as obs_trace
    from deeplearning4j_tpu.serving.engine import ServingEngine

    conf, mix = cell["conf"], cell["mix"]
    vocab = conf["vocab_size"]
    cfg = TransformerConfig(**harness.program_config(conf))
    key = harness.seed_key(seed)
    make = jax.jit(lambda k: reference.init_params(
        conf, k, **cell.get("weights", {})))
    lm = TransformerLM.from_state(cfg, make(key))
    engine = ServingEngine(model=lm, port=0, **cell.get("engine", {})).start()
    try:
        harness.say(f"set-up: engine up at {time.perf_counter() - t_start:.1f} s")
        _warm(engine.port, mix, vocab, seed, t_start)
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
        spans = [s for s in obs_trace.tracer().spans("serve.batch")
                 if s["attrs"].get("kind") == "decode.paged"
                 and s["duration_s"] is not None and t0 <= s["t_mono"] < t1]
        traced = None
        if trace:
            logdir = harness.trace_dir()
            trace_reduce.start(logdir)
            ta = time.perf_counter()
            loop.pump(ta + float(cell.get("trace_seconds", 3)),
                      send_new=True)
            traced = {"window_s": time.perf_counter() - ta,
                      "logdir": logdir}
            trace_reduce.stop()
        loop.pump(time.perf_counter() + DRAIN_S, send_new=False)
        loop.close()
        kv = engine.kv_report()
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
        width = reference_width(mix)
        t_ref = time.perf_counter()
        params = make(key)
        for r in sample:
            gaps += reference.serve_gaps(conf, params, r.spec["tokens"],
                                         r.tokens, width).tolist()
        del params
        harness.say(f"reference: {len(sample)} requests, {len(gaps)} tokens "
                    f"in {time.perf_counter() - t_ref:.1f} s")
    checks = compare.serve_checks(gaps, len(malformed),
                                  cell.get("limits", {}))
    checks["unanswered"] = {"value": float(len(unanswered)), "limit": 0.0,
                            "ok": not unanswered}
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
