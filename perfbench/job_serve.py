"""The `serve` job: HTTP `POST /generate` with `"stream": true` on a
ServingEngine in this process, answered by the paged decoder, under a
closed loop of clients (perfbench/loadgen.py) whose requests come from the
cell's traffic mix and the seed.

Set-up makes the weights from the seed in one jitted call, starts the engine
and sends one short request at every prompt length a prefill shape can start
at, so that every program the window will use is compiled (or found in the
cache) before it; it ends where the clients start, and says where it went
(`setup`: imports and weights, the engine's start, the warm-up). The clients
then run for the cell's `warm_in_seconds` before the window opens: their
opening burst, every client's first request at one instant, is the load
generator's start and no deployment's, so it is in no tail (perfbench/
window.py says what belongs to the window). After the window the clients
stop sending, every request in flight is waited for, the engine is stopped
and freed, and the reference runs once over a sample of the finished greedy
requests.

One job for every served model: `Served` says how the cell's model is built
and judged. This file's is the GPT-2-shaped TransformerLM;
perfbench/job_serve_hybrid.py hands `run` another.
"""
from __future__ import annotations

import dataclasses
import gc
import re
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from perfbench import compare, harness, loadgen, reference, trace_reduce, \
    traffic, window
from perfbench.compile_log import CompileLog
from perfbench.window import DRAIN_S

WARM_S = 1000.0         # a cold first run compiles every shape in warm-up
TICK_SAMPLES = re.compile(
    r"^dl4j_dispatch_(decode_ticks|decode_tokens)(?:_total)?\{[^}]*\}\s+(\S+)",
    re.M)


@dataclasses.dataclass
class Served:
    """How a cell's model is built and judged. `init(conf, key, **weights)`
    makes the weights (the plain reference's own function: the program makes
    none); `model_of(params)` is the program's model on them; `gaps(cell,
    params, sample)` runs the reference over `sample`, (prompt, served
    tokens) pairs, and returns every served token's gap; `after_drain(
    engine)`, where the served tokens cannot tell all that the configuration
    states, reads further numbers off the engine once no request is in
    flight, each compared under the limit of its name."""
    init: Callable[..., Any]
    model_of: Callable[[Any], Any]
    gaps: Callable[[Dict[str, Any], Any, List[Any]], List[float]]
    after_drain: Optional[Callable[[Any], Dict[str, float]]] = None


def dense(cell: Dict[str, Any]) -> Served:
    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    cfg = TransformerConfig(**harness.program_config(cell["conf"]))
    return Served(reference.init_params,
                  lambda params: TransformerLM.from_state(cfg, params),
                  dense_gaps)


def dense_gaps(cell: Dict[str, Any], params, sample) -> List[float]:
    width = reference_width(cell["mix"])
    gaps: List[float] = []
    for prompt, served in sample:
        gaps += reference.serve_gaps(cell["conf"], params, prompt, served,
                                     width).tolist()
    return gaps


def _counters(engine) -> Dict[str, float]:
    """The program's own counts: the serving ledger that /metrics shows
    (the ledger itself: `engine.metrics()` also asks the device for its
    memory, a call that waits behind what runs there, and this is read at
    the window's edges under load), and the decoder's tick ledger from the
    central registry's exposition."""
    from deeplearning4j_tpu.obs import registry as obs_registry

    serving = engine.stats.snapshot()
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


def _ticks(lo: float, hi: float) -> List[Dict[str, Any]]:
    """The decoder's finished ticks that started in `[lo, hi)`, from the
    program's tracer (none where its spans are off)."""
    from deeplearning4j_tpu.obs import trace as obs_trace

    return [s for s in obs_trace.tracer().spans("serve.batch")
            if s["attrs"].get("kind") == "decode.paged"
            and s["duration_s"] is not None and lo <= s["t_mono"] < hi]


def measure(engine, cell: Dict[str, Any], clients, seconds: float,
            trace: bool) -> Dict[str, Any]:
    """The clients' whole life on a warmed engine: start, warm-in, the
    window `[t0, t1)`, the traced tail where asked for, the drain. The
    program's counters and spans are read from `t0` on."""
    from deeplearning4j_tpu.obs import trace as obs_trace

    # spans of the program's own tracer: on in the traced run only, so
    # that the end-to-end run pays nothing for them
    obs_trace.set_enabled(True if trace else None)
    loop = loadgen.ClosedLoop(engine.port, clients)
    t_clients = loop.start()
    loop.pump(t_clients + float(cell["warm_in_seconds"]), send_new=True)
    obs_trace.tracer().clear()
    c0 = _counters(engine)
    t0 = time.perf_counter()      # whatever the two lines above took is out
    loop.pump(t0 + seconds, send_new=True)
    t1 = time.perf_counter()
    c1 = _counters(engine)
    traced = None
    if trace:
        logdir = harness.trace_dir()
        trace_reduce.start(logdir)
        ta = time.perf_counter()
        loop.pump(ta + float(cell.get("trace_seconds", 3)), send_new=True)
        tb = time.perf_counter()
        trace_reduce.stop()
        traced = {"window_s": tb - ta, "logdir": logdir,
                  "ticks": _ticks(ta, tb)}
    loop.pump(time.perf_counter() + DRAIN_S, send_new=False)
    loop.close()
    return {"requests": loop.requests, "t_clients": t_clients, "t0": t0,
            "t1": t1, "counters": {k: c1[k] - c0[k] for k in c0},
            "spans": _ticks(t0, t1), "traced": traced}


def sample_of(greedy: List[Any], n: int, seed: int) -> List[Any]:
    """`n` of the finished greedy requests, drawn from the seed, the
    longest among them."""
    if not greedy:
        return []
    greedy = sorted(greedy,
                    key=lambda r: -(len(r.spec["tokens"]) + len(r.tokens)))
    n = min(n, len(greedy))
    if n <= 1:
        return greedy[:1]
    rng = np.random.default_rng([int(seed), 11])
    return [greedy[0]] + [greedy[i] for i in sorted(
        1 + rng.choice(len(greedy) - 1, n - 1, replace=False))]


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_start: float, chips: int, served: Optional[Served] = None
        ) -> Dict[str, Any]:
    import jax

    from deeplearning4j_tpu.obs import trace as obs_trace
    from deeplearning4j_tpu.serving.engine import ServingEngine

    t_enter = time.perf_counter()
    served = served or dense(cell)
    conf, mix = cell["conf"], cell["mix"]
    vocab = conf["vocab_size"]
    make = jax.jit(lambda k: served.init(conf, k, **cell.get("weights", {})))
    with CompileLog() as compiles:
        key = harness.seed_key(seed)
        lm = served.model_of(jax.block_until_ready(make(key)))
        t_weights = time.perf_counter()
        engine = ServingEngine(model=lm, port=0,
                               **cell.get("engine", {})).start()
        try:
            clients = traffic.chat_requests(mix, vocab, seed,
                                            int(cell.get("per_client", 16)))
            t_engine = time.perf_counter()
            harness.say(f"set-up: engine up at {t_engine - t_start:.1f} s")
            _warm(engine.port, mix, vocab, seed, t_start)
            m = measure(engine, cell, clients, seconds, trace)
            kv = engine.kv_report()
            more = served.after_drain(engine) if served.after_drain else {}
        finally:
            obs_trace.set_enabled(None)
            engine.stop(drain=True)
    peak = harness.memory_peak_bytes(chips)
    t_clients, t0, t1 = m["t_clients"], m["t0"], m["t1"]
    # where set-up went (`start_s`: the process, its imports and the look
    # for the chip, before this job was called; it is part of the next);
    # `programs` says how many executables set-up built, how many of them
    # the persistent cache held, and the seconds they took
    setup = {"start_s": t_enter - t_start,
             "imports_weights_s": t_weights - t_start,
             "engine_s": t_engine - t_weights,
             "warm_s": t_clients - t_engine,
             "programs": compiles.between(0.0, t_clients)}

    # ---- the window, as the clients saw it -------------------------------
    seen = window.read(m["requests"], t0, t1, seconds)
    mine, failed = seen["mine"], seen["failed"]
    built = {"compiles": compiles.between(t0, t1)["programs"],
             "compiles_warm_in": compiles.between(t_clients, t0)["programs"]}
    seen["window"].update(warm_in_s=t0 - t_clients, **built)

    # ---- correct ----------------------------------------------------------
    del engine, lm
    gc.collect()
    unanswered = [r for r in mine if not r.finished and r.error is None]
    malformed = [r for r in mine if r.ok and (
        len(r.tokens) != r.spec["n_new"]
        or any(not (isinstance(t, int) and 0 <= t < vocab)
               for t in r.tokens))]
    picked = sample_of([r for r in mine if r.ok
                        and r.spec["temperature"] == 0.0
                        and r not in malformed],
                       int(cell.get("compare_requests", 8)), seed)
    sample = [(r.spec["tokens"], r.tokens) for r in picked]
    gaps: List[float] = []
    if sample:
        t_ref = time.perf_counter()
        gaps = served.gaps(cell, make(key), sample)
        harness.say(f"reference: {len(sample)} requests, {len(gaps)} tokens "
                    f"in {time.perf_counter() - t_ref:.1f} s")
    limits = cell.get("limits", {})
    checks = compare.serve_checks(gaps, len(malformed), limits)
    compare.exact(checks, "unanswered", len(unanswered))
    # a program built once the clients run is work that set-up left out:
    # in the warm-in it is in no metric, in the window it is in every one
    compare.exact(checks, "compiles_in_window", sum(built.values()))
    for name, value in more.items():
        compare._check(checks, name, value, limits)
    for r in failed[:3]:
        harness.say(f"failed request: {r.error}")
    return {
        "attempted": len(mine), "failed": len(failed),
        "end_to_end": dict(seen["end_to_end"], setup_s=t_clients - t_start),
        "window": seen["window"], "setup": setup, "t0": t0,
        "counters": m["counters"], "spans": m["spans"],
        "traced": m["traced"], "memory_peak_bytes": peak, "checks": checks,
        "kv": kv, "sample": sample,
    }
