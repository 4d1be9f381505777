"""The serving window as the clients saw it: one pure function over the load
generator's requests and the window's two edges. No JAX here, and nothing of
the program.

A request belongs to the window when it was SENT inside `[t0, t1)`: only
those are in a tail of the time to first token, in `attempted` and among the
requests `correct` samples. A token belongs to the window when it ARRIVED
inside it, whenever its request was sent: the requests in flight as the
window opens (the clients start `warm_in_seconds` earlier, see job_serve)
still decode in it, so their tokens count for the rate, the gaps and the
operations, and their first tokens are in no tail.

Every candidate for the tail is printed from the one list, so that one set of
runs gives the spread of each (perfbench/tools/aa.py).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

DRAIN_S = 60.0          # how long past the close an answer is waited for
TTFT_PERCENTILES = (50, 90, 95, 99)
GAP_PERCENTILES = (50, 95, 99)


def percentile(values: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def slowest_tenth_mean(values: Sequence[float]) -> float:
    """Mean of the slowest tenth (one value at the least): the tail as a
    mean, which no edge between two humps of a distribution moves."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[len(v) - max(1, len(v) // 10):].mean())


def tails(ttft: Sequence[float], gap_ms: Sequence[float]
          ) -> Dict[str, Optional[float]]:
    """Every candidate for the two tails, by the name a metric would have;
    None where there is nothing to read."""
    def of(f, values, *args):
        return f(values, *args) if len(values) else None

    out = {f"ttft_p{q}_ms": of(percentile, ttft, q) for q in TTFT_PERCENTILES}
    out["ttft_slow10_mean_ms"] = of(slowest_tenth_mean, ttft)
    out.update({f"gap_p{q}_ms": of(percentile, gap_ms, q)
                for q in GAP_PERCENTILES})
    return out


def read(requests: List[Any], t0: float, t1: float, seconds: float
         ) -> Dict[str, Any]:
    """`requests`: every `loadgen.Request` of the run, those of the warm-in
    and of the traced tail among them. `seconds` is the window as asked for
    (a failed request's time to first token reads `seconds + DRAIN_S`).
    Returns `mine` (the requests sent in the window), `failed` (those of
    them that did not end well), `window` (counts for the readers, and
    every candidate) and `end_to_end` (the rate and every candidate under
    the name a metric would have: BENCHMARK.json says which are metrics)."""
    mine = [r for r in requests if t0 <= r.t_send < t1]
    failed = [r for r in mine if not r.ok]
    worst_ms = 1e3 * (seconds + DRAIN_S)
    ttft = [1e3 * (r.token_times[0] - r.t_send)
            if r.ok and r.token_times else worst_ms for r in mine]
    arrivals = prefill_tokens = 0
    gap_ms: List[float] = []
    stamps: List[float] = [t0, t1]
    pairs = 0.0
    for r in requests:
        n_p = len(r.spec["tokens"])
        times = r.token_times
        if times and t0 <= times[0] < t1:
            prefill_tokens += n_p
            pairs += n_p * (n_p + 1) / 2
        for i, t in enumerate(times):
            if t0 <= t < t1:
                arrivals += 1
                stamps.append(t)
                pairs += n_p + i
                if i > 0:
                    gap_ms.append(1e3 * (t - times[i - 1]))
    found = tails(ttft, gap_ms)
    stamps = np.sort(stamps)
    quiet = np.diff(stamps)
    order = np.argsort(-quiet)[:3]
    window: Dict[str, Any] = {
        "seconds": t1 - t0, "requests": len(mine),
        "prefill_tokens": prefill_tokens, "decode_tokens": arrivals,
        "attended_pairs": pairs, "gaps": len(gap_ms), **found,
        # the longest stretch of the window in which no token of any request
        # arrived: some ticks long in a sound run, seconds where the server
        # (or this process) stood still, which a slow run is then put down to
        "silence_max_ms": 1e3 * float(quiet[order[0]]),
        # the three longest, each [seconds into the window, ms]
        "silences": [[float(stamps[i] - t0), 1e3 * float(quiet[i])]
                     for i in order]}
    # a metric is never missing from a result: with nothing to read it is
    # the worst value, a failed request's
    end_to_end = {k: worst_ms if v is None else v for k, v in found.items()}
    end_to_end["serve_tokens_per_s"] = arrivals / (t1 - t0)
    return {"mine": mine, "failed": failed, "window": window,
            "end_to_end": end_to_end}
