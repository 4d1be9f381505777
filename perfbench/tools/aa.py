#!/usr/bin/env python3
"""The benchmark judged against itself: runs of ONE tree on one machine,
labelled A and B in turn as a check alternates parent and change, then read
as a check reads them. What it answers: under which bound would a PR that
changes nothing be told `unchanged`, for each metric and each candidate for a
tail (perfbench/window.py prints them all, so one set of runs serves).

    chiprun -- python3 perfbench/tools/aa.py runs --workload <cell> \\
        --seeds 11,12,13,14,15,16 --out chiprun_out/aa_<call>.jsonl
    python3 perfbench/tools/aa.py table chiprun_out/aa_*.jsonl

`runs` starts `perfbench/run.py` once to fill the compile cache (label
`cold`, in no set), then once a label and seed: A s1, B s1, B s2, A s2, ...,
the side that goes first alternating, each run a new process as in a check.
It never touches JAX itself: a parent that did would hold the chip. `table`
has no JAX in it either and reads any number of such files, one a call.

Two measures of a set's spread, side by side. The driver's, as
PERF_LEDGER.jsonl words it: the range of the runs after leaving out the one
farthest from their median, where that narrows it. The contract's: the
distance of the quartiles (`statistics.quantiles(values, n=4)`). Both as a
share of the median.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import subprocess
import sys
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# the arithmetic
# ---------------------------------------------------------------------------


def driver_spread(values: Sequence[float]) -> float:
    """Range of the runs, the one farthest from their median left out where
    that narrows it, in the metric's own unit."""
    v = sorted(values)
    if len(v) < 3:
        return v[-1] - v[0]
    med = statistics.median(v)
    far = max(v, key=lambda x: abs(x - med))
    rest = list(v)
    rest.remove(far)
    return min(v[-1] - v[0], rest[-1] - rest[0])


def quartile_spread(values: Sequence[float]) -> float:
    """Distance of the first and third quartile, in the metric's own unit."""
    if len(values) < 2:
        return 0.0
    q = statistics.quantiles(values, n=4)
    return q[2] - q[0]


def round_up(x: float, step: float) -> float:
    return math.ceil(round(x / step, 9)) * step


def lower_is_better(name: str) -> bool:
    """Every time is (the tails, set-up and its parts); a rate is not."""
    return not name.endswith("_per_s")


def verdict(name: str, parent: Sequence[float], change: Sequence[float],
            bound: float, by_median_alone: bool = False) -> str:
    """`regressed`, `unresolved` or `unchanged`, as a check tells a metric
    that a PR does not claim: worse by more than the bound at the medians;
    else not to be told where either side's runs spread wider than the
    bound, unless every run of one side reads better than every run of the
    other; else unchanged. `by_median_alone` is `setup_s`'s rule."""
    lower = lower_is_better(name)
    mp, mc = statistics.median(parent), statistics.median(change)
    room = bound * mp
    worse = (mc - mp) if lower else (mp - mc)
    if worse > room:
        return "regressed"
    if by_median_alone:
        return "unchanged"
    apart = (max(change) < min(parent) or max(parent) < min(change))
    if not apart and max(driver_spread(parent), driver_spread(change)) > room:
        return "unresolved"
    return "unchanged"


# ---------------------------------------------------------------------------
# reading result lines
# ---------------------------------------------------------------------------


def numbers(result: Dict[str, Any]) -> Dict[str, float]:
    """Every number of one run that a set's spread is taken of: the metrics
    of the line, every candidate of `window` and the parts of `setup`."""
    out = {k: float(m["value"]) for k, m in result.get("metrics", {}).items()}
    for k, v in (result.get("window") or {}).items():
        if isinstance(v, (int, float)) and (k.endswith("_ms")
                                            or k == "requests"):
            out.setdefault(k, float(v))
    for k, v in (result.get("setup") or {}).items():
        if isinstance(v, (int, float)):
            out["setup." + k] = float(v)
    return out


def load(paths: Iterable[str]) -> List[Dict[str, Any]]:
    rows = []
    for path in paths:
        call = os.path.splitext(os.path.basename(path))[0]
        with open(path) as f:
            for line in f:
                if line.strip():
                    rows.append(dict(json.loads(line), call=call))
    return rows


def sets_of(rows: List[Dict[str, Any]]
            ) -> Dict[Tuple[str, str, str], List[Dict[str, float]]]:
    """(cell, call, label) -> the numbers of each of its end-to-end runs,
    in the order they ran."""
    out: Dict[Tuple[str, str, str], List[Dict[str, float]]] = {}
    for r in rows:
        if r["label"] in ("A", "B") and not r["trace"] and r.get("result"):
            out.setdefault((r["workload"], r["call"], r["label"]),
                           []).append(numbers(r["result"]))
    return out


def table(paths: Sequence[str], bounds: Dict[str, float],
          cells_of: Optional[Dict[str, List[str]]] = None) -> str:
    """`bounds`: the metrics B is judged against A under; `cells_of` names,
    for a metric that not every cell reports, the cells that do."""
    rows = load(paths)
    sets = sets_of(rows)
    lines = []
    bad = [r for r in rows if not (r.get("result") or {}).get("correct")]
    lines.append(f"{len(rows)} runs in {len(set(r['call'] for r in rows))} "
                 f"calls, {len(bad)} not correct"
                 + "".join(f"\n  NOT CORRECT: {r['workload']} seed "
                           f"{r['seed']} ({r['call']}, {r['label']})"
                           for r in bad))
    cells = sorted({k[0] for k in sets})
    names: List[str] = []
    for runs in sets.values():
        for n in runs[0]:
            if n not in names:
                names.append(n)

    # -- every set's spread, and the widest a name has anywhere ------------
    lines += ["", "| Number | Cell | set: median, driver's spread, "
              "quartiles' spread (shares of the median) |", "| --- | --- | "
              "--- |"]
    widest: Dict[str, Dict[str, float]] = {}
    for n in names:
        for cell in cells:
            parts = []
            for (c, call, label), runs in sorted(sets.items()):
                v = [r[n] for r in runs if n in r]
                if c != cell or len(v) < 2:
                    continue
                med = statistics.median(v)
                d, q = driver_spread(v) / med, quartile_spread(v) / med
                w = widest.setdefault(n, {"driver": 0.0, "quartile": 0.0})
                w["driver"] = max(w["driver"], d)
                w["quartile"] = max(w["quartile"], q)
                parts.append(f"{call}/{label} {med:.6g}, {d:.4f}, {q:.4f}")
            if parts:
                lines.append(f"| `{n}` | {cell} | " + "; ".join(parts) + " |")
    lines += ["", "| Number | widest driver's spread | x 1.5, up to 0.005 | "
              "widest quartiles' spread | x 5 |", "| --- | --- | --- | --- | "
              "--- |"]
    for n, w in widest.items():
        lines.append(f"| `{n}` | {w['driver']:.4f} | "
                     f"{round_up(1.5 * w['driver'], 0.005):.3f} | "
                     f"{w['quartile']:.4f} | {5 * w['quartile']:.4f} |")

    # -- A against B, call by call ------------------------------------------
    if bounds:
        lines += ["", "| Cell | Call | Metric | Bound | A median (spread) | "
                  "B median (spread) | B against A |", "| --- | --- | --- | "
                  "--- | --- | --- | --- |"]
        for cell in cells:
            for call in sorted({k[1] for k in sets if k[0] == cell}):
                a = sets.get((cell, call, "A"), [])
                b = sets.get((cell, call, "B"), [])
                for n, bound in bounds.items():
                    va = [r[n] for r in a if n in r]
                    vb = [r[n] for r in b if n in r]
                    if len(va) < 2 or len(vb) < 2 \
                            or cell not in (cells_of or {}).get(n, [cell]):
                        continue
                    told = verdict(n, va, vb, bound, n == "setup_s")
                    lines.append(
                        f"| {cell} | {call} | `{n}` | {bound:g} | "
                        f"{statistics.median(va):.6g} "
                        f"({driver_spread(va):.4g}) | "
                        f"{statistics.median(vb):.6g} "
                        f"({driver_spread(vb):.4g}) | {told} |")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# making the runs
# ---------------------------------------------------------------------------


def one_run(workload: str, seed: int, seconds: float, trace: int,
            label: str, out: str, timeout: float) -> Dict[str, Any]:
    cmd = [sys.executable, os.path.join("perfbench", "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", f"{seconds:g}", "--trace", str(trace)]
    t = time.perf_counter()
    try:
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           timeout=timeout)
        rc, stdout, stderr = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc, stdout, stderr = 124, "", str(e)
    row: Dict[str, Any] = {"label": label, "workload": workload,
                           "seed": seed, "seconds": seconds, "trace": trace,
                           "rc": rc, "wall_s": time.perf_counter() - t,
                           "result": None}
    last = stdout.strip().splitlines()[-1:] or [""]
    try:
        row["result"] = json.loads(last[0])
    except ValueError:
        row["stderr_end"] = stderr[-2000:]
    with open(out, "a") as f:
        f.write(json.dumps(row) + "\n")
    res = row["result"] or {}
    shown = {k: round(m["value"], 3)
             for k, m in res.get("metrics", {}).items()}
    print(f"{label} {workload} seed {seed} trace {trace}: rc {rc}, "
          f"{row['wall_s']:.0f} s, correct {res.get('correct')}, {shown}",
          flush=True)
    if not res.get("correct"):
        print(stderr[-1500:], flush=True)
    return row


def runs(args) -> int:
    seeds = [int(s) for s in args.seeds.split(",") if s]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    bad = 0
    plan: List[Tuple[str, int, int]] = []
    if args.cold:
        plan.append(("cold", args.cold, 0))
    for i, seed in enumerate(seeds):
        first, second = ("A", "B") if i % 2 == 0 else ("B", "A")
        plan += [(first, seed, 0), (second, seed, 0)]
    plan += [("traced", int(s), 1) for s in args.traced.split(",") if s]
    for label, seed, trace in plan:
        row = one_run(args.workload, seed, args.seconds, trace, label,
                      args.out, args.timeout)
        bad += not (row["result"] or {}).get("correct")
    return 1 if bad else 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="what", required=True)
    r = sub.add_parser("runs")
    r.add_argument("--workload", required=True)
    r.add_argument("--seeds", default="", help="a seed a pair, A and B")
    r.add_argument("--cold", type=int, default=0,
                   help="seed of a first run that fills the compile cache")
    r.add_argument("--traced", default="", help="seeds of --trace 1 runs")
    r.add_argument("--seconds", type=float, default=40.0)
    r.add_argument("--timeout", type=float, default=1200.0)
    r.add_argument("--out", required=True)
    t = sub.add_parser("table")
    t.add_argument("files", nargs="+")
    t.add_argument("--bounds", default="",
                   help="metric=bound,... to judge B against A under "
                   "(BENCHMARK.json's where none is given)")
    args = ap.parse_args(argv)
    if args.what == "runs":
        return runs(args)
    bounds = {k: float(v) for k, v in
              (kv.split("=") for kv in args.bounds.split(",") if kv)}
    cells_of = None
    if not bounds:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            listed = json.load(f)["end_to_end"]
        bounds = {m["name"]: m["bound"] for m in listed}
        cells_of = {m["name"]: m["workloads"] for m in listed
                    if "workloads" in m}
    print(table(args.files, bounds, cells_of))
    return 0


if __name__ == "__main__":
    sys.exit(main())
