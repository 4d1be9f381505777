#!/usr/bin/env python3
"""Run a cell's job with its traced tail and print what the trace holds:
planes and lines, the device operations by self time, the host's events by
total time. For looking at a trace by hand before (or after) a reader is
written against its names. Prints no result line.

    python3 perfbench/tools/trace_names.py --workload <name> [--seconds 3]
"""
from __future__ import annotations

import argparse
import importlib
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness, trace_reduce   # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--top", type=int, default=60)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    harness.set_compile_cache()
    harness.check_device(int(cell["chips"]))
    job = importlib.import_module("perfbench.job_" + cell["job"])
    out = job.run(cell, args.seed, args.seconds, True, time.perf_counter(),
                  int(cell["chips"]))
    tr = trace_reduce.Trace(out["traced"]["logdir"], int(cell["chips"]))
    print("file", tr.path, os.path.getsize(tr.path), "bytes")
    for ln in tr.lines_seen:
        print("line", ln)
    print("busy_s", tr.busy_s(), "window_s", out["traced"]["window_s"])
    ops = tr.op_seconds()
    counts = {}
    for _, _, n in tr.device_ops.get(0, []):
        n = trace_reduce.short_name(n)
        counts[n] = counts.get(n, 0) + 1
    for name, sec in trace_reduce.top(ops, args.top):
        print(f"op {sec:10.6f} s x{counts.get(name, 0):5d}  {name}")
    host = {}
    for s, e, n in tr.host:
        host[n] = host.get(n, 0.0) + (e - s)
    for name, sec in trace_reduce.top(host, 40):
        print(f"host {sec:10.6f} s  {name}")
    for k in ("window", "counters", "kv", "memory_peak_bytes", "attempted",
              "failed"):
        if k in out:
            v = dict(out[k]) if isinstance(out[k], dict) else out[k]
            if isinstance(v, dict):
                v.pop("step_seconds", None)
            print(k, v)
    if out.get("spans"):
        d = sorted(s["duration_s"] for s in out["spans"])
        print("tick spans", len(d), "p50", d[len(d) // 2], "max", d[-1])
    print("checks", out["checks"])
    print("end_to_end", out["end_to_end"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
