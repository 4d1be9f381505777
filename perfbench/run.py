#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: validates BENCHMARK.json, refuses a machine without the chips
the cell asks for (or with a device that perfbench/peaks.json does not know),
builds the system under test on weights made from the seed, warms the cell's
own shapes (set-up), measures for --seconds, checks what the timed path
produced against the plain reference, prints each number compared beside its
limit on stderr, prints ONE JSON object as the last line of stdout, and
exits 0. With --trace 0 the metrics are the cell's end-to-end metrics, with
--trace 1 its per-layer metrics (readers under perfbench/metrics/, found by
the metric's name) from the same window plus a short traced tail.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # process start, as near as Python can say

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
from typing import Any, Dict, Optional   # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import check_manifest, harness   # noqa: E402


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             manifest: Dict[str, Any], base: str = harness.HERE,
             device: Optional[Dict[str, Any]] = None,
             t_start: Optional[float] = None) -> Dict[str, Any]:
    """Everything of a run but the look for a chip: the job, the metrics,
    the result object. `device` is what check_device found (the tests, which
    have no chip, pass None and get the device as JAX describes it)."""
    import jax

    cell = harness.load_cell(name, base)
    chips = int(cell["chips"])
    if device is None:
        device = harness.describe_device(chips)
    job = importlib.import_module("perfbench.job_" + cell["job"])
    out = job.run(cell, seed, seconds, trace,
                  T_START if t_start is None else t_start, chips)

    e2e = harness.cell_metrics(manifest, name, "end_to_end")
    reported = [m["name"] for m in e2e]
    metrics: Dict[str, Dict[str, Any]] = {}
    result: Dict[str, Any] = {
        "correct": False, "attempted": out["attempted"],
        "failed": out["failed"], "metrics": metrics,
        "device": dict(device, memory_peak_bytes=out["memory_peak_bytes"]),
    }
    if not trace:
        for m in e2e:
            if m["name"] in out["end_to_end"]:
                metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    else:
        from perfbench import flops, trace_reduce

        traced = out["traced"]
        tr = trace_reduce.Trace(traced["logdir"], chips)
        shutil.rmtree(traced["logdir"], ignore_errors=True)
        ctx = dict(out, cell=cell, conf=cell["conf"], mix=cell["mix"],
                   trace=tr, flops=flops,
                   peaks=(harness.peaks_for(device["kind"])
                          if device["platform"] != "cpu" else None))
        for m in harness.cell_metrics(manifest, name, "per_layer", reported):
            value = harness.load_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        result["device"]["busy_s"] = tr.busy_s()
        result["device"]["window_s"] = traced["window_s"]
        result["breakdown"] = tr.breakdown()
        result["end_to_end_of_this_run"] = out["end_to_end"]
    from perfbench import compare

    result["correct"] = compare.all_ok(out["checks"])
    result["jax"] = jax.__version__
    # what the job read of its window and of its set-up, as it read it: a
    # serve job's every candidate for a tail, where set-up went
    for key in ("window", "setup"):
        if key in out:
            result[key] = out[key]
    result["checks"] = out["checks"]       # comes last in the line
    harness.print_checks(out["checks"])
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = harness.load_manifest()
    bad = check_manifest.check(manifest)
    if bad:
        for b in bad:
            harness.say("check_manifest: " + b)
        return 2
    cell = harness.load_cell(args.workload)
    harness.set_compile_cache()
    device = harness.check_device(int(cell["chips"]))
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), manifest=manifest, device=device)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
