#!/usr/bin/env python3
"""Read, on the chip and at the cell's own size, the numbers a `serve_sambay`
cell's `limits` are set from (PERF.md, "How correct is decided"): over many
seeds in one process what sound runs of the program give (the lower
reading), and on the first `--controls` seeds what the two controls give on
the same prompts and tokens (the upper readings): the reference with every
linear layer's operands in float8_e4m3, and the reference with the Mamba-1
state rounded to bfloat16 after every step. Served tokens tell of the first
and hardly of the second; `state_bits_lost` reads the state itself, the
program's from its pool and a control's from the reference's scan over the
first compared request.

    python3 perfbench/tools/calibrate_sambay.py --workload <name> \
        --seeds 101,102,... --controls 2 [--seconds 40] [--out file.jsonl]

One JSON line a seed. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import compare, harness   # noqa: E402

CONTROLS = ("fp8", "state_bf16")


def values(checks):
    return {k: c["value"] for k, c in checks.items()}


def serve_seed(cell, seed: int, control: bool, seconds: float):
    import jax
    import numpy as np

    from perfbench import job_serve_sambay as job
    from perfbench.job_serve_hybrid import mantissa_bits_lost

    t0 = time.perf_counter()
    out = job.run(cell, seed, seconds, False, time.perf_counter(),
                  int(cell["chips"]))
    row = {"seed": seed, "program": values(out["checks"]),
           "compared_tokens": out["checks"]["logit_gap"]["tokens"],
           "requests": out["attempted"], "failed": out["failed"],
           "end_to_end": out["end_to_end"],
           "memory_peak_bytes": out["memory_peak_bytes"]}
    if control and out["sample"]:
        _model_of, reference, _leaf = job.build(cell)
        params = jax.jit(lambda k: reference.init_params(
            cell["conf"], k, **cell.get("weights", {})))(
                harness.seed_key(seed))
        for lowp in CONTROLS:
            t1 = time.perf_counter()
            gaps = job.reference_gaps(cell, params, out["sample"],
                                      reference=reference, lowp=lowp)
            got = values(compare.serve_checks(gaps, 0, {}))
            prompt, served = out["sample"][0]
            fed = np.concatenate([prompt, served])[:-1]
            got["state_bits_lost"] = mantissa_bits_lost(
                [reference.state_one(params, fed, cell["conf"], lowp)])
            got["seconds"] = round(time.perf_counter() - t1, 1)
            row["control_" + lowp] = got
        del params
    row["seconds"] = round(time.perf_counter() - t0, 1)
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--no-device-check", action="store_true",
                    help="for a CPU rehearsal of this script at a toy size")
    ap.add_argument("--base", default=harness.HERE)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload, args.base)
    harness.set_compile_cache()
    if not args.no_device_check:
        harness.check_device(int(cell["chips"]))
    sink = open(args.out, "a") if args.out else None
    try:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            line = json.dumps(serve_seed(cell, seed, i < args.controls,
                                         args.seconds))
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
