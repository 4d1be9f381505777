#!/usr/bin/env python3
"""Read, on the chip and at the cell's own size, the numbers a cell's
`limits` are set from (see "How correct is decided" in PERF.md): over many
seeds in one process what sound runs of the program give (the lower
reading), and on the first `--controls` seeds what the control gives (the
reference in fp8 put in the program's place) and, for a training cell, the
planted fault "half of the batch left out" (the upper readings).

    python3 perfbench/tools/calibrate.py --workload <name> \
        --seeds 101,102,... --controls 3 [--seconds 8] [--out file.jsonl]

One JSON line a seed. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import compare, harness, reference, traffic   # noqa: E402


def values(checks):
    return {k: c["value"] for k, c in checks.items()}


def train_seed(cell, seed, control: bool):
    from perfbench import job_train

    conf, mix = cell["conf"], cell["mix"]
    t0 = time.perf_counter()
    lm, key = job_train.build(cell, seed)
    prog = job_train.first_steps(lm, cell, seed, key)
    del lm
    gc.collect()
    batches = [traffic.train_batch(mix, conf["vocab_size"], seed, k)
               for k in range(job_train.REF_STEPS)]
    ref = reference.train_reference(conf, key, batches)
    checks = compare.train_checks(prog, ref, {})
    row = {"seed": seed, "program": values(checks),
           "losses": {"program": prog["losses"], "reference": ref["losses"]},
           "worst_leaf": {k: c["leaf"] for k, c in checks.items()
                          if "leaf" in c}}
    if control:
        ctrl = reference.train_reference(conf, key, batches, lowp="fp8")
        row["control_fp8"] = values(compare.train_checks(ctrl, ref, {}))
        half = reference.train_reference(conf, key, batches,
                                         rows=mix["batch"] // 2)
        row["fault_half_batch"] = values(compare.train_checks(half, ref, {}))
    row["seconds"] = round(time.perf_counter() - t0, 1)
    return row


def serve_seed(cell, seed, control: bool, seconds: float):
    import jax

    from perfbench import job_serve

    conf, mix = cell["conf"], cell["mix"]
    t0 = time.perf_counter()
    out = job_serve.run(cell, seed, seconds, False, time.perf_counter(),
                        int(cell["chips"]))
    row = {"seed": seed, "program": values(out["checks"]),
           "compared_tokens": out["checks"]["logit_gap"]["tokens"],
           "requests": out["attempted"], "failed": out["failed"],
           "end_to_end": out["end_to_end"], "kv": out["kv"],
           "memory_peak_bytes": out["memory_peak_bytes"]}
    if control:
        width = job_serve.reference_width(mix)
        params = jax.jit(lambda k: reference.init_params(
            conf, k, **cell.get("weights", {})))(harness.seed_key(seed))
        gaps = []
        for p, t in out["sample"]:
            gaps += reference.serve_gaps(conf, params, p, t, width,
                                         lowp="fp8").tolist()
        del params
        row["control_fp8"] = values(compare.serve_checks(gaps, 0, {}))
    row["seconds"] = round(time.perf_counter() - t0, 1)
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
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
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        if cell["job"] == "train":
            row = train_seed(cell, seed, i < args.controls)
        else:
            row = serve_seed(cell, seed, i < args.controls, args.seconds)
        line = json.dumps(row)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
