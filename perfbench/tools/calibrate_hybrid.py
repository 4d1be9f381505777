#!/usr/bin/env python3
"""Read, on the chip and at the cell's own size, the numbers a `serve_hybrid`
cell's `limits` are set from (PERF.md, "How correct is decided"): over many
seeds in one process what sound runs of the program give (the lower
reading), and on the first `--controls` seeds what the two controls give on
the same prompts and tokens (the upper readings): the reference with every
linear layer's operands in float8_e4m3, and the reference with the recurrent
state rounded to bfloat16 after every step. Served tokens tell of the first
and not of the second; `state_bits_lost` reads the state itself, the
program's from its pool and a control's from the reference's scan.

    python3 perfbench/tools/calibrate_hybrid.py --workload <name> \
        --seeds 101,102,... --controls 2 [--seconds 8] [--out file.jsonl]

One JSON line a seed. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import compare, harness   # noqa: E402

CONTROLS = ("fp8", "state_bf16")


def values(checks):
    return {k: c["value"] for k, c in checks.items()}


def logits_moved(cell, reference, params, request, lowp):
    """How far a control moves the reference's logits over one request's
    answer (a control that flips no token reads 0 in every gap, live or
    not: this says which)."""
    import numpy as np

    from perfbench.job_serve import reference_width

    prompt, served = request
    buf = np.zeros((reference_width(cell["mix"]),), np.int32)
    seq = np.concatenate([prompt, served])[:-1]
    buf[:seq.size] = seq
    rows = cell["mix"]["output_tokens"]["max"]
    at = lambda how: np.asarray(reference.logits_one(
        params, buf, cell["conf"], how, len(prompt) - 1, rows))[:len(served)]
    base, got = at(None), at(lowp)
    return {"max": float(np.abs(got - base).max()),
            "mean": float(np.abs(got - base).mean()),
            "logits_std": float(base.std()),
            "flips": int((got.argmax(-1) != base.argmax(-1)).sum()),
            "tokens": len(served)}


def state_read(cell, reference, params, request, lowp):
    """What a control leaves in the first recurrent layer's state after one
    request's prompt and the tokens fed back: the mantissa bits it leaves
    unused (`state_bits_lost`, as the job reads the program's pool) and how
    far it lies from the float32 scan's, as a share of that state's norm."""
    import numpy as np

    from perfbench import job_serve_hybrid as job

    prompt, served = request
    fed = np.concatenate([prompt, served])[:-1]
    base = reference.state_one(params, fed, cell["conf"])
    got = reference.state_one(params, fed, cell["conf"], lowp)
    return {"state_bits_lost": job.mantissa_bits_lost([got]),
            "state_moved": float(np.linalg.norm(np.asarray(got - base))
                                 / np.linalg.norm(np.asarray(base)))}


def serve_seed(cell, seed, control: bool, seconds: float):
    import jax

    from perfbench import job_serve_hybrid as job

    t0 = time.perf_counter()
    out = job.run(cell, seed, seconds, False, time.perf_counter(),
                  int(cell["chips"]))
    served = [t for _p, toks in out["sample"] for t in toks]
    prompts_last = [int(p[-1]) for p, _t in out["sample"]]
    row = {"seed": seed, "program": values(out["checks"]),
           "compared_tokens": out["checks"]["logit_gap"]["tokens"],
           "requests": out["attempted"], "failed": out["failed"],
           "end_to_end": out["end_to_end"], "kv": out["kv"],
           "memory_peak_bytes": out["memory_peak_bytes"],
           # how often an answer's first token repeats the prompt's last:
           # near 1 would mean the tied head decides, not the arithmetic
           "first_token_repeats_share": sum(
               int(toks[0]) == last for (_p, toks), last
               in zip(out["sample"], prompts_last)) / max(1, len(prompts_last)),
           "distinct_served_share": len(set(served)) / max(1, len(served))}
    if control:
        _model_of, reference, _leaf = job.build(cell)
        params = jax.jit(lambda k: reference.init_params(
            cell["conf"], k, **cell.get("weights", {})))(
                harness.seed_key(seed))
        for lowp in CONTROLS:
            gaps = job.reference_gaps(cell, params,
                                      out["sample"], reference=reference,
                                      lowp=lowp)
            row["control_" + lowp] = values(
                compare.serve_checks(gaps, 0, {}))
            row["control_" + lowp]["logits_moved"] = logits_moved(
                cell, reference, params, out["sample"][0], lowp)
            row["control_" + lowp].update(state_read(
                cell, reference, params, out["sample"][0], lowp))
        del params
    row["seconds"] = round(time.perf_counter() - t0, 1)
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
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
    try:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            row = serve_seed(cell, seed, i < args.controls, args.seconds)
            line = json.dumps(row)
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
