#!/usr/bin/env python3
"""Read, on the chip and at the cell's own size, the numbers a `serve_moe`
cell's `limits` are set from (PERF.md, "How correct is decided"): over many
seeds in one process what sound runs of the program give (the lower
reading), and on the first `--controls` seeds what the control gives on the
same prompts and tokens (the upper reading): the reference with every linear
layer's operands in float8_e4m3. It also counts routing flips: at how many
of the compared positions of the first compared request the program's
arithmetic (bfloat16 into every product) would choose another set of experts
than float32 does, in the first layer, where both read the same input.

    python3 perfbench/tools/calibrate_moe.py --workload <name> \
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


def values(checks):
    return {k: c["value"] for k, c in checks.items()}


def router_margins(cell, params, request):
    """Over one request's tokens, in layer 0: how close the last chosen
    router logit and the runner-up lie (a routing flip needs them within the
    rounding of what feeds the router), as quantiles, and the logits' own
    spread."""
    import jax.numpy as jnp
    import numpy as np

    conf = cell["conf"]
    k = conf["moe_num_active_primary_experts"]
    prompt, served = request
    seq = np.concatenate([prompt, served])[:-1]
    h = params["embed"][jnp.asarray(seq)].astype(jnp.float32)
    g = params["attn"]["norm1"][0].astype(jnp.float32)
    u = h * (1.0 / jnp.sqrt(jnp.mean(h * h, -1, keepdims=True)
                            + conf["rms_norm_eps"])) * g
    r = np.sort(np.asarray(jnp.matmul(
        u, params["moe"]["router"][0].astype(jnp.float32),
        precision="highest")), axis=-1)[:, ::-1]
    margin = r[:, k - 1] - r[:, k]
    return {"tokens": int(seq.size), "logits_std": float(r.std()),
            "margin_p01": float(np.quantile(margin, 0.01)),
            "margin_p10": float(np.quantile(margin, 0.10)),
            "margin_p50": float(np.quantile(margin, 0.50)),
            "share_under_0.01": float((margin < 0.01).mean())}


def serve_seed(cell, seed, control: bool, seconds: float):
    import jax

    from perfbench import job_serve_moe as job

    t0 = time.perf_counter()
    out = job.run(cell, seed, seconds, False, time.perf_counter(),
                  int(cell["chips"]))
    row = {"seed": seed, "program": values(out["checks"]),
           "compared_tokens": out["checks"]["logit_gap"]["tokens"],
           "compared_requests": len(out["sample"]),
           "requests": out["attempted"], "failed": out["failed"],
           "end_to_end": out["end_to_end"], "kv": out["kv"],
           "counters": out["counters"],
           "memory_peak_bytes": out["memory_peak_bytes"],
           "run_seconds": round(time.perf_counter() - t0, 1)}
    if control and out["sample"]:
        _model_of, reference = job.build(cell)
        params = jax.jit(lambda k: reference.init_params(
            cell["conf"], k, **cell.get("weights", {})))(
                harness.seed_key(seed))
        t1 = time.perf_counter()
        gaps = job.reference_gaps(cell, params, out["sample"],
                                  reference=reference, lowp="fp8")
        row["control_fp8"] = values(compare.serve_checks(gaps, 0, {}))
        row["control_seconds"] = round(time.perf_counter() - t1, 1)
        row["router"] = router_margins(cell, params, out["sample"][0])
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
