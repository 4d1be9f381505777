#!/usr/bin/env python
"""Prove-or-drop benchmark: fused Pallas LSTM scan vs XLA lax.scan on the
chip. Needs a TPU: the kernel is compiled, never interpreted, and a machine
without a chip is an error.

Methodology: each (N, T, H) case times 60 jitted calls per implementation,
fenced with jax.block_until_ready, and asserts on-chip numerical
equivalence between kernel and scan before recording. The per-shape rows —
written to PALLAS_BENCH.json — drive whether the kernel engages for a shape
class (ops/pallas_kernels.lstm_kernel_wins; DL4J_TPU_PALLAS=0 disables).
This is the selectable-backend slot mirroring the reference's reflective
cuDNN helper loading (ConvolutionLayer.java:64-70).

One process per chip: run it directly (`python benchmarks/pallas_lstm_bench.py`)
or through `python bench.py --only=lstm_kernel`, which calls run() in its
own process.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops import pallas_kernels as pk


def _bench(fn, args, steps=60):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def run() -> dict:
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"pallas_lstm_bench measures the chip and jax found backend "
            f"{backend!r}")
    results = {"backend": backend,
               "device_kind": jax.devices()[0].device_kind, "cases": []}
    rng = np.random.default_rng(0)
    for n, t, h in ((32, 128, 128), (64, 256, 256), (128, 512, 512)):
        xproj = jnp.asarray(rng.standard_normal((n, t, 4 * h)), jnp.float32)
        u = jnp.asarray(rng.standard_normal((h, 4 * h)) * 0.05, jnp.float32)
        p = jnp.zeros((3, h), jnp.float32)
        h0 = jnp.zeros((n, h), jnp.float32)
        c0 = jnp.zeros((n, h), jnp.float32)

        scan_fn = jax.jit(pk._lstm_scan_reference)
        scan_ms = _bench(scan_fn, (xproj, u, p, h0, c0)) * 1e3
        scan_out = scan_fn(xproj, u, p, h0, c0)

        pallas_fn = jax.jit(lambda *a: pk.lstm_pallas_scan(*a, False))
        try:
            pallas_ms = _bench(pallas_fn, (xproj, u, p, h0, c0)) * 1e3
        except Exception as e:  # noqa: BLE001
            pallas_ms = None
            results["cases"].append(
                {"n": n, "t": t, "h": h, "scan_ms": round(scan_ms, 3),
                 "pallas_error": f"{type(e).__name__}: {e}"}
            )
            continue
        # on-chip numerical equivalence: the kernel must match the scan
        pal_out = pallas_fn(xproj, u, p, h0, c0)
        max_dev = max(
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(pal_out, scan_out)
        )
        if max_dev >= 1e-4:
            results["cases"].append(
                {"n": n, "t": t, "h": h, "scan_ms": round(scan_ms, 3),
                 "pallas_error": f"DIVERGENCE vs scan: max_abs_dev={max_dev}"}
            )
            continue
        case = {
            "n": n, "t": t, "h": h,
            "scan_ms": round(scan_ms, 3),
            "pallas_ms": round(pallas_ms, 3),
            "pallas_interpret_mode": False,
            "scan_speedup_over_pallas": round(pallas_ms / scan_ms, 2),
            "max_abs_dev_vs_scan": max_dev,
        }

        # fwd+bwd (the training step shape): reverse-time pallas backward
        # kernel vs scan autodiff

        def grad_of(fn):
            return jax.jit(jax.grad(
                lambda xp, uu: jnp.sum(fn(xp, uu, p, h0, c0)[0] ** 2),
                argnums=(0, 1)))

        scan_g = grad_of(lambda *a: pk._lstm_scan_reference(*a))
        pallas_g = grad_of(lambda *a: pk.lstm_pallas_scan(*a, False))
        try:
            scan_bwd_ms = _bench(scan_g, (xproj, u), steps=30) * 1e3
            pallas_bwd_ms = _bench(pallas_g, (xproj, u), steps=30) * 1e3
            g_dev = max(
                float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(pallas_g(xproj, u), scan_g(xproj, u))
            )
            case.update({
                "scan_fwdbwd_ms": round(scan_bwd_ms, 3),
                "pallas_fwdbwd_ms": round(pallas_bwd_ms, 3),
                "bwd_kernel_engaged": pk.lstm_bwd_fits(n, h, t),
                "scan_speedup_over_pallas_fwdbwd":
                    round(pallas_bwd_ms / scan_bwd_ms, 2),
                "max_grad_dev_vs_scan": g_dev,
            })
        except Exception as e:  # noqa: BLE001
            case["bwd_error"] = f"{type(e).__name__}: {e}"
        results["cases"].append(case)
    ratios = [c["scan_speedup_over_pallas"] for c in results["cases"]
              if "pallas_ms" in c]
    results["verdict"] = (
        "scan/pallas time ratios per shape (<1 = kernel faster): "
        + ", ".join(f"{r:.2f}" for r in ratios))
    # Merge into PALLAS_BENCH.json (never clobber other kernel groups) the
    # per-shape win-table rows ops/pallas_kernels.lstm_kernel_wins consults.
    from deeplearning4j_tpu.ops.kernel_gate import record_win

    for c in results["cases"]:
        if "pallas_ms" not in c:
            continue
        row = {
            "n": c["n"], "t": c["t"], "h": c["h"],
            "speedup": round(c["scan_ms"] / c["pallas_ms"], 2),
            "scan_ms": c["scan_ms"], "pallas_ms": c["pallas_ms"],
            "backend": results["backend"],
            "interpret": c["pallas_interpret_mode"],
        }
        if "pallas_fwdbwd_ms" in c:
            row["fwdbwd_speedup"] = round(
                c["scan_fwdbwd_ms"] / c["pallas_fwdbwd_ms"], 2)
            row["scan_fwdbwd_ms"] = c["scan_fwdbwd_ms"]
            row["pallas_fwdbwd_ms"] = c["pallas_fwdbwd_ms"]
            row["bwd_kernel_engaged"] = c.get("bwd_kernel_engaged")
        record_win("lstm", f"n{c['n']}_t{c['t']}_h{c['h']}", row)
    return results


if __name__ == "__main__":
    print(json.dumps(run()))
