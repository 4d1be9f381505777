#!/usr/bin/env python3
"""Compile a cell's train step for a DESCRIBED v5e chip (no chip attached).

    JAX_PLATFORMS=cpu python3 perfbench/tools/aot_train_step.py \
        --config cerebras-gpt-590m --batch 8 --seq 2048

Prints, for each (remat, accum_steps), what the TPU compiler's
`memory_analysis` says one optimizer step needs, and whether the lowered
step holds the Mosaic flash kernel (`tpu_custom_call`). The cell's file
records the setting chosen from this table: the first that fits the
device's bytes_limit with the least recomputation. Nothing runs, so this
gives no time and no rate.

The program's platform-keyed policies (pallas gate, donation) see the CPU
here, so this script steers them through the program's existing env knobs
for the length of the compile only; the benchmark's runs set neither.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["DL4J_TPU_PALLAS"] = "force"   # a chip's default: kernel on
os.environ["DL4J_TPU_DONATE"] = "1"       # a chip's default: donate opt

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

BYTES_LIMIT = 16909336064   # memory_stats()["bytes_limit"] of the v5e (PR 21's chip run)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--remat", default="none,dots,block")
    ap.add_argument("--accum", default="1,2,4,8")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    from perfbench.harness import program_config  # noqa: E402

    from deeplearning4j_tpu.models import transformer as tr

    with open(os.path.join(ROOT, "perfbench", "configs",
                           args.config + ".json")) as f:
        conf = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    for remat in args.remat.split(","):
        for accum in (int(a) for a in args.accum.split(",")):
            cfg = tr.TransformerConfig(
                **program_config(conf, max_len=args.seq, remat=remat,
                                 accum_steps=accum))
            shapes = jax.eval_shape(lambda: tr.init_params(cfg))
            sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
            p = jax.tree_util.tree_map(sds, shapes)
            opt = {"m": p, "v": p,
                   "t": jax.ShapeDtypeStruct((), jnp.int32, sharding=one)}
            x = jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32,
                                     sharding=one)
            step = tr.make_train_step(cfg)
            t0 = time.time()
            row = {"config": args.config, "remat": remat, "accum_steps": accum}
            try:
                lowered = step.lower(p, opt, x, x)
                row["tpu_custom_call"] = "tpu_custom_call" in lowered.as_text()
                ma = lowered.compile().memory_analysis()
                total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                         + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
                row.update(argument=ma.argument_size_in_bytes,
                           output=ma.output_size_in_bytes,
                           temp=ma.temp_size_in_bytes,
                           alias=ma.alias_size_in_bytes, total=total,
                           fits=total <= BYTES_LIMIT)
            except Exception as e:  # the compiler's refusal is the answer
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            row["compile_s"] = round(time.time() - t0, 1)
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
