"""The `train` job: TransformerLM.fit on fresh batches for the window.

Set-up builds ONE object, the compiled step with its state, drives it from
the seed through its first three optimizer steps (the steps the reference
follows), and hands that same object to the measured window. After the
window the program's state is freed and the reference follows the same three
steps from the same seed; `correct` compares the two.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict

import numpy as np

from perfbench import compare, harness, reference, trace_reduce, traffic

REF_STEPS = 3
TRACED_STEPS = 3


def build(cell: Dict[str, Any], seed: int):
    """The program's object for this cell, on weights made from the seed in
    one jitted call."""
    import jax

    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    conf, mix = cell["conf"], cell["mix"]
    cfg = TransformerConfig(**harness.program_config(
        conf, max_len=mix["seq"], **cell.get("program", {})))
    key = harness.seed_key(seed)
    params = jax.jit(lambda k: reference.init_params(conf, k))(key)
    return TransformerLM.from_state(cfg, params), key


def first_steps(lm, cell: Dict[str, Any], seed: int, key) -> Dict[str, Any]:
    """Drive the object through the steps the reference follows, through the
    window's own call and feed, and read what `correct` compares: each loss,
    the first gradient's norms as the optimizer got it (Adam's first moment
    after one step is (1 - beta1) times it), and the norms of the parameters'
    change after the last."""
    import jax
    import jax.numpy as jnp

    conf, mix = cell["conf"], cell["mix"]
    b1 = conf["optimizer"]["beta1"]
    norms = jax.jit(reference.leaf_norms)
    change = jax.jit(lambda p, k: reference.delta_norms(p, conf, k))
    out: Dict[str, Any] = {"losses": []}
    for k in range(REF_STEPS):
        x, y = traffic.train_batch(mix, conf["vocab_size"], seed, k)
        loss = lm.fit(jnp.asarray(x), jnp.asarray(y))
        out["losses"].append(float(loss))
        if k == 0:
            out["grad_norms"] = {n: float(v) / (1.0 - b1)
                                 for n, v in norms(lm.opt["m"]).items()}
    out["change_norms"] = {n: float(v)
                           for n, v in change(lm.params, key).items()}
    return out


def _steps(lm, cell, seed, first: int, until) -> Dict[str, Any]:
    """Optimizer steps from batch number `first` on, one in flight while the
    host makes the next batch, until `until(steps_done, now)` says stop. The
    last step is fenced. Returns the count, the start and end times and the
    time at which each step's loss was ready."""
    import jax.numpy as jnp

    conf, mix = cell["conf"], cell["mix"]
    ready, pending, k = [], None, first
    t0 = time.perf_counter()
    while True:
        x, y = traffic.train_batch(mix, conf["vocab_size"], seed, k)
        loss = lm.fit(jnp.asarray(x), jnp.asarray(y))
        k += 1
        if pending is not None:
            pending.block_until_ready()
            ready.append(time.perf_counter())
        pending = loss
        if until(k - first, time.perf_counter() - t0):
            break
    pending.block_until_ready()
    ready.append(time.perf_counter())
    return {"steps": k - first, "t0": t0, "t1": ready[-1], "ready": ready,
            "next": k, "last_loss": float(pending)}


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_start: float, chips: int) -> Dict[str, Any]:
    conf, mix = cell["conf"], cell["mix"]
    harness.say(f"set-up: imports done at {time.perf_counter() - t_start:.1f} s")
    lm, key = build(cell, seed)
    import jax
    jax.block_until_ready(lm.params)
    harness.say(f"set-up: weights and object at "
                f"{time.perf_counter() - t_start:.1f} s")
    prog = first_steps(lm, cell, seed, key)
    setup_s = time.perf_counter() - t_start
    harness.say(f"set-up: first {REF_STEPS} steps read at {setup_s:.1f} s")

    win = _steps(lm, cell, seed, REF_STEPS, lambda n, dt: dt >= seconds)
    tokens_per_step = mix["batch"] * mix["seq"]
    elapsed = win["t1"] - win["t0"]
    gaps = np.diff([win["t0"]] + win["ready"])
    # the first interval holds the pipeline's fill: two dispatches
    step_seconds = [float(g) for g in gaps[1:]] or [float(elapsed)]
    window = {"tokens": win["steps"] * tokens_per_step, "seconds": elapsed,
              "steps": win["steps"], "step_seconds": step_seconds,
              "tokens_per_step": tokens_per_step}

    traced = None
    if trace:
        logdir = harness.trace_dir()
        trace_reduce.start(logdir)
        t0 = time.perf_counter()
        _steps(lm, cell, seed, win["next"], lambda n, dt: n >= TRACED_STEPS)
        traced = {"window_s": time.perf_counter() - t0,
                  "steps": TRACED_STEPS, "logdir": logdir}
        trace_reduce.stop()

    peak = harness.memory_peak_bytes(chips)
    finite = bool(np.isfinite(win["last_loss"]))
    del lm
    gc.collect()

    batches = [traffic.train_batch(mix, conf["vocab_size"], seed, k)
               for k in range(REF_STEPS)]
    t0 = time.perf_counter()
    ref = reference.train_reference(conf, key, batches)
    harness.say(f"reference: {REF_STEPS} steps in "
                f"{time.perf_counter() - t0:.1f} s")
    checks = compare.train_checks(prog, ref, cell.get("limits", {}))
    checks["last_loss_finite"] = {"value": float(not finite), "limit": 0.0,
                                  "ok": finite}
    return {
        "attempted": win["steps"], "failed": 0 if finite else win["steps"],
        "end_to_end": {
            "train_tokens_per_s": window["tokens"] / window["seconds"],
            "setup_s": setup_s,
        },
        "window": window, "traced": traced, "memory_peak_bytes": peak,
        "checks": checks,
    }
