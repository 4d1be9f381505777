"""Dispatch-efficiency layer: buffer donation, shape bucketing, persistent
compile cache, and retrace/dispatch telemetry.

The reference's training entry points accept arbitrary batch shapes
(``MultiLayerNetwork.fit(DataSet)`` — MultiLayerNetwork.java:1017) and pay a
per-op JVM dispatch cost; under jax the cost model shifts but does not
vanish: a NEW batch shape is a full XLA retrace of the whole-step program,
and a jit without donated buffers copies params + optimizer state through
HBM on every step. What a dispatch costs on the attached chip is not
measured yet. This module concentrates the counter-measures
the containers (nn/multilayer.py, nn/graph.py), the Solver
(optimize/solvers.py), the parallel trainers (parallel/data_parallel.py)
and the flagship factories (models/transformer.py) all share:

  1. donation policy   — ``donation_enabled()`` / ``instrumented_jit(...,
     donate=...)``: donate ``params/states/upd_state`` into the step so the
     update is in-place on device. Default ON on accelerators, OFF on CPU
     (the test/equivalence substrate routinely re-reads params trees — the
     same rationale as models/transformer._donation_kwargs); the env knob
     ``DL4J_TPU_DONATE`` overrides both ways ("force" turns it on even on
     CPU, which this jax implements for real — tests use it to verify the
     call sites never re-read a donated buffer).
  2. shape bucketing   — ``bucket_size()`` pads ragged batches up to a
     small power-of-two-ish set so ``fit``/``fit_iterator``/``output``
     compile once per BUCKET instead of once per shape; the pad rows are
     masked out of the loss through the existing mask plumbing
     (nn/losses._masked_mean_per_example), which makes padding
     semantically free. Knob: ``DL4J_TPU_BUCKET_BATCHES`` (default on).
  3. compile cache     — ``enable_compile_cache()`` wires jax's persistent
     XLA compilation cache so a second process warm-starts instead of
     recompiling. The directory is ``JAX_COMPILATION_CACHE_DIR`` when
     that (jax's own variable) is set, ``<checkout>/.jax_cache``
     otherwise; the flagship, the serving engine and every
     ``instrumented_jit`` call it.
  4. telemetry         — ``DispatchStats``: per-network counters of traces
     (XLA compiles), dispatches (calls; calls - traces = compiled-cache
     hits), donated-vs-copied steps and padded batches, surfaced through
     the listener chain (optimize/listeners.DispatchStatsListener) and the
     ``dispatch_overhead`` bench leg.
"""

from __future__ import annotations

import os
import time
from collections import defaultdict
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.obs import trace as obs_trace
from deeplearning4j_tpu.ops import device
from deeplearning4j_tpu.ops import env as envknob

ENV_DONATE = "DL4J_TPU_DONATE"
ENV_BUCKET = "DL4J_TPU_BUCKET_BATCHES"
ENV_FUSE = "DL4J_TPU_FUSE"

_OFF = ("0", "off", "false", "no")
_ON = ("1", "on", "true", "yes", "force")



# ---------------------------------------------------------------------------
# donation policy
# ---------------------------------------------------------------------------


def donation_enabled() -> bool:
    """Should train-step jits donate their params/states/upd_state buffers?

    Read at jit-CONSTRUCTION time (the containers cache jits, so flipping
    the env after a net has compiled does not retro-actively change it).

    Default: donate on accelerators, skip on CPU — CPU runs are the
    test/equivalence substrate where callers routinely hold one initial
    params tree across several step functions (the serial-vs-distributed
    pattern), which donation would poison. The decision asks the backend
    (ops/device.platform), so it is ON wherever a chip is attached.
    """
    v = envknob.raw(ENV_DONATE, "").strip().lower()
    if v in _OFF:
        return False
    if v in _ON:
        return True
    return device.platform() != "cpu"


def arena_jit(fn, donate: Sequence[int] = ()):
    """jit for SINGLE-OWNER accumulator buffers — donated by default
    even on CPU.

    donation_enabled() defaults off on CPU because equivalence tests
    hold one params tree across several step functions; that caveat does
    not apply to a buffer with exactly one owner who always rebinds the
    result and never re-reads the input — the paged-KV serving arena
    (serving/paged.py), where an un-donated tick would copy the whole
    arena per generated token. An explicit ``DL4J_TPU_DONATE=0`` still
    wins (the knob's 'never' contract covers every donating jit)."""
    v = envknob.raw(ENV_DONATE, "").strip().lower()
    if v in _OFF or not donate:
        return jax.jit(fn)
    return jax.jit(fn, donate_argnums=tuple(donate))


# ---------------------------------------------------------------------------
# fusion policy (fit_batches' scan-of-steps)
# ---------------------------------------------------------------------------


def fusion_enabled(scanned_conv: bool = False) -> bool:
    """Should fit_batches fuse K steps into one lax.scan program?

    Fusion is the dispatch-amortization win everywhere EXCEPT scanned
    conv programs on XLA:CPU, which that backend compiles far slower than
    the per-step program. The
    containers pass ``scanned_conv=True`` when the net has conv/
    subsampling layers; on the CPU substrate that falls back to per-step
    fits (recorded in ``DispatchStats.fused_fallbacks``). The env knob
    ``DL4J_TPU_FUSE`` overrides: ``force`` (or any _ON value) always
    fuses — the equivalence tests and the lenet5_cpu leg pin the fused
    program with it — and ``0`` never does. Asks the backend
    (ops/device.platform), like the donation policy."""
    v = envknob.raw(ENV_FUSE, "").strip().lower()
    if v in _ON:  # "force" and its _ON siblings ("1"/"on"/...) all pin fusion
        return True
    if v in _OFF:
        return False
    if not scanned_conv:
        return True
    return device.platform() != "cpu"


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------


class DispatchStats:
    """Per-network dispatch-efficiency counters.

    The reference has nothing like this because its failure mode (per-op
    dispatch) is uniform; under jax the pathologies are *episodic* (a
    ragged batch triggering a silent 30s retrace) and need a counter to be
    visible at all.

      traces[name]   python-level traces of the named jit == XLA compiles
                     (a retrace on a new shape increments it again)
      calls[name]    dispatches of the named jit; calls - traces is the
                     compiled-program cache-hit count
      donated_steps / copied_steps
                     steps executed with / without buffer donation
      padded_batches / padded_examples
                     shape-bucketing activity (fit calls that padded, and
                     the total pad rows fed)
      trace_seconds[name]
                     wall-seconds spent in calls that TRACED (trace +
                     XLA compile + the first dispatch per shape) — the
                     compile-time ledger: which programs are worth
                     warming before traffic arrives
      fused_fallbacks
                     fit_batches calls that fell back to per-step fits
                     under the fusion policy (fusion_enabled: the
                     XLA:CPU scan-of-conv pessimization guard)
      loss_scale_skips
                     bf16 loss-scaled training (DL4J_TPU_BF16 /
                     ops/lowprec.py): optimizer steps SKIPPED on
                     non-finite grads (the halve-and-skip half of
                     dynamic loss scaling). Refreshed at explicit sync
                     points (training_state() / net.loss_scale), never
                     per step — reading it per step would be a hidden
                     device sync.
      decode_ticks / decode_tokens
                     continuous-decode dispatch amortization (ISSUE 16:
                     serving/decode.py + serving/paged.py multi-token
                     ticks): jitted decode dispatches and the tokens
                     they produced, summed over every lane. The derived
                     ``tokens_per_dispatch`` in snapshot() is the number
                     the per-dispatch overhead divides by — 1.0 is the
                     single-token baseline, k*lanes the scanned ceiling.
    """

    def __init__(self) -> None:
        self.traces: Dict[str, int] = defaultdict(int)
        self.calls: Dict[str, int] = defaultdict(int)
        self.trace_seconds: Dict[str, float] = defaultdict(float)
        self.donated_steps = 0
        self.copied_steps = 0
        self.padded_batches = 0
        self.padded_examples = 0
        self.fused_fallbacks = 0
        self.loss_scale_skips = 0
        self.decode_ticks = 0
        self.decode_tokens = 0

    def cache_hits(self, name: Optional[str] = None) -> int:
        if name is not None:
            return self.calls.get(name, 0) - self.traces.get(name, 0)
        return sum(self.calls.values()) - sum(self.traces.values())

    def snapshot(self) -> Dict[str, Any]:
        return {
            "traces": dict(self.traces),
            "calls": dict(self.calls),
            "cache_hits": {n: self.cache_hits(n) for n in self.calls},
            "trace_seconds": {n: round(s, 3)
                              for n, s in self.trace_seconds.items()},
            "donated_steps": self.donated_steps,
            "copied_steps": self.copied_steps,
            "padded_batches": self.padded_batches,
            "padded_examples": self.padded_examples,
            "fused_fallbacks": self.fused_fallbacks,
            "loss_scale_skips": self.loss_scale_skips,
            "decode_ticks": self.decode_ticks,
            "decode_tokens": self.decode_tokens,
            "tokens_per_dispatch": (
                round(self.decode_tokens / self.decode_ticks, 4)
                if self.decode_ticks else None),
        }


def instrumented_jit(fn, name: str, stats: DispatchStats, *,
                     donate: Sequence[int] = (),
                     static_argnums=None, step: bool = False,
                     mem_stats=None):
    """``jax.jit`` with retrace/dispatch telemetry and policy-gated donation.

    ``donate``: argnums to donate WHEN the donation policy is on; the
    caller guarantees those arguments are re-bound from the return value
    and never re-read (the containers' ``self.params, ... = step(...)``
    discipline). Call sites that DO re-read an argument — the Solver's
    line-search oracle re-probes the same flat param vector — must pass
    ``donate=()``.

    ``step=True`` marks a training step for the donated/copied counters.

    ``mem_stats``: an ops/memory.MemoryStats to receive AOT byte
    accounting; the wrapper's ``.measure_memory(*args)`` lowers +
    compiles WITHOUT executing and records the analysis under ``name``
    (the memory plane beside this dispatch plane — never paid implicitly
    on the hot path).

    The returned wrapper exposes ``.lower`` (bench cost-analysis uses it)
    and ``.donated_argnums`` (tests assert the policy). Calls that trace
    also accrue wall-seconds into ``stats.trace_seconds[name]`` (trace +
    compile + first dispatch — the compile-time triage ledger).
    """
    enable_compile_cache()
    donated: Tuple[int, ...] = tuple(donate) if (
        donate and donation_enabled()) else ()
    kw: Dict[str, Any] = {}
    if donated:
        kw["donate_argnums"] = donated
    if static_argnums is not None:
        kw["static_argnums"] = static_argnums

    counting = [True]  # AOT .lower() re-traces for analysis, not dispatch
    span_name = f"dispatch.{name}"  # hoisted off the per-call hot path

    def traced(*args, **kwargs):
        if counting[0]:
            stats.traces[name] += 1
        return fn(*args, **kwargs)

    jfn = jax.jit(traced, **kw)

    def wrapper(*args, **kwargs):
        stats.calls[name] += 1
        if step:
            if donated:
                stats.donated_steps += 1
            else:
                stats.copied_steps += 1
        before = stats.traces[name]
        t0 = time.perf_counter()
        # obs span (DL4J_TPU_OBS, default off -> shared null context):
        # HOST-side dispatch timing only — the jit returns async, so the
        # span never adds a device sync (the listener-chain bulk-readback
        # rule). Attrs distinguish trace vs compiled-cache-hit dispatch.
        with obs_trace.span(span_name, donated=bool(donated),
                            step=step) as sp:
            out = jfn(*args, **kwargs)
            if stats.traces[name] > before:
                # this call traced: its wall time is dominated by
                # trace+XLA compile (dispatch itself returns async) — the
                # per-trace compile-cost ledger the DispatchStatsListener
                # and the dispatch_overhead leg surface
                stats.trace_seconds[name] += time.perf_counter() - t0
                sp.set_attr("traced", True)
        return out

    def lower(*args, **kwargs):
        # cost-analysis lowering (bench legs) must not skew the
        # traces-vs-calls cache-hit arithmetic: it traces without
        # dispatching, which would read as a phantom retrace
        counting[0] = False
        try:
            return jfn.lower(*args, **kwargs)
        finally:
            counting[0] = True

    def measure_memory(*args, **kwargs):
        from deeplearning4j_tpu.ops import memory as memory_mod

        analysis = memory_mod.analyze_lowered(lower(*args, **kwargs))
        if mem_stats is not None and analysis is not None:
            mem_stats.record(name, analysis)
        return analysis

    wrapper.lower = lower
    wrapper.measure_memory = measure_memory
    wrapper.donated_argnums = donated
    wrapper._jitted = jfn
    wrapper.__name__ = f"jit_{name}"
    return wrapper


# ---------------------------------------------------------------------------
# shape bucketing
# ---------------------------------------------------------------------------


def bucketing_mode() -> str:
    """Bucketing policy, read at CALL time (per fit) so tests can toggle.

      "off"    — never pad (DL4J_TPU_BUCKET_BATCHES=0)
      "always" — every fit() buckets (DL4J_TPU_BUCKET_BATCHES=1)
      "auto"   — the default: bucket inside fit_iterator (the hot loop
                 where ragged tails and shape drift actually occur) and in
                 inference (output), but leave DIRECT fit(features, labels)
                 calls byte-exact — the repo's equivalence contracts
                 (fit_batches == K serial fits, distributed == serial)
                 compare direct-fit trajectories at tight tolerance, and
                 padding legitimately reassociates float32 reductions and
                 reshapes dropout draws.
    """
    v = envknob.raw(ENV_BUCKET, "").strip().lower()
    if v in _OFF:
        return "off"
    if v in _ON:
        return "always"
    return "auto"


def bucket_size(n: int) -> int:
    """Smallest power-of-two-ish size >= n.

    The bucket set is {1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, ...}
    — powers of two and 1.5x powers of two — so padding waste stays under
    50% (worst case sits just above a power of two) and a stream of
    arbitrary batch sizes compiles O(log n) programs instead of one per
    distinct size (the reference's fit(DataSet) accepts any shape because
    a JVM op re-dispatch is cheap; an XLA retrace is not)."""
    if n <= 2:
        return max(n, 1)
    p = 1
    while p < n:
        p <<= 1
    mid = (p >> 1) + (p >> 2)  # 1.5 * (p/2), sits between p/2 and p
    return mid if (p >= 4 and n <= mid) else p


def pad_axis0(a, target: int):
    """Zero-pad axis 0 up to ``target`` rows (no-op when already there)."""
    a = jnp.asarray(a)
    if a.shape[0] == target:
        return a
    pad = [(0, target - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad)


def inference_bucket(stats: DispatchStats, n: int) -> Optional[int]:
    """Inference-side bucketing decision shared by both containers'
    output(): the padded size to use (recording the activity in
    ``stats``), or None when no padding applies. Inference padding is
    unconditionally safe — BN uses running stats and dropout is off — so
    the only gates are the mode knob and n already being a bucket."""
    if bucketing_mode() == "off":
        return None
    target = bucket_size(n)
    if target == n:
        return None
    stats.padded_batches += 1
    stats.padded_examples += target - n
    return target


def pad_rows(stats: DispatchStats, target: int, arrays):
    """Pad each array (None entries pass through) along axis 0 to
    ``target`` and record the bucketing activity ONCE in ``stats`` — the
    single home of the pad-and-count discipline both containers' fit hooks
    share. Call only when padding is actually needed (target > batch)."""
    n = next(a for a in arrays if a is not None).shape[0]
    stats.padded_batches += 1
    stats.padded_examples += target - n
    return [None if a is None else pad_axis0(a, target) for a in arrays]


# memoized host-side masks: the mask is a pure function of
# (n_real, n_padded, time_steps), and building it eagerly with jnp ops
# would cost per-fit device dispatches on the exact hot path this module
# exists to thin out. A numpy array rides the jit call's normal argument
# transfer instead.
_ROW_MASKS: Dict[Tuple[int, int, Optional[int]], "np.ndarray"] = {}


def row_validity_mask(n_real: int, n_padded: int,
                      time_steps: Optional[int] = None):
    """1.0 for real rows, 0.0 for pad rows — fed as the label mask so the
    masked-mean loss (nn/losses._masked_mean_per_example) divides by the
    REAL example count. For an unpadded batch this is all-ones, and
    sum(loss * 1) / sum(ones) is bit-identical to the plain mean — which is
    why the containers attach it even when no padding happened: every
    bucket then shares ONE jit signature instead of splitting into
    padded/unpadded variants of the same shape."""
    key = (n_real, n_padded, time_steps)
    m = _ROW_MASKS.get(key)
    if m is None:
        m = (np.arange(n_padded) < n_real).astype(np.float32)
        if time_steps is not None:
            m = np.broadcast_to(m[:, None], (n_padded, time_steps))
        _ROW_MASKS[key] = m
    return m


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------

#: <checkout>/.jax_cache, from this file's own location — the path is
#: part of the cache key, so it must not move with the working directory
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set (jax's own variable: the
    one way to place the cache from outside), else
    ``<checkout>/.jax_cache``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
            or _DEFAULT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Wire jax's persistent XLA compilation cache at
    :func:`compile_cache_dir` (idempotent; the only place the program
    sets ``jax_compilation_cache_dir``). Returns the directory."""
    d = compile_cache_dir()
    if jax.config.jax_compilation_cache_dir != d:
        jax.config.update("jax_compilation_cache_dir", d)
    return d
