"""Pallas TPU fused SGNS (skip-gram negative sampling) step.

W2V_SCATTER_PREANALYSIS.json quantifies the target: the XLA SGNS step is
67% scatter at 10k vocab and 92% at 253k (a CPU profile) — the gather ->
dot/sigmoid -> scatter-add chain is memory-bound on the two [V, D]
embedding tables, which argues for one fused program instead of XLA's
gather + einsum + two scatter dispatches.
This kernel IS that one program, the embedding-plane twin of
nlp/word2vec._neg_body (SkipGram.java:214-252 semantics — see that
docstring for the reference provenance):

  phase 1 (all reads at STALE values, exactly XLA's gather-before-
  scatter): per batch element, DMA the context row of syn0 and the K+1
  target rows of syn1neg HBM->VMEM, compute dot, the MAX_EXP-saturated
  gradient coefficient g, and neu1e = g . s1, parking l1/g/neu1e in VMEM;

  phase 2 (read-modify-write scatter): per batch element, DMA each
  destination row in, add its contribution, DMA it back. The grid-free
  sequential loop makes colliding rows accumulate exactly like
  ``.at[].add()``, and the 1/sqrt(k) collision mean-scale
  (word2vec._mean_scale) is precomputed OUTSIDE the kernel — the
  histogram is a cheap [V] scatter; the [V, D] row traffic is what the
  kernel fuses.

Scope & fallback policy (the kernel-rent convention, CLAUDE.md):
  - engages only behind ``DL4J_TPU_PALLAS_SGNS``: '' auto = pallas
    enabled + VMEM fit (sgns_fits) + a real-chip measured win in
    PALLAS_BENCH.json's ``sgns`` group (no such row exists: not
    measured); 0 = never; force = on wherever the scratch fits
    (compiled);
  - fallback is word2vec._neg_body (the XLA step), selected at trace
    time through the epoch scan's static args;
  - interpret mode is an argument only tests pass, including the f64
    equivalence gradcheck (tests/test_pallas_sgns.py, quick tier).

Written per /opt/skills/guides/pallas_guide.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import env as envknob
from deeplearning4j_tpu.ops.pallas_kernels import pallas_enabled

MAX_EXP = 6.0  # must match nlp/word2vec.MAX_EXP (SkipGram.java saturation)

# VMEM scratch: l1 + neu1e caches [B, D], g cache [B, K+1], one staged
# [K+1, D] target block and a [1, D] RMW row — budget leaves headroom
# for the coefficient inputs and Mosaic padding inside ~16MB/core
_VMEM_BUDGET_FLOATS = 2_000_000


def sgns_fits(batch: int, k1: int, dim: int) -> bool:
    """VMEM gate: the per-batch caches must fit the scratch budget."""
    return (2 * batch * dim + 2 * batch * k1 + (k1 + 1) * dim + batch
            <= _VMEM_BUDGET_FLOATS)


def sgns_kernel_enabled(batch: int, k1: int, dim: int) -> bool:
    """Trace-time gate for the fused SGNS kernel: knob 0 = never, force =
    fit only, '' = pallas + fit + the measured-win
    ``sgns`` group row (real-chip, non-interpret — ops/kernel_gate.py)."""
    knob = envknob.raw("DL4J_TPU_PALLAS_SGNS")
    if knob in ("0", "false", "False"):
        return False
    if not sgns_fits(batch, k1, dim):
        return False
    if knob == "force":
        return True
    from deeplearning4j_tpu.ops.kernel_gate import measured_win

    return pallas_enabled() and measured_win("sgns", "fused_step")


def _sgns_kernel(ctx_ref, tgt_ref, labels_ref, gmul_ref, ts_ref, cs_ref,
                 syn0_in, syn1_in, syn0_out, syn1_out,
                 l1_buf, neu1e_buf, g_buf, s1_blk, row, sem,
                 *, batch: int, k1: int):
    """Two-phase fused step (see module docstring). Scalar-prefetch:
    ctx_ref [B], tgt_ref [B, K+1] (SMEM row indices). VMEM coefficient
    inputs: labels/gmul/ts [B, K+1], cs [B, 1]. syn0/syn1 stay in HBM
    (memory_space ANY, input-output aliased) and move row-by-row through
    explicit DMA — the kernel never materializes a [B, K+1, D] gather."""

    def fetch(src, dst):
        cp = pltpu.make_async_copy(src, dst, sem)
        cp.start()
        cp.wait()

    def phase1(i, _):
        ci = ctx_ref[i]
        fetch(syn0_in.at[pl.ds(ci, 1)], row)
        l1_buf[pl.ds(i, 1), :] = row[...]
        l1 = row[...]                                   # [1, D]

        def gather_tgt(k, _):
            fetch(syn1_in.at[pl.ds(tgt_ref[i, k], 1)],
                  s1_blk.at[pl.ds(k, 1)])
            return 0

        lax.fori_loop(0, k1, gather_tgt, 0)
        s1 = s1_blk[...]                                # [K+1, D]
        dot = lax.dot_general(l1, s1, (((1,), (1,)), ((), ())))  # [1, K+1]
        labels = labels_ref[pl.ds(i, 1), :]
        # saturation semantics (SkipGram.java:234-246), keyed on dot like
        # the XLA twin: dot > MAX_EXP -> labels-1, dot < -MAX_EXP ->
        # labels, else labels - sigmoid(dot)
        base = jnp.where(dot > MAX_EXP, labels - 1.0,
                         jnp.where(dot < -MAX_EXP, labels,
                                   labels - jax.nn.sigmoid(dot)))
        g = base * gmul_ref[pl.ds(i, 1), :]             # [1, K+1]
        g_buf[pl.ds(i, 1), :] = g
        neu1e_buf[pl.ds(i, 1), :] = lax.dot_general(
            g, s1, (((1,), (0,)), ((), ())))            # [1, D]
        return 0

    def phase2(i, _):
        ci = ctx_ref[i]
        fetch(syn0_out.at[pl.ds(ci, 1)], row)
        row[...] = (row[...] + cs_ref[pl.ds(i, 1), :]
                    * neu1e_buf[pl.ds(i, 1), :])
        fetch(row, syn0_out.at[pl.ds(ci, 1)])

        def scatter_tgt(k, _):
            t = tgt_ref[i, k]
            fetch(syn1_out.at[pl.ds(t, 1)], row)
            coef = (g_buf[pl.ds(i, 1), pl.ds(k, 1)]
                    * ts_ref[pl.ds(i, 1), pl.ds(k, 1)])  # [1, 1]
            row[...] = row[...] + coef * l1_buf[pl.ds(i, 1), :]
            fetch(row, syn1_out.at[pl.ds(t, 1)])
            return 0

        lax.fori_loop(0, k1, scatter_tgt, 0)
        return 0

    lax.fori_loop(0, batch, phase1, 0)
    lax.fori_loop(0, batch, phase2, 0)


def sgns_fused_step(syn0, syn1neg, contexts, targets, labels, live, alpha,
                    *, interpret: bool = False):
    """Drop-in fused twin of word2vec._neg_body: syn0/syn1neg [V, D]
    (donated through input-output aliasing), contexts [B] i32, targets
    [B, K+1] i32, labels/live [B, K+1], alpha scalar -> (syn0', syn1neg').

    Math identical to the XLA step up to fp association order in the
    colliding-row accumulation (tests pin f64 agreement at 1e-9)."""
    from deeplearning4j_tpu.nlp.word2vec import _mean_scale

    b, k1 = targets.shape
    v, d = syn0.shape
    dt = syn0.dtype
    live = live.astype(dt)
    t_scale = _mean_scale(syn1neg.shape[0], targets, live)
    ctx_live = (live.sum(axis=1) > 0).astype(dt)
    ctx_scale = _mean_scale(v, contexts, ctx_live)
    gmul = (alpha * live).astype(dt)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((b, k1), lambda i, ctx, tgt: (0, 0)),
            pl.BlockSpec((b, k1), lambda i, ctx, tgt: (0, 0)),
            pl.BlockSpec((b, k1), lambda i, ctx, tgt: (0, 0)),
            pl.BlockSpec((b, 1), lambda i, ctx, tgt: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, d), dt),          # l1 cache
            pltpu.VMEM((b, d), dt),          # neu1e cache
            pltpu.VMEM((b, k1), dt),         # g cache
            pltpu.VMEM((k1, d), dt),         # staged target rows
            pltpu.VMEM((1, d), dt),          # DMA / RMW row
            pltpu.SemaphoreType.DMA,
        ],
    )
    # input indices for aliasing count the scalar-prefetch operands:
    # (ctx, tgt, labels, gmul, ts, cs, syn0, syn1) -> syn0 is 6, syn1 is 7
    out = pl.pallas_call(
        functools.partial(_sgns_kernel, batch=b, k1=k1),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((v, d), dt),
                   jax.ShapeDtypeStruct((syn1neg.shape[0], d), dt)],
        input_output_aliases={6: 0, 7: 1},
        interpret=interpret,
    )(contexts.astype(jnp.int32), targets.astype(jnp.int32),
      labels.astype(dt), gmul, t_scale.astype(dt),
      ctx_scale.astype(dt)[:, None], syn0, syn1neg)
    return out[0], out[1]
