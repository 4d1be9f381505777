"""Pallas TPU paged-decode attention kernel.

The paged serving tick's XLA path (serving/paged.py chunked_attention)
gathers every lane's KV blocks a chunk at a time, up to the longest live
lane, into a copy it reads back once — S * chunk * H * hd elements of HBM
traffic a pass, for dead lanes and short ones too, even though a lane
typically occupies a handful of blocks. This kernel is the vLLM
PagedAttention move (Kwon et
al., 2023) fused with the flash-attention online softmax (Dao et al.,
2022; same recipe as ops/pallas_attention.py): the grid walks each lane's
BLOCK TABLE via scalar prefetch, Mosaic streams exactly the referenced
arena blocks HBM->VMEM (the table entry IS the block index map), and a
running (max, denominator, accumulator) triple in VMEM scratch folds each
block into the softmax without ever materializing the gathered window.

Mask contract (byte-for-byte the gather path's): a token at global
position t = j * block_tokens + offset is visible iff ``t <= pos[lane]``
— the same ``arange <= pos`` predicate that keeps the trash block
(physical block 0, where inactive lanes and unallocated table entries
point) invisible: trash content can enter a score only at masked
positions, where the online softmax assigns it exp(-inf) = 0 weight
exactly.

Scope & fallback policy (the kernel-rent convention, CLAUDE.md):
  - engages only behind ``DL4J_TPU_PALLAS_PAGED``: '' auto = pallas
    enabled + VMEM/shape fit (paged_fits) + a real-chip measured win in
    PALLAS_BENCH.json's ``paged`` group (ops/kernel_gate.py); 0 = never;
    force = on wherever the VMEM budget fits (compiled: off a TPU that
    fails, and the CPU equivalence tests substitute the interpreted
    kernel themselves);
  - fallback is serving/paged.py's existing gather path, selected at
    trace time (the tick cache keys on the resolved path);
  - interpret mode is an argument only tests pass
    (tests/test_pallas_paged.py, quick tier).

Written per /opt/skills/guides/pallas_guide.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import env as envknob
from deeplearning4j_tpu.ops.pallas_kernels import pallas_enabled

# one k + one v arena block resident per grid step (double-buffered by
# Mosaic), plus q/o lane blocks and the running-stat scratch — keep well
# under the ~16MB/core VMEM like the other kernels' budgets
_VMEM_BUDGET_FLOATS = 1_000_000


def paged_fits(block_tokens: int, n_heads: int, head_dim: int) -> bool:
    """VMEM/alignment gate: the streamed (bt, H, hd) k/v blocks must fit
    the budget and the trailing (H, hd) dims must be Mosaic-tileable
    ((8, 128) lanes) — serving shapes like H=16, hd=128 qualify; the tiny
    CPU-test shapes run in interpret mode where alignment is free."""
    return (2 * block_tokens * n_heads * head_dim <= _VMEM_BUDGET_FLOATS
            and head_dim % 128 == 0 and n_heads % 8 == 0)


def paged_kernel_enabled(n_heads: int, head_dim: int,
                         block_tokens: int) -> bool:
    """Trace-time gate for the paged-decode attention kernel. force
    bypasses the measured-win table AND the alignment half of the fit
    check (Mosaic then says what it will not tile), never the VMEM
    budget."""
    knob = envknob.raw("DL4J_TPU_PALLAS_PAGED")
    if knob in ("0", "false", "False"):
        return False
    if knob == "force":
        return (2 * block_tokens * n_heads * head_dim
                <= _VMEM_BUDGET_FLOATS)
    from deeplearning4j_tpu.ops.kernel_gate import measured_win

    return (pallas_enabled()
            and paged_fits(block_tokens, n_heads, head_dim)
            and measured_win("paged", "decode_attention"))


def _paged_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, block_tokens: int,
                  scale: float):
    """Grid (lane s, table slot j): fold arena block ``tables[s, j]`` into
    lane s's online softmax. q_ref/o_ref: [1, H, hd]; k_ref/v_ref:
    [1, bt, H, hd] (the block the index map fetched); m/l scratch:
    [H, 128] f32 (running max / denominator broadcast across lanes for
    Mosaic alignment); acc scratch: [H, hd] f32."""
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0].astype(jnp.float32) * scale          # [H, hd]
    k = k_ref[0].astype(jnp.float32)                  # [bt, H, hd]
    v = v_ref[0].astype(jnp.float32)

    # scores[h, t] = q[h] . k[t, h]; multiply-reduce keeps the layout
    # VPU-friendly (no per-head dot_general on a [bt, H, hd] operand)
    sc = jnp.sum(q[None, :, :] * k, axis=-1).T        # [H, bt]
    t_glob = j * block_tokens + lax.broadcasted_iota(
        jnp.int32, (1, block_tokens), 1)              # [1, bt]
    sc = jnp.where(t_glob <= pos_ref[s], sc, -jnp.inf)

    m_prev = m_scr[...]                               # [H, 128]
    blk_max = jnp.max(sc, axis=-1, keepdims=True)     # [H, 1]
    m_new = jnp.maximum(m_prev, blk_max)
    # a block past the lane's write position is fully masked: keep the
    # exp argument finite (exp(-inf - -inf) would be nan)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(sc - m_safe[:, :1])
    p = jnp.where(jnp.isfinite(sc), p, 0.0)           # [H, bt]
    corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    m_scr[...] = m_new
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    # acc[h] += sum_t p[h, t] * v[t, h]: broadcast-multiply-reduce again
    acc_scr[...] = (acc_scr[...] * corr[:, :1]
                    + jnp.sum(p.T[:, :, None] * v, axis=0))

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        l_safe = jnp.maximum(l_scr[...][:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def paged_attention(q, ck, cv, tables, pos, *, interpret: bool = False):
    """Block-table decode attention: q [S, H, hd] (any float dtype),
    ck/cv [n_blocks+1, bt, H, hd] arena (block 0 = trash), tables [S, m]
    int32, pos [S] int32 -> att [S, H, hd] float32.

    Numerically the gather path's f32 masked softmax-attention with the
    gather replaced by table-indexed block streaming; the causal
    ``t <= pos`` mask is applied per block at global token positions."""
    s, h, hd = q.shape
    bt = ck.shape[1]
    m = tables.shape[1]
    scale = 1.0 / float(np.sqrt(hd))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, m),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda s, j, tables, pos: (s, 0, 0)),
            pl.BlockSpec((1, bt, h, hd),
                         lambda s, j, tables, pos: (tables[s, j], 0, 0, 0)),
            pl.BlockSpec((1, bt, h, hd),
                         lambda s, j, tables, pos: (tables[s, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, hd),
                               lambda s, j, tables, pos: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, block_tokens=bt, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, hd), jnp.float32),
        interpret=interpret,
    )(tables.astype(jnp.int32), pos.astype(jnp.int32), q, ck, cv)
