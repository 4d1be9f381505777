"""Pallas TPU kernel: the admission prefill's causal grouped-query attention
with bf16 products (models/hybrid.attention_full where the configuration
states ``attn_exact=False``), optionally over a window, without a score in
HBM.

The plain path (the by-blocks XLA one in models/hybrid.py, kept as the CPU
path, the float32 path and this kernel's oracle) writes every pass's float32
scores ``[Hkv, g * rows, keys]`` to HBM, masks them, reduces them and reads
them back for the value product, and computes the causal half it masks away.
Here:

  * the grid is (KV head, step), and the steps are the (query block, key
    block) pairs that some row of the query block can see, listed on the host
    at trace time and handed to the kernel as scalar-prefetched tables: the
    query blocks in order, each one's key blocks in order. A block above the
    diagonal, or below a window layer's band, is neither fetched nor
    computed; K and V stream one block a step, never resident whole;
  * a query block is the g query heads of its KV head side by side (columns
    ``[j*g*hd, (j+1)*g*hd)`` of q ``[T, H*hd]``), stacked at the block's
    first step into ``[g * bq, hd]`` rows of a VMEM scratch, so each K and V
    block is read once for all g heads;
  * the running max, the denominator and the accumulator stay in VMEM,
    float32; the output ``[T, H*hd]`` is written once a query block, in the
    heads' own columns (what ``Wo`` reads, no transpose on either side);
  * only a step flagged on the host (the diagonal's partial blocks, the
    window's lower edge) builds a mask.

The arithmetic is the plain path's: q, K and V in the arena's dtype, both
products summing in float32, the scale on the float32 scores, the max, the
exponentials, the correction and the denominator in float32, the
probabilities rounded to V's dtype before the value product, a block none of
whose keys a row sees leaving that row's max at -inf (the exponentials are
taken against 0 there). Only the blocks' edges differ from the plain path's
chunks, which moves where the probabilities are rounded against a running
max: the same rounding, another order.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# g * bq query rows a block at the most: the float32 scores [g * bq, bk] and
# their exponentials lie in VMEM beside the scratch
ROWS_MOST = 2048
# key and query block sizes tried, largest first; a width of no more than
# the largest is one block. Read on the chip at 3,072 and 8,192 positions
# (PERF.md section 6, PR 38): keys of 1,024 a step against 512 and 256
# took a global layer's kernel from 34% and 19% of its roofline to 51%;
# query blocks of 256 (1,792 rows) against 128 gain a few percent more
KEY_BLOCKS = (1024, 512, 256, 128)
QUERY_BLOCKS = (256, 128, 64)
# the flags of a step (bits)
FIRST, LAST, MASKED = 1, 2, 4


def tiles(t: int, group: int) -> Tuple[Optional[int], Optional[int]]:
    """(query rows a block, keys a block) for a sequence of t positions with
    `group` query heads a KV head; None where no tile divides t."""
    bk = t if t <= KEY_BLOCKS[0] else next(
        (b for b in KEY_BLOCKS if t % b == 0), None)
    bq = t if group * t <= ROWS_MOST else next(
        (b for b in QUERY_BLOCKS if t % b == 0 and group * b <= ROWS_MOST),
        None)
    return bq, bk


def fits(t: int, group: int, head_dim: int) -> bool:
    """The kernel takes t positions: a head fills whole lanes and a tile of
    16 rows (bf16) divides both blocks."""
    bq, bk = tiles(t, group)
    return head_dim % 128 == 0 and bq is not None and bk is not None \
        and bq % 16 == 0 and bk % 16 == 0


def steps(t: int, bq: int, bk: int, window: int = 0) -> np.ndarray:
    """The visible (query block, key block) pairs in the kernel's order,
    with their flags: int32 [3, n] rows (query block, key block, flags).
    A query block starting at `start` sees the key blocks from the one
    holding ``start - window + 1`` (a window layer) or 0 to the one holding
    its last row; a pair needs a mask where some key lies past the block's
    first row, or (a window) at or before its last row less the window."""
    out = []
    for i in range(t // bq):
        start, last = i * bq, i * bq + bq - 1
        lo = max(start - window + 1, 0) if window else 0
        first_k, last_k = lo // bk, last // bk
        for j in range(first_k, last_k + 1):
            masked = j * bk + bk - 1 > start or \
                bool(window and j * bk <= last - window)
            out.append((i, j, (FIRST if j == first_k else 0)
                        | (LAST if j == last_k else 0)
                        | (MASKED if masked else 0)))
    return np.asarray(out, np.int32).T.copy()


def _kernel(qb_ref, kb_ref, fl_ref, q_ref, k_ref, v_ref, o_ref,
            q_s, m_s, l_s, acc_s, *, group, bq, bk, hd, scale, window):
    s = pl.program_id(1)
    flag = fl_ref[s]

    @pl.when((flag & FIRST) != 0)
    def _start():
        for g in range(group):
            q_s[g * bq:(g + 1) * bq, :] = q_ref[:, g * hd:(g + 1) * hd]
        m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def fold(masked: bool):
        sc = lax.dot_general(q_s[...], k_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
        if masked:
            # [bq, bk] of one head's rows, the same for each of the g heads
            at = qb_ref[s] * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            key = kb_ref[s] * bk + lax.broadcasted_iota(jnp.int32,
                                                        (bq, bk), 1)
            see = key <= at
            if window:
                see = see & (key > at - window)
            sc = jnp.where(see[None], sc.reshape(group, bq, bk),
                           -jnp.inf).reshape(group * bq, bk)
        m = m_s[...]                                   # [g * bq, 128]
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        # a block none of whose keys a row sees leaves its max at -inf
        base = jnp.where(m_new > -jnp.inf, m_new, 0.0) if masked else m_new
        p = jnp.exp(sc - base[:, :1])
        corr = jnp.exp(m - base)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_s[...] = m_new
        pv = lax.dot_general(p.astype(v_ref.dtype), v_ref[...],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_s[...] = acc_s[...] * corr[:, :1] + pv

    @pl.when((flag & MASKED) != 0)
    def _edge():
        fold(True)

    @pl.when((flag & MASKED) == 0)
    def _inside():
        fold(False)

    @pl.when((flag & LAST) != 0)
    def _end():
        for g in range(group):
            rows = slice(g * bq, (g + 1) * bq)
            o_ref[:, g * hd:(g + 1) * hd] = (
                acc_s[rows, :] / l_s[rows, :1]).astype(o_ref.dtype)


def name(window: int = 0) -> str:
    """The pallas call's name, which the compiled instruction and the device
    event carry: the window where there is one."""
    return f"prefill_attn_w{window}" if window else "prefill_attn"


def prefill_attention(q, k, v, *, head_dim: int, scale: float,
                      window: int = 0, interpret: bool = False):
    """Causal attention of one sequence, grouped heads: q [T, H*hd], k, v
    [T, Hkv*hd] (a position's heads side by side; KV head j serves query
    heads g*j .. g*j+g-1), all in one dtype -> [T, H*hd] in q's dtype. Row t
    sees keys s <= t, and with a window only ``t - window < s``."""
    t, hk = q.shape[0], k.shape[1] // head_dim
    group = q.shape[1] // k.shape[1]
    if not fits(t, group, head_dim):
        raise ValueError(f"prefill_attention takes no tiles at T={t}, "
                         f"{group} heads a KV head of {head_dim}")
    bq, bk = tiles(t, group)
    table = steps(t, bq, bk, window)
    n = table.shape[1]
    rows = group * bq
    w = window if window and window < t else t
    pairs = w * (w + 1) // 2 + (t - w) * w
    itemsize = jnp.dtype(q.dtype).itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(hk, n),
        in_specs=[
            pl.BlockSpec((bq, group * head_dim),
                         lambda h, s, qb, kb, fl: (qb[s], h)),
            pl.BlockSpec((bk, head_dim), lambda h, s, qb, kb, fl: (kb[s], h)),
            pl.BlockSpec((bk, head_dim), lambda h, s, qb, kb, fl: (kb[s], h)),
        ],
        out_specs=pl.BlockSpec((bq, group * head_dim),
                               lambda h, s, qb, kb, fl: (qb[s], h)),
        scratch_shapes=[
            pltpu.VMEM((rows, head_dim), q.dtype),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, head_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, group=group, bq=bq, bk=bk, hd=head_dim,
                          scale=float(scale), window=int(window)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * pairs * head_dim * hk * group,
            transcendentals=pairs * hk * group,
            bytes_accessed=2 * q.size * itemsize
            + 2 * n * bk * head_dim * itemsize * hk),
        interpret=interpret,
        name=name(window),
    )(jnp.asarray(table[0]), jnp.asarray(table[1]), jnp.asarray(table[2]),
      q, k, v)
