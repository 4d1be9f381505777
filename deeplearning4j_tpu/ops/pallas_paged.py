"""Pallas TPU kernel: the decode tick's attention over the paged block arena,
each lane reading its own live blocks and no others.

The gather path (serving/paged.chunked_attention, kept as the CPU path, the
float32-arena path and this kernel's oracle) gathers every lane's blocks a
chunk at a time as far as the LONGEST lane reaches, dead lanes and short ones
too. Here (the vLLM PagedAttention move, Kwon et al. 2023, with the flash
online softmax):

  * the grid is the lanes; a lane's first and last chunk come from its
    scalar-prefetched ``pos`` (and ``lo``: a window layer starts at its own
    first chunk), and a ``fori_loop`` with that data-dependent trip count
    runs inside the grid step. The grid is static and the bound is data, so
    one program serves every live length;
  * the arena stays in HBM; each chunk is ``c`` table entries, the lane's
    blocks DMA'd into one slot of a double-buffered VMEM scratch, the next
    chunk's copies started before the current one is computed (and the
    next lane's first chunk before the lane's last). Only blocks that hold
    a position the lane sees are copied: a dead lane (pos 0 on trash block
    0) reads one block, and no lane reads past its ``pos``;
  * all heads take part in one product a side, against the chunk's rows as
    stored (a token's heads side by side): query head i's rows hold its
    values in the columns of its KV head (i // g) and zeros elsewhere, and
    its output is its row of the accumulator in those columns. One softmax
    chain a chunk for every head, for every grouping: multi-head (g = 1) to
    g = 8. Read on the chip (PERF.md section 6), a product pair and a
    softmax a KV head cost 12 serial chains a chunk at 590M and ran slower
    than the gather; the one product reads 60% of the roofline there;
  * only a lane's first and last chunk build a mask; there the rows no copy
    filled are also zeroed in V, so what the scratch held before never
    meets a probability.

The arithmetic is chunked_attention's: scores of the float32 query against K
as stored, summed in float32, times the caller's scale; max, denominator,
correction and accumulator in float32; the float32 probabilities against V
as stored. Both products take the float32 operand as its three exact bf16
rows (serving/paged._exact_rows), so they stay on the matrix unit and round
nothing. Only the chunks' length differs from the gather's (it follows the
bytes of the whole row, on one device or split over several), which moves
where the running max is taken: the same rounding in another order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import device

# bytes of one K (and one V) wave of block copies a chunk: enough blocks in
# flight that the copies are bound by bandwidth and not by their latency,
# and enough positions a chunk that its fixed cost (two products, the
# softmax's reductions) is spread thin. Read on the chip (PERF.md section
# 6): at 590M's 48 KB blocks 16 a chunk beat 32; at 16 KB blocks 32 and 64
# read within 3% of each other, both ahead of 16
WAVE_BYTES = 512 << 10


def fits(kv_dtype, head_dim: int, block_tokens: int) -> bool:
    """The kernel takes the arena: on a TPU, bf16 (the compute dtype of the
    served models; a float32 arena takes the gather), a head filling whole
    lanes of 128 and a block of whole bf16 tiles of 16 rows."""
    return (device.on_tpu()
            and jnp.dtype(kv_dtype) == jnp.dtype(jnp.bfloat16)
            and head_dim % 128 == 0 and block_tokens % 16 == 0)


def chunk_blocks(block_bytes: int, table_width: int) -> int:
    """Blocks a chunk: the power of two whose wave reaches WAVE_BYTES, and
    no more than the table holds."""
    c = 1 << max(0, int(np.ceil(np.log2(WAVE_BYTES / block_bytes))))
    return max(1, min(c, table_width))


# the pallas call's name, which the compiled instruction and the device
# event carry (perfbench/metrics/decode_attn_roofline.serve.py reads them)
NAME = "decode_attn"


def _exact3(x, dtype):
    """x [r, n] float32 as its three exact bf16 rows stacked, [3r, n] in
    ``dtype`` (serving/paged._exact_rows, rows of a part side by side)."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    rest = x - hi
    mid = rest.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (rest - mid).astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.concatenate([hi, mid, lo], axis=0).astype(dtype)


def _kernel(tab_ref, pos_ref, lo_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, slot_ref, qf_s, q3_s, m_s, l_s, acc_s, *,
            lanes, m, c, bt, kv_heads, group, hd, scale):
    s = pl.program_id(0)
    chunk, width = c * bt, kv_heads * hd
    rows = m_s.shape[0]                 # the query heads, padded to 8
    prec = lax.Precision.HIGHEST if q3_s.dtype == jnp.float32 else None

    def span(lane):
        """(first chunk, last chunk, first block, last block) of a lane."""
        p, low = pos_ref[lane], lo_ref[lane]
        return low // chunk, p // chunk, low // bt, p // bt

    def copies(lane, j, slot, go):
        """Start (go) or wait for the copies of chunk j of a lane into a
        slot: the table entries of the chunk that hold a visible block."""
        _, _, b_lo, b_hi = span(lane)
        start = j * c

        def one(b, carry):
            blk = tab_ref[lane * m + b]
            for hbm, buf, i in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                cp = pltpu.make_async_copy(hbm.at[blk],
                                           buf.at[slot, b - start],
                                           sems.at[i, slot])
                if go:
                    cp.start()
                else:
                    cp.wait()
            return carry

        lax.fori_loop(jnp.maximum(start, b_lo),
                      jnp.minimum(start + c, b_hi + 1), one, 0)

    @pl.when(s == 0)
    def _first_lane():
        slot_ref[0] = 0
        copies(0, span(0)[0], 0, True)

    first, last, _, _ = span(s)
    base = slot_ref[0]

    # the query as the rows of one product against the whole row of K: part
    # p (of its three exact rows) of query head i is row p * rows + i, in
    # the columns of its KV head i // group and zero elsewhere
    parts = _exact3(q_ref[0].astype(jnp.float32), jnp.float32)
    n_heads = kv_heads * group
    qf_s[...] = jnp.zeros(qf_s.shape, jnp.float32)
    for p in range(3):
        for h in range(kv_heads):
            at = p * rows + h * group
            qf_s[at:at + group, h * hd:(h + 1) * hd] = \
                parts[p * n_heads + h * group:p * n_heads + (h + 1) * group]
    q3_s[...] = qf_s[...].astype(q3_s.dtype)
    m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
    l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
    acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def fold(j, slot, masked: bool):
        k = kbuf[slot].reshape(chunk, width)
        sc3 = lax.dot_general(q3_s[...], k, (((1,), (1,)), ((), ())),
                              precision=prec,
                              preferred_element_type=jnp.float32)
        sc = (sc3[:rows] + sc3[rows:2 * rows] + sc3[2 * rows:]) * scale
        v = vbuf[slot].reshape(chunk, width)
        if masked:
            p, low = pos_ref[s], lo_ref[s]
            t = j * chunk + lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
            sc = jnp.where((t >= low) & (t <= p), sc, -jnp.inf)
            tv = j * chunk + lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
            v = jnp.where((tv >= low) & (tv <= p), v, jnp.zeros_like(v))
        m_prev = m_s[...]                                  # [rows, 128]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        pr = jnp.exp(sc - m_new[:, :1])
        corr = jnp.exp(m_prev - m_new)
        m_s[...] = m_new
        l_s[...] = l_s[...] * corr + jnp.sum(pr, axis=1, keepdims=True)
        pv3 = lax.dot_general(_exact3(pr, v.dtype), v,
                              (((1,), (0,)), ((), ())), precision=prec,
                              preferred_element_type=jnp.float32)
        acc_s[...] = acc_s[...] * corr[:, :1] + (
            pv3[:rows] + pv3[rows:2 * rows] + pv3[2 * rows:])

    def step(t, carry):
        j = first + t
        slot = (base + t) % 2

        @pl.when(j < last)
        def _next_chunk():
            copies(s, j + 1, 1 - slot, True)

        @pl.when((j == last) & (s + 1 < lanes))
        def _next_lane():
            nxt = jnp.minimum(s + 1, lanes - 1)
            copies(nxt, span(nxt)[0], 1 - slot, True)
            slot_ref[0] = 1 - slot

        copies(s, j, slot, False)
        edge = (j == first) | (j == last)

        @pl.when(edge)
        def _edge():
            fold(j, slot, True)

        @pl.when(jnp.logical_not(edge))
        def _inside():
            fold(j, slot, False)

        return carry

    lax.fori_loop(0, last - first + 1, step, 0)
    # query head i's output is its row of the accumulator, in its KV
    # head's columns
    for h in range(kv_heads):
        i = slice(h * group, (h + 1) * group)
        o_ref[0, i, :] = acc_s[i, h * hd:(h + 1) * hd] / l_s[i, :1]


@functools.partial(jax.jit, static_argnames=("scale", "c", "interpret"))
def _decode_call(q, ck, cv, tables, pos, lo, *, scale, c, interpret=False):
    """The pallas call, jitted so that every layer of an unrolled tick with
    the same shapes traces and lowers it once."""
    s, n_heads, hd = q.shape
    bt, width = ck.shape[1], ck.shape[2]
    kv_heads = width // hd
    rows = -(-n_heads // 8) * 8
    m = tables.shape[1]
    # the arena where it lies, in HBM, read only by the kernel's copies: left
    # free to choose, the compiler prefetches a layer's buffer that fits
    # into VMEM whole, every tick (the interpreter takes no such constraint)
    hbm = (lambda a: a) if interpret else (
        lambda a: pltpu.with_memory_space_constraint(a, pltpu.HBM))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s,),
        in_specs=[
            pl.BlockSpec((1, n_heads, hd), lambda i, tab, p, low: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, n_heads, hd),
                               lambda i, tab, p, low: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, c, bt, width), ck.dtype),
            pltpu.VMEM((2, c, bt, width), cv.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((3 * rows, width), jnp.float32),
            pltpu.VMEM((3 * rows, width), ck.dtype),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, width), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, lanes=s, m=m, c=c, bt=bt,
                          kv_heads=kv_heads, group=n_heads // kv_heads,
                          hd=hd, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, n_heads, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=NAME,
    )(tables.reshape(-1), pos, lo, q.astype(jnp.float32), hbm(ck), hbm(cv))


def decode_attention(q, ck, cv, tables, pos, scale=None, lo=None, *,
                     shards: int = 1):
    """Masked single-query attention over the block arena, with
    serving/paged.chunked_attention's signature and contract: q [S, H, hd],
    ck/cv [B, bt, Hkv * hd] (block 0 trash), tables [S, m] int32, pos [S]
    int32, ``scale`` (None: 1/sqrt(hd)), ``lo`` [S] (a window layer: lane s
    sees ``lo <= t <= pos``) -> att [S, H, hd] float32.

    The kernel where it takes the arena (``fits``: a TPU, a bf16 arena,
    whole lanes), else the gather path. ``shards``: the arena's heads are
    split over that many devices and these are one device's (serving/mesh.py).
    The chunk follows the bytes of the whole row, so a device folds its heads
    in the chunks one device would: the same sums in the same order, bit for
    bit."""
    hd = q.shape[-1]
    bt = ck.shape[1]
    ck = ck.reshape(ck.shape[0], bt, -1)
    cv = cv.reshape(cv.shape[0], bt, -1)
    if not fits(ck.dtype, hd, bt):
        from deeplearning4j_tpu.serving import paged

        return paged.chunked_attention(q, ck, cv, tables, pos, scale=scale,
                                       lo=lo)
    if scale is None:
        scale = 1.0 / float(np.sqrt(hd))
    if lo is None:
        lo = jnp.zeros_like(pos)
    c = chunk_blocks(shards * bt * ck.shape[2] * ck.dtype.itemsize,
                     tables.shape[1])
    with jax.named_scope("tick.attend"):
        return _decode_call(q, ck, cv, tables.astype(jnp.int32),
                            pos.astype(jnp.int32), lo.astype(jnp.int32),
                            scale=float(scale), c=int(c))
