"""Pallas TPU flash-attention kernel.

Dense attention materializes the [T, T] score matrix in HBM per (batch,
head) — at T=4096 that is 64MB of f32 traffic each way, and HBM bandwidth
(not MXU FLOPs) bounds the op. The kernel below never materializes scores:
each q block stays in VMEM while k/v blocks stream through an online-softmax
accumulation (running max + denominator), so HBM traffic drops from
O(T^2) to O(T * D) per row — the flash-attention recipe, written per
/opt/skills/guides/pallas_guide.md.

The reference has no attention at all (2016 — SURVEY.md section 2.7: its
only long-sequence mechanism is truncated BPTT); attention enters this
framework via the MultiHeadAttention layer conf and the transformer
flagship (models/transformer.py), and THIS kernel is their TPU hot path.
The multi-chip path (ring attention over the 'seq' axis,
parallel/sequence_parallel.py) composes with it: the ring rotates K/V
shards between chips while each chip's local block product can run through
this kernel.

Scope & fallback policy (mirrors ops/pallas_kernels.py):
  - pallas forward kernel + blocked XLA backward: the fwd saves each row's
    log-sum-exp, and the custom_vjp recomputes probabilities K-block by
    K-block (lax.scan), so neither pass ever materializes the [T, T]
    score matrix;
  - causal and full attention; key padding masks run through the EXTENDED
    kernel (_flash_ext: additive key bias + traced visibility offset),
    which also powers the ring's local block product
    (flash_attention_block — shard-level causality as qi + off >= ki);
  - engages when pallas is enabled (ops.pallas_kernels.pallas_enabled) and
    the k/v rows fit VMEM (flash_fits / ext_fits); else dense XLA;
  - CPU tests run the same kernels under interpret=True.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas_kernels import pallas_enabled

_BLOCK_Q = 128
_BLOCK_K = 128
# K + V resident per (batch, head): 2 * T * D floats; budget well under the
# ~16MB/core VMEM, leaving room for the double-buffered q/o blocks + scratch.
_KV_BUDGET_FLOATS = 1_500_000


def flash_fits(t: int, d: int) -> bool:
    return (t % _BLOCK_Q == 0 and t % _BLOCK_K == 0
            and 2 * t * d <= _KV_BUDGET_FLOATS)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal: bool,
                  scale: float, block_k: int):
    """One q block vs all k/v blocks of one (batch*head) row.
    q_ref/o_ref: [1, Bq, D]; k_ref/v_ref: [1, T, D]; lse_ref: [1, 8, Bq]
    (log-sum-exp of each row's scores, broadcast over an 8-sublane padding
    dim for Mosaic block alignment — the residual the blocked backward
    needs to recompute softmax probabilities without the running max)."""
    q = q_ref[0].astype(jnp.float32) * scale          # [Bq, D]
    bq, d = q.shape
    t = k_ref.shape[1]
    qi = pl.program_id(1) * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    m0 = jnp.full((bq,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, d), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [Bq, Bk]
        if causal:
            ki = j * block_k + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            s = jnp.where(qi >= ki, s, -jnp.inf)
        blk_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, blk_max)
        # a fully-masked block leaves m_new at -inf on no row in the causal
        # case (the diagonal is always visible); guard anyway for the loop
        # iterations before any visible key
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[:, None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * corr + p.sum(-1)
        acc = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        return m_new, l, acc

    if causal:
        # keys strictly after this q block's last row never contribute
        n_blocks = (pl.program_id(1) * bq + bq + block_k - 1) // block_k
    else:
        n_blocks = t // block_k
    m, l, acc = lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # lse block is [1, 8, Bq]: Mosaic requires the last two block dims be
    # (8, 128)-aligned, so the scalar-per-row lse is broadcast across an
    # 8-sublane dim the caller slices back off
    lse_ref[0] = jnp.broadcast_to(
        (m_safe_final(m) + jnp.log(l_safe))[None, :], (8, l.shape[0]))


def m_safe_final(m):
    """-inf running max (row saw no visible key) -> 0 so lse stays finite."""
    return jnp.where(jnp.isfinite(m), m, 0.0)


def _flash_raw(q, k, v, *, causal: bool, interpret: bool):
    """q,k,v: [B, T, D] (B = batch*heads) -> (out [B, T, D], lse [B, 8, T])."""
    b, t, d = q.shape
    if t % _BLOCK_Q != 0 or t % _BLOCK_K != 0:
        # without this guard tail rows would silently come back unwritten
        # (NaN) — the grid and key loop both floor-divide by the block size
        raise ValueError(
            f"flash attention needs T divisible by {max(_BLOCK_Q, _BLOCK_K)}; "
            f"got T={t} (use attention_auto for automatic dense fallback)")
    scale = 1.0 / (d ** 0.5)
    grid = (b, t // _BLOCK_Q)
    return pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, scale=scale,
                          block_k=_BLOCK_K),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, _BLOCK_Q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, _BLOCK_Q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 8, _BLOCK_Q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, d), q.dtype),
            jax.ShapeDtypeStruct((b, 8, t), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",   # the kernel's name in the compiled program
    )(q, k, v)


def _dense_reference(q, k, v, *, causal: bool):
    """XLA dense attention on [B, T, D] (autodiff oracle + fallback).
    Softmax upcast is at-least-f32 (ops/dtypes.softmax_dtype): bf16
    upcasts as before, f64 stays f64 for the gradcheck substrate."""
    from deeplearning4j_tpu.ops.dtypes import softmax_dtype

    d = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q, k)
    s = s.astype(softmax_dtype(s.dtype)) / (d ** 0.5)
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bqk,bkd->bqd", p, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, interpret):
    return _flash_raw(q, k, v, causal=causal, interpret=interpret)[0]


def _flash_fwd(q, k, v, causal, interpret):
    o, lse = _flash_raw(q, k, v, causal=causal, interpret=interpret)
    return o, (q, k, v, o, lse[:, 0, :])  # drop the sublane-padding dim


def _flash_bwd(causal, interpret, res, g):
    """Blocked flash backward in plain XLA: softmax probabilities are
    recomputed per K-block from the saved log-sum-exp, so peak memory is
    O(T * block_k) per (batch*head) — never the [T, T] score matrix the
    dense autodiff would materialize (which OOMs at large batch*T).

    Standard flash-attention backward identities:
      D_i  = sum_d dO_id O_id
      P_ij = exp(S_ij - lse_i)
      dV_j = P^T dO;  dP = dO V^T;  dS = P * (dP - D);  dQ += dS K;
      dK_j = dS^T Q.
    """
    q, k, v, o, lse = res
    b, t, d = q.shape
    scale = 1.0 / (d ** 0.5)
    f32 = lambda a: a.astype(jnp.float32)
    q32, k32, v32 = f32(q), f32(k), f32(v)
    g32 = f32(g)
    Dvec = (g32 * f32(o)).sum(-1)                      # [B, T]
    nb = t // _BLOCK_K
    qi = jnp.arange(t)

    def block(dq, j):
        ks = lax.dynamic_slice_in_dim(k32, j * _BLOCK_K, _BLOCK_K, 1)
        vs = lax.dynamic_slice_in_dim(v32, j * _BLOCK_K, _BLOCK_K, 1)
        s = jnp.einsum("bqd,bkd->bqk", q32, ks) * scale
        if causal:
            ki = j * _BLOCK_K + jnp.arange(_BLOCK_K)
            s = jnp.where((qi[:, None] >= ki[None, :])[None], s, -jnp.inf)
        p = jnp.exp(s - lse[..., None])                # masked -> exp(-inf)=0
        dv_j = jnp.einsum("bqk,bqd->bkd", p, g32)
        dp = jnp.einsum("bqd,bkd->bqk", g32, vs)
        ds = p * (dp - Dvec[..., None]) * scale
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, ks)
        dk_j = jnp.einsum("bqk,bqd->bkd", ds, q32)
        return dq, (dk_j, dv_j)

    dq, (dks, dvs) = lax.scan(block, jnp.zeros_like(q32), jnp.arange(nb))
    # scan stacks K-blocks on the leading axis: [nb, B, Bk, D] -> [B, T, D]
    unstack = lambda a: a.transpose(1, 0, 2, 3).reshape(b, t, d)
    return (dq.astype(q.dtype), unstack(dks).astype(k.dtype),
            unstack(dvs).astype(v.dtype))


# the backward is plain XLA: the scope is what tells its fusions from the
# rest of the step in a device trace
_flash.defvjp(_flash_fwd, jax.named_call(_flash_bwd, name="flash_bwd"))


# ---------------------------------------------------------------------------
# Extended kernel: additive key bias (padding masks) + TRACED causal offset
# (ring attention). Kept separate from _flash so the mask-free single-device
# hot path (and its PALLAS_BENCH numbers) is untouched.
#
# The offset generalizes causal masking to sequence SHARDS: a key is visible
# iff qi + off >= ki (local indices). off = 0 is plain causal; off >= T makes
# everything visible (non-causal); off <= -T hides everything (a ring step
# whose K/V shard lies entirely in the future). Because off is a traced
# scalar (scalar-prefetch SMEM operand), the SAME compiled kernel serves
# every step of a lax.scan ring schedule — which is what lets the ring's
# local block product run through pallas at all.
# ---------------------------------------------------------------------------


def _flash_ext_kernel(off_ref, q_ref, k_ref, v_ref, kb_ref, o_ref, lse_ref,
                      *, scale: float, block_k: int):
    """Like _flash_kernel plus: kb_ref [1, 8, T] additive key bias (0 keeps,
    -inf masks; row 0 is real, rows 1-7 Mosaic sublane padding) and off_ref
    scalar-prefetch visibility offset."""
    off = off_ref[0]
    q = q_ref[0].astype(jnp.float32) * scale
    bq, d = q.shape
    t = k_ref.shape[1]
    qi = pl.program_id(1) * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    m0 = jnp.full((bq,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, d), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        kb = kb_ref[0, 0, pl.dslice(j * block_k, block_k)]  # [Bk] f32
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [Bq, Bk]
        s = s + kb[None, :]
        ki = j * block_k + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        s = jnp.where(qi + off >= ki, s, -jnp.inf)
        blk_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, blk_max)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[:, None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * corr + p.sum(-1)
        acc = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        return m_new, l, acc

    # off is traced, so no static causal truncation of the key loop (the
    # ring's shards are short; the full sweep is the price of one kernel
    # serving every ring step)
    m, l, acc = lax.fori_loop(0, t // block_k, body, (m0, l0, a0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # rows with NO visible key emit lse = -inf (not a ~-69 sentinel): the
    # ring combiner takes M = max over shard lse's, and a finite sentinel
    # could dominate a real block whose visible logits all sit below it,
    # collapsing the combined output toward the sentinel's zero o-block.
    # -inf gets weight exp(-inf - M_safe) = 0 in the combiner — exact.
    lse = jnp.where(jnp.isfinite(m),
                    m_safe_final(m) + jnp.log(l_safe), -jnp.inf)
    lse_ref[0] = jnp.broadcast_to(lse[None, :], (8, l.shape[0]))


def _flash_ext_raw(q, k, v, kb, off, *, interpret: bool):
    """q,k,v: [B, Tq, D] / [B, Tk, D]; kb: [B, 8, Tk] f32 additive key bias;
    off: [1] i32 -> (out [B, Tq, D], lse [B, 8, Tq]). Tq and Tk may differ
    (ring steps attend a local Q shard against a rotating K/V shard)."""
    b, tq, d = q.shape
    tk = k.shape[1]
    if tq % _BLOCK_Q != 0 or tk % _BLOCK_K != 0:
        raise ValueError(
            f"flash_ext needs Tq % {_BLOCK_Q} == 0 and Tk % {_BLOCK_K} == 0; "
            f"got Tq={tq}, Tk={tk}")
    scale = 1.0 / (d ** 0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, tq // _BLOCK_Q),
        in_specs=[
            pl.BlockSpec((1, _BLOCK_Q, d), lambda b, i, off: (b, i, 0)),
            pl.BlockSpec((1, tk, d), lambda b, i, off: (b, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda b, i, off: (b, 0, 0)),
            pl.BlockSpec((1, 8, tk), lambda b, i, off: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, _BLOCK_Q, d), lambda b, i, off: (b, i, 0)),
            pl.BlockSpec((1, 8, _BLOCK_Q), lambda b, i, off: (b, 0, i)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_flash_ext_kernel, scale=scale, block_k=_BLOCK_K),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, tq, d), q.dtype),
            jax.ShapeDtypeStruct((b, 8, tq), jnp.float32),
        ],
        interpret=interpret,
        name="flash_ext_fwd",
    )(off, q, k, v, kb)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _flash_ext(q, k, v, kb, off, interpret):
    return _flash_ext_raw(q, k, v, kb, off, interpret=interpret)


def _flash_ext_fwd(q, k, v, kb, off, interpret):
    o, lse = _flash_ext_raw(q, k, v, kb, off, interpret=interpret)
    return (o, lse), (q, k, v, kb, off, o, lse[:, 0, :])


def _flash_ext_bwd(interpret, res, gs):
    """Blocked XLA backward (same identities as _flash_bwd) with the key
    bias and visibility offset applied when recomputing probabilities,
    PLUS the lse cotangent: ring callers combine shard results through the
    returned log-sum-exp, so dL/dlse_i contributes p_ij to dS (the softmax
    jacobian of logsumexp). Masked/invisible keys have p = 0, hence zero
    dK/dV — exact."""
    q, k, v, kb, off, o, lse = res
    g, g_lse = gs
    b, tq, d = q.shape
    tk = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    f32 = lambda a: a.astype(jnp.float32)
    q32, k32, v32, g32 = f32(q), f32(k), f32(v), f32(g)
    # the kernel emits lse broadcast over 8 sublanes; fold the cotangent
    g_lse_row = (f32(g_lse).sum(axis=1) if g_lse is not None
                 else jnp.zeros((b, tq), jnp.float32))
    kb_row = kb[:, 0, :]                                # [B, Tk]
    Dvec = (g32 * f32(o)).sum(-1)                       # [B, Tq]
    nb = tk // _BLOCK_K
    qi = jnp.arange(tq)

    def block(dq, j):
        ks = lax.dynamic_slice_in_dim(k32, j * _BLOCK_K, _BLOCK_K, 1)
        vs = lax.dynamic_slice_in_dim(v32, j * _BLOCK_K, _BLOCK_K, 1)
        kbs = lax.dynamic_slice_in_dim(kb_row, j * _BLOCK_K, _BLOCK_K, 1)
        s = jnp.einsum("bqd,bkd->bqk", q32, ks) * scale + kbs[:, None, :]
        ki = j * _BLOCK_K + jnp.arange(_BLOCK_K)
        s = jnp.where((qi[:, None] + off[0] >= ki[None, :])[None], s,
                      -jnp.inf)
        # lse = -inf marks a no-visible-key row: p must be 0 there, and
        # exp(-inf - -inf) would be nan — substitute a finite lse first
        lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
        p = jnp.where(jnp.isfinite(s),
                      jnp.exp(s - lse_safe[..., None]), 0.0)
        dv_j = jnp.einsum("bqk,bqd->bkd", p, g32)
        dp = jnp.einsum("bqd,bkd->bqk", g32, vs)
        ds = p * (dp - Dvec[..., None]
                  + g_lse_row[..., None]) * scale
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, ks)
        dk_j = jnp.einsum("bqk,bqd->bkd", ds, q32)
        return dq, (dk_j, dv_j)

    dq, (dks, dvs) = lax.scan(block, jnp.zeros_like(q32), jnp.arange(nb))
    unstack = lambda a: a.transpose(1, 0, 2, 3).reshape(b, tk, d)
    return (dq.astype(q.dtype), unstack(dks).astype(k.dtype),
            unstack(dvs).astype(v.dtype), jnp.zeros_like(kb),
            np.zeros(off.shape, jax.dtypes.float0))


_flash_ext.defvjp(_flash_ext_fwd,
                  jax.named_call(_flash_ext_bwd, name="flash_ext_bwd"))


def flash_attention_block(q, k, v, *, offset, key_mask=None,
                          interpret: bool = False):
    """Flash attention of a Q shard against a K/V shard with shard-level
    causal visibility (qi + offset >= ki) and an optional key padding mask.

    q,k,v: [B, Tq, D] / [B, Tk, D] (B = batch*heads, heads already folded);
    offset: traced i32 scalar (see module notes); key_mask: [B, Tk] 0/1.
    Returns (out [B, Tq, D], lse [B, Tq]) — the log-sum-exp lets callers
    combine shard results exactly (ring attention's online softmax)."""
    b, _, _ = q.shape
    tk = k.shape[1]
    if key_mask is None:
        kb = jnp.zeros((b, 8, tk), jnp.float32)
    else:
        km = jnp.asarray(key_mask, bool)
        kb = jnp.broadcast_to(
            jnp.where(km, 0.0, -jnp.inf).astype(jnp.float32)[:, None, :],
            (b, 8, tk))
    off = jnp.asarray(offset, jnp.int32).reshape((1,))
    o, lse = _flash_ext(q, k, v, kb, off, interpret)
    return o, lse[:, 0, :]


def ext_fits(tq: int, tk: int, d: int) -> bool:
    """VMEM gate for the extended kernel (K + V + bias resident)."""
    return (tq % _BLOCK_Q == 0 and tk % _BLOCK_K == 0
            and 2 * tk * d + 8 * tk <= _KV_BUDGET_FLOATS)


def _apply_folded(fn, q, k, v):
    """Run fn on [N*H, T, D]-folded q/k/v and unfold back to [N, T, H, D]."""
    n, t, h, d = q.shape
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(n * h, t, d)
    out = fn(fold(q), fold(k), fold(v))
    return out.reshape(n, h, t, d).transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal: bool = False,
                    interpret: bool = False) -> jax.Array:
    """q,k,v: [N, T, H, D] -> [N, T, H, D] softmax attention, flash kernel."""
    return _apply_folded(
        lambda q, k, v: _flash(q, k, v, causal, interpret), q, k, v)


def dense_attention(q, k, v, *, causal: bool = False) -> jax.Array:
    """q,k,v: [N, T, H, D] -> [N, T, H, D] dense XLA attention (the fallback
    path and the flash kernel's equivalence oracle)."""
    return _apply_folded(
        lambda q, k, v: _dense_reference(q, k, v, causal=causal), q, k, v)


def _fold_heads(x):
    n, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(n * h, t, d)


def _unfold_heads(x, n, h):
    b, t, d = x.shape
    return x.reshape(n, h, t, d).transpose(0, 2, 1, 3)


def flash_attention_masked(q, k, v, key_mask, *, causal: bool = False,
                           interpret: bool = False) -> jax.Array:
    """q,k,v: [N, T, H, D]; key_mask: [N, T] 0/1 — flash attention with
    padded keys excluded from the softmax (the extended kernel's key bias;
    previously masked batches always fell back to dense XLA attention)."""
    n, t, h, d = q.shape
    km = jnp.repeat(jnp.asarray(key_mask, bool), h, axis=0)  # [N*H, T]
    off = t if not causal else 0
    o, _ = flash_attention_block(
        _fold_heads(q), _fold_heads(k), _fold_heads(v),
        offset=off, key_mask=km, interpret=interpret)
    return _unfold_heads(o, n, h)


def _dense_masked(q, k, v, key_mask, *, causal: bool):
    """Dense fallback with a key padding mask, [N, T, H, D] layout."""
    from deeplearning4j_tpu.ops.dtypes import softmax_dtype

    d = q.shape[-1]
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k)
    s = s.astype(softmax_dtype(s.dtype)) / (d ** 0.5)
    if causal:
        t = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s,
                      -jnp.inf)
    km = jnp.asarray(key_mask, bool)[:, None, None, :]
    s = jnp.where(km, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isfinite(s).any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("nhqk,nkhd->nqhd", p.astype(q.dtype), v)


def attention_auto(q, k, v, *, causal: bool = False,
                   key_mask=None) -> jax.Array:
    """Backend registry slot (the reference's reflective cuDNN-helper
    pattern, ConvolutionLayer.java:64-70): flash kernel when pallas is on
    and the shape fits VMEM, dense XLA attention otherwise. key_mask
    ([N, T] 0/1) runs through the extended kernel's key bias — default-on
    only once PALLAS_BENCH.json proves the ext kernel on chip (the
    measured-win rent rule, ops/kernel_gate.py)."""
    from deeplearning4j_tpu.ops.kernel_gate import measured_win

    t, d = q.shape[1], q.shape[3]
    if key_mask is not None:
        if (pallas_enabled() and ext_fits(t, t, d)
                and measured_win("attention", "masked_flash")):
            return flash_attention_masked(q, k, v, key_mask, causal=causal)
        return _dense_masked(q, k, v, key_mask, causal=causal)
    if pallas_enabled() and flash_fits(t, d):
        return flash_attention(q, k, v, causal=causal)
    return dense_attention(q, k, v, causal=causal)
