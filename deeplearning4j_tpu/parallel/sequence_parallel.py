"""Sequence/context parallelism: ring attention over the device mesh.

The reference's only long-sequence mechanism is truncated BPTT (SURVEY.md
section 5 "Long-context": no ring attention / CP / Ulysses existed in 2016).
This framework treats long-context as first-class: sequences too long for
one chip's HBM are sharded over the mesh's sequence axis and attention runs
as a RING — each device holds its Q shard permanently, while K/V shards
rotate around the ring via `ppermute` over ICI; softmax is accumulated
online (running max + denominator, flash-attention style) so the result is
EXACTLY full attention, never an approximation.

Pieces:
  - `multi_head_attention(...)`: the single-device reference math;
  - `ring_attention(...)`: per-shard body (runs inside shard_map);
  - `ring_attention_sharded(...)`: user entry — builds the shard_map over a
    ('seq',) mesh axis and returns the full attention output;
  - `ulysses_attention_sharded(...)`: the all-to-all alternative (swap the
    sharded axis seq->heads, attend locally, swap back) for when heads
    divide the mesh and per-device [T, T] blocks fit memory;
  - causal masking is exact across shards via global position indexing.

Design notes (scaling-book recipe): the ring overlaps compute of block t
with the DCN/ICI transfer of block t+1 when XLA schedules the ppermute
asynchronously; per-device memory is O(T_local * T_local) per block pair
instead of O(T^2).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SEQ_AXIS = "seq"


# ---------------------------------------------------------------------------
# Reference single-device attention
# ---------------------------------------------------------------------------


def multi_head_attention(q, k, v, *, causal: bool = False,
                         q_offset: int = 0, k_offset: int = 0,
                         key_mask=None):
    """q,k,v: [N, T, H, D] -> [N, T, H, D]; plain softmax attention.
    Offsets give global positions for causal masking of shards.
    key_mask: optional [N, Tk] 0/1 — padded keys are excluded from the
    softmax (variable-length batches)."""
    d = q.shape[-1]
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / jnp.sqrt(jnp.asarray(d, q.dtype))
    # softmax in AT LEAST f32 (ops/dtypes.softmax_dtype): a bf16 exp/sum
    # over thousands of keys loses mass (every other attention path —
    # serial _attention, the ring body, the flash kernel — already
    # upcasts); f64 inputs stay f64 so the x64 gradcheck substrate keeps
    # its resolution
    from deeplearning4j_tpu.ops.dtypes import softmax_dtype

    s = s.astype(softmax_dtype(s.dtype))
    if causal:
        qi = q_offset + jnp.arange(q.shape[1])
        ki = k_offset + jnp.arange(k.shape[1])
        mask = qi[:, None] >= ki[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    if key_mask is not None:
        km = jnp.asarray(key_mask, bool)[:, None, None, :]  # [N,1,1,Tk]
        s = jnp.where(km, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows (causal shard with no visible keys) -> zeros not NaN
    p = jnp.where(jnp.isfinite(s).any(axis=-1, keepdims=True), p, 0.0)
    return jnp.einsum("nhqk,nkhd->nqhd", p.astype(q.dtype), v)


# ---------------------------------------------------------------------------
# Ring attention (runs inside shard_map over the sequence axis)
# ---------------------------------------------------------------------------


def _ring_attention_body(q, k, v, key_mask=None, *, causal: bool,
                         t_local: int, axis_name: str = SEQ_AXIS):
    """Per-device body. q,k,v: [N, T_local, H, D] shards; key_mask an
    optional [N, T_local] 0/1 shard that rotates with its K/V block. Exact
    full attention via online softmax over rotating K/V blocks."""
    n_dev = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    n, tq, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    q32 = q.astype(jnp.float32)

    # accumulators: running max m, denominator l, numerator o
    m = jnp.full((n, h, tq), -jnp.inf, jnp.float32)
    l = jnp.zeros((n, h, tq), jnp.float32)
    o = jnp.zeros((n, tq, h, d), jnp.float32)

    q_pos = my * t_local + jnp.arange(tq)
    # the mask shard (when present) travels around the ring WITH its K/V
    # block; the mask-free hot path carries (and ppermutes) nothing extra
    km0 = () if key_mask is None else (jnp.asarray(key_mask, bool),)

    def step_fn(carry, step):
        m, l, o, k_blk, v_blk, km_blk = carry
        # the block currently held arrived from device (my - step) mod n_dev
        src = (my - step) % n_dev
        s = jnp.einsum("nqhd,nkhd->nhqk", q32, k_blk.astype(jnp.float32))
        s = s * scale
        if causal:
            k_pos = src * t_local + jnp.arange(t_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        if key_mask is not None:
            s = jnp.where(km_blk[0][:, None, None, :], s, -jnp.inf)
        blk_max = jnp.max(s, axis=-1)  # [N,H,Tq]
        m_new = jnp.maximum(m, blk_max)
        # guard -inf - -inf
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * corr + p.sum(axis=-1)
        o = o * jnp.moveaxis(corr, 1, 2)[..., None] + jnp.einsum(
            "nhqk,nkhd->nqhd", p, v_blk.astype(jnp.float32)
        )
        # rotate K/V (and the mask that travels with them) around the ring
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        km_blk = tuple(lax.ppermute(km, axis_name, perm) for km in km_blk)
        return (m_new, l, o, k_blk, v_blk, km_blk), None

    (m, l, o, _, _, _), _ = lax.scan(
        step_fn, (m, l, o, k, v, km0), jnp.arange(n_dev)
    )
    # where-based safe denominator, NOT maximum(l, 1e-30): the division
    # backward computes -o/denom^2 and (1e-30)^2 underflows f32 to 0,
    # turning all-masked rows (l = 0, o = 0) into 0/0 = NaN grads
    denom = jnp.moveaxis(jnp.where(l > 0, l, 1.0), 1, 2)[..., None]
    return (o / denom).astype(q.dtype)


def _ring_attention_body_flash(q, k, v, key_mask=None, *, causal: bool,
                               t_local: int, axis_name: str = SEQ_AXIS,
                               interpret: bool = False):
    """Ring body with the LOCAL block product running through the pallas
    flash kernel (ops/pallas_attention.flash_attention_block — the
    composition that module's header promises): per ring step the kernel
    returns (block_out, lse) and the shard results are combined exactly in
    log space. The kernel's TRACED visibility offset (qi + off >= ki with
    off = (my - src) * t_local) expresses shard-level causality, so one
    compiled kernel serves every step of the lax.scan ring."""
    from deeplearning4j_tpu.ops.pallas_attention import (
        _fold_heads,
        _unfold_heads,
        flash_attention_block,
    )

    n_dev = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    n, tq, h, d = q.shape

    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    # mask shard travels with its K/V block; mask-free path carries nothing
    km0 = () if key_mask is None else (jnp.asarray(key_mask, bool),)

    # combined accumulators over ring steps: running max M of the lse,
    # denominator l (in M scale), numerator o (in M scale)
    M = jnp.full((n * h, tq), -jnp.inf, jnp.float32)
    l = jnp.zeros((n * h, tq), jnp.float32)
    o = jnp.zeros((n * h, tq, d), jnp.float32)

    def step_fn(carry, step):
        M, l, o, k_blk, v_blk, km_blk = carry
        src = (my - step) % n_dev
        # visible iff my*t+qi >= src*t+ki  <=>  qi + (my-src)*t >= ki;
        # non-causal: off = t_local*n_dev makes every key visible
        off = ((my - src) * t_local) if causal else t_local * n_dev
        o_b, lse_b = flash_attention_block(
            qf, k_blk, v_blk, offset=off,
            key_mask=(jnp.repeat(km_blk[0], h, axis=0) if km_blk else None),
            interpret=interpret)
        M_new = jnp.maximum(M, lse_b)
        M_safe = jnp.where(jnp.isfinite(M_new), M_new, 0.0)
        corr = jnp.where(jnp.isfinite(M), jnp.exp(M - M_safe), 0.0)
        w = jnp.exp(lse_b - M_safe)
        l = l * corr + w
        o = o * corr[..., None] + w[..., None] * o_b.astype(jnp.float32)
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        km_blk = tuple(lax.ppermute(km, axis_name, perm) for km in km_blk)
        return (M_new, l, o, k_blk, v_blk, km_blk), None

    (M, l, o, _, _, _), _ = lax.scan(
        step_fn, (M, l, o, kf, vf, km0), jnp.arange(n_dev))
    # where-based safe denominator (see _ring_attention_body): with the
    # kernel's lse = -inf for all-masked rows, l = 0 here, and a
    # maximum(l, 1e-30) denominator NaNs the backward via (1e-30)^2
    # f32 underflow in -o/denom^2
    out = o / jnp.where(l > 0, l, 1.0)[..., None]
    return _unfold_heads(out, n, h).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: Mesh, *, causal: bool = False,
                           key_mask=None, use_flash: Optional[bool] = None,
                           interpret: bool = False,
                           batch_axis: Optional[str] = None):
    """Full exact attention with the SEQUENCE dimension sharded over
    mesh axis 'seq'. q,k,v: [N, T, H, D] with T divisible by the axis size.
    key_mask: optional [N, T] 0/1, sharded with the keys (padded timesteps
    excluded exactly — the mask shard rotates with its K/V block).
    use_flash: run the local block product through the pallas flash kernel
    (ops/pallas_attention.py); default auto — on when pallas is enabled and
    the local shard fits the kernel's block/VMEM constraints.
    batch_axis: optional second mesh axis sharding the BATCH dim (DP x SP
    composition) — without it a ('data','seq') caller would all-gather the
    batch and compute every data slice's attention redundantly."""
    from deeplearning4j_tpu.ops.pallas_attention import (
        ext_fits,
        pallas_enabled,
    )

    n_dev = mesh.shape[SEQ_AXIS]
    t = q.shape[1]
    if t % n_dev != 0:
        raise ValueError(f"sequence length {t} not divisible by {n_dev} devices")
    t_local = t // n_dev
    if use_flash is None:
        # default-on needs BOTH the fit check and a committed on-chip win
        # (kernel_gate rent rule); explicit use_flash=True bypasses only
        # the win check
        from deeplearning4j_tpu.ops.kernel_gate import measured_win

        use_flash = (pallas_enabled()
                     and ext_fits(t_local, t_local, q.shape[-1])
                     and measured_win("attention", "ring_local_flash"))
    elif use_flash and not ext_fits(t_local, t_local, q.shape[-1]):
        raise ValueError(
            f"use_flash=True but the local shard (T_local={t_local}, "
            f"D={q.shape[-1]}) does not fit the kernel's block/VMEM "
            "constraints (ops/pallas_attention.ext_fits); use more/fewer "
            "'seq' devices or use_flash=False")
    body = (_ring_attention_body_flash if use_flash
            else _ring_attention_body)
    kwargs = dict(causal=causal, t_local=t_local)
    if use_flash:
        kwargs["interpret"] = interpret
    spec = P(batch_axis, SEQ_AXIS, None, None)
    args = (q, k, v)
    in_specs = (spec, spec, spec)
    if key_mask is not None:
        args += (key_mask,)
        in_specs += (P(batch_axis, SEQ_AXIS),)
    fn = shard_map(
        partial(body, **kwargs),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=spec,
        check_vma=False,
    )
    return fn(*args)


# ---------------------------------------------------------------------------
# Ulysses: all-to-all sequence parallelism (the ring's sibling strategy)
# ---------------------------------------------------------------------------


def _ulysses_body(q, k, v, *, causal: bool, axis_name: str = SEQ_AXIS):
    """Per-device body. q,k,v: [N, T_local, H, D] sequence shards.

    Two all_to_alls instead of T/T_local ppermutes: swap the sharded axis
    from sequence to heads (each device then holds ALL timesteps for H/p
    heads), run plain dense attention locally, and swap back. Cheaper in
    collective count than the ring when the full [T, T] block fits memory;
    the ring wins when T is too long for any single device to hold T x T.
    """
    qh = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    att = multi_head_attention(qh, kh, vh, causal=causal)
    return lax.all_to_all(att, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_attention_sharded(q, k, v, mesh: Mesh, *, causal: bool = False,
                              batch_axis: Optional[str] = None):
    """Exact full attention with the sequence dim sharded over mesh axis
    'seq' via head<->sequence all_to_alls (DeepSpeed-Ulysses strategy).
    q,k,v: [N, T, H, D]; T and H must both divide by the axis size.
    batch_axis: optional second mesh axis sharding the batch (DP x SP)."""
    n_dev = mesh.shape[SEQ_AXIS]
    t, h = q.shape[1], q.shape[2]
    if t % n_dev != 0:
        raise ValueError(f"sequence length {t} not divisible by {n_dev}")
    if h % n_dev != 0:
        raise ValueError(f"num heads {h} not divisible by {n_dev} devices "
                         "(Ulysses shards heads; use ring attention instead)")
    spec = P(batch_axis, SEQ_AXIS, None, None)
    fn = shard_map(
        partial(_ulysses_body, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Layer-zoo integration: MultiHeadAttention for [N, T, F] activations
# ---------------------------------------------------------------------------


def mha_apply(params, x, num_heads: int, *, causal: bool = False,
              mesh: Optional[Mesh] = None, key_mask=None):
    """x: [N, T, F] -> [N, T, F]; runs ring attention when a mesh with a
    'seq' axis is supplied, single-device attention otherwise. key_mask
    ([N, T] 0/1) excludes padded timesteps from attention (single-device
    path; the ring path shards full sequences)."""
    n, t, f = x.shape
    proj = params["Wq"].shape[1]
    head_dim = proj // num_heads

    def split(w):
        return (x @ w).reshape(n, t, num_heads, head_dim)

    q, k, v = split(params["Wq"]), split(params["Wk"]), split(params["Wv"])
    if mesh is not None and SEQ_AXIS in mesh.shape:
        # the mask shard rotates with its K/V block through the ring, so
        # padded timesteps are excluded exactly even across shards
        att = ring_attention_sharded(q, k, v, mesh, causal=causal,
                                     key_mask=key_mask)
    else:
        # single-device path: ONE dispatch policy (attention_auto) — flash
        # pallas kernel when on TPU and the shape fits VMEM (masked batches
        # ride the extended kernel's key bias), dense XLA otherwise
        from deeplearning4j_tpu.ops.pallas_attention import attention_auto

        att = attention_auto(q, k, v, causal=causal, key_mask=key_mask)
    return att.reshape(n, t, proj) @ params["Wo"]
