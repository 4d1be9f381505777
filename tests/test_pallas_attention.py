"""Flash-attention pallas kernel == dense attention (interpret mode on CPU).

Same strategy as tests/test_pallas.py for the LSTM kernel: the kernel runs
under interpret=True on the CPU mesh and must reproduce the dense XLA
attention bit-for-bit-ish (f32 accumulation in both paths)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.pallas_attention import (
    dense_attention,
    flash_attention,
    flash_fits,
)


def _qkv(n=2, t=256, h=2, d=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return [
        jnp.asarray(rng.standard_normal((n, t, h, d)), dtype)
        for _ in range(3)
    ]


def _dense_nthd(q, k, v, causal):
    return dense_attention(q, k, v, causal=causal)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = _dense_nthd(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_multiple_k_blocks():
    q, k, v = _qkv(t=512, d=32)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = _dense_nthd(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_bf16_io():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = _dense_nthd(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2)


def test_flash_gradients_match_dense():
    # t=256 = two K blocks: exercises the lax.scan accumulation, the
    # cross-block causal masking, and the dK/dV unstack in _flash_bwd
    q, k, v = _qkv(t=256, d=32)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_nthd(q, k, v, True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   err_msg=f"grad d{name}")


# T against the forward's tiles: 128 and 384 and 640 take tiles of 128,
# 1,024 the largest (several q blocks, several key tiles below the diagonal)
FWD_T = [128, 384, 640, 1024]


def _rows_qkv(t, dtype, seed=0, rows=2, d=128):
    """[rows, T, D] q, k, v drawn in float64 and rounded to `dtype`."""
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((rows, t, d)), dtype)
            for _ in range(3)]


def _scores_f32(q, k, causal):
    """The scaled float32 scores of exact products of q and k, masked."""
    t, d = q.shape[1], q.shape[2]
    s = jnp.einsum("bqd,bkd->bqk", q, k, preferred_element_type=jnp.float32,
                   precision="highest") / np.float32(np.sqrt(d))
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    return s


def _dense_bf16(q, k, v, causal):
    """Dense attention at the configurations' precision: float32 scores of
    the bf16 products, float32 softmax, probabilities rounded to bf16, the
    value product summed in float32, the output rounded to bf16.
    (_dense_reference at bf16 also rounds the scores to bf16, which moves
    its output by up to two of the output's ulps from exact.)"""
    p = jax.nn.softmax(_scores_f32(q, k, causal), axis=-1).astype(v.dtype)
    return jnp.einsum("bqk,bkd->bqd", p, v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", FWD_T)
def test_flash_forward_f32_matches_dense_by_t(t, causal):
    """float32 inputs keep float32 products: the dense oracle to 2e-5."""
    from deeplearning4j_tpu.ops.pallas_attention import (
        _dense_reference,
        _flash_raw,
    )

    q, k, v = _rows_qkv(t, jnp.float32)
    o, _ = _flash_raw(q, k, v, causal=causal, interpret=True)
    assert o.dtype == jnp.float32
    ref = _dense_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", FWD_T)
def test_flash_forward_bf16_within_an_ulp_by_t(t, causal):
    """bf16 inputs: bf16 products summed in float32, bf16 probabilities.
    The output lies within one bf16 ulp (at its largest magnitude) of the
    dense attention at the same precision."""
    from deeplearning4j_tpu.ops.pallas_attention import _flash_raw

    q, k, v = _rows_qkv(t, jnp.bfloat16, seed=t)
    o, _ = _flash_raw(q, k, v, causal=causal, interpret=True)
    assert o.dtype == jnp.bfloat16
    ref = np.asarray(_dense_bf16(q, k, v, causal), np.float32)
    ulp = 2.0 ** (np.floor(np.log2(np.abs(ref).max())) - 7)
    assert np.abs(np.asarray(o, np.float32) - ref).max() <= ulp


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", FWD_T)
def test_flash_forward_lse_by_t(t, causal, dtype):
    """The log-sum-exp the backward reads: a float32 logsumexp of the
    scaled, masked scores to 1e-5, on every one of its 8 sublanes."""
    from deeplearning4j_tpu.ops.pallas_attention import _flash_raw

    q, k, v = _rows_qkv(t, dtype, seed=t + 1)
    _, lse = _flash_raw(q, k, v, causal=causal, interpret=True)
    assert lse.dtype == jnp.float32 and lse.shape == (2, 8, t)
    ref = jax.scipy.special.logsumexp(_scores_f32(q, k, causal), axis=-1)
    for row in range(8):
        np.testing.assert_allclose(np.asarray(lse[:, row]), np.asarray(ref),
                                   atol=1e-5, rtol=0)


@pytest.mark.parametrize("t", [640, 1024])
def test_flash_gradients_match_dense_by_t(t):
    """The unchanged blocked backward over the new forward's lse: the
    dense gradients at the existing tolerance."""
    q, k, v = _qkv(n=1, t=t, d=32)
    loss_flash = lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True, interpret=True) ** 2)
    loss_dense = lambda q, k, v: jnp.sum(_dense_nthd(q, k, v, True) ** 2)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   err_msg=f"grad d{name}")


@pytest.mark.parametrize("t,tiles", [(128, (128, 128)), (384, (128, 128)),
                                     (640, (128, 128)), (1024, (512, 512)),
                                     (1536, (512, 512)), (2048, (512, 512))])
def test_forward_tiles_follow_t(t, tiles):
    from deeplearning4j_tpu.ops.pallas_attention import _fwd_tiles

    assert _fwd_tiles(t) == tiles


def test_fits_gate():
    assert flash_fits(1024, 64)
    assert not flash_fits(1000, 64)       # not a block multiple
    assert not flash_fits(65536, 128)     # k/v would blow VMEM


def test_attention_auto_dense_fallback():
    """Off-TPU (pallas disabled) attention_auto must take the dense path and
    still be correct."""
    from deeplearning4j_tpu.ops.pallas_attention import attention_auto

    q, k, v = _qkv(t=64)  # 64 not a block multiple -> dense even if enabled
    out = attention_auto(q, k, v, causal=True)
    ref = _dense_nthd(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# Extended kernel: key padding mask + traced visibility offset
# ---------------------------------------------------------------------------


def test_ext_masked_matches_dense_masked():
    from deeplearning4j_tpu.ops.pallas_attention import (
        _dense_masked,
        flash_attention_masked,
    )

    q, k, v = _qkv()
    rng = np.random.default_rng(3)
    km = rng.random((2, 256)) > 0.3
    for causal in (False, True):
        out = flash_attention_masked(q, k, v, km, causal=causal,
                                     interpret=True)
        ref = _dense_masked(q, k, v, km, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)


def test_ext_offset_visibility():
    """offset generalizes causality to shards: off=0 == causal, off>=T ==
    full, off<=-T == nothing visible (zero output)."""
    from deeplearning4j_tpu.ops.pallas_attention import (
        flash_attention_block,
    )

    rng = np.random.default_rng(0)
    b, t, d = 4, 256, 32
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, d)), jnp.float32)
               for _ in range(3))
    from deeplearning4j_tpu.ops.pallas_attention import _dense_reference

    o0, _ = flash_attention_block(q, k, v, offset=0, interpret=True)
    np.testing.assert_allclose(
        np.asarray(o0), np.asarray(_dense_reference(q, k, v, causal=True)),
        atol=2e-5)
    of, _ = flash_attention_block(q, k, v, offset=t, interpret=True)
    np.testing.assert_allclose(
        np.asarray(of), np.asarray(_dense_reference(q, k, v, causal=False)),
        atol=2e-5)
    oh, _ = flash_attention_block(q, k, v, offset=-t, interpret=True)
    assert float(jnp.max(jnp.abs(oh))) == 0.0


def test_ext_gradients_include_lse_cotangent():
    """Gradients through BOTH outputs (o and lse) match the dense oracle —
    the lse cotangent is what ring combination differentiates through."""
    from deeplearning4j_tpu.ops.pallas_attention import (
        flash_attention_block,
    )

    rng = np.random.default_rng(1)
    b, t, d = 2, 256, 32
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, d)), jnp.float32)
               for _ in range(3))

    def f(q, k, v):
        o, lse = flash_attention_block(q, k, v, offset=0, interpret=True)
        return (o ** 2).mean() + 0.01 * lse.mean()

    def f_ref(q, k, v):
        s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(d)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        o = jnp.einsum("bqk,bkd->bqd", p, v)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        return (o ** 2).mean() + 0.01 * lse.mean()

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-5,
                                   err_msg=f"d{name}")


def test_attention_auto_masked_dispatch():
    """attention_auto with a key_mask must agree between its two backends
    (ext kernel vs dense fallback)."""
    from deeplearning4j_tpu.ops.pallas_attention import (
        _dense_masked,
        attention_auto,
    )

    q, k, v = _qkv()
    rng = np.random.default_rng(5)
    km = rng.random((2, 256)) > 0.4
    out = attention_auto(q, k, v, causal=True, key_mask=km)
    ref = _dense_masked(q, k, v, km, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
