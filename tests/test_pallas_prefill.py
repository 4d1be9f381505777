"""The admission's attention kernel (ops/pallas_prefill.py) against the
plain by-blocks path of models/hybrid.attention_full, on the CPU under
Pallas's interpreter (ISSUE 38).

The model here has the routed-expert cell's heads (28 query heads of 128
over 4 KV heads: 7 a KV head) on a narrow stream (d 256), bf16 weights and
arena, as the configuration states (``attn_exact=False``). Both paths read
q, K and V in bf16, sum both products in float32 and round the
probabilities to bf16 before the value product; they differ in where a
block's edge falls, so in which running max a probability is rounded
against. ``TOL`` is one bf16 rounding (2^-8) of the largest output: where
one block is one chunk (to 384 positions) the two agree bit for bit, at
1,536 they read 7e-4 of it apart.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import hybrid
from deeplearning4j_tpu.ops import pallas_prefill as pp
from deeplearning4j_tpu.ops.pallas_kernels import pallas_disabled

TOL = 2 ** -8
HD = 128
# the served ladder of the routed-expert cell (ops/dispatch.bucket_size)
LADDER = (128, 192, 256, 384, 512, 768, 1024, 1536, 2048, 3072, 4096,
          6144, 8192)


def _cfg(window, attn_exact=False):
    return hybrid.HybridConfig(
        vocab_size=64, d_model=256, n_heads=28, n_kv_heads=4,
        attn_head_dim=HD, d_ff=16, layer_types=("attention",), max_len=8192,
        rope=(bool(window),), rope_theta=1.5e6, window=(window,),
        attn_exact=attn_exact, ffn="experts", ffn_act="relu", moe_experts=4,
        moe_top_k=2, tie_head=False, attention_multiplier=HD ** -0.5)


def _weights(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    w = lambda k, shape, gain: (jax.random.normal(k, shape) * gain
                                * shape[0] ** -0.5).astype(jnp.bfloat16)
    # q and k drawn wide, so that the softmax is sharp and the max matters
    return {"Wq": w(ks[0], (256, 28 * HD), 3.0),
            "Wk": w(ks[1], (256, 4 * HD), 3.0),
            "Wv": w(ks[2], (256, 4 * HD), 1.0),
            "Wo": w(ks[3], (28 * HD, 256), 1.0)}


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel engages as on the chip (Pallas on, bf16 products) and
    runs under the interpreter: the CPU has no Mosaic."""
    monkeypatch.setenv("DL4J_TPU_PALLAS", "force")
    monkeypatch.setattr(pp, "prefill_attention", functools.partial(
        pp.prefill_attention, interpret=True))


# (width, window): the three narrow widths and a 1.5x width above 1,024; a
# window whose band starts inside a key block; at 1,536 over a window of
# 700 the query block at 1,024 walks key block 0 (keys 0 .. 511), of which
# its last rows (1,212 .. 1,279) see nothing
@pytest.mark.parametrize("t, window", [
    (128, 0), (192, 0), (384, 0), (1536, 0), (384, 100), (1536, 700)],
    ids=["128", "192", "384", "1536", "384_window_100", "1536_window_700"])
def test_the_kernel_equals_the_plain_path(interpreted, t, window):
    cfg = _cfg(window)
    assert cfg.admit_attend(t) == "kernel"
    ap = _weights(t + window)
    u = jax.random.normal(jax.random.PRNGKey(t), (t, 256), jnp.float32)
    got, gk, gv = hybrid.attention_full(u, ap, cfg, jnp.bfloat16)
    with pallas_disabled():
        assert cfg.admit_attend(t) == "xla"
        want, wk, wv = hybrid.attention_full(u, ap, cfg, jnp.bfloat16)
    # K and V returned to the arena are the same either way
    np.testing.assert_array_equal(np.asarray(gk), np.asarray(wk))
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    got, want = np.asarray(got), np.asarray(want)
    assert 0.3 < np.abs(want).max() < 10
    np.testing.assert_allclose(got, want, atol=TOL * np.abs(want).max(),
                               rtol=0)


@pytest.mark.parametrize("window", [0, 100])
def test_a_prompts_rows_do_not_depend_on_the_padding_beyond_it(window):
    """A prompt of 300 tokens in a bucket of 384: its rows come out bit for
    bit the same whatever the 84 padded positions hold."""
    t, n = 384, 300
    ks = jax.random.split(jax.random.PRNGKey(window), 4)
    draw = lambda k, cols: jax.random.normal(k, (t, cols)).astype(
        jnp.bfloat16)
    q, k, v = draw(ks[0], 28 * HD), draw(ks[1], 4 * HD), draw(ks[2], 4 * HD)
    pad = lambda a: a.at[n:].set(
        jax.random.normal(ks[3], (t - n, a.shape[1])).astype(a.dtype) * 50)
    run = lambda q, k, v: np.asarray(pp.prefill_attention(
        q, k, v, head_dim=HD, scale=HD ** -0.5, window=window,
        interpret=True).astype(jnp.float32))
    a, b = run(q, k, v), run(pad(q), pad(k), pad(v))
    np.testing.assert_array_equal(a[:n], b[:n])
    assert not np.array_equal(a[n:], b[n:])


def _visible(t, window):
    at = np.arange(t)[:, None]
    key = np.arange(t)[None]
    see = key <= at
    if window:
        see &= key > at - window
    return see


@pytest.mark.parametrize("t, window", [(t, 0) for t in LADDER]
                         + [(t, 4096) for t in (6144, 8192)])
def test_the_steps_walk_each_visible_block_once_and_no_other(t, window):
    """Every (query, key) pair a row sees lies in exactly one step; no step
    is a block no row sees; a step is flagged for a mask exactly where some
    pair of its block is hidden; FIRST and LAST open and close each query
    block, in order."""
    bq, bk = pp.tiles(t, 7)
    assert pp.fits(t, 7, HD) and t % bq == 0 and t % bk == 0
    qb, kb, flags = pp.steps(t, bq, bk, window)
    see = _visible(t, window)
    covered = np.zeros((t // bq, t // bk), bool)
    for i, j, f in zip(qb, kb, flags):
        block = see[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
        assert block.any()
        assert bool(f & pp.MASKED) == (not block.all())
        assert not covered[i, j]
        covered[i, j] = True
    any_seen = see.reshape(t // bq, bq, t // bk, bk).any(axis=(1, 3))
    np.testing.assert_array_equal(covered, any_seen)
    assert list(qb) == sorted(qb)
    starts = np.flatnonzero(flags & pp.FIRST)
    ends = np.flatnonzero(flags & pp.LAST)
    assert list(starts) == [0] + list(ends[:-1] + 1)
    assert ends[-1] == len(qb) - 1


def test_the_kernel_engages_by_pallas_and_the_stated_precision(monkeypatch):
    """Every width of the ladder takes the kernel where Pallas is on and the
    products read bf16; float32 products (granite's ``attn_exact``) and
    Pallas off keep the plain path, at every width."""
    monkeypatch.setenv("DL4J_TPU_PALLAS", "force")
    for window in (0, 4096):
        assert {_cfg(window).admit_attend(t) for t in LADDER} == {"kernel"}
    assert {_cfg(0, attn_exact=True).admit_attend(t) for t in LADDER} \
        == {"xla"}
    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
    assert {_cfg(0).admit_attend(t) for t in LADDER} == {"xla"}
    # granite's heads of 64 fill no lane, nor does a width of 40 a tile
    assert not pp.fits(512, 4, 64) and not pp.fits(40, 7, HD)
    assert pp.name() == "prefill_attn" and pp.name(4096) == \
        "prefill_attn_w4096"
