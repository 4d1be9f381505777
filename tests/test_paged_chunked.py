"""The paged tick attends what is live (ISSUE 27): K and V read in the
arena's dtype, chunk by chunk up to the longest live lane, one program.

  * paged.chunked_attention against a dense masked-softmax oracle in
    float32: lanes at the chunk's edges and at max_len - 1, trash block
    filled with large values, f32 / bf16 / down-cast arenas;
  * a lane's logits are bit-equal alone and beside a lane four chunks
    longer (the trip count is the longest lane's, and a chunk wholly
    past a lane's position is an exact no-op);
  * the lowered tick holds no float32 array of the gathered window's
    size and no gather wider than one chunk;
  * both products read the gathered chunk as stored (ISSUE 35): in the
    lowered tick's loop the chunk is [S, chunk, Hkv*hd] and nothing else,
    a head is a column range of that row, its rows padded to whole
    sublane tiles, and a head's output keeps its bits whatever the other
    heads' columns hold;
  * the bound follows LIVE lanes: the release resets the lane's
    position, so the next tick's ``kv_read`` falls;
  * one tick program whatever the live lengths (no retrace);
  * a k = 2 scanned tick equals two k = 1 ticks across a chunk edge;
  * the arena is ONE buffer a leaf, [L, blocks+1, bt, H*hd], carried
    through the layer scan and addressed by row (ISSUE 29): a tick
    writes the rows (layer, block, offset) of its lanes and each
    layer's trash row and nothing else, a lane on a layer's last block
    does not reach the next layer's trash, and a run through export,
    import, admissions, ticks and a preemption is decode_step_slots'
    tokens, on one device and on the serving mesh.

Reference anchor: none in the reference (one record per route callback,
dl4j-streaming/.../routes/DL4jServeRouteBuilder.java); provenance is the
online softmax of ops/pallas_paged.py and the vLLM block table.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    init_params,
)
from deeplearning4j_tpu.obs import trace as obs_trace
from deeplearning4j_tpu.serving import paged

BT = 4                                   # block_tokens of every case here
CHUNK = paged.ATTN_CHUNK_COLS * BT       # tokens a pass
MAX_LEN = 6 * CHUNK                      # six chunks: room for "four longer"


def _cfg(**over):
    kw = dict(vocab_size=29, d_model=16, n_layers=2, n_heads=2, d_ff=32,
              max_len=MAX_LEN, use_flash=False)
    kw.update(over)
    return TransformerConfig(**kw)


def _dense_tables(lanes, m):
    """Lane i owns blocks 1 + i*m .. (i+1)*m: every position is backed, so
    the oracle can attend any ``pos``."""
    return (1 + np.arange(lanes * m, dtype=np.int32)).reshape(lanes, m)


def _oracle(q, ck, cv, tables, pos):
    """Dense masked softmax attention in float32 over the gathered window,
    with numpy: the parent's gather path, one lane at a time. Fewer KV
    heads than query heads (the arena's row says how many): KV head j
    serves query heads g*j .. g*j+g-1."""
    q, ck, cv = (np.asarray(a, np.float32) for a in (q, ck, cv))
    s, n_heads, hd = q.shape
    kv_heads = int(np.prod(ck.shape[2:])) // hd
    out = np.zeros((s, n_heads, hd), np.float32)
    for i in range(s):
        k = ck[tables[i]].reshape(-1, kv_heads, hd)[:pos[i] + 1]
        v = cv[tables[i]].reshape(-1, kv_heads, hd)[:pos[i] + 1]
        k, v = (np.repeat(a, n_heads // kv_heads, axis=1) for a in (k, v))
        sc = np.einsum("hd,thd->ht", q[i], k) / np.sqrt(hd)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("ht,thd->hd", p, v)
    return out


# ---------------------------------------------------------------------------
# (a) against the dense oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("q_dtype,kv_dtype", [
    (jnp.float32, jnp.float32),      # f32 model, f32 arena
    (jnp.bfloat16, jnp.bfloat16),    # the benchmark's: bf16 model and arena
    (jnp.float32, jnp.bfloat16),     # down-cast arena under an f32 model
], ids=["f32", "bf16", "downcast"])
@pytest.mark.parametrize("pos", [
    [0, 0, 0],
    [CHUNK - 1, CHUNK, MAX_LEN - 1],
    [0, CHUNK - 1, 3 * CHUNK + 5],
    [MAX_LEN - 1, 1, CHUNK],
], ids=["zero", "edges", "mixed", "full"])
def test_chunked_attention_equals_dense_oracle(q_dtype, kv_dtype, pos):
    lanes, n_heads, hd = 3, 2, 8
    m = MAX_LEN // BT
    rng = np.random.default_rng(11)
    tables = _dense_tables(lanes, m)
    shape = (lanes * m + 1, BT, n_heads, hd)
    ck = rng.normal(size=shape).astype(np.float32)
    cv = rng.normal(size=shape).astype(np.float32)
    ck[0] = cv[0] = 1e4           # trash: visible to nobody, whatever it holds
    # a lane's table ends where its blocks end; the tail is trash
    pos = np.asarray(pos, np.int32)
    for i in range(lanes):
        tables[i, pos[i] // BT + 1:] = 0
    q = jnp.asarray(rng.normal(size=(lanes, n_heads, hd)), q_dtype)
    ck, cv = jnp.asarray(ck, kv_dtype), jnp.asarray(cv, kv_dtype)
    got = jax.jit(paged.chunked_attention)(
        q, ck, cv, jnp.asarray(tables), jnp.asarray(pos))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got),
                               _oracle(q, ck, cv, tables, pos),
                               rtol=1e-6, atol=1e-6)


def test_exact_rows_sum_to_the_float32_value():
    """The three bfloat16 rows hold all 24 bits: probabilities are not
    rounded on their way into the value product."""
    rng = np.random.default_rng(3)
    x = np.concatenate([rng.random(4096), np.exp(-30 * rng.random(4096)),
                        [0.0, 1.0, 2.0 ** -100]]).astype(np.float32)
    rows = paged._exact_rows(jnp.asarray(x).reshape(1, 1, -1))
    assert rows.dtype == jnp.bfloat16 and rows.shape[2] == 3
    back = np.asarray(rows, np.float32).sum(axis=2).reshape(-1)
    assert np.array_equal(back, x)


# ---------------------------------------------------------------------------
# (b) a lane's bits do not depend on its co-residents
# ---------------------------------------------------------------------------


def _tick_inputs(cfg, lanes, pos, seed=0):
    params = init_params(cfg)
    m = cfg.max_len // BT
    hd = cfg.d_model // cfg.n_heads
    rng = np.random.default_rng(seed)
    shape = (cfg.n_layers, lanes * m + 1, BT, cfg.n_heads * hd)
    arena = {"k": jnp.asarray(rng.normal(size=shape), cfg.compute_dtype),
             "v": jnp.asarray(rng.normal(size=shape), cfg.compute_dtype)}
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, lanes), jnp.int32)
    return params, arena, tok, jnp.asarray(pos, jnp.int32), \
        jnp.asarray(_dense_tables(lanes, m))


def _pr27_chunked_attention(q, ck, cv, tables, pos):
    """paged.chunked_attention as PR 27 left it, kept here letter for
    letter: full multi-head attention only, scores over sqrt(hd), the
    heads a batch axis of both products. The oracle for the logits of a
    GPT-2-shaped model's tick, whatever paged.chunked_attention does to
    tell the heads apart (ISSUE 28, ISSUE 35)."""
    from jax import lax

    s, n_heads, hd = q.shape
    bt = ck.shape[1]
    chunk = paged._chunk_tokens(bt, tables.shape[1])
    c = chunk // bt
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % c)))
    scale = 1.0 / float(np.sqrt(hd))
    q_rows = paged._exact_rows(q).astype(ck.dtype)
    t_in = jnp.arange(chunk)[None, :]

    def rows_dot(spec, rows, gathered):
        return jnp.einsum(spec, rows, gathered,
                          precision=lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32).sum(axis=2)

    def fold(j, carry):
        m, l, acc = carry
        cols = lax.dynamic_slice_in_dim(tables, j * c, c, axis=1)
        with jax.named_scope("tick.gather_kv"):
            kg = ck[cols].reshape(s, chunk, n_heads, hd)
            vg = cv[cols].reshape(s, chunk, n_heads, hd)
        with jax.named_scope("tick.attend"):
            sc = rows_dot("nhrd,nthd->nhrt", q_rows, kg) * scale
            visible = j * chunk + t_in <= pos[:, None]
            sc = jnp.where(visible[:, None, :], sc, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + rows_dot(
                "nhrt,nthd->nhrd", paged._exact_rows(p).astype(cv.dtype), vg)
        return m_new, l, acc

    init = (jnp.full((s, n_heads), -jnp.inf, jnp.float32),
            jnp.zeros((s, n_heads), jnp.float32),
            jnp.zeros((s, n_heads, hd), jnp.float32))
    _, l, acc = lax.fori_loop(0, jnp.max(pos) // chunk + 1, fold, init)
    return acc / l[..., None]


@pytest.mark.parametrize("shape", [
    {},                                        # the file's tiny model
    # the serve cell's heads: 12 of 128 at d 1536, bfloat16 (one layer and a
    # narrow MLP: the attention is what is pinned)
    dict(d_model=1536, n_heads=12, n_layers=1, d_ff=64,
         dtype_policy="performance"),
], ids=["tiny", "590m-shaped"])
def test_lane_logits_bit_equal_alone_and_beside_a_longer_lane(shape,
                                                              monkeypatch):
    cfg = _cfg(**shape)
    short, long_ = CHUNK // 2, 4 * CHUNK + CHUNK // 2
    params, arena, tok, _, tables = _tick_inputs(cfg, 2, [0, 0])
    tick = lambda a, p: paged.paged_decode_step(
        params, a, tok, p, tables, cfg, attention="gather")[1]
    step = jax.jit(tick)
    # lane 1 dead at position 0: the loop runs one chunk; then live four
    # chunks further on: it runs five
    both = jnp.asarray([short, long_], jnp.int32)
    alone = np.asarray(step(arena, jnp.asarray([short, 0], jnp.int32)))
    beside = np.asarray(step(arena, both))
    assert np.array_equal(alone[0], beside[0])
    assert not np.array_equal(alone[1], beside[1])
    # and the tick's logits are those of PR 27's body, the oracle: the
    # heads are told apart by their columns and no longer by a batch axis
    # (ISSUE 35), so the program text differs and the float32 sums behind a
    # logit may round in another order
    monkeypatch.setattr(paged, "chunked_attention", _pr27_chunked_attention)
    was = jax.jit(lambda a, p: tick(a, p))
    np.testing.assert_allclose(np.asarray(was(arena, both)), beside,
                               rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# (c) nothing of the window's width in the lowered tick
# ---------------------------------------------------------------------------


def _sizes(dims):
    return int(np.prod([int(d) for d in dims.split("x") if d]))


def test_lowered_tick_holds_no_window_wide_array():
    # the benchmark's policy: bf16 compute, bf16 arena, f32 masters
    cfg = _cfg(dtype_policy="performance")
    lanes = 3
    params, arena, tok, pos, tables = _tick_inputs(cfg, lanes, [0] * lanes)
    assert arena["k"].dtype == jnp.bfloat16
    tick = paged._paged_tick_for(cfg, BT)
    keys = jnp.zeros((lanes, 2), jnp.uint32)
    temps = jnp.zeros((lanes,), jnp.float32)
    text = tick.lower(params, arena, tok, pos, tables, keys, temps).as_text()
    hd = cfg.d_model // cfg.n_heads
    window = lanes * cfg.max_len * cfg.n_heads * hd
    one_chunk = lanes * CHUNK * cfg.n_heads * hd
    f32 = [_sizes(dims) for dims in
           re.findall(r"tensor<((?:\d+x)+)f32>", text)]
    # no float32 array of the gathered window's size (the parent's upcast),
    # and none beyond one chunk but the largest master weight
    assert max(f32) < window
    assert max(f32) <= max(one_chunk, max(
        leaf.size for leaf in jax.tree.leaves(params)))
    # what the gathers of K and V return, in any dtype, is one chunk wide
    gathered = [_sizes(dims) for dims in re.findall(
        r"stablehlo\.gather.*-> tensor<((?:\d+x)+)\w+>", text)]
    assert one_chunk in gathered
    assert max(gathered) == one_chunk < window


# ---------------------------------------------------------------------------
# (c') both products read the gathered chunk as stored (ISSUE 35)
# ---------------------------------------------------------------------------


def _hybrid_tick_text(lanes, heads, kv_heads, hd):
    """The lowered tick of a hybrid model with one attention layer of the
    given heads (shapes only: nothing is computed)."""
    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.ops import memory as opsmem

    cfg = hybrid.HybridConfig(
        vocab_size=64, d_model=heads * hd, n_heads=heads,
        n_kv_heads=kv_heads, d_ff=32, layer_types=("mamba", "attention"),
        max_len=MAX_LEN, ssm_heads=2, ssm_head_dim=8, ssm_state=8)
    arg = jax.ShapeDtypeStruct
    params = jax.tree.map(lambda sh: arg(sh, jnp.bfloat16),
                          hybrid.param_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    needs = opsmem.cache_needs(cfg)
    kv = arg((lanes * (MAX_LEN // BT) + 1, BT, kv_heads * hd), jnp.bfloat16)
    arena = {"k": (kv,), "v": (kv,)}
    for leaf in needs.state:
        arena[leaf.name] = tuple(arg((lanes,) + leaf.shape, leaf.dtype)
                                 for _ in range(leaf.layers))
    tick = paged._paged_tick_for(cfg, BT)
    paged._PAGED_TICK_CACHE.pop((cfg, BT, "gather", 1), None)
    return tick.lower(
        params, arena, arg((lanes,), jnp.int32), arg((lanes,), jnp.int32),
        arg((lanes, MAX_LEN // BT), jnp.int32), arg((lanes, 2), jnp.uint32),
        arg((lanes,), jnp.float32)).as_text()


def _dense_tick_text(lanes, heads, hd):
    cfg = _cfg(d_model=heads * hd, n_heads=heads, n_layers=1, d_ff=64,
               dtype_policy="performance")
    params, arena, tok, pos, tables = _tick_inputs(cfg, lanes, [0] * lanes)
    tick = paged._paged_tick_for(cfg, BT)
    paged._PAGED_TICK_CACHE.pop((cfg, BT, "gather", 1), None)
    return tick.lower(params, arena, tok, pos, tables,
                      jnp.zeros((lanes, 2), jnp.uint32),
                      jnp.zeros((lanes,), jnp.float32)).as_text()


@pytest.mark.parametrize("heads,kv_heads,hd,rows",
                         [(12, 12, 128, 8), (32, 8, 64, 16)],
                         ids=["590m-heads", "granite-heads"])
def test_lowered_tick_reads_the_gathered_chunk_as_stored(heads, kv_heads, hd,
                                                         rows):
    """The serve cell's heads (12 of 128) and the hybrid cell's (32 over 8
    of 64), bfloat16: in the lowered tick the gathered chunk exists as
    [S, chunk, Hkv*hd] and in no other arrangement. No tensor names the
    head as an axis of it, nothing transposes it, and every product that
    reads K or V takes a KV head's own columns of that row, sliced where
    they lie (a product batched over the head made the chip's compiler
    re-lay each chunk before it: a third of the serve cell's device time,
    PERF.md section 6, PR 35)."""
    lanes = 3
    text = _dense_tick_text(lanes, heads, hd) if heads == kv_heads \
        else _hybrid_tick_text(lanes, heads, kv_heads, hd)
    width = kv_heads * hd
    row = f"{lanes}x{CHUNK}x{width}xbf16"
    assert f"tensor<{row}>" in text
    # the head is nowhere an axis of a chunk: not [S, chunk, Hkv, hd] and
    # not any order of those four
    for dims in re.findall(r"tensor<((?:\d+x)+)bf16>", text):
        shape = [int(d) for d in dims.split("x") if d]
        assert not (len(shape) >= 4 and int(np.prod(shape))
                    == lanes * CHUNK * width and hd in shape
                    and kv_heads in shape[:-1] and CHUNK in shape), dims
    # nothing transposes a chunk or a head's columns of it
    moved = [ln for ln in text.splitlines() if "stablehlo.transpose" in ln
             and (f"x{CHUNK}x{width}x" in ln or f"x{CHUNK}x{hd}xbf16" in ln)]
    assert not moved, moved
    # what the products read of K and V: a KV head's columns of the row as
    # stored, a static slice with the chunk's own leading axes
    cols = f"{lanes}x{CHUNK}x{hd}xbf16"
    slices = re.findall(
        rf"stablehlo\.slice .*\(tensor<{row}>\) -> tensor<{cols}>", text)
    assert len(slices) == 2 * kv_heads
    # against that head's rows: 3 a query head, padded to whole tiles of 8
    products = [ln for ln in text.splitlines()
                if "stablehlo.dot_general" in ln and f"tensor<{cols}>" in ln]
    assert len(products) == 2 * kv_heads
    assert all(f"tensor<{lanes}x{rows}x" in ln for ln in products)


@pytest.mark.parametrize("heads,kv_heads", [(12, 12), (32, 8), (2, 2),
                                            (16, 16), (6, 2)])
@pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_padded_rows_of_every_head_layout_equal_the_dense_oracle(
        heads, kv_heads, kv_dtype):
    """A KV head's rows in its products are 3 exact rows for each of its
    query heads, padded with zero rows to whole tiles of 8: 8 for full
    multi-head attention, 16 for four query heads a KV head, 16 for three
    (9 rows). The padding reaches no output, whatever the layout."""
    lanes, hd = 3, 8
    m = MAX_LEN // BT
    rng = np.random.default_rng(heads * 100 + kv_heads)
    tables = _dense_tables(lanes, m)
    shape = (lanes * m + 1, BT, kv_heads * hd)
    ck = rng.normal(size=shape).astype(np.float32)
    cv = rng.normal(size=shape).astype(np.float32)
    ck[0] = cv[0] = 1e4
    pos = np.asarray([CHUNK - 1, 2 * CHUNK + 3, 0], np.int32)
    for i in range(lanes):
        tables[i, pos[i] // BT + 1:] = 0
    q = jnp.asarray(rng.normal(size=(lanes, heads, hd)), jnp.float32)
    ck, cv = jnp.asarray(ck, kv_dtype), jnp.asarray(cv, kv_dtype)
    fn = jax.jit(paged.chunked_attention)
    got = fn(q, ck, cv, jnp.asarray(tables), jnp.asarray(pos))
    np.testing.assert_allclose(np.asarray(got),
                               _oracle(q, ck, cv, tables, pos),
                               rtol=1e-6, atol=1e-6)
    rows = {1: 8, 3: 16, 4: 16}[heads // kv_heads]
    text = fn.lower(q, ck, cv, jnp.asarray(tables),
                    jnp.asarray(pos)).as_text()
    products = [ln for ln in text.splitlines()
                if "stablehlo.dot_general" in ln]
    assert products and all(f"tensor<{lanes}x{rows}x" in ln
                            for ln in products), products[:2]


@pytest.mark.parametrize("heads,kv_heads,hd", [(12, 12, 128), (32, 8, 64),
                                               (2, 2, 8)])
def test_a_heads_output_keeps_its_bits_whatever_other_heads_columns_hold(
        heads, kv_heads, hd):
    """KV head j's products read columns j*hd .. j*hd+hd-1 of a row and no
    others: overwrite every OTHER head's K and V columns with other finite
    values (large ones) and the outputs of head j's query heads are the
    same bits."""
    lanes, bt, m = 2, 16, 16
    group = heads // kv_heads
    rng = np.random.default_rng(5)
    tables = jnp.asarray(
        (1 + np.arange(lanes * m, dtype=np.int32)).reshape(lanes, m))
    shape = (lanes * m + 1, bt, kv_heads * hd)
    ck = rng.normal(size=shape).astype(np.float32)
    cv = rng.normal(size=shape).astype(np.float32)
    pos = jnp.asarray([paged.ATTN_CHUNK_COLS * bt + 3, 17], jnp.int32)
    q = jnp.asarray(rng.normal(size=(lanes, heads, hd)), jnp.bfloat16)
    fn = jax.jit(paged.chunked_attention)
    base = np.asarray(fn(q, jnp.asarray(ck, jnp.bfloat16),
                         jnp.asarray(cv, jnp.bfloat16), tables, pos))
    for j in (0, kv_heads - 1):
        own = np.zeros(kv_heads * hd, bool)
        own[j * hd:(j + 1) * hd] = True
        k2 = np.where(own, ck, 300.0 * rng.normal(size=shape))
        v2 = np.where(own, cv, -77.0 + rng.normal(size=shape))
        got = np.asarray(fn(q, jnp.asarray(k2, jnp.bfloat16),
                            jnp.asarray(v2, jnp.bfloat16), tables, pos))
        mine = slice(j * group, (j + 1) * group)
        assert np.array_equal(got[:, mine], base[:, mine])
        others = np.ones(heads, bool)
        others[mine] = False
        assert not np.array_equal(got[:, others], base[:, others])


# ---------------------------------------------------------------------------
# (d) the bound follows live lanes
# ---------------------------------------------------------------------------


@pytest.fixture
def tracing():
    obs_trace.set_enabled(True)
    obs_trace.tracer().clear()
    yield obs_trace.tracer()
    obs_trace.set_enabled(None)


def _paged_ticks(tracer):
    return [s for s in tracer.spans("serve.batch")
            if s["attrs"].get("kind") == "decode.paged"]


def test_kv_read_falls_when_the_longest_lane_finishes(tracing):
    lm = TransformerLM(_cfg())
    dec = paged.PagedDecoder(lm, block_tokens=BT, lanes=2, n_blocks=80)
    try:
        rng = np.random.default_rng(0)
        long_p = rng.integers(0, 29, 3 * CHUNK + 2).astype(np.int32)
        short_p = rng.integers(0, 29, 3).astype(np.int32)
        f_long = dec.submit(long_p, 2, temperature=0.0)
        f_short = dec.submit(short_p, 12, temperature=0.0)
        f_long.result(timeout=120)
        f_short.result(timeout=120)
    finally:
        dec.stop()
    ticks = _paged_ticks(tracing)
    reads = [s["attrs"]["kv_read"] for s in ticks]
    lives = [s["attrs"]["kv_live"] for s in ticks]
    # beside the long lane both lanes loop four chunks; once it is released
    # (its position back at 0) the short lane's one chunk is all that is read
    assert reads[0] == 2 * 4 * CHUNK
    assert reads[-1] == 2 * CHUNK
    assert all(0 < live <= read for live, read in zip(lives, reads))
    # kv_live counts active lanes alone: the last ticks hold the short one
    assert lives[-1] == short_p.size + 12 - 1
    assert int(dec._pos.max()) == 0 and int(dec._tok.max()) == 0


def test_kv_read_tokens_is_the_programs_trip_count():
    m = MAX_LEN // BT
    for top, chunks in [(0, 1), (CHUNK - 1, 1), (CHUNK, 2),
                        (MAX_LEN - 1, MAX_LEN // CHUNK)]:
        assert paged.kv_read_tokens(top, BT, m) == chunks * CHUNK
    # a table narrower than the chunk is one pass of its own width
    assert paged.kv_read_tokens(5, BT, 2) == 2 * BT


# ---------------------------------------------------------------------------
# (e) one program whatever the live lengths
# ---------------------------------------------------------------------------


def test_one_tick_program_for_every_live_length():
    cfg = _cfg(vocab_size=31)          # a config no other test has compiled
    bt, blocks = BT, [1, 5, 40]
    assert 40 * bt <= cfg.max_len
    lanes = 2
    params, arena, tok, _, tables = _tick_inputs(cfg, lanes, [0, 0])
    before = dict(paged._PAGED_TICK_CACHE)
    tick = paged._paged_tick_for(cfg, bt)
    keys = jnp.zeros((lanes, 2), jnp.uint32)
    temps = jnp.zeros((lanes,), jnp.float32)
    for nb in blocks:
        pos = jnp.asarray([nb * bt - 1, 0], jnp.int32)
        arena, nxt, keys = tick(params, arena, tok, pos, tables, keys, temps)
        np.asarray(nxt)
    added = [k for k in paged._PAGED_TICK_CACHE if k not in before]
    assert added == [(cfg, bt, "gather", 1)]
    assert paged._paged_tick_for(cfg, bt) is tick
    assert tick._cache_size() == 1


# ---------------------------------------------------------------------------
# (f) k = 2 across a chunk edge
# ---------------------------------------------------------------------------


def test_k2_tick_equals_two_k1_ticks_across_a_chunk_edge():
    cfg = _cfg()
    lanes = 2
    # lane 0 steps from the last position of chunk 0 into chunk 1: the
    # scanned tick's second step loops one chunk further than its first
    start = [CHUNK - 1, 3]
    params, arena, tok, pos, tables = _tick_inputs(cfg, lanes, start)
    keys = jnp.asarray(np.arange(2 * lanes, dtype=np.uint32).reshape(lanes, 2))
    temps = jnp.asarray([0.0, 0.9], jnp.float32)
    copy = lambda a: jax.tree.map(jnp.copy, a)
    one, two = paged._paged_tick_for(cfg, BT, 1), \
        paged._paged_tick_for(cfg, BT, 2)
    a1, t1, k1 = one(params, copy(arena), tok, pos, tables, keys, temps)
    a1, t2, k1 = one(params, a1, t1[:, 0], pos + 1, tables, k1, temps)
    a2, toks, k2 = two(params, copy(arena), tok, pos, tables, keys, temps)
    assert np.array_equal(np.asarray(toks),
                          np.concatenate([np.asarray(t1), np.asarray(t2)], 1))
    assert np.array_equal(np.asarray(k1), np.asarray(k2))
    for leaf in ("k", "v"):
        assert np.array_equal(np.asarray(a1[leaf], np.float32),
                              np.asarray(a2[leaf], np.float32))


# ---------------------------------------------------------------------------
# (g) one buffer, addressed by row: a layer never writes into its neighbour
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["strict", "performance"],
                         ids=["f32", "bf16"])
def test_a_tick_writes_its_lanes_rows_of_every_layer_and_nothing_else(policy):
    cfg = _cfg(n_layers=3, dtype_policy=policy)
    n_blocks, lanes = 6, 3
    m = cfg.max_len // BT
    sentinel = 7.0
    shape = (cfg.n_layers, n_blocks + 1, BT, cfg.d_model)
    arena = {"k": jnp.full(shape, sentinel, cfg.compute_dtype),
             "v": jnp.full(shape, sentinel, cfg.compute_dtype)}
    # lane 0 writes the LAST block of every layer, the row before the next
    # layer's trash block in the carried buffer; lane 1 a block in the
    # middle; lane 2 is dead (table of trash, position 0)
    tables = np.zeros((lanes, m), np.int32)
    tables[0, :2] = [3, n_blocks]
    tables[1, :1] = [2]
    pos = np.asarray([BT + 1, 2, 0], np.int32)
    tok = jnp.asarray([5, 11, 0], jnp.int32)
    out, logits = jax.jit(
        lambda a: paged.paged_decode_step(
            init_params(cfg), a, tok, jnp.asarray(pos), jnp.asarray(tables),
            cfg, attention="gather"))(arena)
    assert np.isfinite(np.asarray(logits)).all()
    wrote = {(n_blocks, 1), (2, 2), (0, 0)}        # (block, offset) a layer
    want = {(l, b, t) for l in range(cfg.n_layers) for b, t in wrote}
    for leaf in ("k", "v"):
        assert out[leaf].shape == shape and out[leaf].dtype == arena[leaf].dtype
        moved = np.asarray(out[leaf], np.float32) != sentinel
        # every element of a written row is new, every other row untouched
        rows = {tuple(int(i) for i in idx)
                for idx in np.argwhere(moved.any(axis=-1))}
        assert rows == want, (leaf, sorted(rows ^ want))
        assert all(moved[l, b, t].all() for l, b, t in want)


def _decoder(kind, lm, **kw):
    if kind == "mesh":
        from deeplearning4j_tpu.serving.mesh import MeshPagedDecoder

        return MeshPagedDecoder(lm, devices=2, **kw)
    return paged.PagedDecoder(lm, **kw)


@pytest.mark.parametrize("kind", ["one-device", "mesh"])
def test_import_admit_tick_preempt_equals_the_fixed_slot_decoder(kind):
    """Every program that writes the arena, in one run: blocks exported by
    one decoder and imported by another (the handoff names the heads,
    [L, n, bt, H, hd]; the arena does not), an admission that hits them,
    ticks, and a preemption that recomputes: greedy tokens are those of
    serving/decode.decode_step_slots' fixed-slot pool, byte for byte."""
    from deeplearning4j_tpu.serving.decode import ContinuousDecoder

    cfg = _cfg(max_len=32)
    lm = TransformerLM(cfg)
    hd = cfg.d_model // cfg.n_heads
    shared = [2, 4, 6, 8, 10, 12, 14, 16, 3]       # two full blocks of 4
    prompts = (shared + [5], [1, 1, 1, 1], shared + [9, 7])
    d0 = ContinuousDecoder(lm, slots=1)
    try:
        bases = [d0.generate(np.asarray([p]), 18, temperature=0.0)[0]
                 for p in prompts]
    finally:
        d0.stop()
    src = paged.PagedDecoder(lm, block_tokens=BT, n_blocks=16)
    try:
        digests, kb, vb = src.export_prefix(prompts[0], 18)
    finally:
        src.stop()
    assert kb.shape == vb.shape == (cfg.n_layers, 2, BT, cfg.n_heads, hd)
    # 12 blocks of 4 cannot hold three sequences of 22 to 29 tokens
    dec = _decoder(kind, lm, block_tokens=BT, n_blocks=12)
    try:
        assert dec._arena["k"].shape == (cfg.n_layers, 13, BT, cfg.d_model)
        assert dec.import_prefix(digests, kb, vb) == 2
        futs = [dec.submit(list(p), 18, temperature=0.0) for p in prompts]
        outs = [f.result(timeout=240) for f in futs]
        assert dec.stats.prefix_hits >= 2
        assert dec.stats.preemptions >= 1
    finally:
        dec.stop()
    for base, out in zip(bases, outs):
        np.testing.assert_array_equal(base, out)


# ---------------------------------------------------------------------------
# (h) a lower bound a lane: the window layers of models/hybrid.py (ISSUE 37)
# ---------------------------------------------------------------------------


def _window_case(pos, window, kv_heads=2, n_heads=4, hd=8, seed=13,
                 freed=True):
    """Lanes at `pos` that each see the newest `window` positions; the
    blocks wholly behind a lane's window are trash in its table, as the
    decoder leaves them once it has let them go."""
    lanes, m = len(pos), MAX_LEN // BT
    rng = np.random.default_rng(seed)
    tables = _dense_tables(lanes, m)
    shape = (lanes * m + 1, BT, kv_heads * hd)
    ck = rng.normal(size=shape).astype(np.float32)
    cv = rng.normal(size=shape).astype(np.float32)
    ck[0] = cv[0] = 1e4
    pos = np.asarray(pos, np.int32)
    lo = np.maximum(pos - (window - 1), 0).astype(np.int32)
    for i in range(lanes):
        tables[i, pos[i] // BT + 1:] = 0
        if freed:
            tables[i, :lo[i] // BT] = 0
    q = rng.normal(size=(lanes, n_heads, hd)).astype(np.float32)
    return q, ck, cv, tables, pos, lo


def _window_oracle(q, ck, cv, tables, pos, lo):
    """The masked dense product, lane by lane: positions lo .. pos alone."""
    s, n_heads, hd = q.shape
    kv_heads = ck.shape[2] // hd
    out = np.zeros((s, n_heads, hd), np.float32)
    for i in range(s):
        k = ck[tables[i]].reshape(-1, kv_heads, hd)[lo[i]:pos[i] + 1]
        v = cv[tables[i]].reshape(-1, kv_heads, hd)[lo[i]:pos[i] + 1]
        k, v = (np.repeat(a, n_heads // kv_heads, axis=1) for a in (k, v))
        sc = np.einsum("hd,thd->ht", q[i], k) / np.sqrt(hd)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("ht,thd->hd", p, v)
    return out


@pytest.mark.parametrize("pos, window", [
    ([0, 5, CHUNK - 1], 16),                      # every lo in chunk 0
    ([CHUNK + 3, 3 * CHUNK + 5, 5], 16),          # lo in chunks 0, 2 and 0
    ([MAX_LEN - 1, 2 * CHUNK, CHUNK], CHUNK),     # a window of one chunk
    ([MAX_LEN - 1, 4 * CHUNK + 1, 0], 2 * CHUNK + 4),
], ids=["inside_chunk_0", "lo_in_different_chunks", "window_of_a_chunk",
        "window_over_chunk_edges"])
def test_chunked_attention_with_a_lower_bound_equals_the_masked_product(
        pos, window):
    q, ck, cv, tables, pos, lo = _window_case(pos, window)
    got = jax.jit(paged.chunked_attention)(
        jnp.asarray(q), jnp.asarray(ck), jnp.asarray(cv),
        jnp.asarray(tables), jnp.asarray(pos), lo=jnp.asarray(lo))
    assert np.isfinite(np.asarray(got)).all()    # no -inf met -inf
    np.testing.assert_allclose(
        np.asarray(got), _window_oracle(q, ck, cv, tables, pos, lo),
        rtol=1e-6, atol=1e-6)


def test_a_lower_bound_of_zero_is_bit_equal_to_none():
    """`lo = 0` walks the same chunks in the same order as no bound: the
    same bits. Without a bound the function is what it was, so the dense
    and the hybrid ticks' programs are too."""
    q, ck, cv, tables, pos, _lo = _window_case(
        [3, CHUNK + 7, 3 * CHUNK], MAX_LEN, freed=False)
    args = tuple(jnp.asarray(a) for a in (q, ck, cv, tables, pos))
    plain = jax.jit(paged.chunked_attention)(*args)
    bounded = jax.jit(paged.chunked_attention)(
        *args, lo=jnp.zeros((3,), jnp.int32))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(bounded))
    np.testing.assert_allclose(
        np.asarray(plain),
        _oracle(q, ck, cv, tables, pos), rtol=1e-6, atol=1e-6)
    text = jax.jit(paged.chunked_attention).lower(*args).as_text()
    assert "gather" in text and text.count("stablehlo.while") == 1


def test_a_window_lane_keeps_its_bits_beside_a_longer_lane():
    """A lane's passes start at its own first chunk and end past its own
    position with exact no-ops: alone or beside a lane four chunks longer
    (whose span of chunks sets the trip count), the same bits."""
    window = 16
    q, ck, cv, tables, pos, lo = _window_case(
        [CHUNK + 3, 5 * CHUNK + 9], window)
    both = jax.jit(paged.chunked_attention)(
        *(jnp.asarray(a) for a in (q, ck, cv, tables, pos)),
        lo=jnp.asarray(lo))
    alone = jax.jit(paged.chunked_attention)(
        *(jnp.asarray(a[:1]) for a in (q,)), jnp.asarray(ck),
        jnp.asarray(cv), jnp.asarray(tables[:1]), jnp.asarray(pos[:1]),
        lo=jnp.asarray(lo[:1]))
    np.testing.assert_array_equal(np.asarray(both[0]), np.asarray(alone[0]))


def test_kv_read_tokens_window_is_the_programs_trip_count():
    m = MAX_LEN // BT
    pos = np.array([CHUNK + 3, 3 * CHUNK + 5, 5], np.int64)
    # window 16: lane 0 spans chunks 0..1, lane 1 chunk 3 alone (its lo,
    # 3 * CHUNK - 10, lies in chunk 2: chunks 2..3), lane 2 chunk 0
    assert paged.kv_read_tokens_window(pos, 16, BT, m) == 2 * CHUNK
    assert paged.kv_read_tokens_window(pos, MAX_LEN, BT, m) \
        == paged.kv_read_tokens(int(pos.max()), BT, m)
    assert paged.kv_read_tokens_window(np.array([0, 0]), 16, BT, m) == CHUNK
