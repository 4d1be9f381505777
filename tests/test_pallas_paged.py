"""The decode tick's attention kernel (ops/pallas_paged.py) and its gate.

The kernel runs under the interpreter here, by the test's request (the
program never chooses interpret mode); on the chip it compiles
(tests/test_chip_compile.py). Contracts:

  * value: ``decode_attention`` equals serving/paged.chunked_attention, the
    gather path and the kernel's oracle, at each serve cell's head shape
    (12/12 x 128, 28/4 x 128 with and without a window, 16/2 x 256,
    32/8 x 64), within 1e-6 on a float32 arena and on a bf16 one (float32
    rounding: a product operand rounded to bf16 fails it), over lanes at
    position 0, dead lanes on the trash block, a lane at the table's last
    column and window lanes whose lower bound crosses a chunk edge;
  * what is read: trash content is invisible, and a block past a lane's
    position is never read (poisoned with NaN it moves nothing);
  * tick: ``paged_decode_step`` and the hybrid tick with the kernel equal
    their gather ticks, the scatter before the attention bit for bit, and
    whole scheduling scenarios give byte-identical greedy transcripts;
  * set-up: one jitted tick serves every longest position with no new
    program;
  * the mesh: a device's share of the heads (serving/mesh.py) folds in the
    chunks of the whole row, so its heads' outputs are one device's bit for
    bit;
  * gate: the kernel where it takes the arena (a TPU, bf16, whole lanes),
    the gather elsewhere; the host's ``attention_path`` and the tick's
    ``attend`` and ``kv_read`` say which; compiled on a CPU it fails, never
    interprets.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def tiny_lm(**over):
    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    kw = dict(vocab_size=29, d_model=16, n_layers=2, n_heads=2, d_ff=32,
              max_len=32, use_flash=False)
    kw.update(over)
    return TransformerLM(TransformerConfig(**kw))


def _take_kernel(mp):
    """The tick takes the kernel as on the chip, run by the interpreter:
    the interpretation is this test's request, not the program's choice."""
    from deeplearning4j_tpu.ops import pallas_paged
    from deeplearning4j_tpu.serving import paged

    call = pallas_paged._decode_call
    mp.setattr(pallas_paged, "fits", lambda *a: True)
    mp.setattr(pallas_paged, "_decode_call",
               lambda *a, **k: call(*a, **dict(k, interpret=True)))
    mp.setattr(paged, "_PAGED_TICK_CACHE", {})


@pytest.fixture
def interpreted_kernel(monkeypatch):
    _take_kernel(monkeypatch)


def _scene(seed, s, kv_heads, hd, bt, m, dtype, n_heads, window=0):
    """Lanes over a shuffled arena: lane 0 at position 0, lane 1 dead (its
    table all trash, position 0), lane 2 at the table's last column, the
    rest mid-table; with a window, lanes long enough that their lower
    bound lies inside the table."""
    rng = np.random.default_rng(seed)
    n_blocks = s * m
    q = jnp.asarray(rng.standard_normal((s, n_heads, hd)), jnp.float32)
    shape = (n_blocks + 1, bt, kv_heads * hd)
    ck = jnp.asarray(rng.standard_normal(shape), dtype)
    cv = jnp.asarray(rng.standard_normal(shape), dtype)
    perm = rng.permutation(np.arange(1, n_blocks + 1))
    tables = np.zeros((s, m), np.int32)
    pos = np.zeros((s,), np.int32)
    nxt = 0
    for i in range(s):
        used = {0: 1, 1: 0, 2: m}.get(i, 1 + (5 * i) % m)
        tables[i, :used] = perm[nxt:nxt + used]
        nxt += used
        pos[i] = {0: 0, 1: 0, 2: m * bt - 1}.get(i, max(used * bt - 1 - i,
                                                      0))
    lo = None
    if window:
        lo = jnp.asarray(np.maximum(pos - (window - 1), 0), jnp.int32)
    return q, ck, cv, jnp.asarray(tables), jnp.asarray(pos), lo


def _attend(q, ck, cv, tables, pos, blocks, **kw):
    """decode_attention with the kernel taken, under the interpreter, with
    ``blocks`` table entries a chunk (the wave of copies set to that many
    blocks' bytes, so that small tables cross chunk edges; None: the chunk
    the row's bytes choose)."""
    from deeplearning4j_tpu.ops import pallas_paged

    with pytest.MonkeyPatch.context() as mp:
        _take_kernel(mp)
        if blocks is not None:
            mp.setattr(pallas_paged, "WAVE_BYTES",
                       blocks * ck[0].size * ck.dtype.itemsize)
        return pallas_paged.decode_attention(q, ck, cv, tables, pos, **kw)


# the kernel and the gather differ only in where the running max is taken:
# float32 rounding, on a float32 arena and on a bf16 one alike
TOL = 1e-6


# (query heads, KV heads, head size, window) of the serve cells: 590M,
# SmallThinker's global and window layers, Qwen3-Next, Granite
SHAPES = [(12, 12, 128, 0), (28, 4, 128, 0), (28, 4, 128, 4096),
          (16, 2, 256, 0), (32, 8, 64, 0)]


class TestPagedAttentionKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n_heads,kv_heads,hd,window", SHAPES)
    def test_kernel_matches_gather_oracle(self, n_heads, kv_heads, hd,
                                          window, dtype):
        from deeplearning4j_tpu.serving.paged import chunked_attention

        bt = 16
        # a window of 4,096 positions needs a table past it; chunks of 4
        # blocks put the window lanes' lower bounds across chunk edges
        m = 4096 // bt + 25 if window else 12
        q, ck, cv, tables, pos, lo = _scene(
            hd + kv_heads, 6, kv_heads, hd, bt, m, dtype, n_heads, window)
        if window:
            assert int(lo[2]) % (4 * bt) and int(lo[2]) > 0
        scale = 0.7 / float(np.sqrt(hd))
        ref = chunked_attention(q, ck, cv, tables, pos, scale=scale, lo=lo)
        out = _attend(q, ck, cv, tables, pos, 4, scale=scale, lo=lo)
        err = np.abs(np.asarray(out) - np.asarray(ref))
        assert err.max() < TOL, err.max()

    @pytest.mark.parametrize("operand", ["probabilities", "query"])
    def test_a_bf16_rounded_product_operand_fails_the_tolerance(
            self, operand, monkeypatch):
        """The control of the value test: the kernel with one float32
        operand of its products rounded to bf16 (the probabilities against
        V, or the query against K) in place of its three exact bf16 rows
        misses the oracle by far more than TOL on a bf16 arena."""
        from deeplearning4j_tpu.ops import pallas_paged
        from deeplearning4j_tpu.serving.paged import chunked_attention

        exact3 = pallas_paged._exact3
        rounded = jnp.bfloat16 if operand == "probabilities" else jnp.float32

        def one_row(x, dtype):
            if jnp.dtype(dtype) != jnp.dtype(rounded):
                return exact3(x, dtype)
            hi = x.astype(jnp.bfloat16).astype(jnp.float32)
            zero = jnp.zeros_like(hi)
            return jnp.concatenate([hi, zero, zero], axis=0).astype(dtype)

        q, ck, cv, tables, pos, _ = _scene(11, 6, 12, 128, 16, 12,
                                           jnp.bfloat16, 12)
        ref = np.asarray(chunked_attention(q, ck, cv, tables, pos))
        assert np.abs(np.asarray(_attend(q, ck, cv, tables, pos, 4))
                      - ref).max() < TOL
        raw = pallas_paged._decode_call.__wrapped__  # traced anew, uncached
        monkeypatch.setattr(pallas_paged, "_exact3", one_row)
        monkeypatch.setattr(pallas_paged, "_decode_call", raw)
        out = _attend(q, ck, cv, tables, pos, 4)
        assert np.abs(np.asarray(out) - ref).max() > 10 * TOL

    def test_single_block_and_full_window_lanes(self):
        """A lane on its very first token (pos 0) and a lane with every
        table entry allocated and full (pos == T-1), at the chunk the row's
        bytes choose and at one block a chunk."""
        from deeplearning4j_tpu.serving.paged import chunked_attention

        q, ck, cv, tables, pos, _ = _scene(1, 4, 2, 128, 16, 8, jnp.float32,
                                           4)
        assert int(pos[0]) == 0 and int(pos[2]) == 8 * 16 - 1
        ref = chunked_attention(q, ck, cv, tables, pos)
        for out in (_attend(q, ck, cv, tables, pos, None),
                    _attend(q, ck, cv, tables, pos, 1)):
            assert float(jnp.max(jnp.abs(out - ref))) < 1e-6

    def test_trash_block_content_is_invisible(self):
        """Physical block 0 backs every unallocated table entry; the mask
        must make its CONTENT unobservable to a lane that owns a block:
        poisoning it with huge values cannot move such a lane's output."""
        q, ck, cv, tables, pos, _ = _scene(2, 5, 2, 128, 16, 6, jnp.float32,
                                           4)
        owned = np.asarray(tables[:, 0]) != 0
        run = lambda ck, cv, tables, pos: _attend(q, ck, cv, tables, pos, 2)
        clean = run(ck, cv, tables, pos)
        poisoned = run(ck.at[0].set(1e6), cv.at[0].set(-1e6), tables, pos)
        np.testing.assert_array_equal(np.asarray(clean)[owned],
                                      np.asarray(poisoned)[owned])

    def test_no_lane_reads_past_its_position(self):
        """A table entry past a lane's last visible block is never copied:
        the block it names may hold anything, NaN among it, and no output
        moves, where a gather of whole chunks would read it (and its NaN
        values would meet a probability of exactly 0)."""
        q, ck, cv, tables, pos, _ = _scene(3, 4, 2, 128, 16, 8, jnp.float32,
                                           4)
        tables = np.asarray(tables).copy()
        pos = np.asarray(pos).copy()
        pos[3] = 20                        # two blocks visible of its five
        bad = int(tables[3, 4])
        assert bad and tables[3, 2] and pos[3] // 16 < 2
        run = lambda ck, cv: _attend(q, ck, cv, jnp.asarray(tables),
                                     jnp.asarray(pos), 8)
        clean = run(ck, cv)
        nan = run(ck.at[bad].set(jnp.nan), cv.at[bad].set(jnp.nan))
        np.testing.assert_array_equal(np.asarray(clean), np.asarray(nan))


# ---------------------------------------------------------------------------
# tick contract: the tick with the kernel == the tick with the gather
# ---------------------------------------------------------------------------


def _tick_scene(cfg, s, bt, n_blocks, seed=7):
    rng = np.random.default_rng(seed)
    hd = cfg.d_model // cfg.n_heads
    shape = (cfg.n_layers, n_blocks + 1, bt, cfg.n_heads * hd)
    arena = {
        "k": jnp.asarray(rng.standard_normal(shape), cfg.compute_dtype),
        "v": jnp.asarray(rng.standard_normal(shape), cfg.compute_dtype),
    }
    m = cfg.max_len // bt
    tables = np.zeros((s, m), np.int32)
    perm = rng.permutation(np.arange(1, n_blocks + 1))
    nxt = 0
    pos = np.zeros((s,), np.int32)
    for i in range(s):
        used = 1 + i
        tables[i, :used] = perm[nxt:nxt + used]
        nxt += used
        pos[i] = used * bt - 2 - i
    return arena, jnp.asarray(tables), jnp.asarray(pos)


class TestPagedDecodeStep:
    def test_kernel_tick_equals_gather_tick(self, monkeypatch):
        from deeplearning4j_tpu.serving.paged import paged_decode_step

        lm = tiny_lm()
        cfg = lm.cfg
        arena, tables, pos = _tick_scene(cfg, 3, 8, 12)
        tok = jnp.asarray([3, 11, 27], jnp.int32)
        step = lambda: paged_decode_step(lm.params, arena, tok, pos, tables,
                                         cfg)
        a_g, logits_g = step()
        with monkeypatch.context() as mp:
            _take_kernel(mp)
            a_k, logits_k = step()
        # layer 0's (block, offset) scatter write happens BEFORE any
        # attention runs: it must be BIT-identical between the paths;
        # deeper layers write values downstream of the previous layer's
        # attention and inherit its f32 rounding
        np.testing.assert_array_equal(np.asarray(a_g["k"][0]),
                                      np.asarray(a_k["k"][0]))
        np.testing.assert_array_equal(np.asarray(a_g["v"][0]),
                                      np.asarray(a_k["v"][0]))
        assert float(jnp.max(jnp.abs(a_g["k"] - a_k["k"]))) < 1e-6
        assert float(jnp.max(jnp.abs(a_g["v"] - a_k["v"]))) < 1e-6
        assert float(jnp.max(jnp.abs(logits_g - logits_k))) < 1e-6

    def test_hybrid_tick_with_window_layers_equals_gather_tick(
            self, monkeypatch):
        """The routed-expert model's tick (a global layer beside window
        layers, grouped heads, a table a KV group) with the kernel in every
        attention layer: the logits and the arena of the gather tick."""
        from deeplearning4j_tpu.models import hybrid
        from deeplearning4j_tpu.ops import memory as opsmem

        cfg = hybrid.HybridConfig(
            vocab_size=64, d_model=64, n_heads=4, n_kv_heads=2,
            attn_head_dim=16, d_ff=32, layer_types=("attention",) * 3,
            max_len=64, rope=(False, True, True), window=(0, 24, 24),
            tie_head=False)
        params = hybrid.init_params(cfg, jax.random.PRNGKey(0))
        needs = opsmem.cache_needs(cfg)
        bt, lanes, m = 8, 3, cfg.max_len // 8
        rng = np.random.default_rng(5)
        width = needs.kv_heads * needs.head_dim
        arena = {name: tuple(
            jnp.asarray(rng.standard_normal((lanes * m + 1, bt, width)),
                        jnp.float32) for _ in range(3))
            for name in ("k", "v")}
        tables = np.zeros((2, lanes, m), np.int32)
        perm = rng.permutation(np.arange(1, lanes * m + 1))
        tables[:, 0, :7] = perm[:7]
        tables[:, 2, :3] = perm[7:10]
        pos = jnp.asarray([53, 0, 17], jnp.int32)
        tok = jnp.asarray([5, 0, 9], jnp.int32)
        run = lambda: hybrid.paged_decode_step(params, arena, tok, pos,
                                               jnp.asarray(tables), cfg)
        a_g, logits_g = run()
        with monkeypatch.context() as mp:
            _take_kernel(mp)
            a_k, logits_k = run()
        assert float(jnp.max(jnp.abs(logits_g - logits_k))) < 1e-5
        for name in ("k", "v"):
            for g, k in zip(a_g[name], a_k[name]):
                assert float(jnp.max(jnp.abs(g - k))) < 1e-5


# ---------------------------------------------------------------------------
# transcript contracts: full scheduling scenarios, the kernel taken
# ---------------------------------------------------------------------------


class TestForcedKernelTranscripts:
    def _decode(self, monkeypatch, kernel, scenario):
        from deeplearning4j_tpu.serving import paged

        with monkeypatch.context() as mp:
            mp.setattr(paged, "_PAGED_TICK_CACHE", {})
            if kernel:
                _take_kernel(mp)
            return scenario(paged.PagedDecoder)

    def test_prefix_sharing_transcripts_identical(self, monkeypatch):
        """The tests/test_serving_paged.py prefix-sharing scenario —
        shared read tables, trash-pointed write tables, a third
        co-resident — replayed with the kernel: greedy transcripts
        byte-identical to the gather path, and the share still registers
        as prefix-cache hits."""
        lm = tiny_lm()
        shared = [2, 4, 6, 8, 10, 12, 14, 16, 3, 5]

        def scenario(PagedDecoder):
            d = PagedDecoder(lm, block_tokens=8, n_blocks=16)
            try:
                before = d.stats.prefix_hits
                f1 = d.submit(shared + [7], 5, temperature=0.0)
                f2 = d.submit(shared + [9], 5, temperature=0.0)
                f3 = d.submit([3, 3, 4], 8, temperature=0.0)
                outs = [f.result(timeout=120) for f in (f1, f2, f3)]
                assert d.stats.prefix_hits > before
                return outs
            finally:
                d.stop()

        base = self._decode(monkeypatch, False, scenario)
        forced = self._decode(monkeypatch, True, scenario)
        for b, f in zip(base, forced):
            np.testing.assert_array_equal(b, f)

    def test_preemption_transcripts_identical(self, monkeypatch):
        """The block-starvation scenario (7 blocks cannot hold three
        23/24-token sequences): preemption + recompute-from-window must
        fire under BOTH paths and the transcripts must agree byte-wise —
        the kernel honors a re-admitted lane's rebuilt table exactly like
        the gather."""
        lm = tiny_lm()
        prompts = ([2, 4, 6], [1, 1, 1, 1], [9, 8, 7])

        def scenario(PagedDecoder):
            d = PagedDecoder(lm, block_tokens=8, n_blocks=7)
            try:
                futs = [d.submit(list(p), 20, temperature=0.0)
                        for p in prompts]
                outs = [f.result(timeout=240) for f in futs]
                assert d.stats.preemptions >= 1
                return outs
            finally:
                d.stop()

        base = self._decode(monkeypatch, False, scenario)
        forced = self._decode(monkeypatch, True, scenario)
        for b, f in zip(base, forced):
            np.testing.assert_array_equal(b, f)


# ---------------------------------------------------------------------------
# set-up: one tick program for every live length
# ---------------------------------------------------------------------------


def test_one_kernel_tick_serves_every_longest_position(interpreted_kernel):
    """One jitted tick with the kernel serves ticks whose longest lane
    stands at 1, 9 and 31 positions (one chunk, several, the table's end)
    and builds no program after its first: the trip count is data, and
    nothing is keyed on the lanes' lengths."""
    from deeplearning4j_tpu.serving import paged
    from perfbench.compile_log import CompileLog

    lm = tiny_lm(vocab_size=23)          # a config no other test compiles
    cfg = lm.cfg
    arena, tables, _ = _tick_scene(cfg, 2, 8, 8)
    tick = paged._paged_tick_for(cfg, 8)
    tok = jnp.asarray([1, 2], jnp.int32)
    keys = jnp.zeros((2, 2), jnp.uint32)
    temps = jnp.zeros((2,), jnp.float32)
    with CompileLog() as log:
        for top in (1, 9, 31):
            pos = jnp.asarray([top, 0], jnp.int32)
            arena, nxt, keys = tick(lm.params, arena, tok, pos, tables, keys,
                                    temps)
            np.asarray(nxt)
    assert len(log.built) == 1, log.built
    assert tick._cache_size() == 1


# ---------------------------------------------------------------------------
# the mesh: a device's heads in the whole row's chunks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_heads,kv_heads", [(12, 12), (16, 4)])
def test_a_devices_heads_equal_one_devices_bit_for_bit(n_heads, kv_heads,
                                                      dtype):
    """The serving mesh attends over each device's head slice of the arena.
    With ``shards`` the slice folds in the chunks of the whole row, and its
    heads' outputs equal those of the whole arena on one device, bit for
    bit. Chunked by the slice's own bytes (shards 1, a quarter of the row)
    it folds four times as many blocks a chunk, and the outputs move."""
    from deeplearning4j_tpu.ops import pallas_paged

    d = 4
    q, ck, cv, tables, pos, _ = _scene(3, 6, kv_heads, 128, 16, 12, dtype,
                                       n_heads)
    chunks = []
    with pytest.MonkeyPatch.context() as mp:
        _take_kernel(mp)
        call = pallas_paged._decode_call
        mp.setattr(pallas_paged, "_decode_call",
                   lambda *a, **k: chunks.append(k["c"]) or call(*a, **k))
        mp.setattr(pallas_paged, "WAVE_BYTES",
                   2 * ck[0].size * ck.dtype.itemsize)
        whole = np.asarray(pallas_paged.decode_attention(q, ck, cv, tables,
                                                         pos))
        hl, w = n_heads // d, kv_heads * 128 // d

        def sharded(shards):
            return np.concatenate([np.asarray(pallas_paged.decode_attention(
                q[:, i * hl:(i + 1) * hl], ck[..., i * w:(i + 1) * w],
                cv[..., i * w:(i + 1) * w], tables, pos, shards=shards))
                for i in range(d)], axis=1)

        np.testing.assert_array_equal(sharded(d), whole)
        own = sharded(1)
    assert chunks == [2] * (1 + d) + [8] * d, chunks
    assert not np.array_equal(own, whole)


def test_the_mesh_tick_folds_in_the_single_devices_chunks():
    """serving/mesh.py's tick hands the kernel its device count, so the
    chunk it traces is the single-device tick's (a quarter of the row, on
    its own, would take four times as many blocks)."""
    from jax.sharding import PartitionSpec as P

    from deeplearning4j_tpu.ops import pallas_paged
    from deeplearning4j_tpu.serving import mesh as smesh
    from deeplearning4j_tpu.serving import paged

    lm = tiny_lm(d_model=64, n_heads=4, max_len=64)
    cfg = lm.cfg
    bt, lanes = 8, 3
    arena, tables, pos = _tick_scene(cfg, lanes, bt, 12)
    tok = jnp.zeros((lanes,), jnp.int32)
    chunks = []
    with pytest.MonkeyPatch.context() as mp:
        _take_kernel(mp)
        call = pallas_paged._decode_call
        mp.setattr(pallas_paged, "_decode_call",
                   lambda *a, **k: chunks.append(k["c"]) or call(*a, **k))
        mp.setattr(pallas_paged, "WAVE_BYTES",
                   2 * bt * cfg.d_model * jnp.dtype(cfg.compute_dtype).itemsize)
        jax.eval_shape(lambda a: paged.paged_decode_step(
            lm.params, a, tok, pos, tables, cfg), arena)
        one = list(chunks)
        chunks.clear()
        sharded = jax.shard_map(
            lambda p, a, t, ps, tb: smesh.mesh_paged_decode_step(
                p, a, t, ps, tb, cfg, 4),
            mesh=smesh.serving_mesh(4),
            in_specs=(P(), smesh.ARENA_SPEC, P(), P(), P()),
            out_specs=(smesh.ARENA_SPEC, P()), check_vma=False)
        jax.eval_shape(sharded, lm.params, arena, tok, pos, tables)
    assert one == [2] and chunks == one, (one, chunks)


# ---------------------------------------------------------------------------
# gate contract
# ---------------------------------------------------------------------------


class TestPagedGate:
    def test_kernel_takes_bf16_arenas_of_whole_lanes_on_a_tpu(
            self, monkeypatch):
        from deeplearning4j_tpu.ops import device, pallas_paged

        fits = pallas_paged.fits
        assert not fits(jnp.bfloat16, 128, 16)         # not a TPU
        monkeypatch.setattr(device, "on_tpu", lambda: True)
        for hd in (128, 256):
            assert fits(jnp.bfloat16, hd, 16)
        assert not fits(jnp.float32, 128, 16)          # the float32 arena
        assert not fits(jnp.bfloat16, 64, 16)          # heads of 64
        assert not fits(jnp.bfloat16, 128, 8)          # half a bf16 tile

    def test_chunk_adapts_on_the_rows_bytes(self):
        """Blocks a chunk from the bytes of a block: 16 of 590M's 48 KB, 32
        of the 16 KB of the other cells, never past the table; and each
        cell's double-buffered K and V fit the 16 MiB of scoped VMEM with
        room for the products' operands."""
        from deeplearning4j_tpu.ops.pallas_paged import chunk_blocks

        assert chunk_blocks(16 * 1536 * 2, 128) == 16
        assert chunk_blocks(16 * 512 * 2, 512) == 32
        assert chunk_blocks(16 * 512 * 2, 1024) == 32
        assert chunk_blocks(8 * 16 * 4, 4) == 4
        assert chunk_blocks(1 << 30, 128) == 1
        for block, m in ((16 * 1536 * 2, 128), (16 * 512 * 2, 1024)):
            assert 4 * chunk_blocks(block, m) * block <= 8 << 20

    def test_auto_stays_on_gather_without_chip_row(self):
        """On this CPU substrate the host's view and the tick both take the
        gather: no kernel is chosen off a TPU, whatever the shapes."""
        from deeplearning4j_tpu.serving import paged

        assert paged.attention_path(tiny_lm().cfg, 8) == "gather"
        bf16 = tiny_lm(d_model=256, n_heads=2, dtype_policy="performance")
        assert paged.attention_path(bf16.cfg, 16) == "gather"

    def test_forced_kernel_is_compiled_not_interpreted(self, monkeypatch):
        """The kernel taken on a CPU backend must FAIL (Mosaic does not
        compile there), never quietly run interpreted: the program may
        not choose interpret mode for itself."""
        from deeplearning4j_tpu.ops import pallas_paged
        from deeplearning4j_tpu.serving import paged

        monkeypatch.setattr(pallas_paged, "fits", lambda *a: True)
        lm = tiny_lm()
        cfg = lm.cfg
        hd = cfg.d_model // cfg.n_heads
        shape = (cfg.n_layers, 5, 8, cfg.n_heads * hd)
        arena = {"k": jnp.zeros(shape, cfg.compute_dtype),
                 "v": jnp.zeros(shape, cfg.compute_dtype)}
        with pytest.raises(ValueError, match="Only interpret mode"):
            jax.block_until_ready(paged.paged_decode_step(
                lm.params, arena, jnp.zeros((1,), jnp.int32),
                jnp.zeros((1,), jnp.int32),
                jnp.zeros((1, cfg.max_len // 8), jnp.int32), cfg))

    def test_tick_span_says_which_attention_and_what_it_reads(
            self, monkeypatch):
        """``attend`` on ``serve.batch``, and ``kv_read`` by the path: the
        gather reads every lane to the longest one's chunk, the kernel each
        lane's own blocks (a dead lane one), a window layer's from the block
        of its lower bound."""
        from deeplearning4j_tpu.serving import paged

        lm = tiny_lm(max_len=64)
        d = paged.PagedDecoder(lm, block_tokens=8, n_blocks=24, lanes=3)
        try:
            d._pos[:] = [40, 0, 9]
            gather = d._kv_counts([0, 2], 1)
            assert gather["attend"] == "gather"
            assert gather["kv_live"] == 41 + 10
            assert gather["kv_read"] == 3 * 64      # one chunk of 8 blocks
            d.attend = "kernel"
            own = d._kv_counts([0, 2], 1)
            assert own == {"attend": "kernel", "kv_live": 51,
                           "kv_read": (6 + 1 + 2) * 8}
            two = d._kv_counts([0, 2], 2)
            assert two["kv_read"] == (6 + 1 + 2) * 8 * 2
        finally:
            d.stop()
        pos = np.asarray([40, 0, 9])
        assert paged.kv_read_tokens_own(pos, 0, 8) == (6 + 1 + 2) * 8
        # a window of 16: lane 0 sees 25 .. 40, blocks 3 .. 5
        assert paged.kv_read_tokens_own(pos, 16, 8) == (3 + 1 + 2) * 8
