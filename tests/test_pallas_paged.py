"""Paged-decode attention kernel contracts (ISSUE 13, quick tier).

The rent the kernel pays before it may ever go default-on
(ops/pallas_paged.py — vLLM PagedAttention + flash online softmax over
the PR 11 block arena; no reference twin, provenance in the module
docstring):

  * value contract — ``paged_attention`` (interpret mode, requested
    here by argument) matches the serving gather path's masked softmax
    attention to f32 rounding, including trash-block invisibility
    (poisoned block 0 cannot move the output);
  * tick contract — ``paged_decode_step(attention='kernel')`` ==
    ``attention='gather'`` logits and arena to 1e-6, with layer 0's
    pre-attention scatter write BIT-identical (shared code);
  * transcript contract — the full prefix-sharing and preemption
    scenarios from tests/test_serving_paged.py produce byte-identical
    greedy transcripts with DL4J_TPU_PALLAS_PAGED=force vs =0 (the
    kernel slots under every scheduling behavior, not just a lone tick);
  * gate contract — knob 0 always gathers, force always kernels (within
    the VMEM budget), and '' auto stays on the gather fallback on this
    substrate (no real-chip measured-win row).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def tiny_lm(**over):
    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    kw = dict(vocab_size=29, d_model=16, n_layers=2, n_heads=2, d_ff=32,
              max_len=32, use_flash=False)
    kw.update(over)
    return TransformerLM(TransformerConfig(**kw))


@pytest.fixture
def interpreted_kernel(monkeypatch):
    """Interpret mode is this test's request, not the program's choice:
    the serving tick calls ``pallas_paged.paged_attention`` compiled, so
    the CPU tests substitute the interpreted kernel themselves."""
    import functools

    from deeplearning4j_tpu.ops import pallas_paged

    monkeypatch.setattr(
        pallas_paged, "paged_attention",
        functools.partial(pallas_paged.paged_attention, interpret=True))


def _arena_case(seed=0, s=4, h=2, hd=16, bt=4, m=4):
    """A hand-built block-table scene: lane ``i`` owns ``used[i]``
    distinct arena blocks (allocated from 1; 0 is trash), its table is
    padded out to ``m`` with trash entries, and ``pos`` sits mid-window
    so both fully-visible and partially-visible blocks occur."""
    rng = np.random.default_rng(seed)
    n_blocks = s * m
    q = jnp.asarray(rng.standard_normal((s, h, hd)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((n_blocks + 1, bt, h, hd)),
                     jnp.float32)
    cv = jnp.asarray(rng.standard_normal((n_blocks + 1, bt, h, hd)),
                     jnp.float32)
    perm = rng.permutation(np.arange(1, n_blocks + 1))
    tables = np.zeros((s, m), np.int32)
    pos = np.zeros((s,), np.int32)
    nxt = 0
    for i in range(s):
        used = 1 + (i % m)                # 1..m allocated blocks
        tables[i, :used] = perm[nxt:nxt + used]
        nxt += used
        pos[i] = used * bt - 1 - (i % bt)  # last block partially filled
    return q, ck, cv, jnp.asarray(tables), jnp.asarray(pos)


def _gather_oracle(q, ck, cv, tables, pos):
    """serving/paged.py's gather-path attention math, verbatim."""
    s, h, hd = q.shape
    bt = ck.shape[1]
    t_total = tables.shape[1] * bt
    kg = ck[tables].reshape(s, t_total, h, hd)
    vg = cv[tables].reshape(s, t_total, h, hd)
    sc = jnp.einsum("nhd,nthd->nht", q.astype(jnp.float32),
                    kg.astype(jnp.float32)) / float(np.sqrt(hd))
    visible = jnp.arange(t_total)[None, :] <= pos[:, None]
    sc = jnp.where(visible[:, None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("nht,nthd->nhd", p, vg.astype(jnp.float32))


# ---------------------------------------------------------------------------
# kernel value contracts (interpret mode — Mosaic only compiles on chip)
# ---------------------------------------------------------------------------


class TestPagedAttentionKernel:
    def test_kernel_matches_gather_oracle(self):
        from deeplearning4j_tpu.ops.pallas_paged import paged_attention

        q, ck, cv, tables, pos = _arena_case()
        out = paged_attention(q, ck, cv, tables, pos, interpret=True)
        ref = _gather_oracle(q, ck, cv, tables, pos)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-6

    def test_single_block_and_full_window_lanes(self):
        """Edge positions: a lane on its very first token (pos 0) and a
        lane with every table entry allocated and full (pos == T-1)."""
        from deeplearning4j_tpu.ops.pallas_paged import paged_attention

        q, ck, cv, tables, pos = _arena_case(seed=1)
        tables = tables.at[0].set(jnp.arange(1, tables.shape[1] + 1))
        pos = pos.at[0].set(tables.shape[1] * ck.shape[1] - 1)
        pos = pos.at[1].set(0)
        out = paged_attention(q, ck, cv, tables, pos, interpret=True)
        ref = _gather_oracle(q, ck, cv, tables, pos)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-6

    def test_trash_block_content_is_invisible(self):
        """Physical block 0 backs every unallocated table entry; the
        ``t <= pos`` mask must make its CONTENT unobservable — poisoning
        it with huge values cannot move any lane's output."""
        from deeplearning4j_tpu.ops.pallas_paged import paged_attention

        q, ck, cv, tables, pos = _arena_case(seed=2)
        clean = paged_attention(q, ck, cv, tables, pos, interpret=True)
        ck = ck.at[0].set(1e6)
        cv = cv.at[0].set(-1e6)
        poisoned = paged_attention(q, ck, cv, tables, pos, interpret=True)
        np.testing.assert_array_equal(np.asarray(clean),
                                      np.asarray(poisoned))


# ---------------------------------------------------------------------------
# tick contract: paged_decode_step kernel == gather
# ---------------------------------------------------------------------------


class TestPagedDecodeStep:
    def test_kernel_tick_equals_gather_tick(self, interpreted_kernel):
        from deeplearning4j_tpu.serving.paged import paged_decode_step

        lm = tiny_lm()
        cfg = lm.cfg
        bt, n_blocks = 8, 12
        s, m = 3, cfg.max_len // bt
        hd = cfg.d_model // cfg.n_heads
        rng = np.random.default_rng(7)
        shape = (cfg.n_layers, n_blocks + 1, bt, cfg.n_heads * hd)
        arena = {
            "k": jnp.asarray(rng.standard_normal(shape), cfg.compute_dtype),
            "v": jnp.asarray(rng.standard_normal(shape), cfg.compute_dtype),
        }
        tables = np.zeros((s, m), np.int32)
        perm = rng.permutation(np.arange(1, n_blocks + 1))
        nxt = 0
        pos = np.zeros((s,), np.int32)
        for i in range(s):
            used = 1 + i
            tables[i, :used] = perm[nxt:nxt + used]
            nxt += used
            pos[i] = used * bt - 2 - i
        tok = jnp.asarray([3, 11, 27], jnp.int32)
        tables = jnp.asarray(tables)
        pos = jnp.asarray(pos)

        a_g, logits_g = paged_decode_step(lm.params, arena, tok, pos,
                                          tables, cfg, attention="gather")
        a_k, logits_k = paged_decode_step(lm.params, arena, tok, pos,
                                          tables, cfg, attention="kernel")
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


# ---------------------------------------------------------------------------
# transcript contracts: full scheduling scenarios, kernel forced
# ---------------------------------------------------------------------------


class TestForcedKernelTranscripts:
    def _decode(self, lm, monkeypatch, knob, scenario):
        monkeypatch.setenv("DL4J_TPU_PALLAS_PAGED", knob)
        from deeplearning4j_tpu.serving.paged import PagedDecoder, \
            attention_path

        want = "kernel" if knob == "force" else "gather"
        assert attention_path(lm.cfg, 8) == want
        return scenario(PagedDecoder)

    def test_prefix_sharing_transcripts_identical(self, monkeypatch,
                                                  interpreted_kernel):
        """The tests/test_serving_paged.py prefix-sharing scenario —
        shared read tables, trash-pointed write tables, a third
        co-resident — replayed with the kernel forced: greedy
        transcripts byte-identical to the gather path, and the share
        still registers as prefix-cache hits."""
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

        base = self._decode(lm, monkeypatch, "0", scenario)
        forced = self._decode(lm, monkeypatch, "force", scenario)
        for b, f in zip(base, forced):
            np.testing.assert_array_equal(b, f)

    def test_preemption_transcripts_identical(self, monkeypatch,
                                              interpreted_kernel):
        """The block-starvation scenario (7 blocks cannot hold three
        23/24-token sequences): preemption + recompute-from-window must
        fire under BOTH paths and the transcripts must agree byte-wise
        — the kernel's mask honors a re-admitted lane's rebuilt table
        exactly like the gather."""
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

        base = self._decode(lm, monkeypatch, "0", scenario)
        forced = self._decode(lm, monkeypatch, "force", scenario)
        for b, f in zip(base, forced):
            np.testing.assert_array_equal(b, f)


# ---------------------------------------------------------------------------
# gate contract
# ---------------------------------------------------------------------------


class TestPagedGate:
    def test_knob_zero_disables(self, monkeypatch):
        from deeplearning4j_tpu.ops.pallas_paged import paged_kernel_enabled

        monkeypatch.setenv("DL4J_TPU_PALLAS_PAGED", "0")
        assert not paged_kernel_enabled(16, 128, 16)

    def test_force_respects_vmem_budget(self, monkeypatch):
        from deeplearning4j_tpu.ops.pallas_paged import (
            _VMEM_BUDGET_FLOATS,
            paged_kernel_enabled,
        )

        monkeypatch.setenv("DL4J_TPU_PALLAS_PAGED", "force")
        assert paged_kernel_enabled(2, 8, 8)
        # force bypasses the measured-win table, never the VMEM fit
        too_big = _VMEM_BUDGET_FLOATS  # 2 * bt * H * hd over budget
        assert not paged_kernel_enabled(too_big, 1, 1)

    def test_auto_stays_on_gather_without_chip_row(self, monkeypatch):
        """'' auto on this CPU substrate: no real-chip measured-win row
        for the paged group exists, so the tick must resolve to the XLA
        gather fallback (the default-off half of the rent contract)."""
        from deeplearning4j_tpu.serving.paged import attention_path

        monkeypatch.delenv("DL4J_TPU_PALLAS_PAGED", raising=False)
        assert attention_path(tiny_lm().cfg, 8) == "gather"

    def test_forced_kernel_is_compiled_not_interpreted(self, monkeypatch):
        """force on a CPU backend must FAIL (Mosaic does not compile
        there), never quietly run interpreted: the program may not
        choose interpret mode for itself."""
        from deeplearning4j_tpu.serving.paged import paged_decode_step

        monkeypatch.setenv("DL4J_TPU_PALLAS_PAGED", "force")
        lm = tiny_lm()
        cfg = lm.cfg
        hd = cfg.d_model // cfg.n_heads
        shape = (cfg.n_layers, 5, 8, cfg.n_heads * hd)
        arena = {"k": jnp.zeros(shape, cfg.compute_dtype),
                 "v": jnp.zeros(shape, cfg.compute_dtype)}
        with pytest.raises(ValueError, match="Only interpret mode"):
            jax.block_until_ready(paged_decode_step(
                lm.params, arena, jnp.zeros((1,), jnp.int32),
                jnp.zeros((1,), jnp.int32),
                jnp.zeros((1, cfg.max_len // 8), jnp.int32), cfg))
