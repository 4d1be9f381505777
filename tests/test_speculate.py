"""Decode amortization (ISSUE 16): multi-token ticks + self-speculative
decoding.

Two ways to pay the fixed per-dispatch overhead less often, both bound
by the same contract — the committed token stream is BYTE-IDENTICAL to
what k=1 ticking produces:

  * k-scanned ticks (serving/decode._tick_for(k) and the paged twin):
    the scan body IS the k=1 body, so a k-tick equals k single ticks
    across the whole PR 11 contract matrix (prefix sharing, preemption,
    crash eviction, streaming order) — the worker's adaptive drop to
    k=1 keeps admission/eviction/SLO semantics per-token;
  * speculative rounds (serving/speculate.SpeculativeDecoder): the int8
    or truncated-layer self-draft proposes, the target verifies k+1
    positions in one dispatch, and greedy acceptance commits only
    tokens the target's own argmax endorses — equal to target-only
    greedy even when chaos forces every proposal to reject.

Reference anchor: the reference decodes one token per model call
(dl4j-streaming/.../routes/DL4jServeRouteBuilder.java); provenance for
the techniques is Leviathan et al. 2023 via serving/speculate.py's
module docstring.
"""

import os
import re
import time

import numpy as np
import pytest

from deeplearning4j_tpu.ops import env
from deeplearning4j_tpu.ops import lowprec
from deeplearning4j_tpu.resilience import (
    InjectedServingFault,
    ServingChaos,
    ServingChaosConfig,
    SpecChaos,
    SpecChaosConfig,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_lm(**over):
    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    kw = dict(vocab_size=29, d_model=16, n_layers=2, n_heads=2, d_ff=32,
              max_len=32, use_flash=False)
    kw.update(over)
    return TransformerLM(TransformerConfig(**kw))


PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]


def run_pool(dec, n_new=10, temps=(0.0, 0.0, 0.0), seed=11, stream=True):
    """Submit PROMPTS concurrently (with per-token streaming callbacks on
    the paged pool); returns (transcripts, per-request streamed tokens)."""
    streams = [[] for _ in PROMPTS]
    try:
        futs = []
        for i, (p, t) in enumerate(zip(PROMPTS, temps)):
            kw = {"on_token": streams[i].append} if stream else {}
            futs.append(dec.submit(p, n_new, temperature=t, seed=seed, **kw))
        outs = [f.result(timeout=240).tolist() for f in futs]
    finally:
        dec.stop()
    return outs, streams


# ---------------------------------------------------------------------------
# k-tick == k x 1-tick byte-identity
# ---------------------------------------------------------------------------


class TestTickIdentity:
    def test_fixed_slot_k_tick(self):
        """ContinuousDecoder at tick_k=4 == tick_k=1 byte-for-byte on a
        mixed greedy/sampled pool, in fewer dispatches."""
        from deeplearning4j_tpu.serving.decode import ContinuousDecoder

        lm = tiny_lm()
        d1 = ContinuousDecoder(lm, slots=3, tick_k=1)
        o1, _ = run_pool(d1, temps=(0.0, 0.8, 0.0), stream=False)
        dk = ContinuousDecoder(lm, slots=3, tick_k=4)
        ok, _ = run_pool(dk, temps=(0.0, 0.8, 0.0), stream=False)
        assert o1 == ok
        assert dk.dispatch_stats.decode_ticks < d1.dispatch_stats.decode_ticks
        assert dk.dispatch_stats.decode_tokens == \
            d1.dispatch_stats.decode_tokens

    def test_paged_k_tick_with_prefix_sharing(self):
        """Paged k-tick identity while co-residents physically share
        prefix blocks (the PR 11 independence matrix at k>1)."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        shared = [2, 4, 6, 8, 10, 12, 14, 16, 3, 5]
        results = []
        for k in (1, 4):
            d = PagedDecoder(lm, block_tokens=8, n_blocks=16, tick_k=k)
            try:
                f1 = d.submit(shared + [7], 5, temperature=0.0)
                f2 = d.submit(shared + [9], 5, temperature=0.0)
                results.append((f1.result(timeout=120).tolist(),
                                f2.result(timeout=120).tolist(),
                                d.stats.prefix_hits > 0))
            finally:
                d.stop()
        assert results[0] == results[1]
        assert results[0][2]  # the share actually registered

    def test_paged_k_tick_under_preemption(self):
        """A starved arena preempts mid-flight at k=4 exactly as it
        would at k=1: transcripts stay byte-equal and the preempted
        sequence replays nothing."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        outs = {}
        for k in (1, 4):
            # 7 blocks * 8 tokens cannot hold three ~24-token sequences
            # at once: growth must preempt (test_serving_paged.py idiom)
            d = PagedDecoder(lm, lanes=3, block_tokens=8, n_blocks=7,
                             tick_k=k)
            try:
                futs = [d.submit(p, 20, temperature=0.7, seed=3)
                        for p in PROMPTS]
                outs[k] = [f.result(timeout=240).tolist() for f in futs]
                preempted = d.stats.preemptions
            finally:
                d.stop()
        assert outs[1] == outs[4]
        assert preempted > 0  # the k=4 run actually exercised the path

    def test_paged_k_tick_crash_eviction(self):
        """A chaos-crashed admission under k=4 fails only its own
        future; the co-resident's stream equals its solo baseline."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        d0 = PagedDecoder(lm, block_tokens=8, n_blocks=16, tick_k=4)
        try:
            solo = d0.generate(np.asarray([[1, 5, 2, 9]]), 8,
                               temperature=0.0)[0]
        finally:
            d0.stop()
        chaos = ServingChaos(ServingChaosConfig(admit_raise_at=2))
        d = PagedDecoder(lm, block_tokens=8, n_blocks=16, tick_k=4,
                         chaos=chaos)
        try:
            ok_fut = d.submit([1, 5, 2, 9], 8, temperature=0.0)
            time.sleep(0.05)
            crash_fut = d.submit([3, 3, 4], 6, temperature=0.0)
            with pytest.raises(InjectedServingFault):
                crash_fut.result(timeout=60)
            np.testing.assert_array_equal(solo, ok_fut.result(timeout=120))
        finally:
            d.stop()

    def test_tokens_per_dispatch_ledger(self):
        """dispatch_stats grows decode_ticks/decode_tokens and derives
        tokens_per_dispatch — and the decoder registered the ledger with
        the obs registry (the scrape surface)."""
        from deeplearning4j_tpu.obs.registry import default_registry
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        d = PagedDecoder(lm, block_tokens=8, n_blocks=16, tick_k=4)
        try:
            d.generate(np.asarray([[1, 5, 2, 9]]), 8, temperature=0.0)
            snap = d.dispatch_stats.snapshot()
            assert snap["decode_ticks"] > 0
            assert snap["decode_tokens"] == 8
            assert snap["tokens_per_dispatch"] == pytest.approx(
                snap["decode_tokens"] / snap["decode_ticks"])
            samples = default_registry().collect_ledger_samples()
            assert any(name == "dl4j_dispatch_decode_ticks"
                       for name, _, _ in samples)
        finally:
            d.stop()


# ---------------------------------------------------------------------------
# speculative greedy == target-only greedy
# ---------------------------------------------------------------------------


def spec_decoder(lm, mode="int8", **kw):
    from deeplearning4j_tpu.serving.speculate import SpeculativeDecoder

    kw.setdefault("lanes", 3)
    kw.setdefault("block_tokens", 4)
    kw.setdefault("n_blocks", 24)
    return SpeculativeDecoder(lm, draft=lowprec.draft_lm(lm, mode),
                              spec_k=3, **kw)


class TestSpeculative:
    def test_spec_equals_target_greedy(self):
        """Both self-draft modes commit the exact target-only greedy
        stream (transcripts AND streaming order), with the acceptance
        ledger live."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        base_o, base_s = run_pool(
            PagedDecoder(lm, lanes=3, block_tokens=4, n_blocks=24))
        for mode in ("int8", "layers:1"):
            d = spec_decoder(lm, mode)
            o, s = run_pool(d)
            assert o == base_o and s == base_s, mode
            assert d.spec_rounds > 0
            snap = d.stats.snapshot()
            assert snap["draft_proposed"] > 0
            assert 0.0 <= snap["acceptance_rate"] <= 1.0

    def test_chaos_all_reject_round_stays_byte_exact(self):
        """SpecChaos corrupts every proposal at acceptance-comparison
        time: the round commits only the target's own correction, so the
        stream is unchanged — the draft can slow decoding, never bend
        it."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        base_o, base_s = run_pool(
            PagedDecoder(lm, lanes=3, block_tokens=4, n_blocks=24))
        chaos = SpecChaos(SpecChaosConfig(reject_at_round=0, count=2))
        d = spec_decoder(lm, spec_chaos=chaos)
        o, s = run_pool(d)
        assert o == base_o and s == base_s
        assert chaos.log and chaos.log[0][1] == "reject_all"
        assert d.stats.draft_rejected > 0
        assert d.stats.snapshot()["acceptance_rate"] < 1.0

    def test_sampled_pool_falls_back_to_base_tick(self):
        """A sampled lane makes the pool ineligible: the decoder runs
        the inherited tick phase (spec_rounds == 0) and stays
        byte-identical to PagedDecoder."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        base_o, base_s = run_pool(
            PagedDecoder(lm, lanes=3, block_tokens=4, n_blocks=24),
            temps=(0.8, 0.8, 0.8))
        d = spec_decoder(lm)
        o, s = run_pool(d, temps=(0.8, 0.8, 0.8))
        assert o == base_o and s == base_s
        assert d.spec_rounds == 0

    def test_spec_under_preemption(self):
        """Block exhaustion preempts and re-admits under the spec
        decoder exactly as under the base pool (greedy: byte-equal)."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        base = PagedDecoder(lm, lanes=3, block_tokens=8, n_blocks=7)
        base_o, base_s = run_pool(base, n_new=20)
        d = spec_decoder(lm, block_tokens=8, n_blocks=7)
        o, s = run_pool(d, n_new=20)
        assert o == base_o and s == base_s
        assert d.stats.preemptions > 0

    def test_draft_validation(self):
        from deeplearning4j_tpu.serving.speculate import SpeculativeDecoder

        lm = tiny_lm()
        with pytest.raises(ValueError):
            SpeculativeDecoder(lm, draft=tiny_lm(vocab_size=31),
                               block_tokens=8, n_blocks=16)
        with pytest.raises(ValueError):
            SpeculativeDecoder(lm, draft=None, block_tokens=8, n_blocks=16)

    def test_acceptance_ledger_arithmetic(self):
        from deeplearning4j_tpu.serving.telemetry import ServingStats

        st = ServingStats()
        st.record_draft(3, 3)
        st.record_draft(3, 0)
        snap = st.snapshot()
        assert snap["draft_proposed"] == 6
        assert snap["draft_accepted"] == 3
        assert snap["draft_rejected"] == 3
        assert snap["acceptance_rate"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# drafts: ops/lowprec.draft_lm + registry caching
# ---------------------------------------------------------------------------


class TestDrafts:
    def test_draft_lm_modes(self):
        lm = tiny_lm()
        d8 = lowprec.draft_lm(lm, "int8")
        assert d8.draft_mode == "int8"
        assert d8._run_cfg == lm._run_cfg
        # fake-quantization actually moved the block weights
        assert not np.allclose(np.asarray(d8.params["blocks"]["Wq"]),
                               np.asarray(lm.params["blocks"]["Wq"]))
        dl = lowprec.draft_lm(lm, "layers:1")
        assert dl._run_cfg.n_layers == 1
        assert np.asarray(dl.params["blocks"]["Wq"]).shape[0] == 1
        with pytest.raises(ValueError):
            lowprec.draft_lm(lm, "layers:9")
        with pytest.raises(ValueError):
            lowprec.draft_lm(lm, "bogus")

    def test_record_draft_net_cached(self):
        """One derivation per (record, mode) however many decoders the
        engine rebuilds around the record."""
        from deeplearning4j_tpu.serving.registry import ModelRecord

        rec = ModelRecord("m", 1, tiny_lm())
        d1 = rec.draft_net("int8")
        assert d1 is rec.draft_net("int8")
        assert d1 is not rec.draft_net("layers:1")

    def test_spec_mode_parsing(self, monkeypatch):
        monkeypatch.delenv("DL4J_TPU_SERVE_SPEC", raising=False)
        assert lowprec.spec_mode() == ""
        monkeypatch.setenv("DL4J_TPU_SERVE_SPEC", "0")
        assert lowprec.spec_mode() == ""
        monkeypatch.setenv("DL4J_TPU_SERVE_SPEC", "1")
        assert lowprec.spec_mode() == "int8"
        monkeypatch.setenv("DL4J_TPU_SERVE_SPEC", "layers:2")
        assert lowprec.spec_mode() == "layers:2"


# ---------------------------------------------------------------------------
# engine wiring
# ---------------------------------------------------------------------------


class TestEngineWiring:
    def test_engine_builds_spec_decoder_and_stays_byte_exact(self,
                                                             monkeypatch):
        """DL4J_TPU_SERVE_SPEC=int8 + a paged pool: the engine serves
        /generate through a SpeculativeDecoder and the greedy output is
        byte-identical to the spec-off engine."""
        from deeplearning4j_tpu.serving.engine import ServingEngine
        from deeplearning4j_tpu.serving.speculate import SpeculativeDecoder

        lm = tiny_lm()
        prompts = np.asarray([[1, 5, 2, 9]])
        monkeypatch.delenv("DL4J_TPU_SERVE_SPEC", raising=False)
        eng = ServingEngine(model=lm, kv_block=8, kv_blocks=16)
        try:
            base = eng.generate(prompts, 8, temperature=0.0)
        finally:
            eng.stop()
        monkeypatch.setenv("DL4J_TPU_SERVE_SPEC", "int8")
        eng = ServingEngine(model=lm, kv_block=8, kv_blocks=16)
        try:
            out = eng.generate(prompts, 8, temperature=0.0)
            rec = eng.registry.default()
            assert isinstance(eng._decoder_for(rec), SpeculativeDecoder)
        finally:
            eng.stop()
        np.testing.assert_array_equal(base, out)


# ---------------------------------------------------------------------------
# knob + bench-leg registration
# ---------------------------------------------------------------------------


class TestRegistration:
    def test_knobs_registered(self):
        for name in ("DL4J_TPU_SERVE_TICK_K", "DL4J_TPU_SERVE_SPEC",
                     "DL4J_TPU_SERVE_SPEC_K"):
            assert env.is_registered(name), name
