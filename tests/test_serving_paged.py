"""Paged-KV serving plane (ISSUE 11): the block-pool /generate decoder.

Contracts carried onto the paged pool from the fixed-slot one
(tests/test_serving.py + tests/test_serving_resilience.py):

  * request independence — a sequence's greedy tokens are byte-invariant
    to pool co-residents, across block eviction, prefix SHARING, and
    preemption-by-recompute (the serving twin of distributed==serial);
  * crash eviction — a crashed admission fails only its own future and
    returns its blocks to the free list (PR 8 semantics).

New contracts this plane introduces: prefix-cache hits on shared
prompts, per-token streaming callbacks in emission order, SLO-class
admission (priority order, shed-youngest-of-lowest, unknown class is a
400-class ClientRequestError), preemption recovery exactness (a
preempted-and-re-admitted sequence re-consumes its window and replays
NOTHING), and HBM-budgeted arena sizing (ops/memory.kv_arena_blocks).

Reference anchor: the reference serves one record per route callback
(dl4j-streaming/.../routes/DL4jServeRouteBuilder.java) — block-pool KV
scheduling has no reference twin; provenance is the vLLM/Orca pair
cited in serving/paged.py's module docstring.
"""

import json
import os
import re
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.resilience import (
    InjectedServingFault,
    ServingChaos,
    ServingChaosConfig,
)
from deeplearning4j_tpu.serving import QueueFullError, ServingEngine
from deeplearning4j_tpu.serving.resilience import ClientRequestError

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


def _post(url, path, payload, timeout=120):
    req = urllib.request.Request(
        url + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(url, path, timeout=60):
    with urllib.request.urlopen(url + path, timeout=timeout) as r:
        return json.loads(r.read())


# ---------------------------------------------------------------------------
# request independence on the paged pool
# ---------------------------------------------------------------------------


class TestPagedIndependence:
    def test_solo_equals_fixed_slot_baseline(self):
        """The paged tick (write-then-gather through a block table) is
        the same arithmetic as the fixed-slot pool: greedy tokens are
        byte-identical between the two decoders."""
        from deeplearning4j_tpu.serving.decode import ContinuousDecoder
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        d0 = ContinuousDecoder(lm, slots=2)
        try:
            base = d0.generate(np.asarray([[1, 5, 2, 9]]), 6,
                               temperature=0.0)[0]
        finally:
            d0.stop()
        d = PagedDecoder(lm, block_tokens=8, n_blocks=16)
        try:
            solo = d.generate(np.asarray([[1, 5, 2, 9]]), 6,
                              temperature=0.0)[0]
        finally:
            d.stop()
        np.testing.assert_array_equal(base, solo)

    def test_coscheduled_with_prefix_sharing_equals_solo(self):
        """Greedy tokens are invariant to co-residents EVEN WHEN the
        co-resident physically shares prefix blocks (the shared blocks
        are read-only to both: write tables point the hit entries at
        trash), and the share registers as a prefix-cache hit."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        shared = [2, 4, 6, 8, 10, 12, 14, 16, 3, 5]  # > one 8-token block
        d = PagedDecoder(lm, block_tokens=8, n_blocks=16)
        try:
            solo_a = d.generate(np.asarray([shared + [7]]), 5,
                                temperature=0.0)[0]
            solo_b = d.generate(np.asarray([shared + [9]]), 5,
                                temperature=0.0)[0]
            before = d.stats.prefix_hits
            f1 = d.submit(shared + [7], 5, temperature=0.0)
            f2 = d.submit(shared + [9], 5, temperature=0.0)
            f3 = d.submit([3, 3, 4], 8, temperature=0.0)
            np.testing.assert_array_equal(solo_a, f1.result(timeout=120))
            np.testing.assert_array_equal(solo_b, f2.result(timeout=120))
            f3.result(timeout=120)
            assert d.stats.prefix_hits > before
        finally:
            d.stop()

    def test_blocks_return_to_free_list(self):
        """After every request completes, only prefix-cache holdings
        remain allocated; a second wave reuses the freed blocks."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        d = PagedDecoder(lm, block_tokens=8, n_blocks=16)
        try:
            for _ in range(2):
                d.generate(np.asarray([[1, 2, 3], [4, 5, 6], [7, 8, 9]]),
                           6, temperature=0.0)
                cap = d.kv_capacity()
                assert cap["blocks_in_use"] == cap["prefix_blocks_cached"]
                assert cap["tokens_in_use"] == 0
        finally:
            d.stop()

    def test_preemption_recovery_is_exact(self):
        """A block-starved arena preempts the youngest admission and
        re-admits it later by re-consuming prompt+generated — the final
        tokens are byte-identical to an uninterrupted run (recompute,
        never resample: the live PRNG key rides the requeue)."""
        from deeplearning4j_tpu.serving.decode import ContinuousDecoder
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        d0 = ContinuousDecoder(lm, slots=1)
        try:
            bases = [d0.generate(np.asarray([p]), 20, temperature=0.0)[0]
                     for p in ([2, 4, 6], [1, 1, 1, 1], [9, 8, 7])]
        finally:
            d0.stop()
        # 7 blocks * 8 tokens cannot hold three 23/24-token sequences
        # at once: growth must preempt
        d = PagedDecoder(lm, block_tokens=8, n_blocks=7)
        try:
            futs = [d.submit([2, 4, 6], 20, temperature=0.0),
                    d.submit([1, 1, 1, 1], 20, temperature=0.0),
                    d.submit([9, 8, 7], 20, temperature=0.0)]
            outs = [f.result(timeout=240) for f in futs]
            assert d.stats.preemptions >= 1
        finally:
            d.stop()
        for base, out in zip(bases, outs):
            np.testing.assert_array_equal(base, out)

    def test_seed_determinism_under_pool(self):
        """Sampling is a function of the request's own seed, not of
        block-pool scheduling: same seed twice -> same tokens."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        d = PagedDecoder(lm, block_tokens=8, n_blocks=16)
        try:
            a = d.generate(np.asarray([[4, 4, 4]]), 5, temperature=0.8,
                           seed=7)[0]
            b = d.generate(np.asarray([[4, 4, 4]]), 5, temperature=0.8,
                           seed=7)[0]
        finally:
            d.stop()
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# crash eviction (PR 8 semantics on the paged pool)
# ---------------------------------------------------------------------------


class TestPagedCrashEviction:
    def test_crashed_admission_frees_blocks_and_spares_coresidents(self):
        """Admission k crashes: ONLY its future fails, its blocks go
        back to the free list, and a co-resident's greedy tokens equal
        its solo baseline."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        chaos = ServingChaos(ServingChaosConfig(admit_raise_at=3))
        d = PagedDecoder(lm, block_tokens=8, n_blocks=16, chaos=chaos)
        try:
            prompt = [1, 5, 2, 9]
            solo = d.generate(np.asarray([prompt]), 8, temperature=0.0)[0]
            long_fut = d.submit(prompt, 8, temperature=0.0)
            time.sleep(0.05)  # let admission 2 land before the crasher
            crash_fut = d.submit([3, 3, 4], 6, temperature=0.0)
            with pytest.raises(InjectedServingFault):
                crash_fut.result(timeout=60)
            np.testing.assert_array_equal(solo,
                                          long_fut.result(timeout=120))
            assert d.stats.slot_crashes == 1
            cap = d.kv_capacity()
            assert cap["blocks_in_use"] == cap["prefix_blocks_cached"]
            # the pool is still alive for fresh traffic
            again = d.generate(np.asarray([prompt]), 8, temperature=0.0)[0]
            np.testing.assert_array_equal(solo, again)
        finally:
            d.stop()


# ---------------------------------------------------------------------------
# SLO classes
# ---------------------------------------------------------------------------


class TestSLOClasses:
    def test_parse_slo_classes(self):
        from deeplearning4j_tpu.serving.slo import parse_slo_classes

        classes = parse_slo_classes("interactive:5,batch:60")
        assert [c.name for c in classes] == ["interactive", "batch"]
        # priority 0 is the HIGHEST (spec order)
        assert classes[0].priority < classes[1].priority
        assert classes[0].deadline_s == 5.0
        for bad in ("interactive", "a:1,a:2", "a:0", "a:-3", "a:x"):
            with pytest.raises(ValueError):
                parse_slo_classes(bad)

    def test_unknown_class_is_client_error(self):
        from deeplearning4j_tpu.serving.paged import PagedDecoder
        from deeplearning4j_tpu.serving.slo import parse_slo_classes

        lm = tiny_lm()
        d = PagedDecoder(lm, block_tokens=8, n_blocks=16,
                         slo_classes=parse_slo_classes("rt:5,bulk:60"))
        try:
            with pytest.raises(ClientRequestError):
                d.submit([1, 2, 3], 4, slo="nope")
        finally:
            d.stop()

    def test_full_queue_sheds_youngest_of_lowest_class(self):
        """Past queue_cap a higher-priority submit sheds the youngest
        pending request of the lowest class strictly below it; a
        low-class submit with nothing to shed gets the 429."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder
        from deeplearning4j_tpu.serving.slo import parse_slo_classes

        lm = tiny_lm()
        d = PagedDecoder(lm, block_tokens=8, n_blocks=16, lanes=1,
                         slo_classes=parse_slo_classes("rt:30,bulk:30"),
                         queue_cap=2)
        try:
            # the hog takes the single lane; its on_token throttle keeps
            # the lane busy long enough for the queue choreography below
            # to be race-free on a loaded host
            hog = d.submit([2, 4, 6], 20, temperature=0.0,
                           on_token=lambda t: time.sleep(0.02))
            time.sleep(0.1)
            old = d.submit([1, 2], 3, temperature=0.0, slo="bulk")
            young = d.submit([3, 4], 3, temperature=0.0, slo="bulk")
            # queue full: the rt submit sheds the YOUNGEST bulk request
            kept = d.submit([5, 6], 3, temperature=0.0, slo="rt")
            with pytest.raises(QueueFullError):
                young.result(timeout=5)
            assert d.stats.shed_by_class.get("bulk") == 1
            # queue full again, and a bulk arrival outranks nobody: 429
            with pytest.raises(QueueFullError):
                d.submit([7, 8], 3, temperature=0.0, slo="bulk")
            assert hog.result(timeout=120).shape == (20,)
            assert old.result(timeout=120).shape == (3,)
            assert kept.result(timeout=120).shape == (3,)
        finally:
            d.stop()


# ---------------------------------------------------------------------------
# streaming
# ---------------------------------------------------------------------------


class TestStreaming:
    def test_on_token_streams_in_emission_order(self):
        """The callback sees every token, in order, and all of them
        BEFORE the future resolves (a consumer observing a done future
        may drain-then-stop without losing tokens)."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        d = PagedDecoder(lm, block_tokens=8, n_blocks=16)
        try:
            streamed = []
            fut = d.submit([1, 5, 2, 9], 6, temperature=0.0,
                           on_token=streamed.append)
            out = fut.result(timeout=120)
            assert streamed == list(out)
        finally:
            d.stop()

    @pytest.mark.parametrize("x64", [False, True])
    @pytest.mark.parametrize("seed", [0, 1, 7, 2**31 - 1, 2**31, 2**32 - 1,
                                      2**32 + 5, 4100003106, -1])
    def test_seed_key_is_the_devices_key(self, seed, x64):
        """A lane's key is made on the host (an admission asks the device
        nothing): bit for bit what ``jax.random.PRNGKey`` gives, with and
        without x64, past 32 bits and below zero."""
        import jax

        from deeplearning4j_tpu.serving.paged import seed_key

        with jax.enable_x64(x64):
            want = np.asarray(jax.random.PRNGKey(seed))
            got = seed_key(seed)
        assert got.dtype == want.dtype and got.tolist() == want.tolist()

    def test_tokens_go_out_once_the_next_program_is_dispatched(self):
        """A tick's tokens are kept back until the next program is on the
        device, and handed over at once where none follows: while lanes
        are live every callback of tick t runs after tick t+1's dispatch
        began; the last tick's run inside its own emit; order and the
        done-after-last-token contract hold."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        d = PagedDecoder(lm, block_tokens=8, n_blocks=16)
        events = []
        fn = d._tick_fn(1)

        def spy(*a):
            events.append("dispatch")
            return fn(*a)

        d._ticks[1] = spy
        try:
            fut = d.submit([1, 5, 2, 9], 4, temperature=0.0,
                           on_token=lambda t: events.append(("token", t)))
            fut.add_done_callback(lambda f: events.append("done"))
            out = fut.result(timeout=120)
        finally:
            d.stop()
        tokens = [e[1] for e in events if isinstance(e, tuple)]
        assert tokens == list(out)
        kinds = ["t" if isinstance(e, tuple) else e for e in events]
        # ticks 1 to 3 leave the lane live: token t is handed over after
        # dispatch t+1; tick 4 empties the pool: its token goes at once
        assert kinds == ["dispatch", "dispatch", "t", "dispatch", "t",
                         "dispatch", "t", "t", "done"]

    def test_a_burst_is_admitted_before_its_first_tick(self, monkeypatch):
        """Requests that arrive a millisecond apart (closer than
        paged.GATHER_S) all sit in lanes when the first tick after them
        is dispatched, though each admission is faster than the gap: the
        worker holds the tick back while the burst is still arriving."""
        from deeplearning4j_tpu.serving import paged

        # the sender's millisecond is the scheduler's to stretch (six
        # test workers share the cores): the test takes a hundredfold
        # margin, the program's constants stay
        monkeypatch.setattr(paged, "GATHER_S", 100 * paged.GATHER_S)
        monkeypatch.setattr(paged, "GATHER_CAP_S", 100 * paged.GATHER_CAP_S)
        lm = tiny_lm()
        d = paged.PagedDecoder(lm, block_tokens=8, n_blocks=32, lanes=8)
        try:
            # every program the burst uses is compiled first
            d.submit([3, 1, 4, 1], 2, temperature=0.0).result(timeout=120)
            live = []
            fn = d._tick_fn(1)

            def spy(*a):
                live.append(sum(st is not None for st in d._slots))
                return fn(*a)

            d._ticks[1] = spy
            futs = []
            for i in range(6):
                futs.append(d.submit([1 + i, 5, 2, 9], 3, temperature=0.0))
                time.sleep(0.001)
            for f in futs:
                f.result(timeout=120)
        finally:
            d.stop()
        assert paged.GATHER_S > 0.001
        assert live[0] == 6, live

    def test_a_pool_in_mid_generation_does_not_wait_for_arrivals(self):
        """The wait is for a pool that was idle: with a lane that has given
        tokens, a submit a moment ago holds no tick back (it would add to
        the gap between two tokens of every live lane)."""
        from deeplearning4j_tpu.serving import paged

        d = paged.PagedDecoder(tiny_lm(), block_tokens=8, n_blocks=32,
                               lanes=4)
        try:
            d.submit([3, 1, 4, 1], 2, temperature=0.0).result(timeout=120)
            time.sleep(0.05)      # the worker sleeps in serve.idle: by hand

            class Lane:
                tokens = [7]

            for slots, least, most in (([None] * 4, paged.GATHER_S * 0.8,
                                        paged.GATHER_CAP_S + 0.5),
                                       ([Lane()] + [None] * 3, 0.0,
                                        paged.GATHER_S * 0.5)):
                d._slots = slots
                d._last_submit = time.monotonic()
                t0 = time.monotonic()
                d._gather()
                assert least <= time.monotonic() - t0 <= most, slots
        finally:
            d._slots = [None] * 4
            d.stop()

    def test_speculative_decoder_hands_over_at_once(self):
        from deeplearning4j_tpu.serving.paged import PagedDecoder
        from deeplearning4j_tpu.serving.speculate import SpeculativeDecoder

        assert PagedDecoder.defer_delivery
        assert not SpeculativeDecoder.defer_delivery

    def test_http_stream_matches_nonstream(self):
        """POST /generate with stream=true chunks NDJSON token events
        and a final done record whose tokens equal the non-streaming
        response for the same request."""
        lm = tiny_lm()
        eng = ServingEngine(model=lm, kv_block=8, kv_blocks=16).start()
        try:
            plain = _post(eng.url, "/generate",
                          {"tokens": [1, 5, 2, 9], "n_new": 6,
                           "temperature": 0.0})["tokens"][0]
            req = urllib.request.Request(
                eng.url + "/generate",
                data=json.dumps({"tokens": [1, 5, 2, 9], "n_new": 6,
                                 "temperature": 0.0,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                assert resp.headers.get("Content-Type") == \
                    "application/x-ndjson"
                events = [json.loads(ln) for ln in resp.read().splitlines()
                          if ln.strip()]
            toks = [e["token"] for e in events if "token" in e]
            done = [e for e in events if e.get("done")]
            assert toks == plain
            assert done and done[0]["tokens"] == plain
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# engine integration: default paged, KV_BLOCK=0 fallback, /models report
# ---------------------------------------------------------------------------


class TestEngineIntegration:
    def test_paged_default_and_fixed_slot_fallback_agree(self):
        """kv_block>0 (the default) serves /generate from the paged
        pool; kv_block=0 falls back to the fixed-slot decoder; both
        return identical greedy tokens and report their scheme (and
        capacity in tokens) at /models."""
        lm = tiny_lm()
        eng = ServingEngine(model=lm, kv_block=8, kv_blocks=16).start()
        try:
            paged = _post(eng.url, "/generate",
                          {"tokens": [1, 5, 2, 9], "n_new": 6,
                           "temperature": 0.0})["tokens"][0]
            kv = _get(eng.url, "/models")["kv"]["default@v1"]
            assert kv["scheme"] == "paged"
            assert kv["capacity_tokens"] == 16 * 8
        finally:
            eng.stop()
        eng = ServingEngine(model=lm, kv_block=0).start()
        try:
            fixed = _post(eng.url, "/generate",
                          {"tokens": [1, 5, 2, 9], "n_new": 6,
                           "temperature": 0.0})["tokens"][0]
            kv = _get(eng.url, "/models")["kv"]["default@v1"]
            assert kv["scheme"] == "fixed-slot"
            assert kv["capacity_tokens"] == kv["slots"] * 32
        finally:
            eng.stop()
        assert paged == fixed

    def test_http_slo_routing_and_unknown_class_400(self):
        lm = tiny_lm()
        eng = ServingEngine(model=lm, kv_block=8, kv_blocks=16,
                            slo_classes="interactive:30,batch:120").start()
        try:
            out = _post(eng.url, "/generate",
                        {"tokens": [1, 5, 2, 9], "n_new": 3,
                         "temperature": 0.0, "slo": "interactive"})
            assert len(out["tokens"][0]) == 3
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(eng.url, "/generate",
                      {"tokens": [1, 2], "n_new": 2, "slo": "nope"})
            assert exc.value.code == 400
        finally:
            eng.stop()

    def test_bad_slo_spec_fails_at_construction(self):
        with pytest.raises(ValueError):
            ServingEngine(model=tiny_lm(), slo_classes="oops")


# ---------------------------------------------------------------------------
# arena sizing (the fixed-pool over-allocation fix)
# ---------------------------------------------------------------------------


class TestArenaSizing:
    def test_kv_block_bytes_closed_form(self):
        from deeplearning4j_tpu.models.transformer import TransformerConfig
        from deeplearning4j_tpu.ops.memory import kv_block_bytes

        cfg = TransformerConfig(vocab_size=29, d_model=16, n_layers=2,
                                n_heads=2, d_ff=32, max_len=32)
        # k+v, per layer: bt * H * hd elements
        itemsize = np.dtype(cfg.compute_dtype).itemsize
        assert kv_block_bytes(cfg, 8) == 2 * 2 * 8 * 16 * itemsize

    def test_kv_arena_blocks_respects_budget_and_floor(self):
        from deeplearning4j_tpu.models.transformer import TransformerConfig
        from deeplearning4j_tpu.ops.memory import (
            kv_arena_blocks,
            kv_block_bytes,
        )

        cfg = TransformerConfig(vocab_size=29, d_model=16, n_layers=2,
                                n_heads=2, d_ff=32, max_len=32)
        per = kv_block_bytes(cfg, 8)
        # budget for exactly 10 blocks at kv_fraction=1.0
        gb = 10 * per / 2**30
        assert kv_arena_blocks(cfg, 8, hbm_gb=gb, kv_fraction=1.0) == 10
        # a starvation budget still floors at one max_len sequence + 1
        floor = cfg.max_len // 8 + 1
        assert kv_arena_blocks(cfg, 8, hbm_gb=1e-9,
                               kv_fraction=1.0) == floor

    def test_arena_too_small_for_one_sequence_raises(self):
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        with pytest.raises(ValueError):
            PagedDecoder(tiny_lm(), block_tokens=8, n_blocks=4)

    def test_block_tokens_auto_divides_max_len(self):
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        d = PagedDecoder(lm, block_tokens=12, n_blocks=40)
        try:
            assert lm.cfg.max_len % d.block_tokens == 0
        finally:
            d.stop()


# ---------------------------------------------------------------------------
# the serving view of the weights (ISSUE 32): block leaves held once in
# the compute dtype, what is read in float32 shared with the model
# ---------------------------------------------------------------------------

VIEW_BT, VIEW_BLOCKS, VIEW_LANES = 4, 12, 4
# a block matrix stacked over the 2 layers, or one layer's slice of it
# inside the scan, converted from float32 (tiny_lm: d 16, d_ff 32)
BLOCK_MATRIX_CAST = re.compile(
    r"convert.*tensor<(2x)?(16x16|16x32|32x16)xf32>\) -> tensor<[0-9x]+xbf16>")


def _view_programs(lm):
    """The admit program, the jitted tick body (for its logits) and the
    tick of a toy PagedDecoder's shape, with their inputs: one prompt
    admitted to lane 0, the lanes half greedy half sampled."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.serving import paged

    cfg = lm._run_cfg
    width = 8
    rng = np.random.default_rng(5)
    window = np.zeros((1, width), np.int32)
    window[0, :6] = rng.integers(1, cfg.vocab_size, 6)
    write_table = np.zeros((cfg.max_len // VIEW_BT,), np.int32)
    write_table[:2] = (1, 2)
    tables = np.zeros((VIEW_LANES, cfg.max_len // VIEW_BT), np.int32)
    tables[0, :4] = (1, 2, 3, 4)          # lane 0: the admitted prompt
    tables[2, :4] = (5, 6, 7, 8)          # lane 2: a sampled cold lane
    pos = np.array([5, 0, 0, 0], np.int32)
    tok = np.array([window[0, 5], 0, 3, 0], np.int32)
    temps = np.array([0.0, 0.0, 0.9, 0.7], np.float32)
    keys = np.stack([paged.seed_key(i) for i in range(VIEW_LANES)])

    def arena():
        shape = (cfg.n_layers, VIEW_BLOCKS + 1, VIEW_BT, cfg.d_model)
        return {"k": jnp.zeros(shape, cfg.compute_dtype),
                "v": jnp.zeros(shape, cfg.compute_dtype)}

    body = jax.jit(lambda p, a, t, ps: paged.paged_decode_step(
        p, a, t, ps, jnp.asarray(tables), cfg)[1])
    return dict(
        admit=paged._paged_admit_for(cfg, width, VIEW_BT),
        tick=paged._paged_tick_for(cfg, VIEW_BT), body=body, arena=arena,
        window=window, write_table=write_table, tables=tables, pos=pos,
        tok=tok, temps=temps, keys=keys)


def _view_drive(params, pr, ticks=9):
    """An admission and ``ticks`` ticks by hand; everything a client or
    the next program would see, as bytes."""
    import jax.numpy as jnp

    arena = pr["admit"](params, pr["arena"](), jnp.asarray(pr["window"]),
                        jnp.asarray(pr["write_table"]))
    seen = [np.asarray(arena["k"]).tobytes()]
    tok, pos, keys = pr["tok"], pr["pos"], jnp.asarray(pr["keys"])
    for _ in range(ticks):
        logits = pr["body"](params, arena, jnp.asarray(tok),
                            jnp.asarray(pos))
        arena, nxt, keys = pr["tick"](
            params, arena, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(pr["tables"]), keys, jnp.asarray(pr["temps"]))
        tok, pos = np.asarray(nxt)[:, 0], pos + 1
        seen += [np.asarray(logits).tobytes(), tok.tobytes()]
    return seen + [np.asarray(arena["k"]).tobytes(),
                   np.asarray(arena["v"]).tobytes(),
                   np.asarray(keys).tobytes()]


class TestServingView:
    def test_programs_are_bit_equal_on_the_view_and_on_the_masters(self):
        """(a) admit, 9 ticks, greedy and sampled lanes: logits, tokens,
        keys and arena byte for byte, the masters cast each time against
        the view cast once."""
        from deeplearning4j_tpu.serving.paged import serving_view

        lm = tiny_lm(dtype_policy="performance")
        pr = _view_programs(lm)
        view = serving_view(lm.params, lm._run_cfg)
        assert view is not lm.params
        assert _view_drive(view, pr) == _view_drive(lm.params, pr)

    def test_view_shares_what_is_read_in_float32_and_casts_the_blocks(self):
        """(b) embed, pos, lnf_g, lnf_b are lm.params' own buffers; every
        leaf of blocks is held in the compute dtype, with the values a
        cast where it is used would give."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm(dtype_policy="performance")
        d = PagedDecoder(lm, block_tokens=VIEW_BT, n_blocks=VIEW_BLOCKS)
        try:
            view = d._infer_params
        finally:
            d.stop()
        assert set(view) == set(lm.params)
        for name in ("embed", "pos", "lnf_g", "lnf_b"):
            assert view[name] is lm.params[name]
            assert view[name].dtype == jnp.float32
        masters = lm.params["blocks"]
        assert set(view["blocks"]) == set(masters)
        for name, leaf in view["blocks"].items():
            assert leaf.dtype == jnp.bfloat16, name
            np.testing.assert_array_equal(
                np.asarray(leaf.astype(jnp.float32)),
                np.asarray(masters[name].astype(jnp.bfloat16)
                           .astype(jnp.float32)))
            assert masters[name].dtype == jnp.float32  # masters stay
        assert jax.tree_util.tree_structure(view) == \
            jax.tree_util.tree_structure(lm.params)

    def test_view_is_the_models_own_tree_under_a_float32_policy(self):
        """(c) nothing to cast: the same object, no copy, no program."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        d = PagedDecoder(lm, block_tokens=VIEW_BT, n_blocks=VIEW_BLOCKS)
        try:
            assert d._infer_params is lm.params
            cap = d.kv_capacity()
        finally:
            d.stop()
        assert cap["weights_dtype"] == "float32"
        assert cap["weights_view_bytes"] == 0

    @pytest.mark.parametrize("program", ["tick", "admit"])
    def test_no_cast_of_a_block_matrix_is_left_in_the_program(self, program):
        """(d) lowered on the view, tick and admit convert no block
        matrix from float32; lowered on lm.params they do, so the test
        fails if the decoder goes back to handing out the masters."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm(dtype_policy="performance")
        d = PagedDecoder(lm, block_tokens=VIEW_BT, n_blocks=VIEW_BLOCKS,
                         lanes=VIEW_LANES)
        try:
            held = d._infer_params
        finally:
            d.stop()
        pr = _view_programs(lm)
        if program == "tick":
            rest = (pr["arena"](), jnp.asarray(pr["tok"]),
                    jnp.asarray(pr["pos"]), jnp.asarray(pr["tables"]),
                    jnp.asarray(pr["keys"]), jnp.asarray(pr["temps"]))
        else:
            rest = (pr["arena"](), jnp.asarray(pr["window"]),
                    jnp.asarray(pr["write_table"]))
        text = lambda params: pr[program].lower(params, *rest).as_text()
        assert BLOCK_MATRIX_CAST.search(text(lm.params))
        assert not BLOCK_MATRIX_CAST.search(text(held))

    def test_kv_capacity_reports_how_the_weights_are_held(self):
        """(e) weights_dtype and weights_view_bytes, in kv_capacity and
        so in what /models prints (engine.kv_report)."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm(dtype_policy="performance")
        d = PagedDecoder(lm, block_tokens=VIEW_BT, n_blocks=VIEW_BLOCKS)
        try:
            cap = d.kv_capacity()
        finally:
            d.stop()
        blocks = lm.params["blocks"]
        assert cap["weights_dtype"] == "bfloat16"
        assert cap["weights_view_bytes"] == \
            sum(a.size for a in blocks.values()) * 2
        eng = ServingEngine(model=lm, kv_block=VIEW_BT,
                            kv_blocks=VIEW_BLOCKS).start()
        try:
            rep = _get(eng.url, "/models")["kv"]["default@v1"]
        finally:
            eng.stop()
        assert rep["weights_dtype"] == "bfloat16"
        assert rep["weights_view_bytes"] == cap["weights_view_bytes"]

    def test_served_tokens_are_those_of_the_masters(self):
        """Through the decoder itself: the tokens a pool serves on the
        view are the ones it serves with the masters handed back."""
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm(dtype_policy="performance")
        reqs = [([1, 5, 2, 9, 3, 3, 7], dict(temperature=0.0)),
                ([4, 4, 4], dict(temperature=0.8, seed=7))]

        def run(masters):
            d = PagedDecoder(lm, block_tokens=VIEW_BT, n_blocks=VIEW_BLOCKS)
            if masters:
                d._infer_params = lm.params
            try:
                futs = [d.submit(p, 9, **kw) for p, kw in reqs]
                return [f.result(timeout=120).tolist() for f in futs]
            finally:
                d.stop()

        assert run(False) == run(True)


# ---------------------------------------------------------------------------
# ledger + bench registration
# ---------------------------------------------------------------------------


class TestPlumbing:
    def test_new_ledger_fields_in_snapshot(self):
        from deeplearning4j_tpu.serving.telemetry import ServingStats

        s = ServingStats()
        s.set_kv_blocks(3, 16)
        s.record_prefix(1, 2)
        s.record_preemption()
        s.record_shed("bulk")
        snap = s.snapshot()
        assert snap["kv_blocks_in_use"] == 3
        assert snap["kv_blocks_total"] == 16
        assert snap["prefix_hits"] == 1 and snap["prefix_lookups"] == 2
        assert snap["preemptions"] == 1
        assert snap["shed_by_class"] == {"bulk": 1}
