"""Mesh-sharded inference plane (ISSUE 18): tensor-parallel decode over
a sharded KV arena + prefill/decode disaggregation.

The plane's acceptance bar is BYTE-identity, not tolerance: the sharded
tick never runs a psum (serving/mesh.py module docstring — column-
parallel QKV by exact weight-column slicing, per-head local attention,
all_gather CONCATENATION, replicated Wo/MLP/logits), so
MeshPagedDecoder must equal the single-device PagedDecoder bit-for-bit
across the WHOLE paged contract matrix: prefix sharing, preemption-by-
recompute, crash eviction, streaming order, k-ticks, sampled lanes.

Incompatibility is LOUD by contract: a knob combination the sharded
plane cannot honor byte-exactly (bf16 KV arena, speculative decode,
indivisible heads, no paged pool) raises at decoder build and surfaces
per-record in /models — never a silent fallback to the dense path.

Disaggregation: a prefill-role replica runs long-prompt prefill as its
own dispatch and hands content-addressed KV blocks to a decode replica
(/prefill -> /prime through the role-aware FleetRouter); the handoff is
best-effort BY CONSTRUCTION, so tokens are byte-identical whether or
not it lands.

Reference anchor: the reference serves one record per route callback
(dl4j-streaming/.../routes/DL4jServeRouteBuilder.java) and has no model
parallelism at all (SURVEY.md section 2.7); provenance for the sharded
decode is the repo's own tensor_parallel plane + the vLLM/Orca pair
cited in serving/paged.py.
"""

import json
import os
import re
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.ops import env
from deeplearning4j_tpu.resilience import (
    InjectedServingFault,
    ServingChaos,
    ServingChaosConfig,
)
from deeplearning4j_tpu.serving import ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MESH_D = 4  # of the 8 virtual devices conftest forces


def tiny_lm(**over):
    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )

    kw = dict(vocab_size=29, d_model=16, n_layers=2, n_heads=MESH_D,
              d_ff=32, max_len=32, use_flash=False)
    kw.update(over)
    return TransformerLM(TransformerConfig(**kw))


def _post(url, path, payload, timeout=240):
    req = urllib.request.Request(
        url + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(url, path, timeout=60):
    with urllib.request.urlopen(url + path, timeout=timeout) as r:
        return json.loads(r.read())


# ---------------------------------------------------------------------------
# byte-identity across the paged contract matrix
# ---------------------------------------------------------------------------


class TestMeshByteIdentity:
    @pytest.mark.parametrize("policy", ["strict", "performance"])
    def test_coscheduled_equals_solo_greedy_and_sampled(self, policy):
        """Sharded tick == solo tick BYTE-identical with greedy and
        temperature-sampled lanes co-resident (the threefry keys are
        replicated, so sampling is bitwise the same program). Under the
        performance policy both serve the view of the weights (block
        leaves cast once, serving/paged.serving_view), the mesh decoder
        its replica of it."""
        from deeplearning4j_tpu.serving.mesh import MeshPagedDecoder
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm(dtype_policy=policy)
        reqs = [([1, 5, 2, 9], dict(temperature=0.0)),
                ([4, 4, 4], dict(temperature=0.8, seed=7)),
                ([9, 8, 7, 6, 5], dict(temperature=0.0))]

        def run(d):
            try:
                futs = [d.submit(p, 6, **kw) for p, kw in reqs]
                return [f.result(timeout=240).tolist() for f in futs]
            finally:
                d.stop()

        solo = run(PagedDecoder(lm, block_tokens=4, n_blocks=16))
        sharded = run(MeshPagedDecoder(lm, devices=MESH_D,
                                       block_tokens=4, n_blocks=16))
        assert sharded == solo

    def test_prefix_sharing_equals_solo(self):
        """Prefix-cache hits on the head-sharded arena: shared prompt
        blocks are read-only to both lanes (write tables at trash) and
        the tokens equal the dense pool's."""
        from deeplearning4j_tpu.serving.mesh import MeshPagedDecoder
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        shared = [2, 4, 6, 8, 10, 12, 14, 16, 3, 5]  # > two 4-tok blocks
        d0 = PagedDecoder(lm, block_tokens=4, n_blocks=16)
        try:
            base_a = d0.generate(np.asarray([shared + [7]]), 5,
                                 temperature=0.0)[0]
            base_b = d0.generate(np.asarray([shared + [9]]), 5,
                                 temperature=0.0)[0]
        finally:
            d0.stop()
        d = MeshPagedDecoder(lm, devices=MESH_D, block_tokens=4,
                             n_blocks=16)
        try:
            f1 = d.submit(shared + [7], 5, temperature=0.0)
            f2 = d.submit(shared + [9], 5, temperature=0.0)
            np.testing.assert_array_equal(base_a, f1.result(timeout=240))
            np.testing.assert_array_equal(base_b, f2.result(timeout=240))
            assert d.stats.prefix_hits > 0
        finally:
            d.stop()

    def test_preemption_recovery_is_exact(self):
        """Block starvation preempts the youngest admission on the
        sharded arena exactly as on the dense one: recompute-from-window
        lands tokens byte-identical to an uninterrupted dense run."""
        from deeplearning4j_tpu.serving.mesh import MeshPagedDecoder
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        prompts = ([2, 4, 6], [1, 1, 1, 1], [9, 8, 7])
        d0 = PagedDecoder(lm, block_tokens=8, n_blocks=16)
        try:
            bases = [d0.generate(np.asarray([p]), 20,
                                 temperature=0.0)[0] for p in prompts]
        finally:
            d0.stop()
        # 7 blocks * 8 tokens cannot hold three 23/24-token sequences
        d = MeshPagedDecoder(lm, devices=MESH_D, block_tokens=8,
                             n_blocks=7)
        try:
            futs = [d.submit(list(p), 20, temperature=0.0)
                    for p in prompts]
            outs = [f.result(timeout=240) for f in futs]
            assert d.stats.preemptions >= 1
        finally:
            d.stop()
        for base, out in zip(bases, outs):
            np.testing.assert_array_equal(base, out)

    def test_crash_eviction_spares_coresidents(self):
        """A chaos-crashed admission fails ONLY its own future; the
        co-resident's tokens stay byte-equal to solo and the freed
        blocks return (PR 8 semantics on the sharded arena)."""
        from deeplearning4j_tpu.serving.mesh import MeshPagedDecoder

        lm = tiny_lm()
        chaos = ServingChaos(ServingChaosConfig(admit_raise_at=3))
        d = MeshPagedDecoder(lm, devices=MESH_D, block_tokens=8,
                             n_blocks=16, chaos=chaos)
        try:
            prompt = [1, 5, 2, 9]
            solo = d.generate(np.asarray([prompt]), 8, temperature=0.0)[0]
            long_fut = d.submit(prompt, 8, temperature=0.0)
            time.sleep(0.05)
            crash_fut = d.submit([3, 3, 4], 6, temperature=0.0)
            with pytest.raises(InjectedServingFault):
                crash_fut.result(timeout=120)
            np.testing.assert_array_equal(solo,
                                          long_fut.result(timeout=240))
            assert d.stats.slot_crashes == 1
            cap = d.kv_capacity()
            assert cap["blocks_in_use"] == cap["prefix_blocks_cached"]
        finally:
            d.stop()

    def test_streaming_order_matches_result(self):
        """on_token fires per tick in emission order on the sharded
        pool — the streamed sequence IS the final result."""
        from deeplearning4j_tpu.serving.mesh import MeshPagedDecoder

        lm = tiny_lm()
        d = MeshPagedDecoder(lm, devices=MESH_D, block_tokens=4,
                             n_blocks=16)
        try:
            streamed = []
            fut = d.submit([1, 5, 2, 9], 6, temperature=0.0,
                           on_token=streamed.append)
            out = fut.result(timeout=240)
            assert streamed == list(out)
        finally:
            d.stop()

    def test_k_tick_equals_one_tick(self):
        """The k-scanned sharded tick == the 1-tick sharded program ==
        the dense pool, byte-identical (ISSUE 16's amortization contract
        carried onto the mesh)."""
        from deeplearning4j_tpu.serving.mesh import MeshPagedDecoder
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        outs = []
        for mk in (dict(), dict(devices=MESH_D),
                   dict(devices=MESH_D, tick_k=4)):
            cls = MeshPagedDecoder if "devices" in mk else PagedDecoder
            d = cls(lm, block_tokens=4, n_blocks=16, **mk)
            try:
                outs.append(d.generate(np.asarray([[1, 5, 2, 9]]), 8,
                                       temperature=0.0)[0].tolist())
            finally:
                d.stop()
        assert outs[0] == outs[1] == outs[2]


# ---------------------------------------------------------------------------
# loud incompatibility gates
# ---------------------------------------------------------------------------


class TestLoudGates:
    def test_indivisible_heads_rejects(self):
        from deeplearning4j_tpu.serving.mesh import MeshPagedDecoder

        with pytest.raises(ValueError, match="divisible"):
            MeshPagedDecoder(tiny_lm(n_heads=3), devices=MESH_D,
                             block_tokens=4, n_blocks=16)

    def test_single_device_rejects(self):
        from deeplearning4j_tpu.serving.mesh import MeshPagedDecoder

        with pytest.raises(ValueError, match="devices"):
            MeshPagedDecoder(tiny_lm(), devices=1, block_tokens=4,
                             n_blocks=16)

    def test_bf16_kv_rejects(self, monkeypatch):
        """DL4J_TPU_SERVE_KV_DTYPE=bf16 x mesh raises at build — the
        arena cast would make the sharded tick's bytes diverge from the
        dense f32 pool, so it must never be silent."""
        from deeplearning4j_tpu.serving.mesh import MeshPagedDecoder

        monkeypatch.setenv("DL4J_TPU_SERVE_KV_DTYPE", "bf16")
        with pytest.raises(ValueError, match="KV_DTYPE"):
            MeshPagedDecoder(tiny_lm(), devices=MESH_D, block_tokens=4,
                             n_blocks=16)

    def test_spec_mode_rejects(self, monkeypatch):
        from deeplearning4j_tpu.serving.mesh import MeshPagedDecoder

        monkeypatch.setenv("DL4J_TPU_SERVE_SPEC", "int8")
        with pytest.raises(ValueError, match="SPEC"):
            MeshPagedDecoder(tiny_lm(), devices=MESH_D, block_tokens=4,
                             n_blocks=16)

    def test_engine_mesh_requires_paged_pool(self):
        """Mesh over the fixed-slot pool is a contradiction (no sharded
        arena): the engine raises LOUDLY instead of quietly serving the
        dense fixed-slot path."""
        lm = tiny_lm()
        eng = ServingEngine(model=lm, kv_block=0, mesh_devices=MESH_D)
        try:
            with pytest.raises(ValueError, match="KV_BLOCK"):
                eng._decoder_for(eng.registry.default())
        finally:
            eng.stop()

    def test_engine_gate_error_is_loud_not_fallback(self):
        """A mesh-ineligible model (indivisible heads) must NOT land in
        _no_decoder and serve dense: /generate answers 400 with the gate
        error and /models carries it per record."""
        lm = tiny_lm(n_heads=3)
        eng = ServingEngine(model=lm, kv_block=4, kv_blocks=16,
                            mesh_devices=MESH_D).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(eng.url, "/generate", {"tokens": [1, 2, 3],
                                             "n_new": 2,
                                             "temperature": 0.0})
            assert exc.value.code == 400
            assert "divisible" in json.loads(exc.value.read())["error"]
            kv = _get(eng.url, "/models")["kv"]["default@v1"]
            assert "divisible" in kv["error"]
            # the record was NOT blacklisted into the silent-dense set
            assert not eng._no_decoder
        finally:
            eng.stop()

    def test_engine_role_validated(self):
        with pytest.raises(ValueError, match="SERVE_ROLE"):
            ServingEngine(model=tiny_lm(), role="sideways")


# ---------------------------------------------------------------------------
# per-device arena accounting (ops/memory.py closed forms)
# ---------------------------------------------------------------------------


class TestArenaSizing:
    def test_kv_block_bytes_devices_closed_form(self):
        """devices=d divides the HEAD axis (ceil) in the per-device
        block footprint: 2 (k+v) * L * bt * ceil(H/d) * hd * itemsize."""
        from deeplearning4j_tpu.ops import memory as opsmem

        cfg = tiny_lm()._run_cfg
        one = opsmem.kv_block_bytes(cfg, 8)
        for d in (1, 2, 4):
            per = opsmem.kv_block_bytes(cfg, 8, devices=d)
            hl = -(-cfg.n_heads // d)
            want = 2 * cfg.n_layers * 8 * hl * (
                cfg.d_model // cfg.n_heads) * 4
            assert per == want
            assert per == one // d  # H=4 divides evenly here

    def test_kv_arena_blocks_scales_with_devices(self):
        """At a fixed per-device HBM budget, the global arena admits ~d
        times the blocks: capacity scales with the mesh (the tentpole's
        capacity claim, closed-form — no device needed)."""
        from deeplearning4j_tpu.ops import memory as opsmem

        cfg = tiny_lm()._run_cfg
        n1 = opsmem.kv_arena_blocks(cfg, 8, hbm_gb=0.001)
        n4 = opsmem.kv_arena_blocks(cfg, 8, hbm_gb=0.001, devices=4)
        assert n4 == 4 * n1

    def test_kv_capacity_stamps_mesh_devices(self):
        from deeplearning4j_tpu.serving.mesh import MeshPagedDecoder
        from deeplearning4j_tpu.serving.paged import PagedDecoder

        lm = tiny_lm()
        d = PagedDecoder(lm, block_tokens=4, n_blocks=16)
        try:
            assert d.kv_capacity()["mesh_devices"] == 1
        finally:
            d.stop()
        d = MeshPagedDecoder(lm, devices=MESH_D, block_tokens=4,
                             n_blocks=16)
        try:
            assert d.kv_capacity()["mesh_devices"] == MESH_D
        finally:
            d.stop()


# ---------------------------------------------------------------------------
# role-aware routing + the prefill->decode handoff
# ---------------------------------------------------------------------------


class TestRolesAndHandoff:
    def test_addr_role_roundtrip_and_backcompat(self, tmp_path):
        from deeplearning4j_tpu.serving.router import (
            publish_replica_addr,
            read_replica_entry,
        )

        publish_replica_addr(str(tmp_path), "r0", "http://x:1",
                             role="prefill")
        entry = read_replica_entry(str(tmp_path), "r0")
        assert entry == {"url": "http://x:1", "role": "prefill"}
        # an addr file written before the role field existed
        with open(os.path.join(str(tmp_path), "replica-r1.addr"),
                  "w") as f:
            json.dump({"url": "http://y:2", "pid": 1}, f)
        assert read_replica_entry(str(tmp_path), "r1") == {
            "url": "http://y:2", "role": ""}

    def test_disaggregated_generate_byte_equal_and_routed(self):
        """/generate through a prefill+decode fleet: every request is
        answered byte-equal to a solo engine, decode traffic never lands
        on the prefill replica, and the handoff adopts blocks that the
        decode replica's admission then HITS in its prefix cache."""
        from deeplearning4j_tpu.serving.router import FleetRouter

        lm = tiny_lm()
        prompt = [1, 5, 2, 9, 3, 7, 4, 8, 6, 2]
        solo = ServingEngine(model=lm, kv_block=4, kv_blocks=24).start()
        try:
            want = _post(solo.url, "/generate",
                         {"tokens": prompt, "n_new": 6,
                          "temperature": 0.0})["tokens"][0]
        finally:
            solo.stop()
        pre = ServingEngine(model=lm, kv_block=4, kv_blocks=24,
                            role="prefill").start()
        dec = ServingEngine(model=lm, kv_block=4, kv_blocks=24,
                            role="decode").start()
        router = FleetRouter(replicas={
            "p0": {"url": pre.url, "role": "prefill"},
            "d0": {"url": dec.url, "role": "decode"},
        }).start()
        try:
            for _ in range(2):
                got = _post(router.url, "/generate",
                            {"tokens": prompt, "n_new": 6,
                             "temperature": 0.0})["tokens"][0]
                assert got == want
            snap = router.stats.snapshot()
            assert snap["prefill_handoffs"] >= 1
            ps, ds = pre.stats.snapshot(), dec.stats.snapshot()
            assert ps["prefix_exports"] >= 1
            assert ps["generated_tokens"] == 0  # no decode leak
            assert ds["prefix_imports"] >= 1
            assert ds["prefix_hits"] >= 1
            assert ds["errors"] == 0 and ds["completed"] == 2
            desc = router.describe_replicas()
            assert desc["p0"]["role"] == "prefill"
        finally:
            router.stop()
            pre.stop()
            dec.stop()

    def test_handoff_failure_falls_back_byte_identical(self):
        """A dead prefill replica degrades to the direct decode path —
        same tokens, fallback counted, zero failed requests (the
        best-effort-by-construction contract)."""
        from deeplearning4j_tpu.serving.router import FleetRouter

        lm = tiny_lm()
        prompt = [1, 5, 2, 9, 3, 7, 4, 8, 6, 2]
        dec = ServingEngine(model=lm, kv_block=4, kv_blocks=24).start()
        want = None
        router = FleetRouter(replicas={
            # unroutable prefill replica (nothing listens there)
            "p0": {"url": "http://127.0.0.1:9", "role": "prefill"},
            "d0": {"url": dec.url, "role": "decode"},
        }).start()
        try:
            got = _post(router.url, "/generate",
                        {"tokens": prompt, "n_new": 6,
                         "temperature": 0.0})["tokens"][0]
            want = dec.generate(np.asarray([prompt]), 6,
                                temperature=0.0)[0].tolist()
            assert got == want
            snap = router.stats.snapshot()
            assert snap["prefill_fallbacks"] == 1
            assert snap["prefill_handoffs"] == 0
        finally:
            router.stop()
            dec.stop()

    def test_short_prompt_skips_handoff(self):
        """A prompt below one full block has nothing to hand off: no
        fallback counted, no /prime, tokens still byte-equal."""
        from deeplearning4j_tpu.serving.router import FleetRouter

        lm = tiny_lm()
        pre = ServingEngine(model=lm, kv_block=8, kv_blocks=24,
                            role="prefill").start()
        dec = ServingEngine(model=lm, kv_block=8, kv_blocks=24,
                            role="decode").start()
        router = FleetRouter(replicas={
            "p0": {"url": pre.url, "role": "prefill"},
            "d0": {"url": dec.url, "role": "decode"},
        }).start()
        try:
            _post(router.url, "/generate", {"tokens": [1, 5, 2],
                                            "n_new": 4,
                                            "temperature": 0.0})
            snap = router.stats.snapshot()
            assert snap["prefill_handoffs"] == 0
            assert snap["prefill_fallbacks"] == 0
            assert dec.stats.snapshot()["prefix_imports"] == 0
        finally:
            router.stop()
            pre.stop()
            dec.stop()


# ---------------------------------------------------------------------------
# knob + ledger + bench-leg registration
# ---------------------------------------------------------------------------


class TestRegistration:
    def test_knobs_registered(self):
        for name in ("DL4J_TPU_SERVE_MESH", "DL4J_TPU_SERVE_ROLE"):
            assert env.is_registered(name), name

    def test_prefix_handoff_counters_in_ledgers(self):
        """The new telemetry fields ride the existing registered
        ledgers (serving_stats / router_stats) — one scrape surface."""
        from deeplearning4j_tpu.serving.router import RouterStats
        from deeplearning4j_tpu.serving.telemetry import ServingStats

        s = ServingStats()
        s.record_prefix_export()
        s.record_prefix_import(3)
        snap = s.snapshot()
        assert snap["prefix_exports"] == 1
        assert snap["prefix_imports"] == 1
        assert snap["prefix_import_blocks"] == 3
        r = RouterStats()
        r.record_prefill_handoff()
        r.record_prefill_fallback()
        snap = r.snapshot()
        assert snap["prefill_handoffs"] == 1
        assert snap["prefill_fallbacks"] == 1
