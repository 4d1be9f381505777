"""Embedding & retrieval serving plane (deeplearning4j_tpu/retrieval/)
— ISSUE 17.

Quick-tier contracts:

  (a) /embed through the DynamicBatcher is BYTE-identical to the direct
      feed_forward slice on the same rows, and the bucket-ladder pad
      rows are inert (a 5-row request padded to bucket 8 equals the
      5 per-row requests) — the serving batcher==direct convention
      extended to the embedding surface.
  (b) ExactIndex matches a numpy full-scan oracle exactly; IVF recall@k
      is MEASURED against that oracle on the same snapshot and clears
      the 0.95 bar on a clustered corpus (never assumed).
  (c) a generation publish racing live /search traffic fails ZERO
      admitted requests, and every answer is coherent (ids from some
      published generation, never a torn mix) — the online/promote
      atomic-swap contract re-proved for indexes.
  (d) a latched DriftMonitor alarm VETOES a publish (generation
      unmoved, PublishVetoed, veto counted); force=True overrides.

Plus satellites: the DL4J_TPU_EMBED_*/DL4J_TPU_ANN_* knob registration,
the retrieval_stats ledger registration convention, /models AOT
embed/index reporting, and StreamSource-fed online mutation windows.

Reference anchor: the reference's nlp plane answers wordsNearest with a
host full scan (InMemoryLookupTable.java:73 / BasicModelUtils role);
the /embed + /search serving surface is beyond-reference (PARITY.md).
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.iterator import DataSet
from deeplearning4j_tpu.nn.conf import (
    DenseLayer,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.online import DriftMonitor, StreamSource
from deeplearning4j_tpu.ops import env as envknob
from deeplearning4j_tpu.retrieval import (
    ExactIndex,
    IndexFullError,
    IVFIndex,
    LookupEmbedding,
    PublishVetoed,
    VectorStore,
    measure_recall,
    resolve_adapter,
)
from deeplearning4j_tpu.serving.engine import ServingEngine


def tiny_net(seed=7, n_in=8, hidden=12, n_out=3):
    conf = (NeuralNetConfiguration.builder().seed(seed).list()
            .layer(0, DenseLayer(n_in=n_in, n_out=hidden,
                                 activation="relu"))
            .layer(1, OutputLayer(n_in=hidden, n_out=n_out,
                                  activation="softmax",
                                  loss_function="mcxent"))
            .build())
    net = MultiLayerNetwork(conf)
    net.init()
    return net


def clustered_corpus(rng, n=512, dim=16, clusters=16, spread=0.05):
    """A corpus with real cluster structure — the regime IVF probing is
    FOR (uniform random vectors would make any recall bar meaningless)."""
    centers = rng.normal(size=(clusters, dim)).astype(np.float32)
    assign = rng.integers(0, clusters, size=n)
    pts = centers[assign] + spread * rng.normal(size=(n, dim))
    return pts.astype(np.float32)


def _post(port, path, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.load(resp)


@pytest.fixture
def engine():
    net = tiny_net()
    eng = ServingEngine(model=net, input_shape=(8,)).start()
    yield eng, net
    eng.stop()


class TestEmbedEquivalence:
    def test_batcher_equals_direct_byte_identical(self, engine):
        """Contract (a): the batcher path answers the exact bytes the
        direct feed_forward hidden-layer slice produces."""
        eng, net = engine
        rng = np.random.default_rng(0)
        x = rng.normal(size=(5, 8)).astype(np.float32)
        via_batcher = eng.embed(x)
        acts = net.feed_forward(x, train=False)
        direct = np.asarray(acts[-2], np.float32).reshape(5, -1)
        assert via_batcher.dtype == direct.dtype
        assert np.array_equal(via_batcher, direct)

    def test_pad_rows_inert(self, engine):
        """Contract (a): a 5-row request (padded to bucket 8 inside the
        dispatch) == the same 5 rows requested one at a time."""
        eng, _ = engine
        rng = np.random.default_rng(1)
        x = rng.normal(size=(5, 8)).astype(np.float32)
        batched = eng.embed(x)
        per_row = np.concatenate([eng.embed(x[i:i + 1]) for i in range(5)])
        assert np.array_equal(batched, per_row)

    def test_concurrent_requests_coalesce_byte_equal(self, engine):
        """Concurrent single-row /embed requests ride one coalesced
        dispatch; each caller still gets its own exact slice."""
        eng, net = engine
        rng = np.random.default_rng(2)
        x = rng.normal(size=(8, 8)).astype(np.float32)
        want = np.asarray(net.feed_forward(x, train=False)[-2],
                          np.float32).reshape(8, -1)
        out = [None] * 8
        errs = []

        def one(i):
            try:
                out[i] = eng.embed(x[i:i + 1])
            except Exception as e:  # noqa: BLE001 — test harness
                errs.append(e)

        threads = [threading.Thread(target=one, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert np.array_equal(np.concatenate(out), want)

    def test_http_embed_record_and_batch(self, engine):
        eng, net = engine
        rng = np.random.default_rng(3)
        x = rng.normal(size=(3, 8)).astype(np.float32)
        want = np.asarray(net.feed_forward(x, train=False)[-2],
                          np.float32).reshape(3, -1)
        r = _post(eng.port, "/embed", {"batch": x.tolist()})
        assert r["dim"] == want.shape[1]
        assert np.array_equal(
            np.asarray(r["embeddings"], np.float32), want)
        r1 = _post(eng.port, "/embed", {"record": x[0].tolist()})
        assert np.array_equal(np.asarray(r1["embedding"], np.float32),
                              want[0])

    def test_embed_counters(self, engine):
        eng, _ = engine
        eng.embed(np.zeros((4, 8), np.float32))
        snap = eng.retrieval_stats.snapshot()
        assert snap["embed_requests"] >= 1
        assert snap["embed_rows"] >= 4


class TestAdapters:
    def test_lookup_adapter_matches_syn0(self):
        class Table:
            vector_length = 6
            syn0 = np.arange(60, dtype=np.float32).reshape(10, 6)

            def vectors(self, idx):
                return self.syn0[np.asarray(idx, np.int64)]

        ad = LookupEmbedding(Table())
        assert ad.dim == 6
        got = ad(np.asarray([[2], [7]]))
        assert np.array_equal(got, Table.syn0[[2, 7]])

    def test_feedforward_aot_dim_without_execution(self):
        net = tiny_net()
        ad = resolve_adapter(net, input_shape=(8,))
        # dim known BEFORE any __call__ (jax.eval_shape — the /models
        # no-dispatch contract)
        assert ad.dim == 12

    def test_unsupported_model_raises(self):
        with pytest.raises(TypeError):
            resolve_adapter(object())


class TestIndexes:
    def test_exact_matches_numpy_oracle(self):
        rng = np.random.default_rng(10)
        vecs = rng.normal(size=(100, 16)).astype(np.float32)
        store = VectorStore(16, capacity=128, kind="exact", name="ex")
        store.upsert(np.arange(100), vecs)
        store.publish()
        q = rng.normal(size=(7, 16)).astype(np.float32)
        ids, scores = store.search(q, k=5)
        vn = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
        qn = q / np.linalg.norm(q, axis=1, keepdims=True)
        oracle = np.argsort(-(qn @ vn.T), axis=1)[:, :5]
        assert np.array_equal(ids, oracle)

    def test_ivf_recall_bar_measured(self):
        """Contract (b): recall@10 >= 0.95 on a clustered corpus,
        measured against the exact oracle on the SAME snapshot."""
        rng = np.random.default_rng(11)
        vecs = clustered_corpus(rng, n=512, dim=16, clusters=16)
        store = VectorStore(16, capacity=1024, kind="ivf", clusters=16,
                            nprobe=6, name="ivf")
        store.upsert(np.arange(512), vecs)
        store.publish()
        assert store.snapshot.centroids is not None
        q = clustered_corpus(rng, n=64, dim=16, clusters=16)
        recall = store.probe_recall(q, k=10)
        assert recall >= 0.95
        assert store.retrieval_stats.snapshot()["last_recall"] == recall

    def test_ivf_below_min_rows_serves_exact(self):
        store = VectorStore(8, capacity=64, kind="ivf", min_ivf_rows=32,
                            name="small")
        rng = np.random.default_rng(12)
        store.upsert(np.arange(4), rng.normal(size=(4, 8)))
        store.publish()
        assert store.snapshot.centroids is None  # exact fallback
        ids, _ = store.search(rng.normal(size=(1, 8)), k=2)
        assert set(ids[0]) <= set(range(4))

    def test_fewer_live_rows_than_k(self):
        store = VectorStore(8, capacity=16, kind="exact", name="few")
        store.upsert([5, 9], np.eye(8, dtype=np.float32)[:2])
        store.publish()
        ids, scores = store.search(np.eye(8, dtype=np.float32)[:1], k=4)
        assert ids[0][0] == 5
        # k clamps to the padded arena; entries past the 2 live rows
        # surface as id -1, never a garbage slot
        assert set(ids[0]) == {5, 9, -1}

    def test_delete_never_returned(self):
        rng = np.random.default_rng(13)
        vecs = rng.normal(size=(40, 8)).astype(np.float32)
        store = VectorStore(8, capacity=64, kind="exact", name="del")
        store.upsert(np.arange(40), vecs)
        store.publish()
        store.delete(np.arange(0, 40, 2))
        store.publish()
        ids, _ = store.search(vecs, k=5)
        assert not np.any(ids % 2 == 0)  # every even id was deleted

    def test_upsert_replaces_in_place(self):
        store = VectorStore(4, capacity=8, kind="exact", name="rep")
        store.upsert([1], [[1, 0, 0, 0]])
        store.upsert([1], [[0, 1, 0, 0]])  # same id: replace, not grow
        store.publish()
        assert store.rows == 1
        ids, _ = store.search(np.asarray([[0, 1, 0, 0]], np.float32), k=1)
        assert ids[0][0] == 1

    def test_capacity_full_raises(self):
        store = VectorStore(4, capacity=2, kind="exact", name="full")
        store.upsert([0, 1], np.eye(4, dtype=np.float32)[:2])
        with pytest.raises(IndexFullError):
            store.upsert([2], np.eye(4, dtype=np.float32)[2:3])

    def test_measure_recall_direct(self):
        rng = np.random.default_rng(14)
        vecs = clustered_corpus(rng, n=256, dim=8, clusters=8)
        store = VectorStore(8, capacity=512, kind="ivf", clusters=8,
                            nprobe=8, name="mr")
        store.upsert(np.arange(256), vecs)
        store.publish()
        # nprobe == clusters probes EVERYTHING: recall is exactly 1.0
        ivf = IVFIndex(clusters=8, nprobe=8)
        assert measure_recall(store.snapshot, ivf,
                              vecs[:16], k=10) == 1.0


class TestGenerationSwap:
    def test_zero_failed_searches_across_publishes(self):
        """Contract (c): publishes racing live search traffic fail zero
        admitted requests, and every answer maps to a coherent
        published generation."""
        rng = np.random.default_rng(20)
        dim = 8
        store = VectorStore(dim, capacity=512, kind="exact", name="swap")
        store.upsert(np.arange(32), rng.normal(size=(32, dim)))
        store.publish()
        q = rng.normal(size=(4, dim)).astype(np.float32)
        stop = threading.Event()
        errs = []
        answered = [0]

        def searcher():
            while not stop.is_set():
                try:
                    ids, scores = store.search(q, k=5)
                    assert ids.shape == (4, 5)
                    assert np.all(np.isfinite(scores[ids >= 0]))
                    answered[0] += 1
                except Exception as e:  # noqa: BLE001 — the contract
                    errs.append(e)
                    return

        threads = [threading.Thread(target=searcher) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            for gen_round in range(8):
                base = 32 + gen_round * 16
                store.upsert(np.arange(base, base + 16),
                             rng.normal(size=(16, dim)))
                store.publish()
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert errs == []
        assert answered[0] > 0
        assert store.generation == 9

    def test_engine_search_across_swap(self):
        """The engine /search surface rides the same snapshot
        discipline — swaps under live HTTP traffic fail nothing."""
        net = tiny_net()
        eng = ServingEngine(model=net, input_shape=(8,)).start()
        try:
            rng = np.random.default_rng(21)
            store = VectorStore(12, capacity=256, kind="exact", name="es")
            corpus = eng.embed(rng.normal(size=(32, 8)).astype(np.float32))
            store.upsert(np.arange(32), corpus)
            store.publish()
            eng.register_index("es", store)
            q = corpus[0].tolist()
            stop = threading.Event()
            errs = []

            def client():
                while not stop.is_set():
                    try:
                        r = _post(eng.port, "/search",
                                  {"index": "es", "query": q, "k": 3})
                        assert len(r["ids"][0]) == 3
                    except Exception as e:  # noqa: BLE001
                        errs.append(e)
                        return

            t = threading.Thread(target=client)
            t.start()
            try:
                for i in range(5):
                    store.upsert([100 + i], rng.normal(size=(1, 12)))
                    store.publish()
            finally:
                stop.set()
                t.join()
            assert errs == []
        finally:
            eng.stop()


class TestDriftVeto:
    def _drifted_monitor(self, dim=8):
        drift = DriftMonitor((np.zeros(dim), np.ones(dim)), min_rows=16)
        drift.observe(np.full((32, dim), 50.0, np.float32))  # z = 50
        assert drift.check()["alarmed"]
        return drift

    def test_veto_blocks_publish(self):
        """Contract (d): a latched alarm vetoes; generation unmoved."""
        store = VectorStore(8, capacity=64, kind="exact", name="veto")
        store.upsert(np.arange(8), np.eye(8, dtype=np.float32))
        store.publish()
        assert store.generation == 1
        store.upsert([9], [np.ones(8, np.float32)])
        drift = self._drifted_monitor()
        with pytest.raises(PublishVetoed):
            store.publish(drift=drift)
        assert store.generation == 1  # unmoved
        assert store.retrieval_stats.snapshot()["publish_vetoes"] == 1
        # the staged row is NOT lost — a forced publish lands it
        store.publish(drift=drift, force=True)
        assert store.generation == 2
        ids, _ = store.search(np.ones((1, 8), np.float32), k=1)
        assert ids[0][0] == 9

    def test_feed_once_reports_veto(self):
        store = VectorStore(8, capacity=64, kind="exact", name="feedveto")
        drift = self._drifted_monitor()
        src = StreamSource(watermark=8, idle_s=0.05)
        src.push(DataSet(np.eye(8, dtype=np.float32)[:4],
                         np.arange(4, dtype=np.float32)[:, None]))
        report = store.feed_once(src, drift=drift)
        assert report["vetoed"] and not report["published"]
        assert report["generation"] == 0
        src.close()


class TestOnlineFeed:
    def test_stream_fed_window_publishes(self):
        rng = np.random.default_rng(30)
        store = VectorStore(8, capacity=128, kind="exact", name="feed")
        src = StreamSource(watermark=16, idle_s=0.05)
        vecs = rng.normal(size=(12, 8)).astype(np.float32)
        src.push(DataSet(vecs[:8], np.arange(8, dtype=np.float32)[:, None]))
        src.push(DataSet(vecs[8:], np.arange(8, 12,
                                             dtype=np.float32)[:, None]))
        report = store.feed_once(src)
        assert report["batches"] == 2
        assert report["upserted"] == 12
        assert report["published"] and report["generation"] == 1
        # delete op rides a tuple batch
        src.push(("delete", np.arange(6)))
        report = store.feed_once(src)
        assert report["deleted"] == 6 and report["generation"] == 2
        assert store.rows == 6
        src.close()
        snap = store.retrieval_stats.snapshot()
        assert snap["feed_windows"] == 2 and snap["feed_batches"] == 3


class TestSatellites:
    def test_knobs_registered(self):
        names = envknob.knob_names()
        for knob in ("DL4J_TPU_EMBED_LAYER", "DL4J_TPU_EMBED_POOL",
                     "DL4J_TPU_ANN_ROWS", "DL4J_TPU_ANN_CLUSTERS",
                     "DL4J_TPU_ANN_NPROBE"):
            assert knob in names, f"{knob} missing from ops/env.py"

    def test_ann_rows_knob_sizes_capacity(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_ANN_ROWS", "77")
        store = VectorStore(8, name="knob")
        assert store.capacity == 77

    def test_auto_capacity_is_aot(self, monkeypatch):
        from deeplearning4j_tpu.ops import memory

        monkeypatch.setenv("DL4J_TPU_HBM_GB", "16")
        rows = memory.ann_arena_rows(64)
        assert rows >= 1024  # closed-form, no device involved
        monkeypatch.setenv("DL4J_TPU_ANN_ROWS", "0")
        store = VectorStore(64, name="auto")
        assert store.capacity == rows

    def test_models_reports_embed_and_indexes(self, engine):
        eng, _ = engine
        store = VectorStore(12, capacity=64, kind="exact", name="default")
        store.upsert([0], np.ones((1, 12), np.float32))
        store.publish()
        eng.register_index("default", store)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{eng.port}/models", timeout=30) as resp:
            m = json.load(resp)
        assert m["embed"]["default@v1"] == {"kind": "feedforward",
                                            "dim": 12}
        rep = m["indexes"]["default"]
        assert rep["rows"] == 1 and rep["capacity"] == 64
        assert rep["generation"] == 1
        assert rep["arena_bytes"] == 65 * 12 * 4

    def test_ledger_registered_with_obs(self):
        from deeplearning4j_tpu.obs import registry as obs_registry

        store = VectorStore(8, capacity=16, name="ledger")
        reg = obs_registry.default_registry()
        assert reg.ledgers(store)["retrieval_stats"] is store.retrieval_stats

    def test_search_unknown_index_is_client_error(self, engine):
        from deeplearning4j_tpu.serving.resilience import ClientRequestError

        eng, _ = engine
        with pytest.raises(ClientRequestError):
            eng.search("nope", np.zeros((1, 4), np.float32))
