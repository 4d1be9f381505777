"""Embedding & retrieval serving: /embed + device-resident ANN /search.

The retrieval plane (deeplearning4j_tpu/retrieval/ — the serving half
the reference's scaleout-nlp module never grew: its InMemoryLookupTable
answers wordsNearest with a host-side full scan, here the arena lives
on device and top-k is one batched matmul) around a plain MLP encoder:

  1. register a trained net with a ``ServingEngine``; ``/embed`` routes
     its last HIDDEN layer through the same dynamic batcher + bucket
     ladder as ``/predict`` (byte-identical to a direct feed_forward);
  2. embed a corpus, upsert it into a ``VectorStore`` and publish —
     an immutable generation snapshot behind ``/search`` (exact top-k
     oracle + an IVF probe whose recall is MEASURED, never assumed);
  3. mutate the index ONLINE: upserts land in a staging arena, a
     publish swaps generations atomically under live search traffic —
     zero failed requests by construction;
  4. a drifted feed (``online/drift.DriftMonitor``) VETOES the publish
     — the serving generation never moves under a distribution shift.

Run from the repo root:  python examples/retrieval.py
"""

import os
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from deeplearning4j_tpu.nn.conf import (  # noqa: E402
    DenseLayer,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.online import DriftMonitor  # noqa: E402
from deeplearning4j_tpu.ops import env as envknob  # noqa: E402
from deeplearning4j_tpu.retrieval import (  # noqa: E402
    PublishVetoed,
    VectorStore,
)
from deeplearning4j_tpu.serving.engine import ServingEngine  # noqa: E402

SMOKE = envknob.nonempty("DL4J_TPU_EXAMPLE_SMOKE")

N_CORPUS = 256 if SMOKE else 4096
N_CLUSTERS = 8 if SMOKE else 32
FEATURES = 16
HIDDEN = 12 if SMOKE else 32


def build_encoder(seed: int = 7) -> MultiLayerNetwork:
    conf = (NeuralNetConfiguration.builder().seed(seed).list()
            .layer(0, DenseLayer(n_in=FEATURES, n_out=HIDDEN,
                                 activation="relu"))
            .layer(1, OutputLayer(n_in=HIDDEN, n_out=N_CLUSTERS,
                                  activation="softmax",
                                  loss_function="mcxent"))
            .build())
    net = MultiLayerNetwork(conf)
    net.init()
    return net


def clustered_rows(rng, n):
    centers = rng.normal(size=(N_CLUSTERS, FEATURES)).astype(np.float32)
    assign = rng.integers(0, N_CLUSTERS, size=n)
    rows = centers[assign] + 0.1 * rng.normal(size=(n, FEATURES))
    return rows.astype(np.float32), assign


def main():
    rng = np.random.default_rng(0)
    net = build_encoder()
    engine = ServingEngine(model=net, input_shape=(FEATURES,)).start()
    try:
        # -- 1. /embed through the serving batcher ------------------------
        corpus_rows, _ = clustered_rows(rng, N_CORPUS)
        emb = engine.embed(corpus_rows)
        direct = np.asarray(net.feed_forward(corpus_rows, train=False)[-2],
                            np.float32).reshape(N_CORPUS, -1)
        assert np.array_equal(emb, direct), "batcher != direct embed"
        print(f"=== /embed: {emb.shape[0]} rows -> dim {emb.shape[1]} "
              "(byte-identical to direct feed_forward) ===")

        # -- 2. index + publish + measured recall -------------------------
        store = VectorStore(emb.shape[1], capacity=N_CORPUS + 64,
                            kind="ivf", clusters=N_CLUSTERS, nprobe=4,
                            min_ivf_rows=32, name="corpus")
        store.upsert(np.arange(N_CORPUS), emb)
        store.publish()
        engine.register_index("corpus", store)
        probe_rows, _ = clustered_rows(rng, 32)
        recall = store.probe_recall(engine.embed(probe_rows), k=10)
        rep = store.report()
        print(f"=== index: {rep['rows']} rows, generation "
              f"{rep['generation']}, ivf_built={rep['ivf_built']}, "
              f"measured recall@10 {recall:.3f} ===")

        ids, scores = engine.search("corpus", emb[:2], k=3)
        assert ids[0][0] == 0 and ids[1][0] == 1, "self-hit failed"
        print(f"=== /search self-hit: ids {ids.tolist()} ===")

        # -- 3. online mutation under live search traffic -----------------
        stop = threading.Event()
        answered, failed = [0], [0]

        def searcher():
            while not stop.is_set():
                try:
                    engine.search("corpus", emb[:4], k=5)
                    answered[0] += 1
                except Exception:  # noqa: BLE001 — the zero-failure claim
                    failed[0] += 1
                    return

        t = threading.Thread(target=searcher)
        t.start()
        fresh_rows, _ = clustered_rows(rng, 16)
        store.upsert(np.arange(N_CORPUS, N_CORPUS + 16),
                     engine.embed(fresh_rows))
        store.publish()
        stop.set()
        t.join()
        assert failed[0] == 0, "a generation swap failed a live search"
        print(f"=== online publish: generation {store.generation}, "
              f"{answered[0]} live searches answered, {failed[0]} failed ===")

        # -- 4. drift veto -------------------------------------------------
        drift = DriftMonitor((emb.mean(axis=0), emb.std(axis=0) + 1e-6),
                             min_rows=8)
        drift.observe(emb[:16] + 100.0)  # a scripted shift
        store.upsert([N_CORPUS + 63], np.ones((1, emb.shape[1])))
        try:
            store.publish(drift=drift)
            raise AssertionError("drifted publish was not vetoed")
        except PublishVetoed:
            pass
        print(f"=== drift veto: publish blocked, generation still "
              f"{store.generation} ===")
        print("OK")
    finally:
        engine.stop(drain=False)


if __name__ == "__main__":
    main()
