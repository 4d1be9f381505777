"""UI tests — mirrors the reference UI test strategy (SURVEY.md section 4:
TestComponentSerialization, TestRendering, ApiTest server smoke)."""

import json
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import (
    DenseLayer,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ui import (
    ChartHistogram,
    ChartHorizontalBar,
    ChartLine,
    ChartScatter,
    ChartStackedArea,
    ChartTimeline,
    ComponentImage,
    ComponentTable,
    ComponentText,
    FlowIterationListener,
    HistogramIterationListener,
    HistoryStorage,
    UiServer,
    component_from_dict,
    render_page,
)


def all_components():
    line = ChartLine(title="L").add_series("a", [0, 1, 2], [1.0, 0.5, 0.2])
    line.add_series("b", [0, 1, 2], [0.2, 0.3, 0.4])
    scatter = ChartScatter(title="S").add_series("pts", [0, 1], [1, 0])
    hist = ChartHistogram(title="H").add_bin(0, 1, 5).add_bin(1, 2, 3)
    stacked = ChartStackedArea(title="SA")
    stacked.add_series("x", [0, 1, 2], [1, 1, 1])
    stacked.add_series("y", [0, 1, 2], [2, 1, 0.5])
    bars = ChartHorizontalBar(title="B").add_bar("w", 3.0).add_bar("b", 1.5)
    tl = ChartTimeline(title="T").add_lane("w0", [(0, 10, "fit"), (10, 12, "avg")])
    table = ComponentTable(title="tab", header=["a", "b"], rows=[["1", "2"]])
    text = ComponentText(title="", text="hello")
    img = ComponentImage.from_array(
        np.linspace(0, 1, 16).reshape(4, 4), title="filters", scale=8)
    return [line, scatter, hist, stacked, bars, tl, table, text, img]


class TestComponentSerde:
    def test_json_roundtrip_all(self):
        for comp in all_components():
            d = json.loads(comp.to_json())
            restored = component_from_dict(d)
            assert restored.to_dict() == comp.to_dict(), type(comp).__name__

    def test_render_all_produce_markup(self):
        for comp in all_components():
            markup = comp.render()
            assert ("<svg" in markup) or ("<table" in markup) \
                or ("<p" in markup) or ("<img" in markup)

    def test_static_page_export(self, tmp_path):
        page = render_page(all_components(), title="export test")
        assert page.count("<svg") >= 6
        assert "export test" in page
        # self-contained: no external scripts/stylesheets/images (inline
        # data: URIs — ComponentImage — are fine; http(s) refs are not)
        assert "<script" not in page and "<link" not in page
        assert 'src="http' not in page
        assert page.count('src="data:image/png;base64,') == 1


class TestUiServer:
    @pytest.fixture()
    def server(self):
        s = UiServer(port=0).start()
        yield s
        s.stop()

    def _post(self, server, payload):
        req = urllib.request.Request(
            server.url + "/train/update",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=5) as r:
            return r.status

    def test_post_and_summary(self, server):
        assert self._post(server, {"type": "score", "iteration": 0,
                                   "score": 1.5}) == 200
        with urllib.request.urlopen(server.url + "/train/summary", timeout=5) as r:
            summary = json.loads(r.read())
        assert summary["score"]["score"] == 1.5

    def test_dashboard_renders(self, server):
        self._post(server, {"type": "score", "iteration": 0, "score": 2.0})
        self._post(server, {"type": "score", "iteration": 1, "score": 1.0})
        with urllib.request.urlopen(server.url + "/", timeout=5) as r:
            page = r.read().decode()
        assert "Score vs iteration" in page and "<svg" in page

    def test_404(self, server):
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(server.url + "/nope", timeout=5)


def small_net():
    conf = (
        NeuralNetConfiguration.builder()
        .seed(1)
        .learning_rate(0.1)
        .list()
        .layer(0, DenseLayer(n_in=4, n_out=8, activation="tanh"))
        .layer(1, OutputLayer(n_in=8, n_out=3, activation="softmax",
                              loss_function="mcxent"))
        .build()
    )
    return MultiLayerNetwork(conf).init()


class TestListeners:
    def test_histogram_listener_local_storage(self):
        net = small_net()
        listener = HistogramIterationListener(frequency=1)
        net.set_listeners(listener)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(16, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
        net.fit(x, y)
        hist = listener.storage.latest("histogram")
        assert hist is not None
        assert "0_W" in hist["params"]
        assert len(hist["params"]["0_W"]["counts"]) == 20

    def test_flow_listener_topology(self):
        net = small_net()
        listener = FlowIterationListener(frequency=1)
        net.set_listeners(listener)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
        net.fit(x, y)
        flow = listener.storage.latest("flow")
        assert [l["layer_type"] for l in flow["layers"]] == [
            "DenseLayer", "OutputLayer",
        ]

    def test_listener_posts_to_server(self):
        server = UiServer(port=0).start()
        try:
            net = small_net()
            net.set_listeners(
                HistogramIterationListener(frequency=1, server_url=server.url)
            )
            rng = np.random.default_rng(0)
            x = rng.normal(size=(8, 4)).astype(np.float32)
            y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
            net.fit(x, y)
            with urllib.request.urlopen(server.url + "/train/summary",
                                        timeout=5) as r:
                summary = json.loads(r.read())
            assert "histogram" in summary and "score" in summary
            with urllib.request.urlopen(server.url + "/", timeout=5) as r:
                page = r.read().decode()
            assert "<svg" in page
        finally:
            server.stop()


# ------------------------------------------------------- explorer resources
class TestExplorers:
    """t-SNE scatter + VPTree nearest-neighbors explorers (reference
    TsneResource.java / NearestNeighborsResource.java)."""

    def _post(self, url, path, obj):
        import json as _json
        import urllib.request

        req = urllib.request.Request(
            url + path, data=_json.dumps(obj).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            return _json.loads(r.read())

    def _get(self, url, path):
        import json as _json
        import urllib.request

        with urllib.request.urlopen(url + path, timeout=10) as r:
            body = r.read()
            ctype = r.headers.get("Content-Type", "")
        return _json.loads(body) if "json" in ctype else body.decode()

    @pytest.fixture()
    def server(self):
        s = UiServer().start()
        yield s
        s.stop()

    def _embeddings(self, n=30, d=8, clusters=2):
        rng = np.random.default_rng(0)
        words, vecs = [], []
        for c in range(clusters):
            center = rng.standard_normal(d) * 5
            for i in range(n // clusters):
                words.append(f"c{c}_w{i}")
                vecs.append(center + 0.1 * rng.standard_normal(d))
        return words, np.asarray(vecs, np.float32).tolist()

    def test_nearest_neighbors_round_trip(self, server):
        words, vecs = self._embeddings()
        res = self._post(server.url, "/word2vec/upload",
                         {"words": words, "vectors": vecs})
        assert res["words"] == len(words)
        vocab = self._get(server.url, "/word2vec/words")
        assert vocab["words"] == words
        out = self._post(server.url, "/word2vec/nearest",
                         {"word": "c0_w0", "k": 5})
        names = [n["word"] for n in out["neighbors"]]
        assert len(names) == 5
        assert all(n.startswith("c0_") for n in names), names
        assert "c0_w0" not in names  # query word excluded
        # query by raw vector too
        out2 = self._post(server.url, "/word2vec/nearest",
                          {"vector": vecs[0], "k": 3})
        assert len(out2["neighbors"]) == 3

    def test_nearest_unknown_word_400(self, server):
        import urllib.error

        self._post(server.url, "/word2vec/upload",
                   {"words": ["a", "b"], "vectors": [[1, 0], [0, 1]]})
        with pytest.raises(urllib.error.HTTPError) as ei:
            self._post(server.url, "/word2vec/nearest", {"word": "zzz"})
        assert ei.value.code == 400

    def test_tsne_upload_and_render(self, server):
        words, vecs = self._embeddings(n=24)
        res = self._post(server.url, "/tsne/upload",
                         {"words": words, "vectors": vecs,
                          "iterations": 50})
        assert res["points"] == len(words)
        coords = self._get(server.url, "/tsne/coords")
        assert len(coords["coords"]) == len(words)
        assert all(len(c) == 2 for c in coords["coords"])
        page = self._get(server.url, "/tsne")
        assert "svg" in page.lower()

    def test_tsne_update_precomputed(self, server):
        self._post(server.url, "/tsne/update",
                   {"words": ["x", "y"], "coords": [[0, 1], [2, 3]]})
        coords = self._get(server.url, "/tsne/coords")
        assert coords == {"words": ["x", "y"], "coords": [[0.0, 1.0], [2.0, 3.0]]}
        page = self._get(server.url, "/tsne")
        assert "svg" in page.lower()
