"""ComputationGraph under the parameter-averaging master/trainer.

The reference trains graphs on Spark through the SAME
ParameterAveragingTrainingMaster as MLNs (SparkComputationGraph.java:68
fit(JavaRDD<DataSet>)); its equivalence bar is
TestCompareParameterAveragingSparkVsSingleMachine.java:115-262 — N-worker
freq-1 SGD averaging equals the serial big-batch step. This suite mirrors
both for the graph container, including multi-input/multi-output graphs
(MultiDataSet) and the ResNet-50 flagship in averaging-compatibility mode.
"""

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.nn.conf.builder import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.conf.graph import MergeVertex
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.parallel.data_parallel import ParameterAveragingTrainer
from deeplearning4j_tpu.parallel.training_master import (
    ParameterAveragingTrainingMaster,
    SparkStyleNetwork,
)
from deeplearning4j_tpu.datasets.iterator import DataSet


def _graph(seed=12345, lr=0.1, updater="sgd"):
    conf = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .learning_rate(lr)
        .updater(updater)
        .graph_builder()
        .add_inputs("in")
        .add_layer("d1", DenseLayer(n_in=4, n_out=8, activation="tanh"), "in")
        .add_layer(
            "out",
            OutputLayer(n_in=8, n_out=3, activation="softmax",
                        loss_function="mcxent"),
            "d1",
        )
        .set_outputs("out")
        .build()
    )
    return ComputationGraph(conf).init()


def _data(n=144, seed=0):
    from deeplearning4j_tpu.datasets.fetchers import load_iris

    x, y = load_iris()
    if seed:
        order = np.random.default_rng(seed).permutation(len(x))
        x, y = x[order], y[order]
    return x[:n], y[:n]


def assert_params_close(p1, p2, rtol=1e-5, atol=1e-6):
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)


class TestGraphAveragingTrainer:
    def test_freq1_sgd_equals_big_batch(self):
        """The reference equivalence assertion (:115-262), graph edition:
        averaging 8 independent one-step workers == one big-batch step."""
        x, y = _data()
        avg = _graph(seed=11)
        ParameterAveragingTrainer(avg, num_workers=8,
                                  averaging_frequency=1).fit(x, y)
        serial = _graph(seed=11)
        serial.fit(x, y)
        assert_params_close(serial.params, avg.params)

    def test_multi_round_trains(self):
        x, y = _data()
        net = _graph(seed=13, updater="adam", lr=0.05)
        trainer = ParameterAveragingTrainer(net, num_workers=8,
                                            averaging_frequency=3)
        s0 = net.score(x, y)
        for _ in range(15):
            trainer.fit(x, y)
        assert net.score(x, y) < s0 * 0.8

    @staticmethod
    def _multi_conf():
        return (
            NeuralNetConfiguration.builder()
            .seed(7)
            .learning_rate(0.1)
            .graph_builder()
            .add_inputs("a", "b")
            .add_vertex("m", MergeVertex(), "a", "b")
            .add_layer("d", DenseLayer(n_in=6, n_out=8, activation="tanh"),
                       "m")
            .add_layer("o1", OutputLayer(n_in=8, n_out=3,
                                         activation="softmax",
                                         loss_function="mcxent"), "d")
            .add_layer("o2", OutputLayer(n_in=8, n_out=2,
                                         activation="softmax",
                                         loss_function="mcxent"), "d")
            .set_outputs("o1", "o2")
            .build()
        )

    def test_multi_input_output_graph(self):
        """MultiDataSet analog: two inputs merged, two outputs — the
        dict/list containers must round-trip the worker loop."""
        rng = np.random.default_rng(0)
        n = 64
        xa = rng.normal(size=(n, 4)).astype(np.float32)
        xb = rng.normal(size=(n, 2)).astype(np.float32)
        y1 = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
        y2 = np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)]

        shapes = {"a": (-1, 4), "b": (-1, 2)}
        avg = ComputationGraph(self._multi_conf()).init(input_shapes=shapes)
        ParameterAveragingTrainer(avg, num_workers=8,
                                  averaging_frequency=1).fit(
            [xa, xb], [y1, y2])
        serial = ComputationGraph(self._multi_conf()).init(input_shapes=shapes)
        serial.fit([xa, xb], [y1, y2])
        assert_params_close(serial.params, avg.params)


class TestGraphUnderMaster:
    def test_spark_style_graph_fit(self):
        """SparkComputationGraph.fit(JavaRDD<DataSet>) analog end-to-end:
        master splits, trainer averages, score drops."""
        x, y = _data(n=144, seed=3)
        net = _graph(seed=21, updater="adam", lr=0.05)
        master = ParameterAveragingTrainingMaster(
            num_workers=8, batch_size_per_worker=2, averaging_frequency=3,
            collect_training_stats=True,
        )
        spark_net = SparkStyleNetwork(net, master)
        datasets = [DataSet(x[i:i + 16], y[i:i + 16])
                    for i in range(0, 144, 16)]
        s0 = net.score(x, y)
        for _ in range(6):
            spark_net.fit(datasets)
        assert net.score(x, y) < s0
        stats = master.get_training_stats()
        assert stats is not None and len(stats.events) > 0

    def test_master_multi_component_split(self):
        """Master splitting with list features/labels (MultiDataSet)."""
        rng = np.random.default_rng(0)
        n = 32
        xa = rng.normal(size=(n, 4)).astype(np.float32)
        xb = rng.normal(size=(n, 2)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
        master = ParameterAveragingTrainingMaster(
            num_workers=2, batch_size_per_worker=4, averaging_frequency=2)
        ds = [DataSet([xa, xb], [y])]
        splits = list(master._splits(ds))
        assert len(splits) == 2  # 32 // (2*4*2)
        (fx, fy) = splits[0]
        assert isinstance(fx, list) and fx[0].shape == (16, 4) \
            and fx[1].shape == (16, 2)
        assert isinstance(fy, list) and fy[0].shape == (16, 3)


class TestResNet50AveragingMode:
    def test_resnet50_averaging_round(self):
        """The flagship CNN in averaging-compatibility mode: one full
        averaging round on the 8-worker mesh, params
        move, BN running stats averaged."""
        from deeplearning4j_tpu.models.resnet import build_resnet50

        net = build_resnet50(input_size=32, num_classes=10,
                             learning_rate=0.01, updater="nesterovs")
        rng = np.random.default_rng(0)
        x = rng.random((16, 32, 32, 3)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 16)]
        trainer = ParameterAveragingTrainer(net, num_workers=8,
                                            averaging_frequency=2)
        loss = float(trainer.fit(x, y))
        assert np.isfinite(loss)
        loss2 = float(trainer.fit(x, y))
        assert np.isfinite(loss2)
