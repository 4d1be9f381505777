"""Early stopping on Iris — the reference's EarlyStoppingMNIST pattern:
score calculator + epoch/iteration terminations + best-model saver."""


import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.datasets.fetchers import IrisDataSetIterator
from deeplearning4j_tpu.earlystopping.config import EarlyStoppingConfiguration
from deeplearning4j_tpu.earlystopping.savers import InMemoryModelSaver
from deeplearning4j_tpu.earlystopping.scorecalc import DataSetLossCalculator
from deeplearning4j_tpu.earlystopping.terminations import (
    InvalidScoreIterationTerminationCondition,
    MaxEpochsTerminationCondition,
    ScoreImprovementEpochTerminationCondition,
)
from deeplearning4j_tpu.earlystopping.trainer import EarlyStoppingTrainer
from deeplearning4j_tpu.nn.conf import (
    DenseLayer,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import env as envknob


# tiny-shape mode for the `-m examples` smoke tier (tests/test_examples.py)
SMOKE = envknob.nonempty("DL4J_TPU_EXAMPLE_SMOKE")


def main():
    conf = (
        NeuralNetConfiguration.builder()
        .seed(42)
        .learning_rate(0.1)
        .updater("adam")
        .weight_init("xavier")
        .list()
        .layer(0, DenseLayer(n_in=4, n_out=16, activation="relu"))
        .layer(1, OutputLayer(n_in=16, n_out=3, activation="softmax",
                              loss_function="negativeloglikelihood"))
        .build()
    )
    net = MultiLayerNetwork(conf).init(input_shape=(1, 4))

    train_iter = IrisDataSetIterator(batch=32, num_examples=120)
    val_iter = IrisDataSetIterator(batch=30, num_examples=150)

    es_conf = (
        EarlyStoppingConfiguration.builder()
        .score_calculator(DataSetLossCalculator(val_iter))
        .epoch_termination_conditions(
            MaxEpochsTerminationCondition(6 if SMOKE else 50),
            ScoreImprovementEpochTerminationCondition(2 if SMOKE else 8),
        )
        .iteration_termination_conditions(
            InvalidScoreIterationTerminationCondition())
        .model_saver(InMemoryModelSaver())
        .build()
    )
    result = EarlyStoppingTrainer(es_conf, net, train_iter).fit()
    print(f"terminated: {result.termination_reason} "
          f"({result.termination_details})")
    print(f"best epoch {result.best_model_epoch}, "
          f"best score {result.best_model_score:.4f}, "
          f"epochs run {result.total_epochs}")


if __name__ == "__main__":
    main()
