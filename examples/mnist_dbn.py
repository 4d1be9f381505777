"""Stacked-RBM DBN on MNIST — the reference's DBNMnistFullExample flow:
layerwise contrastive-divergence pretraining, then supervised fine-tune."""


import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deeplearning4j_tpu.datasets.fetchers import load_mnist_info
from deeplearning4j_tpu.eval.evaluation import Evaluation
from deeplearning4j_tpu.models.dbn import build_dbn
from deeplearning4j_tpu.ops import env as envknob


# tiny-shape mode for the `-m examples` smoke tier (tests/test_examples.py)
SMOKE = envknob.nonempty("DL4J_TPU_EXAMPLE_SMOKE")


def main():
    x, y, provenance = load_mnist_info(train=True,
                                       num_examples=256 if SMOKE else 1024,
                                       binarize=True)
    xt, yt, _ = load_mnist_info(train=False, num_examples=256, binarize=True)
    x, xt = x.reshape(len(x), -1), xt.reshape(len(xt), -1)
    print(f"data: {provenance}")

    net = build_dbn(n_in=784, hidden=(256, 128), num_classes=10,
                    learning_rate=0.05)
    print("pretraining (layerwise CD-1)...")
    net.pretrain(x, num_epochs=1)

    print("fine-tuning...")
    batch = 128
    for epoch in range(1 if SMOKE else 3):
        losses = [float(net.fit(x[i:i + batch], y[i:i + batch]))
                  for i in range(0, len(x), batch)]
        print(f"epoch {epoch}: mean loss {np.mean(losses):.4f}")

    ev = Evaluation(num_classes=10)
    ev.eval(yt, np.asarray(net.output(xt)))
    print(f"test accuracy: {ev.accuracy():.3f}")


if __name__ == "__main__":
    main()
