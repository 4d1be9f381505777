"""LeNet-5 on MNIST — the reference's LenetMnistExample, TPU-native.

Builds the conf through the DSL, trains with the single jitted train step,
evaluates, and writes a ModelSerializer checkpoint."""


import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deeplearning4j_tpu.datasets.fetchers import load_mnist_info
from deeplearning4j_tpu.eval.evaluation import Evaluation
from deeplearning4j_tpu.models.lenet import build_lenet5
from deeplearning4j_tpu.utils.serialization import ModelSerializer
from deeplearning4j_tpu.ops import env as envknob


# tiny-shape mode for the `-m examples` smoke tier (tests/test_examples.py):
# the stock flow unchanged, just fewer examples/epochs so 11 entrypoints
# finish in minutes on the 1-core CPU host
SMOKE = envknob.nonempty("DL4J_TPU_EXAMPLE_SMOKE")


def main():
    net = build_lenet5()
    x, y, provenance = load_mnist_info(train=True,
                                       num_examples=512 if SMOKE else 2048)
    xt, yt, _ = load_mnist_info(train=False, num_examples=512)
    print(f"data: {provenance}; train {x.shape}, test {xt.shape}")

    batch = 256
    for epoch in range(1 if SMOKE else 3):
        perm = np.random.default_rng(epoch).permutation(len(x))
        losses = []
        for i in range(0, len(x), batch):
            idx = perm[i:i + batch]
            losses.append(float(net.fit(x[idx], y[idx])))
        print(f"epoch {epoch}: mean loss {np.mean(losses):.4f}")

    ev = Evaluation(num_classes=10)
    ev.eval(yt, np.asarray(net.output(xt)))
    print(ev.stats())

    ModelSerializer.write_model(net, "/tmp/lenet_mnist.zip")
    print("checkpoint written to /tmp/lenet_mnist.zip")


if __name__ == "__main__":
    main()
