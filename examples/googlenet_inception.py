"""GoogLeNet / Inception-v1 — the DAG the graph machinery exists for:
nine four-tower inception modules merged on the channel axis, plus the
paper's auxiliary softmax heads as extra graph OUTPUTS (multi-output
training: one label array per head). Runs a tiny 64px smoke train on the
virtual CPU mesh; identical code drives a TPU at 224px."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from deeplearning4j_tpu.models.googlenet import build_googlenet  # noqa: E402
from deeplearning4j_tpu.ops import env as envknob


# tiny-shape mode for the `-m examples` smoke tier (tests/test_examples.py)
SMOKE = envknob.nonempty("DL4J_TPU_EXAMPLE_SMOKE")


def main():
    rng = np.random.default_rng(0)
    size, batch = (32, 4) if SMOKE else (64, 8)
    net = build_googlenet(input_size=size, num_classes=10, aux_heads=True)
    print(f"GoogLeNet (aux heads): {net.num_params()/1e6:.2f}M params, "
          f"{len(net.conf.outputs)} outputs")
    x = rng.random((batch, size, size, 3)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]
    for step in range(2 if SMOKE else 5):
        loss = float(net.fit(x, [y, y, y]))  # main + two aux heads
        print(f"step {step}: summed 3-head loss {loss:.3f}")
    main_out = net.output(x)[0]
    print(f"main head output: {main_out.shape}, "
          f"row sums {np.asarray(main_out).sum(1)[:3].round(3)}")


if __name__ == "__main__":
    main()
