"""Fault-tolerant training: kill a run mid-fit, resume it bit-exactly.

The resilience runtime (deeplearning4j_tpu/resilience/) around a plain
MLP classification fit:

  1. an UNINTERRUPTED run — the ground truth;
  2. the same run under ResilientTrainer + async CheckpointManager,
     KILLED mid-training by the deterministic chaos harness;
  3. a resumed run pointed at the same checkpoint directory — it
     restores params, updater state, step counters, RNG key and the
     data-iterator cursor, replays the exact remaining batch stream, and
     finishes bit-identical to run 1 (max |param delta| printed — it is
     exactly 0.0, and the stitched loss curve matches element-for-element).

The reference survives worker loss through Spark lineage recomputation;
this shows the TPU-native answer: checkpoint-and-replay with full
training-state capture, so nothing is recomputed and nothing drifts.

Run from the repo root:  python examples/resilient_training.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator  # noqa: E402
from deeplearning4j_tpu.nn.conf import (  # noqa: E402
    DenseLayer,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.resilience import (  # noqa: E402
    ChaosConfig,
    ChaosMonkey,
    CheckpointManager,
    InjectedKill,
    ResilientTrainer,
)
from deeplearning4j_tpu.ops import env as envknob

# tiny-shape mode for the `-m examples` smoke tier (tests/test_examples.py)
SMOKE = envknob.nonempty("DL4J_TPU_EXAMPLE_SMOKE")

N_EXAMPLES = 128 if SMOKE else 512
HIDDEN = 16 if SMOKE else 64
EPOCHS = 2 if SMOKE else 4
BATCH = 16


def build() -> MultiLayerNetwork:
    conf = (
        NeuralNetConfiguration.builder().seed(42).learning_rate(0.05)
        .updater("adam").list()
        .layer(0, DenseLayer(n_in=8, n_out=HIDDEN, activation="relu"))
        .layer(1, OutputLayer(n_in=HIDDEN, n_out=4, activation="softmax",
                              loss_function="mcxent"))
        .build()
    )
    return MultiLayerNetwork(conf)


def make_iterator() -> ListDataSetIterator:
    rng = np.random.default_rng(1)
    centers = rng.standard_normal((4, 8)) * 2.0
    labels = rng.integers(0, 4, N_EXAMPLES)
    x = (centers[labels] + rng.standard_normal((N_EXAMPLES, 8))).astype(
        np.float32)
    y = np.eye(4, dtype=np.float32)[labels]
    return ListDataSetIterator(x, y, batch=BATCH)


def main() -> None:
    steps_per_epoch = N_EXAMPLES // BATCH
    kill_at = steps_per_epoch + 2  # dies early in epoch 2

    print("=== run 1: uninterrupted (ground truth) ===")
    truth = ResilientTrainer(build())
    truth.fit(make_iterator(), num_epochs=EPOCHS)
    print(f"    {truth.step} steps, final loss {truth.losses[-1]:.4f}")

    with tempfile.TemporaryDirectory() as ckdir:
        print(f"=== run 2: checkpointed (async, every 4 steps), killed "
              f"at step {kill_at} ===")
        mgr = CheckpointManager(ckdir, every_steps=4, keep_last=3)
        chaos = ChaosMonkey(ChaosConfig(kill_at_step=kill_at))
        victim = ResilientTrainer(build(), mgr, chaos=chaos)
        try:
            victim.fit(make_iterator(), num_epochs=EPOCHS)
        except InjectedKill as e:
            print(f"    KILLED: {e}")
        mgr.close()
        kept = [s for s, _ in mgr.checkpoints()]
        print(f"    checkpoints on disk: steps {kept}")

        print("=== run 3: resume from the newest intact checkpoint ===")
        mgr2 = CheckpointManager(ckdir, every_steps=4, keep_last=3)
        survivor = ResilientTrainer(build(), mgr2)
        survivor.fit(make_iterator(), num_epochs=EPOCHS)
        mgr2.close()
        print(f"    resumed at step {survivor.resumed_step}, finished at "
              f"step {survivor.step}")

    stitched = victim.losses[:survivor.resumed_step] + survivor.losses
    curve_ok = stitched == truth.losses
    max_dev = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(jax.tree_util.tree_leaves(truth.net.params),
                        jax.tree_util.tree_leaves(survivor.net.params))
    )
    print("=== verdict ===")
    print(f"    loss curve (pre-kill prefix + resumed) == uninterrupted: "
          f"{curve_ok}")
    print(f"    max |param delta| vs uninterrupted: {max_dev}")
    if not curve_ok or max_dev != 0.0:
        raise SystemExit("resume was not bit-exact")
    print("    interrupted-and-resumed training == uninterrupted training")


if __name__ == "__main__":
    main()
