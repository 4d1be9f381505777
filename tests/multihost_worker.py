"""Worker process for the multi-host CPU harness (test_multihost_cpu.py).

Runs as ONE process of a 2-process jax.distributed cluster, wired through
the SAME env-var contract the pod provisioner injects
(multihost.COORDINATOR_ENV et al.) — the cross-process analogue of the
reference's Spark executor role (SURVEY.md section 2.3: one worker JVM per
partition feeding ParameterAveragingTrainingMaster; here one OS process
per host feeding XLA collectives over Gloo/ICI).

Each worker:
  1. initializes jax.distributed from the env contract,
  2. trains a serial reference net on its own full copy of the data,
  3. trains the SAME net via ParallelWrapper on the global 2-process x
     2-device mesh, feeding only its process-local batch slice,
  4. asserts bit-identical parameters and prints `MH_OK ...` for the
     parent test to collect.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# 2 local devices per process (2 procs x 2 = 4 global); the config wins
# over the parent's 8-device setting
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from deeplearning4j_tpu.nn.conf.builder import NeuralNetConfiguration  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer  # noqa: E402
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.parallel import multihost  # noqa: E402
from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper  # noqa: E402


def build_net(seed=7):
    conf = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .learning_rate(0.1)
        .updater("sgd")
        .list()
        .layer(0, DenseLayer(n_in=8, n_out=16, activation="tanh"))
        .layer(1, OutputLayer(n_in=16, n_out=3, activation="softmax",
                              loss_function="mcxent"))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def main() -> None:
    assert multihost.initialize_multihost(), "env contract not configured"
    info = multihost.process_info()
    assert info["process_count"] == 2, info
    assert info["global_device_count"] == 4, info
    assert multihost.is_multihost()

    # an uneven global batch must raise CONSISTENTLY on every process —
    # a per-process divergence here would deadlock the collectives
    try:
        multihost.local_batch_slice(17)
    except ValueError as e:
        assert "17" in str(e), e
    else:
        raise AssertionError("uneven global batch must raise")

    rng = np.random.RandomState(0)
    X = rng.randn(16, 8)
    Y = np.eye(3)[rng.randint(0, 3, size=16)]

    serial = build_net()
    for _ in range(5):
        serial.fit(X, Y)

    net = build_net()
    mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
    pw = ParallelWrapper(net, mesh=mesh)
    sl = multihost.local_batch_slice(16)
    for _ in range(5):
        loss = pw.fit(X[sl], Y[sl])

    # fused multi-step path too (fit_batches: [K, N, ...] per-process
    # shard of the stacked batches through one lax.scan program)
    Xs = np.stack([X, X[::-1]])
    Ys = np.stack([Y, Y[::-1]])
    serial.fit_batches(Xs, Ys)
    pw.fit_batches(Xs[:, sl], Ys[:, sl])

    dev = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(jax.tree_util.tree_leaves(serial.params),
                        jax.tree_util.tree_leaves(net.params))
    )
    assert dev == 0.0, f"param deviation {dev}"
    print(f"MH_OK proc={info['process_index']} loss={float(loss):.6f} "
          f"max_param_dev={dev}", flush=True)


if __name__ == "__main__":
    main()
