"""Multi-host data parallelism: one training script, N OS processes.

The reference scales out by running one Spark executor per partition that
feeds a ParameterAveragingTrainingMaster
(dl4j-spark SparkDl4jMultiLayer.fit — SURVEY.md section 2.3). The
TPU-native shape of that plane is jax.distributed: one controller process
per host, XLA collectives over ICI/DCN, each process feeding ONLY the
examples it loaded (`multihost.put_batch` assembles the global array with
zero cross-host data movement).

This example launches the 2-process cluster LOCALLY (CPU devices, Gloo
collectives) — the exact same script a TPU pod runs per host, where the
provisioner (provision/tpu_pod.py) injects the same env contract. Run:

    python examples/multihost_dp.py            # parent: spawns 2 workers
    # or launch each worker yourself (the full contract, one process each):
    DL4J_TPU_COORDINATOR=host:port DL4J_TPU_NUM_PROCESSES=2 \
        DL4J_TPU_PROCESS_ID=<0|1> python examples/multihost_dp.py

Each worker trains the same MLP data-parallel over the global mesh and
verifies its parameters track a serial run to float32 tolerance (the
gradient psum reduces in a different order than the serial batch sum;
tests/test_multihost_cpu.py pins BIT-exactness under float64).
"""
import os
import socket
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.parallel import multihost  # noqa: E402

N_PROCESSES = 2


def worker() -> None:
    import jax

    from deeplearning4j_tpu.parallel.mesh import virtual_cpu_devices

    virtual_cpu_devices(2)

    import numpy as np
    from jax.sharding import Mesh

    from deeplearning4j_tpu.nn.conf.builder import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper

    multihost.initialize_multihost()
    info = multihost.process_info()
    print(f"[proc {info['process_index']}] sees "
          f"{info['local_device_count']} local / "
          f"{info['global_device_count']} global devices", flush=True)

    def build():
        conf = (
            NeuralNetConfiguration.builder()
            .seed(7).learning_rate(0.1).updater("sgd")
            .list()
            .layer(0, DenseLayer(n_in=8, n_out=16, activation="tanh"))
            .layer(1, OutputLayer(n_in=16, n_out=3, activation="softmax",
                                  loss_function="mcxent"))
            .build()
        )
        return MultiLayerNetwork(conf).init()

    rng = np.random.RandomState(0)
    X = rng.randn(32, 8)
    Y = np.eye(3)[rng.randint(0, 3, size=32)]

    serial = build()
    for _ in range(10):
        serial.fit(X, Y)

    net = build()
    mesh = Mesh(np.array(jax.devices()), ("data",))
    pw = ParallelWrapper(net, mesh=mesh)
    sl = multihost.local_batch_slice(len(X))  # this process's shard
    for _ in range(10):
        loss = pw.fit(X[sl], Y[sl])

    dev = max(
        float(abs(np.asarray(a) - np.asarray(b)).max())
        for a, b in zip(jax.tree_util.tree_leaves(serial.params),
                        jax.tree_util.tree_leaves(net.params))
    )
    print(f"[proc {info['process_index']}] final loss {float(loss):.6f}, "
          f"max param deviation vs serial: {dev:.2e}", flush=True)
    assert dev < 1e-5, dev


def parent() -> None:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    for pid in range(N_PROCESSES):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env[multihost.COORDINATOR_ENV] = f"127.0.0.1:{port}"
        env[multihost.NUM_PROCESSES_ENV] = str(N_PROCESSES)
        env[multihost.PROCESS_ID_ENV] = str(pid)
        procs.append(subprocess.Popen([sys.executable, __file__], env=env))
    rcs = [p.wait() for p in procs]
    if any(rcs):
        raise SystemExit(f"worker failures: {rcs}")
    print("both processes trained data-parallel, matching serial")


if __name__ == "__main__":
    if os.environ.get(multihost.COORDINATOR_ENV):
        worker()
    else:
        parent()
