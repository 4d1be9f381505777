"""North-star CPU↔accelerator equivalence harness.

BASELINE.json's north_star demands "CPU-bitwise-equivalent loss curves for
100 steps from stock dl4j-examples entrypoints". SURVEY.md §7 "Hard parts"
refines this: bf16 MXU matmuls and fused reductions make literal bitwise
equality unattainable, so the bar is float32-strict mode
(`jax.default_matmul_precision('float32')`) + identical RNG streams, with a
measured, tolerance-bounded max deviation.

This module trains the SAME model config with the SAME data and seed once on
the CPU backend and once on the default (accelerator) backend and reports
per-step loss curves and their deviation. Our RNG is jax's counter-based
threefry, so the dropout/init streams are identical across backends by
construction — remaining deviation is reduction order + libm differences.

Used by: bench.py (emits the deviation + writes NORTHSTAR artifact) and
tests/test_equivalence.py (determinism + tolerance gates on the CPU mesh).
"""

from __future__ import annotations

import json
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np


def loss_curve(
    net_builder: Callable[[], object],
    batches: Sequence[Tuple[np.ndarray, np.ndarray]],
    device=None,
    matmul_precision: str = "float32",
) -> np.ndarray:
    """Train a fresh net over `batches` (one fit per batch) and return the
    per-step loss curve. float32-strict matmuls by default (the equivalence
    mode; pass None to benchmark native precision instead)."""
    import contextlib

    import jax

    ctx = (
        jax.default_matmul_precision(matmul_precision)
        if matmul_precision
        else contextlib.nullcontext()
    )
    dev_ctx = jax.default_device(device) if device is not None else contextlib.nullcontext()
    # strict mode compares MATH, not kernels: force the XLA paths (lax.scan
    # LSTM, dense attention) on both legs — the default-on TPU pallas
    # kernels are bench-verified equivalent, but their in-kernel reduction
    # order differs, and the strict curve should isolate backend numerics
    from deeplearning4j_tpu.ops.pallas_kernels import pallas_disabled
    from deeplearning4j_tpu.ops.precision import strict_conv_3pass

    kern_ctx = (pallas_disabled() if matmul_precision == "float32"
                else contextlib.nullcontext())
    # strict convs via the bf16x3 decomposition on BOTH legs: the HIGHEST-
    # precision conv compile wedges the remote compile helper, and running
    # the same decomposition on CPU and accel isolates backend accumulation
    # order (ops/precision.py)
    conv_ctx = (strict_conv_3pass() if matmul_precision == "float32"
                else contextlib.nullcontext())
    with kern_ctx, conv_ctx, ctx, dev_ctx:
        net = net_builder()
        losses = []
        for x, y in batches:
            # keep losses device-resident: a float() per step is 100
            # synchronous device round-trips; one bulk readback at the
            # end has a data dependency on every step
            losses.append(net.fit(x, y))
        import jax.numpy as jnp

        stacked = jnp.stack([jnp.asarray(l) for l in losses])
        out = np.asarray(stacked, np.float64)  # ONE bulk transfer
    return out


def compare_backends(
    net_builder: Callable[[], object],
    batches: Sequence[Tuple[np.ndarray, np.ndarray]],
    steps: Optional[int] = None,
    accel_matmul_precision: str = "float32",
    precision_note: Optional[str] = None,
) -> Dict:
    """Run the 100-step (or `steps`-step) curve on the CPU backend and on the
    default backend in float32-strict mode; report both curves and their
    max absolute / relative deviation.

    When the default backend IS cpu (the test environment), this degenerates
    to a two-run determinism check — deviation must then be exactly 0."""
    import jax

    if steps is not None:
        batches = batches[:steps]
    cpu = jax.local_devices(backend="cpu")[0]
    default_dev = jax.devices()[0]

    curve_cpu = loss_curve(net_builder, batches, device=cpu,
                           matmul_precision="float32")
    curve_acc = loss_curve(net_builder, batches, device=default_dev,
                           matmul_precision=accel_matmul_precision)
    abs_dev = np.abs(curve_acc - curve_cpu)
    denom = np.maximum(np.abs(curve_cpu), 1e-12)
    return {
        "steps": len(batches),
        "backend_cpu": str(cpu.platform),
        "backend_accel": str(default_dev.platform),
        "accel_matmul_precision": accel_matmul_precision or "default",
        **({"precision_note": precision_note} if precision_note else {}),
        "same_backend": cpu.platform == default_dev.platform,
        "curve_cpu": curve_cpu.tolist(),
        "curve_accel": curve_acc.tolist(),
        "max_abs_deviation": float(abs_dev.max()) if len(batches) else 0.0,
        "max_rel_deviation": float((abs_dev / denom).max()) if len(batches) else 0.0,
        "final_loss_cpu": float(curve_cpu[-1]) if len(batches) else None,
        "final_loss_accel": float(curve_acc[-1]) if len(batches) else None,
    }


def mnist_batches(
    n_steps: int = 100, batch: int = 64, seed: int = 123
) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Deterministic LeNet-style step batches (cycled when the loaded set is
    smaller than n_steps * batch)."""
    from deeplearning4j_tpu.datasets.fetchers import load_mnist_info

    x, y, _ = load_mnist_info(train=True, num_examples=n_steps * batch, download=False)
    reps = -(-n_steps * batch // x.shape[0])
    if reps > 1:
        x = np.concatenate([x] * reps)[: n_steps * batch]
        y = np.concatenate([y] * reps)[: n_steps * batch]
    return [
        (x[i * batch : (i + 1) * batch], y[i * batch : (i + 1) * batch])
        for i in range(n_steps)
    ]


def char_batches(
    n_steps: int = 100, batch: int = 16, seq: int = 32, vocab: int = 40, seed: int = 5
) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Deterministic char-RNN step batches (one-hot next-char prediction)."""
    rng = np.random.default_rng(seed)
    eye = np.eye(vocab, dtype=np.float32)
    out = []
    for _ in range(n_steps):
        ids = rng.integers(0, vocab, (batch, seq + 1))
        out.append((eye[ids[:, :-1]], eye[ids[:, 1:]]))
    return out


def run_north_star(
    steps: int = 100, artifact_path: Optional[str] = None
) -> Dict:
    """The committed north-star run: LeNet-5 and char-RNN 100-step CPU vs
    accelerator curves in float32-strict mode (BASELINE.json north_star;
    reference comparison paths MultiLayerNetwork.fit:1017 on nd4j-native vs
    nd4j-cuda)."""
    from deeplearning4j_tpu.models.char_rnn import char_rnn_conf
    from deeplearning4j_tpu.models.lenet import build_lenet5
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    def lenet_builder():
        return build_lenet5(seed=12345)

    def char_builder():
        net = MultiLayerNetwork(
            char_rnn_conf(40, lstm_size=64, num_layers=1, seed=777,
                          tbptt_length=16)
        )
        return net.init(input_shape=(1, 40))

    # Round-2's accel LeNet leg dropped to default precision because the
    # HIGHEST-precision conv compile wedges the remote compile helper.
    # Round 3 restores a STRICT conv leg via the bf16x3 decomposition
    # (ops/precision.py): matmuls run under default_matmul_precision
    # ('float32') as before, convs as three DEFAULT-precision passes on
    # BOTH legs — fast compile path, f32-class math, deviation isolates
    # backend accumulation order.
    results = {
        "lenet5": compare_backends(
            lenet_builder, mnist_batches(steps),
            precision_note=("strict conv via bf16x3 decomposition on both "
                            "legs (ops/precision.py) — HIGHEST-precision "
                            "conv compiles wedge the remote compile "
                            "helper"),
        ),
        "char_rnn": compare_backends(char_builder, char_batches(steps)),
    }
    if artifact_path:
        with open(artifact_path, "w") as f:
            json.dump(results, f, indent=1)
    return results
