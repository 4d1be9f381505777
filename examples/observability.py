"""Unified observability plane: trace a training run, scrape it, read the
flight recorder.

The obs plane (deeplearning4j_tpu/obs/ — the TPU-native growth of the
reference's IterationListener chain + UI/stats plane,
deeplearning4j-ui-parent) around a plain MLP fit:

  1. ``DL4J_TPU_OBS=1`` turns the span tracer on: every jit dispatch,
     checkpoint phase and staging wait becomes a monotonic-clock span
     with ids + parent ids (host-side events only — no device syncs);
  2. the five telemetry ledgers (dispatch/memory/pipeline/resilience/
     serving) register in ONE MetricsRegistry; a standalone stdlib-HTTP
     exporter serves it as Prometheus text exposition during the fit;
  3. the flight-recorder journal keeps the last-N-events timeline and
     flushes crash-safely — a dead run leaves a readable JSONL file.

Run from the repo root:  python examples/observability.py
"""

import os
import sys
import tempfile
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# obs on for this process BEFORE the framework imports; journal into a
# scratch dir so repeated runs don't collide
os.environ["DL4J_TPU_OBS"] = "1"
os.environ.setdefault(
    "DL4J_TPU_OBS_JOURNAL",
    os.path.join(tempfile.mkdtemp(prefix="obs_example_"), "journal.jsonl"))

import numpy as np  # noqa: E402

from deeplearning4j_tpu import obs  # noqa: E402
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator  # noqa: E402
from deeplearning4j_tpu.nn.conf import (  # noqa: E402
    DenseLayer,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.optimize.listeners import (  # noqa: E402
    DispatchStatsListener,
)
from deeplearning4j_tpu.ops import env as envknob

# tiny-shape mode for the `-m examples` smoke tier (tests/test_examples.py)
SMOKE = envknob.nonempty("DL4J_TPU_EXAMPLE_SMOKE")

N_EXAMPLES = 128 if SMOKE else 1024
HIDDEN = 16 if SMOKE else 128
EPOCHS = 1 if SMOKE else 3
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
    exporter = obs.MetricsExporter().start()
    print(f"=== metrics exporter live at {exporter.url}/metrics ===")

    net = build()
    net.set_listeners(DispatchStatsListener(frequency=4))
    net.fit_iterator(make_iterator(), num_epochs=EPOCHS)

    # -- spans: the per-dispatch timeline the ledgers can't show ----------
    steps = obs.tracer().spans("dispatch.train_step")
    traced = [s for s in steps if s["attrs"].get("traced")]
    assert steps, "tracing was on but no dispatch spans were recorded"
    print(f"=== {len(steps)} train-step dispatch spans "
          f"({len(traced)} traced/compiled, {len(steps) - len(traced)} "
          "compiled-cache hits) ===")
    for s in steps[:3]:
        print(f"    span {s['span_id']} {s['name']} "
              f"{s['duration_s'] * 1e3:.2f}ms attrs={s['attrs']}")

    # -- one Prometheus scrape over every registered ledger ---------------
    with urllib.request.urlopen(exporter.url + "/metrics",
                                timeout=10) as r:
        page = r.read().decode()
    samples = [ln for ln in page.splitlines()
               if ln and not ln.startswith("#")]
    assert any(ln.startswith("dl4j_dispatch_") for ln in samples), \
        "dispatch ledger missing from the scrape"
    print(f"=== /metrics: {len(samples)} Prometheus samples; a taste: ===")
    for ln in samples[:5]:
        print("    " + ln)

    # -- the flight recorder: what a post-mortem would read ---------------
    path = obs.default_journal().flush(fsync=True)
    assert path, "journal flush failed (journal path unwritable?)"
    events = obs.FlightRecorder.load(path)
    assert events, "journal flushed empty — the flight recorder saw nothing"
    print(f"=== flight recorder: {len(events)} events at {path} ===")
    print(f"    last event: {events[-1]['kind']} seq={events[-1]['seq']}")

    exporter.stop()
    print("OK")


if __name__ == "__main__":
    main()
