"""DataVec-parity ETL: schema'd transforms, fitted normalizers, and the
overlapped InputPipeline (deeplearning4j_tpu/etl/).

The 2016 DataVec workflow, end to end, on a real on-disk CSV:

  1. a typed Schema + TransformProcess (drop a column, filter bad rows,
     one-hot a categorical, add a rolling mean) compiled into one record
     function;
  2. a NormalizerStandardize FITTED over the training stream (one pass,
     streaming statistics) — not per-batch statistics;
  3. an InputPipeline: parallel off-thread transform + vectorized batch
     assembly, deterministic batch order (byte-identical to direct
     iteration — asserted below), double-buffered device staging, and
     the pipeline_stats stall ledger;
  4. the fitted statistics ride the ModelSerializer zip, so a reloaded
     model + normalizer predicts identically to the live one.

Run from the repo root:  python examples/etl_pipeline.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from deeplearning4j_tpu.datasets.records import (  # noqa: E402
    CSVRecordReader,
    RecordReaderDataSetIterator,
)
from deeplearning4j_tpu.etl import (  # noqa: E402
    InputPipeline,
    NormalizerStandardize,
    Schema,
    TransformProcess,
)
from deeplearning4j_tpu.etl.transforms import (  # noqa: E402
    TransformProcessRecordReader,
)
from deeplearning4j_tpu.nn.conf import (  # noqa: E402
    DenseLayer,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.utils.serialization import (  # noqa: E402
    ModelSerializer,
    read_normalizer,
)
from deeplearning4j_tpu.ops import env as envknob

# tiny-shape mode for the `-m examples` smoke tier (tests/test_examples.py)
SMOKE = envknob.nonempty("DL4J_TPU_EXAMPLE_SMOKE")

ROWS = 400 if SMOKE else 4000
BATCH = 32
EPOCHS = 1 if SMOKE else 3
WORKERS = 2
CLASSES = 3
SPECIES = ["setosa", "versicolor", "virginica"]


def write_csv(path: str) -> None:
    """Synthetic iris-shaped CSV: 4 numeric columns, a throwaway id, a
    categorical species column, a label — plus a few deliberately broken
    rows the filter step must drop."""
    rng = np.random.default_rng(42)
    with open(path, "w") as f:
        f.write("id,f0,f1,f2,f3,species,label\n")
        for i in range(ROWS):
            label = int(rng.integers(0, CLASSES))
            feats = rng.standard_normal(4) + label
            if i % 97 == 0:  # corrupt row -> filtered by the transform
                f.write(f"{i},oops,,x,y,{SPECIES[label]},{label}\n")
                continue
            f.write(f"{i}," + ",".join(f"{v:.6f}" for v in feats)
                    + f",{SPECIES[label]},{label}\n")


def build_transform() -> TransformProcess:
    schema = (Schema.builder()
              .add_integer_column("id")
              .add_numeric_column("f0", "f1", "f2", "f3")
              .add_categorical_column("species", SPECIES)
              .add_integer_column("label")
              .build())
    return (TransformProcess(schema)
            .remove_columns("id")
            .filter_invalid(["f0", "f1", "f2", "f3"])   # drop corrupt rows
            .one_hot("species")                          # 3 extra columns
            .rolling_window("f0", 4, "mean"))            # time-window feat


def build_net(n_in: int) -> MultiLayerNetwork:
    conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.05)
            .updater("adam").list()
            .layer(0, DenseLayer(n_in=n_in, n_out=16, activation="tanh"))
            .layer(1, OutputLayer(n_in=16, n_out=CLASSES,
                                  activation="softmax",
                                  loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf)


def main() -> None:
    work = tempfile.mkdtemp(prefix="etl_example_")
    csv = os.path.join(work, "iris_like.csv")
    write_csv(csv)

    tp = build_transform()
    final = tp.final_schema()
    label_idx = final.index_of("label")
    n_features = final.num_columns() - 1
    print(f"transformed schema: {final.names()}")

    reader = lambda: CSVRecordReader(csv, skip_lines=1)

    # fitted statistics: ONE streaming pass over the transformed stream
    norm = NormalizerStandardize().fit(RecordReaderDataSetIterator(
        TransformProcessRecordReader(reader(), tp), BATCH,
        label_index=label_idx, num_possible_labels=CLASSES))

    pipeline = InputPipeline.from_reader(
        reader(), BATCH, label_index=label_idx,
        num_possible_labels=CLASSES, transform=tp, normalizer=norm,
        workers=WORKERS, prefetch=4)

    # pipeline == serial contract (the test suite proves it at byte
    # level; the example spot-checks the first batch)
    direct = RecordReaderDataSetIterator(
        TransformProcessRecordReader(reader(), tp), BATCH,
        label_index=label_idx, num_possible_labels=CLASSES)
    first_direct = next(iter(direct))
    norm.transform(first_direct)
    first_piped = next(iter(pipeline))
    assert (np.asarray(first_piped.features).tobytes()
            == np.asarray(first_direct.features).tobytes()), \
        "pipeline diverged from direct iteration"
    print("pipeline == direct iteration: byte-identical first batch")

    net = build_net(n_features)
    net.fit_iterator(pipeline, num_epochs=EPOCHS)
    stats = net.pipeline_stats.snapshot()
    print(f"trained {EPOCHS} epoch(s): loss {net.score_value:.4f}")
    print(f"pipeline_stats: {stats['batches']} batches, "
          f"{stats['records_per_sec']:.0f} records/s, "
          f"stall {stats['stall_fraction']:.0%} of wall, "
          f"producer stall {stats['producer_stall_seconds']:.3f}s")

    # the statistics ride the checkpoint: reloaded model + normalizer
    # predict identically to the live pair
    zip_path = os.path.join(work, "model.zip")
    ModelSerializer.write_model(net, zip_path, normalizer=norm)
    net2 = ModelSerializer.restore(zip_path)
    norm2 = read_normalizer(zip_path)
    probe = np.asarray(first_direct.features)  # already normalized
    live = np.asarray(net.output(probe))
    loaded = np.asarray(net2.output(probe))
    assert live.tobytes() == loaded.tobytes()
    raw = norm.revert_array(probe)
    assert (norm2.transform_array(raw).tobytes()
            == norm.transform_array(raw).tobytes())
    print(f"normalizer rides the zip: reloaded predictions identical "
          f"({type(norm2).__name__})")


if __name__ == "__main__":
    main()
