"""Distributed-training control plane: TrainingMaster / TrainingWorker.

Capability mirror of the reference Spark training contract (SURVEY.md
sections 2.3 and 3.3):
  - TrainingMaster/TrainingWorker pluggable contract
    (dl4j-spark/.../spark/api/TrainingMaster.java:24-93, TrainingWorker.java)
    with WorkerConfiguration and Repartition strategy;
  - ParameterAveragingTrainingMaster
    (.../impl/paramavg/ParameterAveragingTrainingMaster.java:47): splits the
    incoming data so each split is numWorkers x batchSizePerWorker x
    averagingFrequency examples (:148), runs workers, averages params (+
    updater state), repeats; builder defaults batchSizePerWorker=16,
    averagingFrequency=5 (:463-471);
  - distributed evaluation (SparkDl4jMultiLayer.evaluate ->
    EvaluateFlatMapFunction + EvaluationReduceFunction.java:18-19 merging
    Evaluation objects);
  - training stats collection per phase (stats.py).

TPU-native mapping: "executors" are mesh devices. The data plane
(broadcast params out / aggregate params in) becomes the
ParameterAveragingTrainer's shard_map + pmean over ICI; this module is the
HOST control plane — data splitting, retries, stats, evaluation merge —
exactly the part of the reference that stays on the driver JVM.
"""

from __future__ import annotations

import contextlib
import logging
import time
from dataclasses import dataclass
from typing import Iterable, List, Optional, Sequence

logger = logging.getLogger("deeplearning4j_tpu")

import numpy as np

from deeplearning4j_tpu.datasets.iterator import DataSet
from deeplearning4j_tpu.eval.evaluation import Evaluation
from deeplearning4j_tpu.parallel.data_parallel import (
    ParallelWrapper,
    ParameterAveragingTrainer,
)
from deeplearning4j_tpu.parallel.stats import TrainingStats


@dataclass
class WorkerConfiguration:
    """Reference api/WorkerConfiguration.java."""

    batch_size_per_worker: int = 16
    averaging_frequency: int = 5
    prefetch_num_batches: int = 2
    collect_training_stats: bool = False


class Repartition:
    """Reference api/Repartition enum."""

    ALWAYS = "always"
    NEVER = "never"
    NUM_PARTITIONS_WORKERS_DIFFERS = "num_partitions_workers_differs"


def balanced_splits(n: int, k: int) -> List[slice]:
    """Exact balanced partitioning (reference BalancedPartitioner +
    AssignIndexFunction semantics): first n%k parts get one extra element."""
    base, extra = divmod(n, k)
    out, start = [], 0
    for i in range(k):
        size = base + (1 if i < extra else 0)
        out.append(slice(start, start + size))
        start += size
    return out


class TrainingMaster:
    """Abstract contract (TrainingMaster.java): executeTraining + stats."""

    def execute_training(self, net, iterator) -> None:
        raise NotImplementedError

    def get_training_stats(self) -> Optional[TrainingStats]:
        return None


# -- exported-dataset plane (RDDTrainingApproach.Export role) ---------------

_EXPORT_PREFIX = "dataset_"


def export_datasets(iterator_or_datasets, dest: str,
                    prefix: str = _EXPORT_PREFIX) -> List[str]:
    """Serialize each DataSet minibatch to its own file — the reference's
    export plumbing (ParameterAveragingTrainingMaster split/export,
    :148-168, writing objects a later fit(String path) consumes,
    SparkDl4jMultiLayer.fit:217). One npz per DataSet (the DataSet.save
    role), named {prefix}{i:05d}.npz; dest is a local directory or a
    gs:// prefix (staged locally, pushed via GcsUploader). Returns the
    written paths/URIs."""
    import os
    import shutil
    import tempfile

    datasets = (list(iterator_or_datasets)
                if not isinstance(iterator_or_datasets, (list, tuple))
                else iterator_or_datasets)
    is_gs = dest.startswith("gs://")
    uploader = None
    if is_gs:
        from deeplearning4j_tpu.provision.gcs import GcsUploader

        uploader = GcsUploader()
        stage = tempfile.mkdtemp(prefix="dl4j_export_")
    else:
        stage = dest
        os.makedirs(dest, exist_ok=True)
    paths = []
    try:
        for i, ds in enumerate(datasets):
            arrays = {"features": np.asarray(ds.features),
                      "labels": np.asarray(ds.labels)}
            if getattr(ds, "features_mask", None) is not None:
                arrays["features_mask"] = np.asarray(ds.features_mask)
            if getattr(ds, "labels_mask", None) is not None:
                arrays["labels_mask"] = np.asarray(ds.labels_mask)
            local = os.path.join(stage, f"{prefix}{i:05d}.npz")
            np.savez(local, **arrays)
            if is_gs:
                uri = f"{dest.rstrip('/')}/{prefix}{i:05d}.npz"
                uploader.upload(local, uri)
                os.unlink(local)  # bound staging disk to one minibatch
                paths.append(uri)
            else:
                paths.append(local)
    finally:
        if is_gs:
            shutil.rmtree(stage, ignore_errors=True)
    return paths


def load_exported_datasets(path,
                           prefix: str = _EXPORT_PREFIX) -> Iterable[DataSet]:
    """Read DataSets back from an export location (the sc.binaryFiles +
    deserialize step of fit(String path), SparkDl4jMultiLayer.java:217-221):
    a local directory, an explicit list of files, or a gs:// prefix
    (fetched through GcsDownloader's idempotent cache). Directory reads
    match `prefix` so two exports into one directory under different
    prefixes stay separate runs; files sort by name so the split order is
    deterministic."""
    import glob
    import os
    import tempfile

    if isinstance(path, (list, tuple)):
        files = sorted(path)
    elif path.startswith("gs://"):
        from deeplearning4j_tpu.provision.gcs import (
            BucketIterator,
            GcsDownloader,
        )

        dl = GcsDownloader(tempfile.mkdtemp(prefix="dl4j_fitpath_"))
        # same prefix/.npz filter as the local branch — co-located exports
        # (or a checkpoint object under the prefix) must not leak in
        uris = [u for u in BucketIterator(path)
                if u.rsplit("/", 1)[-1].startswith(prefix)
                and u.endswith(".npz")]
        files = sorted(dl.fetch(uri) for uri in uris)
    else:
        files = sorted(glob.glob(os.path.join(path, f"{prefix}*.npz")))
    if not files:
        raise ValueError(f"no exported datasets under {path!r}")
    # native ordered prefetch: a background C thread parses file i+1..i+k
    # while the device trains on file i (AsyncDataSetIterator ring buffer
    # applied to the exported feed; np.load fallback inside iter_npz)
    from deeplearning4j_tpu.native import iter_npz

    for z in iter_npz(files):
        yield DataSet(
            z["features"], z["labels"],
            z.get("features_mask"),
            z.get("labels_mask"),
        )


class ParameterAveragingTrainingMaster(TrainingMaster):
    """Host control plane over the device-side ParameterAveragingTrainer."""

    def __init__(
        self,
        num_workers: Optional[int] = None,
        batch_size_per_worker: int = 16,
        averaging_frequency: int = 5,
        save_updater: bool = True,
        repartition: str = Repartition.ALWAYS,
        collect_training_stats: bool = False,
        max_retries: int = 2,
        rng_seed: int = 12345,
    ):
        # worker count defaults to the device count, resolved LAZILY at
        # first use (the num_workers property): len(jax.devices()) here
        # would make a master that is only being configured/serialized
        # the owner of the chip
        self._num_workers = int(num_workers) if num_workers else None
        self.batch_size_per_worker = batch_size_per_worker
        self.averaging_frequency = max(1, averaging_frequency)
        self.save_updater = save_updater
        self.repartition = repartition
        self.collect_training_stats = collect_training_stats
        self.max_retries = max_retries
        self.rng_seed = rng_seed
        self.stats = TrainingStats() if collect_training_stats else None
        self._trainer: Optional[ParameterAveragingTrainer] = None
        self._trainer_net = None
        self._round = 0

    @property
    def num_workers(self) -> int:
        if self._num_workers is None:
            import jax

            self._num_workers = len(jax.devices())
        return self._num_workers

    @num_workers.setter
    def num_workers(self, value: int) -> None:
        self._num_workers = int(value)

    # -- data plane helpers -----------------------------------------------
    def _examples_per_split(self) -> int:
        # reference :148 — one split feeds every worker for `freq` minibatches
        return self.num_workers * self.batch_size_per_worker * self.averaging_frequency

    def _collect(self, iterator) -> List[DataSet]:
        if isinstance(iterator, (list, tuple)):
            return list(iterator)
        out = list(iterator)
        if hasattr(iterator, "reset"):
            iterator.reset()
        return out

    def _splits(self, datasets: List[DataSet]):
        """Concatenate and re-split so each split is exactly
        workers x batch x freq examples (repartition=Always; the reference's
        Balanced repartition becomes an exact reshape here). Features/labels
        may be per-component LISTS (multi-input/multi-output
        ComputationGraph — the reference's MultiDataSet); every component is
        permuted and sliced with the same index set."""

        def cat(get):
            first = get(datasets[0])
            if isinstance(first, (list, tuple)):
                return [
                    np.concatenate([np.asarray(get(d)[i]) for d in datasets])
                    for i in range(len(first))
                ]
            return np.concatenate([np.asarray(get(d)) for d in datasets])

        # DataSet carries arrays (or component lists); MultiDataSet carries
        # features_list/labels_list — normalize the accessors
        def accessor(multi_attr, single_attr):
            def get(d):
                comp = getattr(d, multi_attr, None)
                if comp is not None:
                    if not comp:
                        raise ValueError(
                            f"{type(d).__name__}.{multi_attr} is empty")
                    return comp
                return getattr(d, single_attr)

            return get

        x = cat(accessor("features_list", "features"))
        y = cat(accessor("labels_list", "labels"))
        take = lambda comp, idx: (
            [c[idx] for c in comp] if isinstance(comp, list) else comp[idx]
        )
        n = (x[0] if isinstance(x, list) else x).shape[0]
        if self.repartition == Repartition.ALWAYS:
            # vary the shuffle per call (the reference repartitions each fit)
            rng = np.random.default_rng(self.rng_seed + self._round)
            self._round += 1
            order = rng.permutation(n)
            x, y = take(x, order), take(y, order)
        per = self._examples_per_split()
        n_full = n // per
        dropped = n - n_full * per
        if dropped:
            # static shard_map shapes require whole averaging rounds; the
            # shuffle rotates which examples land in the tail across rounds
            logger.warning(
                "parameter averaging: dropping %d tail examples "
                "(< one %d-example round)", dropped, per,
            )
        for s in range(n_full):
            sl = slice(s * per, (s + 1) * per)
            yield take(x, sl), take(y, sl)

    # -- TrainingMaster contract ------------------------------------------
    def execute_training(self, net, iterator) -> None:
        """fit(JavaRDD<DataSet>) analog (SparkDl4jMultiLayer.fit:194-230 →
        executeTraining:163; SparkComputationGraph.fit:68 for graphs): per
        split, one averaging round on the mesh. Drives BOTH containers —
        the trainer dispatches on MultiLayerNetwork vs ComputationGraph."""
        if self._trainer is None or self._trainer_net is not net:
            self._trainer = ParameterAveragingTrainer(
                net,
                num_workers=self.num_workers,
                averaging_frequency=self.averaging_frequency,
                save_updater=self.save_updater,
            )
            self._trainer_net = net
        datasets = self._collect(iterator)
        stats = self.stats
        with stats.timed("split") if stats else contextlib.nullcontext():
            splits = list(self._splits(datasets))
        if not splits:
            raise ValueError(
                f"not enough examples for one averaging round "
                f"(need {self._examples_per_split()})"
            )
        for x, y in splits:
            # x may be a per-component LIST (multi-input graph): the example
            # count is the leading dim of a component, not the list length
            n_examples = (x[0] if isinstance(x, list) else x).shape[0]
            attempt = 0
            while True:
                try:
                    t0 = stats.time_source.current_time_millis() if stats else 0
                    p0 = time.perf_counter()
                    self._trainer.fit(x, y)
                    if stats:  # record successful attempts only
                        stats.record(
                            "fit", t0, (time.perf_counter() - p0) * 1000.0,
                            example_count=n_examples,
                        )
                    break
                except Exception:
                    # Spark retries failed tasks natively (SURVEY.md section 5
                    # failure detection); parameter averaging is idempotent
                    # per split, so a bounded retry reproduces that behavior.
                    attempt += 1
                    if attempt > self.max_retries:
                        raise

    def get_training_stats(self) -> Optional[TrainingStats]:
        return self.stats

    def execute_training_paths(self, net, path) -> None:
        """Fit from a previously exported location (the reference's
        executeTraining(JavaPairRDD<String, PortableDataStream>) branch,
        ParameterAveragingTrainingMaster.java:189-210, fed by
        SparkDl4jMultiLayer.fit(String path) :217): deserialize the
        exported DataSets, then run the same split/average loop."""
        self.execute_training(net, load_exported_datasets(path))


class ElasticParameterAveragingTrainingMaster(ParameterAveragingTrainingMaster):
    """The averaging master over the ELASTIC fleet (ISSUE 6): identical
    split/average control plane, but each split executes through
    parallel/fleet.ElasticParameterAveragingTrainer — workers join and
    leave mid-run (every round re-forms over the live membership, the
    split count tracking the survivor set), a dead member's in-flight
    work is reclaimed, and SIGTERM'd OS-process members announce
    departure. ``num_workers`` here sizes the SPLITS (examples per round
    = workers x batch x freq, reference :148) and the initial in-process
    fleet; the live round fan-out is the membership's business.

    Pick ``batch_size_per_worker * averaging_frequency * num_workers``
    divisible by every membership size the run may shrink/grow through —
    an indivisible round raises loudly (multihost.local_batch_slice
    rule) instead of silently truncating the tail."""

    def __init__(self, *args, fleet_kwargs: Optional[dict] = None, **kw):
        super().__init__(*args, **kw)
        self.fleet_kwargs = dict(fleet_kwargs or {})

    def execute_training(self, net, iterator) -> None:
        from deeplearning4j_tpu.parallel.fleet import (
            ElasticParameterAveragingTrainer,
        )

        if self._trainer is None or self._trainer_net is not net:
            if self._trainer is not None:
                # the old fleet's worker threads must not outlive the
                # trainer swap (they would keep polling the old tracker
                # on the shared core forever)
                self._trainer.close()
            self._trainer = ElasticParameterAveragingTrainer(
                net,
                num_workers=self.num_workers,
                averaging_frequency=self.averaging_frequency,
                save_updater=self.save_updater,
                **self.fleet_kwargs,
            )
            self._trainer_net = net
        # the split/retry/stats loop is inherited verbatim: the parent
        # only drives self._trainer through .fit(x, y), a contract the
        # fleet trainer implements, and it rebuilds the trainer only when
        # _trainer_net is not net — which we just pinned
        super().execute_training(net, iterator)

    @property
    def fleet(self):
        """The live ElasticParameterAveragingTrainer (None before the
        first execute_training) — membership surface for admit/evict."""
        return self._trainer

    def close(self) -> None:
        """Stop the fleet this master spawned (worker threads + any
        tracker server) — the master owns the trainer lifecycle, so the
        caller that used it like the base master must not be left with
        daemon threads polling the job queue forever."""
        if self._trainer is not None:
            self._trainer.close()
            self._trainer = None
            self._trainer_net = None

    def __enter__(self) -> "ElasticParameterAveragingTrainingMaster":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


class DistributedEvaluator:
    """Map-reduce evaluation (EvaluateFlatMapFunction +
    EvaluationReduceFunction): evaluate shards independently, merge."""

    def __init__(self, num_shards: Optional[int] = None):
        # same lazy rule as ParameterAveragingTrainingMaster.num_workers:
        # never touch jax.devices() before work actually arrives
        self._num_shards = int(num_shards) if num_shards else None

    @property
    def num_shards(self) -> int:
        if self._num_shards is None:
            import jax

            self._num_shards = len(jax.devices())
        return self._num_shards

    @num_shards.setter
    def num_shards(self, value: int) -> None:
        self._num_shards = int(value)

    def evaluate(self, net, datasets: Iterable[DataSet]) -> Evaluation:
        datasets = list(datasets)
        shards = balanced_splits(len(datasets), self.num_shards)
        partials: List[Evaluation] = []
        for sl in shards:
            ev = Evaluation()
            for ds in datasets[sl]:
                out = net.output(ds.features)
                out0 = out[0] if isinstance(out, (list, tuple)) else out
                ev.eval(np.asarray(ds.labels), np.asarray(out0),
                        mask=ds.labels_mask)
            partials.append(ev)
        merged = partials[0]
        for ev in partials[1:]:
            merged.merge(ev)
        return merged


class SparkStyleNetwork:
    """User-facing wrapper pairing a net with a TrainingMaster
    (SparkDl4jMultiLayer / SparkComputationGraph role — both containers
    train under the averaging master)."""

    def __init__(self, net, training_master: TrainingMaster):
        self.net = net
        self.training_master = training_master

    def fit(self, iterator_or_datasets) -> "SparkStyleNetwork":
        self.training_master.execute_training(self.net, iterator_or_datasets)
        return self

    def fit_paths(self, path) -> "SparkStyleNetwork":
        """Fit from exported DataSet files — a directory, file list, or
        gs:// prefix (SparkDl4jMultiLayer.fit(String path) :217)."""
        self.training_master.execute_training_paths(self.net, path)
        return self

    def evaluate(self, datasets) -> Evaluation:
        return DistributedEvaluator().evaluate(self.net, datasets)

    def score_examples(self, datasets) -> np.ndarray:
        """Per-example scores (SparkDl4jMultiLayer.scoreExamples): one loss
        value per example, concatenated over all datasets. Computed by
        scoring batch-1 slices (one extra XLA compile at batch 1)."""
        scores = []
        for ds in datasets:
            f = np.asarray(ds.features)
            l = np.asarray(ds.labels)
            for i in range(f.shape[0]):
                scores.append(self.net.score(f[i : i + 1], l[i : i + 1]))
        return np.asarray(scores)
