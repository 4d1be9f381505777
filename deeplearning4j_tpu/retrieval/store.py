"""VectorStore: online-mutable vector index with atomic generation swaps.

The mutation/publish split re-proves the PR 14 promotion contract for
indexes (online/promote.py's atomic default swap): writers mutate a
STAGING arena — slot-addressed ``[capacity + 1, dim]`` device buffer,
row ``capacity`` a permanent zero TRASH row (the paged-KV block-0
argument, serving/paged.py), updated in place through a DONATED
``ops/dispatch.arena_jit`` scatter (single-owner accumulator: the store
always rebinds, never re-reads a donated input) — while readers search
an IMMUTABLE published :class:`~deeplearning4j_tpu.retrieval.index.
IndexSnapshot`. ``publish()`` packs live slots into a fresh device
arena (one jitted gather — no host->device re-upload of the corpus),
optionally trains the IVF quantizer, and swaps the published reference
atomically: in-flight ``/search`` readers keep the old generation's
buffers (searches never donate), so a swap fails ZERO admitted
requests by construction.

Publishes are gated like promotions: a latched
``online/drift.DriftMonitor`` alarm (live embedding moments past the z
bar) VETOES the publish (:class:`PublishVetoed` — journaled, counted,
the published generation unmoved). Feeds ride the PR 14
``StreamSource``: one :meth:`feed_once` = one poll window of
upsert/delete batches then a gated publish.

Capacity is sized AOT against ``DL4J_TPU_HBM_GB`` via
``ops/memory.ann_arena_rows`` when ``DL4J_TPU_ANN_ROWS`` is 0 —
closed-form arithmetic over shapes.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.obs import journal as obs_journal
from deeplearning4j_tpu.obs import registry as obs_registry
from deeplearning4j_tpu.ops import dispatch, env as envknob
from deeplearning4j_tpu.retrieval.index import (
    ExactIndex,
    IndexSnapshot,
    IVFIndex,
    measure_recall,
)
from deeplearning4j_tpu.retrieval.stats import RetrievalStats


class IndexFullError(RuntimeError):
    """No free slot for a new id — the arena is at capacity."""


class PublishVetoed(RuntimeError):
    """A latched drift alarm blocked the publish; the previously
    published generation keeps serving (a veto is not an outage)."""


def _resolve_capacity(dim: int, capacity: Optional[int]) -> int:
    if capacity is not None and int(capacity) > 0:
        return int(capacity)
    rows = envknob.get_int("DL4J_TPU_ANN_ROWS", 0)
    if rows and rows > 0:
        return int(rows)
    from deeplearning4j_tpu.ops import memory

    return memory.ann_arena_rows(dim)


class VectorStore:
    """One named, online-mutable ANN index (``kind`` = ``exact``/``ivf``)."""

    def __init__(self, dim: int, *, capacity: Optional[int] = None,
                 kind: str = "ivf", metric: str = "cosine",
                 clusters: Optional[int] = None,
                 nprobe: Optional[int] = None, ivf_iters: int = 25,
                 min_ivf_rows: int = 32, name: str = "index",
                 stats: Optional[RetrievalStats] = None) -> None:
        if kind not in ("exact", "ivf"):
            raise ValueError(f"kind must be exact|ivf, got {kind!r}")
        if metric not in ("cosine", "ip"):
            raise ValueError(f"metric must be cosine|ip, got {metric!r}")
        self.name = name
        self.dim = int(dim)
        self.kind = kind
        self.metric = metric
        self.capacity = _resolve_capacity(self.dim, capacity)
        self.min_ivf_rows = int(min_ivf_rows)
        self.retrieval_stats = stats or RetrievalStats()
        obs_registry.default_registry().register_ledger(
            self, "retrieval_stats", self.retrieval_stats)
        self._exact = ExactIndex()
        self._ivf = IVFIndex(clusters=clusters, nprobe=nprobe,
                             iters=ivf_iters)
        # host master (the authoritative copy, kmeans training substrate)
        self._host_vecs = np.zeros((self.capacity, self.dim), np.float32)
        self._ids = np.full(self.capacity, -1, np.int64)
        self._id2slot: Dict[int, int] = {}
        self._free = list(range(self.capacity - 1, -1, -1))
        # staging arena: slot-addressed, trash row at index `capacity`,
        # mutated only through the donated scatter below
        self._staging = jnp.zeros((self.capacity + 1, self.dim), jnp.float32)
        self._scatter = dispatch.arena_jit(
            lambda arena, slots, rows: arena.at[slots].set(rows),
            donate=(0,))
        self._pack = dispatch.arena_jit(
            lambda arena, slots: jnp.take(arena, slots, axis=0))
        self._mut = threading.Lock()
        self._pub = threading.Lock()  # serializes whole publishes
        self._snapshot = self._empty_snapshot()
        self._dirty = False

    # -- snapshot plumbing -------------------------------------------------

    def _empty_snapshot(self) -> IndexSnapshot:
        n_pad = dispatch.bucket_size(1)
        return IndexSnapshot(
            vecs=jnp.zeros((n_pad, self.dim), jnp.float32),
            ids=np.full(n_pad, -1, np.int64), n=0, generation=0,
            metric=self.metric)

    @property
    def snapshot(self) -> IndexSnapshot:
        """The current published generation (immutable; safe to search
        without any lock — a concurrent publish swaps the reference,
        never the buffers)."""
        return self._snapshot

    @property
    def rows(self) -> int:
        return len(self._id2slot)

    @property
    def generation(self) -> int:
        return self._snapshot.generation

    # -- mutation plane (staging arena + host master) ----------------------

    def _norm_rows(self, vecs: np.ndarray) -> np.ndarray:
        rows = np.array(vecs, np.float32, copy=True).reshape(-1, self.dim)
        if self.metric == "cosine":
            norms = np.linalg.norm(rows, axis=1, keepdims=True)
            rows = rows / np.maximum(norms, 1e-12)
        return rows

    def _scatter_padded(self, slots, rows) -> None:
        """Donated scatter with the slot list padded up the bucket
        ladder onto the TRASH row (zero writes to row `capacity` keep it
        zero), so mutation batch sizes reuse one program per bucket."""
        m = len(slots)
        pad = dispatch.bucket_size(m)
        s = np.full(pad, self.capacity, np.int32)
        s[:m] = slots
        r = np.zeros((pad, self.dim), np.float32)
        r[:m] = rows
        self._staging = self._scatter(self._staging, jnp.asarray(s),
                                      jnp.asarray(r))

    def upsert(self, ids, vecs) -> int:
        """Insert-or-replace rows by external id. Returns rows written."""
        id_arr = np.asarray(ids, np.int64).reshape(-1)
        rows = self._norm_rows(vecs)
        if rows.shape[0] != id_arr.shape[0]:
            raise ValueError(
                f"{id_arr.shape[0]} ids vs {rows.shape[0]} vectors")
        with self._mut:
            slots = []
            for ext in id_arr:
                ext = int(ext)
                slot = self._id2slot.get(ext)
                if slot is None:
                    if not self._free:
                        raise IndexFullError(
                            f"index {self.name!r} full at "
                            f"{self.capacity} rows")
                    slot = self._free.pop()
                    self._id2slot[ext] = slot
                    self._ids[slot] = ext
                slots.append(slot)
            self._host_vecs[slots] = rows
            self._scatter_padded(slots, rows)
            self._dirty = True
        self.retrieval_stats.bump("upserts", len(slots))
        return len(slots)

    def delete(self, ids) -> int:
        """Drop rows by external id (unknown ids ignored). Returns rows
        dropped."""
        id_arr = np.asarray(ids, np.int64).reshape(-1)
        with self._mut:
            slots = []
            for ext in id_arr:
                slot = self._id2slot.pop(int(ext), None)
                if slot is None:
                    continue
                slots.append(slot)
                self._ids[slot] = -1
                self._free.append(slot)
            if slots:
                self._host_vecs[slots] = 0.0
                self._scatter_padded(slots, np.zeros((len(slots), self.dim),
                                                     np.float32))
                self._dirty = True
        if slots:
            self.retrieval_stats.bump("deletes", len(slots))
        return len(slots)

    # -- publish plane (generation swap) -----------------------------------

    def publish(self, drift=None, force: bool = False) -> IndexSnapshot:
        """Pack live slots into a fresh immutable generation and swap it
        in atomically. ``drift`` (an ``online/drift.DriftMonitor``) with
        a latched/firing alarm VETOES the publish unless ``force``."""
        if drift is not None and not force:
            verdict = drift.check()
            if verdict.get("alarmed"):
                self.retrieval_stats.bump("publish_vetoes")
                obs_journal.event(
                    "retrieval.publish_veto", index=self.name,
                    generation=self._snapshot.generation,
                    max_z=verdict.get("max_z"))
                raise PublishVetoed(
                    f"index {self.name!r}: drift alarm "
                    f"(max_z={verdict.get('max_z')}) vetoed the publish; "
                    f"generation {self._snapshot.generation} keeps serving")
        with self._pub:
            with self._mut:
                live = sorted(self._id2slot.values())
                n = len(live)
                # n_pad >= n + 1 guarantees at least one zero pad row —
                # the IVF member-table sentinel (index.py layout
                # discipline)
                n_pad = dispatch.bucket_size(n + 1)
                slots = np.full(n_pad, self.capacity, np.int32)
                slots[:n] = live
                ids = np.full(n_pad, -1, np.int64)
                ids[:n] = self._ids[slots[:n]]
                packed = self._pack(self._staging, jnp.asarray(slots))
                host_live = self._host_vecs[slots[:n]]
                gen = self._snapshot.generation + 1
                self._dirty = False
            snap = IndexSnapshot(vecs=packed, ids=ids, n=n, generation=gen,
                                 metric=self.metric)
            if self.kind == "ivf" and n >= self.min_ivf_rows:
                snap = self._ivf.build(snap, host_live)
            with self._mut:
                self._snapshot = snap
        self.retrieval_stats.bump("publishes")
        self.retrieval_stats.set("generation", gen)
        self.retrieval_stats.set("rows", n)
        obs_journal.event("retrieval.publish", index=self.name,
                          generation=gen, rows=n,
                          ivf=snap.centroids is not None)
        return snap

    # -- search plane (lock-free over the published generation) -----------

    def search(self, queries, k: int = 10,
               nprobe: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k over the CURRENT published generation. Returns
        ``(ids [B, k] int64, scores [B, k] float32)``; id -1 marks
        fewer-than-k live rows."""
        snap = self._snapshot
        if self.kind == "ivf" and snap.centroids is not None:
            ids, scores = self._ivf.search(snap, queries, k, nprobe=nprobe)
        else:
            ids, scores = self._exact.search(snap, queries, k)
        self.retrieval_stats.bump("search_requests")
        self.retrieval_stats.bump("search_rows", int(ids.shape[0]))
        return ids, scores

    def search_exact(self, queries, k: int = 10):
        """The oracle path, always exhaustive — recall probes and tests
        compare against this on the SAME generation."""
        snap = self._snapshot
        ids, scores = self._exact.search(snap, queries, k)
        return ids, scores

    def probe_recall(self, queries, k: int = 10) -> float:
        """Measured recall@k of this store's probe path vs the exact
        oracle on the current generation (never assumed)."""
        snap = self._snapshot
        if snap.centroids is None:
            recall = 1.0  # exact path IS the oracle
        else:
            recall = measure_recall(snap, self._ivf, queries, k)
        self.retrieval_stats.bump("recall_probes")
        self.retrieval_stats.set("last_recall", recall)
        return recall

    # -- online feed (PR 14 StreamSource loop) -----------------------------

    def apply_batch(self, batch) -> Tuple[int, int]:
        """One feed batch -> (upserted, deleted). Accepts a DataSet
        (features = vectors, labels = ids; features None => labels are
        ids to DELETE) or an ('upsert'|'delete', ...) tuple."""
        if isinstance(batch, tuple) and batch and isinstance(batch[0], str):
            op = batch[0]
            if op == "delete":
                return 0, self.delete(batch[1])
            if op == "upsert":
                return self.upsert(batch[1], batch[2]), 0
            raise ValueError(f"unknown feed op {op!r}")
        feats = getattr(batch, "features", None)
        labels = getattr(batch, "labels", None)
        if labels is None:
            raise ValueError(
                "feed batch needs labels (external ids); got "
                f"{type(batch).__name__}")
        if feats is None:
            return 0, self.delete(labels)
        return self.upsert(labels, feats), 0

    def feed_once(self, stream, drift=None, publish: bool = True) -> dict:
        """Drain ONE StreamSource poll window (ends when the feed idles
        ``DL4J_TPU_ONLINE_IDLE_S``), observing vectors into ``drift``
        before they land, then publish gated on the drift verdict.
        Returns a window report; a veto rides it as ``vetoed=True``
        (the generation field then names the UNMOVED generation)."""
        upserted = deleted = batches = 0
        for batch in stream:
            feats = getattr(batch, "features", None)
            if drift is not None and feats is not None:
                drift.observe(np.asarray(feats, np.float32).reshape(
                    -1, self.dim))
            u, d = self.apply_batch(batch)
            upserted += u
            deleted += d
            batches += 1
            self.retrieval_stats.bump("feed_batches")
        self.retrieval_stats.bump("feed_windows")
        report = {"batches": batches, "upserted": upserted,
                  "deleted": deleted, "published": False, "vetoed": False,
                  "generation": self._snapshot.generation}
        if publish and batches:
            try:
                snap = self.publish(drift=drift)
                report.update(published=True, generation=snap.generation)
            except PublishVetoed:
                report.update(vetoed=True)
        return report

    # -- reporting (AOT, no device read) -----------------------------------

    def report(self) -> Dict[str, Any]:
        """Capacity/row-count report for ``/models`` — host-side ints
        only, beside the serving engine's ``kv_report``."""
        from deeplearning4j_tpu.ops import memory

        snap = self._snapshot
        return {
            "kind": self.kind,
            "metric": self.metric,
            "dim": self.dim,
            "capacity": self.capacity,
            "rows": self.rows,
            "generation": snap.generation,
            "ivf_built": snap.centroids is not None,
            "clusters": (int(snap.centroids.shape[0])
                         if snap.centroids is not None else 0),
            "nprobe": envknob.get_int("DL4J_TPU_ANN_NPROBE", 8),
            "arena_bytes": (self.capacity + 1) * memory.ann_row_bytes(
                self.dim),
        }
