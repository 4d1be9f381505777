"""Device-resident vector indexes: exact top-k oracle + IVF probing.

The reference answers ``wordsNearest`` with a host-side full scan
(BasicModelUtils.java wordsNearest — an O(vocab) numpy pass per query);
this module is the TPU-native serving form: batched top-k over a
device-resident arena, the MXU-friendly matmul shape (its rate on the
attached chip: not measured).

Two index families over ONE immutable published snapshot layout
(:class:`IndexSnapshot`, produced by ``retrieval/store.VectorStore``
generation publishes):

- :class:`ExactIndex` — one jitted ``scores = q @ vecs.T`` +
  ``jax.lax.top_k`` over the whole arena. Exact by construction: the
  correctness oracle every IVF recall number is MEASURED against.
- :class:`IVFIndex` — a k-means coarse quantizer
  (``clustering/kmeans.KMeansClustering``, the reference
  KMeansClustering.java:31 machinery reused as infrastructure) built at
  publish time; a query scores ``DL4J_TPU_ANN_NPROBE`` nearest clusters
  and ranks only their members — one jit, zero retrace across
  publishes at a fixed (n_pad, cap_per, k, nprobe) bucket.

Snapshot layout discipline (mirrors the paged-KV trash-block argument,
serving/paged.py): the packed arena is ``[n_pad, dim]`` with rows
``>= n`` zero; IVF member tables pad with sentinel ``n_pad - 1``
(guaranteed a pad row — the store packs to ``bucket_size(n + 1)``), and
sentinel/pad scores are masked to ``-inf`` before top_k, so garbage is
invisible by construction. Searches never donate — published snapshots
stay valid for in-flight readers across a generation swap; only the
store's STAGING arena rides ``ops/dispatch.arena_jit`` donation.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops import env as envknob

_EPS = 1e-12


@dataclass(frozen=True)
class IndexSnapshot:
    """One immutable published index generation. ``vecs`` is the packed
    device arena [n_pad, dim] (rows >= n zero); ``ids`` the aligned
    external ids (int64, -1 on pad rows); IVF fields are None on
    exact-only publishes."""

    vecs: Any
    ids: np.ndarray
    n: int
    generation: int
    metric: str = "cosine"
    centroids: Any = None
    members: Any = None

    @property
    def dim(self) -> int:
        return int(self.vecs.shape[1])

    @property
    def n_pad(self) -> int:
        return int(self.vecs.shape[0])


def _normalize(q):
    return q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), _EPS)


@functools.partial(jax.jit, static_argnames=("k", "cosine"))
def _exact_topk(q, vecs, n, *, k: int, cosine: bool):
    """[B, n_pad] scores -> top-k (scores, packed row indices); pad rows
    (arange >= n) masked to -inf so they can never win."""
    if cosine:
        q = _normalize(q)
    scores = q @ vecs.T
    valid = jnp.arange(vecs.shape[0]) < n
    scores = jnp.where(valid[None, :], scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "cosine"))
def _ivf_topk(q, vecs, centroids, members, *, k: int, nprobe: int,
              cosine: bool):
    """Coarse-probe then rank: top-nprobe centroids -> gather member
    rows -> exact scores on the candidate set only. Sentinel member
    slots (n_pad - 1, a zero pad row) masked to -inf."""
    if cosine:
        q = _normalize(q)
    coarse = q @ centroids.T                        # [B, K]
    _, probe = jax.lax.top_k(coarse, nprobe)        # [B, nprobe]
    cand = members[probe]                           # [B, nprobe, cap_per]
    cand = cand.reshape(cand.shape[0], -1)          # [B, M]
    cvecs = vecs[cand]                              # [B, M, dim]
    scores = jnp.einsum("bd,bmd->bm", q, cvecs)
    sentinel = vecs.shape[0] - 1
    scores = jnp.where(cand != sentinel, scores, -jnp.inf)
    top, pos = jax.lax.top_k(scores, k)
    return top, jnp.take_along_axis(cand, pos, axis=1)


def _as_queries(queries, dim: int) -> np.ndarray:
    q = np.asarray(queries, np.float32)
    if q.ndim == 1:
        q = q[None, :]
    if q.ndim != 2 or q.shape[1] != dim:
        raise ValueError(f"queries must be [B, {dim}], got {q.shape}")
    return q


def _bucket_queries(q: np.ndarray) -> Tuple[np.ndarray, int]:
    """Pad the query batch up the serving bucket ladder (zero rows,
    sliced back off the result) so a stream of ragged /search batch
    sizes compiles one program per bucket, not per shape."""
    from deeplearning4j_tpu.ops import dispatch

    b = q.shape[0]
    pad = dispatch.bucket_size(b)
    if pad > b:
        q = np.concatenate([q, np.zeros((pad - b, q.shape[1]), q.dtype)])
    return q, b


def _finalize(snap: IndexSnapshot, scores, rows, b: int):
    """Host readback + slot->external-id mapping; -inf entries (fewer
    than k live rows) surface as id -1."""
    scores = np.asarray(scores)[:b]
    rows = np.asarray(rows)[:b]
    ids = snap.ids[rows]
    ids = np.where(np.isfinite(scores), ids, -1)
    return ids, scores


class ExactIndex:
    """Exhaustive batched top-k — the correctness oracle
    (reference wordsNearest full-scan role, device-batched)."""

    kind = "exact"

    def search(self, snap: IndexSnapshot, queries, k: int = 10):
        q = _as_queries(queries, snap.dim)
        q, b = _bucket_queries(q)
        k_eff = min(int(k), snap.n_pad)
        scores, rows = _exact_topk(
            jnp.asarray(q), snap.vecs, np.int32(snap.n),
            k=k_eff, cosine=snap.metric == "cosine")
        return _finalize(snap, scores, rows, b)


class IVFIndex:
    """Inverted-file probing over a k-means coarse quantizer. Recall is
    a property of (clusters, nprobe, data) — ``measure_recall`` reports
    it against the exact oracle on the SAME snapshot, never assumed."""

    kind = "ivf"

    def __init__(self, clusters: Optional[int] = None,
                 nprobe: Optional[int] = None, seed: int = 0,
                 iters: int = 25) -> None:
        self.clusters = clusters
        self.nprobe = nprobe
        self.seed = seed
        self.iters = int(iters)
        self._exact = ExactIndex()

    def _n_clusters(self, n: int) -> int:
        k = self.clusters
        if k is None:
            k = envknob.get_int("DL4J_TPU_ANN_CLUSTERS", 0)
        if not k or k <= 0:
            k = int(np.sqrt(max(1, n)))
        return max(1, min(int(k), max(1, n)))

    def _n_probe(self, n_clusters: int, override=None) -> int:
        p = override if override is not None else self.nprobe
        if p is None:
            p = envknob.get_int("DL4J_TPU_ANN_NPROBE", 8)
        return max(1, min(int(p), n_clusters))

    def build(self, snap: IndexSnapshot,
              host_vecs: np.ndarray) -> IndexSnapshot:
        """Train the coarse quantizer on the live rows (host-side master
        copy — no device readback) and attach centroids + padded member
        tables to the snapshot. cap_per is bucketed so membership churn
        across publishes reuses the same search program."""
        from deeplearning4j_tpu.clustering.kmeans import KMeansClustering
        from deeplearning4j_tpu.ops import dispatch

        n, n_pad = snap.n, snap.n_pad
        if n < 1:
            raise ValueError("cannot build an IVF quantizer over 0 rows")
        kc = self._n_clusters(n)
        km = KMeansClustering(kc, max_iterations=self.iters, seed=self.seed)
        km.apply_to(np.asarray(host_vecs[:n], np.float32))
        assign = km.assignments_
        counts = np.bincount(assign, minlength=kc)
        cap_per = dispatch.bucket_size(max(1, int(counts.max())))
        sentinel = n_pad - 1
        members = np.full((kc, cap_per), sentinel, np.int32)
        fill = np.zeros(kc, np.int64)
        for row, c in enumerate(assign):
            members[c, fill[c]] = row
            fill[c] += 1
        centroids = km.centers_
        if snap.metric == "cosine":
            norms = np.linalg.norm(centroids, axis=1, keepdims=True)
            centroids = centroids / np.maximum(norms, _EPS)
        return IndexSnapshot(
            vecs=snap.vecs, ids=snap.ids, n=n, generation=snap.generation,
            metric=snap.metric, centroids=jnp.asarray(centroids, jnp.float32),
            members=jnp.asarray(members))

    def search(self, snap: IndexSnapshot, queries, k: int = 10,
               nprobe: Optional[int] = None):
        if snap.centroids is None:
            return self._exact.search(snap, queries, k)
        q = _as_queries(queries, snap.dim)
        q, b = _bucket_queries(q)
        k_eff = min(int(k), snap.n_pad)
        scores, rows = _ivf_topk(
            jnp.asarray(q), snap.vecs, snap.centroids, snap.members,
            k=k_eff, nprobe=self._n_probe(int(snap.centroids.shape[0]),
                                          nprobe),
            cosine=snap.metric == "cosine")
        return _finalize(snap, scores, rows, b)


def measure_recall(snap: IndexSnapshot, ivf: IVFIndex, queries,
                   k: int = 10) -> float:
    """recall@k of the IVF probe vs the exact oracle on the SAME
    snapshot — the measured-never-assumed discipline (the Pallas
    measured-win gate's sibling for index quality)."""
    exact_ids, _ = ExactIndex().search(snap, queries, k)
    ivf_ids, _ = ivf.search(snap, queries, k)
    hits, total = 0, 0
    for row_e, row_i in zip(exact_ids, ivf_ids):
        truth = set(int(i) for i in row_e if i >= 0)
        if not truth:
            continue
        got = set(int(i) for i in row_i if i >= 0)
        hits += len(truth & got)
        total += len(truth)
    return hits / total if total else 1.0
