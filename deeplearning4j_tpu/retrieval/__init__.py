"""Embedding & retrieval serving (ISSUE 17): /embed adapters +
device-resident ANN search with online, generation-swapped index
updates. The serving half the reference's scaleout-nlp module never
grew — its InMemoryLookupTable answers wordsNearest with a host-side
full scan; here the arena lives on device and top-k is one batched
matmul (the MXU-friendly shape)."""

from deeplearning4j_tpu.retrieval.embed import (
    BertEmbedding,
    FeedForwardEmbedding,
    LookupEmbedding,
    resolve_adapter,
)
from deeplearning4j_tpu.retrieval.index import (
    ExactIndex,
    IndexSnapshot,
    IVFIndex,
    measure_recall,
)
from deeplearning4j_tpu.retrieval.stats import RetrievalStats
from deeplearning4j_tpu.retrieval.store import (
    IndexFullError,
    PublishVetoed,
    VectorStore,
)

__all__ = [
    "BertEmbedding",
    "ExactIndex",
    "FeedForwardEmbedding",
    "IndexFullError",
    "IndexSnapshot",
    "IVFIndex",
    "LookupEmbedding",
    "PublishVetoed",
    "RetrievalStats",
    "VectorStore",
    "measure_recall",
    "resolve_adapter",
]
