"""Embedding adapters: one uniform ``rows -> [N, dim] float32`` surface.

The reference serves models, not embeddings — its nlp module (SURVEY
module map, deeplearning4j-scaleout-nlp; InMemoryLookupTable.java:73)
trains word vectors and answers ``wordsNearest`` on the host. This
module is the serving half that never existed: every registered net
becomes an encoder behind ``/embed``, routed through the same
``DynamicBatcher`` bucket ladder as ``/predict`` so the batcher==direct
byte-equivalence contract comes for free.

Three adapter families, resolved by duck type (``resolve_adapter``):

- ``FeedForwardEmbedding`` — MLN/CG hidden-layer activations via
  ``feed_forward`` (reference feedForward(train),
  MultiLayerNetwork.java:1016 role). ``layer`` picks the activation:
  an int index into the MLN activations list (input is index 0; the
  default -2 is the last hidden layer), or a vertex NAME for a
  ComputationGraph (default: the vertex feeding the first output).
- ``BertEmbedding`` — ``BertMLM.embed_tokens`` contextual embeddings
  pooled over the sequence axis (``mean``/``cls``/``max``).
- ``LookupEmbedding`` — word2vec ``InMemoryLookupTable.vectors`` row
  lookup (token-id rows; the vocab-scale table the SGNS plane trains).

``dim`` is resolved WITHOUT running the model: config fields, param
shapes, or ``jax.eval_shape`` abstract evaluation — so ``/models``
reports per-model embedding dims without a dispatch (the same AOT
discipline as ``ops/memory``).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import numpy as np

from deeplearning4j_tpu.ops import env as envknob

_POOLS = ("mean", "cls", "max")


def _env_layer() -> Optional[int]:
    return envknob.get_int("DL4J_TPU_EMBED_LAYER", None)


def _env_pool() -> str:
    pool = envknob.get_str("DL4J_TPU_EMBED_POOL") or "mean"
    return pool if pool in _POOLS else "mean"


class FeedForwardEmbedding:
    """Hidden-layer encoder over MLN/CG ``feed_forward`` activations
    (reference feedForward map, MultiLayerNetwork.java feedForward /
    ComputationGraph.java feedForward roles)."""

    kind = "feedforward"

    def __init__(self, net: Any, layer=None,
                 input_shape: Optional[Sequence[int]] = None) -> None:
        self.net = net
        self._graph = hasattr(net, "conf") and hasattr(
            getattr(net, "conf", None), "vertex_inputs")
        if layer is None and not self._graph:
            layer = _env_layer()
        self.layer = self._default_layer() if layer is None else layer
        self._input_shape = tuple(input_shape) if input_shape else None
        self._dim: Optional[int] = self._aot_dim()

    def _default_layer(self):
        if self._graph:
            conf = self.net.conf
            out = conf.outputs[0]
            return conf.vertex_inputs[out][0]
        return -2

    def _pick(self, acts):
        if self._graph:
            return acts[self.layer]
        idx = int(self.layer)
        if not (-len(acts) <= idx < len(acts)):
            raise ValueError(
                f"embed layer {idx} out of range for {len(acts)} activations")
        return acts[idx]

    def _aot_dim(self) -> Optional[int]:
        """Abstract-eval the forward pass for the embedding width — no
        execution, no device dispatch."""
        if self._input_shape is None or self._graph:
            return None
        try:
            import jax

            spec = jax.ShapeDtypeStruct(
                (1,) + self._input_shape, np.float32)
            shapes = jax.eval_shape(
                lambda x: self.net.feed_forward(x), spec)
            return int(self._pick(shapes).shape[-1])
        except Exception:
            return None

    @property
    def dim(self) -> Optional[int]:
        return self._dim

    def __call__(self, rows) -> np.ndarray:
        x = np.asarray(rows, np.float32)
        if self._graph:
            acts = self.net.feed_forward(x)
        else:
            acts = self.net.feed_forward(x, train=False)
        out = np.asarray(self._pick(acts), np.float32)
        out = out.reshape(out.shape[0], -1)
        if self._dim is None:
            self._dim = int(out.shape[-1])
        return out


class BertEmbedding:
    """Pooled contextual embeddings over ``BertMLM.embed_tokens``
    (the feature-extraction use; reference word-vector serving never had
    a contextual analogue)."""

    kind = "bert"

    def __init__(self, lm: Any, pool: Optional[str] = None) -> None:
        if pool is None:
            pool = _env_pool()
        if pool not in _POOLS:
            raise ValueError(f"pool must be one of {_POOLS}, got {pool!r}")
        self.lm = lm
        self.pool = pool
        self._dim = int(lm.cfg.d_model)

    @property
    def dim(self) -> int:
        return self._dim

    def __call__(self, rows) -> np.ndarray:
        tokens = np.asarray(rows)
        if tokens.dtype.kind == "f":
            tokens = np.rint(tokens)
        tokens = tokens.astype(np.int32)
        emb = np.asarray(self.lm.embed_tokens(tokens), np.float32)  # [N,T,d]
        if self.pool == "cls":
            return emb[:, 0, :]
        if self.pool == "max":
            return emb.max(axis=1)
        return emb.mean(axis=1)


class LookupEmbedding:
    """Word2vec table rows by token id (reference
    InMemoryLookupTable.java:73 syn0; the lookup IS the encoder)."""

    kind = "lookup"

    def __init__(self, table: Any) -> None:
        # accept a Word2Vec model or the bare lookup table
        if hasattr(table, "lookup_table") and table.lookup_table is not None:
            table = table.lookup_table
        if not hasattr(table, "syn0"):
            raise TypeError("LookupEmbedding needs an InMemoryLookupTable "
                            "(or a fitted Word2Vec)")
        self.table = table
        self._dim = int(table.vector_length)

    @property
    def dim(self) -> int:
        return self._dim

    def __call__(self, rows) -> np.ndarray:
        ids = np.asarray(rows)
        if ids.dtype.kind == "f":
            ids = np.rint(ids)
        return self.table.vectors(ids.astype(np.int64).reshape(ids.shape[0], -1)[:, 0])


def resolve_adapter(model: Any, layer=None, pool: Optional[str] = None,
                    input_shape: Optional[Sequence[int]] = None):
    """Duck-typed adapter resolution for any registrable model: BertMLM
    (``embed_tokens``), word2vec tables (``syn0``/``lookup_table``), and
    the MLN/CG container family (``feed_forward``)."""
    if hasattr(model, "embed_tokens"):
        return BertEmbedding(model, pool=pool)
    if hasattr(model, "syn0") or (
            hasattr(model, "lookup_table")
            and getattr(model, "lookup_table", None) is not None):
        return LookupEmbedding(model)
    if hasattr(model, "feed_forward"):
        return FeedForwardEmbedding(model, layer=layer,
                                    input_shape=input_shape)
    raise TypeError(
        f"no embedding surface on {type(model).__name__}: expected "
        "embed_tokens (BERT), lookup_table/syn0 (word2vec), or "
        "feed_forward (MLN/CG)")
