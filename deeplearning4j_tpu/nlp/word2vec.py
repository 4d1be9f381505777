"""Word2Vec: batched TPU-native skip-gram / CBOW trainer.

Capability mirror of the reference embedding trainer (SURVEY.md section 3.4):
  - Word2Vec driver + SequenceVectors.fit pipeline (buildVocab → Huffman →
    resetWeights → training threads;
    deeplearning4j-nlp/.../models/sequencevectors/SequenceVectors.java:137-210);
  - SkipGram hierarchical softmax + negative sampling
    (models/embeddings/learning/impl/elements/SkipGram.java:170-258):
    per (center, context) pair, HS walks the center word's Huffman path
    updating syn1 rows and accumulating neu1e into the CONTEXT word's syn0
    row; negative sampling draws from the unigram table; f outside
    [-MAX_EXP, MAX_EXP] skips/saturates the update;
  - CBOW (models/embeddings/learning/impl/elements/CBOW.java): mean of
    context vectors predicts the center word, neu1e added to every context
    row;
  - subsampling of frequent words (SkipGram.applySubsampling, :100-110);
  - linear learning-rate decay to minLearningRate over total words
    (SequenceVectors wordsCounter-driven alpha).

TPU-native redesign: the reference's Hogwild VectorCalculationsThreads
(lock-free racy updates to shared syn0/syn1) become ONE jitted XLA program
per minibatch of pairs — gathers, sigmoid math, and `.at[].add()`
scatter-adds, with buffer donation so syn0/syn1 stay resident on device.
Deterministic by construction, and the scatter-add reproduces the "many
threads add concurrently" semantics exactly (addition commutes).
"""

from __future__ import annotations

import functools
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.lookup import InMemoryLookupTable
from deeplearning4j_tpu.nlp.text import DefaultTokenizerFactory, common_preprocessor
from deeplearning4j_tpu.nlp.vocab import VocabCache, VocabConstructor

MAX_EXP = 6.0


# ---------------------------------------------------------------------------
# Jitted training steps (compiled once per (L, K, D) static shape)
# ---------------------------------------------------------------------------


def _mean_scale(n_rows: int, idx, live):
    """Per-element scale turning scatter-ADD into scatter-MEAN over rows that
    collide within the batch.

    The reference applies updates sequentially (Hogwild threads): a row hit
    k times moves by up to k steps, but sigmoid saturation shrinks later
    steps, so total movement grows sublinearly in k. A plain batched
    `.at[].add()` sums k STALE-value updates — a full k-times step that
    diverges when k ~ B/V is large. Scaling each contribution by 1/sqrt(k)
    is the compromise: frequent rows still learn faster than a pure mean
    (1/k) would allow, total movement stays bounded like the saturating
    sequential process, and the result is deterministic and
    order-independent. (Verified empirically: 1/1 diverges on small vocabs,
    1/k under-trains, 1/sqrt(k) matches sequential quality.)
    """
    counts = jnp.zeros((n_rows,), jnp.float32).at[idx].add(live)
    return live / jnp.sqrt(jnp.maximum(counts[idx], 1.0))


def _hs_body(syn0, syn1, contexts, points, codes, mask, alpha):
    """One minibatch of HS skip-gram pairs.

    The Huffman path tensors points/codes/mask (B,L) are pre-gathered by
    center word on the host (w1 in SkipGram.iterateSample); contexts (B,)
    int32 is the word whose syn0 row is updated (w2/l1). Fully-padded rows
    carry mask == 0 everywhere and contribute nothing.
    """
    l1 = syn0[contexts]  # (B, D)
    s1 = syn1[points]  # (B, L, D)
    dot = jnp.einsum("bd,bld->bl", l1, s1)
    # Reference skips the update when |dot| >= MAX_EXP (SkipGram.java:193-196).
    live = mask * (jnp.abs(dot) < MAX_EXP)
    f = jax.nn.sigmoid(dot)
    g = (1.0 - codes - f) * alpha * live  # (B, L)
    neu1e = jnp.einsum("bl,bld->bd", g, s1)
    s1_scale = _mean_scale(syn1.shape[0], points, live)
    syn1 = syn1.at[points].add((g * s1_scale)[..., None] * l1[:, None, :])
    ctx_live = (mask.sum(axis=1) > 0).astype(jnp.float32)
    ctx_scale = _mean_scale(syn0.shape[0], contexts, ctx_live)
    syn0 = syn0.at[contexts].add(ctx_scale[:, None] * neu1e)
    return syn0, syn1


def _neg_body(syn0, syn1neg, contexts, targets, labels, live, alpha):
    """One minibatch of negative-sampling pairs (SkipGram.java:214-252).

    contexts (B,) — syn0 input rows; targets (B, K+1) — column 0 is the
    center word (label 1), the rest unigram-table negatives (label 0);
    live masks out negatives that collided with the center word (the
    reference `continue`s on target == w1).
    """
    l1 = syn0[contexts]  # (B, D)
    s1 = syn1neg[targets]  # (B, K+1, D)
    dot = jnp.einsum("bd,bkd->bk", l1, s1)
    f = jax.nn.sigmoid(dot)
    # Saturation semantics (SkipGram.java:234-246): f>MAX_EXP -> g=(label-1),
    # f<-MAX_EXP -> g=label, else label - sigmoid(f).
    base = jnp.where(
        dot > MAX_EXP, labels - 1.0, jnp.where(dot < -MAX_EXP, labels, labels - f)
    )
    g = base * alpha * live  # (B, K+1)
    neu1e = jnp.einsum("bk,bkd->bd", g, s1)
    t_scale = _mean_scale(syn1neg.shape[0], targets, live)
    syn1neg = syn1neg.at[targets].add((g * t_scale)[..., None] * l1[:, None, :])
    ctx_live = (live.sum(axis=1) > 0).astype(jnp.float32)
    ctx_scale = _mean_scale(syn0.shape[0], contexts, ctx_live)
    syn0 = syn0.at[contexts].add(ctx_scale[:, None] * neu1e)
    return syn0, syn1neg


def _cbow_body(syn0, syn1, ctx_idx, ctx_mask, points, codes, mask, alpha):
    """One minibatch of HS CBOW examples (CBOW.java): input = mean of context
    vectors, path = center word's; neu1e added to every live context row."""
    cvecs = syn0[ctx_idx]  # (B, C, D)
    denom = jnp.maximum(ctx_mask.sum(axis=1, keepdims=True), 1.0)
    l1 = (cvecs * ctx_mask[..., None]).sum(axis=1) / denom  # (B, D)
    s1 = syn1[points]
    dot = jnp.einsum("bd,bld->bl", l1, s1)
    live = mask * (jnp.abs(dot) < MAX_EXP)
    f = jax.nn.sigmoid(dot)
    g = (1.0 - codes - f) * alpha * live
    neu1e = jnp.einsum("bl,bld->bd", g, s1)  # (B, D)
    s1_scale = _mean_scale(syn1.shape[0], points, live)
    syn1 = syn1.at[points].add((g * s1_scale)[..., None] * l1[:, None, :])
    ctx_scale = _mean_scale(syn0.shape[0], ctx_idx, ctx_mask)
    upd = neu1e[:, None, :] * ctx_scale[..., None]  # (B, C, D)
    syn0 = syn0.at[ctx_idx].add(upd)
    return syn0, syn1


# per-batch jitted HS step (used by graph/deepwalk.py and its tests; the
# NS/CBOW bodies run only inside the fused epoch scans below)
# graftlint: disable=donation-through-dispatch -- functional-update idiom predating ops/dispatch: every caller rebinds to the returned tables and never re-reads the donated args (the no-re-read contract is structural at each call site)
_skipgram_hs_step = functools.partial(jax.jit, donate_argnums=(0, 1))(_hs_body)


# ---------------------------------------------------------------------------
# Whole-epoch device scans
#
# The per-batch step is ~0.1 ms on a TPU chip but each host->device transfer
# through the runtime costs ~ms, so a Python batch loop is transfer-bound
# (measured 71k pairs/sec vs ~16M pairs/sec device capability). The epoch
# scan stages a CHUNK of batches on device in a few large transfers, gathers
# the Huffman path tensors ON DEVICE (P/C/M stay device-resident), and runs
# the whole chunk in one lax.scan — the TPU-native replacement for the
# reference's Hogwild thread pool (SequenceVectors.java:179-198).
# ---------------------------------------------------------------------------


# graftlint: disable=donation-through-dispatch -- functional-update idiom predating ops/dispatch: every caller rebinds to the returned tables and never re-reads the donated args (the no-re-read contract is structural at each call site)
@functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                   static_argnames=("use_neg", "negative_k",
                                    "sgns_kernel", "sgns_interpret"))
def _skipgram_epoch(syn0, syn1, syn1neg, P, C, M, table, cens, cxs,
                    pair_live, keys, alphas, *, use_neg, negative_k,
                    sgns_kernel=False, sgns_interpret=False):
    """Scan over stacked skip-gram batches.

    cens/cxs: [NB, B] int32; pair_live: [NB, B] (0 for padding);
    keys: [NB] uint32 PRNG keys — negatives are drawn ON DEVICE from the
    device-resident unigram `table` (shipping pre-drawn [NB, B, K+1]
    targets/labels/live costs ~75 MB per chunk through the runtime;
    drawing device-side moves only the key); alphas: [NB] per-batch LR.
    sgns_kernel (static, resolved by the caller through
    ops/pallas_sgns.sgns_kernel_enabled) swaps _neg_body for the fused
    Pallas gather-dot-scatter step; sgns_interpret is for tests, which
    pass it themselves."""

    def body(carry, inp):
        syn0, syn1, syn1neg = carry
        cen, cx, plive, key, alpha = inp
        pts = P[cen]
        codes = C[cen]
        mask = M[cen] * plive[:, None]
        syn0, syn1 = _hs_body(syn0, syn1, cx, pts, codes, mask, alpha)
        if use_neg:
            b = cen.shape[0]
            draw_idx = jax.random.randint(
                key, (b, negative_k), 0, table.shape[0]
            )
            draws = table[draw_idx]  # (B, K)
            tgt = jnp.concatenate([cen[:, None], draws], axis=1)
            lbl = jnp.zeros((b, negative_k + 1), jnp.float32).at[:, 0].set(1.0)
            nlive = jnp.concatenate(
                [
                    jnp.ones((b, 1), jnp.float32),
                    (draws != cen[:, None]).astype(jnp.float32),
                ],
                axis=1,
            )
            if sgns_kernel:
                from deeplearning4j_tpu.ops.pallas_sgns import sgns_fused_step

                syn0, syn1neg = sgns_fused_step(
                    syn0, syn1neg, cx, tgt, lbl, nlive * plive[:, None],
                    alpha, interpret=sgns_interpret,
                )
            else:
                syn0, syn1neg = _neg_body(
                    syn0, syn1neg, cx, tgt, lbl, nlive * plive[:, None], alpha
                )
        return (syn0, syn1, syn1neg), None

    (syn0, syn1, syn1neg), _ = jax.lax.scan(
        body, (syn0, syn1, syn1neg),
        (cens, cxs, pair_live, keys, alphas),
    )
    return syn0, syn1, syn1neg


# graftlint: disable=donation-through-dispatch -- functional-update idiom predating ops/dispatch: every caller rebinds to the returned tables and never re-reads the donated args (the no-re-read contract is structural at each call site)
@functools.partial(jax.jit, donate_argnums=(0, 1))
def _cbow_epoch(syn0, syn1, P, C, M, cens, ctxs, cmasks, pair_live, alphas):
    """Scan over stacked CBOW batches (ctxs/cmasks: [NB, B, 2w])."""

    def body(carry, inp):
        syn0, syn1 = carry
        cen, ctx, cmask, plive, alpha = inp
        pts = P[cen]
        codes = C[cen]
        mask = M[cen] * plive[:, None]
        syn0, syn1 = _cbow_body(
            syn0, syn1, ctx, cmask * plive[:, None], pts, codes, mask, alpha
        )
        return (syn0, syn1), None

    (syn0, syn1), _ = jax.lax.scan(
        body, (syn0, syn1), (cens, ctxs, cmasks, pair_live, alphas)
    )
    return syn0, syn1


def _chunk_size(nb: int, cap: int = 128) -> int:
    """Batches per device scan step: the largest power of two <= nb (capped),
    with a floor of 16 — power-of-two buckets bound the number of compiled
    shapes while the largest-fitting choice keeps scan-step padding waste
    under ~8% (a greedy 64+16+16 split for nb=89, not one padded 128)."""
    if nb >= cap:
        return cap
    size = 16
    while size * 2 <= nb:
        size *= 2
    return size


# ---------------------------------------------------------------------------
# Word2Vec
# ---------------------------------------------------------------------------


class Word2Vec:
    """Reference Word2Vec builder surface (models/word2vec/Word2Vec.java:33 +
    SequenceVectors builder): layerSize, windowSize, minWordFrequency,
    learningRate/minLearningRate, iterations/epochs, negativeSample,
    sampling, seed, elements learning algorithm (SkipGram | CBOW)."""

    def __init__(
        self,
        layer_size: int = 100,
        window: int = 5,
        min_word_frequency: int = 1,
        learning_rate: float = 0.025,
        min_learning_rate: float = 1e-4,
        epochs: int = 1,
        iterations: int = 1,
        negative: int = 0,
        sampling: float = 0.0,
        seed: int = 123,
        batch_size: int = 2048,
        use_cbow: bool = False,
        tokenizer: Optional[DefaultTokenizerFactory] = None,
        stop_words: Sequence[str] = (),
        num_workers: Optional[int] = None,
        mesh=None,
    ):
        self.layer_size = layer_size
        self.window = window
        self.min_word_frequency = min_word_frequency
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.epochs = epochs
        self.iterations = iterations
        self.negative = negative
        self.sampling = sampling
        self.seed = seed
        self.batch_size = batch_size
        self.use_cbow = use_cbow
        self.tokenizer = tokenizer or DefaultTokenizerFactory(common_preprocessor)
        self.stop_words = set(stop_words)
        self.vocab: Optional[VocabCache] = None
        self.lookup_table: Optional[InMemoryLookupTable] = None
        # data-parallel training over a device mesh (role of the reference
        # dl4j-spark-nlp distributed Word2Vec driver,
        # spark/models/embeddings/word2vec/Word2Vec.java:65 — partition
        # batches of pairs train against broadcast tables; here the batch is
        # SHARDED over the mesh and GSPMD inserts the psum of the sparse
        # scatter updates, which is deterministic where the reference's
        # asynchronous Word2VecChange application is not)
        self.mesh = None
        if mesh is not None or num_workers is not None:
            from deeplearning4j_tpu.parallel.mesh import device_mesh

            self.mesh = mesh if mesh is not None else device_mesh(num_workers)
            n = int(np.prod(self.mesh.devices.shape))
            if self.batch_size % n != 0:
                raise ValueError(
                    f"batch_size {self.batch_size} not divisible by "
                    f"{n} mesh devices"
                )

    # -- vocab ------------------------------------------------------------
    def _tokenize_corpus(self, sentences: Iterable[str]) -> List[List[str]]:
        out = []
        for s in sentences:
            toks = [t for t in self.tokenizer.tokenize(s) if t not in self.stop_words]
            if toks:
                out.append(toks)
        return out

    def build_vocab(self, token_sequences: Sequence[Sequence[str]]) -> VocabCache:
        self.vocab = VocabConstructor(self.min_word_frequency).build(token_sequences)
        self.lookup_table = InMemoryLookupTable(
            self.vocab,
            self.layer_size,
            seed=self.seed,
            negative=self.negative,
        )
        return self.vocab

    # -- pair assembly (host side) ---------------------------------------
    def _sequences_as_indices(self, token_sequences) -> List[np.ndarray]:
        vocab = self.vocab
        seqs = []
        for toks in token_sequences:
            idx = [vocab.index_of(t) for t in toks]
            idx = np.array([i for i in idx if i >= 0], np.int32)
            if idx.size:
                seqs.append(idx)
        return seqs

    def _subsample(self, seq: np.ndarray, rng: np.random.Generator) -> np.ndarray:
        """Frequent-word subsampling (SkipGram.applySubsampling:100-110):
        keep probability (sqrt(f/(s*N)) + 1) * s*N/f."""
        if self.sampling <= 0:
            return seq
        counts = self._counts[seq]
        total = self.vocab.total_word_occurrences
        s = self.sampling
        ran = (np.sqrt(counts / (s * total)) + 1.0) * (s * total) / counts
        keep = ran >= rng.random(seq.shape)
        return seq[keep]

    def _make_pairs(
        self, seqs: List[np.ndarray], rng: np.random.Generator
    ) -> Tuple[np.ndarray, np.ndarray]:
        """All (center, context) skip-gram pairs with the reference's random
        window shrink b ~ U[0, window) (SkipGram.skipGram: b = nextRandom %
        window, context span a in [b, 2w+1-b), c = i - w + a)."""
        centers, contexts = [], []
        w = self.window
        for seq in seqs:
            seq = self._subsample(seq, rng)
            n = len(seq)
            if n < 2:
                continue
            bs = rng.integers(0, w, size=n)
            for i in range(n):
                b = bs[i]
                lo, hi = max(0, i - w + b), min(n, i + w - b + 1)
                for c in range(lo, hi):
                    if c != i:
                        centers.append(seq[i])
                        contexts.append(seq[c])
        if not centers:
            return np.zeros((0,), np.int32), np.zeros((0,), np.int32)
        return np.asarray(centers, np.int32), np.asarray(contexts, np.int32)

    def _make_cbow_batches(self, seqs, rng):
        """(center, padded-context-window) examples for CBOW."""
        w = self.window
        centers, ctx, cmask = [], [], []
        width = 2 * w
        for seq in seqs:
            seq = self._subsample(seq, rng)
            n = len(seq)
            if n < 2:
                continue
            bs = rng.integers(0, w, size=n)
            for i in range(n):
                b = bs[i]
                lo, hi = max(0, i - w + b), min(n, i + w - b + 1)
                window_idx = [seq[c] for c in range(lo, hi) if c != i]
                if not window_idx:
                    continue
                row = np.zeros((width,), np.int32)
                m = np.zeros((width,), np.float32)
                row[: len(window_idx)] = window_idx
                m[: len(window_idx)] = 1.0
                centers.append(seq[i])
                ctx.append(row)
                cmask.append(m)
        if not centers:
            z = np.zeros((0, width), np.int32)
            return np.zeros((0,), np.int32), z, z.astype(np.float32)
        return (
            np.asarray(centers, np.int32),
            np.stack(ctx),
            np.stack(cmask),
        )

    # -- training ---------------------------------------------------------
    def fit(self, sentences: Iterable[str]) -> "Word2Vec":
        token_sequences = self._tokenize_corpus(sentences)
        return self.fit_tokens(token_sequences)

    def fit_tokens(self, token_sequences: Sequence[Sequence[str]]) -> "Word2Vec":
        if self.vocab is None:
            self.build_vocab(token_sequences)
        lt = self.lookup_table
        self._counts = np.array(
            [wd.count for wd in self.vocab.vocab_words()], np.float64
        )
        seqs = self._sequences_as_indices(token_sequences)
        rng = np.random.default_rng(self.seed)

        P, C, M = lt.huffman_tensors()
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as PSpec

            from deeplearning4j_tpu.parallel.mesh import DATA_AXIS

            repl = NamedSharding(self.mesh, PSpec())
            mesh = self.mesh

            def pb(a):
                # stacked [NB, B, ...] batches: shard the example axis (1)
                a = np.asarray(a)
                spec = PSpec(*((None, DATA_AXIS) + (None,) * (a.ndim - 2)))
                return jax.device_put(jnp.asarray(a), NamedSharding(mesh, spec))

            pt = lambda a: jax.device_put(jnp.asarray(a), repl)
        else:
            pb = pt = jnp.asarray
        syn0 = pt(lt.syn0)
        syn1 = pt(lt.syn1)
        syn1neg = pt(lt.syn1neg) if lt.syn1neg is not None else None

        # Huffman tensors stay device-resident; per-batch path gathers run
        # ON DEVICE inside the epoch scan (transfer-bound otherwise)
        P_dev, C_dev, M_dev = pt(P), pt(C.astype(np.float32)), pt(M.astype(np.float32))

        n_phases = max(1, self.epochs * self.iterations)
        B = self.batch_size
        use_neg = self.negative > 0 and syn1neg is not None
        if not use_neg:
            syn1neg = pt(np.zeros((1, self.layer_size), np.float32))
            table_dev = pt(np.zeros((1,), np.int32))
        else:
            table_dev = pt(np.asarray(lt.table, np.int32))
        base_key = jax.random.PRNGKey(self.seed)
        for phase in range(n_phases):
            if self.use_cbow:
                centers, ctx, cmask = self._make_cbow_batches(seqs, rng)
                order = rng.permutation(len(centers))
                centers, ctx, cmask = centers[order], ctx[order], cmask[order]
                n_ex = len(centers)
                nb = max(1, -(-n_ex // B))
                alphas = np.array(
                    [self._alpha(phase, bi, n_phases, nb) for bi in range(nb)],
                    np.float32,
                )
                for s0, s1, chunk in self._chunks(nb):
                    sl = slice(s0 * B, s1 * B)
                    cen = _pad_rows(centers[sl], chunk * B)
                    cx = _pad_rows(ctx[sl], chunk * B)
                    cm = _pad_rows(cmask[sl], chunk * B)
                    plive = (
                        np.arange(s0 * B, s0 * B + chunk * B) < n_ex
                    ).astype(np.float32)
                    al = _pad_rows(alphas[s0:s1], chunk)
                    syn0, syn1 = _cbow_epoch(
                        syn0, syn1, P_dev, C_dev, M_dev,
                        pb(cen.reshape(chunk, B)),
                        pb(cx.reshape(chunk, B, -1)),
                        pb(cm.reshape(chunk, B, -1)),
                        pb(plive.reshape(chunk, B)),
                        jnp.asarray(al),
                    )
            else:
                centers, contexts = self._make_pairs(seqs, rng)
                order = rng.permutation(len(centers))
                centers, contexts = centers[order], contexts[order]
                n_ex = len(centers)
                # kernel-rent gate, resolved once per fit (trace-time
                # static args — a knob flip recompiles the epoch scan)
                from deeplearning4j_tpu.ops import pallas_sgns

                sgns_on = use_neg and pallas_sgns.sgns_kernel_enabled(
                    B, self.negative + 1, syn0.shape[1]
                )
                nb = max(1, -(-n_ex // B))
                alphas = np.array(
                    [self._alpha(phase, bi, n_phases, nb) for bi in range(nb)],
                    np.float32,
                )
                # The reference runs the HS path always and the NS block
                # additionally when negative>0 (SkipGram.iterateSample:179-252).
                for s0, s1, chunk in self._chunks(nb):
                    sl = slice(s0 * B, s1 * B)
                    cen = _pad_rows(centers[sl], chunk * B)
                    cx = _pad_rows(contexts[sl], chunk * B)
                    plive = (
                        np.arange(s0 * B, s0 * B + chunk * B) < n_ex
                    ).astype(np.float32)
                    al = _pad_rows(alphas[s0:s1], chunk)
                    keys = jax.vmap(
                        lambda i: jax.random.fold_in(base_key, i)
                    )(jnp.arange(s0, s0 + chunk) + phase * nb)
                    syn0, syn1, syn1neg = _skipgram_epoch(
                        syn0, syn1, syn1neg, P_dev, C_dev, M_dev, table_dev,
                        pb(cen.reshape(chunk, B)),
                        pb(cx.reshape(chunk, B)),
                        pb(plive.reshape(chunk, B)),
                        keys,
                        jnp.asarray(al),
                        use_neg=use_neg,
                        negative_k=self.negative,
                        sgns_kernel=sgns_on,
                    )

        lt.syn0 = np.asarray(syn0)
        lt.syn1 = np.asarray(syn1)
        if use_neg:
            lt.syn1neg = np.asarray(syn1neg)
        return self

    @staticmethod
    def _chunks(nb: int):
        """Yield (start_batch, end_batch, chunk_size) macro-chunks; chunk
        sizes are power-of-two buckets so only a handful of XLA shapes
        compile (see _chunk_size)."""
        s0 = 0
        while s0 < nb:
            chunk = _chunk_size(nb - s0)
            yield s0, min(s0 + chunk, nb), chunk
            s0 += chunk

    def _alpha(self, phase, bi, n_phases, nb) -> float:
        progress = (phase * nb + bi) / max(1, n_phases * nb)
        return max(
            self.min_learning_rate, self.learning_rate * (1.0 - progress)
        )

    # -- query API (Word2Vec.java surface) --------------------------------
    def get_word_vector(self, word: str) -> Optional[np.ndarray]:
        return self.lookup_table.vector(word)

    def similarity(self, w1: str, w2: str) -> float:
        return self.lookup_table.similarity(w1, w2)

    def words_nearest(self, word, top_n: int = 10) -> List[str]:
        return self.lookup_table.words_nearest(word, top_n)

    def words_nearest_sum(self, positive, negative, top_n: int = 10) -> List[str]:
        return self.lookup_table.words_nearest_sum(positive, negative, top_n)

    def vocab_size(self) -> int:
        return 0 if self.vocab is None else self.vocab.num_words()


def _pad_batch(arr: np.ndarray, batch: int) -> np.ndarray:
    """Pad the leading dim to `batch` by repeating row 0 — keeps the jitted
    step's shapes static (one XLA compile per batch size)."""
    n = len(arr)
    if n == batch:
        return arr
    pad = np.repeat(arr[:1], batch - n, axis=0)
    return np.concatenate([arr, pad], axis=0)


def _pad_rows(arr: np.ndarray, n: int) -> np.ndarray:
    """Pad the leading dim to n with zeros (dead rows are masked out by the
    pair_live tensor in the epoch scans)."""
    if len(arr) == n:
        return arr
    pad_shape = (n - len(arr),) + arr.shape[1:]
    return np.concatenate([arr, np.zeros(pad_shape, arr.dtype)], axis=0)


