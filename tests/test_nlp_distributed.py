"""Distributed NLP training tests.

Mirrors the reference dl4j-spark-nlp surface: TextPipeline partitioned vocab
build (spark/text/functions/TextPipeline.java) and data-parallel
Word2Vec/GloVe (spark/models/embeddings/word2vec/Word2Vec.java:65) — on the
virtual 8-device CPU mesh, following the distributed==serial test strategy
(SURVEY.md §4)."""

import numpy as np
import pytest

from deeplearning4j_tpu.nlp.glove import Glove
from deeplearning4j_tpu.nlp.text_pipeline import TextPipeline
from deeplearning4j_tpu.nlp.word2vec import Word2Vec

from tests.test_nlp import make_corpus


class TestTextPipeline:
    def test_counts_match_serial(self):
        corpus = make_corpus(n=200)
        tp8 = TextPipeline(min_word_frequency=1, num_partitions=8).fit(corpus)
        tp1 = TextPipeline(min_word_frequency=1, num_partitions=1).fit(corpus)
        assert tp8.word_counts == tp1.word_counts

    def test_vocab_matches_word2vec_build(self):
        corpus = make_corpus(n=200)
        tp = TextPipeline(min_word_frequency=2, num_partitions=8).fit(corpus)
        w2v = Word2Vec(layer_size=8, min_word_frequency=2)
        w2v.build_vocab(w2v._tokenize_corpus(corpus))
        words_tp = {w.word for w in tp.vocab.vocab_words()}
        words_w2v = {w.word for w in w2v.vocab.vocab_words()}
        assert words_tp == words_w2v

    def test_min_frequency_filter(self):
        tp = TextPipeline(min_word_frequency=3, num_partitions=4).fit(
            ["a a a b b c"]
        )
        assert set(tp.filtered_counts()) == {"a"}


class TestDistributedWord2Vec:
    def test_8dev_matches_serial_exactly(self):
        """Sharded batches + GSPMD psum of the scatter updates compute the
        SAME math as the serial step — tables must match (tolerance covers
        reduction-order-sensitive float sums)."""
        corpus = make_corpus(n=120)
        kw = dict(layer_size=16, window=3, epochs=1, seed=4, negative=5,
                  batch_size=256)
        serial = Word2Vec(**kw).fit(corpus)
        dist = Word2Vec(num_workers=8, **kw).fit(corpus)
        np.testing.assert_allclose(
            serial.lookup_table.syn0, dist.lookup_table.syn0,
            rtol=5e-4, atol=5e-6,
        )

    def test_8dev_similarity_quality(self):
        """The distributed model passes the same topical-similarity bar as
        the serial tests (reference Word2VecTests pattern)."""
        vec = Word2Vec(layer_size=32, window=3, epochs=3, seed=11,
                       negative=5, batch_size=512, num_workers=8)
        vec.fit(make_corpus(n=300))
        in_cluster = vec.similarity("day", "night")
        cross = vec.similarity("day", "cat")
        assert in_cluster > cross, (in_cluster, cross)

    def test_batch_size_divisibility_validated(self):
        with pytest.raises(ValueError, match="divisible"):
            Word2Vec(batch_size=100, num_workers=8)


class TestDistributedGlove:
    def test_8dev_matches_serial(self):
        corpus = make_corpus(n=150)
        kw = dict(layer_size=16, epochs=2, min_word_frequency=1, seed=5,
                  batch_size=512)
        serial = Glove(**kw).fit(corpus)
        dist = Glove(num_workers=8, **kw).fit(corpus)
        np.testing.assert_allclose(serial.W, dist.W, rtol=5e-4, atol=5e-6)

    def test_batch_size_divisibility_validated(self):
        with pytest.raises(ValueError, match="divisible"):
            Glove(batch_size=100, num_workers=8)
