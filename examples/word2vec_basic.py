"""Word2Vec skip-gram negative sampling — the reference's
Word2VecRawTextExample: build vocab, train embeddings, query nearest words."""


import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.nlp.word2vec import Word2Vec
from deeplearning4j_tpu.ops import env as envknob

SENTENCES = [
    "the king rules the kingdom with the queen",
    "the queen rules beside the king",
    "a dog chases the cat around the yard",
    "the cat sleeps while the dog barks",
    "day follows night and night follows day",
    "the sun shines during the day",
    "the moon glows at night",
    "kings and queens live in castles",
    "dogs and cats are animals",
] * 30


# tiny-shape mode for the `-m examples` smoke tier (tests/test_examples.py)
SMOKE = envknob.nonempty("DL4J_TPU_EXAMPLE_SMOKE")


def main():
    w2v = Word2Vec(layer_size=48, window=4, negative=5, min_word_frequency=3,
                   epochs=2 if SMOKE else 8, seed=42)
    w2v.fit(SENTENCES)
    for word in ("king", "dog", "day"):
        print(f"nearest to '{word}':", w2v.words_nearest(word, 4))
    print("similarity(king, queen) =",
          round(w2v.similarity("king", "queen"), 3))
    print("similarity(king, cat)   =",
          round(w2v.similarity("king", "cat"), 3))


if __name__ == "__main__":
    main()
