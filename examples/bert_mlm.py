"""BERT-style masked-LM pretraining over the framework's NLP pipeline.

End-to-end text path: DefaultTokenizerFactory -> VocabCache (the same
vocab plane word2vec uses — reference AbstractCache/VocabConstructor,
SURVEY.md section 2.3) -> id sequences -> BertMLM whole-step-jit
pretraining -> masked-token recovery + contextual embeddings. The corpus
is deterministic synthetic "sentences" with strong local structure, so a
minute of CPU training visibly learns to fill in the blanks.

Run from the repo root:  python examples/bert_mlm.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from deeplearning4j_tpu.models.bert import BertConfig, BertMLM  # noqa: E402
from deeplearning4j_tpu.nlp.text import DefaultTokenizerFactory  # noqa: E402
from deeplearning4j_tpu.nlp.vocab import VocabCache  # noqa: E402
from deeplearning4j_tpu.ops import env as envknob

SEQ_LEN = 12
PAD, MASK = "[PAD]", "[MASK]"

SUBJECTS = ["the cat", "a dog", "the bird", "one fish"]
VERBS = ["sat on", "ran past", "looked at", "slept under"]
OBJECTS = ["the mat", "a tree", "the fence", "one rock"]

# tiny-shape mode for the `-m examples` smoke tier (tests/test_examples.py)
SMOKE = envknob.nonempty("DL4J_TPU_EXAMPLE_SMOKE")


def corpus(n: int, rng) -> list:
    return [f"{SUBJECTS[rng.integers(4)]} {VERBS[rng.integers(4)]} "
            f"{OBJECTS[rng.integers(4)]} today" for _ in range(n)]


def main() -> None:
    rng = np.random.default_rng(0)
    sentences = corpus(256, rng)

    tok = DefaultTokenizerFactory()
    vocab = VocabCache()
    # huge counts pin the special tokens to indices 0/1 after finalize
    vocab.add_token(PAD, 1e9)
    vocab.add_token(MASK, 1e8)
    tokenized = [tok.tokenize(s) for s in sentences]
    for words in tokenized:
        for w in words:
            vocab.add_token(w)
    vocab.finalize_vocab()
    print(f"vocab: {vocab.num_words()} words "
          f"(pad={vocab.index_of(PAD)}, mask={vocab.index_of(MASK)})")

    def to_ids(words):
        ids = [vocab.index_of(w) for w in words][:SEQ_LEN]
        return ids + [vocab.index_of(PAD)] * (SEQ_LEN - len(ids))

    data = np.asarray([to_ids(w) for w in tokenized])

    cfg = BertConfig(vocab_size=vocab.num_words(), d_model=48, n_layers=2,
                     n_heads=4, d_ff=96, max_len=SEQ_LEN,
                     learning_rate=5e-3, mlm_prob=0.2,
                     pad_token_id=vocab.index_of(PAD),
                     mask_token_id=vocab.index_of(MASK), seed=0)
    lm = BertMLM(cfg)
    first = lm.fit(data[:64])
    for epoch in range(4 if SMOKE else 30):
        for i in range(0, len(data), 64):
            loss = lm.fit(data[i:i + 64])
        if epoch % 10 == 0:
            acc = lm.masked_accuracy(data[:64], n_draws=2)
            print(f"epoch {epoch:2d}: loss {loss:.3f}, masked acc {acc:.2f}")
    acc = lm.masked_accuracy(data[:64], n_draws=4)
    print(f"final: loss {first:.3f} -> {loss:.3f}, masked acc {acc:.2f}")

    # fill-in-the-blank: mask the verb of a fresh sentence
    words = tok.tokenize("the cat sat on the mat today")
    ids = np.asarray([to_ids(words)])
    masked = ids.copy()
    masked[0, 2] = cfg.mask_id  # "sat"
    pred = int(lm.predict_logits(masked)[0, 2].argmax())
    print(f"'the cat [MASK] on the mat today' -> {vocab.word_at_index(pred)!r}")

    emb = lm.embed_tokens(ids)
    print(f"contextual embeddings: {emb.shape}")


if __name__ == "__main__":
    main()
