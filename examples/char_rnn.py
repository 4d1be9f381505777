"""Char-RNN language modelling — the reference's
GravesLSTMCharModellingExample: 2-layer LSTM, TBPTT training, then
streaming generation through the jitted `rnn_time_step` path."""


import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deeplearning4j_tpu.models.char_rnn import char_rnn_conf
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import env as envknob

CORPUS = (
    "the quick brown fox jumps over the lazy dog. "
    "she sells sea shells by the sea shore. "
    "peter piper picked a peck of pickled peppers. "
) * 40

# tiny-shape mode for the `-m examples` smoke tier (tests/test_examples.py)
SMOKE = envknob.nonempty("DL4J_TPU_EXAMPLE_SMOKE")


def main():
    chars = sorted(set(CORPUS))
    stoi = {c: i for i, c in enumerate(chars)}
    vocab = len(chars)
    ids = np.array([stoi[c] for c in CORPUS], np.int64)
    eye = np.eye(vocab, dtype=np.float32)

    seq, batch = 60, 16
    net = MultiLayerNetwork(
        char_rnn_conf(vocab, lstm_size=96, num_layers=2, tbptt_length=30)
    ).init(input_shape=(1, vocab))

    rng = np.random.default_rng(0)
    for step in range(8 if SMOKE else 60):
        starts = rng.integers(0, len(ids) - seq - 1, batch)
        x = eye[np.stack([ids[s:s + seq] for s in starts])]
        y = eye[np.stack([ids[s + 1:s + seq + 1] for s in starts])]
        loss = float(net.fit(x, y))
        if step % 20 == 0:
            print(f"step {step}: loss {loss:.3f}")

    # streaming sampling (reference rnnTimeStep :2152)
    net.rnn_clear_previous_state()
    cur = stoi["t"]
    out = ["t"]
    g = np.random.default_rng(1)
    for _ in range(20 if SMOKE else 120):
        probs = np.asarray(net.rnn_time_step(eye[cur][None, None, :]))[0, 0]
        probs = np.maximum(probs, 0)
        probs /= probs.sum()
        cur = int(g.choice(vocab, p=probs))
        out.append(chars[cur])
    print("sample:", "".join(out))


if __name__ == "__main__":
    main()
