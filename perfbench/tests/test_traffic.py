"""The traffic generators: the same seed gives the same inputs, lengths stay
within their clips, and every seed gets the same sizes in the same order."""
import os

import numpy as np
import pytest

from perfbench import harness, traffic

CHAT = harness.load_json(os.path.join(harness.HERE, "traffic", "chat.json"))
SEQ = harness.load_json(os.path.join(harness.HERE, "traffic", "seq2048.json"))
BIG = 2**31 + 12345      # more than 32 signed bits hold


def test_train_batches_repeat_and_differ():
    x1, y1 = traffic.train_batch(SEQ, 50257, BIG, 3)
    x2, y2 = traffic.train_batch(SEQ, 50257, BIG, 3)
    x3, _ = traffic.train_batch(SEQ, 50257, BIG, 4)
    assert x1.shape == (8, 2048) and x1.dtype == np.int32
    assert (x1 == x2).all() and (y1 == y2).all()
    assert (x1[:, 1:] == y1[:, :-1]).all()          # next-token targets
    assert (x1 != x3).any()
    assert len({row.tobytes() for row in x1}) == 8   # rows all differ
    assert 0 <= x1.min() and x1.max() < 50257


def test_chat_requests_repeat_and_keep_their_clips():
    a = traffic.chat_requests(CHAT, 50257, BIG, 16)
    b = traffic.chat_requests(CHAT, 50257, BIG, 16)
    assert len(a) == 32 and all(len(c) == 16 for c in a)
    for ca, cb in zip(a, b):
        for ra, rb in zip(ca, cb):
            assert (ra["tokens"] == rb["tokens"]).all()
            assert (ra["n_new"], ra["seed"], ra["temperature"]) == \
                (rb["n_new"], rb["seed"], rb["temperature"])
    for c in a:
        for r in c:
            assert 128 + 32 <= len(r["tokens"]) <= 128 + 864
            assert 16 <= r["n_new"] <= 192
    assert sum(c[0]["temperature"] == 0.0 for c in a) == 16


def test_every_seed_gets_the_same_sizes_in_the_same_order():
    a = traffic.chat_requests(CHAT, 50257, 1, 16)
    b = traffic.chat_requests(CHAT, 50257, BIG, 16)
    shape = lambda cs: [[(len(r["tokens"]), r["n_new"], r["temperature"])
                         for r in c] for c in cs]
    assert shape(a) == shape(b)
    # and other tokens, other system prompts, other sampling seeds
    assert (a[0][0]["tokens"][:128] != b[0][0]["tokens"][:128]).any()
    assert (a[0][0]["tokens"][128:] != b[0][0]["tokens"][128:]).any()
    assert [r["seed"] for r in a[-1]] != [r["seed"] for r in b[-1]]


def test_prompts_open_with_one_of_the_system_prompts():
    a = traffic.chat_requests(CHAT, 50257, 5, 4)
    heads = {r["tokens"][:128].tobytes() for c in a for r in c}
    assert len(heads) == 4


def test_warm_lengths_reach_both_ends():
    ls = traffic.warm_lengths(CHAT)
    assert ls[0] == 160 and ls[-1] == 992 and ls == sorted(set(ls))
    # never more than a quarter longer than the last: no rung of a ladder
    # whose rungs lie a quarter apart or more is stepped over
    assert all(b <= 1.25 * a for a, b in zip(ls, ls[1:]))
    assert len(ls) == 12


@pytest.mark.parametrize("lo, hi", [(1, 9), (3, 200), (16, 448), (100, 101),
                                    (7, 7)])
def test_warm_lengths_step_a_quarter_at_the_most(lo, hi):
    mix = {"system_tokens": 0, "user_tokens": {"min": lo, "max": hi}}
    ls = traffic.warm_lengths(mix)
    assert ls[0] == lo and ls[-1] == hi
    assert all(a < b <= max(a + 1, 1.25 * a) for a, b in zip(ls, ls[1:]))
