"""Tests for util extras:
MovingWindowMatrix, DiskBasedQueue, moving-window text context,
inverted index."""

import numpy as np

from deeplearning4j_tpu.nlp.invertedindex import InvertedIndex
from deeplearning4j_tpu.nlp.movingwindow import (
    Window,
    WindowConverter,
    strip_context_labels,
    window_for_word_in_position,
    windows,
)
from deeplearning4j_tpu.utils.disk_queue import DiskBasedQueue
from deeplearning4j_tpu.utils.moving_window import MovingWindowMatrix


# --------------------------------------------------------- MovingWindowMatrix
def test_moving_window_matrix_chunks():
    m = np.arange(24).reshape(4, 6)
    wins = MovingWindowMatrix(m, 2, 3).windows()
    assert len(wins) == 4
    np.testing.assert_array_equal(wins[0], [[0, 1, 2], [3, 4, 5]])
    np.testing.assert_array_equal(wins[-1], [[18, 19, 20], [21, 22, 23]])


def test_moving_window_matrix_flattened_and_rotate():
    m = np.arange(8)
    flat = MovingWindowMatrix(m, 2, 2).windows(flattened=True)
    assert len(flat) == 2 and flat[0].shape == (4,)
    rot = MovingWindowMatrix(m, 2, 2, add_rotate=True).windows()
    assert len(rot) == 8  # each window + 3 rotations
    # the last entry of each group of 4 is the unrotated window
    np.testing.assert_array_equal(rot[3], [[0, 1], [2, 3]])


# -------------------------------------------------------------- DiskBasedQueue
def test_disk_queue_fifo(tmp_path):
    q = DiskBasedQueue(str(tmp_path))
    assert q.is_empty() and q.poll() is None
    q.add({"a": 1})
    q.add(np.arange(3))
    assert len(q) == 2
    assert q.peek() == {"a": 1}
    assert q.poll() == {"a": 1}
    np.testing.assert_array_equal(q.poll(), np.arange(3))
    assert q.poll() is None
    # spill files cleaned up
    q.add(1)
    q.clear()
    assert q.is_empty()
    assert not list(tmp_path.glob("*.pkl"))


# --------------------------------------------------------------- movingwindow
def test_windows_padding_and_focus():
    toks = "the quick brown fox jumps".split()
    ws = windows(toks, window_size=5)
    assert len(ws) == 5
    w0 = ws[0]
    assert w0.words == ["<s>", "<s>", "the", "quick", "brown"]
    assert w0.focus_word == "the"
    assert w0.is_begin_label()
    w_last = ws[-1]
    assert w_last.words == ["brown", "fox", "jumps", "</s>", "</s>"]
    assert ws[2].words == toks
    assert ws[2].focus_word == "brown"


def test_window_converter_concatenates_vectors():
    vecs = {"a": np.ones(3, np.float32), "b": 2 * np.ones(3, np.float32)}
    w = window_for_word_in_position(3, 0, ["a", "b"])
    ex = WindowConverter.as_example(w, vecs, 3)
    assert ex.shape == (9,)
    np.testing.assert_array_equal(ex[:3], 0)  # <s> has no vector
    np.testing.assert_array_equal(ex[3:6], 1)
    np.testing.assert_array_equal(ex[6:], 2)


def test_strip_context_labels():
    plain, spans = strip_context_labels(
        "went to <LOC> new york </LOC> with <PER>alice</PER>"
    )
    assert plain == "went to new york with alice"
    assert spans == [("LOC", "new york"), ("PER", "alice")]


# --------------------------------------------------------------- invertedindex
def test_inverted_index_postings_and_sample():
    ix = InvertedIndex()
    d0 = ix.add_words_to_doc("the cat sat".split(), label="x")
    d1 = ix.add_words_to_doc("the dog ran".split())
    assert (d0, d1) == (0, 1)
    assert ix.num_documents() == 2
    assert ix.documents("the") == [0, 1]
    assert ix.documents("cat") == [0]
    assert ix.doc_frequency("dog") == 1
    assert ix.document(1) == ["the", "dog", "ran"]
    assert ix.document_label(0) == "x"
    assert len(ix.sample(5)) == 5
    seen = []
    ix.eachDoc(seen.append)
    assert len(seen) == 2
