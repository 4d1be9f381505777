"""The reader of the serving window (perfbench/window.py) over hand-made
`loadgen.Request` lists: plain Python, no JAX, no program. What belongs to the
window, what a failed request reads, and that every candidate for a tail is
the statistic its name says."""
import numpy as np
import pytest

from perfbench import loadgen, window

T0, T1, SECONDS = 100.0, 140.0, 40.0
WORST_MS = 1e3 * (SECONDS + window.DRAIN_S)


def req(t_send, first_after, n_tokens, gap=0.02, prompt=10, ok=True):
    """A request sent at `t_send` whose first token comes `first_after`
    seconds later and the others `gap` apart."""
    r = loadgen.Request(0, {"tokens": np.zeros(prompt, np.int32),
                            "n_new": n_tokens, "temperature": 0.0, "seed": 0})
    r.t_send = t_send
    r.token_times = [t_send + first_after + i * gap for i in range(n_tokens)]
    r.tokens = [1] * n_tokens
    r.finished = ok
    if not ok:
        r.error = "HTTP 500"
    return r


def test_a_request_sent_before_the_window_is_in_no_tail_but_its_tokens_count():
    early = req(T0 - 1.0, 5.0, 100, gap=0.02)      # a burst's: 5 s to wait
    # its tokens from T0 - 1 + 5 = T0 + 4 on, all inside the window
    inside = [req(T0 + 1.0 + i, 0.050, 10) for i in range(10)]
    got = window.read([early] + inside, T0, T1, SECONDS)
    assert early not in got["mine"] and len(got["mine"]) == 10
    w = got["window"]
    assert w["requests"] == 10
    assert w["ttft_p99_ms"] == pytest.approx(50.0)       # not 5,000
    assert w["decode_tokens"] == 100 + 10 * 10
    assert w["gaps"] == 99 + 10 * 9
    assert w["prefill_tokens"] == 11 * 10     # its first token came inside
    assert got["end_to_end"]["serve_tokens_per_s"] == pytest.approx(200 / 40)


def test_tokens_outside_the_window_are_not_counted():
    # first token before T0, the rest inside; and one that runs past T1
    before = req(T0 - 0.5, 0.1, 50, gap=0.02)       # tokens from T0 - 0.4
    after = req(T1 - 0.1, 0.05, 50, gap=0.02)       # sent inside, ends after
    got = window.read([before, after], T0, T1, SECONDS)
    inside_b = sum(1 for t in before.token_times if T0 <= t < T1)
    inside_a = sum(1 for t in after.token_times if T0 <= t < T1)
    assert 0 < inside_b < 50 and 0 < inside_a < 50
    assert got["window"]["decode_tokens"] == inside_b + inside_a
    # a gap counts where its later token arrived inside
    assert got["window"]["gaps"] == inside_b + (inside_a - 1)
    assert got["mine"] == [after]
    # `before`'s first token came before the window: no prefill of its own
    assert got["window"]["prefill_tokens"] == 10


def test_a_request_sent_at_the_close_belongs_to_the_traced_tail():
    got = window.read([req(T1, 0.05, 5), req(T1 + 1, 0.05, 5)], T0, T1,
                      SECONDS)
    assert got["mine"] == [] and got["window"]["requests"] == 0


def test_a_failed_request_reads_the_worst_value():
    reqs = [req(T0 + i, 0.040, 5) for i in range(19)]
    reqs.append(req(T0 + 20, 0.040, 2, ok=False))
    got = window.read(reqs, T0, T1, SECONDS)
    assert got["failed"] == [reqs[-1]] and len(got["mine"]) == 20
    assert got["window"]["ttft_p99_ms"] > 0.5 * WORST_MS
    assert got["window"]["ttft_slow10_mean_ms"] == pytest.approx(
        (WORST_MS + 40.0) / 2)
    assert got["window"]["ttft_p50_ms"] == pytest.approx(40.0)
    # the tokens it did stream still arrived
    assert got["window"]["decode_tokens"] == 19 * 5 + 2


def test_a_request_without_a_token_reads_the_worst_value():
    r = req(T0 + 1, 0.0, 0)
    assert r.ok and not r.token_times
    got = window.read([r], T0, T1, SECONDS)
    assert got["window"]["ttft_p95_ms"] == WORST_MS


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_candidates_are_the_statistics_their_names_say(seed):
    rng = np.random.default_rng(seed)
    n = 1000 + seed
    waits = rng.lognormal(np.log(0.05), 0.5, n)
    gap = 0.013
    reqs = [req(T0 + 39.0 * i / n, float(w), 3, gap=gap)
            for i, w in enumerate(waits)]
    w = window.read(reqs, T0, T1, SECONDS)["window"]
    ms = 1e3 * waits
    for q in (50, 90, 95, 99):
        assert w[f"ttft_p{q}_ms"] == pytest.approx(np.percentile(ms, q))
    slowest = np.sort(ms)[-(n // 10):]
    assert len(slowest) == n // 10
    assert w["ttft_slow10_mean_ms"] == pytest.approx(slowest.mean())
    assert w["ttft_p90_ms"] <= slowest.min() + 1e-9
    for q in (50, 95, 99):
        assert w[f"gap_p{q}_ms"] == pytest.approx(1e3 * gap)
    assert w["requests"] == n and w["gaps"] == 2 * n


def test_the_slowest_tenth_of_a_few_is_the_slowest_one():
    assert window.slowest_tenth_mean([3.0, 1.0, 2.0]) == 3.0
    assert window.slowest_tenth_mean(list(range(20))) == 18.5


def test_an_empty_window_reads_the_worst_value_and_no_candidate():
    got = window.read([req(T0 - 5, 0.1, 3)], T0, T1, SECONDS)
    assert got["mine"] == [] and got["failed"] == []
    e2e, w = got["end_to_end"], got["window"]
    assert e2e["serve_tokens_per_s"] == 0.0
    for name in ("ttft_p90_ms", "ttft_p95_ms", "ttft_slow10_mean_ms",
                 "gap_p95_ms"):
        assert e2e[name] == WORST_MS and w[name] is None
    assert w["requests"] == 0 and w["gaps"] == 0


def test_end_to_end_holds_every_candidate_under_a_metrics_name():
    got = window.read([req(T0 + 1, 0.05, 4)], T0, T1, SECONDS)
    assert set(got["end_to_end"]) == {
        "serve_tokens_per_s", "ttft_p50_ms", "ttft_p90_ms", "ttft_p95_ms",
        "ttft_p99_ms", "ttft_slow10_mean_ms", "gap_p50_ms", "gap_p95_ms",
        "gap_p99_ms"}
    for name, value in got["end_to_end"].items():
        if name != "serve_tokens_per_s":
            assert value == got["window"][name]


def test_the_longest_silence_is_read_across_all_requests():
    a = req(T0 + 1.0, 0.05, 100, gap=0.02)     # tokens until T0 + 3.03
    b = req(T0 + 9.0, 0.05, 1500, gap=0.02)    # from T0 + 9.05 to T0 + 39.03
    got = window.read([a, b], T0, T1, SECONDS)
    # nothing arrived between a's last token and b's first: 6.02 s
    assert got["window"]["silence_max_ms"] == pytest.approx(6020.0)
    at, ms = got["window"]["silences"][0]
    assert at == pytest.approx(3.03) and ms == pytest.approx(6020.0)
    assert len(got["window"]["silences"]) == 3
    assert window.read([], T0, T1, SECONDS)["window"]["silence_max_ms"] \
        == pytest.approx(1e3 * SECONDS)


def test_attended_pairs_count_the_prompt_and_each_token_inside():
    r = req(T0 + 1, 0.05, 3, prompt=7)
    got = window.read([r], T0, T1, SECONDS)
    # the prefill's triangle, then a row a token: 7, 8, 9 positions seen
    assert got["window"]["attended_pairs"] == 7 * 8 / 2 + 7 + 8 + 9

