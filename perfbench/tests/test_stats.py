"""The rate and percentile arithmetic on a synthetic event log."""
import pytest

from perfbench.harness import stats


def synthetic(stall_at=None, stall_s=0.0, n_req=40, tick=0.25):
    """Requests due every 0.5 s from t=0; a round every `tick` seconds
    commits one first token a round after a request is due, then 8 tokens a
    round for each resident request, 32 tokens in all. A stall of `stall_s`
    at `stall_at` delays every round after it."""
    def at(t):
        return t + (stall_s if stall_at is not None and t >= stall_at
                    else 0.0)
    log = []
    for i in range(n_req):
        due = 0.5 * i
        r = stats.Served(seq=i, due=due, prompt_len=10, max_new=33)
        r.sent = due + 0.001
        k = int(due / tick) + 1             # the next round's boundary
        r.commits.append((at(k * tick), 1, 0))
        for j in range(4):
            r.commits.append((at((k + 1 + j) * tick), 8, 1 + 8 * j))
        r.finished = r.commits[-1][0]
        log.append(r)
    return log


def test_rate_is_all_tokens_over_all_time():
    log = synthetic()
    t0, t1 = 5.0, 15.0
    counted = sum(n for r in log for t, n, _ in r.commits if t0 < t <= t1)
    s = stats.serving_summary(log, t0, t1)
    assert s["tokens"] == pytest.approx(counted)
    assert s["tokens_per_s"] == pytest.approx(counted / 10.0)
    # a window that closes half way through a round counts half of what
    # that round commits (two resident requests, 8 tokens each), not
    # nothing: the rate does not step with where the edges fall
    assert stats.tokens_in(log, t0, t1 + 0.125) == pytest.approx(counted + 8)
    # requests in flight at the open count for the rate and for no latency
    early = [r for r in log if r.due < t0 and r.last > t0]
    assert early
    assert s["attempted"] == sum(t0 <= r.due < t1 for r in log)
    assert len(s["ttft_ms"]) == s["attempted"]


def test_a_three_second_stall_shows_in_every_number():
    t0, t1 = 5.0, 15.0
    calm = stats.serving_summary(synthetic(), t0, t1)
    log = synthetic(stall_at=9.0, stall_s=3.0)
    hit = stats.serving_summary(log, t0, t1)
    assert hit["tokens_per_s"] < calm["tokens_per_s"]
    base = {r.seq: r for r in synthetic()}
    for r in stats.due_in(log, t0, t1):
        b = base[r.seq]
        resident = b.first < 9.0 <= b.last     # resident during the stall
        if resident:
            assert stats.tpot_ms(r) == pytest.approx(
                stats.tpot_ms(b) + 3000.0 / 32)
        if b.due < 9.0 <= b.first or 9.0 <= r.due < 12.0:
            assert stats.ttft_ms(r) > stats.ttft_ms(b) + 1.0   # due in it
    assert stats.percentile(hit["ttft_ms"], 75) > \
        stats.percentile(calm["ttft_ms"], 75)
    assert stats.percentile(hit["tpot_ms"], 90) > \
        stats.percentile(calm["tpot_ms"], 90)


def test_an_unfinished_or_tokenless_request_fails():
    log = synthetic(n_req=4)
    log[1].finished = None
    log[2].commits.clear()
    log[3].error = "OverloadError()"
    s = stats.serving_summary(log, 0.0, 10.0)
    assert (s["attempted"], s["failed"]) == (4, 3)
    assert len(s["ttft_ms"]) == 1
    log[1].withdrawn = True                    # a closed loop's client left
    assert stats.serving_summary(log, 0.0, 10.0)["attempted"] == 3


def test_work_counts_live_rows():
    r = stats.Served(seq=0, due=0.0, prompt_len=100, max_new=10)
    r.commits = [(1.0, 1, 0), (2.0, 8, 1), (3.0, 1, 9)]
    w = stats.decode_and_prefill_work([r], 0.0, 10.0)
    assert w["prefill_tokens"] == 100 and w["prefill_pairs"] == 5050
    assert w["decode_tokens"] == 9
    # output index j (1..9) reads a cache of 100 + j rows
    assert w["decode_rows"] == sum(100 + j for j in range(1, 10))
    assert stats.decode_and_prefill_work([r], 1.5, 2.5)["decode_tokens"] == 8


def test_percentile_is_numpys_linear():
    assert stats.percentile([1, 2, 3, 4], 75) == pytest.approx(3.25)
