"""Percentile, latency and rate arithmetic of the benchmark."""
import benchpaths  # noqa: F401
import pytest

from benchlib import stats


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.beyond(100, 90) == 10
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_failed_requests_sort_last():
    lat = stats.latencies([(0.0, 0.1, True), (0.1, None, False),
                           (0.2, 0.3, True)], 0.0, 1.0)
    assert stats.percentile(lat, 90) == float("inf")


def test_latency_runs_from_the_due_time_through_a_stall():
    # one request every 0.1 s for 10 s; the server stalls from 5.0 to
    # 6.0 s and answers everything due meanwhile at 6.0 s
    recs = []
    for i in range(100):
        due = i * 0.1
        done = 6.0 if 5.0 <= due < 6.0 else due + 0.01
        recs.append((due, done, True))
    lat = stats.latencies(recs, 0.0, 10.0)
    assert len(lat) == 100
    # the ten requests due in the stall wait 1.0 s down to 0.1 s, and
    # all ten lie beyond the 90th percentile
    assert max(lat) == pytest.approx(1.0)
    assert stats.percentile(lat, 90) == pytest.approx(0.01)
    assert stats.percentile(lat, 91) == pytest.approx(0.1)
    assert stats.percentile(lat, 95) == pytest.approx(0.5)
    assert stats.rate(len(lat), 0.0, 10.0) == 10


def test_window_edges():
    recs = [(0.0, 0.5, True), (0.5, 1.0, True), (0.9, 1.2, True),
            (1.0, 1.1, True), (0.2, 0.8, False)]
    lat = stats.latencies(recs, 0.0, 1.0)
    assert len(lat) == 4
    assert sorted(lat)[:3] == pytest.approx([0.3, 0.5, 0.5])
    assert sorted(lat)[-1] == float("inf")
    with pytest.raises(ValueError):
        stats.rate(3, 1.0, 1.0)


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (10.0, 11.0)]
    assert stats.union_length(iv) == pytest.approx(4.0)
    assert stats.union_length(iv, 0.5, 3.5) == pytest.approx(2.0)
    assert stats.gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert stats.gaps([], 0.0, 1.0) == [(0.0, 1.0)]
    assert stats.gaps([(-1.0, 9.0)], 0.0, 1.0) == []


def test_tokens_are_spread_over_the_call_that_made_them():
    from benchlib import files
    lm = files.module("systems", "lm")
    now = [0.0]

    class Stepper:
        def prefill(self, spec):
            now[0] += 0.5
            return [object()]

        def insert(self, state, slot, row):
            return state

        def step(self, state):
            now[0] += 1.0
            return state, None

        def finish(self, state, slot, first, collected):
            return None

    st = Stepper()
    clock = lm.TokenClock(st, clock=lambda: now[0])
    st.prefill(None)                       # 1 token over [0.0, 0.5)
    for slot in range(8):
        st.insert(None, slot, None)
    for _ in range(3):                     # 8 tokens over each second
        st.step(None)
    st.finish(None, 0, None, [])
    st.step(None)                          # 7 tokens over [3.5, 4.5)
    assert clock.tokens_in(0.0, 10.0) == pytest.approx(1 + 24 + 7)
    assert clock.tokens_in(0.25, 1.0) == pytest.approx(0.5 + 4)
    assert clock.tokens_in(4.0, 4.5) == pytest.approx(3.5)
    assert clock.prefills_in(0.0, 0.25) == pytest.approx(0.5)
    assert clock.prefills_in(1.0, 9.0) == 0.0
