"""The window loop on a fake query and a fake clock."""
import numpy as np
import pytest

import loop


class FakeClock:
    """Nanoseconds that move only when the fake query runs."""

    def __init__(self):
        self.now = 0

    def __call__(self):
        return self.now


def fake_query(clock, durations_ns, fail_at=()):
    calls = iter(range(10**9))

    def query():
        i = next(calls)
        clock.now += durations_ns[i % len(durations_ns)]
        if i in fail_at:
            raise RuntimeError("boom")
        return i
    return query


def test_whole_queries_only_and_window_closes_at_a_boundary():
    clock = FakeClock()
    ns, attempted, failed = loop.measure(
        fake_query(clock, [400_000_000]), lambda a: True, seconds=1.0,
        clock=clock)
    # 0.4 s a query: the third starts at 0.8 s, inside the window, and is
    # finished and counted; the window closes at 1.2 s, a query boundary
    assert list(ns) == [400_000_000] * 3
    assert (attempted, failed, clock.now) == (3, 0, 1_200_000_000)


def test_a_long_query_is_measured_at_least_twice():
    clock = FakeClock()
    ns, attempted, _ = loop.measure(
        fake_query(clock, [21_000_000_000]), lambda a: True, seconds=1.0,
        min_queries=2, clock=clock)
    assert len(ns) == attempted == 2


def test_raised_and_wrong_answers_are_failed():
    clock = FakeClock()
    ns, attempted, failed = loop.measure(
        fake_query(clock, [100_000_000], fail_at={1}),
        lambda answer: answer != 3, seconds=0.5, clock=clock)
    assert attempted == 5 and failed == 2
    assert len(ns) == 4   # the query that raised has no time


def test_order_statistics_are_samples_not_means():
    ns = np.array([1, 2, 3, 4, 1000], dtype=np.int64) * 1_000_000
    stats = loop.order_statistics(ns)
    assert stats == {"query_s": pytest.approx(0.003)}   # no tail from 5


@pytest.mark.parametrize("n, has_tail", [(99, False), (100, True)])
def test_tail_only_from_100_samples(n, has_tail):
    ns = np.arange(1, n + 1, dtype=np.int64) * 1_000_000
    stats = loop.order_statistics(ns[::-1])
    assert ("query_p90_s" in stats) == has_tail
    if has_tail:
        assert stats["query_p90_s"] == pytest.approx(0.090)
        assert stats["query_s"] == pytest.approx(0.0505)


def test_no_samples_no_statistics():
    assert loop.order_statistics(np.array([], dtype=np.int64)) == {}
