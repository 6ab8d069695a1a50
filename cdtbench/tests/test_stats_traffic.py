"""Percentile and window arithmetic; the generator's schedules."""

import statistics
import threading
import time

import pytest

from cdtbench import stats, traffic


def test_percentile_interpolates_between_order_statistics():
    values = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert stats.percentile(values, 50) == 3.0
    assert stats.percentile(values, 90) == pytest.approx(4.6)
    assert stats.percentile(values, 0) == 1.0
    assert stats.percentile(values, 100) == 5.0
    assert stats.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_quartile_distance_over_median():
    values = [10.0, 10.2, 10.4, 10.6, 10.8, 11.0]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q3 - q1) / 10.5)


def test_window_span_runs_from_first_post_to_last_completion():
    records = [{"posted": 10.0, "done": 12.0}, {"posted": 12.0, "done": 15.5}]
    assert stats.window_span(records) == 5.5


def test_union_counts_overlap_once():
    assert stats.union_seconds([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == 4.0
    assert stats.union_seconds([]) == 0.0


def test_request_stream_is_the_seed():
    big = 2**31 + 11                       # more than 32 signed bits hold
    a, b = traffic.request_stream(big), traffic.request_stream(big)
    first = [next(a) for _ in range(5)]
    assert first == [next(b) for _ in range(5)]
    assert first != [next(traffic.request_stream(big + 1)) for _ in range(5)]
    assert all(0 < seed < 2**31 for seed, _ in first)
    assert len({text for _, text in first}) == 5


def test_open_loop_schedule_offers_every_seed_the_same_load():
    one = traffic.arrival_schedule(1, rate=4.0, seconds=30.0, burst=4)
    two = traffic.arrival_schedule(2, rate=4.0, seconds=30.0, burst=4)
    assert one != two and one == traffic.arrival_schedule(
        1, rate=4.0, seconds=30.0, burst=4)
    assert all(0 <= t < 30.0 for t in one + two)
    assert abs(len(one) - 120) <= 8 and abs(len(two) - 120) <= 8
    from collections import Counter

    def gaps(due):
        return Counter(round(b - a, 9) for a, b in zip(due, due[1:]) if b > a)

    # the same set of gaps in another order: all but the one gap that
    # follows each seed's last burst, which no due time shows
    differ = (gaps(one) - gaps(two)) + (gaps(two) - gaps(one))
    assert sum(differ.values()) <= 2
    assert one.count(one[0]) == 4          # a burst of four shares one time


def test_open_loop_times_from_due_and_reports_lateness():
    def do_request(index, seed, prompt):
        t0 = time.monotonic()
        time.sleep(0.02)
        return {"index": index, "posted": t0, "done": time.monotonic(),
                "status": "success"}

    due = [0.0, 0.0, 0.05, 0.05]
    records = traffic.run_open(do_request, traffic.request_stream(3), due,
                               max_in_flight=1)
    assert [r["index"] for r in records] == [0, 1, 2, 3]
    # one slot: the second of a burst waits for the first, and its
    # latency counts from when it was due, not from when it was posted
    assert records[1]["seconds_from_due"] >= 0.035
    late = traffic.lateness(records)
    assert late["max_s"] >= 0.015 and late["mean_s"] > 0


def test_closed_loop_posts_the_next_when_the_last_is_done():
    seen, hooks = [], []

    def do_request(index, seed, prompt):
        t0 = time.monotonic()
        seen.append(threading.get_ident())
        time.sleep(0.03)
        return {"index": index, "posted": t0, "done": time.monotonic()}

    records = traffic.run_closed(do_request, traffic.request_stream(5),
                                 seconds=0.2, on_index=hooks.append)
    assert 4 <= len(records) <= 8 and len(set(seen)) == 1
    assert all(b["posted"] >= a["done"]
               for a, b in zip(records, records[1:]))
    # the hook is called before every request and once after the last
    assert hooks == list(range(len(records) + 1))
    many = traffic.run_closed(do_request, traffic.request_stream(5),
                              seconds=0.2, clients=3)
    assert len(many) > len(records)
