"""The window arithmetic on synthetic records: the chunk rates and
the window-edge accounting of streamed tokens and gaps."""

import pytest

from benchmarks.harness import window


def _chunks(seconds, tokens=1000):
    return [{"seconds": s, "tokens": tokens} for s in seconds]


def test_the_end_to_end_rate_counts_every_chunk_over_the_whole_window():
    steady = window.chunk_rates(_chunks([2.0] * 10))
    one_stall = window.chunk_rates(_chunks([2.0] * 9 + [2.4]))
    assert steady["tokens_per_s"] == pytest.approx(500.0)
    # a stall counts in full in the end-to-end rate ...
    assert one_stall["tokens_per_s"] == pytest.approx(10000 / 20.4)
    # ... and time between chunks is window time too
    assert window.chunk_rates(_chunks([2.0] * 10), window_s=20.5)[
        "tokens_per_s"] == pytest.approx(10000 / 20.5)


def test_the_steady_rate_beside_it_drops_one_slow_chunk():
    one_stall = window.chunk_rates(_chunks([2.0] * 9 + [2.4]))
    assert one_stall["steady_tokens_per_s"] == pytest.approx(500.0)
    assert one_stall["slowest_chunk"] == 9
    # what the stall cost is reported, not hidden
    assert one_stall["stall_share"] == pytest.approx(0.4 / 20.4)


def test_two_slow_chunks_count_in_the_steady_rate_too():
    two = window.chunk_rates(_chunks([2.0] * 8 + [2.4, 2.4]))
    assert two["steady_tokens_per_s"] == pytest.approx(9000 / 18.4)
    assert two["tokens_per_s"] == pytest.approx(10000 / 20.8)


def test_a_recurring_slowness_is_not_trimmed_away():
    slow = window.chunk_rates(_chunks([2.2] * 10))
    assert slow["steady_tokens_per_s"] == pytest.approx(1000 / 2.2)
    assert slow["stall_share"] == pytest.approx(0.0)


def test_a_window_needs_two_chunks():
    with pytest.raises(ValueError):
        window.chunk_rates(_chunks([2.0]))


def test_percentile_interpolates():
    assert window.percentile([1, 2, 3, 4, 5], 50) == 3
    assert window.percentile([0.0, 10.0], 95) == pytest.approx(9.5)
    assert window.percentile([], 50) is None


def _req(due, times, asked=None, **kw):
    return dict({"due": due, "sent": due + 0.001, "token_times": times,
                 "asked": asked if asked is not None else len(times),
                 "done": True, "error": None, "cut": False}, **kw)


def test_tokens_and_gaps_count_where_they_fall_inside_the_window():
    # window [10, 20): the stream straddles the opening edge
    early = _req(8.0, [9.0, 9.5, 10.5, 11.5])
    # ... and this one the closing edge, cut by the generator's stop
    late = _req(18.0, [18.5, 19.5, 20.5], asked=10, done=False, cut=True)
    win = window.stream_window([early, late], 10.0, 20.0)
    assert win["tokens"] == 4            # 10.5, 11.5, 18.5, 19.5
    assert win["tokens_per_s"] == pytest.approx(0.4)
    # gaps that END inside: 9.5->10.5, 10.5->11.5, 18.5->19.5
    assert sorted(win["gaps_s"]) == pytest.approx([1.0, 1.0, 1.0])
    # only `late` was due inside; it is timed from when it was due
    assert win["attempted"] == 1 and win["failed"] == 0
    assert win["ttft_s"] == pytest.approx([0.5])
    assert win["late_s"] == pytest.approx([0.001])


def test_a_refused_broken_or_short_request_is_failed():
    refused = _req(11.0, [], error="HTTP 503", done=False)
    short = _req(12.0, [12.5], asked=4)
    never = _req(13.0, [], asked=4, done=False, cut=True)
    fine = _req(14.0, [14.2, 14.3])
    win = window.stream_window([refused, short, never, fine], 10.0, 20.0)
    assert win["attempted"] == 4 and win["failed"] == 3
    assert win["ttft_s"] == pytest.approx([0.2])
