"""The end-to-end numbers of a window, on a hand-made record: a rate over
whole ticks, and only the inter-token gaps that lie inside them."""

import types

import numpy as np
import pytest

import run
from driver import Record, Tick


def tick(begin, end):
    return Tick(begin, end, 2, np.zeros(2, int), np.ones(2, int))


def flight(times):
    return types.SimpleNamespace(token_times=list(times))


def test_rate_and_gaps_over_whole_ticks():
    # ticks end at 2.1, 4.2, 6.3 and 8.4 s; the window closes at 7 s, so the
    # last tick is past the close and 6.3 s is the time the rate is over
    ticks = [tick(0.0, 2.1), tick(2.1, 4.2), tick(4.2, 6.3), tick(6.3, 8.4)]
    a = flight([-2.0, 2.1, 4.2, 6.3, 8.4])     # one token from set-up
    b = flight([4.2, 6.3, 8.4])                # first token inside
    out = run.end_to_end(Record(7.0, [a, b], ticks), setup_s=3.0)
    assert out["setup_s"] == 3.0
    assert out["output_tok_s"] == pytest.approx(5 / 6.3)
    # gaps: a 2.1 -> 4.2 -> 6.3, b 4.2 -> 6.3; none reaches into set-up
    assert out["tpot_ms"] == pytest.approx(2100.0)
    assert out["itl_p95_ms"] == pytest.approx(2100.0)


def test_rate_moves_with_the_tick_time_not_in_whole_ticks():
    def rate(tick_s):
        ends = np.arange(1, 20) * tick_s
        ticks = [tick(e - tick_s, e) for e in ends]
        fl = [flight(ends) for _ in range(32)]
        return run.end_to_end(Record(20.0, fl, ticks), 0.0)["output_tok_s"]
    # a tick 1 % faster reads as about 1 % more tokens a second
    assert rate(2.079) / rate(2.1) == pytest.approx(2.1 / 2.079, rel=1e-9)
