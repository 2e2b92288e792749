"""The serving driver's host-side readings: every inter-token gap of every
request, and time-weighted means and peaks cut at the window's close."""
from types import SimpleNamespace

import pytest

import sizes  # noqa: F401  (puts the repo on the path)

from chipbench.drivers import serve


def _handle(t_first):
    return SimpleNamespace(out=[], t_first=t_first, done=False)


def test_token_gaps_count_every_token_after_the_first():
    a, b = _handle(1.0), _handle(0.0)
    gaps = serve.TokenGaps()
    gaps.add(a)
    gaps.add(b)
    a.out += [7, 8]                  # prefill and a decode in one step
    gaps.look(1.5)
    a.out += [9]
    gaps.look(1.75)
    b.out += [5]                     # b's first token; no gap yet
    b.t_first = 1.75
    a.out += [3, 4]                  # two tokens in one look: two gaps
    a.done = True
    gaps.look(2.75)
    assert gaps.gaps == [0.5, 0.25, 0.5, 0.5]
    assert list(gaps.live) == [id(b)]


def test_time_weighted_mean_and_peak_stop_at_the_close():
    tw = serve.TimeWeighted(0.0, 10.0)
    tw.sample(0.0, 2)
    tw.sample(4.0, 6)
    tw.sample(9.0, 0)
    tw.sample(12.0, 50)              # after the close: no weight, no peak
    assert tw.mean() == pytest.approx((2 * 4 + 6 * 5) / 10)
    assert tw.peak == 6
