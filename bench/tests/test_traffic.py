"""The traffic generator: one Poisson draw of arrivals that every seed
replays; prompts that the reference can draw again."""
import numpy as np
import pytest

from benchlib import traffic


def test_every_seed_replays_one_poisson_draw_of_the_rate():
    a = traffic.hi_arrivals(5.0, 51.0)
    assert len(a) == 255
    assert a == sorted(a) and all(0 <= t < 51.0 for t in a)
    assert a == traffic.hi_arrivals(5.0, 51.0)
    assert a != traffic.hi_arrivals(8.0, 51.0)[:255]


def test_arrivals_are_poisson_not_evened_out():
    """Gaps of a Poisson process are exponential: their coefficient of
    variation is near 1, and clusters of short gaps occur (a stratified
    or even schedule reads far less)."""
    a = np.asarray(traffic.hi_arrivals(50.0, 200.0))
    gaps = np.diff(a)
    assert gaps.mean() == pytest.approx(1 / 50.0, rel=0.02)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)
    # three arrivals inside a tenth of the mean gap happen by chance
    tight = (a[2:] - a[:-2]) < 0.1 / 50.0
    assert tight.sum() > 0
    # counts per second spread as a Poisson count's (variance ~ mean)
    counts = np.bincount(a.astype(int), minlength=200)
    assert counts.var() / counts.mean() == pytest.approx(1.0, abs=0.25)


def test_prompts_are_a_function_of_seed_role_phase_and_number():
    t = traffic.tokens(5, "hi", 1, 3, 100, 2, 8)
    assert t.shape == (2, 8) and t.dtype == np.int32
    assert (t >= 0).all() and (t < 100).all()
    assert np.array_equal(t, traffic.tokens(5, "hi", 1, 3, 100, 2, 8))
    for other in [(6, "hi", 1, 3), (5, "lo", 1, 3), (5, "hi", 0, 3),
                  (5, "hi", 1, 4)]:
        assert not np.array_equal(t, traffic.tokens(*other, 100, 2, 8))


@pytest.mark.parametrize("bad", [
    {"hi": {"rate_per_s": 0}, "lo": {"backlog": 1}},
    {"hi": {"rate_per_s": float("inf")}, "lo": {"backlog": 1}},
    {"hi": {}, "lo": {"backlog": 1}},
    {"hi": {"rate_per_s": 1}, "lo": {}},
])
def test_refuses_traffic_it_cannot_generate(bad):
    with pytest.raises(ValueError):
        traffic.check_traffic(bad)
