"""The traffic generator and the arrival processes: every seed gets the same
amount of work, in another order."""
import numpy as np
import pytest

from bench import harness, traffic

MIX = {"rate_per_s": 100.0, "images_per_request": 1, "pool": 64,
       "sides": [{"share": 1, "lo": 224, "hi": 224}]}


def test_every_seed_gets_the_same_arrivals_in_another_order():
    mix = dict(MIX, arrivals="poisson")
    due = harness.load_arrivals("poisson")
    a, _ = traffic.open_schedule(mix, 2**33 + 1, 20.0, due)
    b, _ = traffic.open_schedule(mix, 7, 20.0, due)
    assert a != b
    # the same gaps, shuffled: the same count of requests within 1 %
    assert abs(len(a) - len(b)) <= 0.01 * len(a)
    assert len(a) == pytest.approx(mix["rate_per_s"] * 20.0, rel=0.05)
    assert all(0.0 <= t < 20.0 for t in a) and a == sorted(a)
    full_a = due(mix, traffic.rng(2**33 + 1, 2), 20.0)
    full_b = due(mix, traffic.rng(7, 2), 20.0)
    assert np.allclose(sorted(np.diff(full_a, prepend=0.0)),
                       sorted(np.diff(full_b, prepend=0.0)))


def test_native_pool_and_picks():
    sizes = traffic.pool_sizes(MIX)
    assert sizes == [(224, 224)] * 64
    _, picks = traffic.open_schedule(dict(MIX, arrivals="poisson"), 5, 1.28,
                                     harness.load_arrivals("poisson"))
    # each pool image once per cycle of 64 requests
    assert sorted(p for (p,) in picks[:64]) == list(range(64))
