"""The traffic generator: the same seed gives the same schedule, images and
sample; another seed gives others."""
import numpy as np
import pytest

from bench.traffic import generate

MIXES = [{"arrivals": {"process": "poisson", "rate_hz": 5000.0}},
         {"arrivals": {"process": "onoff", "on_rate_hz": 16000.0,
                       "mean_on_s": 0.2, "mean_off_s": 0.2}}]
BIG = 2 ** 31 + 12345


@pytest.mark.parametrize("mix", MIXES, ids=["poisson", "onoff"])
def test_same_seed_same_schedule(mix):
    a = generate.arrivals(mix, BIG, 2.0)
    b = generate.arrivals(mix, BIG, 2.0)
    c = generate.arrivals(mix, BIG + 1, 2.0)
    assert np.array_equal(a, b)
    assert len(a) != len(c) or not np.array_equal(a, c)
    assert np.all(np.diff(a) > 0) and a[0] >= 0 and a[-1] < 2.0


def test_poisson_rate():
    a = generate.arrivals(MIXES[0], 7, 4.0)
    assert abs(len(a) - 20000) < 5 * np.sqrt(20000)      # five sigma


def test_onoff_mean_rate():
    # mean rate = on_rate * on / (on + off) = 8000/s
    a = generate.arrivals(MIXES[1], 7, 20.0)
    assert 6000 < len(a) / 20.0 < 10000


def test_image_pool():
    a = generate.image_pool(BIG, 16, 32, 3)
    assert a.shape == (16, 32, 32, 3) and a.dtype == np.float32
    assert a.min() >= 0 and a.max() < 1
    assert np.array_equal(a, generate.image_pool(BIG, 16, 32, 3))
    assert not np.array_equal(a, generate.image_pool(BIG + 1, 16, 32, 3))
    assert len({float(x.std()) for x in a}) == 16   # images differ in spread


def test_sample():
    s = generate.sample(BIG, 1000, 50)
    assert len(s) == 50 and len(set(s)) == 50 and np.all(np.diff(s) > 0)
    assert np.array_equal(s, generate.sample(BIG, 1000, 50))
    assert np.array_equal(generate.sample(BIG, 30, 50), np.arange(30))
