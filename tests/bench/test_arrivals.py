"""The benchmark's schedule generator: deterministic per seed, the stated
rate and Zipf shares, the same work for every seed."""
import numpy as np
import pytest

from bench import arrivals

MIX = {"arrivals": "poisson", "rate_rps": 400.0, "zipf_s": 1.0, "pool_size": 16}
MMPP = dict(MIX, arrivals="mmpp", burst_factor=4.0, mean_normal_s=8.0,
            mean_burst_s=2.0)


@pytest.mark.parametrize("mix", [MIX, MMPP], ids=["poisson", "mmpp"])
def test_deterministic_per_seed(mix):
    a = arrivals.schedule(mix, 4, 30.0, 2**33 + 5)
    b = arrivals.schedule(mix, 4, 30.0, 2**33 + 5)
    c = arrivals.schedule(mix, 4, 30.0, 5)
    for f in ("due", "tenant", "item"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.due, c.due)


@pytest.mark.parametrize("mix", [MIX, MMPP], ids=["poisson", "mmpp"])
def test_rate_and_window(mix):
    s = arrivals.schedule(mix, 4, 30.0, 11)
    assert len(s) == 12_000
    assert np.all(np.diff(s.due) >= 0)
    assert s.due[0] >= 0 and s.due[-1] < 30.0
    assert set(np.unique(s.item)) <= set(range(16))


def test_zipf_shares_exact():
    shares = arrivals.zipf_shares(4, 1.0)
    np.testing.assert_allclose(shares, np.array([1, 1 / 2, 1 / 3, 1 / 4]) / (25 / 12))
    s = arrivals.schedule(MIX, 4, 30.0, 3)
    counts = np.bincount(s.tenant, minlength=4)
    assert counts.sum() == 12_000
    np.testing.assert_array_equal(counts, arrivals.apportion(12_000, shares))
    assert np.all(np.abs(counts - 12_000 * shares) < 1)


def test_every_seed_offers_the_same_work():
    a = arrivals.schedule(MIX, 3, 20.0, 1)
    b = arrivals.schedule(MIX, 3, 20.0, 2)
    # The gaps, with the one before the first request and the one after
    # the last, are the same set in another order.
    def gaps(s):
        return np.sort(np.diff(np.concatenate([[0.0], s.due, [20.0]])))

    np.testing.assert_allclose(gaps(a), gaps(b), rtol=0, atol=1e-9)
    np.testing.assert_array_equal(np.bincount(a.tenant), np.bincount(b.tenant))


def test_poisson_gaps_are_exponential():
    s = arrivals.schedule(MIX, 4, 30.0, 9)
    gaps = np.diff(s.due) * MIX["rate_rps"]
    assert abs(gaps.mean() - 1.0) < 0.01
    # Exponential law: P(gap > 1) = e^-1, P(gap > 3) = e^-3.
    assert abs(np.mean(gaps > 1.0) - np.exp(-1)) < 0.01
    assert abs(np.mean(gaps > 3.0) - np.exp(-3)) < 0.005


def test_mmpp_bursts_raise_the_local_rate():
    s = arrivals.schedule(dict(MMPP, mean_normal_s=2.0, mean_burst_s=2.0),
                          1, 60.0, 4)
    per_s = np.bincount(s.due.astype(int), minlength=60)
    # Normal and burst seconds differ by about the burst factor.
    assert np.percentile(per_s, 90) > 2.5 * np.percentile(per_s, 10)


def test_bad_mix_is_refused():
    with pytest.raises(ValueError):
        arrivals.schedule(dict(MIX, arrivals="uniform"), 2, 1.0, 0)
    with pytest.raises(ValueError):
        arrivals.schedule(dict(MIX, rate_rps=0), 2, 1.0, 0)
