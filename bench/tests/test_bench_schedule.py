"""The open-loop schedule: the same seed gives the same arrivals; another
seed gives the same gaps and datapaths in another order."""
import numpy as np

from bench.traffic import open_loop

SHARES = {"forward": 0.5, "inverse": 0.5}


def test_same_seed_same_arrivals():
    a = open_loop.schedule(400.0, 10.0, SHARES, np.random.default_rng(5))
    b = open_loop.schedule(400.0, 10.0, SHARES, np.random.default_rng(5))
    assert (a[0] == b[0]).all() and a[1] == b[1]


def test_other_seed_same_work_other_order():
    a_off, a_kind = open_loop.schedule(400.0, 10.0, SHARES,
                                       np.random.default_rng(5))
    b_off, b_kind = open_loop.schedule(400.0, 10.0, SHARES,
                                       np.random.default_rng(6))
    assert len(a_off) == len(b_off) == 4000
    assert (np.diff(a_off) >= 0).all() and (np.diff(b_off) >= 0).all()
    assert sorted(a_kind) == sorted(b_kind)
    assert a_kind.count("forward") == 2000
    assert a_kind != b_kind and not (a_off == b_off).all()
    assert abs(a_off[-1] - 10.0) < 0.05 and abs(b_off[-1] - 10.0) < 0.05


def test_percentile_matches_numpy_and_ranks_inf_last():
    from bench import stats
    xs = np.random.default_rng(1).exponential(1.0, 101)
    for q in (50, 95, 99):
        assert np.isclose(stats.percentile(xs, q), np.percentile(xs, q))
    assert stats.percentile([1.0, 2.0, float("inf")], 50) == 2.0
    assert stats.percentile([1.0, 2.0, float("inf")], 95) == float("inf")
