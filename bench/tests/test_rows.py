"""The row generator and the arrival schedule keep their parameters."""
import numpy as np

import rows

RS = {"draw": "random_search", "lo": 1, "hi": 16}


def _draw(wants, seed=7, F=64):
    return rows.draw_streams(F, wants, np.random.default_rng(seed))


def test_random_search_rows_are_uniform_in_lo_hi_and_unique():
    (D,) = _draw([(20000, RS)])
    assert D.shape == (20000, 64) and D.dtype == np.int64
    assert D.min() == 1 and D.max() == 16
    assert len(np.unique(D, axis=0)) == len(D)
    counts = np.bincount(D.ravel(), minlength=17)[1:]
    assert abs(counts / counts.sum() - 1 / 16).max() < 0.005
    (E,) = _draw([(500, dict(RS, lo=0, hi=3))])
    assert E.min() == 0 and E.max() == 3


def test_rows_are_unique_across_streams():
    a, b = _draw([(3000, dict(RS, hi=2)), (3000, dict(RS, hi=2))], F=13)
    both = np.concatenate([a, b])
    assert len(np.unique(both, axis=0)) == 6000


def test_repeat_share_repeats_earlier_rows():
    (D,) = _draw([(8000, dict(RS, repeat_share=0.25))])
    seen, repeats = set(), 0
    for row in map(bytes, D):
        repeats += row in seen
        seen.add(row)
    assert 0.23 < repeats / len(D) < 0.27


def test_same_seed_same_rows():
    w = [(512, RS), (64, RS)]
    for x, y in zip(_draw(w, seed=2**31 + 11), _draw(w, seed=2**31 + 11)):
        assert np.array_equal(x, y)
    assert not np.array_equal(_draw(w, seed=1)[0], _draw(w, seed=2)[0])


def test_arrivals_same_count_and_span_for_every_seed():
    a = rows.arrivals(8, 30, 0, np.random.default_rng(1))
    b = rows.arrivals(8, 30, 0, np.random.default_rng(2**33))
    assert len(a) == len(b) == 240
    assert a[0] == 0 and (np.diff(a) > 0).all() and a[-1] < 30
    assert np.allclose(np.sort(np.diff(np.append(a, 30))),
                       np.sort(np.diff(np.append(b, 30))))


def test_bursts_arrive_together():
    a = rows.arrivals(8, 30, 0, np.random.default_rng(1), burst=4)
    assert len(a) == 240
    assert (a[0::4] == a[3::4]).all() and (np.diff(a[0::4]) > 0).all()
