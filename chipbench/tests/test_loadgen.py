"""The seeded traffic repeats, and every seed offers the same work."""

from __future__ import annotations

import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import loadgen  # noqa: E402


def test_schedule_repeats_for_a_seed():
    a = loadgen.open_schedule(2 ** 31 + 11, 250.0, 2.0, 10.0, 8)
    b = loadgen.open_schedule(2 ** 31 + 11, 250.0, 2.0, 10.0, 8)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_every_seed_offers_the_same_work_in_another_order():
    scheds = [loadgen.open_schedule(seed, 250.0, 2.0, 10.0, 8)
              for seed in (1, 2)]
    assert not np.array_equal(scheds[0][0], scheds[1][0])
    for due, idx in scheds:
        window = due[due >= 2.0]
        assert len(due) == 3000 and len(window) == 2500
        assert window[0] == 2.0 and window.max() < 12.0
        assert idx.min() >= 0 and idx.max() < 8
    # one set of gaps, in two orders: every gap of one schedule's window
    # is a gap of the other's (the permutation's last gap is left out)
    g1, g2 = (np.diff(d[d >= 2.0]) for d, _ in scheds)
    assert np.isin(np.round(g1, 9), np.round(np.sort(
        np.concatenate([g2, [0]])), 9)).mean() > 0.99


def test_pool_repeats_and_differs_by_seed():
    a = loadgen.make_pool(2 ** 33, 8, (3, 8, 8))
    assert np.array_equal(a, loadgen.make_pool(2 ** 33, 8, (3, 8, 8)))
    assert not np.array_equal(a, loadgen.make_pool(2 ** 33 + 1, 8,
                                                   (3, 8, 8)))
    assert len({x.tobytes() for x in a}) == 8
