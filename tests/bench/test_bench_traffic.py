"""The FL traffic generator's client sizes: one fixed set for every seed,
and, with ``prime_sizes``, no two clients of different sizes whose age
keys A * D can be equal at the ages a run reaches."""
from __future__ import annotations

import itertools
import json
import sys

import numpy as np
import pytest

from _bench_cells import REPO

sys.path.insert(0, str(REPO))

from bench.drivers import fl  # noqa: E402

TRAFFIC = json.loads((REPO / "bench" / "traffic" / "fl_cohort10.json")
                     .read_text())


@pytest.mark.parametrize("seed", [0, 475444118, 2 ** 31 + 5])
def test_sizes_are_one_set_dealt_in_seed_order(seed):
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    sizes = fl.client_sizes(TRAFFIC, 50, rng)
    ref = fl.client_sizes(TRAFFIC, 50, np.random.default_rng(0))
    assert sorted(sizes) == sorted(ref)
    lo, hi = TRAFFIC["samples_per_client"]
    assert lo <= sizes.min() and sizes.max() <= hi


def test_prime_sizes_leave_no_exact_key_tie_between_sizes():
    sizes = sorted(set(fl.client_sizes(TRAFFIC, 50,
                                       np.random.default_rng(0)).tolist()))
    assert all(all(s % d for d in range(2, s)) for s in sizes)
    ages = range(1, 101)
    for p, q in itertools.combinations(sizes, 2):
        assert not any(a * p == b * q for a in ages for b in ages)


def test_even_grid_has_the_ties_that_prime_sizes_remove():
    grid = fl.client_sizes(dict(TRAFFIC, prime_sizes=False), 50,
                           np.random.default_rng(0))
    assert 5 * 207 == 9 * 115 and {207, 115} <= set(grid.tolist())
