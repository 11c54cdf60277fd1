"""Inputs made from the seed: deterministic in it, and the same amount of
work for every seed."""
import numpy as np

from bench import data

from conftest import small_cell

SEED = 2**31 + 11


def test_row_counts_are_one_multiset_permuted_by_the_seed():
    cfg = small_cell("emnist62_writers.learn").config
    a, b = data.row_counts(cfg, SEED), data.row_counts(cfg, SEED + 1)
    assert sorted(a) == sorted(b) and list(a) != list(b)
    assert a.min() >= 1 and a.max() <= cfg["max_rows_per_task"]


def test_full_size_row_law_matches_the_source():
    from bench import spec

    cfg = spec.cell("emnist62_writers.learn").config
    counts = data.row_count_multiset(cfg)
    # TFF EMNIST-62: 671585 rows over 3400 writers; clipping moves it little
    assert abs(counts.sum() - 671_585) < 0.01 * 671_585
    assert counts.max() == 512 and counts.min() >= 1


def test_large_seeds_keep_their_high_bits():
    ka, kb = data.base_key(2**33 + 5), data.base_key(5)
    assert not np.array_equal(np.asarray(ka), np.asarray(kb))


def test_store_is_a_function_of_the_seed():
    cfg = small_cell("emnist62_writers.learn").config
    xa, ya, ca, _ = data.store(cfg, SEED)
    xb, yb, cb, _ = data.store(cfg, SEED)
    xc, _, _, _ = data.store(cfg, SEED + 1)
    assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
    assert np.array_equal(ca, cb) and not np.array_equal(xa, xc)
    # rows past a writer's count are zero, labels of the others +-1
    valid = np.arange(cfg["capacity"])[None, :] < ca[:, None]
    assert not np.any(np.asarray(xa)[~valid])
    assert set(np.unique(np.asarray(ya)[valid])) <= {-1.0, 1.0}
