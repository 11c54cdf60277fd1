"""bench/work.py: required operations and bytes on hand-worked shapes."""
from bench import work

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_sampled_grad_counts_only_the_selected_rows():
    w = work.sampled_grad(b=32, d=784)
    # X_S w and X_S^T r: 2 * 32 * 784 each.
    assert w.flops == 100_352.0
    # X_S (32 x 784), y_S (32), w and g (784 each), 4 bytes each.
    assert w.bytes == 4 * (25_088 + 32 + 1_568) == 106_752


def test_column_update_counts_the_touched_columns():
    w = work.column_update(d=784, events=32)
    assert w.flops == 5 * 784 * 32 == 125_440
    assert w.bytes == 4 * 5 * 784 * 32 == 501_760
    # bound by HBM: 501760 B / 819 GB/s
    assert abs(w.least_seconds(PEAKS) - 501_760 / 819e9) < 1e-18


def test_prox_refresh_on_hand_worked_shape():
    # d = 8, t = 4, rank 16 -> p = min(24, 8, 4) = 4
    w = work.prox_refresh(d=8, t=4, rank=16)
    assert w.flops == 3 * 2 * 8 * 4 * 4 + 4 * 8 * 16 + 14 * 4 * 16
    assert w.bytes == 4 * (3 * 32 + 2 * 32 + 2 * 16)


def test_engine_events_sums_refreshes_gradients_and_updates():
    cfg = {"dim": 784, "num_tasks": 3400, "prox_every": 32, "prox_rank": 16,
           "batch_size": 32}
    got = work.engine_events(cfg, 64)
    want = (work.prox_refresh(784, 3400, 16) * 2
            + work.sampled_grad(32, 784) * 64 + work.column_update(784, 64))
    assert got == want
    # the refresh dominates: 3 * 2 * 784 * 3400 * 24 a refresh
    assert got.flops > 2 * 383_846_400


def test_least_time_takes_the_larger_bound():
    compute_bound = work.Work(197e12, 1.0)
    memory_bound = work.Work(1.0, 819e9)
    assert compute_bound.least_seconds(PEAKS) == 1.0
    assert memory_bound.least_seconds(PEAKS) == 1.0
