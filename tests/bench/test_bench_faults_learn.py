"""The learner cell's check at a small size on the CPU: the program
passes, the control and each fault of the timed path fail.

These drive the whole run past the harness's look for a chip."""
import time

import jax.numpy as jnp
import pytest

from bench import harness, system

from conftest import small_cell

SEED = 2**31 + 3
WORKLOAD = "emnist62_writers.learn"


def _run(cpu_device, control=False, limits=None):
    cell = small_cell(WORKLOAD)
    if limits is not None:
        cell = cell._replace(limits=dict(cell.limits, limits=limits))
    return harness.execute(cell, SEED, 0.3, False, time.perf_counter(),
                           cpu_device, control=control)


def test_program_is_correct_and_the_control_is_not(cpu_device):
    res, _ = _run(cpu_device)
    assert res["correct"], res["checks"]
    # XLA-CPU computes the reference's QR and SVD in f32 whatever the
    # precision, so the control reads some 5e-6 here (2e-3 on the chip,
    # bench/limits): judge it by the limit that CPU readings set.
    cpu = {"v_gap": 3e-6}
    res, _ = _run(cpu_device, limits=cpu)
    assert res["correct"], res["checks"]
    res, _ = _run(cpu_device, control=True, limits=cpu)
    assert not res["correct"], res["checks"]


def _unchanged(eng):
    return lambda st, offs, n: st


def _half_batch(eng):
    return lambda st, offs, n: eng.run(st, offs, n // 2)


def _answer_altered(eng):
    def run(st, offs, n):
        st = eng.run(st, offs, n)
        return st._replace(v=st.v.at[0, 0].add(1e-3 * jnp.max(jnp.abs(st.v))))
    return run


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_each_fault_of_the_timed_path_is_not_correct(fault, monkeypatch,
                                                     cpu_device):
    real = system.engine

    def broken(cfg, prob):
        eng = real(cfg, prob)
        return eng._replace(run=fault(eng))

    monkeypatch.setattr(system, "engine", broken)
    res, _ = _run(cpu_device)
    assert not res["correct"], res["checks"]
