"""SGD-AMTL (the paper's §V stated future work, implemented here):
minibatch asynchronous coordinate updates vs full-gradient AMTL at EQUAL
WALL-CLOCK.

Finding (EXPERIMENTS.md §Paper-claims): every asynchronous cycle pays the
network delay once, so cheap minibatch gradients only help when gradient
compute dominates the delay — in the compute-bound regime SGD-AMTL
pipelines ~n/b more KM writes into the same wall-clock and reaches a
lower objective; in the delay-bound regime it degenerates to
noisier-but-not-faster and loses.  Both regimes are reported.

The `engine_*` rows re-measure the compute-bound finding on the JITTED
path (`AMTLConfig(batch_size=...)`, the seeded in-kernel selection of
PR 6) instead of the numpy simulator: the minibatch engine's measured
events/sec sets how many extra events fit the full-gradient run's
wall-clock, and the objective it reaches in that budget is reported next
to the full-gradient objective at equal wall-clock.
"""
from __future__ import annotations

import time

from benchmarks.common import Row, timed
from repro.core import NetworkModel, make_synthetic, simulate_amtl

EPOCHS = 10
SAMPLES = 200

# engine-backed row: large-n stacked problem, jitted batch engine, one
# event a step
E_TASKS, E_SAMPLES, E_DIM, E_BSZ, E_EVENTS = 8, 512, 1024, 32, 256


def _engine_rows() -> list[Row]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import AMTLConfig, MTLProblem, amtl_max_step
    from repro.core.amtl import amtl_events_only, current_iterate

    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    xs = jax.random.normal(kx, (E_TASKS, E_SAMPLES, E_DIM)) / np.sqrt(E_DIM)
    ys = jax.random.normal(ky, (E_TASKS, E_SAMPLES))
    problem = MTLProblem(xs, ys, "lstsq", "nuclear", 0.1)
    cfg_full = AMTLConfig(eta=1.0 / problem.lipschitz(),
                          eta_k=amtl_max_step(4, E_TASKS), tau=4,
                          engine="batch", prox_every=8, prox_rank=8)
    cfg_sgd = cfg_full._replace(batch_size=E_BSZ)
    w0 = jnp.zeros((E_DIM, E_TASKS), jnp.float32)
    key = jax.random.PRNGKey(7)

    def eps(cfg, events):
        run_ = lambda: jax.block_until_ready(
            amtl_events_only(problem, cfg, w0, key, events))
        run_()                              # compile + warm-up
        t0 = time.perf_counter()
        st = run_()
        return events / (time.perf_counter() - t0), st

    full_eps, full_st = eps(cfg_full, E_EVENTS)
    sgd_eps, _ = eps(cfg_sgd, E_EVENTS)
    # equal wall-clock: the minibatch engine fits speedup-times more
    # events into the full-gradient run's budget
    sgd_events = max(1, int(E_EVENTS * sgd_eps / full_eps))
    _, sgd_st = eps(cfg_sgd, sgd_events)
    obj_full = float(problem.objective(current_iterate(full_st)))
    obj_sgd = float(problem.objective(current_iterate(sgd_st)))
    return [
        Row("sgd_amtl/engine_full", 1e6 / full_eps,
            f"events={E_EVENTS};events_per_sec={full_eps:.1f};"
            f"objective={obj_full:.3f}"),
        Row(f"sgd_amtl/engine_b{E_BSZ}_equalwallclock", 1e6 / sgd_eps,
            f"events={sgd_events};events_per_sec={sgd_eps:.1f};"
            f"speedup={sgd_eps / full_eps:.2f}x;objective={obj_sgd:.3f}"),
    ]


def run() -> list[Row]:
    rows = _engine_rows()
    regimes = {
        "computebound": NetworkModel(delay_offset=0.05, delay_jitter=0.05,
                                     compute_time=2.0, prox_time=0.01),
        "delaybound": NetworkModel(delay_offset=2.0, delay_jitter=0.5,
                                   compute_time=0.5, prox_time=0.01),
    }
    for regime, net in regimes.items():
        for tasks in (5, 10):
            prob = make_synthetic(num_tasks=tasks, samples=SAMPLES, dim=50,
                                  seed=0)
            r_full, us_f = timed(lambda: simulate_amtl(
                prob, net, EPOCHS, eta_k=1.0, seed=1,
                record_objective=False))
            budget = r_full.total_time
            rows.append(Row(f"sgd_amtl/{regime}_full_tasks{tasks}", us_f,
                            f"sim_time_s={budget:.2f};"
                            f"objective={prob.objective(r_full.w):.3f}"))
            for bsz in (25, 50):
                # cycles that fit the SAME wall-clock budget
                cyc_t = (net.node_compute(0) * bsz / SAMPLES
                         + net.delay_offset + net.delay_jitter / 2
                         + net.prox_time)
                cycles = max(1, int(budget / cyc_t))
                r_sgd, us_s = timed(lambda: simulate_amtl(
                    prob, net, cycles, eta_k=1.0, seed=1,
                    record_objective=False, batch_size=bsz))
                rows.append(Row(
                    f"sgd_amtl/{regime}_b{bsz}_tasks{tasks}", us_s,
                    f"sim_time_s={r_sgd.total_time:.2f};"
                    f"objective={prob.objective(r_sgd.w):.3f}"))
    return rows
