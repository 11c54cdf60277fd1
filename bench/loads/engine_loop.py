"""Closed loop of `engine.run(state, None, K)` calls, a fixed number of
them dispatched ahead of the one waited on.

Traffic parameters (bench/traffic/<mix>.json):

    events_per_call   K, the events of one `engine.run` call, or "epoch":
                      T rounded down to a multiple of event_batch
    checked_calls     calls made in set-up through the same engine and
                      state; the reference follows them and the window
                      continues from where they left off
    dispatch_ahead    calls in flight ahead of the one waited on (0: each
                      call waited on before the next is made), so that a
                      stall of the host does not idle the device
    traced_calls      calls a traced run profiles, from the window's start

Set-up makes the store on the device, builds the engine, and makes the
checked calls (the first compiles).  The window makes calls until
`--seconds` have passed, then makes no more and waits for every call in
flight; `events_per_s` is every event of every call over the whole
window, that last wait included.  After it, with the program's state freed, the reference
replays the checked calls and `correct` compares the iterates.

A control run (`Run.control`, bench/control.py) puts the reference, each
product in three bfloat16 passes, in the program's place for the checked
calls; the window then runs the engine from its initial state.
"""
from __future__ import annotations

import collections
import time

import jax
import numpy as np

from bench import check, data, system, work


def events_per_call(cell) -> int:
    k, cfg = cell.traffic["events_per_call"], cell.config
    if k == "epoch":
        return cfg["num_tasks"] // cfg["event_batch"] * cfg["event_batch"]
    return int(k)


def setup(cell, seed: int, calls: int, control: bool = False):
    """(engine, state after the checked calls, their host iterates, store)."""
    cfg, k = cell.config, events_per_call(cell)
    xs, ys, counts, _ = data.store(cfg, seed)
    if control:
        iterates = check.reference_iterates(cell, seed, (xs, ys, counts), k,
                                            calls, "high")
    eng = system.engine(cfg, system.problem(cfg, xs, ys, counts))
    state = eng.init(system.zeros(cfg), data.keys(seed)["engine"])
    if control:
        state = jax.block_until_ready(eng.run(state, None, k))
        return eng, state, iterates, (xs, ys, counts)
    iterates = []
    for _ in range(calls):
        with jax.profiler.TraceAnnotation("bench.engine_run"):
            state = jax.block_until_ready(eng.run(state, None, k))
        iterates.append(np.asarray(eng.iterate(state)))
    return eng, state, iterates, (xs, ys, counts)


def run(r) -> None:
    cell = r.cell
    cfg, k = cell.config, events_per_call(cell)
    eng, state, iterates, store = setup(cell, r.seed,
                                        cell.traffic["checked_calls"],
                                        r.control)
    ahead = cell.traffic["dispatch_ahead"]

    def calls_until(limit) -> int:
        """Make calls until the window's time is up or `limit` calls were
        made; returns how many, every one of them finished."""
        nonlocal state
        inflight: collections.deque = collections.deque()
        n = 0
        while True:
            with jax.profiler.TraceAnnotation("bench.engine_run"):
                state = eng.run(state, None, k)
                inflight.append(state)
                if len(inflight) > ahead:
                    jax.block_until_ready(inflight.popleft())
            n += 1
            if (time.perf_counter() - r.t_window >= r.seconds
                    or n == limit):
                break
        with jax.profiler.TraceAnnotation("bench.engine_run"):
            while inflight:
                jax.block_until_ready(inflight.popleft())
        return n

    # A traced run profiles its first `traced_calls` calls and makes the
    # rest of the window's calls untraced: the profiler's buffer holds
    # only some seconds of this engine's operations.
    r.window_begins()
    with r.traced_window():
        traced = calls_until(cell.traffic["traced_calls"] if r.traced
                             else None)
        traced_s = time.perf_counter() - r.t_window
    calls = traced
    if traced_s < r.seconds:
        calls += calls_until(None)
    elapsed = time.perf_counter() - r.t_window
    r.window_ends()
    r.attempted = calls
    r.values["events_per_s"] = calls * k / elapsed
    r.info["memory_peak_bytes"] = r.memory_peak()
    r.info["window_calls"] = calls
    r.info["capacity_doublings"] = 0
    r.layer.window_s = traced_s         # the traced calls, on the host clock
    r.layer.chips = cell.chips
    r.layer.work = work.engine_events(cfg, traced * k)
    r.layer.kernel_calls = {            # made in the traced calls
        "lstsq_grad_sampled": traced * k,
        "amtl_event_batch": traced * k // cfg["event_batch"],
    }
    r.layer.kernel_work = {
        "lstsq_grad_sampled": work.sampled_grad(cfg["batch_size"],
                                                cfg["dim"]),
        "amtl_event_batch": work.column_update(cfg["dim"],
                                               cfg["event_batch"]),
    }
    del state, eng
    t_check = time.perf_counter()
    for name, value in check.learn(cell, r.seed, k, iterates,
                                   store).items():
        r.check(name, value)
    r.info["check_s"] = time.perf_counter() - t_check
