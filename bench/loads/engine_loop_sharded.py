"""Closed loop of `engine.run(state, None, K)` calls on the sharded engine:
the writers split over the run's chips as sites, the server prox
distributed over them.

Traffic parameters (bench/traffic/<mix>.json) are `engine_loop`'s:
`events_per_call`, `checked_calls`, `dispatch_ahead`, `traced_calls`.  The
window, the traced calls, the control branch and the check are
`engine_loop`'s too; only the system under test differs.

Set-up makes the store on a 1-D "tasks" mesh over the run's chips, writer
t on chip t // (T / chips): `bench/data.lowrank_store`, the one-chip cell's
law and call, compiled with its `xs` and `ys` sharded, so each chip draws
only its own writers' rows (JAX's threefry draws the same bits however the
output is sharded: a seed gives the one-chip cell's store).  No chip ever
holds the whole store while the program runs.  Set-up then builds the
engine through the program's public entry point, `make_engine(problem,
AMTLConfig(engine="sharded", prox_mode=...), mesh)`, and makes the checked
calls (the first compiles).

The reference runs on the first chip: the check (and the control) gets the
store gathered there by one all-gather over the chips' links, not by
`jax.device_put`, which gathers a sharded array through the host (some 30
s for the 5.46 GB store on a v5e 2x2 host).

The per-layer readers get the work the events of the traced calls require,
counted once (bench/work.py), and the device time of every chip: each chip
replays every event of a batch and drops the foreign ones at the scatter,
so the kernels' roofline shares show that replay.
"""
from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, data, system, work
from bench.loads.engine_loop import events_per_call


def task_mesh(devices: list, cfg: dict):
    """The 1-D "tasks" mesh over the run's chips, one site a chip."""
    from repro.launch.mesh import make_task_mesh

    if len(devices) != cfg["shards"]:
        raise ValueError(f"the configuration has {cfg['shards']} sites and "
                         f"the run {len(devices)} chips")
    mesh = make_task_mesh(len(devices))
    if list(mesh.devices.flat) != list(devices):
        raise ValueError("the run's chips are not the first visible devices")
    return mesh


def site_store(cfg: dict, seed: int, mesh):
    """(xs, ys, row counts) of `data.store`, each writer's rows on its site,
    and the row counts on the host."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.distributed.sharding import TASK_AXIS

    site = NamedSharding(mesh, PartitionSpec(TASK_AXIS))
    counts = data.row_counts(cfg, seed)
    make = jax.jit(data.lowrank_store.__wrapped__,
                   static_argnames=("t", "n", "d", "rank"),
                   out_shardings=(site, site, NamedSharding(
                       mesh, PartitionSpec())))
    xs, ys, _ = make(data.keys(seed)["data"], jnp.asarray(counts),
                     jnp.float32(cfg["label_noise"]), t=cfg["num_tasks"],
                     n=cfg["capacity"], d=cfg["dim"], rank=cfg["truth_rank"])
    return (xs, ys, jax.device_put(jnp.asarray(counts, jnp.int32), site),
            counts)


def on_first_chip(a):
    """The sharded array `a` whole on the mesh's first chip: gathered to
    every chip, then the first chip's copy kept."""
    from jax.sharding import NamedSharding, PartitionSpec

    whole = jax.jit(lambda x: x, out_shardings=NamedSharding(
        a.sharding.mesh, PartitionSpec()))(a)
    return whole.addressable_shards[0].data


def engine(cfg: dict, prob, mesh):
    """The program's sharded engine for the configuration on `mesh`."""
    from repro.core import make_engine

    solver = system.solver(cfg)._replace(prox_mode=cfg["prox_mode"])
    return make_engine(prob, solver, mesh)


def setup(cell, seed: int, calls: int, devices: list, control: bool = False):
    """(engine, state after the checked calls, their host iterates, store
    for the check: the sharded xs and ys and the host row counts)."""
    cfg, k = cell.config, events_per_call(cell)
    mesh = task_mesh(devices, cfg)
    xs, ys, site_counts, counts = site_store(cfg, seed, mesh)
    if control:
        iterates = check.reference_iterates(
            cell, seed, (on_first_chip(xs), on_first_chip(ys), counts), k,
            calls, "high")
    eng = engine(cfg, system.problem(cfg, xs, ys, site_counts), mesh)
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
                                        r.devices, r.control)
    ahead = cell.traffic["dispatch_ahead"]

    # From here to the check, `engine_loop.run`'s loop.
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
    r.layer.window_s = traced_s
    r.layer.chips = len(r.devices)
    r.layer.work = work.engine_events(cfg, traced * k)
    r.layer.kernel_calls = {
        "lstsq_grad_sampled": traced * k,
        "amtl_event_batch": traced * k // cfg["event_batch"],
    }
    r.layer.kernel_work = {
        "lstsq_grad_sampled": work.sampled_grad(cfg["batch_size"],
                                                cfg["dim"]),
        "amtl_event_batch": work.column_update(cfg["dim"],
                                               cfg["event_batch"]),
    }
    xs, ys, counts = store
    del state, eng, store
    t_check = time.perf_counter()
    whole = (on_first_chip(xs), on_first_chip(ys), counts)
    del xs, ys
    for name, value in check.learn(cell, r.seed, k, iterates,
                                   whole).items():
        r.check(name, value)
    r.info["check_s"] = time.perf_counter() - t_check
