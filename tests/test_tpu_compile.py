"""Compile the main path for a described TPU v5e chip, with no chip attached.

Interpret mode runs the Pallas kernels on the CPU and cannot see what the
chip's compiler (Mosaic) refuses: casts it has no rule for, tiles that are
not aligned, more VMEM than a kernel may use.  These tests lower and
compile every main-path kernel and the batch engine's jitted step for one
chip of a described `v5e:2x2` topology, at the widths the engine and the
serving platform run on the chip (`chip_smoke.py`), and assert that the
compiled program holds the Pallas kernel (`tpu_custom_call`).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every xdist
worker imports this file.  The persistent compilation cache is off around
these compiles (a chipless compile cannot read its entries back), and the
jit caches are cleared on both sides so no CPU-traced (oracle) program
is reused for the chip and no chip-traced program leaks into later CPU
tests of the same worker.
"""
import os
import re

import pytest
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.core.amtl import (AMTLConfig, _run_events, _sharded_state_specs,
                             init_batch_state, init_sharded_state)
from repro.core.losses import MTLProblem
from repro.core.operators import amtl_max_step
from repro.core.prox import sketch_width
from repro.distributed.sharding import TASK_AXIS, task_shard_specs
from repro.kernels import ops

# Engine/machinery shape, gradient shape, serve shape (chip_smoke.py).
D, T, N, TAU, EVENT_BATCH, PROX_RANK = 8192, 128, 4, 8, 32, 16
D_G, T_G, N_G, BATCH_SIZE = 4096, 32, 512, 32
D_S, T_S, N_S = 4096, 128, 512
D_C, T_C, N_C = 784, 3400, 512      # bench/configs/emnist62_writers.json


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    yield desc
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_pallas(compiled):
    assert "tpu_custom_call" in compiled.as_text(), \
        "compiled program took the jnp oracle, not the Pallas kernel"


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(")


def _callees(line: str) -> list[str]:
    names = re.findall(r"(?:body|calls|to_apply|condition)=%([\w.\-]+)", line)
    for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
        names += re.findall(r"%([\w.\-]+)", group)
    return names


def _loops_holding(text: str, kernel: str) -> list[str]:
    """op_names of the compiled program's while loops whose body runs the
    Pallas call named `kernel.N`, directly or in a computation it calls."""
    comps, name = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    call = re.compile(rf"%{re.escape(kernel)}\.\d+ = .*tpu_custom_call")
    holds = {c for c, lines in comps.items()
             if any(call.search(line) for line in lines)}

    def runs_kernel(c, seen):
        if c in seen:
            return False
        seen.add(c)
        return c in holds or any(runs_kernel(d, seen)
                                 for line in comps.get(c, ())
                                 for d in _callees(line))

    loops = []
    for lines in comps.values():
        for line in lines:
            m = re.search(r" while\(.*body=%([\w.\-]+)", line)
            if m and runs_kernel(m.group(1), set()):
                loops.append(re.search(r'op_name="([^"]*)"', line).group(1))
    return loops


BATCH_LOOP = "jit(_run_events)/while"


def _assert_gradients_batched(text: str, store_shape) -> None:
    """One `lstsq_grad_sampled.N` Pallas call a batch: no loop over the
    events holds it (the batch loop of `_run_events` alone does), and the
    program copies the (t, n, d) task store at most once."""
    assert len(re.findall(r"%lstsq_grad_sampled\.\d+ = .*tpu_custom_call",
                          text)) == 1
    assert set(_loops_holding(text, "lstsq_grad_sampled")) == {BATCH_LOOP}
    store = "f32[{},{},{}]".format(*store_shape)
    assert len(re.findall(rf" = {re.escape(store)}\S* copy\(", text)) <= 1


KERNELS = ("amtl_event_batch", "lstsq_grad_sampled",
           "lstsq_grad_sampled_grown", "lstsq_grad_sampled_wide",
           "sample_mask", "gauss_sketch", "gauss_sketch_shard",
           "svt_reconstruct", "lstsq_grad_sampled_batch")


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    s = lambda shape, dtype=jnp.float32: _spec(one_chip, shape, dtype)
    seed, n_t = s((), jnp.uint32), s((), jnp.int32)
    p = sketch_width(PROX_RANK, D, T)
    if name == "amtl_event_batch":
        lowered = ops.amtl_event_batch.lower(
            s((D, T)), s((D, EVENT_BATCH)), s((D, EVENT_BATCH)),
            s((EVENT_BATCH,), jnp.int32), s(()), s((EVENT_BATCH,)),
            use_pallas=True)
    elif name == "lstsq_grad_sampled_batch":
        # a batch of the benchmark cell's events over its whole store
        lowered = ops.lstsq_grad_sampled_batch.lower(
            s((T_C, N_C, D_C)), s((T_C, N_C)), s((EVENT_BATCH,), jnp.int32),
            s((EVENT_BATCH, D_C)), s((EVENT_BATCH,), jnp.uint32),
            batch_size=BATCH_SIZE, row_counts=s((T_C,), jnp.int32),
            use_pallas=True)
    elif name.startswith("lstsq_grad_sampled"):
        # the serve store after its first doubling; the engine's width
        n, d = {"grown": (2 * N_S, D_S), "wide": (N_G, D)}.get(
            name.rsplit("_", 1)[1], (N_G, D_G))
        lowered = ops.lstsq_grad_sampled.lower(
            s((n, d)), s((d,)), s((n,)), seed, batch_size=BATCH_SIZE,
            n_t=n_t, use_pallas=True)
    elif name == "sample_mask":
        lowered = ops.sample_mask.lower(N_S, BATCH_SIZE, seed, n_t=n_t,
                                        use_pallas=True)
    elif name.startswith("gauss_sketch"):
        t_local = T // 4 if name.endswith("shard") else T
        lowered = ops.gauss_sketch.lower(s((D, t_local)), seed,
                                         s((), jnp.int32), p=p,
                                         use_pallas=True)
    else:
        lowered = ops.svt_reconstruct.lower(s((D, p)), s((p,)), s((p, T)),
                                            use_pallas=True)
    _assert_pallas(lowered.compile())


def _engine_args(sharding, d, t, n, *, ragged, event_batch=EVENT_BATCH,
                 prox_every=EVENT_BATCH):
    cfg = AMTLConfig(eta=0.1, eta_k=amtl_max_step(TAU, t), tau=TAU,
                     engine="batch", event_batch=event_batch,
                     prox_every=prox_every, prox_rank=PROX_RANK,
                     batch_size=BATCH_SIZE if n > BATCH_SIZE else None)
    state = jax.eval_shape(
        lambda v0, k: init_batch_state(cfg, v0, t, k),
        jax.ShapeDtypeStruct((d, t), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    on_chip = lambda a: _spec(sharding, a.shape, a.dtype)
    problem = MTLProblem(_spec(sharding, (t, n, d)), _spec(sharding, (t, n)),
                         "lstsq", "nuclear", 0.1,
                         _spec(sharding, (t,), jnp.int32) if ragged else None)
    return problem, cfg, jax.tree.map(on_chip, state), _spec(sharding, (t,))


@pytest.mark.parametrize("shape", ("engine", "gradient", "serve", "cell",
                                   "one_event"))
def test_batch_engine_step_compiles_for_v5e(one_chip, monkeypatch, shape):
    """The whole `_run_events` program (randomized prox, sampled grads,
    batched column update) with the dispatch steered to Pallas in-test.
    Where it samples minibatches (all but `engine`), a batch's gradients
    are one Pallas call; `cell` is the benchmark cell's step (3400 ragged
    writers of 512 rows at 784 features), which compiles in about 20 s.
    `one_event` is the default `event_batch=1` with a prox cadence of 4
    events (a carried prox cache), at the gradient shape."""
    d, t, n = {"engine": (D, T, N), "gradient": (D_G, T_G, N_G),
               "serve": (D_S, T_S, 2 * N_S), "cell": (D_C, T_C, N_C),
               "one_event": (D_G, T_G, N_G)}[shape]
    cadence = {"event_batch": 1, "prox_every": 4} if shape == "one_event" \
        else {}
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    problem, cfg, state, offs = _engine_args(
        one_chip, d, t, n, ragged=shape in ("serve", "cell"), **cadence)
    compiled = _run_events.lower(problem, cfg, state, offs,
                                 2 * EVENT_BATCH).compile()
    _assert_pallas(compiled)
    if cfg.batch_size is not None:
        _assert_gradients_batched(compiled.as_text(), (t, n, d))


@pytest.mark.parametrize("prox_mode,cell", (("replicated", False),
                                            ("distributed", False),
                                            ("distributed", True)),
                         ids=("replicated", "distributed", "cell"))
def test_sharded_engine_step_compiles_for_v5e_2x2(topo, monkeypatch,
                                                  prox_mode, cell):
    """engine='sharded' over the four chips of the described 2x2 host: the
    task columns split over a 1-D "tasks" mesh, the prox's collectives
    (all_gather, or psum + all_gather) compiled around the kernels.
    With `cell`, the four-site benchmark cell's step (3400 ragged writers of
    512 rows at 784 features, minibatch 32, the distributed prox): each
    chip holds its 850 writers' rows and no more.  It compiles in about
    20 s on the CPU."""
    mesh = Mesh(np.asarray(topo.devices), (TASK_AXIS,))
    n_shards = len(topo.devices)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    d, t, n = (D_C, T_C, N_C) if cell else (D, T, N)
    cfg = AMTLConfig(eta=0.1, eta_k=amtl_max_step(TAU, t), tau=TAU,
                     engine="sharded", event_batch=EVENT_BATCH,
                     prox_every=EVENT_BATCH, prox_rank=PROX_RANK,
                     prox_mode=prox_mode,
                     batch_size=BATCH_SIZE if cell else None)
    state = jax.eval_shape(
        lambda v0, k: init_sharded_state(cfg, v0, t, k, n_shards),
        jax.ShapeDtypeStruct((d, t), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    on_mesh = lambda a, spec: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, spec))
    state = jax.tree.map(on_mesh, state, _sharded_state_specs(cfg))
    sp = task_shard_specs()
    per_task = lambda shape, dtype=jnp.float32: on_mesh(
        jax.ShapeDtypeStruct(shape, dtype), sp["per_task"])
    problem = MTLProblem(per_task((t, n, d)), per_task((t, n)),
                         "lstsq", "nuclear", 0.1,
                         per_task((t,), jnp.int32) if cell else None)
    offs = on_mesh(jax.ShapeDtypeStruct((t,), jnp.float32), sp["replicated"])
    compiled = _run_events.lower(problem, cfg, state, offs, 2 * EVENT_BATCH,
                                 mesh).compile()
    _assert_pallas(compiled)
    text = compiled.as_text()
    assert "all-gather" in text
    if cfg.prox_mode == "distributed":
        assert "all-reduce" in text
    if cell:
        store = t * n * d * 4
        args = compiled.memory_analysis().argument_size_in_bytes
        assert store / n_shards <= args < store / n_shards + 8 * d * t * 4
        _assert_gradients_batched(text, (t // n_shards, n, d))


PHASES = ("amtl.sample", "amtl.prox", "amtl.grad", "amtl.update")


@pytest.mark.parametrize("engine", ("batch", "replicated", "distributed",
                                    "batch_cell"))
def test_engine_phases_are_named_in_the_program_for_v5e(topo, one_chip,
                                                        monkeypatch, engine):
    """Each engine phase is a named scope in the lowered `_run_events`, so
    a device trace attributes every op of it to its phase.  `batch_cell`
    is the batch engine at the benchmark cell's shape (3400 writers, 784
    features, 512 rows), whose sampler draws the batch without a loop."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    if engine.startswith("batch"):
        d, t, n = (D_C, T_C, N_C) if engine == "batch_cell" \
            else (D_G, T_G, N_G)
        problem, cfg, state, offs = _engine_args(one_chip, d, t, n,
                                                 ragged=True)
        mesh = None
    else:
        mesh = Mesh(np.asarray(topo.devices), (TASK_AXIS,))
        cfg = AMTLConfig(eta=0.1, eta_k=amtl_max_step(TAU, T), tau=TAU,
                         engine="sharded", event_batch=EVENT_BATCH,
                         prox_every=EVENT_BATCH, prox_rank=PROX_RANK,
                         prox_mode=engine, batch_size=BATCH_SIZE)
        on_mesh = lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec))
        state = jax.tree.map(on_mesh, jax.eval_shape(
            lambda v0, k: init_sharded_state(cfg, v0, T, k, len(topo.devices)),
            jax.ShapeDtypeStruct((D_G, T), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.uint32)), _sharded_state_specs(cfg))
        sp = task_shard_specs()
        problem = MTLProblem(
            on_mesh(jax.ShapeDtypeStruct((T, N_G, D_G), jnp.float32),
                    sp["per_task"]),
            on_mesh(jax.ShapeDtypeStruct((T, N_G), jnp.float32),
                    sp["per_task"]),
            "lstsq", "nuclear", 0.1)
        offs = on_mesh(jax.ShapeDtypeStruct((T,), jnp.float32),
                       sp["replicated"])
    text = _run_events.lower(problem, cfg, state, offs, 2 * EVENT_BATCH,
                             mesh).as_text(debug_info=True)
    for scope in PHASES:
        assert scope in text, scope
