"""The four-site learner cell at a small size on the CPU: the sharded
engine with the distributed prox, on one device and on four, judged by the
same check as the one-chip cell.

These drive the whole run past the harness's look for a chip."""
import contextlib
import json
import os
import subprocess
import sys
import time

import numpy as np

from bench import harness, spec
from bench.loads import engine_loop, engine_loop_sharded

from conftest import SMALL, small_cell

SEED = 2**31 + 5
WORKLOAD = "emnist62_writers.learn_4chip"


def _cell(shards: int):
    cell = small_cell(WORKLOAD)
    return cell._replace(config=dict(cell.config, shards=shards))


# The exchanges of the distributed prox (`svt_randomized_dist`): the jax.lax
# collective each makes, and what a site computes with it left out.
EXCHANGES = ("sketch_psum", "core_gather")


def _own_core(sites: int):
    """The site's own columns of the projected core, zeros in the others'."""
    import jax
    import jax.numpy as jnp

    def gather(x, axis_name, *, axis, tiled):
        assert axis == 1 and tiled
        whole = jnp.zeros((x.shape[0], x.shape[1] * sites), x.dtype)
        return jax.lax.dynamic_update_slice_in_dim(
            whole, x, jax.lax.axis_index(axis_name) * x.shape[1], 1)
    return gather


@contextlib.contextmanager
def exchange_left_out(name: str, sites: int):
    """Inside, the program's distributed prox runs with the collective of
    its `comm.<name>` scope left out: with `sketch_psum` each site
    orthonormalizes its own partial sketch, with `core_gather` each site's
    SVD sees only its own columns of the core.  The jit caches are
    cleared on both sides, so the fault is compiled in and out."""
    import jax

    from repro.core import amtl, prox

    collective, stand_in = {
        "sketch_psum": ("psum", lambda x, axis_name: x),
        "core_gather": ("all_gather", _own_core(sites)),
    }[name]
    real = prox.svt_randomized_dist

    def faulty(*args, **kwargs):
        kept = getattr(jax.lax, collective)
        setattr(jax.lax, collective, stand_in)
        try:
            return real(*args, **kwargs)
        finally:
            setattr(jax.lax, collective, kept)

    jax.clear_caches()
    amtl.svt_randomized_dist = faulty
    try:
        yield
    finally:
        amtl.svt_randomized_dist = real
        jax.clear_caches()


def test_one_device_program_is_correct_and_the_control_is_not(cpu_device):
    cell = _cell(1)
    res, _ = harness.execute(cell, SEED, 0.3, False, time.perf_counter(),
                             cpu_device)
    assert res["correct"], res["checks"]
    # As in test_bench_faults_learn: XLA-CPU computes the reference's QR
    # and SVD in f32 whatever the precision, so the control is judged by
    # the limit CPU readings set.
    cell = cell._replace(limits=dict(cell.limits, limits={"v_gap": 3e-6}))
    res, _ = harness.execute(cell, SEED, 0.3, False, time.perf_counter(),
                             cpu_device, control=True)
    assert not res["correct"], res["checks"]


FOUR_DEVICES = r"""
import json, sys, time
root, tests, workload, seed = sys.argv[1], sys.argv[2], sys.argv[3], int(sys.argv[4])
sys.path[:0] = [root, root + "/src", tests]
import jax
from bench import harness
from bench.loads import engine_loop_sharded as loop
from conftest import small_cell
from test_bench_learn_4chip import EXCHANGES, exchange_left_out

devices = jax.devices()[:4]
cell = small_cell(workload)
res, _ = harness.execute(cell, seed, 0.3, False, time.perf_counter(), devices)
out = {"correct": res["correct"]}
_, _, _, (xs, _, _) = loop.setup(cell, seed, 1, devices)
out["xs_shards"] = sorted(s.data.shape[0] for s in xs.addressable_shards)
out["xs_devices"] = len({s.device for s in xs.addressable_shards})

real = loop.engine

def half_batch(cfg, prob, mesh):
    eng = real(cfg, prob, mesh)
    return eng._replace(run=lambda st, offs, n: eng.run(st, offs, n // 2))

loop.engine = half_batch
res, _ = harness.execute(cell, seed, 0.3, False, time.perf_counter(), devices)
out["half_batch_correct"] = res["correct"]
loop.engine = real
for name in EXCHANGES:
    with exchange_left_out(name, len(devices)):
        res, _ = harness.execute(cell, seed, 0.3, False, time.perf_counter(),
                                 devices)
    out[name] = res["checks"]["v_gap"]["value"], res["correct"]
print(json.dumps(out))
"""


def test_four_devices_correct_sharded_and_a_fault_is_not():
    """Four CPU devices in one subprocess: the small cell is correct
    against bench/reference.py with each device holding T/4 writers; a
    run that drops half of each call's events is not, nor is one that
    leaves out either exchange between the sites (the sketch's psum, the
    core's all-gather)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", FOUR_DEVICES, str(spec.ROOT),
                           os.path.dirname(__file__), WORKLOAD, str(SEED)],
                          cwd=spec.ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"]
    assert not out["half_batch_correct"]
    for name in EXCHANGES:
        assert out[name][1] is False, (name, out[name])
    t = SMALL["num_tasks"]
    assert out["xs_shards"] == [t // 4] * 4 and out["xs_devices"] == 4


def test_one_device_sharded_iterate_equals_the_batch_cell(cpu_device):
    """At the small size and one seed, the four-site configuration on a
    one-device mesh and the one-chip cell's batch engine give the same
    iterate, bit for bit."""
    one = small_cell("emnist62_writers.learn")
    four = _cell(1)
    k = engine_loop.events_per_call(one)
    _, _, it_batch, _ = engine_loop.setup(one, SEED, 2)
    _, _, it_shard, _ = engine_loop_sharded.setup(four, SEED, 2, cpu_device)
    assert k == engine_loop.events_per_call(four)
    for a, b in zip(it_batch, it_shard, strict=True):
        np.testing.assert_array_equal(a, b)
