"""Sharded task-parallel AMTL engine: 1-device-mesh bitwise equivalence to
the batch engine on the CPU oracle path, the shard-local rollback and
sentinel-task batch dispatch, and the engine='sharded' validation surface.

Real multi-shard boundaries (2/8 fake devices, shard-count invariance, the
straggler shard) are exercised by the slow subprocess suite in
tests/test_amtl_sharded_multidevice.py.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import AMTLConfig, amtl_solve
from repro.core.amtl import amtl_events_only
from repro.core.operators import (rollback_columns_batch,
                                  rollback_columns_shard)
from repro.core.prox import (ProxPlan, sketch_width, svt_randomized,
                             svt_randomized_dist)
from repro.distributed.sharding import TASK_AXIS
from repro.kernels.ops import amtl_event_batch, amtl_event_batch_sharded
from repro.kernels.ref import shard_local_tasks
from repro.launch.mesh import make_task_mesh


def _cfg_pair(problem, tau, bsz, **kw):
    """(batch cfg, sharded cfg) aligned: prox_every == event_batch."""
    eta = 1.0 / problem.lipschitz()
    batch = AMTLConfig(eta=eta, eta_k=0.7, tau=tau, engine="batch",
                       prox_every=bsz, event_batch=bsz, **kw)
    return batch, batch._replace(engine="sharded")


@pytest.fixture(scope="module")
def mesh1():
    return make_task_mesh(1)


# ----------------------------------------------------------- equivalence
@pytest.mark.parametrize("tau,bsz", [(0, 4), (3, 5), (8, 5), (3, 1), (4, 10)])
def test_sharded_1shard_bitwise_matches_batch(small_problem, mesh1, tau, bsz):
    """On a 1-device "tasks" mesh every shard-local expression degenerates
    to the batch engine's, so iterates, objectives, and residuals must
    match bitwise on the CPU oracle path (incl. event_batch > ring depth
    and event_batch=1)."""
    batch_cfg, sharded_cfg = _cfg_pair(small_problem, tau, bsz)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(3)
    epe = 10 if bsz != 4 else 8
    batch = amtl_solve(small_problem, batch_cfg, w0, key, num_epochs=8,
                       events_per_epoch=epe)
    sharded = amtl_solve(small_problem, sharded_cfg, w0, key, num_epochs=8,
                         events_per_epoch=epe, mesh=mesh1)
    np.testing.assert_array_equal(np.asarray(batch.v), np.asarray(sharded.v))
    np.testing.assert_array_equal(np.asarray(batch.w), np.asarray(sharded.w))
    np.testing.assert_array_equal(np.asarray(batch.objectives),
                                  np.asarray(sharded.objectives))
    np.testing.assert_array_equal(np.asarray(batch.residuals),
                                  np.asarray(sharded.residuals))


def test_sharded_bitwise_under_delays_dynamic_step_and_sketch(
        small_problem, mesh1):
    """The folded sketch key, delay-adaptive KM step, and per-event history
    recording must all replay exactly through the shard_map path."""
    batch_cfg, sharded_cfg = _cfg_pair(small_problem, tau=4, bsz=5,
                                       dynamic_step=True, prox_rank=5)
    offsets = jnp.asarray([3.0, 1.0, 0.0, 2.0, 4.0])
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(11)
    batch = amtl_solve(small_problem, batch_cfg, w0, key, num_epochs=6,
                       delay_offsets=offsets)
    sharded = amtl_solve(small_problem, sharded_cfg, w0, key, num_epochs=6,
                         delay_offsets=offsets, mesh=mesh1)
    np.testing.assert_array_equal(np.asarray(batch.v), np.asarray(sharded.v))


def test_sharded_state_stream_matches_batch(small_problem, mesh1):
    """Beyond the iterate: the private undo ring, the global-id task ring,
    pointer, event counter, PRNG chain, and delay history must equal the
    batch engine's — they seed every later stale read."""
    batch_cfg, sharded_cfg = _cfg_pair(small_problem, tau=3, bsz=5)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(5)
    b = amtl_events_only(small_problem, batch_cfg, w0, key, 25)
    s = amtl_events_only(small_problem, sharded_cfg, w0, key, 25, mesh=mesh1)
    assert s.delta_ring.shape[0] == 1  # one shard -> one private ring
    np.testing.assert_array_equal(np.asarray(b.v), np.asarray(s.v))
    np.testing.assert_array_equal(np.asarray(b.delta_ring),
                                  np.asarray(s.delta_ring[0]))
    np.testing.assert_array_equal(np.asarray(b.task_ring),
                                  np.asarray(s.task_ring))
    assert int(b.ptr) == int(s.ptr)
    assert int(b.event) == int(s.event) == 25
    np.testing.assert_array_equal(np.asarray(b.key), np.asarray(s.key))
    np.testing.assert_array_equal(np.asarray(b.history.buf),
                                  np.asarray(s.history.buf))
    np.testing.assert_array_equal(np.asarray(b.history.count),
                                  np.asarray(s.history.count))


# ------------------------------------------- rank-distributed server prox
def test_svt_randomized_dist_1shard_bitwise_matches_serial(mesh1):
    """On a 1-shard mesh the psum and both gathers degenerate to the
    identity, Omega is un-partitioned, and every expression in
    `svt_randomized_dist` is the serial path's — so the distributed prox
    must reproduce `svt_randomized` BITWISE on the CPU oracle path."""
    d, T, rank = 24, 8, 4
    w = jax.random.normal(jax.random.PRNGKey(0), (d, T), jnp.float32)
    t = jnp.asarray(0.3, jnp.float32)
    key = jax.random.PRNGKey(42)
    plan = ProxPlan(axis=TASK_AXIS, num_tasks=T, n_local=T)
    from jax.sharding import PartitionSpec as P
    dist = jax.shard_map(
        lambda w_loc: svt_randomized_dist(w_loc, t, rank=rank, key=key,
                                          plan=plan),
        mesh=mesh1, in_specs=(P(None, TASK_AXIS),),
        out_specs=P(None, TASK_AXIS), check_vma=False)
    want = svt_randomized(w, t, rank=rank, key=key)
    np.testing.assert_array_equal(np.asarray(dist(w)), np.asarray(want))


@pytest.mark.parametrize("tau,bsz,k", [(3, 5, 1), (3, 4, 2), (0, 2, 3)])
def test_sharded_distributed_prox_1shard_bitwise_matches_batch(
        small_problem, mesh1, tau, bsz, k):
    """engine='sharded' with prox_mode='distributed' on a 1-shard mesh must
    reproduce the batch engine (replicated randomized prox) bitwise on the
    CPU oracle path — full state including the (column-sharded) prox cache
    at the decoupled cadence k > 1."""
    batch_cfg, sharded_cfg = _cfg_pair(small_problem, tau, bsz, prox_rank=4)
    batch_cfg = batch_cfg._replace(prox_every=k * bsz)
    dist_cfg = sharded_cfg._replace(prox_every=k * bsz,
                                    prox_mode="distributed")
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(9)
    n_events = 8 * bsz * k
    b = amtl_events_only(small_problem, batch_cfg, w0, key, n_events)
    s = amtl_events_only(small_problem, dist_cfg, w0, key, n_events,
                         mesh=mesh1)
    np.testing.assert_array_equal(np.asarray(b.v), np.asarray(s.v))
    np.testing.assert_array_equal(np.asarray(b.p_cache),
                                  np.asarray(s.p_cache))
    np.testing.assert_array_equal(np.asarray(b.task_ring),
                                  np.asarray(s.task_ring))
    np.testing.assert_array_equal(np.asarray(b.key), np.asarray(s.key))
    np.testing.assert_array_equal(np.asarray(b.delta_ring),
                                  np.asarray(s.delta_ring[0]))


def test_sharded_distributed_prox_dynamic_step_and_straggler_offsets(
        small_problem, mesh1):
    """Distributed prox composed with the delay-adaptive KM step and skewed
    per-task delays: still bitwise vs the batch engine at 1 shard."""
    batch_cfg, sharded_cfg = _cfg_pair(small_problem, tau=4, bsz=5,
                                       dynamic_step=True, prox_rank=5)
    dist_cfg = sharded_cfg._replace(prox_mode="distributed")
    offsets = jnp.asarray([3.0, 1.0, 0.0, 2.0, 4.0])
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(11)
    batch = amtl_solve(small_problem, batch_cfg, w0, key, num_epochs=6,
                       delay_offsets=offsets)
    dist = amtl_solve(small_problem, dist_cfg, w0, key, num_epochs=6,
                      delay_offsets=offsets, mesh=mesh1)
    np.testing.assert_array_equal(np.asarray(batch.v), np.asarray(dist.v))


def test_prox_plan_comm_bytes_beats_replicated_gather():
    """The collective payload the plan advertises must be the (d, p) psum +
    (p, T) core gather, and strictly under the replicated (d, T)
    all_gather at the bench scale (d=8192, T=128, rank=16)."""
    d, T, rank = 8192, 128, 16
    plan = ProxPlan(axis=TASK_AXIS, num_tasks=T, n_local=T // 8)
    p = sketch_width(rank, d, T)
    assert plan.comm_bytes_per_refresh(d, rank) == (d * p + p * T) * 4
    assert plan.comm_bytes_per_refresh(d, rank) < d * T * 4


# ------------------------------------------------- shard-local primitives
def test_rollback_columns_shard_tiles_the_batch_rollback():
    """Concatenating per-shard rollbacks in shard order must equal the
    global vectorized rollback bitwise, for every (ptr, nu) and a task ring
    with duplicates spanning shard boundaries."""
    d, T, tau, n_shards = 6, 8, 4, 4
    n_local = T // n_shards
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.standard_normal((d, T)), jnp.float32)
    ring = jnp.asarray(rng.standard_normal((tau + 1, d)), jnp.float32)
    task_ring = jnp.asarray([1, 6, 1, 0, 7], jnp.int32)
    for ptr in range(tau + 1):
        for nu in range(tau + 1):
            ptr_j = jnp.asarray(ptr, jnp.int32)
            nu_j = jnp.asarray(nu, jnp.int32)
            want = rollback_columns_batch(v, ring, task_ring, ptr_j, nu_j,
                                          tau)
            got = jnp.concatenate([
                rollback_columns_shard(
                    v[:, s * n_local:(s + 1) * n_local], ring, task_ring,
                    ptr_j, nu_j, tau, jnp.asarray(s * n_local, jnp.int32))
                for s in range(n_shards)], axis=1)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_shard_local_tasks_sentinel_and_ownership():
    tasks = jnp.asarray([0, 3, 4, 7, 2], jnp.int32)
    local, owned = shard_local_tasks(tasks, jnp.asarray(4, jnp.int32), 4)
    np.testing.assert_array_equal(np.asarray(owned),
                                  [False, False, True, True, False])
    np.testing.assert_array_equal(np.asarray(local), [4, 4, 0, 3, 4])


def test_sharded_batch_dispatch_drops_sentinel_events():
    """Foreign events (sentinel column id T_local) must leave the local
    block untouched while owned events match the unsharded op bitwise —
    including a duplicate chain that spans owned and foreign events."""
    d, T, b = 16, 6, 8
    n_local, t_off = 3, 3
    k = jax.random.PRNGKey(0)
    kv, kp, kg, ke = jax.random.split(k, 4)
    v = jax.random.normal(kv, (d, T), jnp.float32)
    p = jax.random.normal(kp, (d, b), jnp.float32)
    g = jax.random.normal(kg, (d, b), jnp.float32)
    eta_ks = jax.random.uniform(ke, (b,), minval=0.1, maxval=0.9)
    eta = jnp.asarray(0.05)
    tasks = jnp.asarray([0, 4, 4, 1, 5, 0, 3, 4], jnp.int32)

    want_v, want_u = amtl_event_batch(v, p, g, tasks, eta, eta_ks)
    local, owned = shard_local_tasks(tasks, jnp.asarray(t_off, jnp.int32),
                                     n_local)
    got_v, got_u = amtl_event_batch_sharded(v[:, t_off:t_off + n_local], p,
                                            g, local, eta, eta_ks)
    np.testing.assert_array_equal(np.asarray(got_v),
                                  np.asarray(want_v[:, t_off:t_off + n_local]))
    np.testing.assert_array_equal(
        np.asarray(got_u)[np.asarray(owned)],
        np.asarray(want_u)[np.asarray(owned)])


# ----------------------------------------------------- validation surface
def test_sharded_requires_prox_alignment(small_problem, mesh1):
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    eta = 1.0 / small_problem.lipschitz()
    cfg = AMTLConfig(eta=eta, eta_k=0.7, tau=3, engine="sharded",
                     prox_every=2, event_batch=4)
    with pytest.raises(ValueError,
                       match=r"prox_every \(2\) must be a multiple of "
                             r"event_batch \(4\)"):
        amtl_solve(small_problem, cfg, w0, jax.random.PRNGKey(0),
                   num_epochs=1, events_per_epoch=4, mesh=mesh1)


def test_distributed_prox_requires_sharded_engine(small_problem):
    from repro.core import validate_config
    cfg = AMTLConfig(eta=0.05, eta_k=0.7, tau=3, engine="batch",
                     prox_every=4, event_batch=4, prox_rank=4,
                     prox_mode="distributed")
    with pytest.raises(ValueError, match="no shards to distribute over"):
        validate_config(cfg, small_problem.reg_name)


def test_distributed_prox_requires_prox_rank(small_problem):
    from repro.core import validate_config
    cfg = AMTLConfig(eta=0.05, eta_k=0.7, tau=3, engine="sharded",
                     prox_every=4, event_batch=4, prox_mode="distributed")
    with pytest.raises(ValueError, match="prox_rank must be set"):
        validate_config(cfg, small_problem.reg_name)


def test_unknown_prox_mode_rejected(small_problem):
    from repro.core import validate_config
    cfg = AMTLConfig(eta=0.05, eta_k=0.7, tau=3, engine="sharded",
                     prox_every=4, event_batch=4, prox_rank=4,
                     prox_mode="sketchy")
    with pytest.raises(ValueError, match="unknown prox_mode"):
        validate_config(cfg, small_problem.reg_name)


def test_sharded_requires_tasks_axis(small_problem):
    from repro.launch.mesh import make_host_mesh
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    eta = 1.0 / small_problem.lipschitz()
    cfg = AMTLConfig(eta=eta, eta_k=0.7, tau=3, engine="sharded",
                     prox_every=4, event_batch=4)
    with pytest.raises(ValueError, match=r"mesh with a 'tasks' axis"):
        amtl_solve(small_problem, cfg, w0, jax.random.PRNGKey(0),
                   num_epochs=1, events_per_epoch=4, mesh=make_host_mesh())


def test_mesh_rejected_for_unsharded_engines(small_problem, mesh1):
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    eta = 1.0 / small_problem.lipschitz()
    cfg = AMTLConfig(eta=eta, eta_k=0.7, tau=3, engine="delta")
    with pytest.raises(ValueError, match=r"mesh is only meaningful.*sharded"):
        amtl_solve(small_problem, cfg, w0, jax.random.PRNGKey(0),
                   num_epochs=1, mesh=mesh1)


def test_make_task_mesh_validates_device_count():
    with pytest.raises(ValueError, match=r"num_shards must be in"):
        make_task_mesh(jax.local_device_count() + 1)
    with pytest.raises(ValueError, match=r"num_shards must be in"):
        make_task_mesh(0)
