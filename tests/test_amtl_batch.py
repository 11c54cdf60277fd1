"""Batched multi-event AMTL engine: `event_batch` leaves the result
unchanged (bitwise serial replay of event_batch=1) for aligned configs,
within-batch conflict semantics, the amtl_event_batch kernel/oracle, and
the AMTLConfig validation surface for engine='batch'."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import AMTLConfig, amtl_solve
from repro.core.amtl import amtl_events_only, current_iterate
from repro.core.operators import rollback_columns, rollback_columns_batch
from repro.kernels import ref
from repro.kernels.amtl_event_batch import \
    amtl_event_batch as amtl_event_batch_pallas
from repro.kernels.ops import amtl_event_batch


def _base_cfg(problem, tau=3, **kw):
    eta = 1.0 / problem.lipschitz()
    return AMTLConfig(eta=eta, eta_k=0.7, tau=tau, **kw)


def _batch_pair(problem, tau, bsz, **kw):
    """(one-event cfg, batch cfg) aligned: prox_every == event_batch.

    The one-event reference is the batch engine at event_batch=1 and the
    same prox_every; at bsz=1 (prox_every=1, where the two would be one
    config) it is the seed dense engine."""
    batch = _base_cfg(problem, tau=tau, engine="batch", prox_every=bsz,
                      event_batch=bsz, **kw)
    one = batch._replace(engine="dense" if bsz == 1 else "batch",
                         event_batch=1)
    return one, batch


# ----------------------------------------------------------- equivalence
@pytest.mark.parametrize("tau,bsz", [(0, 5), (3, 5), (8, 5), (3, 1), (4, 10)])
def test_batch_engine_bitwise_matches_delta(small_problem, tau, bsz):
    """Aligned configs (prox_every == event_batch, same key): the batch
    engine replays the one-event iterates bitwise on the CPU oracle path.
    tau=3/bsz=5 exercises event_batch > ring depth (only the newest tau+1
    undo entries survive a batch)."""
    one_cfg, batch_cfg = _batch_pair(small_problem, tau, bsz)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(3)
    epe = 10 if bsz != 5 else 5
    one = amtl_solve(small_problem, one_cfg, w0, key, num_epochs=8,
                     events_per_epoch=epe)
    batch = amtl_solve(small_problem, batch_cfg, w0, key, num_epochs=8,
                       events_per_epoch=epe)
    np.testing.assert_array_equal(np.asarray(one.v), np.asarray(batch.v))
    np.testing.assert_array_equal(np.asarray(one.w), np.asarray(batch.w))
    np.testing.assert_array_equal(np.asarray(one.objectives),
                                  np.asarray(batch.objectives))
    np.testing.assert_array_equal(np.asarray(one.residuals),
                                  np.asarray(batch.residuals))


def test_batch_engine_bitwise_under_delays_dynamic_step_and_sketch(
        small_problem):
    """The batch engine must replay the delay history (per-event recording
    order), the delay-adaptive KM step, and the folded sketch key exactly."""
    one_cfg, batch_cfg = _batch_pair(small_problem, tau=4, bsz=5,
                                     dynamic_step=True, prox_rank=5)
    offsets = jnp.asarray([3.0, 1.0, 0.0, 2.0, 4.0])
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(11)
    one = amtl_solve(small_problem, one_cfg, w0, key, num_epochs=6,
                     delay_offsets=offsets)
    batch = amtl_solve(small_problem, batch_cfg, w0, key, num_epochs=6,
                       delay_offsets=offsets)
    np.testing.assert_array_equal(np.asarray(one.v), np.asarray(batch.v))


def test_batch_engine_state_stream_matches_delta(small_problem):
    """Beyond the iterate: the undo ring, ring pointer, event counter, PRNG
    key, and delay history of the batch engine must equal serial replay —
    they are what the next batch's stale read is reconstructed from."""
    one_cfg, batch_cfg = _batch_pair(small_problem, tau=3, bsz=5)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(5)
    d = amtl_events_only(small_problem, one_cfg, w0, key, 25)
    b = amtl_events_only(small_problem, batch_cfg, w0, key, 25)
    np.testing.assert_array_equal(np.asarray(d.v), np.asarray(b.v))
    np.testing.assert_array_equal(np.asarray(d.delta_ring),
                                  np.asarray(b.delta_ring))
    np.testing.assert_array_equal(np.asarray(d.task_ring),
                                  np.asarray(b.task_ring))
    assert int(d.ptr) == int(b.ptr)
    assert int(d.event) == int(b.event) == 25
    np.testing.assert_array_equal(np.asarray(d.key), np.asarray(b.key))
    np.testing.assert_array_equal(np.asarray(d.history.buf),
                                  np.asarray(b.history.buf))


def test_batch_events_only_matches_solve(small_problem):
    _, cfg = _batch_pair(small_problem, tau=3, bsz=5)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(1)
    st = amtl_events_only(small_problem, cfg, w0, key, 15)
    full = amtl_solve(small_problem, cfg, w0, key, num_epochs=1,
                      events_per_epoch=15)
    np.testing.assert_array_equal(np.asarray(current_iterate(st)),
                                  np.asarray(full.v))


# ----------------------------------------------------- validation surface
def test_event_batch_must_be_positive(small_problem):
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(0)
    for bad in (0, -3):
        with pytest.raises(ValueError, match=r"event_batch must be >= 1"):
            amtl_solve(small_problem,
                       _base_cfg(small_problem, engine="batch",
                                 prox_every=1, event_batch=bad),
                       w0, key, num_epochs=1)


@pytest.mark.parametrize("engine", ["dense"])
def test_one_event_engines_reject_event_batch(small_problem, engine):
    """The error must name event_batch (the offending parameter), not the
    prox knobs."""
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    with pytest.raises(ValueError, match=r"event_batch=4.*engine='batch'"):
        amtl_solve(small_problem,
                   _base_cfg(small_problem, engine=engine, event_batch=4),
                   w0, jax.random.PRNGKey(0), num_epochs=1)


def test_batch_requires_prox_alignment(small_problem):
    """prox_every may exceed event_batch (decoupled cadence) but must land
    on batch boundaries: non-multiples are rejected."""
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    err = r"prox_every \(2\) must be a multiple of event_batch \(4\)"
    with pytest.raises(ValueError, match=err):
        amtl_solve(small_problem,
                   _base_cfg(small_problem, engine="batch", prox_every=2,
                             event_batch=4),
                   w0, jax.random.PRNGKey(0), num_epochs=1)
    with pytest.raises(ValueError,
                       match=r"prox_every \(6\) must be a multiple"):
        amtl_solve(small_problem,
                   _base_cfg(small_problem, engine="batch", prox_every=6,
                             event_batch=4),
                   w0, jax.random.PRNGKey(0), num_epochs=1)


def test_batch_prox_rank_requires_nuclear(small_problem):
    l21 = small_problem._replace(reg_name="l21")
    w0 = jnp.zeros((l21.dim, l21.num_tasks), jnp.float32)
    with pytest.raises(ValueError, match=r"prox_rank.*nuclear.*'l21'"):
        amtl_solve(l21,
                   _base_cfg(l21, engine="batch", prox_every=4,
                             event_batch=4, prox_rank=3),
                   w0, jax.random.PRNGKey(0), num_epochs=1)


def test_batch_event_count_divisibility(small_problem):
    _, cfg = _batch_pair(small_problem, tau=3, bsz=4)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match=r"num_events \(10\).*event_batch"):
        amtl_events_only(small_problem, cfg, w0, key, 10)
    with pytest.raises(ValueError,
                       match=r"events_per_epoch \(10\).*event_batch"):
        amtl_solve(small_problem, cfg, w0, key, num_epochs=1,
                   events_per_epoch=10)


# ------------------------------------------------- vectorized rollback
def test_rollback_columns_batch_matches_serial():
    """The one-scatter rollback must agree bitwise with the sequential
    replay for every nu, including masked-out slots and duplicate tasks."""
    d, T, tau = 6, 3, 4
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.standard_normal((d, T)), jnp.float32)
    delta_ring = jnp.asarray(rng.standard_normal((tau + 1, d)), jnp.float32)
    task_ring = jnp.asarray([1, 2, 1, 0, 2], jnp.int32)
    for ptr in range(tau + 1):
        for nu in range(tau + 1):
            want = rollback_columns(v, delta_ring, task_ring,
                                    jnp.asarray(ptr, jnp.int32),
                                    jnp.asarray(nu, jnp.int32), tau)
            got = rollback_columns_batch(v, delta_ring, task_ring,
                                         jnp.asarray(ptr, jnp.int32),
                                         jnp.asarray(nu, jnp.int32), tau)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------ kernel validation
def _random_batch(d, T, b, seed, dtype=jnp.float32, dup_heavy=False):
    k = jax.random.PRNGKey(seed)
    kv, kp, kg, kt, ke = jax.random.split(k, 5)
    v = jax.random.normal(kv, (d, T), dtype)
    p = jax.random.normal(kp, (d, b), dtype)
    g = jax.random.normal(kg, (d, b), dtype)
    hi = 2 if dup_heavy else T
    tasks = jax.random.randint(kt, (b,), 0, hi)
    eta_ks = jax.random.uniform(ke, (b,), minval=0.1, maxval=0.9)
    return v, p, g, tasks, jnp.asarray(0.05), eta_ks


def _numpy_serial_replay(v, p, g, tasks, eta, eta_ks):
    """Literal event-order replay — the within-batch conflict spec."""
    v = np.asarray(v, np.float32).copy()
    p, g = np.asarray(p, np.float32), np.asarray(g, np.float32)
    eta = np.float32(np.asarray(eta))
    undos = []
    for i, t in enumerate(np.asarray(tasks)):
        cur = v[:, t].copy()
        undos.append(cur)
        ek = np.float32(np.asarray(eta_ks[i]))
        v[:, t] = cur + ek * (p[:, i] - eta * g[:, i] - cur)
    return v, np.stack(undos)


def test_batch_ref_matches_numpy_serial_replay():
    """The scan-based oracle IS sequential replay: same bits, duplicate
    tasks chained in event order."""
    v, p, g, tasks, eta, eta_ks = _random_batch(17, 3, 12, 0, dup_heavy=True)
    assert len(set(np.asarray(tasks).tolist())) < 12  # duplicates present
    got_v, got_u = ref.amtl_event_batch_ref(v, p, g, tasks, eta, eta_ks)
    want_v, want_u = _numpy_serial_replay(v, p, g, tasks, eta, eta_ks)
    np.testing.assert_allclose(np.asarray(got_v), want_v, rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_u), want_u, rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d,T,b", [(20, 5, 8), (128, 128, 64), (1000, 7, 3),
                                   (260, 130, 5),
                                   # one event a step (event_batch=1)
                                   (7, 3, 1), (128, 128, 1), (1000, 7, 1),
                                   (1024, 130, 1), (5000, 5, 1)])
def test_amtl_event_batch_kernel_matches_ref(d, T, b, dtype):
    """Interpret-mode Pallas kernel vs the jnp oracle, duplicate-free and
    duplicate-heavy shapes, padded and exact lane counts.  A single event's
    undo-log entry must be its column's exact pre-write bits."""
    v, p, g, tasks, eta, eta_ks = _random_batch(d, T, b, d + b, dtype)
    got_v, got_u = amtl_event_batch_pallas(v, p, g, tasks, eta, eta_ks,
                                           interpret=True)
    want_v, want_u = ref.amtl_event_batch_ref(
        v.astype(jnp.float32), p.astype(jnp.float32),
        g.astype(jnp.float32), tasks, eta, eta_ks)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got_v, np.float32),
                               np.asarray(want_v), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got_u, np.float32),
                               np.asarray(want_u), rtol=tol, atol=tol)
    if b == 1:
        np.testing.assert_array_equal(np.asarray(got_u[0]),
                                      np.asarray(v[:, tasks[0]]))


def test_amtl_event_batch_kernel_duplicates_serialize():
    """Duplicate-heavy batch (tasks drawn from {0,1}): the kernel's in-batch
    forwarding must chain updates exactly like serial replay."""
    v, p, g, tasks, eta, eta_ks = _random_batch(64, 4, 16, 9, dup_heavy=True)
    got_v, got_u = amtl_event_batch_pallas(v, p, g, tasks, eta, eta_ks,
                                           interpret=True)
    want_v, want_u = _numpy_serial_replay(v, p, g, tasks, eta, eta_ks)
    np.testing.assert_allclose(np.asarray(got_v), want_v, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_u), want_u, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("b", [7, 1])
def test_amtl_event_batch_ops_dispatch_cpu_is_oracle(b):
    """On CPU the ops wrapper must hit the jnp oracle path bitwise."""
    v, p, g, tasks, eta, eta_ks = _random_batch(129, 6, b, 2)
    got_v, got_u = amtl_event_batch(v, p, g, tasks, eta, eta_ks)
    want_v, want_u = jax.jit(ref.amtl_event_batch_ref)(v, p, g, tasks, eta,
                                                       eta_ks)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_u), np.asarray(want_u))
