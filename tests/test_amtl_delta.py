"""The delta ring one event a step (engine='batch', event_batch=1):
event-for-event equivalence with the seed dense ring, prox amortization
(paper §III-C), and the undo-log rollback."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import AMTLConfig, amtl_solve
from repro.core.amtl import amtl_events_only, current_iterate
from repro.core.operators import rollback_columns


def _base_cfg(problem, tau=3, **kw):
    eta = 1.0 / problem.lipschitz()
    return AMTLConfig(eta=eta, eta_k=0.7, tau=tau, **kw)


# ----------------------------------------------------------- equivalence
@pytest.mark.parametrize("tau", [0, 1, 3, 8])
def test_delta_engine_bitwise_matches_dense(small_problem, tau):
    """Same PRNG key, prox_every=1: the delta ring, one event a step,
    reconstructs exactly the stale reads of the seed (tau+1, d, T) ring,
    event for event."""
    cfg = _base_cfg(small_problem, tau=tau)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(3)
    dense = amtl_solve(small_problem, cfg._replace(engine="dense"), w0, key,
                       num_epochs=8)
    one_event = amtl_solve(small_problem, cfg._replace(engine="batch"), w0,
                           key, num_epochs=8)
    np.testing.assert_array_equal(np.asarray(dense.v), np.asarray(one_event.v))
    np.testing.assert_array_equal(np.asarray(dense.w), np.asarray(one_event.w))
    np.testing.assert_array_equal(np.asarray(dense.objectives),
                                  np.asarray(one_event.objectives))
    np.testing.assert_array_equal(np.asarray(dense.residuals),
                                  np.asarray(one_event.residuals))


def test_delta_engine_bitwise_under_delays_and_dynamic_step(small_problem):
    """Equivalence must survive nonzero staleness and the delay-adaptive
    step (Eq. III.5/III.6), which both consume extra state."""
    cfg = _base_cfg(small_problem, tau=4, dynamic_step=True)
    offsets = jnp.asarray([3.0, 1.0, 0.0, 2.0, 4.0])
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(11)
    dense = amtl_solve(small_problem, cfg._replace(engine="dense"), w0, key,
                       num_epochs=6, delay_offsets=offsets)
    one_event = amtl_solve(small_problem, cfg._replace(engine="batch"), w0,
                           key, num_epochs=6, delay_offsets=offsets)
    np.testing.assert_array_equal(np.asarray(dense.v), np.asarray(one_event.v))


def test_events_only_matches_solve(small_problem):
    cfg = _base_cfg(small_problem)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(1)
    st = amtl_events_only(small_problem, cfg, w0, key, 15)
    full = amtl_solve(small_problem, cfg, w0, key, num_epochs=1,
                      events_per_epoch=15)
    np.testing.assert_array_equal(np.asarray(current_iterate(st)),
                                  np.asarray(full.v))


def test_engine_config_validation(small_problem):
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="dense"):
        amtl_solve(small_problem,
                   _base_cfg(small_problem, engine="dense", prox_every=4),
                   w0, key, num_epochs=1)
    with pytest.raises(ValueError, match="unknown AMTL engine"):
        amtl_solve(small_problem, _base_cfg(small_problem, engine="sparse"),
                   w0, key, num_epochs=1)
    # the one-event delta engine is the batch engine at event_batch=1 now
    with pytest.raises(ValueError, match=r"unknown AMTL engine 'delta'; "
                       r"expected 'dense', 'batch', or 'sharded'"):
        amtl_solve(small_problem, _base_cfg(small_problem, engine="delta"),
                   w0, key, num_epochs=1)


# ----------------------------------------------------- prox amortization
def test_prox_every_objective_decreases(small_problem):
    """Amortized server prox (§III-C) still drives the objective down."""
    cfg = _base_cfg(small_problem, tau=3, prox_every=4)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    res = amtl_solve(small_problem, cfg, w0, jax.random.PRNGKey(0),
                     num_epochs=120)
    objs = np.asarray(res.objectives)
    assert objs[-1] < objs[0]
    assert objs[-1] < objs[len(objs) // 2] + 1e-3  # keeps improving late

def _amortized_oracle_numpy(problem, cfg, key, num_events):
    """Sequential pure-numpy replay of the amortized algorithm (§III-C).

    Event k: sample (t, nu) with the engine's exact PRNG calls; if
    k % prox_every == 0 recompute the server prox on the stale read
    (iterate from nu events ago, own column patched current) and cache it,
    else reuse the cache; then apply the KM-relaxed forward step to column
    t.  Float64 numpy arithmetic — the test checks the engine produces THE
    amortized iterates, not merely a decreasing objective.
    """
    xs = np.asarray(problem.xs, np.float64)
    ys = np.asarray(problem.ys, np.float64)
    T = xs.shape[0]
    v = np.zeros((problem.dim, T))
    history = [v.copy()]
    p_cache = None
    for k in range(num_events):
        key, k_task, k_delay = jax.random.split(key, 3)
        t = int(jax.random.randint(k_task, (), 0, T))
        raw = cfg.delay_jitter * float(jax.random.uniform(k_delay))
        nu = min(int(np.round(raw)), min(cfg.tau, k))
        if k % cfg.prox_every == 0:
            v_hat = history[len(history) - 1 - nu].copy()
            v_hat[:, t] = v[:, t]
            u, s, vt = np.linalg.svd(v_hat, full_matrices=False)
            s = np.maximum(s - cfg.eta * problem.lam, 0.0)
            p_cache = (u * s[None, :]) @ vt
        p_t = p_cache[:, t]
        g_t = 2.0 * (xs[t].T @ (xs[t] @ p_t - ys[t]))
        v = v.copy()
        v[:, t] = v[:, t] + cfg.eta_k * (p_t - cfg.eta * g_t - v[:, t])
        history.append(v.copy())
    return v


@pytest.mark.parametrize("prox_every", [2, 4])
def test_prox_every_matches_sequential_oracle(small_problem, prox_every):
    """The amortized engine's iterates are the ones §III-C specifies: a
    refresh exactly at events 0, K, 2K, ... on the then-current stale read,
    the cached prox in between — verified column-for-column against an
    event-by-event numpy replay, not just by objective decrease."""
    cfg = _base_cfg(small_problem, tau=3, prox_every=prox_every)
    key = jax.random.PRNGKey(17)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    num_events = 24
    got = amtl_events_only(small_problem, cfg, w0, key, num_events)
    want = _amortized_oracle_numpy(small_problem, cfg, key, num_events)
    np.testing.assert_allclose(np.asarray(current_iterate(got), np.float64),
                               want, rtol=5e-4, atol=5e-5)
    # the caching matters: an exact-prox (prox_every=1) run must NOT match
    exact = amtl_events_only(small_problem, cfg._replace(prox_every=1),
                             w0, key, num_events)
    assert not np.allclose(np.asarray(current_iterate(exact), np.float64),
                           want, rtol=5e-4, atol=5e-5)


def test_randomized_prox_refresh_converges(small_problem):
    """Randomized SVT refresh (large-d*T mode) reaches a comparable
    objective to the exact-prox run."""
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(0)
    exact = amtl_solve(small_problem, _base_cfg(small_problem), w0, key,
                       num_epochs=120)
    sketch = amtl_solve(small_problem,
                        _base_cfg(small_problem, prox_every=2,
                                  prox_rank=small_problem.num_tasks),
                        w0, key, num_epochs=120)
    assert float(sketch.objectives[-1]) <= float(exact.objectives[-1]) * 1.1


def test_sketch_mode_keeps_event_stream(small_problem):
    """The randomized-refresh key is folded, not split, off the main PRNG
    chain, so the activation/staleness sequence matches the dense engine
    even with prox_rank set (recorded delays are the witness)."""
    cfg = _base_cfg(small_problem, tau=3)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(9)
    dense = amtl_events_only(small_problem, cfg._replace(engine="dense"),
                             w0, key, 25)
    sketch = amtl_events_only(
        small_problem, cfg._replace(prox_every=2, prox_rank=5), w0, key, 25)
    np.testing.assert_array_equal(np.asarray(dense.history.buf),
                                  np.asarray(sketch.history.buf))


# --------------------------------------------------------- rollback unit
def test_rollback_columns_replays_undo_log():
    """Restoring the nu newest log entries reproduces the older iterate
    bitwise, including repeated writes to the same column."""
    d, T, tau = 6, 3, 4
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.standard_normal((d, T)), jnp.float32)
    history = [np.asarray(v)]
    delta_ring = jnp.zeros((tau + 1, d), jnp.float32)
    task_ring = jnp.zeros((tau + 1,), jnp.int32)
    ptr = 0
    for k, t in enumerate([1, 2, 1, 0]):   # column 1 written twice
        ptr = (ptr + 1) % (tau + 1)
        delta_ring = delta_ring.at[ptr].set(v[:, t])
        task_ring = task_ring.at[ptr].set(t)
        v = v.at[:, t].set(jnp.asarray(rng.standard_normal(d), jnp.float32))
        history.append(np.asarray(v))
    for nu in range(5):
        got = rollback_columns(v, delta_ring, task_ring,
                               jnp.asarray(ptr, jnp.int32),
                               jnp.asarray(nu, jnp.int32), tau)
        np.testing.assert_array_equal(np.asarray(got), history[len(history) - 1 - nu])
