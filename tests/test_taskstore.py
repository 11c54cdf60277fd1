"""TaskStore + ragged cohort contracts (PR 9).

Three layers, matching how raggedness enters the stack:

  * the store itself: arrival-order appends, power-of-two capacity
    doubling, cached problem view, bitwise checkpoint round-trip;
  * the ragged engine math: masked gradients equal per-task-trimmed
    dense gradients, the valid-row cutoff keeps exactly min(b, n_t)
    rows under the unbiased (n_t/bsz) scaling, uniform row_counts are
    BITWISE the row_counts=None baseline, and row_counts never touch
    the activation/PRNG event stream;
  * the serving platform: label-carrying `submit_feedback` folds
    accepted rows at chunk boundaries such that the state is bitwise a
    fold/rebuild/run replay of one engine session, resume (store +
    engine) is bitwise invisible through capacity growth, and the
    label-free path never creates a store.

Deterministic sweeps here; the hypothesis-driven generalizations live
in tests/test_sampling_properties.py (skipped when hypothesis is
absent, as conftest documents).
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (AMTLConfig, MTLProblem, NetworkModel, SimProblem,
                        amtl_events_only, amtl_solve, make_engine,
                        simulate_amtl)
from repro.core.operators import amtl_max_step
from repro.data import TaskStore, stack_ragged
from repro.kernels import ops, ref
from repro.serve import AMTLServer, ServeConfig

RAGGED_ENGINES = ("batch1", "batch", "sharded")


def _ragged_lists(sizes, d, seed=0):
    rng = np.random.default_rng(seed)
    xs = [(rng.standard_normal((n, d)) / np.sqrt(d)).astype(np.float32)
          for n in sizes]
    ys = [rng.standard_normal(n).astype(np.float32) for n in sizes]
    return xs, ys


@pytest.fixture(scope="module")
def ragged_problem():
    xs, ys = _ragged_lists([6, 17, 11, 3], d=8, seed=1)
    return stack_ragged(xs, ys, "lstsq", "nuclear", 0.1)


def _cfg(problem, engine, **kw):
    eta = 1.0 / problem.lipschitz()
    if engine == "batch1":              # the batch engine, one event a step
        engine = "batch"
        kw.setdefault("event_batch", 1)
    if engine in ("batch", "sharded"):
        kw.setdefault("event_batch", 4)
        kw.setdefault("prox_every", kw["event_batch"])
    return AMTLConfig(eta=eta, eta_k=0.7, tau=3, engine=engine, **kw)


def _mesh1():
    from repro.launch.mesh import make_task_mesh
    return make_task_mesh(1)


def _run(problem, cfg, n_events, mesh=None, key=0):
    eng = make_engine(problem, cfg, mesh)
    w0 = jnp.zeros((problem.dim, problem.num_tasks), jnp.float32)
    return eng, eng.run(eng.init(w0, jax.random.PRNGKey(key)), None, n_events)


def _assert_states_equal(a, b, msg=""):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=msg)


# ================================================================= store


def test_from_ragged_pads_and_masks():
    xs, ys = _ragged_lists([3, 7, 2], d=5, seed=2)
    store = TaskStore.from_ragged(xs, ys, "lstsq", "nuclear", 0.1)
    assert (store.num_tasks, store.capacity, store.dim) == (3, 7, 5)
    assert store.row_counts.tolist() == [3, 7, 2]
    assert store.num_rows == 12
    prob = store.problem()
    assert prob.xs.shape == (3, 7, 5)
    np.testing.assert_array_equal(np.asarray(prob.row_counts), [3, 7, 2])
    # valid rows are the cohorts verbatim; padding rows are zero
    np.testing.assert_array_equal(np.asarray(prob.xs[0, :3]), xs[0])
    np.testing.assert_array_equal(np.asarray(prob.xs[0, 3:]), 0.0)
    np.testing.assert_array_equal(np.asarray(prob.ys[2, :2]), ys[2])


def test_append_arrival_order_and_pow2_growth():
    store = TaskStore.from_ragged(*_ragged_lists([2, 3], d=4, seed=3),
                                  loss_name="lstsq", reg_name="nuclear",
                                  lam=0.1)
    assert store.capacity == 3
    rng = np.random.default_rng(4)
    x6 = rng.standard_normal((6, 4)).astype(np.float32)
    y6 = rng.standard_normal(6).astype(np.float32)
    # task 0 takes 4 rows (2 -> 6 > 3: doubles 3 -> 6), task 1 takes 2
    assert store.append([0, 1, 0, 0, 1, 0], x6, y6) == 6
    assert store.capacity == 6
    assert store.row_counts.tolist() == [6, 5]
    prob = store.problem()
    # arrival order within a task: submissions 0, 2, 3, 5 land at rows
    # 2, 3, 4, 5 of task 0
    np.testing.assert_array_equal(np.asarray(prob.xs[0, 2:]),
                                  x6[[0, 2, 3, 5]])
    np.testing.assert_array_equal(np.asarray(prob.ys[1, 3:5]), y6[[1, 4]])
    # one more overflow doubles again: 6 -> 12
    store.append([1, 1], x6[:2], y6[:2])
    assert store.capacity == 12
    assert store.row_counts.tolist() == [6, 7]


def test_append_validates():
    store = TaskStore.from_ragged(*_ragged_lists([2, 2], d=3, seed=5),
                                  loss_name="lstsq", reg_name="nuclear",
                                  lam=0.1)
    with pytest.raises(ValueError, match="append expects features"):
        store.append([0], np.zeros((1, 5), np.float32), [0.0])
    with pytest.raises(ValueError, match="append expects features"):
        store.append([0, 1], np.zeros((2, 3), np.float32), [0.0])
    with pytest.raises(ValueError, match="task_ids must lie"):
        store.append([2], np.zeros((1, 3), np.float32), [0.0])
    assert store.append([], np.zeros((0, 3), np.float32), []) == 0


def test_problem_view_cached_until_append():
    store = TaskStore.from_ragged(*_ragged_lists([2, 4], d=3, seed=6),
                                  loss_name="lstsq", reg_name="nuclear",
                                  lam=0.1)
    p1 = store.problem()
    assert store.problem() is p1       # same arrays -> same jit cache keys
    store.append([0], np.ones((1, 3), np.float32), [1.0])
    p2 = store.problem()
    assert p2 is not p1
    assert np.asarray(p2.row_counts).tolist() == [3, 4]


def test_checkpoint_roundtrip_bitwise(tmp_path):
    store = TaskStore.from_ragged(*_ragged_lists([5, 9, 2], d=6, seed=7),
                                  loss_name="lstsq", reg_name="nuclear",
                                  lam=0.1)
    rng = np.random.default_rng(8)
    store.append(np.zeros(8, np.int64),
                 rng.standard_normal((8, 6)).astype(np.float32),
                 rng.standard_normal(8).astype(np.float32))
    assert store.capacity == 18        # 9 -> 18: growth history on disk
    store.save(str(tmp_path), 7, keep_last=2)
    back = TaskStore.restore(str(tmp_path), 7, "lstsq", "nuclear", 0.1)
    assert back.capacity == store.capacity
    a, b = store.state(), back.state()
    np.testing.assert_array_equal(a.xs, b.xs)
    np.testing.assert_array_equal(a.ys, b.ys)
    np.testing.assert_array_equal(a.row_counts, b.row_counts)


# ===================================================== ragged engine math


@pytest.mark.parametrize("engine", RAGGED_ENGINES)
@pytest.mark.parametrize("batch_size", (None, 4))
def test_uniform_row_counts_are_bitwise_baseline(small_problem, engine,
                                                 batch_size):
    """Acceptance anchor: row_counts == n everywhere + no appends must
    reproduce the row_counts=None engine BITWISE on the full state."""
    cfg = _cfg(small_problem, engine, batch_size=batch_size)
    mesh = _mesh1() if engine == "sharded" else None
    n = small_problem.xs.shape[1]
    uniform = small_problem._replace(row_counts=jnp.full(
        (small_problem.num_tasks,), n, jnp.int32))
    _, st_none = _run(small_problem, cfg, 24, mesh)
    _, st_uni = _run(uniform, cfg, 24, mesh)
    _assert_states_equal(st_none, st_uni, f"{engine}/bsz={batch_size}")


def test_dense_engine_rejects_ragged(ragged_problem):
    with pytest.raises(ValueError, match="dense"):
        make_engine(ragged_problem, _cfg(ragged_problem, "dense"))


def test_ragged_grad_matches_trimmed_dense(ragged_problem):
    """Masked per-task gradients equal the gradient over the trimmed
    (n_t, d) cohort.  Not bitwise — XLA reassociates the contraction
    differently across row counts — but ulp-tight."""
    counts = np.asarray(ragged_problem.row_counts)
    w = jax.random.normal(jax.random.PRNGKey(9),
                          (ragged_problem.dim, ragged_problem.num_tasks),
                          jnp.float32)
    g_masked = np.asarray(ragged_problem.full_grad(w))
    for t in range(ragged_problem.num_tasks):
        n_t = int(counts[t])
        trimmed = 2.0 * (np.asarray(ragged_problem.xs[t, :n_t]).T
                         @ (np.asarray(ragged_problem.xs[t, :n_t])
                            @ np.asarray(w[:, t])
                            - np.asarray(ragged_problem.ys[t, :n_t])))
        np.testing.assert_allclose(g_masked[:, t], trimmed, rtol=2e-4,
                                   atol=1e-6)
    # the masked loss value likewise sums only valid rows
    v = float(ragged_problem.loss_value(w))
    want = sum(float(np.sum((np.asarray(ragged_problem.xs[t, :counts[t]])
                             @ np.asarray(w[:, t])
                             - np.asarray(ragged_problem.ys[t, :counts[t]]))
                            ** 2))
               for t in range(ragged_problem.num_tasks))
    np.testing.assert_allclose(v, want, rtol=1e-5)


def test_ragged_cutoff_keeps_exactly_min_b_nt_rows():
    """The masked counter-hash selection keeps exactly min(b, n_t) VALID
    rows for every (n, b, n_t, seed) in the sweep, and the kernel
    (interpret mode) emits the oracle's bits."""
    for n, b, n_t, seed in [(12, 4, 7, 0), (12, 4, 2, 1), (12, 12, 5, 2),
                            (37, 9, 37, 3), (37, 40, 17, 4), (5, 1, 0, 5),
                            (600, 50, 300, 6), (600, 700, 600, 7)]:
        s = jnp.asarray(seed, jnp.uint32)
        nt = jnp.asarray(n_t, jnp.int32)
        mask = np.asarray(ref.sample_mask_masked_ref(n, b, s, nt))
        assert mask.sum() == min(b, n_t), (n, b, n_t, seed)
        assert not mask[n_t:].any()           # never selects padding
        got = np.asarray(ops.sample_mask(n, b, s, n_t=nt, interpret=True))
        np.testing.assert_array_equal(got, mask, err_msg=str((n, b, n_t)))
        if n_t == n:                          # uniform: bitwise unmasked law
            np.testing.assert_array_equal(
                mask, np.asarray(ref.sample_mask_ref(n, b, s)))


def test_ragged_sampled_grad_saturates_to_masked_full():
    """batch_size >= n_t: selection saturates to all valid rows and the
    (n_t/bsz) scale to 1 — bitwise the masked full gradient."""
    n, d = 14, 6
    kx, kw, ky = jax.random.split(jax.random.PRNGKey(10), 3)
    x = jax.random.normal(kx, (n, d), jnp.float32)
    w = jax.random.normal(kw, (d,), jnp.float32)
    y = jax.random.normal(ky, (n,), jnp.float32)
    for n_t in (3, 9, 14):
        nt = jnp.asarray(n_t, jnp.int32)
        got = ops.lstsq_grad_sampled(x, w, y, jnp.uint32(5), batch_size=n,
                                     n_t=nt, use_pallas=False)
        want = ops.lstsq_grad(x, w, y, n_t=nt, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ragged_minibatch_gradient_unbiased_over_seeds():
    """E_seed over the masked selection approaches the masked full
    gradient under the (n_t/bsz) scaling — the simulator's law."""
    n, d, b, n_t = 40, 6, 10, 23
    kx, kw, ky = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(kx, (n, d), jnp.float32)
    w = jax.random.normal(kw, (d,), jnp.float32)
    y = jax.random.normal(ky, (n,), jnp.float32)
    nt = jnp.asarray(n_t, jnp.int32)
    seeds = jnp.arange(6000, dtype=jnp.uint32)
    grads = jax.vmap(lambda s: ref.lstsq_grad_sampled_masked_ref(
        x, w, y, s, b, nt))(seeds)
    mean = np.asarray(grads, np.float64).mean(axis=0)
    full = np.asarray(ref.lstsq_grad_masked_ref(x, w, y, nt), np.float64)
    rel = np.linalg.norm(mean - full) / np.linalg.norm(full)
    assert rel < 0.08, rel


@pytest.mark.parametrize("batch_size", (None, 3))
def test_row_counts_leave_event_stream_untouched(ragged_problem, batch_size):
    """Raggedness only reshapes gradients: the PRNG chain head and the
    (task, staleness) history are data-independent, so they must match
    the same problem with row_counts dropped."""
    cfg = _cfg(ragged_problem, "batch1", batch_size=batch_size)
    uniform = ragged_problem._replace(row_counts=None)
    w0 = jnp.zeros((ragged_problem.dim, ragged_problem.num_tasks),
                   jnp.float32)
    key = jax.random.PRNGKey(12)
    st_r = amtl_events_only(ragged_problem, cfg, w0, key, 16)
    st_u = amtl_events_only(uniform, cfg, w0, key, 16)
    np.testing.assert_array_equal(np.asarray(st_r.key), np.asarray(st_u.key))
    np.testing.assert_array_equal(np.asarray(st_r.history.buf),
                                  np.asarray(st_u.history.buf))


def test_mid_session_append_continues_event_stream(ragged_problem):
    """Rebuilding the engine against a grown store mid-session continues
    the SAME activation stream: the chain state lives in the engine
    state, not the problem."""
    cfg = _cfg(ragged_problem, "batch1")
    store = TaskStore.from_problem(ragged_problem)
    eng1 = make_engine(store.problem(), cfg)
    w0 = jnp.zeros((ragged_problem.dim, ragged_problem.num_tasks),
                   jnp.float32)
    st = eng1.run(eng1.init(w0, jax.random.PRNGKey(13)), None, 8)
    rng = np.random.default_rng(14)
    store.append([0, 3], rng.standard_normal((2, 8)).astype(np.float32),
                 rng.standard_normal(2).astype(np.float32))
    eng2 = make_engine(store.problem(), cfg)
    st2 = eng2.run(st, None, 8)
    # reference: the un-grown engine run the same 16 events
    ref_st = eng1.run(st, None, 8)
    np.testing.assert_array_equal(np.asarray(st2.key), np.asarray(ref_st.key))
    np.testing.assert_array_equal(np.asarray(st2.history.buf),
                                  np.asarray(ref_st.history.buf))
    assert int(st2.event) == 16


@pytest.mark.parametrize("engine", ("batch", "sharded"))
@pytest.mark.parametrize("batch_size", (None, 3))
def test_ragged_engines_agree_bitwise(ragged_problem, engine, batch_size):
    """One event a step, batch and sharded on the same ragged problem
    replay the same event stream and masked arithmetic — full state bitwise (the
    multi-shard boundary is the CI serving smoke at 8 fake devices)."""
    base = _cfg(ragged_problem, "batch1", batch_size=batch_size,
                prox_every=4)
    other = base._replace(engine=engine, event_batch=4)
    mesh = _mesh1() if engine == "sharded" else None
    _, st_d = _run(ragged_problem, base, 16)
    _, st_o = _run(ragged_problem, other, 16, mesh)
    np.testing.assert_array_equal(np.asarray(st_d.v), np.asarray(st_o.v))
    np.testing.assert_array_equal(np.asarray(st_d.key),
                                  np.asarray(st_o.key))


# ================================================ ragged vs f64 simulator

SIM_SIZES = (18, 30, 24, 12)
SIM_T, SIM_D, SIM_TAU, SIM_EPOCHS = len(SIM_SIZES), 10, 3, 250


def test_ragged_engine_tracks_trimmed_float64_simulator():
    """The ragged engine's trajectory must track the float64
    event-driven reference run DIRECTLY on the per-task-trimmed ragged
    cohorts — the cross-validation that the masked math implements the
    paper's per-node objective, not an artifact of the padding."""
    xs, ys = _ragged_lists(SIM_SIZES, SIM_D, seed=15)
    sim_prob = SimProblem(xs, ys, "lstsq", "nuclear", 0.1)
    stacked = stack_ragged(xs, ys, "lstsq", "nuclear", 0.1)
    eta = 1.0 / stacked.lipschitz()
    eta_k = amtl_max_step(SIM_TAU, SIM_T)
    sim = simulate_amtl(sim_prob,
                        NetworkModel(delay_offset=0.0, delay_jitter=1.0),
                        num_epochs=SIM_EPOCHS, eta=float(eta),
                        eta_k=float(eta_k), tau=SIM_TAU, seed=0)
    sim_traj = np.asarray(sim.objectives)[SIM_T - 1::SIM_T]

    cfg = AMTLConfig(eta=eta, eta_k=eta_k, tau=SIM_TAU, engine="batch")
    w0 = jnp.zeros((SIM_D, SIM_T), jnp.float32)
    res = amtl_solve(stacked, cfg, w0, jax.random.PRNGKey(0),
                     num_epochs=SIM_EPOCHS)
    objs = np.asarray(res.objectives, np.float64)
    rel = np.abs(objs - sim_traj) / sim_traj
    assert rel.max() < 0.35, rel.max()        # independent transients
    assert rel[100:].max() < 0.05, rel[100:].max()
    assert rel[-1] < 0.02, rel[-1]
    assert objs[-1] < objs[100] < objs[0]
    w_rel = (np.linalg.norm(np.asarray(res.w, np.float64) - sim.w)
             / np.linalg.norm(sim.w))
    assert w_rel < 0.05, w_rel


# ======================================================= serving platform


def _server(problem, cfg, serve_cfg, key=0):
    w0 = jnp.zeros((problem.dim, problem.num_tasks), jnp.float32)
    return AMTLServer(problem, cfg, w0, jax.random.PRNGKey(key), serve_cfg)


def _labeled_batch(problem, k, rng):
    t = rng.integers(0, problem.num_tasks, size=k)
    x = rng.standard_normal((k, problem.dim)).astype(np.float32)
    y = rng.standard_normal(k).astype(np.float32)
    return t, x, y


@pytest.mark.parametrize("engine", ("batch1", "batch"))
def test_labeled_feedback_replays_fold_run_sequence_bitwise(small_problem,
                                                            engine):
    """The acceptance contract: after any mix of labeled and label-free
    feedback, the server state is bitwise the replay — fold the same
    rows at the same chunk boundaries, rebuild, run — over ONE engine
    session against a replayed TaskStore."""
    cfg = _cfg(small_problem, engine)
    per = 4 if engine == "batch" else 1
    server = _server(small_problem, cfg, ServeConfig(chunk_events=2 * per))
    rng = np.random.default_rng(16)
    log = []                               # (rows | None, chunk size)
    for i in range(6):
        if i % 2 == 0:
            t, x, y = _labeled_batch(small_problem, 2 * per, rng)
            assert server.submit_feedback(t, x, y).accepted == 2 * per
            rows = (t, x, y)
        else:
            server.submit_feedback(
                rng.integers(0, small_problem.num_tasks, size=2 * per))
            rows = None
        log.append((rows, server.step()))
    n0 = small_problem.num_tasks * small_problem.xs.shape[1]
    assert server.store_rows == n0 + 3 * 2 * per

    store = TaskStore.from_problem(small_problem)
    prob = small_problem
    eng = make_engine(prob, cfg)
    w0 = jnp.zeros((prob.dim, prob.num_tasks), jnp.float32)
    st = eng.init(w0, jax.random.PRNGKey(0))
    for rows, n in log:
        if rows is not None:
            store.append(*rows)
            prob = store.problem()
            eng = make_engine(prob, cfg)
        if n:
            st = eng.run(st, None, n)
    np.testing.assert_array_equal(np.asarray(server.iterate()),
                                  np.asarray(eng.iterate(st)))
    _assert_states_equal(server._state, st, engine)


def test_label_free_path_never_creates_store(small_problem):
    """Satellite (a) regression: the PR-8 API (no features/labels) must
    stay bitwise PR-8 — same replay, no store, no problem rebuild."""
    cfg = _cfg(small_problem, "batch1")
    server = _server(small_problem, cfg, ServeConfig(chunk_events=4))
    prob_obj = server.problem
    eng_obj = server.engine
    rng = np.random.default_rng(17)
    for _ in range(4):
        server.submit_feedback(
            rng.integers(0, small_problem.num_tasks, size=5))
        server.step()
    assert server._store is None and server.store_rows is None
    assert server.problem is prob_obj and server.engine is eng_obj
    eng = make_engine(small_problem, cfg)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    st = eng.run(eng.init(w0, jax.random.PRNGKey(0)), None,
                 sum(server.chunk_log))
    _assert_states_equal(server._state, st)


def test_submit_feedback_validates_rows(small_problem):
    server = _server(small_problem, _cfg(small_problem, "batch1"),
                     ServeConfig(chunk_events=4))
    with pytest.raises(ValueError, match="given together"):
        server.submit_feedback([0], features=np.zeros((1, small_problem.dim),
                                                      np.float32))
    with pytest.raises(ValueError, match="given together"):
        server.submit_feedback([0], labels=[1.0])
    with pytest.raises(ValueError, match="features must be"):
        server.submit_feedback([0, 1], np.zeros((2, 3), np.float32),
                               [0.0, 1.0])
    dense = _server(small_problem, _cfg(small_problem, "dense"),
                    ServeConfig(chunk_events=4))
    with pytest.raises(ValueError, match="dense"):
        dense.submit_feedback([0], np.zeros((1, small_problem.dim),
                                            np.float32), [0.0])


def test_rejected_items_drop_their_rows(small_problem):
    """Admission caps apply to the item: a rejected item contributes
    neither an event nor a row."""
    server = _server(small_problem, _cfg(small_problem, "batch1"),
                     ServeConfig(chunk_events=4, max_pending_per_task=3))
    rng = np.random.default_rng(18)
    x = rng.standard_normal((10, small_problem.dim)).astype(np.float32)
    y = rng.standard_normal(10).astype(np.float32)
    receipt = server.submit_feedback([0] * 10, x, y)
    assert receipt == (3, 7)
    assert server.stats()["pending_rows"] == 3
    server.step()
    n = small_problem.xs.shape[1]
    assert server._store.row_counts[0] == n + 3
    # the three ACCEPTED rows, in arrival order, right after the
    # adopted cohort (capacity doubled past n, so the tail is padding)
    np.testing.assert_array_equal(
        np.asarray(server._store.problem().xs[0, n:n + 3]), x[:3])


def test_feedback_rows_change_future_predictions(small_problem):
    """Appended rows reshape the gradients the next chunks use: two
    servers fed the same events, one with rows and one without, serve
    different predictions after the fold."""
    cfg = _cfg(small_problem, "batch1")
    a = _server(small_problem, cfg, ServeConfig(chunk_events=4))
    b = _server(small_problem, cfg, ServeConfig(chunk_events=4))
    rng = np.random.default_rng(19)
    t, x, y = _labeled_batch(small_problem, 4, rng)
    # rows big enough to move the lstsq gradients measurably
    a.submit_feedback(t, 5.0 * x, 5.0 * y)
    b.submit_feedback(t)
    a.step()
    b.step()
    q_t, q_x = t[:3], x[:3]
    pa = np.asarray(a.predict(q_t, q_x))
    pb = np.asarray(b.predict(q_t, q_x))
    assert not np.array_equal(pa, pb)


def test_resume_with_store_is_bitwise_invisible(small_problem, tmp_path):
    """Kill a server whose store grew past a capacity doubling; resume
    must restore store + engine state such that identical subsequent
    traffic produces bitwise identical predictions and states."""
    cfg = _cfg(small_problem, "batch1")
    serve_cfg = ServeConfig(chunk_events=4, ckpt_dir=str(tmp_path),
                            keep_last=2)
    a = _server(small_problem, cfg, serve_cfg, key=1)
    b = _server(small_problem, cfg, serve_cfg._replace(ckpt_dir=None), key=1)
    n0 = small_problem.xs.shape[1]
    rng_a, rng_b = (np.random.default_rng(20), np.random.default_rng(20))
    for srv, rng in ((a, rng_a), (b, rng_b)):
        for _ in range(4):
            # 68 rows on one task crosses the 50 -> 100 -> 200 doublings
            t = np.full(17, 0, np.int64)
            x = rng.standard_normal((17, small_problem.dim)).astype(
                np.float32)
            y = rng.standard_normal(17).astype(np.float32)
            srv.submit_feedback(t, x, y)
            while srv.step():
                pass
    assert a._store.capacity == 4 * n0
    a.checkpoint()
    del a
    c = AMTLServer.resume(
        small_problem, cfg,
        jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32),
        jax.random.PRNGKey(1), serve_cfg)
    assert c._store is not None
    assert c._store.capacity == 4 * n0
    np.testing.assert_array_equal(c._store.row_counts, b._store.row_counts)
    # identical post-restart traffic, bitwise identical serving
    rng_c, rng_b2 = (np.random.default_rng(21), np.random.default_rng(21))
    for srv, rng in ((c, rng_c), (b, rng_b2)):
        t, x, y = _labeled_batch(small_problem, 4, rng)
        srv.submit_feedback(t, x, y)
        while srv.step():
            pass
    _assert_states_equal(c._state, b._state)
    q = np.random.default_rng(22).standard_normal(
        (5, small_problem.dim)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(c.predict([0, 1, 2, 3, 4], q)),
                                  np.asarray(b.predict([0, 1, 2, 3, 4], q)))


def test_store_checkpoints_pair_with_engine_records(small_problem, tmp_path):
    """Once labeled rows fold, every checkpoint writes a store record at
    the same step under <ckpt_dir>/store/, rotated with the same
    keep_last; resume reads the paired record."""
    cfg = _cfg(small_problem, "batch1")
    serve_cfg = ServeConfig(chunk_events=4, ckpt_dir=str(tmp_path),
                            checkpoint_every=4, keep_last=2)
    server = _server(small_problem, cfg, serve_cfg)
    rng = np.random.default_rng(23)
    for _ in range(3):
        t, x, y = _labeled_batch(small_problem, 4, rng)
        server.submit_feedback(t, x, y)
        server.step()                      # chunk + auto-checkpoint
    engine_records = sorted(f for f in os.listdir(tmp_path)
                            if f.endswith(".npz"))
    store_records = sorted(os.listdir(tmp_path / "store"))
    assert engine_records == ["step_00000008.npz", "step_00000012.npz"]
    assert store_records == engine_records
