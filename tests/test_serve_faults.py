"""Fault-tolerance contracts of the learn-while-serve platform under
DETERMINISTIC fault injection (`repro.serve.faults.FaultPlan`).

Every recovery path is asserted bitwise, exactly like the no-fault
contracts in tests/test_serve.py:

  * supervised learner: a scripted crash auto-restarts under backoff
    and the final state is bitwise ONE `engine.run` replay of the
    surviving chunk log; an exhausted restart budget trips the circuit
    breaker (frozen serving: predictions flow, feedback rejected with
    reason "breaker", terminal exception surfaces once on stop);
  * non-finite guard: NaN feedback is rejected at admission and the
    session is bitwise the one where the poisoned rows were never
    submitted; a poisoned ITERATE is quarantined — state, snapshot,
    chunk log, and the boundary's folded rows all roll back bitwise;
  * resume: a corrupted newest checkpoint record falls back one
    interval (every engine case, sharded under a degenerate 1-device
    mesh) and subsequent predictions are bitwise the uninterrupted
    server's at that boundary; a crash in the store/engine checkpoint
    split window leaves a resumable directory;
  * `BackgroundLearner.join` timeout leaves the learner joinable and
    surfaces a captured exception exactly once (regression).
"""
import os
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import checkpoint
from repro.checkpoint import CheckpointCorruptError
from repro.core import AMTLConfig, make_engine
from repro.launch.mesh import make_task_mesh
from repro.serve import (AMTLServer, BackgroundLearner, FaultPlan,
                         InjectedFault, ServeConfig, corrupt_leaf,
                         truncate_record)

ENGINES = ("dense", "batch1", "batch", "sharded")
RAGGED_ENGINES = ("batch1", "batch", "sharded")


def _cfg(problem, engine, tau=3, **kw):
    eta = 1.0 / problem.lipschitz()
    if engine == "batch1":              # the batch engine, one event a step
        engine = "batch"
        kw.setdefault("event_batch", 1)
    if engine in ("batch", "sharded"):
        kw.setdefault("event_batch", 4)
        kw.setdefault("prox_every", kw["event_batch"])
    return AMTLConfig(eta=eta, eta_k=0.7, tau=tau, engine=engine, **kw)


@pytest.fixture(scope="module")
def mesh1():
    return make_task_mesh(1)


def _server(problem, cfg, mesh1, serve_cfg=ServeConfig(chunk_events=4),
            key=0, fault_plan=None):
    w0 = jnp.zeros((problem.dim, problem.num_tasks), jnp.float32)
    mesh = mesh1 if cfg.engine == "sharded" else None
    return AMTLServer(problem, cfg, w0, jax.random.PRNGKey(key), serve_cfg,
                      mesh=mesh, fault_plan=fault_plan)


def _rows(problem, k, seed):
    rng = np.random.default_rng(seed)
    t = rng.integers(0, problem.num_tasks, size=k)
    x = (rng.standard_normal((k, problem.dim))
         / np.sqrt(problem.dim)).astype(np.float32)
    y = rng.standard_normal(k).astype(np.float32)
    return t, x, y


def _wait(predicate, timeout_s=60.0):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return False


# --------------------------------------------------- supervised learner --
def test_supervised_restart_replays_surviving_chunk_log(small_problem,
                                                        mesh1):
    """A scripted mid-stream crash loses exactly the crashed chunk's
    coalesced events (the documented at-most-once window), the
    supervisor restarts the learner, and the final state is bitwise ONE
    engine.run replay of the surviving chunk log."""
    cfg = _cfg(small_problem, "batch")
    serve_cfg = ServeConfig(chunk_events=4, restart_limit=2,
                            restart_backoff_s=0.01)
    plan = FaultPlan(crash_on_chunks={1})
    server = _server(small_problem, cfg, mesh1, serve_cfg, fault_plan=plan)
    server.start_learner()
    for i in range(4):
        server.submit_feedback(np.arange(4) % small_problem.num_tasks)
    assert _wait(lambda: server.stats()["health"]["learner_restarts"] >= 1
                 and len(server.chunk_log) >= 3)
    learned = server.stop_learner(drain=True, timeout=60)
    health = server.stats()["health"]
    assert health["learner_restarts"] == 1
    assert health["learner_crashes"] == 1
    assert len(health["crash_log"]) == 1
    assert "InjectedFault" in health["crash_log"][0]
    assert len(health["recovery_ms"]) == 1 and health["recovery_ms"][0] > 0
    # 16 events submitted, chunk 1 (4 events) lost to the crash window
    assert server.chunk_log == [4, 4, 4]
    assert learned == sum(server.chunk_log)
    eng = server.engine
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks),
                   jnp.float32)
    state = eng.run(eng.init(w0, jax.random.PRNGKey(0)), None,
                    sum(server.chunk_log))
    np.testing.assert_array_equal(np.asarray(server.iterate()),
                                  np.asarray(eng.iterate(state)))


def test_supervised_no_faults_is_bitwise_plain_learner(small_problem,
                                                       mesh1):
    """restart_limit set but nothing crashing: the supervised drain is
    bitwise the cooperative loop — supervision is pure scaffolding
    until a crash happens."""
    cfg = _cfg(small_problem, "batch1")
    fb = [np.arange(4) % small_problem.num_tasks for _ in range(3)]

    sup = _server(small_problem, cfg, mesh1,
                  ServeConfig(chunk_events=4, restart_limit=3))
    sup.start_learner()
    for t in fb:
        sup.submit_feedback(t)
    sup.stop_learner(drain=True, timeout=60)

    coop = _server(small_problem, cfg, mesh1, ServeConfig(chunk_events=4))
    for t in fb:
        coop.submit_feedback(t)
    while coop.step():
        pass

    assert sup.chunk_log == coop.chunk_log
    np.testing.assert_array_equal(np.asarray(sup.iterate()),
                                  np.asarray(coop.iterate()))
    health = sup.stats()["health"]
    assert health["learner_crashes"] == 0
    assert not health["breaker_tripped"]


def test_breaker_latches_frozen_serving(small_problem, mesh1):
    """Crash budget exhausted -> breaker: predictions keep flowing off
    the last committed snapshot, feedback is rejected with reason
    "breaker", cooperative steps are no-ops, the terminal exception
    surfaces exactly once on stop, and the learner cannot be
    restarted."""
    cfg = _cfg(small_problem, "batch")
    serve_cfg = ServeConfig(chunk_events=4, restart_limit=1,
                            restart_backoff_s=0.01)
    plan = FaultPlan(crash_on_chunks=set(range(64)))
    server = _server(small_problem, cfg, mesh1, serve_cfg, fault_plan=plan)
    before = server.serving()
    server.start_learner()
    server.submit_feedback([0, 1, 2, 3])

    def _feed_until_tripped():
        if not server.breaker_tripped:
            server.submit_feedback([0, 1, 2, 3])
        return server.breaker_tripped
    assert _wait(_feed_until_tripped)
    assert not server.learner_running
    # frozen serving: the request path still answers off the snapshot
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3, small_problem.dim)).astype(np.float32)
    preds = server.predict([0, 1, 2], x)
    assert preds.shape == (3,)
    assert server.serving() is before  # nothing ever committed
    receipt = server.submit_feedback([0, 1])
    assert receipt == (0, 2)
    assert receipt.reason == "breaker"
    assert server.step() == 0
    health = server.stats()["health"]
    assert health["breaker_tripped"]
    assert health["breaker_rejected"] >= 2
    assert health["learner_restarts"] == 1     # the budget, spent
    assert health["learner_crashes"] == 2
    with pytest.raises(InjectedFault):
        server.stop_learner(drain=False, timeout=60)
    # surfaced exactly once: a second stop is clean
    assert server.stop_learner(drain=False, timeout=60) == 0
    with pytest.raises(RuntimeError, match="circuit breaker"):
        server.start_learner()


# --------------------------------------------------- non-finite guard ----
def test_nonfinite_feedback_rejected_at_admission(small_problem, mesh1):
    """Rows with non-finite features or labels die at admission with
    their events; the engine and store never see them."""
    cfg = _cfg(small_problem, "batch")
    server = _server(small_problem, cfg, mesh1)
    t, x, y = _rows(small_problem, 6, seed=1)
    x[2, 5] = np.inf
    y[4] = np.nan
    receipt = server.submit_feedback(t, x, y)
    assert receipt == (4, 2)
    assert receipt.reason == "nonfinite"
    assert server.stats()["health"]["nonfinite_feedback"] == 2
    assert server.pending_feedback == 4
    server.step()
    assert np.isfinite(np.asarray(server.iterate())).all()


def test_nan_quarantine_is_bitwise_never_submitted(small_problem, mesh1):
    """The satellite contract: a session whose poisoned rows were
    rejected at admission has chunk log, final state, AND store bitwise
    equal to the same session where those rows were never submitted.

    The poison arrives via the fault plan (scripted NaN injection into
    chosen feedback rows), so both sessions issue IDENTICAL
    submit_feedback calls — the admission guard alone must produce the
    never-submitted outcome."""
    cfg = _cfg(small_problem, "batch")
    t, x, y = _rows(small_problem, 12, seed=2)

    plan = FaultPlan(nan_feedback=[(0, 3), (1, 0)])
    poisoned = _server(small_problem, cfg, mesh1, fault_plan=plan)
    clean = _server(small_problem, cfg, mesh1)
    for lo in (0, 4, 8):  # 3 labeled calls; calls 0 and 1 get a NaN row
        rp = poisoned.submit_feedback(t[lo:lo + 4], x[lo:lo + 4],
                                      y[lo:lo + 4])
        keep = np.ones(4, bool)
        if lo == 0:
            keep[3] = False
        if lo == 4:
            keep[0] = False
        rc = clean.submit_feedback(t[lo:lo + 4][keep], x[lo:lo + 4][keep],
                                   y[lo:lo + 4][keep])
        assert rp.accepted == rc.accepted
    while poisoned.step():
        pass
    while clean.step():
        pass
    assert poisoned.chunk_log == clean.chunk_log
    np.testing.assert_array_equal(np.asarray(poisoned.iterate()),
                                  np.asarray(clean.iterate()))
    assert poisoned.store_rows == clean.store_rows
    sp, sc = poisoned._store.state(), clean._store.state()
    np.testing.assert_array_equal(sp.xs, sc.xs)
    np.testing.assert_array_equal(sp.ys, sc.ys)
    np.testing.assert_array_equal(sp.row_counts, sc.row_counts)
    assert poisoned.stats()["health"]["nonfinite_feedback"] == 2


def test_poisoned_iterate_quarantined_with_rollback(small_problem, mesh1):
    """A non-finite ITERATE (scripted past admission, modelling in-kernel
    divergence) never reaches the snapshot: the chunk is quarantined,
    the boundary's folded rows roll back out of the store bitwise —
    across the capacity doubling the fold caused — and the session
    continues from the last committed state as if the boundary never
    ran."""
    cfg = _cfg(small_problem, "batch")
    plan = FaultPlan(poison_iterate_on_chunks={1})
    server = _server(small_problem, cfg, mesh1, fault_plan=plan)

    t0, x0, y0 = _rows(small_problem, 4, seed=3)
    server.submit_feedback(t0, x0, y0)
    assert server.step() == 4               # chunk 0 commits
    committed = server.serving()
    store_snapshot = server._store.state()
    cap_before = server._store.capacity
    problem_before, engine_before = server.problem, server.engine
    assert len(server.chunk_log) == 1

    # enough rows on one task to force a capacity doubling at the fold
    k = server._store.capacity + 2
    t1 = np.zeros(k, np.int64)
    rng = np.random.default_rng(4)
    x1 = (rng.standard_normal((k, small_problem.dim))
          / np.sqrt(small_problem.dim)).astype(np.float32)
    y1 = rng.standard_normal(k).astype(np.float32)
    server.submit_feedback(t1, x1, y1)
    consumed = server.step()                # chunk 1: poisoned
    assert consumed > 0                     # the boundary consumed events
    assert server.chunk_log == [4]          # ...but nothing committed
    assert server.serving() is committed    # snapshot untouched
    assert np.isfinite(np.asarray(server.iterate())).all()
    # the fold unwound bitwise: buffers, counts, capacity, and the very
    # problem/engine objects (jit cache keys) of the pre-fold session
    assert server._store.capacity == cap_before
    after = server._store.state()
    np.testing.assert_array_equal(after.xs, store_snapshot.xs)
    np.testing.assert_array_equal(after.ys, store_snapshot.ys)
    np.testing.assert_array_equal(after.row_counts,
                                  store_snapshot.row_counts)
    assert server.problem is problem_before
    assert server.engine is engine_before
    health = server.stats()["health"]
    assert health["nonfinite_chunks"] == 1
    assert health["quarantined_feedback"] == consumed
    assert health["quarantine_log"] == [{0: consumed}]

    # the session continues cleanly from the committed state
    t2, x2, y2 = _rows(small_problem, 4, seed=5)
    server.submit_feedback(t2, x2, y2)
    assert server.step() == 4
    assert server.chunk_log == [4, 4]
    eng = server.engine
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks),
                   jnp.float32)
    # bitwise ONE replay of the committed chunk log: fold -> run at the
    # same boundaries, with the quarantined boundary absent entirely
    replay = AMTLServer(small_problem, cfg, jnp.zeros_like(w0),
                        jax.random.PRNGKey(0),
                        ServeConfig(chunk_events=4))
    replay.submit_feedback(t0, x0, y0)
    replay.step()
    replay.submit_feedback(t2, x2, y2)
    replay.step()
    np.testing.assert_array_equal(np.asarray(server.iterate()),
                                  np.asarray(replay.iterate()))


def test_poisoned_chunk_never_reaches_checkpoint(small_problem, mesh1,
                                                 tmp_path):
    """checkpoint_every cadence + a poisoned chunk: the quarantined
    boundary writes nothing, and every record on disk verifies and
    holds finite data."""
    cfg = _cfg(small_problem, "batch")
    serve_cfg = ServeConfig(chunk_events=4, ckpt_dir=str(tmp_path),
                            checkpoint_every=4)
    plan = FaultPlan(poison_iterate_on_chunks={1})
    server = _server(small_problem, cfg, mesh1, serve_cfg, fault_plan=plan)
    for seed in range(3):
        t, x, y = _rows(small_problem, 4, seed=seed)
        server.submit_feedback(t, x, y)
        server.step()
    assert server.stats()["health"]["nonfinite_chunks"] == 1
    steps = checkpoint.record_steps(str(tmp_path))
    assert steps == [8, 4]  # chunk 1's would-be step 8 was the 2nd commit
    for s in steps:
        state = checkpoint.restore(str(tmp_path), s,
                                   like=server.engine.init(
                                       jnp.zeros((small_problem.dim,
                                                  small_problem.num_tasks),
                                                 jnp.float32),
                                       jax.random.PRNGKey(0)))
        for leaf in jax.tree_util.tree_leaves(state):
            arr = np.asarray(leaf)
            if np.issubdtype(arr.dtype, np.floating):
                assert np.isfinite(arr).all()


# ------------------------------------------------------- resume paths ----
@pytest.mark.parametrize("engine", ENGINES)
def test_corrupt_newest_checkpoint_falls_back_one_interval(
        small_problem, mesh1, engine, tmp_path):
    """The satellite contract, every engine case (sharded under a
    degenerate 1-device mesh): bit rot on the newest record costs one
    checkpoint interval — resume lands on the previous boundary and
    subsequent predictions are bitwise an uninterrupted server's at
    that same boundary."""
    cfg = _cfg(small_problem, engine)
    serve_cfg = ServeConfig(chunk_events=4, ckpt_dir=str(tmp_path))
    server = _server(small_problem, cfg, mesh1, serve_cfg)
    server.submit_feedback([0, 1, 2, 3])
    server.step()
    server.checkpoint()                       # step 4 — the fallback
    server.submit_feedback([1, 2, 3, 4])
    server.step()
    server.checkpoint()                       # step 8 — about to rot
    corrupt_leaf(os.path.join(str(tmp_path), "step_00000008.npz"))

    resumed = AMTLServer.resume(
        small_problem, cfg,
        jnp.zeros((small_problem.dim, small_problem.num_tasks),
                  jnp.float32),
        jax.random.PRNGKey(0), serve_cfg,
        mesh=mesh1 if engine == "sharded" else None)
    assert resumed.event_count == 4

    # uninterrupted reference at the same boundary
    reference = _server(small_problem, cfg, mesh1,
                        ServeConfig(chunk_events=4))
    reference.submit_feedback([0, 1, 2, 3])
    reference.step()
    np.testing.assert_array_equal(np.asarray(resumed.iterate()),
                                  np.asarray(reference.iterate()))
    t, x = (np.arange(6) % small_problem.num_tasks,
            np.random.default_rng(9).standard_normal(
                (6, small_problem.dim)).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(resumed.predict(t, x)),
                                  np.asarray(reference.predict(t, x)))
    # and the resumed session keeps advancing bitwise
    resumed.submit_feedback([0, 1, 2, 3])
    reference.submit_feedback([0, 1, 2, 3])
    assert resumed.step() == reference.step() == 4
    np.testing.assert_array_equal(np.asarray(resumed.iterate()),
                                  np.asarray(reference.iterate()))


def test_resume_refuses_all_corrupt_directory(small_problem, mesh1,
                                              tmp_path):
    """Every engine record damaged: resume raises CheckpointCorruptError
    instead of silently restarting the session from scratch."""
    cfg = _cfg(small_problem, "batch1")
    serve_cfg = ServeConfig(chunk_events=4, ckpt_dir=str(tmp_path))
    server = _server(small_problem, cfg, mesh1, serve_cfg)
    server.submit_feedback([0, 1, 2, 3])
    server.step()
    server.checkpoint()
    truncate_record(os.path.join(str(tmp_path), "step_00000004.npz"))
    with pytest.raises(CheckpointCorruptError):
        AMTLServer.resume(
            small_problem, cfg,
            jnp.zeros((small_problem.dim, small_problem.num_tasks),
                      jnp.float32),
            jax.random.PRNGKey(0), serve_cfg)


@pytest.mark.parametrize("engine", RAGGED_ENGINES)
def test_resume_drops_to_older_store_record_on_corruption(
        small_problem, mesh1, engine, tmp_path):
    """Satellite bugfix: a corrupt store record (torn zip) used to kill
    resume outright (only FileNotFoundError was caught).  Now the store
    scan drops to the newest remaining valid record."""
    cfg = _cfg(small_problem, engine)
    serve_cfg = ServeConfig(chunk_events=4, ckpt_dir=str(tmp_path))
    server = _server(small_problem, cfg, mesh1, serve_cfg)
    t, x, y = _rows(small_problem, 4, seed=6)
    server.submit_feedback(t, x, y)
    server.step()
    server.checkpoint()                         # store + engine at 4
    rows_at_4 = server.store_rows
    t, x, y = _rows(small_problem, 4, seed=7)
    server.submit_feedback(t, x, y)
    server.step()
    server.checkpoint()                         # store + engine at 8
    truncate_record(os.path.join(str(tmp_path), "store",
                                 "step_00000008.npz"))
    resumed = AMTLServer.resume(
        small_problem, cfg,
        jnp.zeros((small_problem.dim, small_problem.num_tasks),
                  jnp.float32),
        jax.random.PRNGKey(0), serve_cfg,
        mesh=mesh1 if engine == "sharded" else None)
    # engine record at 8 is intact; the store dropped one interval
    assert resumed.event_count == 8
    assert resumed.store_rows == rows_at_4


def test_checkpoint_crash_split_window_resumes(small_problem, mesh1,
                                               tmp_path):
    """A scripted crash between the store write and the engine write
    (the documented split window) leaves one unpaired newer store
    record.  Resume prefers the record PAIRED with the surviving engine
    step; if that pairing is gone too, it drops to the unpaired newer
    record — a superset of the paired rows the engine state never saw
    (appends only affect future chunks)."""
    cfg = _cfg(small_problem, "batch")
    serve_cfg = ServeConfig(chunk_events=4, ckpt_dir=str(tmp_path))
    plan = FaultPlan(fail_checkpoint_calls={1})
    server = _server(small_problem, cfg, mesh1, serve_cfg, fault_plan=plan)
    t, x, y = _rows(small_problem, 4, seed=8)
    server.submit_feedback(t, x, y)
    server.step()
    server.checkpoint()                       # call 0: store 4 + engine 4
    rows_after_first_fold = server.store_rows
    t, x, y = _rows(small_problem, 4, seed=9)
    server.submit_feedback(t, x, y)
    server.step()
    rows_after_second_fold = server.store_rows
    with pytest.raises(InjectedFault):
        server.checkpoint()                   # call 1: store 8, no engine
    assert checkpoint.record_steps(str(tmp_path)) == [4]
    assert checkpoint.record_steps(
        os.path.join(str(tmp_path), "store")) == [8, 4]
    v0 = jnp.zeros((small_problem.dim, small_problem.num_tasks),
                   jnp.float32)
    resumed = AMTLServer.resume(small_problem, cfg, v0,
                                jax.random.PRNGKey(0), serve_cfg)
    assert resumed.event_count == 4
    assert resumed.store_rows == rows_after_first_fold
    # paired record gone too: the unpaired newer record still resumes
    os.remove(os.path.join(str(tmp_path), "store", "step_00000004.npz"))
    resumed = AMTLServer.resume(small_problem, cfg, v0,
                                jax.random.PRNGKey(0), serve_cfg)
    assert resumed.event_count == 4
    assert resumed.store_rows == rows_after_second_fold


# ------------------------------------------ learner join regression ------
def test_learner_join_timeout_retries_and_surfaces_once():
    """Satellite bugfix: a timed-out join used to leave the learner
    half-stopped; now a later stop/join retries cleanly and a captured
    exception surfaces exactly once, never lost to the timeout path."""
    import threading

    gate = threading.Event()

    class _FakeServer:
        def _step_once(self):
            gate.wait()
            raise RuntimeError("boom after the gate")

    learner = BackgroundLearner(_FakeServer())
    learner.start()
    with pytest.raises(TimeoutError, match="retry stop"):
        learner.stop(drain=False, timeout=0.05)
    assert learner.running                      # still joinable
    gate.set()
    with pytest.raises(RuntimeError, match="boom after the gate"):
        learner.stop(drain=False, timeout=60)
    # surfaced exactly once: subsequent stops are clean no-ops
    assert learner.stop(drain=False, timeout=60) == 0
    assert not learner.running
    # and the learner is restartable after the failure was surfaced
    gate.clear()

    class _CleanServer:
        def _step_once(self):
            return 0
    learner2 = BackgroundLearner(_CleanServer())
    learner2.start()
    assert learner2.stop(drain=False, timeout=60) == 0


def test_fault_plan_counters_are_deterministic(small_problem, mesh1):
    """Two identical plans against identical traffic fire identically —
    the whole point of scripting faults instead of timing them."""
    cfg = _cfg(small_problem, "batch")
    logs = []
    for _ in range(2):
        plan = FaultPlan(poison_iterate_on_chunks={0})
        server = _server(small_problem, cfg, mesh1, fault_plan=plan)
        server.submit_feedback([0, 1, 2, 3])
        server.step()
        server.submit_feedback([0, 1, 2, 3])
        server.step()
        logs.append((list(server.chunk_log),
                     server.stats()["health"]["quarantine_log"]))
    assert logs[0] == logs[1]
    assert logs[0][0] == [4]                  # chunk 0 quarantined


def test_serve_config_validates_restart_knobs(small_problem, mesh1):
    with pytest.raises(ValueError, match="restart_limit"):
        _server(small_problem, _cfg(small_problem, "batch"), mesh1,
                ServeConfig(chunk_events=4, restart_limit=-1))
    with pytest.raises(ValueError, match="restart_backoff_s"):
        _server(small_problem, _cfg(small_problem, "batch"), mesh1,
                ServeConfig(chunk_events=4, restart_backoff_s=-0.5))
