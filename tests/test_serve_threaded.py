"""Concurrent learn-while-serve front-end (PR 8): the background
learner thread, the atomic snapshot flip, and the latency-SLO admission
controller.

Contracts pinned here:

  * NO TORN READS — under N predict threads hammering while the learner
    runs, every observed serving snapshot `(v, event)` is bitwise the
    chunk-boundary `engine.iterate` at that event (reconstructed by
    replaying the server's own chunk log through a fresh engine).
  * DRAIN == COOPERATIVE — with no concurrent submissions,
    `start_learner()` ... `stop_learner(drain=True)` reproduces the
    cooperative `while step(): pass` loop's chunk log and full engine
    state bitwise.
  * REPLAY LAW — even with submissions racing the learner, the final
    state is bitwise ONE `engine.run(init, offs, sum(chunk_log))`.
  * SLO PURITY — the admission controller's decision/chunk-size trace
    is a pure function of the recorded latency sequence.

Plus the learner lifecycle (exceptions surfaced on join, cooperative
`step()` fenced off while the thread owns the chunk loop, checkpoint
cadence preserved on the learner thread).
"""
import os
import re
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import AMTLConfig, make_engine
from repro.serve import (AMTLServer, LatencySLOController, ServeConfig,
                         degraded_budget)


def _cfg(problem, engine="batch1", tau=3, **kw):
    eta = 1.0 / problem.lipschitz()
    if engine == "batch1":              # the batch engine, one event a step
        engine = "batch"
        kw.setdefault("event_batch", 1)
    if engine in ("batch", "sharded"):
        kw.setdefault("event_batch", 4)
        kw.setdefault("prox_every", kw["event_batch"])
    return AMTLConfig(eta=eta, eta_k=0.7, tau=tau, engine=engine, **kw)


def _server(problem, cfg, serve_cfg=ServeConfig(chunk_events=4), key=0):
    w0 = jnp.zeros((problem.dim, problem.num_tasks), jnp.float32)
    return AMTLServer(problem, cfg, w0, jax.random.PRNGKey(key), serve_cfg)


def _requests(problem, n, seed=0):
    rng = np.random.default_rng(seed)
    t = rng.integers(0, problem.num_tasks, size=n)
    x = rng.standard_normal((n, problem.dim)).astype(np.float32)
    return t, x


def _boundary_iterates(problem, cfg, chunk_log):
    """event -> iterate bytes at every chunk boundary of `chunk_log`,
    replayed incrementally (the PR-4 composition contract makes the
    incremental replay bitwise the one-shot run)."""
    eng = make_engine(problem, cfg)
    w0 = jnp.zeros((problem.dim, problem.num_tasks), jnp.float32)
    state = eng.init(w0, jax.random.PRNGKey(0))
    out = {0: np.asarray(eng.iterate(state)).tobytes()}
    event = 0
    for n in chunk_log:
        state = eng.run(state, None, n)
        event += n
        out[event] = np.asarray(eng.iterate(state)).tobytes()
    return out


# --------------------------------------------------------- torn-read stress
def test_no_torn_reads_under_concurrent_predict_load(small_problem):
    """4 predict threads hammer while the learner absorbs a feedback
    stream: every snapshot any thread ever observes must be bitwise a
    chunk-boundary iterate of the server's own chunk log."""
    cfg = _cfg(small_problem, "batch1")
    server = _server(small_problem, cfg, ServeConfig(chunk_events=4))
    t, x = _requests(small_problem, 8, seed=1)
    observed = [[] for _ in range(4)]
    stop = threading.Event()

    def hammer(slot):
        while not stop.is_set():
            snap = server.serving()
            server.predict(t, x)
            observed[slot].append(snap)

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(4)]
    server.start_learner()
    for th in threads:
        th.start()
    rng = np.random.default_rng(7)
    for _ in range(25):
        server.submit_feedback(rng.integers(0, small_problem.num_tasks,
                                            size=rng.integers(1, 6)))
    server.stop_learner(drain=True)
    stop.set()
    for th in threads:
        th.join()

    assert sum(server.chunk_log) > 0
    boundaries = _boundary_iterates(small_problem, cfg, server.chunk_log)
    seen_events = set()
    for snaps in observed:
        assert snaps, "every predict thread observed at least one snapshot"
        for snap in snaps:
            assert snap.event in boundaries, \
                f"served event {snap.event} is not a chunk boundary"
            assert np.asarray(snap.v).tobytes() == boundaries[snap.event], \
                f"torn read: snapshot at event {snap.event} is not the " \
                "committed boundary iterate"
            seen_events.add(snap.event)
    # the final committed snapshot is the last boundary
    final = server.serving()
    assert final.event == sum(server.chunk_log)
    assert np.asarray(final.v).tobytes() == boundaries[final.event]


def test_threaded_final_state_replays_chunk_log(small_problem):
    """Submissions racing the learner: whatever chunk sizes it coalesced,
    the final state is bitwise ONE plain run over their sum."""
    cfg = _cfg(small_problem, "batch")
    server = _server(small_problem, cfg, ServeConfig(chunk_events=8))
    server.start_learner()
    rng = np.random.default_rng(0)
    for _ in range(20):
        server.submit_feedback(rng.integers(0, small_problem.num_tasks,
                                            size=rng.integers(1, 7)))
    server.stop_learner(drain=True)
    assert sum(server.chunk_log) > 0
    eng = make_engine(small_problem, cfg)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    state = eng.run(eng.init(w0, jax.random.PRNGKey(0)), None,
                    sum(server.chunk_log))
    for la, lb in zip(jax.tree.leaves(server._state),
                      jax.tree.leaves(state), strict=True):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ------------------------------------------------------ drain == cooperative
@pytest.mark.parametrize("engine", ("batch1", "batch"))
def test_drain_then_join_equals_cooperative_loop_bitwise(small_problem,
                                                         engine):
    """Same queued feedback, no concurrent submissions: the drained
    learner's chunk log and full state are bitwise the cooperative
    step() loop's."""
    cfg = _cfg(small_problem, engine)
    fb = [i % small_problem.num_tasks for i in range(13)]
    a = _server(small_problem, cfg, ServeConfig(chunk_events=8,
                                                task_chunk_quota=3))
    b = _server(small_problem, cfg, ServeConfig(chunk_events=8,
                                                task_chunk_quota=3))
    a.submit_feedback(fb)
    b.submit_feedback(fb)
    a.start_learner()
    learned = a.stop_learner(drain=True)
    while b.step():
        pass
    assert learned == sum(a.chunk_log)
    assert a.chunk_log == b.chunk_log
    assert a.pending_feedback == b.pending_feedback
    for la, lb in zip(jax.tree.leaves(a._state),
                      jax.tree.leaves(b._state), strict=True):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=engine)
    np.testing.assert_array_equal(np.asarray(a.iterate()),
                                  np.asarray(b.iterate()))


def test_threaded_then_resume_matches_cooperative(small_problem, tmp_path):
    """Threaded phase -> drain -> checkpoint -> crash -> resume: the
    resumed server serves bitwise the cooperative reference."""
    cfg = _cfg(small_problem, "batch1")
    sc = ServeConfig(chunk_events=4, ckpt_dir=str(tmp_path), keep_last=2)
    fb = [i % small_problem.num_tasks for i in range(9)]
    a = _server(small_problem, cfg, sc, key=2)
    ref = _server(small_problem, cfg, sc._replace(ckpt_dir=None), key=2)
    a.submit_feedback(fb)
    ref.submit_feedback(fb)
    a.start_learner()
    a.stop_learner(drain=True)
    while ref.step():
        pass
    a.checkpoint()
    del a
    c = AMTLServer.resume(
        small_problem, cfg,
        jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32),
        jax.random.PRNGKey(2), sc)
    assert c.event_count == ref.event_count
    t, x = _requests(small_problem, 6, seed=3)
    np.testing.assert_array_equal(np.asarray(c.predict(t, x)),
                                  np.asarray(ref.predict(t, x)))
    # and learning continues bitwise after the restart, on the learner
    c.submit_feedback(fb)
    ref.submit_feedback(fb)
    c.start_learner()
    c.stop_learner(drain=True)
    while ref.step():
        pass
    np.testing.assert_array_equal(np.asarray(c.iterate()),
                                  np.asarray(ref.iterate()))


# ----------------------------------------------------------- learner lifecycle
def test_cooperative_step_is_fenced_while_learner_runs(small_problem):
    server = _server(small_problem, _cfg(small_problem, "batch1"))
    server.start_learner()
    with pytest.raises(RuntimeError, match="owns the chunk loop"):
        server.step()
    with pytest.raises(RuntimeError, match="already running"):
        server.start_learner()
    server.stop_learner()
    assert server.step() == 0          # cooperative again after stop
    assert server.stop_learner() == 0  # idempotent


def test_learner_exception_surfaces_on_stop(small_problem):
    server = _server(small_problem, _cfg(small_problem, "batch1"))

    def boom(state, offs, n):
        raise RuntimeError("engine exploded")

    server.engine = server.engine._replace(run=boom)
    before = server.serving()
    server.submit_feedback([0, 1, 2])
    server.start_learner()
    with pytest.raises(RuntimeError, match="engine exploded"):
        server.stop_learner(drain=True, timeout=30)
    # a dead learner never corrupts serving: the committed snapshot and
    # state are untouched and the request path still answers
    assert server.serving() is before
    assert not server.learner_running
    t, x = _requests(small_problem, 3)
    assert np.asarray(server.predict(t, x)).shape == (3,)


def test_frozen_server_refuses_learner(small_problem):
    server = _server(small_problem, _cfg(small_problem, "batch1"),
                     ServeConfig(chunk_events=4, learning=False))
    with pytest.raises(RuntimeError, match="frozen"):
        server.start_learner()


def test_checkpoint_cadence_preserved_on_learner_thread(small_problem,
                                                        tmp_path):
    """Auto-checkpoints keep landing (and rotating) when the chunk loop
    runs on the background thread."""
    sc = ServeConfig(chunk_events=4, ckpt_dir=str(tmp_path),
                     checkpoint_every=4, keep_last=2)
    server = _server(small_problem, _cfg(small_problem, "batch1"), sc)
    server.submit_feedback([i % small_problem.num_tasks for i in range(16)])
    server.start_learner()
    server.stop_learner(drain=True)
    names = sorted(os.listdir(tmp_path))
    assert names == ["step_00000012.npz", "step_00000016.npz"]
    assert all(re.fullmatch(r"step_\d{8}\.npz", f) for f in names)


def test_serve_leaves_chunks_to_running_learner(small_problem):
    """serve() never steps cooperatively while the learner owns the
    chunk loop — it reports 0 events and lets the thread absorb."""
    server = _server(small_problem, _cfg(small_problem, "batch1"))
    t, x = _requests(small_problem, 4)
    server.start_learner()
    _, receipt, ran = server.serve(t, x, feedback_task_ids=[0, 1])
    assert receipt.accepted == 2 and ran == 0
    server.stop_learner(drain=True)
    assert sum(server.chunk_log) == 2


# ------------------------------------------------------------ SLO admission
def _trace(controller):
    return [(d.sample, d.level_before, d.level, d.chunk_events)
            for d in controller.decisions]


def test_slo_trace_is_pure_function_of_latency_sequence():
    """Identical latency sequences -> identical decision traces, level
    transitions follow the tumbling-window p95 law exactly."""
    rng = np.random.default_rng(5)
    lat = list(rng.uniform(0.1, 2.0, size=40)) \
        + list(rng.uniform(30.0, 60.0, size=60)) \
        + list(rng.uniform(0.1, 2.0, size=60))
    a = LatencySLOController(10.0, 32, 4, window=20)
    b = LatencySLOController(10.0, 32, 4, window=20)
    for v in lat:
        a.record(v)
    for v in lat:
        b.record(v)
    assert _trace(a) == _trace(b)
    assert a.violations == b.violations == sum(v > 10.0 for v in lat)
    # windows: [fast] healthy, [fast20+slow..] then slow -> shrink, then
    # fast windows restore; every decision obeys the one-step law
    level = 0
    for d in a.decisions:
        assert d.level_before == level
        want = min(level + 1, a._max_level) if d.p95_ms > 10.0 \
            else max(level - 1, 0)
        assert d.level == want
        assert d.chunk_events == degraded_budget(32, 4, d.level)
        level = d.level
    assert any(d.level > d.level_before for d in a.decisions)   # degraded
    assert a.level == 0                                         # recovered


def test_degraded_budget_halves_floored_to_events_per_step():
    assert [degraded_budget(32, 4, L) for L in range(5)] == \
        [32, 16, 8, 4, 4]
    assert degraded_budget(8, 8, 3) == 8          # never below one step
    c = LatencySLOController(1.0, 32, 4, window=2)
    for _ in range(40):                            # violate forever
        c.record(100.0)
    assert c.level == c._max_level == 3
    assert c.chunk_events == 4
    c.record(0.001)
    c.record(0.001)                                # one healthy window
    assert c.level == 2 and c.chunk_events == 8    # restores one level


def test_server_degrades_chunk_budget_under_slo_violation(small_problem):
    """An impossible SLO (every predict violates) shrinks the coalesced
    chunk sizes; the decisions land in stats()["slo"]."""
    sc = ServeConfig(chunk_events=8, slo_ms=1e-6, slo_window=4)
    server = _server(small_problem, _cfg(small_problem, "batch1"), sc)
    t, x = _requests(small_problem, 4)
    for _ in range(12):                 # 3 windows, every sample violates
        server.predict(t, x)
    slo = server.stats()["slo"]
    assert slo["level"] == 3 and slo["chunk_events"] == 1
    assert slo["violations"] == 12
    assert [d["level"] for d in slo["decisions"]] == [1, 2, 3]
    server.submit_feedback([0, 1, 2, 3, 4])
    assert server.step() == 1           # degraded budget, not the base 8
    assert server.chunk_log == [1]
    # a healthy SLO would have coalesced the full budget
    relaxed = _server(small_problem, _cfg(small_problem, "batch1"),
                      ServeConfig(chunk_events=8, slo_ms=1e6, slo_window=4))
    relaxed.submit_feedback([0, 1, 2, 3, 4])
    assert relaxed.step() == 5


def test_slo_shed_rejects_feedback_while_degraded(small_problem):
    sc = ServeConfig(chunk_events=8, slo_ms=1e-6, slo_window=2,
                     slo_shed=True)
    server = _server(small_problem, _cfg(small_problem, "batch1"), sc)
    assert server.submit_feedback([0, 1]).accepted == 2   # healthy: flows
    t, x = _requests(small_problem, 4)
    server.predict(t, x)
    server.predict(t, x)                                  # window closes
    assert server.stats()["slo"]["level"] == 1
    receipt = server.submit_feedback([0, 1, 2])
    assert receipt == (0, 3)
    assert server.stats()["shed_feedback"] == 3
    assert server.pending_feedback == 2                   # earlier items kept


def test_slo_config_validates(small_problem):
    with pytest.raises(ValueError, match="slo_shed requires slo_ms"):
        _server(small_problem, _cfg(small_problem, "batch1"),
                ServeConfig(chunk_events=4, slo_shed=True))
    with pytest.raises(ValueError, match="slo_ms must be > 0"):
        _server(small_problem, _cfg(small_problem, "batch1"),
                ServeConfig(chunk_events=4, slo_ms=0.0))
    with pytest.raises(ValueError, match="slo_window must be >= 1"):
        _server(small_problem, _cfg(small_problem, "batch1"),
                ServeConfig(chunk_events=4, slo_ms=5.0, slo_window=0))
