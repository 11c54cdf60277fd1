"""Learning-while-serving platform contracts (`repro.serve.AMTLServer`).

The double-buffer equivalence contract (module doc of
`repro.serve.server`):

  * frozen-mode serving is bitwise `engine.iterate(engine.init(...))`;
  * feedback-driven serving reproduces a plain `engine.run` over the
    same coalesced event chunks bitwise;
  * checkpoint-restart of a live server is invisible to subsequent
    predictions;

for every engine, sharded included (degenerate 1-device "tasks" mesh
here; the multi-shard boundary is the CI serving smoke at 8 fake
devices).  Plus the feedback router's admission/QoS semantics and the
predict micro-batching surface.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import AMTLConfig, make_engine
from repro.launch.mesh import make_task_mesh
from repro.serve import AMTLServer, ServeConfig

ENGINES = ("dense", "batch1", "batch", "sharded")


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
            key=0, cls_kw=None):
    w0 = jnp.zeros((problem.dim, problem.num_tasks), jnp.float32)
    mesh = mesh1 if cfg.engine == "sharded" else None
    return AMTLServer(problem, cfg, w0, jax.random.PRNGKey(key), serve_cfg,
                      mesh=mesh, **(cls_kw or {}))


def _requests(problem, n, seed=0):
    rng = np.random.default_rng(seed)
    t = rng.integers(0, problem.num_tasks, size=n)
    x = rng.standard_normal((n, problem.dim)).astype(np.float32)
    return t, x


# ------------------------------------------------------------- frozen path
@pytest.mark.parametrize("engine", ENGINES)
def test_frozen_serving_is_bitwise_frozen_engine(small_problem, mesh1,
                                                 engine):
    """Zero feedback: the served iterate is bitwise the frozen engine's,
    and predictions are exactly scores off that iterate."""
    cfg = _cfg(small_problem, engine)
    server = _server(small_problem, cfg, mesh1,
                     ServeConfig(chunk_events=4, learning=False))
    eng = make_engine(small_problem, cfg,
                      mesh1 if engine == "sharded" else None)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    frozen = eng.iterate(eng.init(w0, jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(np.asarray(server.iterate()),
                                  np.asarray(frozen))
    t, x = _requests(small_problem, 7)
    preds, receipt, ran = server.serve(t, x, feedback_task_ids=t)
    assert ran == 0 and receipt.accepted == 0 and receipt.rejected == 7
    want = np.einsum("bd,bd->b", x, np.asarray(frozen)[:, t].T)
    np.testing.assert_allclose(np.asarray(preds), want, rtol=1e-6)
    # still frozen after the request batch
    np.testing.assert_array_equal(np.asarray(server.iterate()),
                                  np.asarray(frozen))


def test_zero_feedback_learning_server_is_also_frozen(small_problem, mesh1):
    """learning=True but no feedback submitted: step() never runs a chunk
    and the served iterate stays the init iterate bitwise."""
    server = _server(small_problem, _cfg(small_problem, "batch"), mesh1)
    before = np.asarray(server.iterate())
    t, x = _requests(small_problem, 5)
    for _ in range(3):
        server.predict(t, x)
        assert server.step() == 0
    np.testing.assert_array_equal(np.asarray(server.iterate()), before)
    assert server.chunk_log == []


# -------------------------------------------------------- feedback replay
@pytest.mark.parametrize("engine", ENGINES)
def test_feedback_serving_replays_plain_run_bitwise(small_problem, mesh1,
                                                    engine):
    """After any sequence of chunk boundaries the server state is bitwise
    one plain `engine.run` over the same coalesced chunks, and the
    serving buffer is that state's iterate."""
    cfg = _cfg(small_problem, engine)
    per = 4 if engine in ("batch", "sharded") else 1
    server = _server(small_problem, cfg, mesh1,
                     ServeConfig(chunk_events=2 * per))
    rng = np.random.default_rng(3)
    t, x = _requests(small_problem, 6)
    for i in range(5):
        fb = rng.integers(0, small_problem.num_tasks,
                          size=rng.integers(1, 3 * per))
        server.serve(t, x, feedback_task_ids=fb)
    assert sum(server.chunk_log) > 0
    for n in server.chunk_log:
        assert n % per == 0 and 0 < n <= 2 * per

    eng = make_engine(small_problem, cfg,
                      mesh1 if engine == "sharded" else None)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    state = eng.init(w0, jax.random.PRNGKey(0))
    state = eng.run(state, None, sum(server.chunk_log))
    np.testing.assert_array_equal(np.asarray(server.iterate()),
                                  np.asarray(eng.iterate(state)))
    for la, lb in zip(jax.tree.leaves(server._state),
                      jax.tree.leaves(state), strict=True):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=engine)


def test_serving_buffer_swaps_only_at_chunk_boundaries(small_problem, mesh1):
    """A request batch's predictions come off the buffer committed at the
    PREVIOUS boundary: feedback in batch k moves predictions from batch
    k+1 on, never batch k's."""
    server = _server(small_problem, _cfg(small_problem, "batch1"), mesh1,
                     ServeConfig(chunk_events=4))
    t, x = _requests(small_problem, 4)
    before = np.asarray(server.predict(t, x))
    preds, _, ran = server.serve(t, x, feedback_task_ids=[0, 1, 2, 3])
    assert ran == 4
    np.testing.assert_array_equal(np.asarray(preds), before)
    after = np.asarray(server.predict(t, x))
    assert not np.array_equal(after, before)


# --------------------------------------------------- checkpoint / restart
@pytest.mark.parametrize("engine", ENGINES)
def test_restart_is_invisible_to_predictions(small_problem, mesh1, engine,
                                             tmp_path):
    """Kill a live server after a rotated checkpoint; `resume` must serve
    bitwise what the uninterrupted server serves, through further
    feedback chunks."""
    cfg = _cfg(small_problem, engine)
    per = 4 if engine in ("batch", "sharded") else 1
    serve_cfg = ServeConfig(chunk_events=2 * per, ckpt_dir=str(tmp_path),
                            checkpoint_every=2 * per, keep_last=2)
    a = _server(small_problem, cfg, mesh1, serve_cfg, key=1)
    b = _server(small_problem, cfg, mesh1, serve_cfg, key=1)
    t, x = _requests(small_problem, 5, seed=9)
    fb = [i % small_problem.num_tasks for i in range(2 * per)]
    a.serve(t, x, feedback_task_ids=fb)     # chunk + auto-checkpoint
    b_preds0, _, _ = b.serve(t, x, feedback_task_ids=fb)

    # "crash" a; resume from its rotated checkpoints
    del a
    c = AMTLServer.resume(
        small_problem, cfg,
        jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32),
        jax.random.PRNGKey(1), serve_cfg,
        mesh=mesh1 if engine == "sharded" else None)
    assert c.event_count == 2 * per
    np.testing.assert_array_equal(np.asarray(c.iterate()),
                                  np.asarray(b.iterate()))
    # identical subsequent traffic -> identical predictions, bitwise
    for i in range(3):
        pc, _, rc = c.serve(t, x, feedback_task_ids=fb)
        pb, _, rb = b.serve(t, x, feedback_task_ids=fb)
        assert rc == rb
        np.testing.assert_array_equal(np.asarray(pc), np.asarray(pb))
    np.testing.assert_array_equal(np.asarray(c.iterate()),
                                  np.asarray(b.iterate()))


def test_checkpoint_rotation_on_disk(small_problem, mesh1, tmp_path):
    """The auto-checkpoint cadence rotates via save(..., keep_last=k)."""
    serve_cfg = ServeConfig(chunk_events=4, ckpt_dir=str(tmp_path),
                            checkpoint_every=4, keep_last=2)
    server = _server(small_problem, _cfg(small_problem, "batch"), mesh1,
                     serve_cfg)
    t, x = _requests(small_problem, 3)
    for _ in range(5):
        server.serve(t, x, feedback_task_ids=[0, 1, 2, 3])
    import os
    names = sorted(os.listdir(tmp_path))
    assert names == ["step_00000016.npz", "step_00000020.npz"]


def test_resume_with_empty_dir_is_fresh_init(small_problem, mesh1,
                                             tmp_path):
    serve_cfg = ServeConfig(chunk_events=4, ckpt_dir=str(tmp_path))
    server = AMTLServer.resume(
        small_problem, _cfg(small_problem, "batch1"),
        jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32),
        jax.random.PRNGKey(0), serve_cfg)
    assert server.event_count == 0


# ------------------------------------------------------- admission / QoS
def test_admission_cap_rejects_burst(small_problem, mesh1):
    server = _server(small_problem, _cfg(small_problem, "batch1"), mesh1,
                     ServeConfig(chunk_events=4, max_pending_per_task=3))
    receipt = server.submit_feedback([0] * 10)
    assert receipt == (3, 7)
    assert server.pending_feedback == 3
    assert server.stats()["rejected_feedback"] == 7


def test_chunk_quota_stops_bursty_task_starving_budget(small_problem,
                                                       mesh1):
    """Task 0 floods the queue; the per-chunk quota keeps every other
    task's feedback flowing within the same chunk."""
    server = _server(small_problem, _cfg(small_problem, "batch1"), mesh1,
                     ServeConfig(chunk_events=6, task_chunk_quota=2))
    server.submit_feedback([0] * 50)
    server.submit_feedback([1, 2, 3, 4])
    ran = server.step()
    assert ran == 6
    # quota'd: 2 events from task 0, the rest from tasks 1..4
    assert server._pending[0] == 48
    assert server._pending[1:].sum() == 0
    # the backlog keeps draining at quota pace on later chunks
    assert server.step() == 2
    assert server._pending[0] == 46


def test_coalesce_floors_to_events_per_step(small_problem, mesh1):
    """A batch engine can only run multiples of event_batch: the floored
    remainder stays queued for the next chunk, never dropped."""
    server = _server(small_problem, _cfg(small_problem, "batch"), mesh1,
                     ServeConfig(chunk_events=8))
    server.submit_feedback([0, 1, 2, 3, 4, 0])      # 6 items, per = 4
    assert server.step() == 4
    assert server.pending_feedback == 2
    server.submit_feedback([1, 2])
    assert server.step() == 4
    assert server.pending_feedback == 0


def test_resume_restores_mixed_padding_checkpoint(small_problem, mesh1,
                                                  tmp_path):
    """Regression: `latest_step` parses step_5.npz to 5 but `restore`
    re-formatted it as step_00000005.npz and raised FileNotFoundError —
    `AMTLServer.resume` crashed on a directory the rotation fix of PR 7
    deliberately tolerates."""
    import os
    serve_cfg = ServeConfig(chunk_events=4, ckpt_dir=str(tmp_path))
    server = _server(small_problem, _cfg(small_problem, "batch1"), mesh1,
                     serve_cfg)
    server.submit_feedback([0, 1, 2, 3])
    server.step()
    server.checkpoint()
    os.rename(tmp_path / "step_00000004.npz", tmp_path / "step_4.npz")
    want = np.asarray(server.iterate())
    del server
    resumed = AMTLServer.resume(
        small_problem, _cfg(small_problem, "batch1"),
        jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32),
        jax.random.PRNGKey(0), serve_cfg)
    assert resumed.event_count == 4
    np.testing.assert_array_equal(np.asarray(resumed.iterate()), want)


def test_resume_builds_init_state_once(small_problem, mesh1, tmp_path,
                                       monkeypatch):
    """Regression: `resume` computed `engine.init(v0, key)` twice (ctor
    + `like`) and materialized a front buffer it immediately replaced.
    Now the init state is built once and only the state actually served
    materializes a snapshot."""
    import repro.serve.server as srv_mod
    serve_cfg = ServeConfig(chunk_events=4, ckpt_dir=str(tmp_path))
    server = _server(small_problem, _cfg(small_problem, "batch1"), mesh1,
                     serve_cfg)
    server.submit_feedback([0, 1, 2])
    server.step()
    server.checkpoint()
    del server

    init_calls = []
    real_make_engine = srv_mod.make_engine

    def spying_make_engine(problem, cfg, mesh=None):
        eng = real_make_engine(problem, cfg, mesh)
        real_init = eng.init

        def counted_init(v0, key):
            init_calls.append(1)
            return real_init(v0, key)
        return eng._replace(init=counted_init)

    monkeypatch.setattr(srv_mod, "make_engine", spying_make_engine)
    resumed = AMTLServer.resume(
        small_problem, _cfg(small_problem, "batch1"),
        jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32),
        jax.random.PRNGKey(0), serve_cfg)
    assert len(init_calls) == 1
    assert resumed.event_count == 3


# ------------------------------------------------------- predict surface
@pytest.mark.parametrize("loss_name", ("lstsq", "logistic"))
def test_predict_empty_batch_returns_empty_scores(small_problem, mesh1,
                                                  loss_name):
    """Regression: `predict([], zeros((0, d)))` reached
    `jnp.concatenate([])` (the slice loop never runs) and raised
    ValueError.  An empty request batch is a valid request: it returns
    an empty (0,) score array in the link's dtype."""
    problem = small_problem._replace(loss_name=loss_name)
    server = _server(problem, _cfg(problem, "batch1"), mesh1)
    out = server.predict([], np.zeros((0, problem.dim), np.float32))
    assert out.shape == (0,)
    assert out.dtype == jnp.float32
    assert server.stats()["requests"] == 1
    assert server.stats()["predictions"] == 0
    # non-empty requests on the same server still serve normally
    t, x = _requests(problem, 3)
    assert np.asarray(server.predict(t, x)).shape == (3,)


def test_predict_micro_batches_pad_and_slice(small_problem, mesh1):
    """Bucketed padding and max_batch slicing return exactly the
    unpadded scores in request order."""
    server = _server(small_problem, _cfg(small_problem, "batch1"), mesh1,
                     ServeConfig(chunk_events=4, max_batch=4))
    server.submit_feedback([0, 1, 2])
    server.step()
    t, x = _requests(small_problem, 11, seed=4)
    got = np.asarray(server.predict(t, x))
    assert got.shape == (11,)
    v = np.asarray(server.iterate())
    np.testing.assert_allclose(got, np.einsum("bd,bd->b", x, v[:, t].T),
                               rtol=1e-6)
    one = np.asarray(server.predict(t[:1], x[:1]))
    np.testing.assert_allclose(one, got[:1], rtol=1e-6)


def test_logistic_predictions_are_probabilities(small_problem, mesh1):
    logit = small_problem._replace(loss_name="logistic")
    server = _server(logit, _cfg(logit, "batch1"), mesh1)
    t, x = _requests(logit, 6)
    p = np.asarray(server.predict(t, x))
    assert ((p > 0) & (p < 1)).all()


def test_predict_validates_inputs(small_problem, mesh1):
    server = _server(small_problem, _cfg(small_problem, "batch1"), mesh1)
    with pytest.raises(ValueError, match="features must be"):
        server.predict([0, 1], np.zeros((2, 3), np.float32))
    with pytest.raises(ValueError, match="task_ids must be in"):
        server.predict([small_problem.num_tasks],
                       np.zeros((1, small_problem.dim), np.float32))
    with pytest.raises(ValueError, match="feedback task_ids"):
        server.submit_feedback([-1])


def test_serve_config_validates(small_problem, mesh1):
    with pytest.raises(ValueError, match="multiple of the engine's"):
        _server(small_problem, _cfg(small_problem, "batch"), mesh1,
                ServeConfig(chunk_events=6))
    with pytest.raises(ValueError, match="task_chunk_quota"):
        _server(small_problem, _cfg(small_problem, "batch1"), mesh1,
                ServeConfig(chunk_events=4, task_chunk_quota=0))
    with pytest.raises(ValueError, match="nowhere to write"):
        _server(small_problem, _cfg(small_problem, "batch1"), mesh1,
                ServeConfig(chunk_events=4, checkpoint_every=4))


def test_stats_telemetry(small_problem, mesh1):
    server = _server(small_problem, _cfg(small_problem, "batch1"), mesh1,
                     ServeConfig(chunk_events=4))
    t, x = _requests(small_problem, 3)
    server.serve(t, x, feedback_task_ids=[0, 1])
    s = server.stats()
    assert s["requests"] == 1 and s["predictions"] == 3
    assert s["events"] == 2 and s["chunks"] == 1
    assert s["learning"] is True


# ------------------------------------------------------------ host spans
def test_a_chunk_boundary_opens_its_spans_with_their_counts(small_problem,
                                                            mesh1, tmp_path):
    """A profile of one `step()` with labeled feedback holds `serve.chunk`
    around the fold, the coalesce, the engine run and the guard, each with
    its arguments; a predict opens `serve.predict` with its rows."""
    from jax.profiler import ProfileData

    server = _server(small_problem, _cfg(small_problem, "batch"), mesh1,
                     ServeConfig(chunk_events=8))
    t, x = _requests(small_problem, 8, seed=3)
    y = np.ones(8, np.float32)
    assert server.submit_feedback(t, x, y).accepted == 8
    server.step()                      # compiles outside the profile
    server.submit_feedback(t, x, y)
    server.predict(t, x)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert server.step() == 8
        jax.block_until_ready(server.predict(t[:5], x[:5]))
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    spans = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns,
                       dict(ev.stats))
             for plane in data.planes for line in plane.lines
             for ev in line.events
             if ev.name.startswith(("serve.", "amtl."))}
    assert set(spans) == {"serve.chunk", "serve.fold", "serve.coalesce",
                          "amtl.run", "serve.guard", "serve.predict"}
    lo, hi, chunk = spans["serve.chunk"]
    assert chunk == {"chunk": 1, "events": 8}
    for name in ("serve.fold", "serve.coalesce", "amtl.run", "serve.guard"):
        s, e, _ = spans[name]
        assert lo <= s and e <= hi, name
    store = server.problem
    assert spans["serve.fold"][2] == {
        "rows": 8, "bytes": store.xs.nbytes + store.ys.nbytes
        + store.row_counts.nbytes}
    assert spans["amtl.run"][2] == {"num_events": 8, "shards": 1,
                                    "comm_bytes": 0,
                                    "grad_batched_events": 0}
    assert spans["serve.predict"][2] == {"rows": 5}
    assert not (lo <= spans["serve.predict"][0] < hi)
