"""Public stepwise engine-session API (`make_engine` / `AMTLEngine`).

Covers the session redesign's three contracts:

  * `run` composes bitwise — a session split at any step boundary resumes
    exactly (the streaming deployment shape: events arrive in chunks);
  * every engine state round-trips through `repro.checkpoint.save/restore`
    and resumes bitwise, including the sharded state under a mesh;
  * the decoupled prox cadence (`prox_every = k * event_batch`) reproduces
    the one-event run (event_batch=1) bitwise at the same prox_every on
    the CPU oracle path, for the batch and sharded engines.

Plus the `default_config` engine-kwarg validation surface and the
backward-compat contract of the `amtl_solve`/`amtl_events_only` wrappers.
Multi-shard boundaries are exercised by the slow suite and the CI
checkpoint smoke; here the mesh is the degenerate 1-device "tasks" mesh.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import checkpoint
from repro.core import (AMTLConfig, amtl_solve, default_config, make_engine,
                        validate_config)
from repro.core.amtl import (BatchAMTLState, ShardedAMTLState,
                             amtl_events_only, current_iterate)
from repro.launch.mesh import make_task_mesh

# "batch1": the batch engine one event a step (event_batch=1).
ENGINES = ("dense", "batch1", "batch", "sharded")


def _cfg(problem, engine, tau=3, **kw):
    eta = 1.0 / problem.lipschitz()
    if engine == "batch1":
        engine = "batch"
        kw.setdefault("event_batch", 1)
    if engine in ("batch", "sharded"):
        kw.setdefault("event_batch", 4)
        kw.setdefault("prox_every", kw["event_batch"])
    return AMTLConfig(eta=eta, eta_k=0.7, tau=tau, engine=engine, **kw)


@pytest.fixture(scope="module")
def mesh1():
    return make_task_mesh(1)


def _engine_for(problem, cfg, mesh1):
    return make_engine(problem, cfg,
                       mesh1 if cfg.engine == "sharded" else None)


def _assert_states_equal(a, b, context=""):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=context)


# ------------------------------------------------------------ API surface
@pytest.mark.parametrize("engine", ENGINES)
def test_engine_metadata_and_iterate(small_problem, mesh1, engine):
    cfg = _cfg(small_problem, engine)
    eng = _engine_for(small_problem, cfg, mesh1)
    assert eng.events_per_step == (4 if engine in ("batch", "sharded") else 1)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    state = eng.init(w0, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(eng.iterate(state)),
                                  np.asarray(w0))
    assert int(state.event) == 0


@pytest.mark.parametrize("engine", ENGINES)
def test_run_matches_amtl_events_only(small_problem, mesh1, engine):
    """The wrappers are thin: one init + run IS amtl_events_only."""
    cfg = _cfg(small_problem, engine)
    mesh = mesh1 if engine == "sharded" else None
    eng = make_engine(small_problem, cfg, mesh)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(2)
    got = eng.run(eng.init(w0, key), None, 20)
    want = amtl_events_only(small_problem, cfg, w0, key, 20, mesh=mesh)
    _assert_states_equal(got, want, engine)


def test_solve_wrapper_equals_session_stream(small_problem):
    """amtl_solve(num_epochs=E, events_per_epoch=n) reaches the same final
    iterate bitwise as one uninterrupted session of E*n events."""
    cfg = _cfg(small_problem, "batch")
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(9)
    res = amtl_solve(small_problem, cfg, w0, key, num_epochs=5,
                     events_per_epoch=8)
    eng = make_engine(small_problem, cfg)
    state = eng.run(eng.init(w0, key), None, 40)
    np.testing.assert_array_equal(np.asarray(res.v),
                                  np.asarray(eng.iterate(state)))


def test_run_rejects_non_multiple_num_events(small_problem):
    eng = make_engine(small_problem, _cfg(small_problem, "batch"))
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    state = eng.init(w0, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match=r"num_events \(10\).*event_batch"):
        eng.run(state, None, 10)


def test_make_engine_validates_eagerly(small_problem, mesh1):
    with pytest.raises(ValueError, match="unknown AMTL engine"):
        make_engine(small_problem, _cfg(small_problem, "sparse"))
    with pytest.raises(ValueError, match=r"mesh is only meaningful"):
        make_engine(small_problem, _cfg(small_problem, "batch1"), mesh1)


# -------------------------------------------------------- split / resume
@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("split", [0, 1, 3, 5])
def test_session_splits_resume_bitwise(small_problem, mesh1, engine, split):
    """run(state, 2N) == run(run(state, n), 2N - n) at any step boundary —
    full state (iterate, rings, ptr, event counter, history, key)."""
    cfg = _cfg(small_problem, engine)
    eng = _engine_for(small_problem, cfg, mesh1)
    per = eng.events_per_step
    offs = jnp.asarray([2.0, 0.0, 1.0, 0.0, 3.0])
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(4)
    total = 5 * per
    full = eng.run(eng.init(w0, key), offs, total)
    mid = eng.run(eng.init(w0, key), offs, split * per)
    resumed = eng.run(mid, offs, total - split * per)
    _assert_states_equal(full, resumed, f"{engine} split={split}")


# ------------------------------------------------------ checkpoint/restore
@pytest.mark.parametrize("engine", ENGINES)
def test_checkpoint_roundtrip_resumes_bitwise(small_problem, mesh1, engine,
                                              tmp_path):
    """run(2N) == run(N) -> checkpoint.save -> restore -> run(N), for every
    engine (sharded under its mesh), on full state."""
    kw = {} if engine == "dense" else {"prox_rank": 3}
    cfg = _cfg(small_problem, engine, dynamic_step=True, **kw)
    eng = _engine_for(small_problem, cfg, mesh1)
    n = 5 * eng.events_per_step
    offs = jnp.asarray([1.0, 0.0, 2.0, 0.0, 1.0])
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(8)
    full = eng.run(eng.init(w0, key), offs, 2 * n)
    half = eng.run(eng.init(w0, key), offs, n)
    checkpoint.save(str(tmp_path), int(half.event), half)
    assert checkpoint.latest_step(str(tmp_path)) == n
    restored = checkpoint.restore(str(tmp_path), n,
                                  like=eng.init(w0, key))
    _assert_states_equal(half, restored, f"{engine} roundtrip")
    resumed = eng.run(restored, offs, n)
    _assert_states_equal(full, resumed, f"{engine} resume")


def test_checkpoint_roundtrip_decoupled_cadence_cache(small_problem,
                                                      tmp_path):
    """The reinstated prox cache is part of the contract: a mid-cadence
    checkpoint must restore the live (d, T) cache, not refresh early."""
    cfg = _cfg(small_problem, "batch", event_batch=2, prox_every=6)
    eng = make_engine(small_problem, cfg)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(5)
    # 8 events = 4 batches: stops between refresh events 6 and 12
    full = eng.run(eng.init(w0, key), None, 16)
    half = eng.run(eng.init(w0, key), None, 8)
    assert half.p_cache.shape == (small_problem.dim,
                                  small_problem.num_tasks)
    checkpoint.save(str(tmp_path), 8, half)
    restored = checkpoint.restore(str(tmp_path), 8, like=eng.init(w0, key))
    resumed = eng.run(restored, None, 8)
    _assert_states_equal(full, resumed, "mid-cadence cache resume")


def test_checkpoint_restore_rejects_layout_drift(small_problem, tmp_path):
    """A record must fail loudly — naming the drifted entries — when the
    state layout or shapes disagree with `like`, instead of misloading."""
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(0)
    batch = make_engine(small_problem, _cfg(small_problem, "batch"))
    checkpoint.save(str(tmp_path), 0, batch.init(w0, key))
    dense = make_engine(small_problem, _cfg(small_problem, "dense"))
    with pytest.raises(ValueError, match="does not match the `like` pytree"):
        checkpoint.restore(str(tmp_path), 0, like=dense.init(w0, key))
    deeper = make_engine(small_problem, _cfg(small_problem, "batch", tau=6))
    with pytest.raises(ValueError, match=r"shape"):
        checkpoint.restore(str(tmp_path), 0, like=deeper.init(w0, key))
    st = batch.init(w0, key)
    wrong_dtype = st._replace(event=st.event.astype(jnp.float32))
    with pytest.raises(ValueError, match=r"dtype"):
        checkpoint.restore(str(tmp_path), 0, like=wrong_dtype)


# ------------------------------------------------- decoupled prox cadence
@pytest.mark.parametrize("tau,bsz,k", [(3, 4, 2), (3, 4, 3), (0, 2, 4),
                                       (3, 5, 2), (8, 5, 3)])
def test_batch_decoupled_cadence_matches_delta(small_problem, tau, bsz, k):
    """prox_every = k*event_batch reproduces the one-event run at the same
    prox cadence bitwise on the CPU oracle path — full state including the
    carried prox cache.  (3,5,2)/(8,5,3) cover event_batch > ring depth
    and deep rings."""
    one_cfg = _cfg(small_problem, "batch1", tau=tau, prox_every=k * bsz)
    batch_cfg = one_cfg._replace(event_batch=bsz)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(3)
    n = 6 * k * bsz
    d = amtl_events_only(small_problem, one_cfg, w0, key, n)
    b = amtl_events_only(small_problem, batch_cfg, w0, key, n)
    np.testing.assert_array_equal(np.asarray(d.v), np.asarray(b.v))
    np.testing.assert_array_equal(np.asarray(d.p_cache),
                                  np.asarray(b.p_cache))
    np.testing.assert_array_equal(np.asarray(d.delta_ring),
                                  np.asarray(b.delta_ring))
    np.testing.assert_array_equal(np.asarray(d.task_ring),
                                  np.asarray(b.task_ring))
    assert int(d.ptr) == int(b.ptr)
    assert int(d.event) == int(b.event) == n
    np.testing.assert_array_equal(np.asarray(d.key), np.asarray(b.key))


def test_batch_decoupled_cadence_dynamic_step_and_sketch(small_problem):
    """Cadence decoupling must also replay the delay-adaptive KM step and
    fold the sketch key at refresh events only, exactly like one event a
    step."""
    one_cfg = _cfg(small_problem, "batch1", tau=4, prox_every=10,
                   dynamic_step=True, prox_rank=5)
    batch_cfg = one_cfg._replace(event_batch=5)
    offs = jnp.asarray([3.0, 1.0, 0.0, 2.0, 4.0])
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(11)
    d = amtl_events_only(small_problem, one_cfg, w0, key, 40,
                         delay_offsets=offs)
    b = amtl_events_only(small_problem, batch_cfg, w0, key, 40,
                         delay_offsets=offs)
    np.testing.assert_array_equal(np.asarray(d.v), np.asarray(b.v))
    np.testing.assert_array_equal(np.asarray(d.p_cache),
                                  np.asarray(b.p_cache))
    np.testing.assert_array_equal(np.asarray(d.history.buf),
                                  np.asarray(b.history.buf))


def test_sharded_decoupled_cadence_matches_batch(small_problem, mesh1):
    """The sharded engine pays its all_gather only at refresh batches; on a
    1-device mesh the decoupled cadence must still match batch bitwise."""
    batch_cfg = _cfg(small_problem, "batch", tau=3, event_batch=5,
                     prox_every=15)
    sharded_cfg = batch_cfg._replace(engine="sharded")
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(6)
    b = amtl_events_only(small_problem, batch_cfg, w0, key, 45)
    s = amtl_events_only(small_problem, sharded_cfg, w0, key, 45,
                         mesh=mesh1)
    np.testing.assert_array_equal(np.asarray(b.v), np.asarray(s.v))
    np.testing.assert_array_equal(np.asarray(b.p_cache),
                                  np.asarray(s.p_cache))
    np.testing.assert_array_equal(np.asarray(b.delta_ring),
                                  np.asarray(s.delta_ring[0]))


def test_prox_cache_carried_only_when_decoupled(small_problem, mesh1):
    """Aligned cadence keeps the (0, 0) stub (no dead (d, T) loop carry);
    k > 1 carries the live cache — for batch and sharded states."""
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    key = jax.random.PRNGKey(0)
    aligned = make_engine(small_problem, _cfg(small_problem, "batch"))
    st = aligned.init(w0, key)
    assert isinstance(st, BatchAMTLState) and st.p_cache.shape == (0, 0)
    decoupled = make_engine(small_problem,
                            _cfg(small_problem, "batch", event_batch=4,
                                 prox_every=8))
    assert decoupled.init(w0, key).p_cache.shape == w0.shape
    sh = make_engine(small_problem,
                     _cfg(small_problem, "sharded", event_batch=4,
                          prox_every=8), mesh1)
    st = sh.init(w0, key)
    assert isinstance(st, ShardedAMTLState) and st.p_cache.shape == w0.shape


# ----------------------------------------------- default_config validation
def test_default_config_accepts_engine_kwargs(small_problem):
    cfg = default_config(small_problem, tau=3, engine="batch",
                         event_batch=8, prox_every=32, prox_rank=4)
    assert (cfg.engine, cfg.event_batch, cfg.prox_every, cfg.prox_rank) == \
        ("batch", 8, 32, 4)
    # the returned config must be directly usable
    eng = make_engine(small_problem, cfg)
    assert eng.events_per_step == 8
    # the default is the batch engine, one event a step
    assert default_config(small_problem).engine == "batch"
    eng = make_engine(small_problem, AMTLConfig(cfg.eta, cfg.eta_k, cfg.tau))
    assert eng.events_per_step == 1
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    assert isinstance(eng.init(w0, jax.random.PRNGKey(0)), BatchAMTLState)


def test_default_config_validates_like_make_engine(small_problem):
    """Invalid engine combinations fail at config construction, through
    the same validate_config path make_engine runs."""
    with pytest.raises(ValueError, match=r"event_batch=4.*engine='batch'"):
        default_config(small_problem, engine="dense", event_batch=4)
    with pytest.raises(ValueError, match="unknown AMTL engine"):
        default_config(small_problem, engine="sparse")
    with pytest.raises(ValueError, match=r"must be a multiple of"):
        default_config(small_problem, engine="batch", event_batch=4,
                       prox_every=6)
    with pytest.raises(ValueError, match="seed baseline"):
        default_config(small_problem, engine="dense", prox_every=2)
    l21 = small_problem._replace(reg_name="l21")
    with pytest.raises(ValueError, match=r"prox_rank.*nuclear.*'l21'"):
        default_config(l21, engine="batch", prox_rank=3)


def test_validate_config_standalone(small_problem):
    validate_config(_cfg(small_problem, "batch", event_batch=4,
                         prox_every=12))
    with pytest.raises(ValueError, match="prox_every must be >= 1"):
        validate_config(_cfg(small_problem, "batch1", prox_every=0))


# ------------------------------------------------------------ phase names
@pytest.mark.parametrize("engine", ("batch1", "batch", "sharded"))
def test_engine_step_names_its_phases(small_problem, mesh1, engine):
    """The sampling, prox, gradient and update phases are named scopes of
    the lowered program (what a device trace attributes time to)."""
    from repro.core.amtl import _run_events

    cfg = _cfg(small_problem, engine, prox_rank=2, batch_size=8)
    eng = _engine_for(small_problem, cfg, mesh1)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    state = eng.init(w0, jax.random.PRNGKey(0))
    text = _run_events.lower(
        small_problem, cfg, state, jnp.zeros((small_problem.num_tasks,)),
        2 * eng.events_per_step,
        mesh1 if engine == "sharded" else None).as_text(debug_info=True)
    for scope in ("amtl.sample", "amtl.prox", "amtl.grad", "amtl.update"):
        assert scope in text, scope


def test_run_opens_a_host_span_with_its_event_count(small_problem, tmp_path):
    from jax.profiler import ProfileData

    cfg = _cfg(small_problem, "batch")
    eng = make_engine(small_problem, cfg)
    w0 = jnp.zeros((small_problem.dim, small_problem.num_tasks), jnp.float32)
    state = eng.init(w0, jax.random.PRNGKey(0))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(eng.run(state, None, 8))
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    runs = [dict(ev.stats) for plane in data.planes for line in plane.lines
            for ev in line.events if ev.name == "amtl.run"]
    # On the CPU no gradient takes the batched (TPU) kernel.
    assert runs == [{"num_events": 8, "shards": 1, "comm_bytes": 0,
                     "grad_batched_events": 0}]



PLACED = r"""
import jax, jax.numpy as jnp
from repro.core import AMTLConfig, MTLProblem, make_engine
from repro.core.amtl import _run_events
from repro.launch.mesh import make_task_mesh

t, n, d = 8, 6, 5
prob = MTLProblem(jnp.ones((t, n, d)), jnp.ones((t, n)), "lstsq", "nuclear",
                  0.1)
cfg = AMTLConfig(eta=0.1, eta_k=0.7, tau=3, engine="sharded", event_batch=4,
                 prox_every=4)
eng = make_engine(prob, cfg, make_task_mesh(2))
state = eng.init(jnp.zeros((d, t)), jax.random.PRNGKey(0))
after = eng.run(state, None, 4)
assert jax.tree.leaves(jax.tree.map(lambda a, b: a.sharding == b.sharding,
                                    state, after)) == [True] * 9
eng.run(after, None, 4)
assert _run_events._cache_size() == 1, _run_events._cache_size()
"""


def test_sharded_init_is_placed_as_run_returns_it():
    """On a two-device mesh the sharded engine's fresh state sits where
    `run` leaves it, so the first call compiles the program every later
    call runs."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", PLACED], env=env, cwd=root,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
