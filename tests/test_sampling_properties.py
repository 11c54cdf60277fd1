"""Property-based tests of the serial-replay contracts.

`_sample_activation_batch` is what lets the batch and sharded engines claim
an event stream identical to the dense engine's BY CONSTRUCTION: it must
consume the same PRNG splits and produce the same (task, staleness) draws
as `event_batch` consecutive `_sample_activation` calls — including the
per-position staleness clamp `nu <= min(tau, event + i)` — for every
`event_batch`, `tau`, `delay_offsets`, jitter, and chain position.  PR 2
only covered this implicitly at the fixed bench shapes; here hypothesis
drives arbitrary configurations.

The session analogue (PR 4): `AMTLEngine.run` must compose bitwise at ANY
step boundary — `run(·, total)` equals `run(run(·, n), total - n)` on the
FULL engine state, for arbitrary engine, tau, event_batch, prox cadence,
and split point, with the mid state additionally round-tripped through the
checkpoint serialization (host numpy and back).  This is the streaming
deployment contract: a server that persists its state after any chunk of
events and restarts resumes the exact event stream.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.amtl import (AMTLConfig, _minibatch_seed, _sample_activation,
                             _sample_activation_batch, amtl_events_only,
                             make_engine)
from repro.core.losses import MTLProblem
from repro.kernels import ops, ref


@st.composite
def _sampler_setups(draw):
    num_tasks = draw(st.integers(1, 8))
    tau = draw(st.integers(0, 6))
    batch = draw(st.integers(1, 12))
    # chain position: 0 exercises the `nu <= event` warm-up clamp, larger
    # values the steady state
    event0 = draw(st.integers(0, 20))
    jitter = draw(st.floats(0.0, 3.0, allow_nan=False, allow_infinity=False))
    offsets = draw(st.lists(
        st.floats(0.0, 6.0, allow_nan=False, allow_infinity=False),
        min_size=num_tasks, max_size=num_tasks))
    seed = draw(st.integers(0, 2**31 - 1))
    return num_tasks, tau, batch, event0, jitter, offsets, seed


@settings(max_examples=50, deadline=None)
@given(_sampler_setups())
def test_batch_sampler_replays_serial_chain_exactly(setup):
    num_tasks, tau, batch, event0, jitter, offsets, seed = setup
    cfg = AMTLConfig(eta=0.1, eta_k=0.5, tau=tau, delay_jitter=jitter)
    offs = jnp.asarray(offsets, jnp.float32)
    key0 = jax.random.PRNGKey(seed)
    event0_j = jnp.asarray(event0, jnp.int32)

    key = key0
    want_ts, want_nus, want_seeds = [], [], []
    for i in range(batch):
        # the minibatch seed is folded off the PRE-event chain key — the
        # exact key a one-event step holds when it derives its seed
        want_seeds.append(int(_minibatch_seed(key)))
        key, t, nu = _sample_activation(cfg, offs, key, num_tasks,
                                        event0_j + i)
        want_ts.append(int(t))
        want_nus.append(int(nu))

    got_key, got_ts, got_nus, got_seeds = _sample_activation_batch(
        cfg, offs, key0, num_tasks, event0_j, batch)

    np.testing.assert_array_equal(np.asarray(got_ts), want_ts)
    np.testing.assert_array_equal(np.asarray(got_nus), want_nus)
    # the batched replay derives the SAME per-event sampling seeds as the
    # one-event engine's serial fold — the SGD engines' equivalence hinge
    np.testing.assert_array_equal(np.asarray(got_seeds), want_seeds)
    # the chain head must also coincide: the next batch continues the same
    # serial split sequence
    np.testing.assert_array_equal(np.asarray(got_key), np.asarray(key))
    # staleness always within the cap and the warm-up window
    assert all(nu <= min(tau, event0 + i)
               for i, nu in enumerate(want_nus))


# The cell's sampler shape (bench/configs/emnist62_writers.json): 3400
# writers, tau 8, jitter 1.0, non-zero per-task delay offsets.
_CELL_T, _CELL_TAU = 3400, 8


@pytest.mark.parametrize("event0", (0, 5, 3392))
@pytest.mark.parametrize("batch", (1, 8, 32))
def test_batch_sampler_replays_serial_chain_at_cell_shape(batch, event0):
    """At the shape the benchmark runs, the batch sampler's unrolled chain
    and vmapped draws equal `batch` serial `_sample_activation` calls bit
    for bit: keys, tasks, stalenesses, seeds, and the chain head."""
    cfg = AMTLConfig(eta=0.1, eta_k=0.5, tau=_CELL_TAU, delay_jitter=1.0)
    offs = jax.random.uniform(jax.random.PRNGKey(3), (_CELL_T,),
                              minval=0.0, maxval=6.0)
    key0 = jax.random.PRNGKey(2**31 - 17)
    serial = jax.jit(_sample_activation, static_argnums=(0, 3))

    key = key0
    want_ts, want_nus, want_seeds = [], [], []
    for i in range(batch):
        want_seeds.append(int(_minibatch_seed(key)))
        key, t, nu = serial(cfg, offs, key, _CELL_T,
                            jnp.asarray(event0 + i, jnp.int32))
        want_ts.append(int(t))
        want_nus.append(int(nu))

    got_key, got_ts, got_nus, got_seeds = jax.jit(
        _sample_activation_batch, static_argnums=(0, 3, 5))(
        cfg, offs, key0, _CELL_T, jnp.asarray(event0, jnp.int32), batch)

    np.testing.assert_array_equal(np.asarray(got_ts), want_ts)
    np.testing.assert_array_equal(np.asarray(got_nus), want_nus)
    np.testing.assert_array_equal(np.asarray(got_seeds), want_seeds)
    np.testing.assert_array_equal(np.asarray(got_key), np.asarray(key))


def test_batch_sampler_lowers_without_a_device_loop():
    """The batch's draws are straight-line code: the sampler the chip runs
    holds no `while`.  Lowered for TPU because on the CPU backend threefry
    itself lowers to a rolled loop."""
    cfg = AMTLConfig(eta=0.1, eta_k=0.5, tau=_CELL_TAU, delay_jitter=1.0)
    text = jax.jit(_sample_activation_batch, static_argnums=(0, 3, 5)).trace(
        cfg, jnp.zeros((_CELL_T,), jnp.float32), jax.random.PRNGKey(0),
        _CELL_T, jnp.asarray(0, jnp.int32), 32,
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert "threefry" in text
    assert "stablehlo.while" not in text


# ------------------------------------------------- session split / resume

_T, _N, _D = 4, 6, 8


def _tiny_problem():
    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    xs = jax.random.normal(kx, (_T, _N, _D)) / np.sqrt(_D)
    ys = jax.random.normal(ky, (_T, _N))
    return MTLProblem(xs, ys, "lstsq", "nuclear", 0.1)


@st.composite
def _session_setups(draw):
    # "batch1": the batch engine one event a step, any prox cadence
    engine = draw(st.sampled_from(["dense", "batch1", "batch", "sharded"]))
    tau = draw(st.integers(0, 4))
    if engine in ("batch", "sharded"):
        bsz = draw(st.integers(1, 4))
        prox_every = bsz * draw(st.integers(1, 3))   # incl. decoupled k > 1
    else:
        bsz = 1
        prox_every = 1 if engine == "dense" else draw(st.integers(1, 4))
        engine = "batch" if engine == "batch1" else engine
    total_steps = draw(st.integers(1, 5))
    split = draw(st.integers(0, total_steps))
    dynamic = draw(st.booleans())
    offsets = draw(st.lists(
        st.floats(0.0, 4.0, allow_nan=False, allow_infinity=False),
        min_size=_T, max_size=_T))
    seed = draw(st.integers(0, 2**31 - 1))
    return engine, tau, bsz, prox_every, total_steps, split, dynamic, \
        offsets, seed


def _roundtrip_host(state):
    """The checkpoint serialization boundary: every leaf to host numpy and
    back (what save -> restore does, minus the filesystem)."""
    return jax.tree.map(lambda a: jnp.asarray(np.asarray(a)), state)


@settings(max_examples=25, deadline=None)
@given(_session_setups())
def test_session_split_at_any_event_boundary_resumes_bitwise(setup):
    """The streaming analogue of the serial-chain replay property: for any
    engine/tau/event_batch/cadence and ANY split point, running the session
    in two chunks (with a host round-trip of the mid state) reproduces the
    uninterrupted run's full state bitwise."""
    (engine, tau, bsz, prox_every, total_steps, split, dynamic, offsets,
     seed) = setup
    problem = _tiny_problem()
    cfg = AMTLConfig(eta=1.0 / problem.lipschitz(), eta_k=0.6, tau=tau,
                     engine=engine, event_batch=bsz, prox_every=prox_every,
                     dynamic_step=dynamic)
    mesh = None
    if engine == "sharded":
        from repro.launch.mesh import make_task_mesh
        mesh = make_task_mesh(1)
    eng = make_engine(problem, cfg, mesh)
    offs = jnp.asarray(offsets, jnp.float32)
    w0 = jnp.zeros((_D, _T), jnp.float32)
    key = jax.random.PRNGKey(seed)

    full = eng.run(eng.init(w0, key), offs, total_steps * bsz)
    mid = eng.run(eng.init(w0, key), offs, split * bsz)
    resumed = eng.run(_roundtrip_host(mid), offs, (total_steps - split) * bsz)

    assert int(resumed.event) == total_steps * bsz
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(resumed),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------ seeded minibatch sampling
#
# SGD-AMTL's forward step (PR 6).  Three contracts:
#   * the in-kernel sampler's keep/drop bits equal the jnp oracle's for
#     every (n, batch_size, seed) — selection is pure counter arithmetic;
#   * the minibatch gradient is unbiased: averaged over seeds it converges
#     to the full gradient under the (n/bsz) scaling;
#   * batch_size >= n (and batch_size=None at the engine level) degrades
#     to the exact full-gradient path, bitwise on a fixed backend.


@st.composite
def _mask_setups(draw):
    n = draw(st.integers(1, 1100))          # crosses the 512 block boundary
    b = draw(st.integers(1, 1100))          # incl. batch_size >= n
    seed = draw(st.integers(0, 2**32 - 1))
    return n, b, seed


@settings(max_examples=40, deadline=None)
@given(_mask_setups())
def test_sample_mask_kernel_matches_oracle_bitwise(setup):
    """The Pallas sampler (interpret mode) and the jnp oracle must emit the
    SAME selection bits — they share `counter_hash`/`sample_cutoff`, and
    this pins that the kernel's iota/padding plumbing preserves them."""
    n, b, seed = setup
    seed_j = jnp.asarray(seed, jnp.uint32)
    want = np.asarray(ref.sample_mask_ref(n, b, seed_j))
    got = np.asarray(ops.sample_mask(n, b, seed_j, interpret=True))
    np.testing.assert_array_equal(got, want)
    # rank-based selection keeps EXACTLY min(b, n) rows — what licenses
    # the oracle's static-size gather
    assert got.sum() == min(b, n)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(0, 40))
def test_batch_size_at_least_n_is_bitwise_full_gradient(seed, extra):
    """batch_size >= n: mask all-ones and scale (n/bsz) == 1, so the sampled
    op must reproduce `ops.lstsq_grad` BITWISE on the oracle path — the
    engines' batch_size=None arithmetic is this path."""
    n, d = 13, 5
    kx, kw, ky = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(kx, (n, d), jnp.float32)
    w = jax.random.normal(kw, (d,), jnp.float32)
    y = jax.random.normal(ky, (n,), jnp.float32)
    got = ops.lstsq_grad_sampled(x, w, y, jnp.asarray(seed, jnp.uint32),
                                 batch_size=n + extra, use_pallas=False)
    want = ops.lstsq_grad(x, w, y, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_minibatch_gradient_is_unbiased_over_seeds():
    """E_seed[(n/bsz) 2 X_S^T(X_S w - y_S)] = 2 X^T(X w - y): the mean over
    a large fixed seed set must approach the full gradient (deterministic
    seed set, statistical tolerance — no flake)."""
    n, d, b = 40, 6, 10
    kx, kw, ky = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(kx, (n, d), jnp.float32)
    w = jax.random.normal(kw, (d,), jnp.float32)
    y = jax.random.normal(ky, (n,), jnp.float32)
    seeds = jnp.arange(6000, dtype=jnp.uint32)
    grads = jax.vmap(
        lambda s: ref.lstsq_grad_sampled_ref(x, w, y, s, b))(seeds)
    mean = np.asarray(grads, np.float64).mean(axis=0)
    full = np.asarray(ref.lstsq_grad_ref(x, w, y), np.float64)
    rel = np.linalg.norm(mean - full) / np.linalg.norm(full)
    assert rel < 0.08, rel


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, _N), st.integers(0, 4))
def test_delta_and_batch_engines_agree_bitwise_with_minibatching(
        seed, batch_size, tau):
    """Aligned configs with batch_size set, one event a step and three: both
    must fold the SAME per-event sampling seed off the same chain position,
    so their full states stay bitwise equal on the CPU oracle path."""
    problem = _tiny_problem()
    eta = 1.0 / problem.lipschitz()
    one_cfg = AMTLConfig(eta=eta, eta_k=0.6, tau=tau, engine="batch",
                         prox_every=3, batch_size=batch_size)
    batch_cfg = one_cfg._replace(event_batch=3)
    w0 = jnp.zeros((_D, _T), jnp.float32)
    key = jax.random.PRNGKey(seed)
    d_st = amtl_events_only(problem, one_cfg, w0, key, 12)
    b_st = amtl_events_only(problem, batch_cfg, w0, key, 12)
    np.testing.assert_array_equal(np.asarray(d_st.v), np.asarray(b_st.v))
    np.testing.assert_array_equal(np.asarray(d_st.key), np.asarray(b_st.key))
    assert int(d_st.event) == int(b_st.event) == 12


# --------------------------------------------------- ragged row masking
#
# PR 9: `MTLProblem.row_counts` restricts every loss, gradient, and
# minibatch selection to each task's first n_t rows of the shared padded
# buffer.  Deterministic sweeps live in tests/test_taskstore.py; here
# hypothesis drives arbitrary (n, batch_size, n_t, seed) configurations.


@st.composite
def _masked_setups(draw):
    n = draw(st.integers(1, 700))           # crosses the 512 block boundary
    b = draw(st.integers(1, 700))
    n_t = draw(st.integers(0, n))           # incl. empty and full cohorts
    seed = draw(st.integers(0, 2**32 - 1))
    return n, b, n_t, seed


@settings(max_examples=40, deadline=None)
@given(_masked_setups())
def test_masked_cutoff_keeps_exactly_min_b_nt_valid_rows(setup):
    """The valid-row cutoff law: exactly min(b, n_t) rows survive, all of
    them valid, the kernel emits the oracle's bits, and n_t == n reduces
    bitwise to the unmasked selection."""
    n, b, n_t, seed = setup
    seed_j = jnp.asarray(seed, jnp.uint32)
    nt = jnp.asarray(n_t, jnp.int32)
    want = np.asarray(ref.sample_mask_masked_ref(n, b, seed_j, nt))
    assert want.sum() == min(b, n_t)
    assert not want[n_t:].any()
    got = np.asarray(ops.sample_mask(n, b, seed_j, n_t=nt, interpret=True))
    np.testing.assert_array_equal(got, want)
    if n_t == n:
        np.testing.assert_array_equal(
            want, np.asarray(ref.sample_mask_ref(n, b, seed_j)))


@st.composite
def _batch_selection_setups(draw):
    n = draw(st.integers(2, 600))           # crosses a 512-row strip
    b = draw(st.integers(1, n - 1))
    # every cohort edge: empty, one row, fewer than b, exactly b, full
    counts = draw(st.lists(
        st.sampled_from([0, 1, max(b - 1, 0), b, n]) | st.integers(0, n),
        min_size=1, max_size=5))
    ts = draw(st.lists(st.integers(0, len(counts) - 1), min_size=1,
                       max_size=8))         # duplicate tasks are common
    seeds = draw(st.lists(st.integers(0, 2**32 - 1), min_size=len(ts),
                          max_size=len(ts)))
    return n, b, counts, ts, seeds


@settings(max_examples=40, deadline=None)
@given(_batch_selection_setups())
def test_batched_selection_is_each_events_oracle_set(setup):
    """One sort for the whole batch picks, for every event, exactly the
    rows `sample_mask_masked_ref` keeps (and `sample_mask_ref` where the
    task is full); its ranks past min(b, n_t) hold only rows past n_t."""
    n, b, counts, ts, seeds = setup
    n_ts = jnp.asarray(counts, jnp.int32)[jnp.asarray(ts)]
    seeds_j = jnp.asarray(seeds, jnp.uint32)
    rows = np.asarray(jax.jit(ref.sample_rows_batch, static_argnums=(0, 1))(
        n, b, seeds_j, n_ts))
    assert rows.shape == (len(ts), b)
    for e, t in enumerate(ts):
        n_t, bsz = counts[t], min(b, counts[t])
        want = np.asarray(ref.sample_mask_masked_ref(
            n, b, seeds_j[e], jnp.asarray(n_t, jnp.int32)))
        assert len(set(rows[e])) == b
        assert set(rows[e, :bsz]) == set(np.flatnonzero(want))
        assert (rows[e, bsz:] >= n_t).all()
        if n_t == n:
            full = np.asarray(ref.sample_mask_ref(n, b, seeds_j[e]))
            assert set(rows[e]) == set(np.flatnonzero(full))


@pytest.mark.parametrize("ragged", (False, True), ids=("uniform", "ragged"))
def test_batched_gradients_cpu_dispatch_is_the_engine_scan_bitwise(ragged):
    """On the oracle path `ops.lstsq_grad_sampled_batch`, and through it
    `MTLProblem.task_grads_sampled`, give the per-event scan's bits: the
    batch and sharded engines' CPU contracts rest on it.  The batch holds
    duplicate tasks and the sharded engine's clamped foreign events."""
    t, n, d, b = 5, 40, 12, 8
    kx, ky, kw, ks = jax.random.split(jax.random.PRNGKey(11), 4)
    counts = jnp.asarray([40, 0, 3, 8, 17], jnp.int32) if ragged else None
    problem = MTLProblem(jax.random.normal(kx, (t, n, d)) / np.sqrt(d),
                         jax.random.normal(ky, (t, n)), "lstsq", "nuclear",
                         0.1, counts)
    ts = jnp.asarray([0, 3, 3, 0, 4, 1, 2, 0], jnp.int32)
    ws = jax.random.normal(kw, (ts.shape[0], d))
    seeds = jax.random.bits(ks, ts.shape, jnp.uint32)

    @jax.jit
    def scan(problem, ts, ws, seeds):
        def one(_, inp):
            return None, problem.task_grad_sampled(*inp, b)
        return jax.lax.scan(one, None, (ts, ws, seeds))[1]

    want = np.asarray(scan(problem, ts, ws, seeds))
    got = jax.jit(lambda p, *a: ops.lstsq_grad_sampled_batch(
        p.xs, p.ys, *a, batch_size=b, row_counts=p.row_counts,
        use_pallas=False))(problem, ts, ws, seeds)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert problem.sampled_grads_batched(b)
    got = jax.jit(lambda p, *a: p.task_grads_sampled(*a, b))(
        problem, ts, ws, seeds)
    np.testing.assert_array_equal(np.asarray(got), want)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(1, 40), st.integers(0, 40))
def test_masked_grad_matches_trimmed_dense_grad(seed, n, n_t_raw):
    """The masked lstsq gradient over a padded (n, d) buffer equals the
    dense gradient over the trimmed (n_t, d) cohort — ulp-tight, not
    bitwise (XLA reassociates across contraction sizes) — and the
    saturated sampled op equals the masked full grad bitwise."""
    n_t = min(n_t_raw, n)
    d = 7
    kx, kw, ky = jax.random.split(jax.random.PRNGKey(seed % 2**31), 3)
    x = jax.random.normal(kx, (n, d), jnp.float32)
    w = jax.random.normal(kw, (d,), jnp.float32)
    y = jax.random.normal(ky, (n,), jnp.float32)
    nt = jnp.asarray(n_t, jnp.int32)
    got = np.asarray(ref.lstsq_grad_masked_ref(x, w, y, nt), np.float64)
    x64 = np.asarray(x, np.float64)[:n_t]
    y64 = np.asarray(y, np.float64)[:n_t]
    want = 2.0 * (x64.T @ (x64 @ np.asarray(w, np.float64) - y64))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    sat = ops.lstsq_grad_sampled(x, w, y, jnp.asarray(seed, jnp.uint32),
                                 batch_size=n, n_t=nt, use_pallas=False)
    np.testing.assert_array_equal(
        np.asarray(sat), np.asarray(ops.lstsq_grad(x, w, y, n_t=nt,
                                                   use_pallas=False)))


@st.composite
def _ragged_stream_setups(draw):
    engine, eb = draw(st.sampled_from([("batch", 1), ("batch", 2),
                                       ("sharded", 2)]))
    counts = draw(st.lists(st.integers(0, _N), min_size=_T, max_size=_T))
    batch_size = draw(st.one_of(st.none(), st.integers(1, _N)))
    split = draw(st.integers(0, 3))
    seed = draw(st.integers(0, 2**31 - 1))
    return engine, eb, counts, batch_size, split, seed


@settings(max_examples=15, deadline=None)
@given(_ragged_stream_setups())
def test_row_counts_and_appends_leave_event_stream_untouched(setup):
    """row_counts — and a mid-session append that rebuilds the engine
    against a grown buffer — must not perturb the PRNG chain head or the
    (task, staleness) history: activation sampling is data-independent,
    so every staleness/shard-invariance contract survives raggedness."""
    engine, eb, counts, batch_size, split, seed = setup
    problem = _tiny_problem()
    ragged = problem._replace(row_counts=jnp.asarray(counts, jnp.int32))
    cfg = AMTLConfig(eta=1.0 / problem.lipschitz(), eta_k=0.6, tau=2,
                     engine=engine, event_batch=eb, prox_every=2,
                     batch_size=batch_size)
    mesh = None
    if engine == "sharded":
        from repro.launch.mesh import make_task_mesh
        mesh = make_task_mesh(1)
    eng_u = make_engine(problem, cfg, mesh)
    eng_r = make_engine(ragged, cfg, mesh)
    w0 = jnp.zeros((_D, _T), jnp.float32)
    key = jax.random.PRNGKey(seed)
    st_u = eng_u.run(eng_u.init(w0, key), None, 8)
    # ragged run with a mid-session append at `split` batches: pad one
    # more row onto every task's buffer and bump the counts — the
    # engine-rebuild boundary the serving platform crosses at a fold
    st_r = eng_r.run(eng_r.init(w0, key), None, 2 * split)
    grown = ragged._replace(
        xs=jnp.pad(ragged.xs, ((0, 0), (0, 1), (0, 0))),
        ys=jnp.pad(ragged.ys, ((0, 0), (0, 1))),
        row_counts=ragged.row_counts + 1)
    eng_g = make_engine(grown, cfg, mesh)
    st_r = eng_g.run(st_r, None, 8 - 2 * split)
    np.testing.assert_array_equal(np.asarray(st_u.key), np.asarray(st_r.key))
    np.testing.assert_array_equal(np.asarray(st_u.history.buf),
                                  np.asarray(st_r.history.buf))
    assert int(st_r.event) == 8


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, _N))
def test_minibatching_leaves_event_stream_untouched(seed, batch_size):
    """The sampling seeds are folded OFF the chain (fold_in derivations,
    never split): enabling batch_size must not perturb the PRNG chain head,
    so the (task, staleness) stream — and hence every staleness/shard
    contract — is identical to the full-gradient run's."""
    problem = _tiny_problem()
    eta = 1.0 / problem.lipschitz()
    full_cfg = AMTLConfig(eta=eta, eta_k=0.6, tau=2, engine="batch",
                          prox_every=2)
    sgd_cfg = full_cfg._replace(batch_size=batch_size)
    w0 = jnp.zeros((_D, _T), jnp.float32)
    key = jax.random.PRNGKey(seed)
    full_st = amtl_events_only(problem, full_cfg, w0, key, 10)
    sgd_st = amtl_events_only(problem, sgd_cfg, w0, key, 10)
    np.testing.assert_array_equal(np.asarray(full_st.key),
                                  np.asarray(sgd_st.key))
    np.testing.assert_array_equal(np.asarray(full_st.history.buf),
                                  np.asarray(sgd_st.history.buf))
    if batch_size >= _N:     # saturated minibatch IS the full gradient
        np.testing.assert_array_equal(np.asarray(full_st.v),
                                      np.asarray(sgd_st.v))
