#!/usr/bin/env python3
"""Smoke run of the AMTL engine and the learn-while-serve path on a TPU.

    python chip_smoke.py             # one chip: kernels, engine, serving
    python chip_smoke.py --chips 4   # engine='sharded' over four chips

Everything runs in this one process, through the entry points a user
calls (`ops.*`, `make_engine`, `AMTLServer`), on random data made from
`--seed`.  The script refuses to run anywhere but a TPU: it never falls
back to the CPU.

One chip runs three phases:

  kernels  each main-path Pallas kernel through its `ops.*` dispatch, at
           the engine and serve widths.  The compiled program must hold
           the kernel (`tpu_custom_call`), and its output must match the
           `kernels/ref.py` oracle evaluated on the host CPU: exactly for
           bits (sample mask, the undo columns that copy V), within
           FLOAT_RTOL for float outputs.
  engine   `make_engine(engine="batch")` at d=8192, T=128, n=4, tau=8,
           event_batch=32, prox_every=32, prox_rank=16: run(n + m) must
           equal run(run(n), m), the iterate must be finite and the
           objective must drop.
  serve    `AMTLServer` over a skewed ragged problem at d=4096, T=128 and
           a 512-row store capacity (1 GiB of f32 on the device), SGD
           minibatches of 32, unsupervised learner thread.  Predict
           requests stream while labeled feedback rounds are learned;
           the first fold doubles the store.  Afterwards the health
           counters must be clean and the served iterate must equal a
           fold/rebuild/`engine.run` replay of the chunk log.

`--chips 4` runs only the sharded engine, `prox_mode` "replicated" and
then "distributed", each against the batch engine on one of the four
chips over the same event stream.

Any failed phase exits 1 (2: no TPU, or too few chips).  On success the
last line of standard output is the JSON device record.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# Engine/machinery shape (the amtl_events bench's).
D, T, N, TAU, EVENT_BATCH, PROX_RANK = 8192, 128, 4, 8, 32, 16
# Serve shape: ragged store of up to N_S rows per task, SGD minibatches.
D_S, T_S, N_S, BATCH_SIZE = 4096, 128, 512, 32
CHUNK_EVENTS = 64          # one feedback round = one engine chunk
ROUNDS = 6                 # labeled feedback rounds
PREDICTS_PER_ROUND = 50    # predict requests streamed per round, at least
REQUEST_ROWS = 64          # rows per predict request
ROUND_TIMEOUT_S = 600.0

# f32 outputs computed on the chip in another order than the CPU oracle
# (MXU accumulation, the chip's log/cos): normwise relative error
# max|chip - cpu| / max|cpu| must stay below this.
FLOAT_RTOL = 1e-4
# Contracts that are bitwise on the CPU and checked to this normwise
# tolerance when the chip is not bitwise (the gap is printed either way).
REPLAY_RTOL = 1e-5
SHARDED_RTOL = 1e-4


def _rel_gap(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.size == 0:
        return 0.0
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


class Checks:
    """The checks of one phase.  Each prints its result and a failure is
    recorded, so one run reports every gap; `done()` then raises if any
    failed.  Unlike `assert`, the checks also hold under `python -O`."""

    def __init__(self):
        self.failed: list[str] = []

    def require(self, ok: bool, what: str) -> None:
        if not ok:
            print(f"  FAILED: {what}", flush=True)
            self.failed.append(what)

    def close(self, label: str, got, want, rtol: float) -> None:
        """Finite, and normwise relative gap to `want` within rtol."""
        gap = _rel_gap(got, want)
        bitwise = np.array_equal(np.asarray(got), np.asarray(want))
        print(f"  {label}: rel_gap={gap!r} bitwise={bitwise} (rtol {rtol})",
              flush=True)
        self.require(bool(np.all(np.isfinite(np.asarray(got)))),
                     f"{label}: non-finite")
        self.require(gap <= rtol, f"{label}: relative gap {gap} > {rtol}")

    def exact(self, label: str, got, want) -> None:
        got, want = np.asarray(got), np.asarray(want)
        if got.shape != want.shape:
            self.require(False, f"{label}: shape {got.shape} != {want.shape}")
            return
        mismatched = int(np.sum(got != want))
        print(f"  {label}: exact={mismatched == 0} ({mismatched} of "
              f"{got.size} differ)", flush=True)
        self.require(mismatched == 0, f"{label}: {mismatched} entries differ")

    def states(self, label: str, got, want, rtol: float) -> None:
        """Integer leaves exactly; float leaves bitwise or within rtol."""
        leaves = jax.tree_util.tree_leaves_with_path(got)
        for (path, a), b in zip(leaves, jax.tree.leaves(want), strict=True):
            name = label + jax.tree_util.keystr(path)
            if np.issubdtype(np.asarray(a).dtype, np.floating):
                self.close(name, a, b, rtol)
            else:
                self.exact(name, a, b)

    def done(self) -> None:
        if self.failed:
            raise AssertionError(f"{len(self.failed)} check(s) failed: "
                                 + "; ".join(self.failed))


def _device_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    return (f"bytes_in_use {stats.get('bytes_in_use')}, "
            f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


# ------------------------------------------------------------------ data

@functools.partial(jax.jit, static_argnames=("t", "n", "d"))
def _lowrank_data(key, t: int, n: int, d: int, row_counts):
    """xs (t, n, d) ~ N(0, 1/d); ys = x . w*_t + 0.1 noise over a rank-8
    ground truth w* (d, t); rows >= row_counts[t] zeroed (store padding)."""
    kx, ku, kv, ke = jax.random.split(key, 4)
    w_true = (jax.random.normal(ku, (d, 8)) @ jax.random.normal(kv, (8, t))
              / jnp.sqrt(8.0))
    xs = jax.random.normal(kx, (t, n, d)) / jnp.sqrt(float(d))
    ys = (jnp.einsum("tnd,dt->tn", xs, w_true)
          + 0.1 * jax.random.normal(ke, (t, n)))
    valid = jnp.arange(n)[None, :] < row_counts[:, None]
    return (jnp.where(valid[..., None], xs, 0.0), jnp.where(valid, ys, 0.0),
            w_true)


# --------------------------------------------------------------- kernels

def phase_kernels(seed: int, chk: Checks) -> None:
    from repro.core.prox import sketch_width
    from repro.kernels import ops, ref

    cpu = jax.devices("cpu")[0]
    rng = np.random.default_rng(seed)
    f32 = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    seed_u = np.uint32(rng.integers(0, 2**32))

    def both(label, chip, oracle, *args):
        """(chip output, CPU oracle output) of one kernel call.  `chip`
        calls the `ops.*` dispatch; its compiled program must hold the
        Pallas kernel, not the jnp oracle."""
        chip = jax.jit(chip)
        on_chip = [jnp.asarray(a) for a in args]
        t0 = time.perf_counter()
        text = chip.lower(*on_chip).compile().as_text()
        chk.require("tpu_custom_call" in text,
                    f"{label}: compiled program has no Pallas kernel")
        out = jax.block_until_ready(chip(*on_chip))
        print(f"  {label}: compile + run {time.perf_counter() - t0!r} s",
              flush=True)
        return out, jax.jit(oracle)(*jax.device_put(args, cpu))

    # amtl_event_batch at the engine shape, with an in-batch duplicate.
    tasks = rng.integers(0, T, EVENT_BATCH).astype(np.int32)
    tasks[5] = tasks[2]
    first = np.asarray([t not in tasks[:i] for i, t in enumerate(tasks)])
    (v_new, undo), (v_ref, undo_ref) = both(
        "amtl_event_batch", ops.amtl_event_batch, ref.amtl_event_batch_ref,
        f32(D, T), f32(D, EVENT_BATCH), f32(D, EVENT_BATCH), tasks,
        np.float32(0.05), rng.uniform(0.1, 0.9, EVENT_BATCH).astype(
            np.float32))
    chk.close("amtl_event_batch v_new", v_new, v_ref, FLOAT_RTOL)
    # An event's undo column is V's column as it read it: a copy of V for
    # a task's first event in the batch, an in-batch update's output for
    # a later duplicate.
    undo, undo_ref = np.asarray(undo), np.asarray(undo_ref)
    chk.exact("amtl_event_batch undo (first events of a task)",
              undo[first], undo_ref[first])
    chk.close("amtl_event_batch undo (in-batch duplicates)",
              undo[~first], undo_ref[~first], FLOAT_RTOL)

    # lstsq_grad_sampled at the gradient shape, uniform and ragged, and at
    # the grown serve capacity (several row strips per call).
    for n, n_t in ((N_S, None), (N_S, 300), (2 * N_S, 700)):
        data = (f32(n, D_S), f32(D_S), f32(n), seed_u)
        if n_t is None:
            got, want = both(
                f"lstsq_grad_sampled n={n}",
                lambda *a: ops.lstsq_grad_sampled(*a, batch_size=BATCH_SIZE),
                lambda *a: ref.lstsq_grad_sampled_ref(*a, BATCH_SIZE), *data)
        else:
            got, want = both(
                f"lstsq_grad_sampled n={n} n_t={n_t}",
                lambda x, w, y, s, k: ops.lstsq_grad_sampled(
                    x, w, y, s, batch_size=BATCH_SIZE, n_t=k),
                lambda x, w, y, s, k: ref.lstsq_grad_sampled_masked_ref(
                    x, w, y, s, BATCH_SIZE, k), *data, np.int32(n_t))
        chk.close(f"lstsq_grad_sampled n={n} n_t={n_t}", got, want,
                  FLOAT_RTOL)

    # sample_mask: the in-kernel selection bits, uniform and ragged.
    got, want = both(
        "sample_mask", lambda s: ops.sample_mask(N_S, BATCH_SIZE, s),
        lambda s: ref.sample_mask_ref(N_S, BATCH_SIZE, s), seed_u)
    chk.exact("sample_mask", got, want)
    got, want = both(
        "sample_mask n_t=300",
        lambda s, k: ops.sample_mask(N_S, BATCH_SIZE, s, n_t=k),
        lambda s, k: ref.sample_mask_masked_ref(N_S, BATCH_SIZE, s, k),
        seed_u, np.int32(300))
    chk.exact("sample_mask n_t=300", got, want)

    # gauss_sketch: the serial prox's full iterate and one shard's block.
    p = sketch_width(PROX_RANK, D, T)
    for t_local, off in ((T, 0), (T // 4, T // 4)):
        label = f"gauss_sketch t={t_local} row_offset={off}"
        got, want = both(
            label, lambda w, s, o: ops.gauss_sketch(w, s, o, p=p),
            lambda w, s, o: ref.gauss_sketch_ref(w, s, o, p),
            f32(D, t_local), seed_u, np.int32(off))
        chk.close(label, got, want, FLOAT_RTOL)

    got, want = both("svt_reconstruct", ops.svt_reconstruct,
                     ref.svt_reconstruct_ref, f32(D, p), np.abs(f32(p)),
                     f32(p, T))
    chk.close("svt_reconstruct", got, want, FLOAT_RTOL)


# ---------------------------------------------------------------- engine

def _engine_problem(seed: int):
    from repro.core import MTLProblem, default_config

    xs, ys, _ = _lowrank_data(jax.random.PRNGKey(seed), T, N, D,
                              jnp.full((T,), N, jnp.int32))
    problem = MTLProblem(xs, ys, "lstsq", "nuclear", 0.1)
    cfg = default_config(problem, tau=TAU, engine="batch",
                         event_batch=EVENT_BATCH, prox_every=EVENT_BATCH,
                         prox_rank=PROX_RANK)
    return problem, cfg


def phase_engine(seed: int, chk: Checks) -> None:
    from repro.core import make_engine

    problem, cfg = _engine_problem(seed)
    engine = make_engine(problem, cfg)
    w0 = jnp.zeros((D, T), jnp.float32)
    s0 = engine.init(w0, jax.random.PRNGKey(seed + 1))
    n1, n2 = 4 * EVENT_BATCH, 6 * EVENT_BATCH

    t0 = time.perf_counter()
    whole = jax.block_until_ready(engine.run(s0, None, n1 + n2))
    t1 = time.perf_counter()
    split = jax.block_until_ready(
        engine.run(engine.run(s0, None, n1), None, n2))
    t2 = time.perf_counter()
    again = jax.block_until_ready(engine.run(s0, None, n1 + n2))
    t3 = time.perf_counter()
    print(f"  run({n1 + n2}) first call (compile + run) {t1 - t0!r} s; "
          f"run({n1}) + run({n2}) first calls {t2 - t1!r} s; "
          f"run({n1 + n2}) cached {t3 - t2!r} s", flush=True)

    chk.states("run(n+m) vs run(run(n), m)", whole, split, REPLAY_RTOL)
    chk.exact("run(n+m) repeated", again.v, whole.v)
    v = np.asarray(engine.iterate(whole))
    chk.require(bool(np.all(np.isfinite(v))), "engine iterate is not finite")
    chk.require(int(whole.event) == n1 + n2, f"event count {whole.event}")
    objective = jax.jit(lambda prob, w: prob.objective(w))
    f0 = float(objective(problem, w0))
    f1 = float(objective(problem, jnp.asarray(v)))
    print(f"  objective {f0!r} -> {f1!r} over {n1 + n2} events", flush=True)
    chk.require(f1 < f0, "objective did not decrease")


# ----------------------------------------------------------------- serve

def _serve_problem(seed: int):
    from repro.core import MTLProblem

    # Skewed cohorts: task t holds 512 / (1 + t % 16) rows (512 .. 32).
    counts = (N_S // (1 + np.arange(T_S) % 16)).astype(np.int32)
    xs, ys, w_true = _lowrank_data(jax.random.PRNGKey(seed), T_S, N_S, D_S,
                                   jnp.asarray(counts))
    return (MTLProblem(xs, ys, "lstsq", "nuclear", 0.1, jnp.asarray(counts)),
            np.asarray(w_true))


def _serve_cfg():
    from repro.core import AMTLConfig, amtl_max_step

    # eta < 2/L: rows ~ N(0, 1/d) give sigma_max(X_t)^2 <= (1 +
    # sqrt(n/d))^2 = 2.25 up to n = 1024 rows, so L = 2 sigma^2 <= 4.5.
    return AMTLConfig(eta=0.2, eta_k=amtl_max_step(TAU, T_S), tau=TAU,
                      engine="batch", event_batch=EVENT_BATCH,
                      prox_every=EVENT_BATCH, prox_rank=PROX_RANK,
                      batch_size=BATCH_SIZE)


def _feedback_rounds(seed: int, w_true: np.ndarray):
    """ROUNDS x CHUNK_EVENTS labeled rows from the data's law.  Half of
    round 0 goes to task 0, which already fills the 512-row capacity, so
    the first fold doubles the store."""
    rng = np.random.default_rng(seed + 2)
    rounds = []
    for r in range(ROUNDS):
        t = rng.zipf(1.5, CHUNK_EVENTS).astype(np.int64) % T_S
        if r == 0:
            t[: CHUNK_EVENTS // 2] = 0
        x = (rng.standard_normal((CHUNK_EVENTS, D_S))
             / np.sqrt(D_S)).astype(np.float32)
        y = (np.einsum("kd,dk->k", x, w_true[:, t])
             + 0.1 * rng.standard_normal(CHUNK_EVENTS)).astype(np.float32)
        rounds.append((t, x, y))
    return rounds


def phase_serve(seed: int, chk: Checks) -> None:
    from repro.core import make_engine
    from repro.data import TaskStore
    from repro.serve import AMTLServer, ServeConfig

    problem, w_true = _serve_problem(seed)
    cfg = _serve_cfg()
    key = jax.random.PRNGKey(seed + 3)
    w0 = jnp.zeros((D_S, T_S), jnp.float32)
    rows0 = int(np.sum(np.asarray(problem.row_counts)))
    print(f"  store: {problem.xs.nbytes} bytes of xs on the device, "
          f"{rows0} rows, capacity {problem.xs.shape[1]}; {_device_bytes()}",
          flush=True)
    # The replay starts from a host copy of the store as served.
    replay_store = TaskStore.from_problem(problem)

    rng = np.random.default_rng(seed + 4)
    requests = [(rng.integers(0, T_S, REQUEST_ROWS),
                 (rng.standard_normal((REQUEST_ROWS, D_S))
                  / np.sqrt(D_S)).astype(np.float32)) for _ in range(16)]
    rounds = _feedback_rounds(seed, w_true)

    server = AMTLServer(problem, cfg, w0, key,
                        ServeConfig(chunk_events=CHUNK_EVENTS,
                                    max_batch=REQUEST_ROWS,
                                    restart_limit=None))
    jax.block_until_ready(server.predict(*requests[0]))
    server.start_learner()
    n_predicts, round_s = 0, []
    try:
        for r, (t, x, y) in enumerate(rounds):
            t0 = time.perf_counter()
            receipt = server.submit_feedback(t, x, y)
            chk.require(receipt == (CHUNK_EVENTS, 0), repr(receipt))
            target, k = CHUNK_EVENTS * (r + 1), 0
            while k < PREDICTS_PER_ROUND or sum(server.chunk_log) < target:
                jax.block_until_ready(
                    server.predict(*requests[n_predicts % len(requests)]))
                n_predicts += 1
                k += 1
                if not server.learner_running:
                    server.stop_learner()  # re-raises what killed it
                    raise RuntimeError("the learner thread stopped")
                if time.perf_counter() - t0 > ROUND_TIMEOUT_S:
                    raise TimeoutError(f"round {r}: no chunk after "
                                       f"{ROUND_TIMEOUT_S} s")
                if k >= PREDICTS_PER_ROUND:
                    time.sleep(0.001)      # leave the GIL to the learner
            round_s.append(time.perf_counter() - t0)
    finally:
        learned = server.stop_learner(drain=True)
    print(f"  {n_predicts} predict requests, {learned} events learned, "
          f"chunk_log={server.chunk_log}, per-round seconds {round_s!r}",
          flush=True)

    stats = server.stats()
    health = stats["health"]
    print(f"  health: {json.dumps(health)}", flush=True)
    chk.require(health["nonfinite_chunks"] == 0
                and health["quarantined_feedback"] == 0
                and health["nonfinite_feedback"] == 0
                and health["learner_restarts"] == 0
                and health["learner_crashes"] == 0
                and not health["breaker_tripped"], "unhealthy serve run")
    chk.require(stats["rejected_feedback"] == 0, "feedback was rejected")
    chunk_log = list(server.chunk_log)
    chk.require(chunk_log == [CHUNK_EVENTS] * ROUNDS, f"chunk_log {chunk_log}")
    chk.require(server.store_rows == rows0 + ROUNDS * CHUNK_EVENTS,
                f"store_rows {server.store_rows}")
    capacity = server.problem.xs.shape[1]
    print(f"  store grew to {server.store_rows} rows, capacity {capacity}; "
          f"{_device_bytes()}", flush=True)
    chk.require(capacity >= 2 * N_S, "the folds did not double the store")

    # Scores off the committed snapshot, against the host's x . v[:, t].
    v = np.asarray(server.iterate())
    chk.require(bool(np.all(np.isfinite(v))), "served iterate is not finite")
    t, x = requests[0]
    chk.close("predict scores", server.predict(t, x),
              np.einsum("kd,dk->k", x.astype(np.float64), v[:, t]),
              FLOAT_RTOL)

    # Replay: fold the same rows at the same boundaries, rebuild, run.
    # Each run is waited for: an in-flight run keeps its 2 GiB store
    # alive, and six of them queued at once nearly fill the chip.
    engine = make_engine(replay_store.problem(), cfg)
    state = engine.init(w0, key)
    for (t, x, y), n in zip(rounds, chunk_log):
        replay_store.append(t, x, y)
        engine = make_engine(replay_store.problem(), cfg)
        state = jax.block_until_ready(engine.run(state, None, n))
    chk.close("served iterate vs replay", v,
              np.asarray(engine.iterate(state)), REPLAY_RTOL)
    print(f"  after the replay: {_device_bytes()}", flush=True)


# --------------------------------------------------------------- sharded

def phase_sharded(seed: int, chk: Checks) -> None:
    from repro.core import make_engine
    from repro.launch.mesh import make_task_mesh

    problem, cfg = _engine_problem(seed)
    key = jax.random.PRNGKey(seed + 1)
    w0 = jnp.zeros((D, T), jnp.float32)
    n_events = 10 * EVENT_BATCH
    batch = make_engine(problem, cfg)
    want = jax.block_until_ready(batch.run(batch.init(w0, key), None,
                                           n_events))
    print(f"  batch engine on {want.v.devices()}", flush=True)
    mesh = make_task_mesh(4)
    for mode in ("replicated", "distributed"):
        engine = make_engine(problem,
                             cfg._replace(engine="sharded", prox_mode=mode),
                             mesh)
        t0 = time.perf_counter()
        got = jax.block_until_ready(engine.run(engine.init(w0, key), None,
                                               n_events))
        shards = got.v.addressable_shards
        placement = sorted((s.device.id, s.data.shape) for s in shards)
        print(f"  {mode}: {time.perf_counter() - t0!r} s (compile + run); "
              f"v shards {placement}", flush=True)
        chk.require(len({s.device for s in shards}) == 4
                    and all(s.data.shape == (D, T // 4) for s in shards),
                    f"v is not split over four chips: {placement}")
        # The event stream (task ring, delay history, PRNG chain) exactly;
        # the iterate within SHARDED_RTOL.
        for name in ("task_ring", "ptr", "event", "key", "history"):
            chk.states(f"{mode} {name}", getattr(got, name),
                       getattr(want, name), 0.0)
        chk.close(f"{mode} v vs batch engine", got.v, want.v, SHARDED_RTOL)


# ------------------------------------------------------------------ main

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels, engine and serving on one chip; "
                         "4: only the sharded engine over four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every random input")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    phases = ([("kernels", phase_kernels), ("engine", phase_engine),
               ("serve", phase_serve)] if args.chips == 1
              else [("sharded", phase_sharded)])
    failed = []
    for name, phase in phases:
        print(f"phase {name}:", flush=True)
        t0 = time.perf_counter()
        try:
            chk = Checks()
            phase(args.seed, chk)
            chk.done()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        print(f"phase {name}: {'FAILED' if name in failed else 'ok'} in "
              f"{time.perf_counter() - t0!r} s; {_device_bytes()}",
              flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
