"""Learning-while-serving platform over the AMTL session API.

`AMTLServer` holds a long-lived `AMTLEngine` (`core.amtl.make_engine`) —
the paper's central server, kept learning while task nodes stream events
at it — and splits its two duties onto two CONCURRENT paths:

  * request path — `predict(task_ids, features)` micro-batches incoming
    (task_id, features) rows (bucketed padding, so distinct batch sizes
    reuse a handful of jit traces) and scores them off the committed
    serving snapshot.  The snapshot is read with ONE atomic reference
    load; the request path never takes the learner's state lock, so a
    prediction never waits on an in-flight `run` chunk or the server
    prox refresh inside it.
  * feedback path — `submit_feedback(task_ids, features=None,
    labels=None)` enqueues labeled feedback, now actually CARRYING the
    labels: an accepted item with `(features, labels)` is both one
    future engine event and one new data row for its task.  The chunk
    runner (the background learner thread via `start_learner()`, or
    the cooperative `step()`) first folds the accepted rows into the
    server's `TaskStore` (`data.store`) AT THE CHUNK BOUNDARY — the
    published ragged problem snapshot, and with it the rebuilt engine,
    changes only between chunks, never under a running one — then
    coalesces the queue into ONE engine chunk (a multiple of
    `engine.events_per_step`), advances the session with `engine.run`,
    and flips the serving snapshot at the chunk boundary.

Label-free feedback (`features=None`) is the PR-8 path unchanged: no
store is ever created, the problem and engine objects are never
rebuilt, and every PR-8 bitwise contract holds verbatim.  The store is
created lazily (`TaskStore.from_problem`) at the first fold; because
its initial capacity is exactly the problem's row budget, the fold
boundary — not store creation — is what first changes the problem.

Threading model (PR 8; components in `serve.learner` / `serve.admission`):

  * State lock (`_state_lock`, learner-side only): serializes
    coalesce -> `engine.run` -> materialize -> flip, `checkpoint()`, and
    the cooperative `step()`.  Held for the whole chunk.
  * Queue lock (`_queue_lock`): guards the pending-feedback counters,
    shared by `submit_feedback` (any thread) and the coalescer.  Never
    held across engine work.
  * Atomic flip: the serving snapshot is an immutable `(iterate, event)`
    pair reassigned as ONE reference ONLY after
    `jax.block_until_ready` — a reader sees the old committed snapshot
    or the new committed snapshot, never a torn or in-flight one.
  * Lifecycle: `start_learner()` / `stop_learner(drain=...)`; learner
    exceptions are captured and re-raised on stop/join; the
    auto-checkpoint cadence runs on the learner thread unchanged.

Double-buffer equivalence contract (tests/test_serve.py,
tests/test_serve_threaded.py — unchanged from PR 7, now also under a
concurrent predict load):

  * Zero feedback: the served iterate is BITWISE
    `engine.iterate(engine.init(v0, key))` — a frozen server serves
    exactly the frozen engine.
  * With feedback: after any sequence of chunk boundaries (cooperative
    OR on the learner thread) the engine state is BITWISE
    `engine.run(engine.init(v0, key), offs, sum(chunk_log))` over the
    same coalesced chunk sizes, every served snapshot is bitwise some
    chunk-boundary `engine.iterate`, and draining the learner with no
    concurrent submissions reproduces the cooperative `step()` loop's
    chunk log exactly (coalescing is deterministic in the queue).
  * With label-carrying feedback: after any sequence of chunk
    boundaries the engine state is BITWISE the replay of the same
    coalesced chunk log with the same rows folded at the same
    boundaries — fold, rebuild, `engine.run` — over ONE engine
    session; the store snapshot at every boundary is itself bitwise
    the replayed `TaskStore.append` sequence.
  * Restart: `AMTLServer.resume(...)` from a rotated checkpoint is
    invisible to subsequent predictions (pending, not-yet-run feedback
    is the one thing a crash loses; clients re-submit — the standard
    at-most-once queue contract).  `checkpoint()` writes the store
    (when one exists) FIRST under `<ckpt_dir>/store/` at the same
    step, then the engine state: resume restores the engine at its
    newest step and the store record paired with it, so the rebuilt
    problem, engine, and state — and therefore every subsequent
    prediction and chunk — are bitwise the uninterrupted server's.

Latency-SLO-driven admission (`ServeConfig.slo_ms`): the request path
records per-batch predict latency into a `LatencySLOController`
(`serve.admission`), which deterministically shrinks the admitted chunk
budget while the rolling p95 violates the SLO and restores it while the
tail is healthy — the chunk-size trace is a pure function of the
recorded latency sequence, logged in `stats()["slo"]`.  With
`slo_shed=True` a degraded controller also sheds NEW feedback at
admission (predictions always flow).

Per-task admission/QoS (`max_pending_per_task`, `task_chunk_quota`)
bounds what one bursty task can inject: excess queue depth is rejected
at admission, and each chunk consumes at most `task_chunk_quota` events
per task — drained round-robin from a rotating start offset — so a
flood on one task can neither evict other tasks' pending feedback nor
starve the per-chunk event budget.

Fault tolerance (PR 10):

  * Supervised learner: with `ServeConfig.restart_limit` set,
    `start_learner()` wraps the thread in a `LearnerSupervisor`
    (`serve.learner`) — a crashed learner auto-restarts under
    exponential backoff, re-serving the last committed snapshot; once
    the budget is exhausted the server's circuit breaker latches it
    into frozen-serving mode (predictions flow, feedback rejected with
    receipt reason "breaker") and the terminal exception surfaces on
    `stop_learner()`.  `restart_limit=None` (default) is the PR-8
    unsupervised learner, byte for byte.
  * Non-finite guard: `submit_feedback` rejects rows with non-finite
    features/labels at admission (reason "nonfinite"); `_step_once`
    checks the freshly materialized iterate with one `isfinite`
    reduction BEFORE the flip — on failure the chunk is discarded, the
    engine state stays at the last committed one, the rows folded at
    that boundary are rolled back out of the store bitwise
    (`TaskStore.rollback`), and the coalesced events are quarantined
    (logged per task in `stats()["health"]`, never re-queued).  The
    served snapshot can never go non-finite, and a poisoned chunk can
    never reach a checkpoint (checkpoints happen after the guard).
  * Deterministic fault injection: a `serve.faults.FaultPlan` threads
    scripted failure points (chunk crash, iterate poison, feedback NaN,
    checkpoint crash-split) through this control flow behind a no-op
    default; `resume` bridges torn/corrupt records via
    `checkpoint.latest_valid_step` and drops to older store records on
    `CheckpointCorruptError`.  Telemetry: `stats()["health"]`.
"""
from __future__ import annotations

import functools
import os
import threading
import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.checkpoint import CheckpointCorruptError
from repro.core.amtl import AMTLConfig, make_engine
from repro.core.losses import MTLProblem, get_loss
from repro.data.store import TaskStore
from repro.serve.admission import make_controller
from repro.serve.faults import FaultPlan
from repro.serve.learner import BackgroundLearner, LearnerSupervisor

Array = jax.Array


class ServeConfig(NamedTuple):
    """Serving-side knobs (the engine itself is configured by AMTLConfig).

    chunk_events         per-chunk event budget: at most this many engine
                         events are coalesced per chunk (must be a
                         positive multiple of `engine.events_per_step`).
                         With an SLO set this is the level-0 budget the
                         admission controller degrades from.
    task_chunk_quota     QoS: max events ONE task contributes to a chunk
                         (None = no per-task cap, the budget still caps
                         the chunk).  Drained round-robin from a rotating
                         offset so tied tasks alternate priority.
    max_pending_per_task admission: feedback beyond this per-task queue
                         depth is rejected at `submit_feedback` (None =
                         unbounded queue).
    learning             False freezes the server: feedback is rejected
                         and `step()` is a no-op — the served iterate
                         stays bitwise `engine.iterate(init_state)`.
    ckpt_dir             checkpoint directory (None disables checkpoints).
    checkpoint_every     auto-checkpoint after this many learned events
                         (None = only explicit `checkpoint()` calls).
    keep_last            rotation: keep only the k newest `step_*.npz`
                         records (repro.checkpoint.save semantics).
    max_batch            predict micro-batch ceiling: larger request
                         batches are served in `max_batch` slices;
                         smaller ones are padded to the next power of
                         two, bounding the number of jit traces.
    slo_ms               predict-latency SLO in ms (None disables the
                         admission controller and latency recording).
                         When set, `predict` blocks on its scores and
                         records the per-batch wall latency.
    slo_window           tumbling-window size (latency samples) between
                         controller decisions.
    slo_shed             True: while the controller is degraded, NEW
                         feedback is shed at admission (rejected) so the
                         backlog cannot grow against a violated SLO.
                         Requires slo_ms.
    restart_limit        fault tolerance: number of learner-thread
                         crashes `start_learner()`'s supervisor will
                         auto-restart through before tripping the
                         circuit breaker (frozen-serving mode).  None
                         (default) = unsupervised PR-8 learner: a crash
                         parks until surfaced on stop.
    restart_backoff_s    base of the supervisor's exponential restart
                         backoff: crash k waits backoff * 2**k seconds.
    """
    chunk_events: int = 32
    task_chunk_quota: Optional[int] = None
    max_pending_per_task: Optional[int] = None
    learning: bool = True
    ckpt_dir: Optional[str] = None
    checkpoint_every: Optional[int] = None
    keep_last: Optional[int] = None
    max_batch: int = 256
    slo_ms: Optional[float] = None
    slo_window: int = 32
    slo_shed: bool = False
    restart_limit: Optional[int] = None
    restart_backoff_s: float = 0.05


class FeedbackReceipt(tuple):
    """An (accepted, rejected) pair with a `reason` annotation.

    Still compares and unpacks as the plain 2-tuple it has always been
    (`receipt == (3, 7)`, `a, r = receipt`); `reason` rides along as an
    instance attribute naming why rows were rejected — None, "frozen",
    "breaker" (learner circuit breaker latched), "shed" (SLO),
    "nonfinite" (non-finite features/labels), or "admission" (per-task
    queue cap).  When one call rejects for several reasons the most
    severe wins (breaker > frozen > shed > nonfinite > admission).
    """
    reason: Optional[str]

    def __new__(cls, accepted: int, rejected: int,
                reason: Optional[str] = None):
        self = super().__new__(cls, (int(accepted), int(rejected)))
        self.reason = reason
        return self

    @property
    def accepted(self) -> int:       # enqueued for a future chunk
        return self[0]

    @property
    def rejected(self) -> int:       # capped, shed, frozen, or non-finite
        return self[1]

    def __repr__(self) -> str:
        return (f"FeedbackReceipt(accepted={self[0]}, rejected={self[1]}, "
                f"reason={self.reason!r})")


class ServingSnapshot(NamedTuple):
    """The committed serving state, flipped as one atomic reference:
    `v` is a fully-materialized chunk-boundary `engine.iterate`, `event`
    the engine event count it was committed at."""
    v: Array
    event: int


@functools.partial(jax.jit, static_argnames=("loss_name",))
def _predict_scores(v: Array, task_ids: Array, x: Array,
                    loss_name: str) -> Array:
    """Row scores off the served iterate: loss-specific link of x_i·v[:, t_i]."""
    cols = v[:, task_ids].T                       # (B, d)
    return get_loss(loss_name).predict(jnp.sum(x * cols, axis=-1))


def _bucket(n: int, cap: int) -> int:
    m = 1
    while m < n:
        m <<= 1
    return min(m, cap)


class AMTLServer:
    """A long-lived learning-while-serving AMTL session (see module doc)."""

    def __init__(self, problem: MTLProblem, cfg: AMTLConfig, v0: Array,
                 key: Array, serve_cfg: ServeConfig = ServeConfig(), *,
                 mesh=None, delay_offsets: Array | None = None,
                 fault_plan: Optional[FaultPlan] = None):
        self._configure(problem, cfg, v0, key, serve_cfg, mesh=mesh,
                        delay_offsets=delay_offsets, fault_plan=fault_plan)
        self._install_state(self.engine.init(v0, key))

    def _configure(self, problem: MTLProblem, cfg: AMTLConfig, v0: Array,
                   key: Array, serve_cfg: ServeConfig, *, mesh=None,
                   delay_offsets: Array | None = None,
                   fault_plan: Optional[FaultPlan] = None) -> None:
        """Everything construction-time except building/serving a state
        (shared by `__init__` and `resume`, which install different
        states — the fresh init vs the restored checkpoint)."""
        self.problem = problem
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        self._mesh = mesh
        self.engine = make_engine(problem, cfg, mesh)
        per = self.engine.events_per_step
        if serve_cfg.chunk_events < per \
                or serve_cfg.chunk_events % per != 0:
            raise ValueError(
                f"chunk_events ({serve_cfg.chunk_events}) must be a "
                f"positive multiple of the engine's events_per_step "
                f"({per}) so every coalesced chunk is runnable")
        if serve_cfg.task_chunk_quota is not None \
                and serve_cfg.task_chunk_quota < 1:
            raise ValueError(
                f"task_chunk_quota must be >= 1 or None, got "
                f"{serve_cfg.task_chunk_quota}")
        if serve_cfg.max_pending_per_task is not None \
                and serve_cfg.max_pending_per_task < 1:
            raise ValueError(
                f"max_pending_per_task must be >= 1 or None, got "
                f"{serve_cfg.max_pending_per_task}")
        if serve_cfg.checkpoint_every is not None \
                and serve_cfg.ckpt_dir is None:
            raise ValueError("checkpoint_every is set but ckpt_dir is None "
                             "— there is nowhere to write the checkpoints")
        if serve_cfg.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got "
                             f"{serve_cfg.max_batch}")
        if serve_cfg.slo_shed and serve_cfg.slo_ms is None:
            raise ValueError("slo_shed requires slo_ms — there is no "
                             "controller to decide when to shed")
        if serve_cfg.restart_limit is not None \
                and serve_cfg.restart_limit < 0:
            raise ValueError(
                f"restart_limit must be >= 0 or None, got "
                f"{serve_cfg.restart_limit} (None = unsupervised learner)")
        if serve_cfg.restart_backoff_s < 0:
            raise ValueError(f"restart_backoff_s must be >= 0, got "
                             f"{serve_cfg.restart_backoff_s}")
        self._slo = make_controller(serve_cfg.slo_ms, serve_cfg.chunk_events,
                                    per, serve_cfg.slo_window)
        # Fault injection: a no-op plan unless a scripted one is given,
        # so the guarded control flow is identical with and without
        # faults armed (each hook is an integer compare).
        self._faults = fault_plan if fault_plan is not None else FaultPlan()
        self._delay_offsets = delay_offsets
        self._pending = np.zeros(problem.num_tasks, np.int64)
        # Label-carrying feedback: accepted (task_id, x_row, y) rows in
        # arrival order, folded into the store at the next chunk
        # boundary.  The store itself is created lazily at the first
        # fold — the label-free path never touches it.
        self._pending_rows: list[tuple[int, np.ndarray, np.float32]] = []
        self._store: Optional[TaskStore] = None
        self._rr = 0                       # rotating round-robin offset
        self.chunk_log: list[int] = []     # coalesced chunk sizes, in order
        # Locks, narrowest-scope first (see module doc threading model):
        # the request path takes NONE of them to read the snapshot.
        self._state_lock = threading.RLock()   # chunk run / checkpoint
        self._queue_lock = threading.Lock()    # pending counters + _rr
        self._stats_lock = threading.Lock()    # request-path counters
        self._learner: Optional[BackgroundLearner | LearnerSupervisor] = None
        self._events_since_ckpt = 0
        self._n_requests = 0
        self._n_predictions = 0
        self._n_rejected = 0
        self._n_shed = 0
        # Fault-tolerance telemetry (stats()["health"]):
        self._breaker_exc: Optional[BaseException] = None
        self._n_breaker_rejected = 0
        self._n_nonfinite_fb = 0       # rows rejected at admission
        self._n_nonfinite_chunks = 0   # chunks discarded by the guard
        self._n_quarantined = 0        # events quarantined by the guard
        self._quarantine_log: list[dict[int, int]] = []  # per-task counts

    def _install_state(self, state) -> None:
        """Serve `state`: materialize its iterate and commit the serving
        snapshot (the only place besides `_step_once` that flips it)."""
        self._state = state
        v = jax.block_until_ready(self.engine.iterate(state))
        self._serving = ServingSnapshot(v, int(state.event))

    # ------------------------------------------------------- request path
    def predict(self, task_ids, features) -> Array:
        """Score a micro-batch of (task_id, features) rows.

        Served off the committed snapshot (one atomic reference read):
        never blocks on a running chunk or prox refresh, never takes the
        learner's lock.  Batches above `max_batch` are served in slices;
        smaller ones pad to the next power of two (same trace).  An
        empty request batch returns an empty (0,) score array.  With an
        SLO set, the call blocks on its scores and records the per-batch
        latency into the admission controller.
        """
        t = np.asarray(task_ids, np.int32).reshape(-1)
        x = jnp.asarray(features)
        if x.ndim != 2 or x.shape[0] != t.shape[0] \
                or x.shape[1] != self.problem.dim:
            raise ValueError(
                f"features must be (len(task_ids), d) = "
                f"({t.shape[0]}, {self.problem.dim}), got {x.shape}")
        if t.size and (t.min() < 0 or t.max() >= self.problem.num_tasks):
            raise ValueError(
                f"task_ids must be in [0, {self.problem.num_tasks}), got "
                f"range [{t.min()}, {t.max()}]")
        snap = self._serving                  # ONE atomic reference read
        with self._stats_lock:
            self._n_requests += 1
            self._n_predictions += int(t.shape[0])
        if t.shape[0] == 0:
            # the slice loop below never runs — return the empty score
            # vector in the link's dtype instead of concatenating nothing
            return jnp.zeros((0,), jnp.result_type(x.dtype, snap.v.dtype))
        t0 = time.perf_counter() if self._slo is not None else 0.0
        cap = self.serve_cfg.max_batch
        outs = []
        with jax.profiler.TraceAnnotation("serve.predict",
                                          rows=int(t.shape[0])):
            for lo in range(0, t.shape[0], cap):
                ts = t[lo:lo + cap]
                xs = x[lo:lo + cap]
                m = _bucket(ts.shape[0], cap)
                pad = m - ts.shape[0]
                if pad:
                    ts = np.pad(ts, (0, pad))
                    xs = jnp.pad(xs, ((0, pad), (0, 0)))
                scores = _predict_scores(snap.v, jnp.asarray(ts), xs,
                                         self.problem.loss_name)
                outs.append(scores[:m - pad] if pad else scores)
            out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
            if self._slo is not None:
                jax.block_until_ready(out)    # latency = computed scores
                self._slo.record(1e3 * (time.perf_counter() - t0))
        return out

    def iterate(self) -> Array:
        """The committed serving iterate (the snapshot's V)."""
        return self._serving.v

    def serving(self) -> ServingSnapshot:
        """The committed `(iterate, event)` snapshot, read atomically."""
        return self._serving

    # ------------------------------------------------------ feedback path
    def submit_feedback(self, task_ids, features=None,
                        labels=None) -> FeedbackReceipt:
        """Enqueue labeled feedback; each accepted item is one future
        engine event.

        `features` (k, d) and `labels` (k,) optionally carry the actual
        labeled rows (all-or-none: both or neither).  An accepted item
        with a row is folded into the server's `TaskStore` at the next
        chunk boundary — BEFORE that chunk runs — growing its task's
        cohort; a rejected item's row is dropped with its event
        (admission cap hit, SLO shed, non-finite row, latched breaker,
        or server frozen — the receipt's `reason` says which).  A row
        whose features or label are not finite is rejected at admission
        with its event: the engine and the store only ever see finite
        data.  Label-free items (the PR-8 API) remain pure event
        triggers against the standing data.  Thread-safe; wakes a
        running learner."""
        t = np.asarray(task_ids, np.int64).reshape(-1)
        if t.size and (t.min() < 0 or t.max() >= self.problem.num_tasks):
            raise ValueError(
                f"feedback task_ids must be in "
                f"[0, {self.problem.num_tasks}), got range "
                f"[{t.min()}, {t.max()}]")
        if (features is None) != (labels is None):
            raise ValueError("features and labels must be given together "
                             "(a labeled row is (x, y)) or both omitted")
        rows = None
        if features is not None:
            if self.cfg.engine == "dense":
                raise ValueError(
                    "engine='dense' is the exact uniform baseline and "
                    "cannot grow ragged cohorts; use engine='batch' or "
                    "'sharded' for label-carrying feedback")
            x = np.asarray(features, np.float32)
            if x.ndim == 1:
                x = x[None, :]
            y = np.atleast_1d(np.asarray(labels, np.float32))
            if x.shape != (t.size, self.problem.dim) \
                    or y.shape != (t.size,):
                raise ValueError(
                    f"features must be ({t.size}, {self.problem.dim}) and "
                    f"labels ({t.size},) for {t.size} task ids; got "
                    f"{x.shape} and {y.shape}")
            x, y = self._faults.feedback(x, y)  # scripted NaN injection
            rows = (x, y)
        if self._breaker_exc is not None:
            with self._stats_lock:
                self._n_rejected += t.size
                self._n_breaker_rejected += t.size
            return FeedbackReceipt(0, int(t.size), reason="breaker")
        if not self.serve_cfg.learning:
            with self._stats_lock:
                self._n_rejected += t.size
            return FeedbackReceipt(0, int(t.size), reason="frozen")
        if self.serve_cfg.slo_shed and self._slo is not None \
                and self._slo.degraded:
            with self._stats_lock:
                self._n_rejected += t.size
                self._n_shed += t.size
            return FeedbackReceipt(0, int(t.size), reason="shed")
        finite = None
        if rows is not None:
            finite = (np.isfinite(rows[0]).all(axis=1)
                      & np.isfinite(rows[1]))
        cap = self.serve_cfg.max_pending_per_task
        accepted = rejected = nonfinite = 0
        with self._queue_lock:
            for i, ti in enumerate(t):
                if finite is not None and not finite[i]:
                    rejected += 1       # the event dies with its row
                    nonfinite += 1
                elif cap is not None and self._pending[ti] >= cap:
                    rejected += 1
                else:
                    self._pending[ti] += 1
                    if rows is not None:
                        self._pending_rows.append(
                            (int(ti), rows[0][i], rows[1][i]))
                    accepted += 1
        with self._stats_lock:
            self._n_rejected += rejected
            self._n_nonfinite_fb += nonfinite
        if accepted and self._learner is not None and self._learner.running:
            self._learner.wake()
        reason = None
        if nonfinite:
            reason = "nonfinite"
        elif rejected:
            reason = "admission"
        return FeedbackReceipt(accepted, rejected, reason=reason)

    def _coalesce(self) -> np.ndarray:
        """Drain the feedback queue into one runnable chunk.

        Round-robin over tasks from the rotating offset, at most
        `task_chunk_quota` events per task, at most the ADMITTED budget
        (`chunk_events`, degraded by the SLO controller when one is
        configured) total, floored to a multiple of `events_per_step`
        (the floored remainder goes back to the queue, reverse
        consumption order).  Deterministic in the queue contents and
        the admitted budget.  Called with the state lock held.
        Returns the per-task taken vector (the chunk size is its sum;
        the non-finite guard quarantines exactly these counts).
        """
        per = self.engine.events_per_step
        budget = (self._slo.chunk_events if self._slo is not None
                  else self.serve_cfg.chunk_events)
        quota = self.serve_cfg.task_chunk_quota
        quota = budget if quota is None else quota
        num_tasks = self.problem.num_tasks
        with self._queue_lock:
            order = [(self._rr + i) % num_tasks for i in range(num_tasks)]
            taken = np.zeros(num_tasks, np.int64)
            total = 0
            for ti in order:
                if total >= budget:
                    break
                k = min(int(self._pending[ti]), quota, budget - total)
                if k > 0:
                    taken[ti] = k
                    total += k
            give_back = total - (total // per) * per
            for ti in reversed(order):
                if give_back == 0:
                    break
                k = min(int(taken[ti]), give_back)
                taken[ti] -= k
                give_back -= k
            self._pending -= taken
            if taken.any():
                self._rr = (self._rr + 1) % num_tasks
        return taken

    def _fold_pending_rows(self) -> Optional[tuple]:
        """Publish the accepted labeled rows into the store (chunk
        boundary only; called with the state lock held).

        Drains `_pending_rows` in arrival order, appends them to the
        store (created lazily from the standing problem at the first
        fold), and rebuilds the published problem and engine against
        the new snapshot — the ragged row_counts grew, and capacity may
        have power-of-two doubled.  The live session STATE is untouched
        (engine state shapes depend on (d, T, tau), never on the row
        budget), so the next `engine.run` continues the same session
        against more data: exactly the paper's nodes streaming new
        local observations at the central server.

        Returns None when nothing folded (no rebuild happened), else an
        undo record `(store_undo, prev_problem, prev_engine, created)`
        the non-finite guard uses to unwind the fold bitwise: rolling
        back the store AND reinstating the exact previous problem and
        engine objects keeps the jit cache keys of the pre-fold session.
        """
        with self._queue_lock:
            rows, self._pending_rows = self._pending_rows, []
        if not rows:
            return None
        with jax.profiler.TraceAnnotation("serve.fold") as span:
            created = self._store is None
            if created:
                self._store = TaskStore.from_problem(self.problem)
            tids = np.asarray([r[0] for r in rows], np.int64)
            xs = np.stack([r[1] for r in rows])
            ys = np.asarray([r[2] for r in rows], np.float32)
            prev = (self.problem, self.engine)
            store_undo = self._store.append_undoable(tids, xs, ys)
            self.problem = self._store.problem()
            self.engine = make_engine(self.problem, self.cfg, self._mesh)
            # `bytes`: the store arrays the fold rebuilt on the device.
            span.set_metadata(rows=len(rows), bytes=sum(
                a.nbytes for a in (self.problem.xs, self.problem.ys,
                                   self.problem.row_counts)))
        return (store_undo, prev[0], prev[1], created)

    def _unfold_rows(self, fold: Optional[tuple]) -> None:
        """Unwind one `_fold_pending_rows` (state lock held): the store,
        problem, and engine return bitwise to their pre-fold snapshots.
        A store created BY the rolled-back fold is discarded outright —
        the session drops back to the label-free path it was on."""
        if fold is None:
            return
        store_undo, prev_problem, prev_engine, created = fold
        if created:
            self._store = None
        else:
            self._store.rollback(store_undo)
        self.problem = prev_problem
        self.engine = prev_engine

    def _step_once(self) -> int:
        """One chunk boundary: fold rows -> coalesce -> `engine.run` ->
        non-finite guard -> atomic flip.

        The engine-side critical section (state lock): accepted labeled
        rows fold into the store FIRST, so the chunk about to run — and
        every later one — sees them; then the serving snapshot is
        reassigned as ONE reference only after the new iterate fully
        materializes, so a concurrent `predict` reads either the
        previous or the new committed snapshot — never an in-flight
        one.  The guard checks the materialized iterate with one
        `isfinite` reduction BEFORE the flip: a non-finite result
        discards the chunk (state, snapshot, and chunk log untouched),
        unwinds the boundary's fold, and quarantines the coalesced
        events (logged per task, not re-queued) — the committed
        snapshot and every checkpoint stay finite by construction.
        Auto-checkpoints on the `checkpoint_every` cadence.  Runs on
        the learner thread, or inline via `step()`.

        Returns the events CONSUMED at this boundary (committed or
        quarantined), so drain loops always make progress past a
        poisoned chunk.
        """
        # `serve.chunk` carries `events` (0 at an idle boundary) and, for
        # a chunk that runs, its index `chunk`.
        with self._state_lock, \
                jax.profiler.TraceAnnotation("serve.chunk") as span:
            fold = self._fold_pending_rows()
            with jax.profiler.TraceAnnotation("serve.coalesce"):
                taken = self._coalesce()
            n = int(taken.sum())
            if n == 0:
                span.set_metadata(events=0)
                return 0
            chunk_idx = self._faults.begin_chunk()
            span.set_metadata(chunk=chunk_idx, events=n)
            self._faults.crash_point(chunk_idx)   # scripted learner crash
            state = self.engine.run(self._state, self._delay_offsets, n)
            v = self.engine.iterate(state)
            v = self._faults.poison(chunk_idx, v)  # scripted NaN iterate
            with jax.profiler.TraceAnnotation("serve.guard"):
                v = jax.block_until_ready(v)
                finite = bool(jnp.isfinite(v).all())
            if not finite:
                # Quarantine: nothing commits.  The last committed
                # snapshot keeps serving, the fold unwinds bitwise, and
                # the chunk's events are logged per task — never
                # re-queued (re-running the same poison forever is the
                # one thing worse than losing it).
                self._unfold_rows(fold)
                with self._stats_lock:
                    self._n_nonfinite_chunks += 1
                    self._n_quarantined += n
                    self._quarantine_log.append(
                        {int(t): int(k) for t, k in enumerate(taken)
                         if k > 0})
                return n
            self._state = state
            self.chunk_log.append(n)
            self._serving = ServingSnapshot(v, int(state.event))  # the flip
            self._events_since_ckpt += n
            every = self.serve_cfg.checkpoint_every
            if every is not None and self._events_since_ckpt >= every:
                self.checkpoint()
            return n

    def step(self) -> int:
        """Cooperative chunk boundary (single-threaded callers).

        Returns the number of events consumed at the boundary — learned,
        or quarantined by the non-finite guard (0 if frozen, breaker
        latched, or nothing runnable yet).  While the background learner
        is running, chunks belong to it — call `stop_learner()` first.
        """
        if not self.serve_cfg.learning or self._breaker_exc is not None:
            return 0
        if self.learner_running:
            raise RuntimeError(
                "the background learner owns the chunk loop; call "
                "stop_learner() before stepping cooperatively")
        return self._step_once()

    # ------------------------------------------------- learner lifecycle
    @property
    def learner_running(self) -> bool:
        return self._learner is not None and self._learner.running

    @property
    def breaker_tripped(self) -> bool:
        """True once the learner circuit breaker latched the server
        into frozen-serving mode (predictions flow, feedback rejected,
        chunks stop).  Latched for the server's lifetime."""
        return self._breaker_exc is not None

    def _trip_breaker(self, exc: BaseException) -> None:
        """Called by the supervisor when the restart budget is spent."""
        with self._stats_lock:
            self._breaker_exc = exc

    def start_learner(self) -> BackgroundLearner | LearnerSupervisor:
        """Start the background chunk runner (`serve.learner`).  The
        request path keeps serving the committed snapshot throughout;
        `submit_feedback` wakes the thread.  With
        `ServeConfig.restart_limit` set the runner is a
        `LearnerSupervisor` (bounded auto-restart + circuit breaker);
        None keeps the PR-8 unsupervised `BackgroundLearner`."""
        if not self.serve_cfg.learning:
            raise RuntimeError("server is frozen (learning=False); there "
                               "is nothing for a learner thread to run")
        if self._breaker_exc is not None:
            raise RuntimeError(
                "learner circuit breaker is latched (restart budget "
                "exhausted); the server is in frozen-serving mode"
            ) from self._breaker_exc
        if self._learner is None:
            limit = self.serve_cfg.restart_limit
            if limit is None:
                self._learner = BackgroundLearner(self)
            else:
                self._learner = LearnerSupervisor(
                    self, limit=limit,
                    backoff_s=self.serve_cfg.restart_backoff_s)
        self._learner.start()
        return self._learner

    def stop_learner(self, drain: bool = True,
                     timeout: Optional[float] = None) -> int:
        """Stop + join the learner; returns events it learned.  With
        drain=True every runnable chunk is finished first (no
        concurrent submissions -> bitwise the cooperative loop).
        Re-raises any exception the learner thread died with."""
        if self._learner is None:
            return 0
        return self._learner.stop(drain=drain, timeout=timeout)

    def serve(self, task_ids, features, feedback_task_ids=None,
              feedback_features=None, feedback_labels=None):
        """One request batch: predict, enqueue feedback, run one chunk.

        Predictions are scored against the CURRENT committed snapshot
        before the chunk runs — this batch's feedback affects the NEXT
        batch's predictions, which is what lets the request path never
        block on learning.  `feedback_features`/`feedback_labels`
        optionally carry the labeled rows (see `submit_feedback`).
        With the background learner running, the chunk step is left to
        it (ran = 0 here).  Returns (predictions, FeedbackReceipt,
        events_learned).
        """
        preds = self.predict(task_ids, features)
        receipt = FeedbackReceipt(0, 0)
        if feedback_task_ids is not None:
            receipt = self.submit_feedback(feedback_task_ids,
                                           feedback_features,
                                           feedback_labels)
        ran = 0 if self.learner_running else self.step()
        return preds, receipt, ran

    # ------------------------------------------------- checkpoint/restart
    def checkpoint(self) -> Optional[str]:
        """Write the engine state as `step_<event>.npz`, rotated to
        `keep_last`.  Returns the written path (None if no ckpt_dir).
        Serialized against the chunk runner by the state lock.

        When a store exists (labeled rows were folded), its buffers are
        written FIRST, under `<ckpt_dir>/store/` at the SAME step: a
        crash between the two writes leaves an unpaired NEWER store
        record — which resume tolerates — never an engine state whose
        data is missing.  A label-free server writes no store subdir
        at all (the PR-8 on-disk layout, byte for byte).  The fault
        plan's checkpoint hook sits exactly in that split window, so
        the crash-split recovery path is testable on demand."""
        if self.serve_cfg.ckpt_dir is None:
            return None
        with self._state_lock:
            if self._store is not None:
                self._store.save(
                    os.path.join(self.serve_cfg.ckpt_dir, "store"),
                    int(self._state.event),
                    keep_last=self.serve_cfg.keep_last)
            self._faults.checkpoint_point()  # scripted crash-split
            path = checkpoint.save(self.serve_cfg.ckpt_dir,
                                   int(self._state.event), self._state,
                                   keep_last=self.serve_cfg.keep_last)
            self._events_since_ckpt = 0
        return path

    @classmethod
    def resume(cls, problem: MTLProblem, cfg: AMTLConfig, v0: Array,
               key: Array, serve_cfg: ServeConfig = ServeConfig(), *,
               mesh=None, delay_offsets: Array | None = None,
               fault_plan: Optional[FaultPlan] = None) -> "AMTLServer":
        """Restart-transparent construction: restore the newest VALID
        rotated checkpoint in `serve_cfg.ckpt_dir` if one exists, else
        a fresh `engine.init(v0, key)` session.  The init state is
        built ONCE (it doubles as `restore`'s `like` layout witness)
        and only the state actually served materializes a serving
        snapshot.  The restored server's snapshot — and therefore every
        subsequent prediction — is bitwise the uninterrupted server's
        at the same chunk boundary.

        Record selection is integrity-checked
        (`checkpoint.latest_valid_step`): a torn or bit-rotted newest
        record is skipped and the session falls back one checkpoint
        interval instead of dying on an opaque zip error.  A directory
        whose records are ALL damaged raises `CheckpointCorruptError` —
        silently restarting a session from scratch is worse than
        failing loudly.

        If the checkpoint has a paired store record (labeled rows had
        been folded), the store is restored FIRST and the problem and
        engine are rebuilt from its snapshot — `problem` then only
        seeds the restored buffers' layout witness — so the resumed
        session continues against exactly the grown cohorts it was
        checkpointed with.  A missing or corrupt paired record drops to
        the remaining store records newest-first (the crash-split and
        bit-rot cases).  Engine state shapes never depend on the row
        budget, so the fresh init state remains a valid `like` layout
        for `restore` either way."""
        server = cls.__new__(cls)
        server._configure(problem, cfg, v0, key, serve_cfg, mesh=mesh,
                          delay_offsets=delay_offsets, fault_plan=fault_plan)
        init_state = server.engine.init(v0, key)
        d = serve_cfg.ckpt_dir
        step = None
        if d is not None:
            step = checkpoint.latest_valid_step(d, like=init_state)
            if step is None and checkpoint.latest_step(d) is not None:
                raise CheckpointCorruptError(
                    d, [], "every engine record in the directory fails "
                    "verification — refusing to silently restart the "
                    "session from scratch")
        if step is None:
            server._install_state(init_state)
            return server
        store_dir = os.path.join(d, "store")

        def _try_store(s: int) -> Optional[TaskStore]:
            try:
                return TaskStore.restore(store_dir, s, problem.loss_name,
                                         problem.reg_name, problem.lam)
            except (FileNotFoundError, CheckpointCorruptError):
                return None

        # Prefer the record paired with the engine step; fall back to
        # the remaining records newest-first.  No record at exactly
        # `step` is either a label-free session (no store subdir — the
        # common case), a crash between the store write and the engine
        # write (one unpaired NEWER record holding a superset of the
        # paired rows — the engine state never saw the extras, appends
        # only affect FUTURE chunks), or a torn/corrupt paired record
        # (drop one interval of rows rather than the session).
        store = _try_store(step)
        if store is None:
            for s in checkpoint.record_steps(store_dir):
                if s == step:
                    continue
                store = _try_store(s)
                if store is not None:
                    break
            if store is None and checkpoint.record_steps(store_dir):
                raise CheckpointCorruptError(
                    store_dir, [], "every store record fails to restore "
                    "— resuming the engine without its folded rows would "
                    "silently change the session")
        if store is not None:
            server._store = store
            server.problem = store.problem()
            server.engine = make_engine(server.problem, cfg, mesh)
        server._install_state(checkpoint.restore(d, step, like=init_state))
        return server

    # ---------------------------------------------------------- telemetry
    @property
    def event_count(self) -> int:
        return int(self._state.event)

    @property
    def pending_feedback(self) -> int:
        return int(self._pending.sum())

    @property
    def store_rows(self) -> Optional[int]:
        """Total rows in the store (None until labeled rows fold)."""
        store = self._store
        return None if store is None else store.num_rows

    def stats(self) -> dict[str, Any]:
        sup = (self._learner
               if isinstance(self._learner, LearnerSupervisor) else None)
        health = {
            "learner_restarts": 0 if sup is None else sup.restarts,
            "learner_crashes": 0 if sup is None else sup.crashes,
            "crash_log": [] if sup is None else list(sup.crash_log),
            "recovery_ms": [] if sup is None else list(sup.recovery_ms),
            "breaker_tripped": self.breaker_tripped,
            "breaker_rejected": self._n_breaker_rejected,
            "nonfinite_feedback": self._n_nonfinite_fb,
            "nonfinite_chunks": self._n_nonfinite_chunks,
            "quarantined_feedback": self._n_quarantined,
            "quarantine_log": [dict(q) for q in self._quarantine_log],
        }
        out = {
            "requests": self._n_requests,
            "predictions": self._n_predictions,
            "events": self.event_count,
            "chunks": len(self.chunk_log),
            "pending_feedback": self.pending_feedback,
            "pending_rows": len(self._pending_rows),
            "store_rows": self.store_rows,
            "rejected_feedback": self._n_rejected,
            "shed_feedback": self._n_shed,
            "learning": self.serve_cfg.learning,
            "learner_running": self.learner_running,
            "learner_chunks": 0 if self._learner is None
                              else self._learner.chunks,
            "slo": None if self._slo is None else self._slo.snapshot(),
            "health": health,
        }
        return out
