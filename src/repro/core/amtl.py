"""AMTL — asynchronous backward-forward coordinate updates (Algorithm 1).

SPMD execution of the ARock semantics: the physical asynchrony of the paper
(threads racing on shared memory) is replayed as a *sequential consistency
simulation* inside `lax.scan`/`fori_loop`:

  event k:  a task t_k is activated (uniform — Poisson thinning under
            Assumption 1);  it reads the server state at staleness nu_k <= tau
            (stale AND inconsistent reads: every block but its own comes from
            an older iterate);  the server computes the backward step
            prox_{eta*lam*g} on that stale copy;  the node applies the forward
            step on its block and writes back with KM relaxation eta_k
            (Eq. III.4), optionally scaled by the delay-adaptive multiplier
            (Eq. III.5/III.6).

Four engines implement the same mathematics:

  engine="delta" (default) — the delta ring.  Only ONE full iterate V is kept;
      each event appends `(task_id, pre-write column)` to a `(tau+1, d)` undo
      log, and the stale read at staleness nu is reconstructed lazily by
      rolling back the nu newest log entries (O(tau*d) work, O(tau*d) memory).
      Per-event state writes are O(d): one column of V plus one ring slot.
      The fused column math (forward step + KM relaxation + undo-log emit)
      is the `amtl_event` kernel (`repro.kernels.ops.amtl_event`).
      The server-side prox can be amortized (`prox_every` — paper §III-C:
      "the proximal mapping can be also applied after several gradient
      updates"), with `svt_randomized` as the refresh for the nuclear norm
      at large d x T (`prox_rank`).

  engine="dense" — the seed engine: a `(tau+1, d, T)` ring of full iterates,
      O(d*T) HBM writes per event.  Kept as the equivalence baseline; the
      delta engine reproduces its iterates bitwise under the same PRNG key
      when `prox_every == 1` and both engines run the same arithmetic
      dispatch (the CPU oracle path, where `ops.amtl_event` lowers to the
      same jnp expression as `km_block_update`; on TPU the Pallas kernel
      may contract FMAs differently, so expect ulp-level, not bitwise,
      agreement there).

  engine="batch" — the delta ring, `event_batch` events per loop step.
      Each step replays `event_batch` draws of the serial PRNG chain (so
      the (task, staleness) event stream is identical to the one-event
      engines by construction), refreshes the server prox only at batch
      boundaries, and applies all column updates through
      `ops.amtl_event_batch` (gather -> fused forward/KM/undo-emit ->
      scatter).  Within-batch conflicts — duplicate tasks — are serialized
      in event order: a later event reads the column as left by the
      earlier in-batch write, and its undo-log entry records that
      pre-write column, so the ring replays exactly as if the events had
      been applied one at a time.  The prox cadence is decoupled from the
      batch size: `prox_every = k * event_batch` refreshes the prox at
      every k-th batch's first event and carries the result in a (d, T)
      prox cache between batches (k == 1 refreshes every batch and carries
      no cache).  For matched cadences (same `prox_every`, same key) the
      batch engine reproduces the delta engine's iterates bitwise on the
      CPU oracle path.

  engine="sharded" — the batch engine with the T task columns partitioned
      over a 1-D "tasks" mesh axis (shard_map).  Each shard owns a (d,
      T/n_shards) block of V, a private (tau+1, d) undo ring, and its
      tasks' data; the task ring records GLOBAL task ids and the scalar
      chain state (PRNG key, ring pointer, event counter) is replicated.
      Every shard replays the FULL serial PRNG chain and masks events to
      their owner, so the (task, staleness) event stream is invariant to
      shard count by construction.  Collectives are paid only at prox
      cadence.  With prox_mode="replicated", one `all_gather` per refresh
      assembles the stale iterate for the server prox (SVT / randomized
      SVT), whose replicated result is the broadcast back; with
      prox_mode="distributed" (prox_rank required) the refresh is the
      rank-distributed randomized SVT — a (d, p) `psum` of per-shard
      sketch partials plus a (p, T/n) `all_gather` of the projected core,
      the thresholded reconstruction applied shard-locally — cutting
      per-refresh communication from O(d*T) to O(d*p + p*T) and dividing
      the sketch flops over the shards.  Gradients, column updates, and
      ring writes stay shard-local in both modes.  With the decoupled
      cadence (`prox_every = k * event_batch`) the collectives are paid
      only every k batches — the true "communication only at prox cadence"
      limit.  This is exactly the paper's server/worker communication
      pattern: task nodes hold their data locally, the central server runs
      the prox.  On a 1-device mesh the engine reproduces engine="batch"
      bitwise on the CPU oracle path, and per-shard `delay_offsets` skews
      model the paper's slow-node regime (a lagging shard's tasks read at
      high staleness without stalling the other shards' event stream).

SGD-AMTL (paper §V): with `AMTLConfig(batch_size=b)` the delta, batch, and
sharded engines replace every forward-step gradient by an unbiased
(n_t/bsz)-scaled seeded minibatch gradient (bsz = min(b, n_t), the
simulator's convention).  The per-event sampling seed is folded off the
main PRNG chain (`_minibatch_seed`), so the (task, staleness) event stream
— and with batch_size=None the engines' every bit — is unchanged; the
selection itself is generated in-kernel from counter hashes
(`repro.kernels.ops.lstsq_grad_sampled`), with no gather and no
materialized index array.

Ragged task cohorts: an `MTLProblem` with `row_counts` set (the
`repro.data.TaskStore` layout — per-task valid-row counts over a shared
padded buffer) runs unchanged through the delta, batch, and sharded
engines; every loss/gradient/minibatch expression masks rows >= n_t
inside `repro.core.losses`, the sharded engine ships row_counts as one
more per_task shard_map input, and uniform row_counts reproduce the
unmasked engines bitwise on the CPU oracle path.  engine="dense" is the
exact uniform seed baseline and rejects ragged problems.

This is bit-faithful to Algorithm 1's mathematics while being jit-compiled,
deterministic under a PRNG key, and mesh-shardable.  Wall-clock behaviour
(Tables I/III) is studied separately by `repro.core.simulator`.

The public surface is the *session* API — the paper's deployment story is
a long-lived asynchronous system, so the solver is a resumable session
over a streaming event source rather than a one-shot batch call:

    engine = make_engine(problem, cfg, mesh=None)   # -> AMTLEngine
    state  = engine.init(v0, key)
    state  = engine.run(state, delay_offsets, num_events)   # resumable
    v      = engine.iterate(state)

`run` is jitted (one compile per distinct `num_events`), advances the
state by any multiple of `engine.events_per_step` events, and composes
bitwise: `run(·, n + m)` == `run(run(·, n), m)` for every engine.  Engine
states are plain pytrees of arrays and round-trip through
`repro.checkpoint.save/restore`, resuming bitwise — including the sharded
state under a mesh.  `amtl_solve` (epoch metrics) and `amtl_events_only`
(bench path) are thin wrappers over the session API, and the online
learning-while-serving platform (`repro.serve.AMTLServer`) holds one of
these sessions long-lived behind a double-buffered prediction path.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.dynamic_step import DelayHistory, dynamic_multiplier
from repro.core.losses import MTLProblem
from repro.core.operators import (amtl_max_step, backward,
                                  fixed_point_residual, km_block_update,
                                  rollback_columns, rollback_columns_batch,
                                  rollback_columns_shard)
from repro.core.prox import ProxPlan, svt_randomized, svt_randomized_dist
from repro.distributed.sharding import (TASK_AXIS, prox_cache_spec,
                                        task_shard_specs)

Array = jax.Array
# The engines compute in f32: their matmuls (task gradients, the prox's
# sketch core and reconstruction) run at f32 precision on every backend.
# XLA's TPU default is one bf16 pass, which on a v5e left the distributed
# and replicated prox 1.4e-3 apart (relative) after 320 events.
F32_MATMUL = "highest"


class AMTLConfig(NamedTuple):
    eta: float                 # inner forward/backward step, in (0, 2/L)
    eta_k: float               # KM relaxation, <= amtl_max_step(tau, T)
    tau: int                   # max staleness (ring-buffer depth - 1)
    dynamic_step: bool = False
    delay_window: int = 5      # paper averages the last 5 delays
    # Per-task mean staleness (in events). The sampled delay is
    # min(round(offset_t + U[0,1) * jitter), tau). offsets=None => all zero.
    delay_jitter: float = 1.0
    # "delta": O(d) per-event state with an undo-log ring (default).
    # "dense": the seed (tau+1, d, T) full-iterate ring, for equivalence.
    # "batch": the delta ring, event_batch events per loop step with
    #          batch-boundary prox refreshes and conflict-aware updates.
    # "sharded": the batch engine with task columns partitioned over a
    #          "tasks" mesh axis; one all_gather per prox refresh.
    engine: str = "delta"
    # Server prox amortization (paper §III-C): refresh the backward step
    # every K events, reuse the cached prox in between.  K=1 == exact AMTL.
    # For engine="batch"/"sharded" K must be a multiple of event_batch
    # (refreshes happen at batch boundaries); K = k*event_batch with k > 1
    # carries the refreshed prox in a (d, T) cache across batches — the
    # sharded engine then pays its all_gather only every k batches.
    prox_every: int = 1
    # If set (nuclear reg only), prox refreshes use the randomized SVT
    # sketch at this rank instead of the dense SVD — the large-d*T regime.
    prox_rank: int | None = None
    # engine="batch"/"sharded" only: activations applied per loop step.
    event_batch: int = 1
    # engine="sharded" only: how the server prox is executed at a refresh.
    # "replicated": ONE all_gather assembles the (d, T) stale iterate and
    #   every shard runs the same SVT / randomized SVT on it (the
    #   replicated result is the broadcast back) — O(d*T) communication
    #   and the prox work duplicated n_shards times.
    # "distributed" (requires prox_rank): the rank-distributed randomized
    #   SVT — each shard sketches only its own (d, T/n) column block (one
    #   (d, p) psum), the projected core is assembled with a (p, T/n)
    #   all_gather, and the thresholded reconstruction is applied
    #   shard-locally: O(d*p + p*T) communication, sketch flops divided
    #   by the shard count, no shard ever holds the full iterate.
    prox_mode: str = "replicated"
    # SGD-AMTL (paper §V): if set, every forward step uses an unbiased
    # (n_t/bsz)-scaled seeded minibatch gradient with bsz =
    # min(batch_size, n_t) — the simulator's convention.  The per-event
    # sampling seed is folded off the main PRNG chain (fold_in constant
    # 11, the sketch-key pattern), so the (task, staleness) event stream
    # is untouched and every shard of the sharded engine re-derives the
    # identical seed, sampling shard-locally.  None = exact full
    # gradients, bitwise-identical to the pre-SGD engines.  Supported by
    # the delta, batch, and sharded engines (dense is the exact seed
    # baseline).
    batch_size: int | None = None


class AMTLState(NamedTuple):
    """Dense-engine state: the seed full-iterate staleness ring."""
    ring: Array            # (tau+1, d, T) past iterates, ring[ptr] = newest
    ptr: Array             # int32 index of newest iterate
    event: Array           # int32 global event counter
    history: DelayHistory  # per-task recent delays (for dynamic step)
    key: Array             # PRNG


class DeltaAMTLState(NamedTuple):
    """Delta-engine state: one iterate + an O(tau*d) undo log."""
    v: Array               # (d, T) current iterate (the only full copy)
    delta_ring: Array      # (tau+1, d) pre-write column per event (undo log)
    task_ring: Array       # (tau+1,) int32 task written at each event
    ptr: Array             # int32 slot of the newest event
    event: Array           # int32 global event counter
    p_cache: Array         # (d, T) cached server prox (prox_every > 1)
    history: DelayHistory
    key: Array


class BatchAMTLState(NamedTuple):
    """Batch-engine state: the delta ring with a per-cadence prox cache.

    At the aligned cadence (prox_every == event_batch) the prox is
    refreshed unconditionally at each batch's first event, so no (d, T)
    cache is carried between loop steps (`p_cache` stays a (0, 0) stub) —
    the per-event `lax.cond` copy of that cache is the delta engine's
    dominant non-prox cost.  With the decoupled cadence (prox_every =
    k*event_batch, k > 1) `p_cache` holds the last refreshed prox and is
    reused by the k-1 batches between refreshes.
    """
    v: Array               # (d, T) current iterate (the only full copy)
    delta_ring: Array      # (tau+1, d) pre-write column per event (undo log)
    task_ring: Array       # (tau+1,) int32 task written at each event
    ptr: Array             # int32 slot of the newest event
    event: Array           # int32 global event counter
    p_cache: Array         # (d, T) cached prox (prox_every > event_batch)
    history: DelayHistory
    key: Array


class ShardedAMTLState(NamedTuple):
    """Sharded-engine state, global view (engine='sharded').

    The T task columns live on a 1-D "tasks" mesh axis.  Each shard runs
    the batch engine's conflict-aware column updates on its own block and
    keeps a private undo ring; the task ring holds GLOBAL task ids and —
    like the scalar chain state — is replicated, because every shard
    replays the full serial PRNG chain and masks events to their owner.
    """
    v: Array               # (d, T) iterate, columns sharded over "tasks"
    delta_ring: Array      # (n_shards, tau+1, d) per-shard undo rings
    task_ring: Array       # (tau+1,) int32 GLOBAL task id per event slot
    ptr: Array             # int32 slot of the newest event (replicated)
    event: Array           # int32 global event counter (replicated)
    p_cache: Array         # (d, T) cached prox, replicated (k > 1 cadence)
    history: DelayHistory  # per-task delays, rows sharded over "tasks"
    key: Array             # PRNG (replicated serial chain)


class AMTLResult(NamedTuple):
    v: Array               # final auxiliary iterate V (d, T)
    w: Array               # final primal W = prox(V) (one extra backward)
    objectives: Array      # objective of prox(V) per recorded epoch
    residuals: Array       # BF fixed-point residual per recorded epoch


def init_state(cfg: AMTLConfig, v0: Array, num_tasks: int,
               key: Array) -> AMTLState:
    ring = jnp.broadcast_to(v0, (cfg.tau + 1, *v0.shape)).astype(v0.dtype)
    return AMTLState(
        ring=ring,
        ptr=jnp.zeros((), jnp.int32),
        event=jnp.zeros((), jnp.int32),
        history=DelayHistory.create(num_tasks, cfg.delay_window),
        key=key,
    )


def init_delta_state(cfg: AMTLConfig, v0: Array, num_tasks: int,
                     key: Array) -> DeltaAMTLState:
    depth = cfg.tau + 1
    return DeltaAMTLState(
        v=v0,
        delta_ring=jnp.zeros((depth, v0.shape[0]), v0.dtype),
        task_ring=jnp.zeros((depth,), jnp.int32),
        ptr=jnp.zeros((), jnp.int32),
        event=jnp.zeros((), jnp.int32),
        p_cache=_prox_cache_init(cfg, v0),
        history=DelayHistory.create(num_tasks, cfg.delay_window),
        key=key,
    )


def _prox_cache_init(cfg: AMTLConfig, v0: Array) -> Array:
    """(d, T) zeros when a cache is actually carried, else a (0, 0) stub.

    The aligned cadence (prox_every <= event_batch for the batch engines,
    prox_every == 1 for delta) refreshes before every read and never
    consults the cache, so no dead (d, T) buffer rides the loop carry;
    with amortization, event 0 always refreshes before the first read.
    """
    carried = cfg.prox_every > (cfg.event_batch
                                if cfg.engine in ("batch", "sharded") else 1)
    return jnp.zeros_like(v0) if carried else jnp.zeros((0, 0), v0.dtype)


def init_batch_state(cfg: AMTLConfig, v0: Array, num_tasks: int,
                     key: Array) -> BatchAMTLState:
    depth = cfg.tau + 1
    return BatchAMTLState(
        v=v0,
        delta_ring=jnp.zeros((depth, v0.shape[0]), v0.dtype),
        task_ring=jnp.zeros((depth,), jnp.int32),
        ptr=jnp.zeros((), jnp.int32),
        event=jnp.zeros((), jnp.int32),
        p_cache=_prox_cache_init(cfg, v0),
        history=DelayHistory.create(num_tasks, cfg.delay_window),
        key=key,
    )


def init_sharded_state(cfg: AMTLConfig, v0: Array, num_tasks: int,
                       key: Array, n_shards: int) -> ShardedAMTLState:
    depth = cfg.tau + 1
    return ShardedAMTLState(
        v=v0,
        delta_ring=jnp.zeros((n_shards, depth, v0.shape[0]), v0.dtype),
        task_ring=jnp.zeros((depth,), jnp.int32),
        ptr=jnp.zeros((), jnp.int32),
        event=jnp.zeros((), jnp.int32),
        p_cache=_prox_cache_init(cfg, v0),
        history=DelayHistory.create(num_tasks, cfg.delay_window),
        key=key,
    )


def _sample_activation(cfg: AMTLConfig, delay_offsets: Array, key: Array,
                       num_tasks: int, event: Array):
    """Shared event sampling: (next key, activated task, staleness nu).

    Identical PRNG consumption in both engines => bitwise-reproducible
    event sequences across `engine=` choices.
    """
    key, k_task, k_delay = jax.random.split(key, 3)
    # Assumption 1: same-rate independent Poisson processes => the next
    # activated node is uniform over tasks.
    t = jax.random.randint(k_task, (), 0, num_tasks)
    # Staleness of this node's read (network delay in iterate space).
    raw = delay_offsets[t] + cfg.delay_jitter * jax.random.uniform(k_delay)
    nu = jnp.minimum(jnp.round(raw).astype(jnp.int32),
                     jnp.minimum(cfg.tau, event))
    return key, t, nu


def _minibatch_seed(key: Array) -> Array:
    """Per-event uint32 sampling seed, folded off the pre-event chain key.

    fold_in (constant 11, distinct from the sketch key's 7) does not
    advance the chain, so deriving the seed leaves the (task, staleness)
    event stream bit-identical to the full-gradient engines; and because
    the chain key is replicated on the sharded engine, every shard
    derives the SAME seed for an event and re-creates its selection bits
    locally.
    """
    return jax.random.bits(jax.random.fold_in(key, 11), dtype=jnp.uint32)


def _sample_activation_batch(cfg: AMTLConfig, delay_offsets: Array,
                             key: Array, num_tasks: int, event: Array,
                             batch: int):
    """Replay `batch` steps of the serial PRNG chain in one scan.

    Same splits, same draws, same staleness clamp (`event + i`) as `batch`
    consecutive calls of `_sample_activation` — the event stream is
    identical to the one-event engines by construction.  Returns
    (next key, tasks (batch,), stalenesses (batch,), minibatch seeds
    (batch,) uint32).  Each seed is `_minibatch_seed` of the chain key
    the serial delta engine would hold at that event, so the one-event
    and batched SGD engines sample identical minibatches; when
    batch_size is None the seeds are unused (and dead-code-eliminated).
    """
    def one(k, i):
        seed = _minibatch_seed(k)
        k, t, nu = _sample_activation(cfg, delay_offsets, k, num_tasks,
                                      event + i)
        return k, (t, nu, seed)

    key, (ts, nus, seeds) = jax.lax.scan(one, key, jnp.arange(batch))
    return key, ts, nus, seeds


def _km_relaxation(cfg: AMTLConfig, history: DelayHistory, t: Array,
                   nu: Array):
    """Record the delay and return (updated history, eta_k for this event)."""
    history = history.record(t, nu.astype(jnp.float32))
    if cfg.dynamic_step:
        eta_k = cfg.eta_k * dynamic_multiplier(history.mean_delay(t))
    else:
        eta_k = jnp.asarray(cfg.eta_k, jnp.float32)
    return history, eta_k


def _one_event_dense(problem: MTLProblem, cfg: AMTLConfig,
                     delay_offsets: Array, state: AMTLState) -> AMTLState:
    """One ARock activation on the seed full-iterate ring (O(d*T)/event)."""
    depth = cfg.tau + 1
    key, t, nu = _sample_activation(cfg, delay_offsets, state.key,
                                    problem.num_tasks, state.event)

    # Stale/inconsistent read: all blocks from iterate (k - nu); the node's
    # own block is current (only node t ever writes block t).
    v_cur = state.ring[state.ptr]
    idx = (state.ptr - nu) % depth
    v_hat = state.ring[idx]
    v_hat = v_hat.at[:, t].set(v_cur[:, t])

    # Backward step at the server on the stale copy.
    p = backward(problem, v_hat, cfg.eta)

    # Forward step on the node's block only (separability of I - eta*grad f).
    p_t = p[:, t]
    g_t = problem.task_grad(t, p_t)

    # KM relaxation, optionally delay-adaptive (Eq. III.5/III.6).
    history, eta_k = _km_relaxation(cfg, state.history, t, nu)

    v_t_new = km_block_update(v_cur[:, t], p_t, g_t,
                              jnp.asarray(cfg.eta, p_t.dtype),
                              eta_k.astype(p_t.dtype))
    v_new = v_cur.at[:, t].set(v_t_new)

    ptr = (state.ptr + 1) % depth
    ring = state.ring.at[ptr].set(v_new)
    return AMTLState(ring, ptr, state.event + 1, history, key)


def _one_event_delta(problem: MTLProblem, cfg: AMTLConfig,
                     delay_offsets: Array,
                     state: DeltaAMTLState) -> DeltaAMTLState:
    """One ARock activation on the delta ring (O(d) state writes/event)."""
    from repro.kernels.ops import amtl_event

    depth = cfg.tau + 1
    use_randomized = cfg.prox_rank is not None and problem.reg_name == "nuclear"
    key, t, nu = _sample_activation(cfg, delay_offsets, state.key,
                                    problem.num_tasks, state.event)
    # The sketch key is folded off the pre-event key instead of split from
    # the main chain, so the task/staleness event stream stays identical to
    # the dense engine even when the randomized refresh is enabled.  The
    # minibatch sampling seed follows the same pattern at a different fold
    # constant.
    k_prox = jax.random.fold_in(state.key, 7) if use_randomized else None
    mb_seed = _minibatch_seed(state.key) if cfg.batch_size is not None \
        else None
    v = state.v

    def refresh(_):
        # Lazy stale read: roll back the nu newest undo-log entries, then
        # patch the node's own (always-current) column.  Only paid when the
        # server actually recomputes the prox.
        v_hat = rollback_columns(v, state.delta_ring, state.task_ring,
                                 state.ptr, nu, cfg.tau)
        v_hat = v_hat.at[:, t].set(v[:, t])
        if use_randomized:
            return svt_randomized(
                v_hat, jnp.asarray(cfg.eta * problem.lam, v_hat.dtype),
                rank=cfg.prox_rank, key=k_prox)
        return backward(problem, v_hat, cfg.eta)

    if cfg.prox_every <= 1:
        p = refresh(None)
        p_cache = state.p_cache      # untouched loop carry: no copy
    else:
        do_prox = (state.event % cfg.prox_every) == 0
        p = jax.lax.cond(do_prox, refresh, lambda _: state.p_cache, None)
        p_cache = p

    p_t = p[:, t]
    if cfg.batch_size is None:
        g_t = problem.task_grad(t, p_t)
    else:
        g_t = problem.task_grad_sampled(t, p_t, mb_seed, cfg.batch_size)

    history, eta_k = _km_relaxation(cfg, state.history, t, nu)

    # Fused column event: forward step + KM relaxation + undo-log emit.
    v_t_new, old_col = amtl_event(v[:, t], p_t, g_t,
                                  jnp.asarray(cfg.eta, p_t.dtype),
                                  eta_k.astype(p_t.dtype))

    ptr = (state.ptr + 1) % depth
    return DeltaAMTLState(
        v=v.at[:, t].set(v_t_new),
        delta_ring=state.delta_ring.at[ptr].set(old_col),
        task_ring=state.task_ring.at[ptr].set(t),
        ptr=ptr,
        event=state.event + 1,
        p_cache=p_cache,
        history=history,
        key=key,
    )


def _one_batch(problem: MTLProblem, cfg: AMTLConfig, delay_offsets: Array,
               state: BatchAMTLState) -> BatchAMTLState:
    """`event_batch` ARock activations in one step (batch engine).

    Serial-replay equivalent: the PRNG chain, the amortized prox schedule
    (refresh at batch-first events that are multiples of prox_every), the
    per-event KM arithmetic, and the undo-log contents all match
    `event_batch` consecutive `_one_event_delta` steps bitwise on the CPU
    oracle path — at the aligned cadence (prox_every == event_batch) and
    the decoupled one (prox_every = k*event_batch, refresh every k-th
    batch via the carried prox cache).
    """
    from repro.kernels.ops import amtl_event_batch

    depth = cfg.tau + 1
    bsz = cfg.event_batch
    use_randomized = cfg.prox_rank is not None and problem.reg_name == "nuclear"
    # Folded off the batch-start key — the key the serial engine would hold
    # at its refresh event (a refresh batch's first event).
    k_prox = jax.random.fold_in(state.key, 7) if use_randomized else None
    key, ts, nus, mb_seeds = _sample_activation_batch(
        cfg, delay_offsets, state.key, problem.num_tasks, state.event, bsz)
    v = state.v

    # Server prox at the batch's first event: stale read at staleness nu_0
    # (vectorized rollback — one masked scatter), own column patched
    # current, then the exact or sketched backward step.
    def refresh(_):
        v_hat = rollback_columns_batch(v, state.delta_ring, state.task_ring,
                                       state.ptr, nus[0], cfg.tau)
        v_hat = v_hat.at[:, ts[0]].set(v[:, ts[0]])
        if use_randomized:
            return svt_randomized(v_hat, jnp.asarray(cfg.eta * problem.lam,
                                                     v_hat.dtype),
                                  rank=cfg.prox_rank, key=k_prox)
        return backward(problem, v_hat, cfg.eta)

    if cfg.prox_every <= bsz:
        # Aligned cadence: refresh unconditionally every batch; the (0, 0)
        # cache stub rides the carry untouched (no copy).
        p = refresh(None)
        p_cache = state.p_cache
    else:
        # Decoupled cadence: refresh only at every k-th batch's first
        # event — exactly the events where the serial delta engine at the
        # same prox_every refreshes — else reuse the carried cache.
        do_prox = (state.event % cfg.prox_every) == 0
        p = jax.lax.cond(do_prox, refresh, lambda _: state.p_cache, None)
        p_cache = p

    # Per-event forward-step gradients at the batch-constant prox.  g_t
    # depends only on (t, p[:, t]) — not on v — so duplicates need no
    # serialization here; the scan body issues the same per-event ops as
    # the serial engine, keeping the bits identical.  With batch_size set
    # each event samples its minibatch from the seed the serial delta
    # engine would derive at that chain position.
    p_cols = p[:, ts]                                        # (d, bsz)

    if cfg.batch_size is None:
        def grad_one(_, inp):
            t, p_t = inp
            return None, problem.task_grad(t, p_t)

        _, g_rows = jax.lax.scan(grad_one, None, (ts, p_cols.T))  # (bsz, d)
    else:
        def grad_one(_, inp):
            t, p_t, s = inp
            return None, problem.task_grad_sampled(t, p_t, s,
                                                   cfg.batch_size)

        _, g_rows = jax.lax.scan(grad_one, None, (ts, p_cols.T, mb_seeds))

    # Delay recording / KM relaxation factors, in event order.
    def relax_one(h, inp):
        t, nu = inp
        h, eta_k = _km_relaxation(cfg, h, t, nu)
        return h, eta_k

    history, eta_ks = jax.lax.scan(relax_one, state.history, (ts, nus))

    # Batched column updates: gather -> fused forward/KM/undo-emit ->
    # scatter, duplicates serialized in event order inside the op.
    v_new, undo_cols = amtl_event_batch(
        v, p_cols, g_rows.T, ts, jnp.asarray(cfg.eta, v.dtype),
        eta_ks.astype(v.dtype))

    # Ring append, batched.  Only the newest `depth` events can ever be
    # rolled back (nu <= tau < depth), so when bsz > depth the overwritten
    # head of the batch is dropped; the surviving slots are distinct and
    # the scatter is deterministic.
    keep = min(bsz, depth)
    slots = (state.ptr + 1 + jnp.arange(bsz - keep, bsz)) % depth
    return BatchAMTLState(
        v=v_new,
        delta_ring=state.delta_ring.at[slots].set(undo_cols[bsz - keep:]),
        task_ring=state.task_ring.at[slots].set(ts[bsz - keep:]),
        ptr=(state.ptr + bsz) % depth,
        event=state.event + bsz,
        p_cache=p_cache,
        history=history,
        key=key,
    )


def _sharded_state_specs(cfg: AMTLConfig,
                         axis: str = TASK_AXIS) -> ShardedAMTLState:
    """PartitionSpec tree mirroring ShardedAMTLState's placement classes.

    The prox cache is the one cfg-dependent placement: replicated for the
    broadcast-back replicated prox, column-sharded like the iterate when
    the rank-distributed prox carries its shard-local reconstruction
    across decoupled-cadence batches (see `prox_cache_spec`).
    """
    sp = task_shard_specs(axis)
    carried = cfg.prox_every > cfg.event_batch
    return ShardedAMTLState(
        v=sp["columns"],
        delta_ring=sp["per_shard"],
        task_ring=sp["replicated"],
        ptr=sp["replicated"],
        event=sp["replicated"],
        p_cache=prox_cache_spec(cfg.prox_mode, carried, axis),
        history=DelayHistory(buf=sp["per_task"], count=sp["per_task"]),
        key=sp["replicated"],
    )


def _one_batch_sharded(problem: MTLProblem, cfg: AMTLConfig,
                       delay_offsets: Array, state: ShardedAMTLState, *,
                       mesh) -> ShardedAMTLState:
    """`event_batch` activations with task columns sharded over "tasks".

    Communication schedule — the paper's server/worker pattern, collectives
    only at prox cadence: each shard reconstructs the stale bits of ITS
    columns from its private undo ring, then per refresh (every k-th batch
    under the decoupled cadence prox_every = k*event_batch) either

      prox_mode="replicated": ONE `all_gather` assembles the (d, T) stale
        iterate and every shard runs the same server prox on it (the
        replicated result is the broadcast back, carried in the replicated
        prox cache between refreshes), or
      prox_mode="distributed": the rank-distributed randomized SVT
        (`svt_randomized_dist`) — one (d, p) `psum` of partial sketches +
        one (p, T/n) `all_gather` of projected-core blocks, thresholded
        reconstruction shard-local, cache column-sharded — O(d*p + p*T)
        bytes instead of O(d*T) and the sketch flops divided over shards;

    gradients, column updates, and ring writes stay shard-local either way.

    Every shard replays the full serial PRNG chain and masks events to
    their owner (sentinel column ids drop foreign events inside the batch
    op), so per-shard execution is a masked replay of `_one_batch`: on a
    1-device mesh every expression below degenerates to the batch engine's
    and the iterates match bitwise on the CPU oracle path; at any shard
    count the event stream and the per-column arithmetic are unchanged.
    """
    from repro.kernels.ops import amtl_event_batch_sharded
    from repro.kernels.ref import shard_local_tasks

    axis = TASK_AXIS
    n_shards = mesh.shape[axis]
    num_tasks = problem.num_tasks
    n_local = num_tasks // n_shards
    depth = cfg.tau + 1
    bsz = cfg.event_batch
    use_randomized = cfg.prox_rank is not None and problem.reg_name == "nuclear"
    distributed = cfg.prox_mode == "distributed"
    plan = ProxPlan(axis=axis, num_tasks=num_tasks, n_local=n_local)

    def local_body(problem_l, offs, st):
        t_off = jax.lax.axis_index(axis) * n_local
        # Folded off the batch-start key, replicated — identical to the
        # serial engines' sketch key.
        k_prox = jax.random.fold_in(st.key, 7) if use_randomized else None
        key, ts, nus, mb_seeds = _sample_activation_batch(
            cfg, offs, st.key, num_tasks, st.event, bsz)
        lts, owned = shard_local_tasks(ts, t_off, n_local)
        lts_clamped = jnp.where(owned, lts, 0)
        v = st.v                                   # (d, n_local)
        ring = st.delta_ring[0]                    # (depth, d) private ring

        # Shard-local stale reconstruction at the batch's first event, then
        # patch that event's column current on its owner shard.  Then the
        # refresh collectives, mode-dependent: replicated assembles the
        # global stale iterate with ONE (d, T) all_gather and runs the
        # identical server prox on every shard (result = broadcast);
        # distributed hands the LOCAL stale block to the rank-distributed
        # SVT, which psums a (d, p) sketch partial, gathers the (p, T/n)
        # projected core, and reconstructs only this shard's columns.
        # With the decoupled cadence this whole branch — collectives
        # included — runs only at every k-th batch; the predicate is
        # replicated, so every shard takes the same branch and the
        # collectives stay SPMD-safe.
        def refresh(_):
            v_hat_loc = rollback_columns_shard(v, ring, st.task_ring,
                                               st.ptr, nus[0], cfg.tau,
                                               t_off)
            c0 = jnp.clip(ts[0] - t_off, 0, n_local - 1)
            own0 = (ts[0] >= t_off) & (ts[0] < t_off + n_local)
            v_hat_loc2 = v_hat_loc.at[:, c0].set(
                jnp.where(own0, v[:, c0], v_hat_loc[:, c0]))
            thresh = jnp.asarray(cfg.eta * problem.lam, v_hat_loc2.dtype)
            if distributed:
                return svt_randomized_dist(v_hat_loc2, thresh,
                                           rank=cfg.prox_rank, key=k_prox,
                                           plan=plan)
            v_hat = jax.lax.all_gather(v_hat_loc2, axis, axis=1, tiled=True)
            if use_randomized:
                return svt_randomized(v_hat, thresh, rank=cfg.prox_rank,
                                      key=k_prox)
            return backward(problem_l, v_hat, cfg.eta)

        if cfg.prox_every <= bsz:
            p = refresh(None)
            p_cache = st.p_cache
        else:
            do_prox = (st.event % cfg.prox_every) == 0
            p = jax.lax.cond(do_prox, refresh, lambda _: st.p_cache, None)
            p_cache = p

        # Per-event prox columns.  The replicated prox yields the global
        # (d, T) result, indexed by global task id; the distributed prox
        # yields only this shard's (d, n_local) block, indexed by local
        # column id (foreign events read the clamped column 0 — their
        # whole pipeline is dropped at the scatter).  On the owner shard
        # both index the same bits of the same reconstruction.
        p_cols = p[:, lts_clamped] if distributed else p[:, ts]  # (d, bsz)

        # Forward-step gradients from the shard-local task data.  Foreign
        # events run on clamped inputs and are dropped at the scatter; the
        # owner's expression is the serial engines', on the same bits.
        # Minibatch seeds come from the replicated chain replay, so the
        # owner samples the same rows of its task's (shard-local) data the
        # unsharded engine would at any shard count.
        if cfg.batch_size is None:
            def grad_one(_, inp):
                t_l, p_t = inp
                return None, problem_l.task_grad(t_l, p_t)

            _, g_rows = jax.lax.scan(grad_one, None,
                                     (lts_clamped, p_cols.T))
        else:
            def grad_one(_, inp):
                t_l, p_t, s = inp
                return None, problem_l.task_grad_sampled(t_l, p_t, s,
                                                         cfg.batch_size)

            _, g_rows = jax.lax.scan(grad_one, None,
                                     (lts_clamped, p_cols.T, mb_seeds))

        # Delay recording / KM relaxation in event order; only the owner
        # keeps each event's history write.
        def relax_one(h, inp):
            t_l, nu, own = inp
            h2, eta_k = _km_relaxation(cfg, h, t_l, nu)
            h = jax.tree.map(lambda a, b: jnp.where(own, a, b), h2, h)
            return h, eta_k

        history, eta_ks = jax.lax.scan(relax_one, st.history,
                                       (lts_clamped, nus, owned))

        # Shard-local batched column updates (foreign events -> sentinel
        # column, dropped inside the op) and private-ring append; the task
        # ring records global ids so later rollbacks can re-mask ownership.
        v_new, undo_cols = amtl_event_batch_sharded(
            v, p_cols, g_rows.T, lts, jnp.asarray(cfg.eta, v.dtype),
            eta_ks.astype(v.dtype))

        keep = min(bsz, depth)
        slots = (st.ptr + 1 + jnp.arange(bsz - keep, bsz)) % depth
        return ShardedAMTLState(
            v=v_new,
            delta_ring=ring.at[slots].set(undo_cols[bsz - keep:])[None],
            task_ring=st.task_ring.at[slots].set(ts[bsz - keep:]),
            ptr=(st.ptr + bsz) % depth,
            event=st.event + bsz,
            p_cache=p_cache,
            history=history,
            key=key,
        )

    sp = task_shard_specs(axis)
    state_specs = _sharded_state_specs(cfg, axis)
    if problem.row_counts is None:
        # Uniform problems keep the exact pre-ragged shard_map signature
        # (and therefore the exact trace/bits of the PR-8 engine).
        def local_step(xs, ys, offs, st):
            problem_l = MTLProblem(xs, ys, problem.loss_name,
                                   problem.reg_name, problem.lam)
            return local_body(problem_l, offs, st)

        step = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(sp["per_task"], sp["per_task"], sp["replicated"],
                      state_specs),
            out_specs=state_specs, check_vma=False)
        return step(problem.xs, problem.ys, delay_offsets, state)

    # Ragged: row_counts ride along as one more per_task input — each
    # shard's local problem masks its own tasks' padded rows, everything
    # else (chain replay, ownership masking, collectives) is unchanged.
    def local_step_ragged(xs, ys, rcs, offs, st):
        problem_l = MTLProblem(xs, ys, problem.loss_name,
                               problem.reg_name, problem.lam, rcs)
        return local_body(problem_l, offs, st)

    step = jax.shard_map(
        local_step_ragged, mesh=mesh,
        in_specs=(sp["per_task"], sp["per_task"], sp["per_task"],
                  sp["replicated"], state_specs),
        out_specs=state_specs, check_vma=False)
    return step(problem.xs, problem.ys, problem.row_counts, delay_offsets,
                state)


def validate_config(cfg: AMTLConfig, reg_name: str | None = None) -> None:
    """The one config-validation path, shared by `make_engine` (and thus
    `amtl_solve`/`amtl_events_only`) and `default_config`.

    `reg_name` enables the problem-dependent prox_rank check when the
    caller knows the regularizer.
    """
    if cfg.engine not in ("delta", "dense", "batch", "sharded"):
        raise ValueError(f"unknown AMTL engine {cfg.engine!r}; "
                         "expected 'delta', 'dense', 'batch', or 'sharded'")
    if cfg.prox_every < 1:
        raise ValueError(f"prox_every must be >= 1, got {cfg.prox_every} "
                         "(1 = exact prox every event)")
    if cfg.event_batch < 1:
        raise ValueError(f"event_batch must be >= 1, got {cfg.event_batch}")
    if cfg.engine in ("dense", "delta") and cfg.event_batch != 1:
        raise ValueError(
            f"engine={cfg.engine!r} processes one event per step; "
            f"event_batch={cfg.event_batch} requires engine='batch' or "
            "engine='sharded'")
    if cfg.prox_rank is not None and reg_name is not None \
            and reg_name != "nuclear":
        raise ValueError(
            "prox_rank selects the randomized SVT refresh, which only "
            f"exists for reg_name='nuclear' (got {reg_name!r})")
    if cfg.engine == "dense" and (cfg.prox_every != 1
                                  or cfg.prox_rank is not None):
        raise ValueError("engine='dense' is the exact seed baseline; "
                         "prox_every>1 / prox_rank require "
                         "engine='delta', 'batch', or 'sharded'")
    if cfg.batch_size is not None:
        if cfg.batch_size < 1:
            raise ValueError(
                f"batch_size must be >= 1 (or None for exact full "
                f"gradients), got {cfg.batch_size}")
        if cfg.engine == "dense":
            raise ValueError(
                "engine='dense' is the exact seed baseline and computes "
                "full gradients only; batch_size requires engine='delta', "
                "'batch', or 'sharded'")
    if cfg.engine in ("batch", "sharded") \
            and cfg.prox_every % cfg.event_batch != 0:
        raise ValueError(
            f"engine={cfg.engine!r} refreshes the server prox only at "
            f"batch boundaries, so prox_every ({cfg.prox_every}) must be a "
            f"multiple of event_batch ({cfg.event_batch})")
    if cfg.prox_mode not in ("replicated", "distributed"):
        raise ValueError(f"unknown prox_mode {cfg.prox_mode!r}; "
                         "expected 'replicated' or 'distributed'")
    if cfg.prox_mode == "distributed":
        if cfg.engine != "sharded":
            raise ValueError(
                "prox_mode='distributed' is the sharded engine's "
                "rank-distributed server prox; "
                f"engine={cfg.engine!r} has no shards to distribute over")
        if cfg.prox_rank is None:
            raise ValueError(
                "prox_mode='distributed' distributes the RANDOMIZED SVT "
                "sketch, so prox_rank must be set (the exact dense SVD "
                "has no column-separable decomposition to distribute)")


def _resolve_mesh(problem: MTLProblem, cfg: AMTLConfig, mesh):
    """Validate/default the mesh; returns (mesh or None, n_shards or None)."""
    if cfg.engine != "sharded":
        if mesh is not None:
            raise ValueError(
                f"mesh is only meaningful for engine='sharded' "
                f"(got engine={cfg.engine!r})")
        return None, None
    if mesh is None:
        from repro.launch.mesh import make_task_mesh
        mesh = make_task_mesh()
    if TASK_AXIS not in mesh.axis_names:
        raise ValueError(
            f"engine='sharded' needs a mesh with a {TASK_AXIS!r} axis; "
            f"got axes {mesh.axis_names}")
    n_shards = mesh.shape[TASK_AXIS]
    if problem.num_tasks % n_shards != 0:
        raise ValueError(
            f"num_tasks ({problem.num_tasks}) must be divisible by the "
            f"{TASK_AXIS!r} mesh axis size ({n_shards})")
    return mesh, n_shards


def _step_fn(cfg: AMTLConfig, mesh):
    if cfg.engine == "dense":
        return _one_event_dense
    if cfg.engine == "delta":
        return _one_event_delta
    if cfg.engine == "batch":
        return _one_batch
    return functools.partial(_one_batch_sharded, mesh=mesh)


@functools.partial(jax.jit, static_argnames=("cfg", "num_events", "mesh"))
def _run_events(problem: MTLProblem, cfg: AMTLConfig, state,
                delay_offsets: Array, num_events: int, mesh=None):
    """Advance any engine state by `num_events` activations (jitted).

    Module-level so the compile cache is shared across every AMTLEngine
    built for the same (cfg, mesh, num_events) — `make_engine` is cheap to
    call repeatedly.
    """
    step = _step_fn(cfg, mesh)
    per_step = cfg.event_batch if cfg.engine in ("batch", "sharded") else 1
    with jax.default_matmul_precision(F32_MATMUL):
        return jax.lax.fori_loop(
            0, num_events // per_step,
            lambda _, s: step(problem, cfg, delay_offsets, s), state)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _iterate_metrics(problem: MTLProblem, cfg: AMTLConfig, v: Array):
    """(W, objective, BF residual) of the current iterate V."""
    with jax.default_matmul_precision(F32_MATMUL):
        w = backward(problem, v, cfg.eta)
        return (w, problem.objective(w),
                fixed_point_residual(problem, v, cfg.eta))


class AMTLEngine(NamedTuple):
    """A resumable AMTL session: pure jittable functions over an engine
    state (the public stepwise API; `make_engine` builds one).

    init(v0, key) -> state
        Fresh engine state for a (d, T) initial iterate and a PRNG key.
    run(state, delay_offsets, num_events) -> state
        Advance the session by `num_events` activations (jitted; one
        compile per distinct num_events).  `delay_offsets` may be None
        (all-zero mean staleness).  num_events must be a multiple of
        `events_per_step`; run composes bitwise across any such split,
        and a state that round-tripped through `repro.checkpoint`
        resumes bitwise.
    iterate(state) -> V
        The newest (d, T) iterate held by the state (any engine).
    events_per_step
        Step granularity: `event_batch` for the batch/sharded engines,
        1 for dense/delta.
    num_tasks
        T, the problem's task count — so session consumers (the
        learning-while-serving platform in `repro.serve`, examples)
        can validate task ids / size event streams without carrying
        the problem alongside the engine.
    """
    init: Callable[[Array, Array], Any]
    run: Callable[[Any, Array | None, int], Any]
    iterate: Callable[[Any], Array]
    events_per_step: int
    num_tasks: int


def make_engine(problem: MTLProblem, cfg: AMTLConfig,
                mesh=None) -> AMTLEngine:
    """Build the resumable session engine for `cfg` (the public API).

    `mesh` (engine='sharded' only) is the 1-D "tasks" mesh to partition
    the task columns over; default is all visible devices
    (`make_task_mesh`).  Validation runs here, eagerly — `run` never
    raises on a well-formed event count.
    """
    validate_config(cfg, problem.reg_name)
    if cfg.engine == "dense" and problem.row_counts is not None:
        raise ValueError(
            "engine='dense' is the exact uniform seed baseline; ragged "
            "problems (row_counts set) require engine='delta', 'batch', "
            "or 'sharded'")
    mesh, n_shards = _resolve_mesh(problem, cfg, mesh)
    num_tasks = problem.num_tasks
    per_step = cfg.event_batch if cfg.engine in ("batch", "sharded") else 1

    def init(v0: Array, key: Array):
        if cfg.engine == "dense":
            return init_state(cfg, v0, num_tasks, key)
        if cfg.engine == "delta":
            return init_delta_state(cfg, v0, num_tasks, key)
        if cfg.engine == "batch":
            return init_batch_state(cfg, v0, num_tasks, key)
        return init_sharded_state(cfg, v0, num_tasks, key, n_shards)

    def run(state, delay_offsets, num_events: int):
        if num_events % per_step != 0:
            raise ValueError(
                f"num_events ({num_events}) must be a multiple of "
                f"event_batch ({per_step}) for engine={cfg.engine!r}")
        if delay_offsets is None:
            delay_offsets = jnp.zeros((num_tasks,), jnp.float32)
        return _run_events(problem, cfg, state, delay_offsets,
                           int(num_events), mesh)

    return AMTLEngine(init=init, run=run, iterate=current_iterate,
                      events_per_step=per_step, num_tasks=num_tasks)


def amtl_solve(problem: MTLProblem, cfg: AMTLConfig, v0: Array, key: Array,
               num_epochs: int, events_per_epoch: int | None = None,
               delay_offsets: Array | None = None, mesh=None) -> AMTLResult:
    """Run AMTL for num_epochs * events_per_epoch activations.

    One "epoch" defaults to T events (each node activated once in
    expectation), matching the paper's per-iteration accounting ("every task
    node updates one forward step for each iteration").

    Thin wrapper over the session API: each epoch is one `engine.run`
    advance followed by the (full-SVD) objective/residual metric tail.
    `mesh` (engine='sharded' only) is the 1-D "tasks" mesh to partition the
    task columns over; default is all visible devices (`make_task_mesh`).
    """
    engine = make_engine(problem, cfg, mesh)
    if events_per_epoch is None:
        events_per_epoch = problem.num_tasks
    if events_per_epoch % engine.events_per_step != 0:
        raise ValueError(
            f"events_per_epoch ({events_per_epoch}) must be a multiple of "
            f"event_batch ({engine.events_per_step}) for "
            f"engine={cfg.engine!r}")

    state = engine.init(v0, key)
    objs, ress, w = [], [], None
    for _ in range(num_epochs):
        state = engine.run(state, delay_offsets, events_per_epoch)
        w, obj, res = _iterate_metrics(problem, cfg, engine.iterate(state))
        objs.append(obj)
        ress.append(res)
    v = engine.iterate(state)
    if w is None:                      # num_epochs == 0
        w = _iterate_metrics(problem, cfg, v)[0]
    empty = jnp.zeros((0,), jnp.float32)
    return AMTLResult(v, w,
                      jnp.stack(objs) if objs else empty,
                      jnp.stack(ress) if ress else empty)


def amtl_events_only(problem: MTLProblem, cfg: AMTLConfig, v0: Array,
                     key: Array, num_events: int,
                     delay_offsets: Array | None = None, mesh=None):
    """Run `num_events` activations with NO per-epoch metric tail.

    Returns the final engine state (AMTLState, DeltaAMTLState,
    BatchAMTLState, or ShardedAMTLState, matching `cfg.engine`).  This is
    the events/sec benchmark path: it isolates the per-event engine cost
    from the (full-SVD) objective/residual instrumentation of `amtl_solve`.
    Thin wrapper over the session API (init + one `run`).
    """
    engine = make_engine(problem, cfg, mesh)
    return engine.run(engine.init(v0, key), delay_offsets, num_events)


def current_iterate(state) -> Array:
    """The newest iterate V held by any engine's state."""
    if isinstance(state, (DeltaAMTLState, BatchAMTLState, ShardedAMTLState)):
        return state.v
    return state.ring[state.ptr]


def default_config(problem: MTLProblem, tau: int = 4, c: float = 0.9,
                   dynamic_step: bool = False, safety: float = 1.0, *,
                   engine: str = "delta", prox_every: int = 1,
                   prox_rank: int | None = None, event_batch: int = 1,
                   prox_mode: str = "replicated",
                   batch_size: int | None = None) -> AMTLConfig:
    """Step sizes from Theorem 1: eta < 2/L, eta_k <= c/(2 tau/sqrt(T)+1).

    Engine-selection kwargs (`engine`, `prox_every`, `prox_rank`,
    `event_batch`, `prox_mode`, `batch_size`) go through
    `validate_config` — the same path `make_engine` runs — so an invalid
    combination fails here, not at the first solve.
    """
    lip = problem.lipschitz()
    cfg = AMTLConfig(
        eta=safety / lip,
        eta_k=amtl_max_step(tau, problem.num_tasks, c),
        tau=tau,
        dynamic_step=dynamic_step,
        engine=engine,
        prox_every=prox_every,
        prox_rank=prox_rank,
        event_batch=event_batch,
        prox_mode=prox_mode,
        batch_size=batch_size,
    )
    validate_config(cfg, problem.reg_name)
    return cfg
