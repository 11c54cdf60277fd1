"""Plain AMTL (arXiv 1609.09563, Algorithm 1) for the comparison that
decides `correct`.  It imports nothing of the program.

The semantics it follows are those the configuration states for the
batch engine with SGD minibatches and the randomized prox:

  * events come from one serial PRNG chain: per event the chain key k
    splits into (k', k_task, k_delay); the task is uniform over T, the
    staleness nu = min(round(U[0, 1)), tau, events so far); the event's
    minibatch seed is bits(fold_in(k, 11));
  * the server prox is refreshed at the first event of every batch of
    `event_batch`, on the iterate as it was nu events ago with the
    event's own column current: a randomized SVT of rank + 8 columns
    whose Gaussian test matrix comes from counter hashes of the seed
    bits(fold_in(k_batch, 7)), thresholded at eta * lam;
  * each event steps its own column:  v_t += eta_k (p_t - eta g_t - v_t)
    with g_t = (n_t / b) 2 X_S^T (X_S p_t - y_S), S the b = min(32, n_t)
    valid rows of smallest hash(seed, row).

It keeps every past iterate of the last tau + 1 events (no undo log) and
applies the events one at a time, so duplicate tasks in a batch read
their own earlier write by construction.  `precision="high"` computes
every product in three bfloat16 passes (the control); "highest" in f32.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

U32 = jnp.uint32
HIGHEST = jax.lax.Precision.HIGHEST


class RefState(NamedTuple):
    v: jax.Array        # (d, T) newest iterate
    hist: jax.Array     # (tau + 1, d, T) iterates of the last events
    ptr: jax.Array      # slot of the newest iterate in hist
    event: jax.Array    # events applied so far
    key: jax.Array      # chain key


def init(v0: jax.Array, key: jax.Array, tau: int) -> RefState:
    return RefState(v0, jnp.broadcast_to(v0, (tau + 1, *v0.shape)),
                    jnp.int32(0), jnp.int32(0), key)


def counter_hash(seed, ctr):
    """lowbias32 finalizer of (seed, counter), uint32."""
    x = ctr * U32(0x9E3779B9) ^ seed
    x = (x ^ (x >> 16)) * U32(0x7FEB352D)
    x = (x ^ (x >> 15)) * U32(0x846CA68B)
    return x ^ (x >> 16)


def gaussian_block(seed, rows: int, cols: int):
    """(rows, cols) normals; entry (r, c) from counter r * cols + c by
    Box-Muller over two hashes (24-bit uniforms, u1 in (0, 1])."""
    ctr = (jnp.arange(rows, dtype=U32)[:, None] * U32(cols)
           + jnp.arange(cols, dtype=U32)[None, :])
    u1 = counter_hash(seed, ctr * U32(2))
    u2 = counter_hash(seed, ctr * U32(2) + U32(1))
    f1 = ((u1 >> 8).astype(jnp.int32).astype(jnp.float32) + 1.0) * 2.0 ** -24
    f2 = (u2 >> 8).astype(jnp.int32).astype(jnp.float32) * 2.0 ** -24
    return jnp.sqrt(-2.0 * jnp.log(f1)) * jnp.cos(
        jnp.float32(2.0 * 3.141592653589793) * f2)


def matmul(a, b, precision: str):
    """a @ b in f32 ("highest") or in three bf16 passes ("high")."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    hi = lambda z: z.astype(jnp.bfloat16).astype(jnp.float32)
    a1, b1 = hi(a), hi(b)
    a2, b2 = hi(a - a1), hi(b - b1)
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    return mm(a1, b1) + (mm(a1, b2) + mm(a2, b1))


def randomized_svt(w, thresh, seed, rank: int, precision: str):
    d, t = w.shape
    p = min(rank + 8, d, t)
    y = matmul(w, gaussian_block(seed, t, p), precision)
    q, _ = jnp.linalg.qr(y)
    core = matmul(q.T, w, precision)
    u, s, vt = jnp.linalg.svd(core, full_matrices=False)
    s = jnp.maximum(s - thresh, 0.0)
    return matmul(matmul(q, u, precision) * s[None, :], vt, precision)


def sampled_grad(x_t, y_t, n_t, w, seed, batch: int, precision: str):
    """(n_t / b) 2 X_S^T (X_S w - y_S) over the b smallest-hash valid rows."""
    cap = x_t.shape[0]
    rows = jnp.arange(cap, dtype=U32)
    h = jnp.where(rows < n_t.astype(U32), counter_hash(seed, rows),
                  U32(0xFFFFFFFF))
    take = min(batch, cap)
    sel = jnp.argsort(h, stable=True)[:take]
    bsz = jnp.minimum(batch, n_t)
    xs, ys = x_t[sel], y_t[sel]
    r = jnp.where(jnp.arange(take) < bsz, matmul(xs, w, precision) - ys, 0.0)
    scale = 2.0 * (n_t.astype(jnp.float32)
                   / jnp.maximum(bsz, 1).astype(jnp.float32))
    return scale * matmul(xs.T, r, precision)


def _one_batch(xs, ys, counts, st: RefState, *, cfg: dict, eta_k: float,
               precision: str) -> RefState:
    t_count = xs.shape[0]
    tau, b = cfg["tau"], cfg["event_batch"]
    depth = tau + 1

    def draw(k, i):
        seed = jax.random.bits(jax.random.fold_in(k, 11), dtype=U32)
        k, k_task, k_delay = jax.random.split(k, 3)
        t = jax.random.randint(k_task, (), 0, t_count)
        nu = jnp.minimum(jnp.round(0.0 + 1.0 * jax.random.uniform(k_delay))
                         .astype(jnp.int32), jnp.minimum(tau, st.event + i))
        return k, (t, nu, seed)

    key, (ts, nus, seeds) = jax.lax.scan(draw, st.key, jnp.arange(b))
    sketch_seed = jax.random.bits(jax.random.fold_in(st.key, 7), dtype=U32)
    stale = st.hist[(st.ptr - nus[0]) % depth]
    stale = stale.at[:, ts[0]].set(st.v[:, ts[0]])
    p = randomized_svt(stale, jnp.float32(cfg["eta"] * cfg["lam"]),
                       sketch_seed, cfg["prox_rank"], precision)

    def event(carry, inp):
        v, hist, ptr = carry
        t, seed = inp
        g = sampled_grad(xs[t], ys[t], counts[t], p[:, t], seed,
                         cfg["batch_size"], precision)
        col = v[:, t]
        v = v.at[:, t].set(col + eta_k * (p[:, t] - cfg["eta"] * g - col))
        ptr = (ptr + 1) % depth
        return (v, hist.at[ptr].set(v), ptr), None

    (v, hist, ptr), _ = jax.lax.scan(event, (st.v, st.hist, st.ptr),
                                     (ts, seeds))
    return RefState(v, hist, ptr, st.event + b, key)


@functools.partial(jax.jit, static_argnames=("cfg_items", "eta_k",
                                             "precision"))
def _run(xs, ys, counts, st, n_batches, *, cfg_items, eta_k, precision):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision(precision):
        return jax.lax.fori_loop(
            0, n_batches,
            lambda _, s: _one_batch(xs, ys, counts, s, cfg=cfg, eta_k=eta_k,
                                    precision=precision), st)


SOLVER_KEYS = ("tau", "event_batch", "eta", "lam", "prox_rank", "batch_size")


def run(xs, ys, counts, st: RefState, num_events: int, cfg: dict,
        eta_k: float, precision: str = "highest") -> RefState:
    """Apply `num_events` (a multiple of event_batch) events to `st`."""
    b = cfg["event_batch"]
    if num_events % b:
        raise ValueError(f"{num_events} events is not a multiple of {b}")
    items = tuple((k, cfg[k]) for k in SOLVER_KEYS)
    return _run(xs, ys, jnp.asarray(counts, jnp.int32), st,
                jnp.int32(num_events // b), cfg_items=items,
                eta_k=float(eta_k), precision=precision)

