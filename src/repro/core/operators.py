"""Operator-splitting building blocks (paper Sec. III-B/III-C).

Forward operator   F = I - eta * grad(f)          (separable across tasks)
Backward operator  B = (I + eta*lam*dg)^{-1}      (= prox, NOT separable)

Forward-backward:   W+ = B(F(W))     — classic proximal gradient (SMTL)
Backward-forward:   V+ = F(B(V))     — the paper's reordering: the *outer*
                                       operator is separable, so a single task
                                       block of V can be updated (Eq. III.4).
W* is recovered from V* with one extra backward step: W* = B(V*).

Both compositions are nonexpansive for eta in (0, 2/L), so the KM iteration
   v <- v + eta_k (Op(v) - v)
converges (Theorem 1 via ARock [6]).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.losses import MTLProblem
from repro.core.prox import get_regularizer

Array = jax.Array


class SplittingConfig(NamedTuple):
    eta: float        # gradient / prox step (0, 2/L)
    lam: float        # regularization weight
    reg_name: str


def backward(problem: MTLProblem, v: Array, eta: float) -> Array:
    """prox_{eta*lam*g}(V)."""
    reg = get_regularizer(problem.reg_name)
    return reg.prox(v, jnp.asarray(eta * problem.lam, v.dtype))


def forward(problem: MTLProblem, w: Array, eta: float) -> Array:
    """(I - eta * grad f)(W) — separable per task column."""
    return w - eta * problem.full_grad(w)


def forward_backward(problem: MTLProblem, w: Array, eta: float) -> Array:
    """One synchronous proximal-gradient step (SMTL inner map)."""
    return backward(problem, forward(problem, w, eta), eta)


def backward_forward(problem: MTLProblem, v: Array, eta: float) -> Array:
    """V+ = (I - eta grad f)(prox(V)) — the paper's reordered iteration."""
    return forward(problem, backward(problem, v, eta), eta)


def km_step(v: Array, op_v: Array, eta_k: Array) -> Array:
    """Krasnosel'skii-Mann relaxation: v + eta_k (Op(v) - v)."""
    return v + eta_k * (op_v - v)


def km_block_update(v_t: Array, prox_t: Array, grad_t: Array,
                    eta: Array, eta_k: Array) -> Array:
    """Paper Eq. III.4 — the fused per-task-block AMTL update.

    v_t^{k+1} = v_t + eta_k * ( prox(v_hat)_t - eta * grad_t(prox(v_hat)_t) - v_t )

    This is the op the `km_update` Pallas kernel fuses.
    """
    return v_t + eta_k * (prox_t - eta * grad_t - v_t)


def rollback_columns(v: Array, delta_ring: Array, task_ring: Array,
                     ptr: Array, nu: Array, tau: int) -> Array:
    """Reconstruct the iterate from `nu` events ago out of an undo log.

    `delta_ring[s]` holds the exact pre-write bits of column `task_ring[s]`
    at the event written to slot `s`; `ptr` is the newest event's slot.
    Restoring the `nu` newest entries newest-first replays each overwritten
    column back to its stored value, so the result is bitwise identical to
    the dense ring's `ring[ptr - nu]` — in O(tau*d) work instead of
    materializing a (tau+1, d, T) ring.

    `tau` is static (loop trip count); `nu <= min(tau, event)` is dynamic
    and masks which entries actually restore.  A masked-out step writes a
    column back onto itself, which is a bitwise no-op.
    """
    if tau == 0:
        return v
    depth = tau + 1

    def undo(j, vh):
        slot = (ptr - j) % depth          # j=0 -> newest event
        t_j = task_ring[slot]
        col = jnp.where(j < nu, delta_ring[slot], vh[:, t_j])
        return vh.at[:, t_j].set(col)

    return jax.lax.fori_loop(0, tau, undo, v)


def rollback_columns_batch(v: Array, delta_ring: Array, task_ring: Array,
                           ptr: Array, nu: Array, tau: int) -> Array:
    """Vectorized multi-column rollback: one masked scatter, no fori_loop.

    Bitwise-equal to `rollback_columns`: the newest-first sequential replay
    ends with the OLDEST restored entry per column winning, so it suffices
    to select, for each column touched within the rollback window, the
    entry with the largest offset j < nu and scatter all winners at once.
    Losers and masked-out slots scatter to column index T, which is out of
    bounds and dropped (`mode="drop"`).  Winners have distinct column
    indices, so the scatter is deterministic; the written bits are the
    stored pre-write bits verbatim.

    The batch engine uses this at its per-batch prox refresh, where the
    fori_loop's tau sequential (d,)-column writes would serialize for no
    reason; `rollback_columns` stays as the serial semantic reference the
    tests check it against.  The winner selection lives in
    `rollback_columns_shard`; this is the t_offset=0 case, where every
    task is owned.
    """
    return rollback_columns_shard(v, delta_ring, task_ring, ptr, nu, tau,
                                  jnp.zeros((), jnp.int32))


def rollback_columns_shard(v: Array, delta_ring: Array, task_ring: Array,
                           ptr: Array, nu: Array, tau: int,
                           t_offset: Array) -> Array:
    """Shard-local rollback: `task_ring` holds GLOBAL task ids, `v` is the
    shard's (d, T_local) column block covering global columns
    [t_offset, t_offset + T_local).

    Same winner selection as the sequential replay — the oldest active
    entry per column wins — but entries whose task lives on another shard
    are dropped alongside the masked-out slots (their restore happens on
    the owner, which holds the stored pre-write bits).  Concatenating the
    per-shard results in shard order is therefore bitwise-equal to the
    global `rollback_columns_batch` — which is this function at
    t_offset=0, every task owned.
    """
    if tau == 0:
        return v
    depth = tau + 1
    n_local = v.shape[1]
    j = jnp.arange(tau)                              # j=0 -> newest event
    slots = (ptr - j) % depth
    tasks = task_ring[slots]                         # (tau,) global ids
    active = j < nu
    # shadowed[j]: an older active entry (j' > j) touches the same column,
    # so entry j's restore would be overwritten in the sequential replay.
    same = tasks[None, :] == tasks[:, None]
    older = j[None, :] > j[:, None]
    shadowed = jnp.any(same & older & active[None, :], axis=1)
    local = tasks - t_offset
    owned = (local >= 0) & (local < n_local)
    win = active & ~shadowed & owned
    cols = jnp.where(win, local, n_local)            # n_local => dropped
    return v.at[:, cols].set(delta_ring[slots].T, mode="drop")


def fixed_point_residual(problem: MTLProblem, v: Array, eta: float) -> Array:
    """||BF(v) - v||_F — zero exactly at a fixed point of the BF operator."""
    return jnp.linalg.norm(backward_forward(problem, v, eta) - v)


def amtl_max_step(tau: int, num_tasks: int, c: float = 0.9) -> float:
    """Theorem 1 step-size cap: eta_k <= c / (2*tau/sqrt(T) + 1), 0<c<1."""
    if not 0.0 < c < 1.0:
        raise ValueError("c must be in (0,1)")
    return c / (2.0 * tau / (num_tasks ** 0.5) + 1.0)
