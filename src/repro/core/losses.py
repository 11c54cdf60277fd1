"""Per-task loss functions for regularized MTL.

The paper assumes each task t has data (x_t, y_t) and a convex, L-Lipschitz-
differentiable loss ell_t (least squares for regression, logistic for binary
classification; tasks may be heterogeneous, Sec. III-A / ref [12]).

Two dataset layouts are supported:

  * "stacked": all tasks share n and d -> X (T, n, d), Y (T, n).  Fully
    jit/vmap-friendly; used by the SPMD engines and property tests.
  * python lists of per-task (x_t, y_t) arrays with ragged n_t; used by the
    event-driven simulator (each node jits its own gradient).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


class TaskLoss(NamedTuple):
    name: str
    value: Callable[[Array, Array, Array], Array]   # (x, y, w) -> scalar
    grad: Callable[[Array, Array, Array], Array]    # (x, y, w) -> (d,)
    lipschitz: Callable[[Array], float]             # (x,) -> L bound
    predict: Callable[[Array], Array]               # linear score -> output
    # ragged variants over a padded row buffer: rows >= n_t (traced) are
    # masked out of the per-row loss/residual.  With n_t == n the all-true
    # mask passes bits through, so the uniform case stays bitwise equal to
    # the unmasked expressions — the ragged path's equivalence anchor.
    value_masked: Callable[[Array, Array, Array, Array], Array]
    grad_masked: Callable[[Array, Array, Array, Array], Array]


# -- least squares:  ||x w - y||_2^2  (paper Eq. IV.1 uses the unnormalized
#    squared loss; gradient 2 x^T (x w - y), L = 2*sigma_max(x^T x)) ---------

def lstsq_value(x: Array, y: Array, w: Array) -> Array:
    r = x @ w - y
    return jnp.sum(r * r)


def lstsq_grad(x: Array, y: Array, w: Array) -> Array:
    return 2.0 * (x.T @ (x @ w - y))


def lstsq_lipschitz(x: Array) -> float:
    s = np.linalg.svd(np.asarray(x, dtype=np.float64), compute_uv=False)
    return float(2.0 * s[0] ** 2) if s.size else 1.0


def lstsq_predict(score: Array) -> Array:
    """Regression serves the raw linear score x·w."""
    return score


def _row_mask(x: Array, n_t: Array) -> Array:
    """(n,) bool: row index < n_t (traced valid-row count)."""
    return jnp.arange(x.shape[0]) < n_t


def lstsq_value_masked(x: Array, y: Array, w: Array, n_t: Array) -> Array:
    r = jnp.where(_row_mask(x, n_t), x @ w - y, 0.0)
    return jnp.sum(r * r)


def lstsq_grad_masked(x: Array, y: Array, w: Array, n_t: Array) -> Array:
    r = jnp.where(_row_mask(x, n_t), x @ w - y, 0.0)
    return 2.0 * (x.T @ r)


# -- logistic: sum log(1 + exp(-y x w)), y in {-1, +1} ----------------------

def logistic_value(x: Array, y: Array, w: Array) -> Array:
    z = y * (x @ w)
    return jnp.sum(jnp.logaddexp(0.0, -z))


def logistic_grad(x: Array, y: Array, w: Array) -> Array:
    z = y * (x @ w)
    s = jax.nn.sigmoid(-z)          # = 1 - sigmoid(z)
    return -(x.T @ (s * y))


def logistic_lipschitz(x: Array) -> float:
    s = np.linalg.svd(np.asarray(x, dtype=np.float64), compute_uv=False)
    return float(0.25 * s[0] ** 2) if s.size else 1.0


def logistic_predict(score: Array) -> Array:
    """Classification serves P(y = +1) = sigmoid(x·w)."""
    return jax.nn.sigmoid(score)


def logistic_value_masked(x: Array, y: Array, w: Array, n_t: Array) -> Array:
    # A zero row is NOT neutral for the logistic value (logaddexp(0, 0) =
    # log 2), so the per-row loss itself is masked, not the data.
    z = y * (x @ w)
    per_row = jnp.logaddexp(0.0, -z)
    return jnp.sum(jnp.where(_row_mask(x, n_t), per_row, 0.0))


def logistic_grad_masked(x: Array, y: Array, w: Array, n_t: Array) -> Array:
    z = y * (x @ w)
    s = jax.nn.sigmoid(-z)          # = 1 - sigmoid(z)
    return -(x.T @ jnp.where(_row_mask(x, n_t), s * y, 0.0))


LOSSES: dict[str, TaskLoss] = {
    "lstsq": TaskLoss("lstsq", lstsq_value, lstsq_grad, lstsq_lipschitz,
                      lstsq_predict, lstsq_value_masked, lstsq_grad_masked),
    "logistic": TaskLoss("logistic", logistic_value, logistic_grad,
                         logistic_lipschitz, logistic_predict,
                         logistic_value_masked, logistic_grad_masked),
}


def get_loss(name: str) -> TaskLoss:
    return LOSSES[name]


class MTLProblem(NamedTuple):
    """A stacked multi-task problem: T padded equal-capacity tasks.

    xs: (T, n, d)  ys: (T, n)  loss: one of LOSSES (homogeneous stacked case;
    heterogeneous losses are handled by the simulator's list layout).

    `row_counts` (optional, (T,) int32) makes the problem RAGGED: task t
    owns only its first row_counts[t] rows of the shared n-row buffer;
    rows past n_t are padding (or data appended to a `TaskStore` buffer
    but not yet published) and are masked out of every loss, gradient,
    and minibatch selection.  row_counts=None means every row is valid —
    the layout and every bitwise contract of the uniform problem are
    preserved (None is an empty pytree subtree, so existing 5-field
    constructions and jit traces are untouched).
    """

    xs: Array
    ys: Array
    loss_name: str
    reg_name: str
    lam: float
    row_counts: Array | None = None

    @property
    def num_tasks(self) -> int:
        return self.xs.shape[0]

    @property
    def dim(self) -> int:
        return self.xs.shape[2]

    def loss_value(self, w_cols: Array) -> Array:
        """f(W) = sum_t ell_t(w_t); w_cols is (d, T)."""
        loss = get_loss(self.loss_name)
        if self.row_counts is None:
            per_task = jax.vmap(loss.value, in_axes=(0, 0, 1))(
                self.xs, self.ys, w_cols)
        else:
            per_task = jax.vmap(loss.value_masked, in_axes=(0, 0, 1, 0))(
                self.xs, self.ys, w_cols, self.row_counts)
        return jnp.sum(per_task)

    def task_grad(self, t: Array, w_t: Array) -> Array:
        """grad of task t's loss at w_t (dynamic task index)."""
        loss = get_loss(self.loss_name)
        x_t = jax.lax.dynamic_index_in_dim(self.xs, t, axis=0, keepdims=False)
        y_t = jax.lax.dynamic_index_in_dim(self.ys, t, axis=0, keepdims=False)
        if self.row_counts is None:
            return loss.grad(x_t, y_t, w_t)
        n_t = jax.lax.dynamic_index_in_dim(self.row_counts, t, axis=0,
                                           keepdims=False)
        return loss.grad_masked(x_t, y_t, w_t, n_t)

    def task_grad_sampled(self, t: Array, w_t: Array, seed: Array,
                          batch_size: int) -> Array:
        """Unbiased seeded-minibatch gradient of task t's loss at w_t.

        SGD-AMTL's forward step: the exactly-`bsz` minibatch (bsz =
        min(batch_size, n_t), the simulator's clamp) of smallest counter
        hashes of (seed, row), scaled by (n_t/bsz).  For lstsq this is the
        fused `ops.lstsq_grad_sampled` (in-kernel selection on TPU, a
        static-size O(bsz d) gather on the CPU oracle path); other losses
        mask the dropped rows of x to zero — a zero row contributes
        nothing to any x^T(...) gradient — and scale the same way.
        batch_size >= n_t reproduces `task_grad` (bitwise for lstsq on a
        fixed backend).  Ragged problems restrict the selection to rows
        < row_counts[t]; uniform row_counts keep the selection, scale,
        and contraction bits of the unmasked path.
        """
        from repro.kernels.ops import lstsq_grad_sampled
        from repro.kernels.ref import sample_mask_masked_ref, sample_mask_ref

        x_t = jax.lax.dynamic_index_in_dim(self.xs, t, axis=0, keepdims=False)
        y_t = jax.lax.dynamic_index_in_dim(self.ys, t, axis=0, keepdims=False)
        n_t = None
        if self.row_counts is not None:
            n_t = jax.lax.dynamic_index_in_dim(self.row_counts, t, axis=0,
                                               keepdims=False)
        if self.loss_name == "lstsq":
            return lstsq_grad_sampled(x_t, w_t, y_t, seed,
                                      batch_size=batch_size, n_t=n_t)
        n = self.xs.shape[1]
        if n_t is None:
            bsz = min(batch_size, n)
            mask = sample_mask_ref(n, batch_size, seed)
            x_s = jnp.where(mask[:, None], x_t, 0.0)
            return (n / bsz) * get_loss(self.loss_name).grad(x_s, y_t, w_t)
        bsz = jnp.minimum(jnp.int32(batch_size), n_t.astype(jnp.int32))
        mask = sample_mask_masked_ref(n, batch_size, seed, n_t)
        x_s = jnp.where(mask[:, None], x_t, 0.0)
        scale = (n_t.astype(jnp.float32)
                 / jnp.maximum(bsz, 1).astype(jnp.float32))
        return scale * get_loss(self.loss_name).grad(x_s, y_t, w_t)

    def sampled_grads_batched(self, batch_size: int) -> bool:
        """Whether `task_grads_sampled` computes its events in one batched
        op (`ops.lstsq_grad_sampled_batch`): the lstsq loss, and a
        minibatch smaller than the row buffer."""
        return self.loss_name == "lstsq" and batch_size < self.xs.shape[1]

    def task_grads_sampled(self, ts: Array, ws: Array, seeds: Array,
                           batch_size: int) -> Array:
        """(B, d): `task_grad_sampled(ts[e], ws[e], seeds[e], batch_size)`
        of each event e of a batch.

        Where `sampled_grads_batched` holds, one batched op selects,
        gathers and contracts every event's minibatch at once on a TPU
        (its oracle path is the per-event scan below, bit for bit);
        otherwise the events are scanned one by one.
        """
        from repro.kernels.ops import lstsq_grad_sampled_batch

        if self.sampled_grads_batched(batch_size):
            return lstsq_grad_sampled_batch(
                self.xs, self.ys, ts, ws, seeds, batch_size=batch_size,
                row_counts=self.row_counts)

        def one(_, inp):
            t, w_t, seed = inp
            return None, self.task_grad_sampled(t, w_t, seed, batch_size)

        return jax.lax.scan(one, None, (ts, ws, seeds))[1]

    def full_grad(self, w_cols: Array) -> Array:
        """nabla f(W) column-stacked, (d, T) — paper Eq. III.2."""
        loss = get_loss(self.loss_name)
        if self.row_counts is None:
            g = jax.vmap(loss.grad, in_axes=(0, 0, 1))(
                self.xs, self.ys, w_cols)
        else:
            g = jax.vmap(loss.grad_masked, in_axes=(0, 0, 1, 0))(
                self.xs, self.ys, w_cols, self.row_counts)
        return g.T  # (T, d) -> (d, T)

    def objective(self, w_cols: Array) -> Array:
        from repro.core.prox import get_regularizer
        reg = get_regularizer(self.reg_name)
        return self.loss_value(w_cols) + self.lam * reg.value(w_cols)

    def lipschitz(self) -> float:
        """max_t L_t — the coordinate-wise Lipschitz bound used for eta.

        Ragged problems bound each task over its VALID rows only (padding
        rows are zero or unpublished data and must not inflate L_t).
        """
        loss = get_loss(self.loss_name)
        if self.row_counts is None:
            return max(loss.lipschitz(np.asarray(self.xs[t]))
                       for t in range(self.num_tasks))
        counts = np.asarray(self.row_counts)
        return max(loss.lipschitz(np.asarray(self.xs[t])[:int(counts[t])])
                   for t in range(self.num_tasks))


# row_counts is a pytree CHILD: None flattens to an empty subtree, so the
# uniform problem's treedef/leaves — and every jit trace keyed on them —
# are identical to the pre-ragged 5-field registration.
jax.tree_util.register_pytree_node(
    MTLProblem,
    lambda p: ((p.xs, p.ys, p.row_counts),
               (p.loss_name, p.reg_name, p.lam)),
    lambda aux, ch: MTLProblem(ch[0], ch[1], aux[0], aux[1], aux[2], ch[2]),
)
