"""Proximal operators for regularized multi-task learning.

The paper couples T task models W = [w_1 ... w_T] in R^{d x T} through a
non-smooth regularizer g(W).  The central server's "backward" step is
prox_{eta*lambda*g}.  All operators here are pure jnp, jit- and vmap-safe,
and differentiable where the math allows.

Registry keys match the MALSAR formulations cited in the paper:
  nuclear      - shared subspace learning, ||W||_*           (paper Eq. IV.2)
  l21          - joint feature learning, sum_i ||w^i||_2     (paper Sec. III-A)
  l1           - elementwise sparsity
  elastic_net  - l1 + ridge (paper's strict-convexity trick, ref [25])
  ridge        - squared Frobenius
  none         - identity (independent single-task learning)
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array


class Regularizer(NamedTuple):
    """A non-smooth penalty g with its proximal mapping.

    value(W)            -> scalar g(W)
    prox(W, t)          -> argmin_Z  (1/2t)||Z - W||_F^2 + g(Z)
    """

    name: str
    value: Callable[[Array], Array]
    prox: Callable[[Array, Array], Array]
    separable_rows: bool  # prox decomposes over rows of W
    separable_cols: bool  # prox decomposes over columns (tasks)


# ---------------------------------------------------------------------------
# nuclear norm: singular value thresholding (paper Eq. IV.2)
# ---------------------------------------------------------------------------

def nuclear_value(w: Array) -> Array:
    return jnp.sum(jnp.linalg.svd(w.astype(jnp.float32), compute_uv=False))


def svt(w: Array, t: Array) -> Array:
    """Singular value thresholding: U (Sigma - t)_+ V^T."""
    dtype = w.dtype
    u, s, vt = jnp.linalg.svd(w.astype(jnp.float32), full_matrices=False)
    s = jnp.maximum(s - t, 0.0)
    return (u * s[None, :] @ vt).astype(dtype)


def sketch_width(rank: int, d: int, num_tasks: int) -> int:
    """Columns of the Halko sketch: `rank` + oversampling, clipped to the
    matrix.  One definition shared by the serial and distributed SVT (and
    the bench's communication-volume accounting)."""
    return min(rank + 8, min(d, num_tasks))


def _sketch_seed(key: Array) -> Array:
    """uint32 counter seed of one refresh's sketch, from the folded key."""
    return jax.random.bits(key, dtype=jnp.uint32)


def svt_randomized(w: Array, t: Array, *, rank: int, key: Array) -> Array:
    """Randomized SVT for very large (d x T): project to `rank` + oversampling.

    Halko et al. range finder; exact when rank >= true rank.  Used when
    d_model * T makes the dense SVD the server-side bottleneck (the paper's
    online-SVD concern, adapted: on TPU a small randomized sketch keeps the
    backward step MXU-friendly instead of sequential Brand updates).

    The (T, p) test matrix Omega is never materialized per refresh: its
    entries are counter-generated from a uint32 seed drawn off `key`, and
    `ops.gauss_sketch` contracts W against Omega tiles generated in-kernel
    (VMEM-resident on TPU; the jnp oracle materializes the same bits on
    the CPU path).
    """
    from repro.kernels.ops import gauss_sketch, svt_reconstruct

    d, T = w.shape
    p = sketch_width(rank, d, T)
    y = gauss_sketch(w, _sketch_seed(key), jnp.zeros((), jnp.int32),
                     p=p)                                    # (d, p)
    q, _ = jnp.linalg.qr(y)                                  # (d, p)
    b = q.T @ w.astype(jnp.float32)                          # (p, T)
    ub, s, vt = jnp.linalg.svd(b, full_matrices=False)
    s = jnp.maximum(s - t, 0.0)
    return svt_reconstruct(q @ ub, s, vt).astype(w.dtype)


class ProxPlan(NamedTuple):
    """Collective schedule of the rank-distributed randomized SVT.

    The T task columns of the iterate live on a 1-D `axis` mesh
    (`n_local = T / n_shards` columns per shard).  One refresh moves

      psum        (d, p)        partial sketches  y = sum_s W_s @ Omega_s
      all_gather  (p, n_local)  projected-core blocks  b_s = Q^T W_s

    i.e. O(d*p + p*T) bytes instead of the O(d*T) iterate all_gather of
    the replicated prox; the QR of the (d, p) sketch and the SVD of the
    (p, T) core are cheap and replicated, the thresholded reconstruction
    `(Q U) * sigma @ V^T_s` is shard-local.
    """
    axis: str          # mesh axis the task columns are sharded over
    num_tasks: int     # global T
    n_local: int       # T // n_shards columns owned per shard

    def comm_bytes_per_refresh(self, d: int, rank: int,
                               itemsize: int = 4) -> int:
        """Collective payload per refresh: the (d, p) psum'd partial plus
        the gathered (p, T) projected core."""
        p = sketch_width(rank, d, self.num_tasks)
        return (d * p + p * self.num_tasks) * itemsize


def svt_randomized_dist(w_local: Array, t: Array, *, rank: int, key: Array,
                        plan: ProxPlan) -> Array:
    """Rank-distributed randomized SVT (inside shard_map over `plan.axis`).

    `w_local` is this shard's (d, n_local) column block of the global
    (d, T) iterate; the return is the thresholded reconstruction of the
    SAME columns — no shard ever materializes the full iterate.  `key`
    must be the replicated folded sketch key every shard holds: Omega's
    entries are counter-generated from the seed drawn off that key
    (position-determined, never materialized as a full (T, p) array), so
    each shard generates exactly ITS row block of the serial
    `svt_randomized`'s Omega — `row_offset = t_off` into the same global
    counters — and the psum'd sketch equals the serial contraction
    `W @ Omega`.

    Equivalence contract: on a 1-shard mesh every collective degenerates
    to the identity and each expression below is the serial path's, so the
    result is bitwise `svt_randomized(w, t)` on the CPU oracle path.  At
    n > 1 shards the psum regroups the sum over T (and hence Q, the core,
    and the reconstruction) relative to the serial matmul, so agreement is
    ulp-level, not bitwise — shard-count-invariance of the *engine* is
    asserted at that tolerance (tests/test_amtl_sharded_multidevice.py).
    """
    from repro.kernels.ops import gauss_sketch, svt_reconstruct

    d = w_local.shape[0]
    p = sketch_width(rank, d, plan.num_tasks)
    t_off = jax.lax.axis_index(plan.axis) * plan.n_local
    # y = sum_s W_s @ Omega_s — ONE (d, p) psum; each shard's sketch flops
    # drop from O(d*T*p) to O(d*T*p / n_shards), and each shard only ever
    # generates its own (n_local, p) rows of Omega (in-kernel on TPU).
    # The two collectives carry `comm.*` scopes of their own, outside the
    # engine's `amtl.*` phases, so a device trace names them by what they
    # move.
    y_loc = gauss_sketch(w_local, _sketch_seed(key), t_off, p=p)
    with jax.named_scope("comm.sketch_psum"):
        y = jax.lax.psum(y_loc, plan.axis)
    q, _ = jnp.linalg.qr(y)                                  # replicated
    b_loc = q.T @ w_local.astype(jnp.float32)                # (p, n_local)
    # Assemble the projected core with a tiny (p, n_local) all_gather; the
    # per-column contraction over d is shard-local, so given Q the gathered
    # core carries the serial `Q^T W` bits.
    with jax.named_scope("comm.core_gather"):
        b = jax.lax.all_gather(b_loc, plan.axis, axis=1, tiled=True)
    ub, s, vt = jnp.linalg.svd(b, full_matrices=False)       # replicated
    s = jnp.maximum(s - t, 0.0)
    vt_loc = jax.lax.dynamic_slice_in_dim(vt, t_off, plan.n_local, 1)
    return svt_reconstruct(q @ ub, s, vt_loc).astype(w_local.dtype)


# ---------------------------------------------------------------------------
# l2,1 row-group soft threshold (joint feature learning)
# ---------------------------------------------------------------------------

def l21_value(w: Array) -> Array:
    return jnp.sum(jnp.linalg.norm(w.astype(jnp.float32), axis=1))


def l21_prox(w: Array, t: Array) -> Array:
    """Row-wise group soft-threshold: w^i * max(0, 1 - t/||w^i||_2)."""
    w32 = w.astype(jnp.float32)
    norms = jnp.linalg.norm(w32, axis=1, keepdims=True)
    scale = jnp.maximum(0.0, 1.0 - t / jnp.maximum(norms, 1e-12))
    return (w32 * scale).astype(w.dtype)


# ---------------------------------------------------------------------------
# l1 / elastic net / ridge
# ---------------------------------------------------------------------------

def l1_value(w: Array) -> Array:
    return jnp.sum(jnp.abs(w.astype(jnp.float32)))


def l1_prox(w: Array, t: Array) -> Array:
    w32 = w.astype(jnp.float32)
    return (jnp.sign(w32) * jnp.maximum(jnp.abs(w32) - t, 0.0)).astype(w.dtype)


def make_elastic_net(alpha: float = 1.0) -> Regularizer:
    """g(W) = ||W||_1 + (alpha/2)||W||_F^2 — the paper's strict-convexity fix."""

    def value(w: Array) -> Array:
        w32 = w.astype(jnp.float32)
        return jnp.sum(jnp.abs(w32)) + 0.5 * alpha * jnp.sum(w32 * w32)

    def prox(w: Array, t: Array) -> Array:
        return (l1_prox(w, t).astype(jnp.float32) / (1.0 + t * alpha)).astype(w.dtype)

    return Regularizer("elastic_net", value, prox, True, True)


def ridge_value(w: Array) -> Array:
    w32 = w.astype(jnp.float32)
    return 0.5 * jnp.sum(w32 * w32)


def ridge_prox(w: Array, t: Array) -> Array:
    return (w.astype(jnp.float32) / (1.0 + t)).astype(w.dtype)


def none_value(w: Array) -> Array:
    return jnp.zeros((), dtype=jnp.float32)


def none_prox(w: Array, t: Array) -> Array:
    del t
    return w


REGISTRY: dict[str, Regularizer] = {
    "nuclear": Regularizer("nuclear", nuclear_value, svt, False, False),
    "l21": Regularizer("l21", l21_value, l21_prox, True, False),
    "l1": Regularizer("l1", l1_value, l1_prox, True, True),
    "elastic_net": make_elastic_net(),
    "ridge": Regularizer("ridge", ridge_value, ridge_prox, True, True),
    "none": Regularizer("none", none_value, none_prox, True, True),
}


def get_regularizer(name: str, **kwargs) -> Regularizer:
    if name == "elastic_net" and kwargs:
        return make_elastic_net(**kwargs)
    if name not in REGISTRY:
        raise KeyError(f"unknown regularizer {name!r}; have {sorted(REGISTRY)}")
    return REGISTRY[name]


@functools.partial(jax.jit, static_argnames=("name",))
def apply_prox(name: str, w: Array, t: Array) -> Array:
    return get_regularizer(name).prox(w, t)
