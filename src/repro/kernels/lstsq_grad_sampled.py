"""Pallas TPU kernel for the fused seeded-minibatch least-squares gradient.

    g = (n_t/bsz) * 2 X_S^T (X_S w - y_S),   S = seeded rank-bsz selection

SGD-AMTL's forward step (the paper's §V future work): per activation only
a bsz-row minibatch of the task's valid rows enters the gradient.  The
selection is generated INSIDE the kernel from a counter-based seed — row
i's keep bit is the local predicate over `counter_hash(seed, i)` and the
two rank-cutoff scalars (`repro.kernels.ref`, the same uint32 expressions
as the jnp oracle) — so there is no gather, no materialized index array,
and no second pass over X: each (block_n, d) strip of X is read from HBM
exactly once, the per-strip residual is masked in VMEM, and the fused
X^T r contraction only ever sees the surviving rows' residuals.
Grid/accumulation structure is `lstsq_grad`'s; (seed, cut_h, cut_i, n_t)
ride along as one (1, 4) uint32 scalar block.  Ragged tasks hand a traced
`n_t` (valid-row count over a padded buffer): the cutoff is then computed
over valid rows only (`ref.sample_cutoff_masked`), the keep predicate
gains the conjunct `row < n_t`, and the unbiased (n_t/bsz) scale is
derived in-kernel from the scalar block — f32 division of integers
< 2^24, which rounds identically to the uniform path's trace-time
Python-float constant, so n_t == n keeps the kernel on the same bits.

`sample_mask` exposes the kernel's selection bits on their own — the
hypothesis suite asserts them equal to `ref.sample_mask_ref` /
`ref.sample_mask_masked_ref` for arbitrary (n, b, seed, n_t), which pins
the in-kernel sampler to the oracle exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lstsq_grad import VMEM_LIMIT, strip_rows
from repro.kernels.ref import counter_hash, sample_cutoff, sample_cutoff_masked

Array = jax.Array
# f32 contractions: on the chip the MXU default is one bf16 pass, which
# puts a ~1e-3 relative error on an f32 problem.
HIGHEST = jax.lax.Precision.HIGHEST

BLOCK_N = 512
LANES = 128


def _keep_bits(scal_ref, row0: Array, bn: int) -> Array:
    """(bn, 1) bool keep bits for rows [row0, row0 + bn).

    `scal_ref` is the (1, 4) uint32 scalar block (seed, cut_h, cut_i, n_t);
    `counter_hash` and the rank-cut predicate are the oracle's own uint32
    expressions, so the bits match `ref.sample_mask_masked_ref` bit-for-bit
    (TPU iota must be >= 2D, hence the broadcasted (bn, 1) layout).  The
    `row < n_t` conjunct drops padded rows: redundant for the gradient
    (X_pad = 0 and y_pad = 0 already zero their residuals) but it is the
    law `sample_mask` exposes, and ragged buffers carry REAL data past
    n_t that must never leak into a minibatch.
    """
    seed, cut_h, cut_i = scal_ref[0, 0], scal_ref[0, 1], scal_ref[0, 2]
    n_t = scal_ref[0, 3]
    rows = (jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
            + row0).astype(jnp.uint32)
    h = counter_hash(seed, rows)
    keep = (h < cut_h) | ((h == cut_h) & (rows <= cut_i))
    return keep & (rows < n_t)


def _sampled_kernel(scal_ref, x_ref, w_ref, y_ref, out_ref, *, bn: int,
                    batch_size: int):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)          # (bn, d)
    w = w_ref[...].astype(jnp.float32)          # (d, 1)
    y = y_ref[...].astype(jnp.float32)          # (bn, 1)
    r = jnp.dot(x, w, preferred_element_type=jnp.float32,
                precision=HIGHEST) - y
    keep = _keep_bits(scal_ref, i * bn, bn)
    r = jnp.where(keep, r, 0.0)
    # (n_t/bsz) unbiased scale from the scalar block: integer operands are
    # < 2^24, so this f32 division carries the exact bits of the former
    # trace-time Python-float constant (x2 is exact in binary fp).  They
    # go through int32 (exact below 2^24): Mosaic has no uint32 -> f32.
    n_t = scal_ref[0, 3].astype(jnp.int32)
    bsz = jnp.minimum(jnp.int32(batch_size), n_t)
    scale2 = 2.0 * (n_t.astype(jnp.float32)
                    / jnp.maximum(bsz, 1).astype(jnp.float32))
    contrib = scale2 * jnp.dot(x.T, r, preferred_element_type=jnp.float32,
                               precision=HIGHEST)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = contrib.astype(out_ref.dtype)

    @pl.when(i > 0)
    def _acc():
        out_ref[...] = (out_ref[...].astype(jnp.float32)
                        + contrib).astype(out_ref.dtype)


def _scalars(n: int, batch_size: int, seed: Array,
             n_t: Array | None = None) -> Array:
    """(1, 4) uint32 scalar block: (seed, cut_h, cut_i, n_t)."""
    seed = jnp.asarray(seed, jnp.uint32)
    if n_t is None:
        cut_h, cut_i = sample_cutoff(n, batch_size, seed)
        n_t_u = jnp.uint32(n)
    else:
        n_t_u = jnp.asarray(n_t).astype(jnp.uint32)
        cut_h, cut_i = sample_cutoff_masked(n, batch_size, seed, n_t_u)
    return jnp.stack([seed, cut_h, cut_i, n_t_u]).reshape(1, 4)


@functools.partial(jax.jit,
                   static_argnames=("batch_size", "block_n", "interpret"))
def lstsq_grad_sampled(x: Array, w: Array, y: Array, seed: Array, *,
                       batch_size: int, n_t: Array | None = None,
                       block_n: int = BLOCK_N,
                       interpret: bool = False) -> Array:
    """Fused (n_t/bsz) * 2 X_S^T (X_S w - y_S) with in-kernel selection.

    Returns (d,) in w.dtype (fp32 accumulate).  `seed` is the uint32
    per-event sampling seed; `batch_size` static; `n_t` an optional traced
    valid-row count over a padded buffer (bsz = min(batch_size, n_t) clamp
    applied in the cutoff, matching the simulator's SGD-AMTL convention;
    n_t=None means every row is valid).
    """
    n, d = x.shape
    pd = _round_up(d, 128)
    bn = strip_rows(n, pd, block_n)
    pn = _round_up(n, bn)
    # Zero padding stays exact under sampling: a padded row's keep bit is
    # dropped by the row < n_t conjunct, and even without it X_pad = 0 AND
    # y_pad = 0 => r_pad = 0, so it contributes nothing to the contraction.
    x_p = jnp.pad(x, ((0, pn - n), (0, pd - d)))
    y_p = jnp.pad(y.reshape(n, 1), ((0, pn - n), (0, 0)))
    w_p = jnp.pad(w.reshape(d, 1), ((0, pd - d), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_sampled_kernel, bn=bn, batch_size=batch_size),
        grid=(pn // bn,),
        in_specs=[pl.BlockSpec((1, 4), lambda i: (0, 0)),
                  pl.BlockSpec((bn, pd), lambda i: (i, 0)),
                  pl.BlockSpec((pd, 1), lambda i: (0, 0)),
                  pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((pd, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((pd, 1), w.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(_scalars(n, batch_size, seed, n_t), x_p, w_p, y_p)
    return out[:d, 0]


def _mask_kernel(scal_ref, out_ref, *, bn: int):
    i = pl.program_id(0)
    out_ref[...] = _keep_bits(scal_ref, i * bn, bn).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("n", "batch_size", "block_n",
                                    "interpret"))
def sample_mask(n: int, batch_size: int, seed: Array, *,
                n_t: Array | None = None,
                block_n: int = BLOCK_N, interpret: bool = False) -> Array:
    """(n,) bool — the kernel's selection bits, standalone.

    Runs `_keep_bits` (the gradient kernel's exact selection expression)
    through its own pallas_call so tests can pin the in-kernel sampler to
    `ref.sample_mask_ref` / `ref.sample_mask_masked_ref` without
    inspecting gradient values.
    """
    bn = min(block_n, _round_up(n, 8))
    pn = _round_up(n, bn)
    out = pl.pallas_call(
        functools.partial(_mask_kernel, bn=bn),
        grid=(pn // bn,),
        in_specs=[pl.BlockSpec((1, 4), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pn, 1), jnp.int32),
        interpret=interpret,
    )(_scalars(n, batch_size, seed, n_t))
    return out[:n, 0] != 0


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m
