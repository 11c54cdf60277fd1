"""Pallas TPU kernel for the fused least-squares task gradient.

    g = 2 X^T (X w - y),   X: (n, d), w: (d,), y: (n,)

This is the paper's forward step — the dominant per-activation cost on a
task node (Sec. III-C: "the gradient computation is typically the most time
consuming step for large datasets").  Fusing the two matmuls means each
(block_n, d) strip of X is read from HBM exactly once and reused for both
X@w and X^T@r while resident in VMEM; arithmetic intensity doubles vs. the
two-pass form.

Grid iterates over row strips of X; the (d, 1) output block is revisited by
every grid step (TPU grid is sequential) and accumulated in fp32.
MXU alignment: d and block_n padded to 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
# f32 contractions: on the chip the MXU default is one bf16 pass, which
# puts a ~1e-3 relative error on an f32 problem.
HIGHEST = jax.lax.Precision.HIGHEST

BLOCK_N = 512
# VMEM budget.  Pallas double-buffers each (block_n, d) strip of X, the
# f32-precision X^T r contraction holds its transpose and bf16 splits of
# it, and the (d, 1) w and output blocks are lane-padded to (d, 128).  A
# strip capped at X_STRIP_BYTES and a scoped VMEM limit of VMEM_LIMIT
# (the default is 16 MiB; a v5e core has 128 MiB) keep d up to 16384
# compiling; 512-row strips at d=4096 did not.
X_STRIP_BYTES = 2 << 20
VMEM_LIMIT = 64 << 20


def _lstsq_kernel(x_ref, w_ref, y_ref, out_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)          # (bn, d)
    w = w_ref[...].astype(jnp.float32)          # (d, 1)
    y = y_ref[...].astype(jnp.float32)          # (bn, 1)
    r = jnp.dot(x, w, preferred_element_type=jnp.float32,
                precision=HIGHEST) - y
    contrib = 2.0 * jnp.dot(x.T, r, preferred_element_type=jnp.float32,
                            precision=HIGHEST)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = contrib.astype(out_ref.dtype)

    @pl.when(i > 0)
    def _acc():
        out_ref[...] = (out_ref[...].astype(jnp.float32)
                        + contrib).astype(out_ref.dtype)


def _lstsq_kernel_masked(scal_ref, x_ref, w_ref, y_ref, out_ref, *, bn: int):
    """`_lstsq_kernel` plus a traced valid-row mask from a (1, 1) block.

    Ragged task buffers carry REAL rows past n_t (the store's padded
    capacity), so unlike the zero-padded tail the kernel pads on, they
    must be masked out of the residual in VMEM.
    """
    i = pl.program_id(0)
    n_t = scal_ref[0, 0]
    x = x_ref[...].astype(jnp.float32)          # (bn, d)
    w = w_ref[...].astype(jnp.float32)          # (d, 1)
    y = y_ref[...].astype(jnp.float32)          # (bn, 1)
    r = jnp.dot(x, w, preferred_element_type=jnp.float32,
                precision=HIGHEST) - y
    rows = (jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
            + i * bn).astype(jnp.uint32)
    r = jnp.where(rows < n_t, r, 0.0)
    contrib = 2.0 * jnp.dot(x.T, r, preferred_element_type=jnp.float32,
                            precision=HIGHEST)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = contrib.astype(out_ref.dtype)

    @pl.when(i > 0)
    def _acc():
        out_ref[...] = (out_ref[...].astype(jnp.float32)
                        + contrib).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def lstsq_grad(x: Array, w: Array, y: Array, *, n_t: Array | None = None,
               block_n: int = BLOCK_N, interpret: bool = False) -> Array:
    """Fused 2 X^T (X w - y).  Returns (d,) in w.dtype (fp32 accumulate).

    `n_t` (optional, traced) is a ragged buffer's valid-row count: rows
    >= n_t are masked out of the residual in VMEM (they may hold real
    appended-but-not-yet-counted data, unlike the kernel's own zero
    padding).  n_t=None keeps the original unmasked kernel body.
    """
    n, d = x.shape
    pd = _round_up(d, 128)
    bn = strip_rows(n, pd, block_n)
    pn = _round_up(n, bn)
    # Zero padding is exact: padded rows contribute X_pad @ w - 0 = 0 rows
    # only when X_pad = 0 AND y_pad = 0 => r_pad = 0 => no gradient effect.
    x_p = jnp.pad(x, ((0, pn - n), (0, pd - d)))
    y_p = jnp.pad(y.reshape(n, 1), ((0, pn - n), (0, 0)))
    w_p = jnp.pad(w.reshape(d, 1), ((0, pd - d), (0, 0)))

    if n_t is None:
        kernel = _lstsq_kernel
        in_specs = []
        args = ()
    else:
        kernel = functools.partial(_lstsq_kernel_masked, bn=bn)
        in_specs = [pl.BlockSpec((1, 1), lambda i: (0, 0))]
        args = (jnp.asarray(n_t).astype(jnp.uint32).reshape(1, 1),)

    out = pl.pallas_call(
        kernel,
        grid=(pn // bn,),
        in_specs=in_specs + [
            pl.BlockSpec((bn, pd), lambda i: (i, 0)),
            pl.BlockSpec((pd, 1), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((pd, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((pd, 1), w.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*args, x_p, w_p, y_p)
    return out[:d, 0]


def strip_rows(n: int, pd: int, block_n: int) -> int:
    """Rows per strip of an (n, pd) X: `block_n`, cut to a multiple of 8
    that keeps one f32 strip within `X_STRIP_BYTES`."""
    fit = max(8, X_STRIP_BYTES // (4 * pd) // 8 * 8)
    return min(block_n, fit, _round_up(n, 128))


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m
