"""Pallas TPU kernel for the thresholded low-rank SVT apply (QU * sigma) @ V^T.

The randomized SVT's reconstruction is a rank-p apply: scale the (d, p)
rotated range basis QU by the p thresholded singular values, then contract
with the (p, m) right factor (m = T for the serial prox, a shard's n_local
column block for the rank-distributed prox).  Done naively that is a
full-size (d, p) temporary (QU * sigma) streamed back out of HBM before the
matmul reads it again; at the engine's scale (d = 8192, p = 24, every prox
refresh) the temporary is pure memory traffic.

This kernel fuses the scale into the MXU contraction's operand load: each
(block_rows, p) tile of QU is read once, scaled in VMEM by the lane-resident
sigma row, and fed straight to the (p, m) matmul — one pass over QU, no
(d, p) temporary, and the small V^T block stays resident in VMEM across the
whole row grid.  p and m are padded to the 128-lane tile; padded sigma
lanes are zero, so padded columns of QU and padded rows of V^T contribute
exactly nothing to the contraction.
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

BLOCK_ROWS = 256   # sublane-multiple tile rows over d
LANES = 128


def _kernel(qu_ref, s_ref, vt_ref, out_ref):
    qu = qu_ref[...].astype(jnp.float32)           # (br, pp)
    s = s_ref[...].astype(jnp.float32)             # (1, pp) lane row
    vt = vt_ref[...].astype(jnp.float32)           # (pp, mp)
    out = jnp.dot(qu * s, vt, preferred_element_type=jnp.float32,
                  precision=HIGHEST)
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def svt_reconstruct(qu: Array, s: Array, vt: Array, *,
                    block_rows: int = BLOCK_ROWS,
                    interpret: bool = False) -> Array:
    """Fused (QU * sigma) @ V^T on TPU (Pallas).

    qu: (d, p); s: (p,); vt: (p, m).  Returns (d, m) matching
    `ref.svt_reconstruct_ref` (ulp-level: the MXU contraction may group
    FMAs differently from the jnp matmul).
    """
    if qu.ndim != 2 or vt.ndim != 2 or qu.shape[1] != vt.shape[0]:
        raise ValueError(f"svt_reconstruct expects qu (d, p) and vt (p, m); "
                         f"got {qu.shape}, {vt.shape}")
    if s.shape != (qu.shape[1],):
        raise ValueError(f"s must be (p,) = ({qu.shape[1]},); got {s.shape}")
    d, p = qu.shape
    m = vt.shape[1]
    pp = _round_up(p, LANES)
    mp = _round_up(m, LANES)
    rows = _round_up(d, 8)
    br = min(block_rows, rows)
    rows = _round_up(rows, br)

    qu_p = jnp.pad(qu, ((0, rows - d), (0, pp - p)))
    vt_p = jnp.pad(vt, ((0, pp - p), (0, mp - m)))
    # padded lanes carry sigma = 0 -> padded columns contribute nothing
    s_row = jnp.pad(s.astype(jnp.float32), (0, pp - p)).reshape(1, pp)

    grid = (rows // br,)
    rep = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, pp), lambda i: (i, 0)),
                  rep((1, pp)), rep((pp, mp))],
        out_specs=pl.BlockSpec((br, mp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, mp), qu.dtype),
        interpret=interpret,
    )(qu_p, s_row, vt_p)
    return out[:d, :m]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m
