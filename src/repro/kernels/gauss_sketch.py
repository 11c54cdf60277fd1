"""Pallas TPU kernel for the unmaterialized Gaussian sketch W @ Omega.

The randomized SVT's range finder contracts the (d, T) iterate against a
(T, p) Gaussian test matrix Omega.  Materializing Omega per refresh costs
a (T, p) HBM round-trip and an extra PRNG kernel launch for a matrix that
is consumed exactly once — instead, this kernel generates each (block_t,
p) tile of Omega in VMEM from the counter-based seed (Box-Muller over
`ref.counter_hash` bits, the jnp oracle's exact expression) while the
matching (block_d, block_t) tile of W is resident, and accumulates the
(block_d, p) partial product.  Omega never exists in HBM.

Entry (r, c) of the GLOBAL Omega depends only on (seed, r, c) — so a
shard of the task-sharded engine generates the rows of ITS column block
from the replicated seed (`row_offset` = its global column offset) and
the partitioned-psum identity sum_s W_s @ Omega_s = W @ Omega is over the
same matrix the serial prox uses.  `row_offset` is traced (it comes from
`lax.axis_index` inside shard_map), so it rides into the kernel as a
(1, 1) scalar block next to the seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import gauss_from_counters

Array = jax.Array
# f32 contractions: on the chip the MXU default is one bf16 pass, which
# puts a ~1e-3 relative error on an f32 problem.
HIGHEST = jax.lax.Precision.HIGHEST

BLOCK_D = 1024
BLOCK_T = 128
LANES = 128


def _sketch_kernel(seed_ref, off_ref, w_ref, out_ref, *, bt: int, p: int,
                   pp: int):
    j = pl.program_id(1)                        # t-strip (minor, sequential)
    w = w_ref[...].astype(jnp.float32)          # (bd, bt)
    # (bt, pp) Omega tile from global counters (row * p + col); lanes
    # >= p hold finite garbage normals whose output columns are sliced
    # away by the host wrapper, and padded t rows multiply zero columns
    # of W, so neither perturbs the first p output columns.
    row0 = (off_ref[0, 0] + j * bt).astype(jnp.uint32)
    rows = (jax.lax.broadcasted_iota(jnp.uint32, (bt, pp), 0)
            + row0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (bt, pp), 1)
    omega = gauss_from_counters(seed_ref[0, 0], rows * jnp.uint32(p) + cols)
    contrib = jnp.dot(w, omega, preferred_element_type=jnp.float32,
                      precision=HIGHEST)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = contrib

    @pl.when(j > 0)
    def _acc():
        out_ref[...] = out_ref[...] + contrib


@functools.partial(jax.jit, static_argnames=("p", "block_d", "block_t",
                                             "interpret"))
def gauss_sketch(w: Array, seed: Array, row_offset: Array, *, p: int,
                 block_d: int = BLOCK_D, block_t: int = BLOCK_T,
                 interpret: bool = False) -> Array:
    """(d, p) f32 sketch W @ Omega, Omega generated in-kernel.

    `w` is (d, t_local) — the full iterate (serial prox, row_offset 0) or
    a shard's column block (row_offset = global column offset).  Returns
    f32 regardless of w.dtype (the sketch feeds a f32 QR).
    """
    d, tt = w.shape
    pd = _round_up(d, 8)
    bd = min(block_d, pd)
    pd = _round_up(pd, bd)
    bt = min(block_t, _round_up(tt, 8))
    pt = _round_up(tt, bt)
    pp = _round_up(p, LANES)
    w_p = jnp.pad(w, ((0, pd - d), (0, pt - tt)))
    seed2 = jnp.asarray(seed, jnp.uint32).reshape(1, 1)
    off2 = jnp.asarray(row_offset, jnp.int32).reshape(1, 1)

    out = pl.pallas_call(
        functools.partial(_sketch_kernel, bt=bt, p=p, pp=pp),
        grid=(pd // bd, pt // bt),
        in_specs=[pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
                  pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
                  pl.BlockSpec((bd, bt), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bd, pp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pd, pp), jnp.float32),
        interpret=interpret,
    )(seed2, off2, w_p)
    return out[:d, :p]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m
