"""Pallas TPU kernel for the batched AMTL multi-event column update.

The batch engine applies `B = event_batch` activations per loop step.  For
each event i the activated task's (d,) column needs

    undo_i = cur_i                                  (undo-log ring entry)
    out_i  = cur_i + eta_k_i * (p_i - eta*g_i - cur_i)   (Eq. III.4)

where cur_i is the column as left by the most recent EARLIER in-batch event
that wrote the same task (duplicate tasks serialize in event order).  Run
one event at a time this is B kernel launches, each re-streaming a column
of V through HBM.  This kernel does the whole batch in one pass over V:

  gather   — the B activated columns are pulled out of the (rows, T) V tile
             with a one-hot MXU matmul (T is lane-sized, so this is a
             single (rows,T)x(T,B) contraction, no dynamic lane indexing);
  fuse     — a static unroll over the B events runs the forward/KM update
             per event and forwards each output to later duplicate events
             with a lane-masked select (the within-batch serialization);
             the pre-write column is accumulated into the undo output;
  scatter  — only the LAST occurrence of each task writes back, via a
             second one-hot matmul masked to last occurrences (host-
             computed), so the scatter indices are conflict-free.

V streams through VMEM once: 3 tile reads + 2 writes for B events, and the
undo-log emit rides along instead of being B extra launches.  Scalars
(tasks, eta, per-event eta_k) live in SMEM; the lane-broadcast copies of
tasks / last-occurrence mask ride in VMEM for the vector compares.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import last_occurrence_mask

Array = jax.Array
HIGHEST = jax.lax.Precision.HIGHEST

BLOCK_ROWS = 256   # sublane-multiple tile rows over d
LANES = 128


def _make_kernel(batch: int):
    def kernel(tasks_s, etaks_s, eta_s, tasks_v, last_v,
               v_ref, p_ref, g_ref, vnew_ref, undo_ref):
        eta = eta_s[0]
        v = v_ref[...].astype(jnp.float32)             # (br, Tp)
        p = p_ref[...].astype(jnp.float32)             # (br, Bp)
        g = g_ref[...].astype(jnp.float32)             # (br, Bp)
        tv = tasks_v[...]                              # (1, Bp) int32
        tp = v.shape[1]
        bp = p.shape[1]

        # gather: one-hot (Tp, Bp) built from a lane iota; padded events
        # carry task -1 and match nothing.
        col_of = jax.lax.broadcasted_iota(jnp.int32, (tp, bp), 0)
        onehot = (col_of == tv).astype(jnp.float32)
        # f32 contract precision keeps the one-hot gather and scatter
        # exact; the MXU's default single bf16 pass rounds V to bf16.
        cols = jnp.dot(v, onehot, preferred_element_type=jnp.float32,
                       precision=HIGHEST)

        # fuse: serialize the B events; each output is forwarded to later
        # duplicate events so their read sees the in-batch write.
        lane_b = jax.lax.broadcasted_iota(jnp.int32, (1, bp), 1)
        outs = jnp.zeros_like(cols)
        undos = jnp.zeros_like(cols)
        for i in range(batch):
            cur = cols[:, i:i + 1]
            eta_k = etaks_s[i]
            out = cur + eta_k * (p[:, i:i + 1] - eta * g[:, i:i + 1] - cur)
            undos = jnp.where(lane_b == i, cur, undos)
            outs = jnp.where(lane_b == i, out, outs)
            dup_later = (tv == tasks_s[i]) & (lane_b > i)
            cols = jnp.where(dup_later, out, cols)

        # scatter: last occurrence per task wins; (Bp, Tp) one-hot rows are
        # conflict-free so the contraction is an exact column placement.
        row_ev = jax.lax.broadcasted_iota(jnp.int32, (bp, tp), 1)
        # last_v carries task id for last occurrences, -1 otherwise, as a
        # (Bp, 1) column so no in-kernel transpose is needed.
        scat = (row_ev == last_v[...]).astype(jnp.float32)      # (Bp, Tp)
        covered = jnp.sum(scat, axis=0, keepdims=True)          # (1, Tp)
        placed = jnp.dot(outs, scat, preferred_element_type=jnp.float32,
                         precision=HIGHEST)
        vnew = jnp.where(covered > 0, placed, v)
        vnew_ref[...] = vnew.astype(vnew_ref.dtype)
        undo_ref[...] = undos.astype(undo_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def amtl_event_batch(v: Array, p_cols: Array, g_cols: Array, tasks: Array,
                     eta: Array, eta_ks: Array, *,
                     block_rows: int = BLOCK_ROWS,
                     interpret: bool = False) -> tuple[Array, Array]:
    """Batched fused multi-event update on a (d, T) iterate (TPU Pallas).

    v: (d, T); p_cols/g_cols: (d, B); tasks: (B,) int32; eta_ks: (B,).
    Returns (v_new (d, T), undo_cols (B, d)) matching
    `ref.amtl_event_batch_ref` (ulp-level on the update — MXU one-hot
    contractions — and exact on the undo bits).
    """
    if v.ndim != 2:
        raise ValueError(f"amtl_event_batch expects v as (d, T), got {v.shape}")
    d, num_t = v.shape
    b = tasks.shape[0]
    if p_cols.shape != (d, b) or g_cols.shape != (d, b):
        raise ValueError("p_cols/g_cols must be (d, B) = "
                         f"({d}, {b}); got {p_cols.shape}, {g_cols.shape}")
    tp = _round_up(num_t, LANES)
    bp = _round_up(b, LANES)
    rows = _round_up(d, 8)
    br = min(block_rows, rows)
    rows = _round_up(rows, br)

    pad_rows = lambda a, w: jnp.pad(a, ((0, rows - d), (0, w - a.shape[1])))
    v_p = pad_rows(v, tp)
    p_p = pad_rows(p_cols, bp)
    g_p = pad_rows(g_cols, bp)
    tasks_pad = jnp.pad(tasks.astype(jnp.int32), (0, bp - b),
                        constant_values=-1)
    # last occurrence of each task within the batch (duplicates scatter
    # conflict-free); encoded as the task id for winners, -1 for losers.
    last_task = jnp.where(last_occurrence_mask(tasks),
                          tasks.astype(jnp.int32), -1)
    last_col = jnp.pad(last_task, (0, bp - b),
                       constant_values=-1).reshape(bp, 1)
    etaks_pad = jnp.pad(eta_ks.astype(jnp.float32), (0, bp - b))
    eta_s = jnp.asarray(eta, jnp.float32).reshape(1)

    grid = (rows // br,)
    smem = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape),
                                      memory_space=pltpu.SMEM)
    rep = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    tile = lambda w: pl.BlockSpec((br, w), lambda i: (i, 0))
    v_new, undo = pl.pallas_call(
        _make_kernel(b),
        grid=grid,
        in_specs=[smem((bp,)), smem((bp,)), smem((1,)),
                  rep((1, bp)), rep((bp, 1)),
                  tile(tp), tile(bp), tile(bp)],
        out_specs=[tile(tp), tile(bp)],
        out_shape=[jax.ShapeDtypeStruct((rows, tp), v.dtype),
                   jax.ShapeDtypeStruct((rows, bp), v.dtype)],
        interpret=interpret,
    )(tasks_pad, etaks_pad, eta_s, tasks_pad.reshape(1, bp), last_col,
      v_p, p_p, g_p)
    return v_new[:d, :num_t], undo[:d, :b].T


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m
