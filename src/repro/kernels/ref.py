"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth: kernels must match these to
numerical tolerance across the shape/dtype sweeps in tests/test_kernels_*.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def km_update_ref(v: Array, p: Array, g: Array, eta: Array,
                  eta_k: Array) -> Array:
    """Fused AMTL update (paper Eq. III.4): v + eta_k*(p - eta*g - v)."""
    return v + eta_k * (p - eta * g - v)


def amtl_event_ref(v_t: Array, p_t: Array, g_t: Array, eta: Array,
                   eta_k: Array) -> tuple[Array, Array]:
    """Fused delta-ring column event: (Eq. III.4 update, undo-log entry).

    The update MUST stay arithmetically identical to km_update_ref (the
    dense engine's expression) or the engines' bitwise equivalence breaks —
    so it is km_update_ref, not a re-derivation.  The second output is the
    exact pre-write bits of v_t — it seeds the delta ring's rollback
    reconstruction, so it must be v_t verbatim.
    """
    return km_update_ref(v_t, p_t, g_t, eta, eta_k), v_t


def last_occurrence_mask(tasks: Array) -> Array:
    """(B,) bool: event i is the LAST in-batch occurrence of its task.

    The within-batch conflict-resolution predicate shared by the oracle and
    the Pallas kernel's host wrapper: only last occurrences scatter back,
    so duplicate tasks write conflict-free.
    """
    idx = jnp.arange(tasks.shape[0])
    later_dup = (tasks[None, :] == tasks[:, None]) & (idx[None, :] > idx[:, None])
    return ~jnp.any(later_dup, axis=1)


def shard_local_tasks(tasks: Array, t_offset: Array,
                      n_local: int) -> tuple[Array, Array]:
    """Map global task ids onto a shard's local column block.

    Returns (local_tasks, owned).  Owned events get their local column id
    in [0, n_local); events owned by other shards get the sentinel id
    `n_local` — one past the shard's last column.  Both amtl_event_batch
    paths treat the sentinel as a dropped event: the jnp oracle's gather
    clamps and its scatter targets column n_local (out of bounds,
    `mode="drop"`), and the Pallas kernel's one-hot either matches nothing
    (n_local lane-aligned) or a padded column that is sliced away.
    Sentinel events still flow through the per-event arithmetic, so
    shard-local execution issues exactly the op sequence of the global
    batch for the events it owns — the sharded engine's bitwise contract.
    """
    local = tasks.astype(jnp.int32) - t_offset
    owned = (local >= 0) & (local < n_local)
    return jnp.where(owned, local, n_local), owned


def amtl_event_batch_ref(v: Array, p_cols: Array, g_cols: Array,
                         tasks: Array, eta: Array,
                         eta_ks: Array) -> tuple[Array, Array]:
    """Batched fused column events, serialized in event order.

    v: (d, T) iterate; tasks: (B,) activated task per event; p_cols/g_cols:
    (d, B) per-event prox column and forward-step gradient; eta_ks: (B,)
    per-event KM relaxation.  Returns (v_new (d, T), undo_cols (B, d)).

    Within-batch conflict semantics: event i reads the column as left by
    the most recent EARLIER event in the batch that wrote the same task
    (duplicate tasks serialize), and its undo entry is that pre-write
    column — iterating `amtl_event_ref` in event order over a shared v is
    the specification.  The implementation gathers the B columns once,
    serializes each duplicate chain through a predecessor pointer inside a
    scan (O(d) per event instead of an O(d*T) scatter per event), and
    scatters back once through the conflict-free last occurrence of each
    task.  Every per-event expression is `amtl_event_ref` on the same bits
    sequential replay would see, so the result — and the batch engine's
    CPU-path iterates — stay bitwise-equal to serial replay.
    """
    b = tasks.shape[0]
    num_cols = v.shape[1]
    idx = jnp.arange(b)
    same = tasks[None, :] == tasks[:, None]
    # prev[i]: most recent earlier in-batch event on the same task (-1: none)
    prev = jnp.max(jnp.where(same & (idx[None, :] < idx[:, None]),
                             idx[None, :], -1), axis=1)
    # last occurrence per task scatters back; earlier duplicates are
    # shadowed, so the scatter indices are conflict-free (losers aim at
    # column T, out of bounds, dropped).
    scatter_to = jnp.where(last_occurrence_mask(tasks), tasks, num_cols)

    cols0 = v[:, tasks]                                      # (d, b) gather

    def one(outbuf, inp):
        i, pr, p_t, g_t, eta_k = inp
        mine = jax.lax.dynamic_slice_in_dim(cols0, i, 1, axis=1)
        inherited = jax.lax.dynamic_slice_in_dim(
            outbuf, jnp.maximum(pr, 0), 1, axis=1)
        cur = jnp.where(pr >= 0, inherited, mine)[:, 0]
        v_t_new, old = amtl_event_ref(cur, p_t, g_t, eta, eta_k)
        outbuf = jax.lax.dynamic_update_slice_in_dim(
            outbuf, v_t_new[:, None], i, axis=1)
        return outbuf, old

    outs, undos = jax.lax.scan(
        one, jnp.zeros_like(cols0),
        (idx, prev, p_cols.T, g_cols.T, eta_ks))
    return v.at[:, scatter_to].set(outs, mode="drop"), undos


def svt_reconstruct_ref(qu: Array, s: Array, vt: Array) -> Array:
    """Thresholded low-rank apply: (QU * sigma) @ V^T.

    qu: (d, p) rotated range basis Q @ U_b; s: (p,) thresholded singular
    values; vt: (p, m) right factor (m = T, or a shard's n_local column
    block in the distributed prox).  Returns (d, m) in float32 cast back
    to qu.dtype.  This expression IS the tail of `prox.svt_randomized` —
    both the serial and the rank-distributed SVT route their
    reconstruction through `ops.svt_reconstruct`, so the CPU oracle path
    keeps them on identical bits.
    """
    qu32 = qu.astype(jnp.float32)
    return ((qu32 * s.astype(jnp.float32)[None, :])
            @ vt.astype(jnp.float32)).astype(qu.dtype)


def l21_prox_ref(w: Array, t: Array) -> Array:
    """Row-group soft threshold: w^i * max(0, 1 - t/||w^i||)."""
    w32 = w.astype(jnp.float32)
    norms = jnp.linalg.norm(w32, axis=-1, keepdims=True)
    scale = jnp.maximum(0.0, 1.0 - t / jnp.maximum(norms, 1e-12))
    return (w32 * scale).astype(w.dtype)


def lstsq_grad_ref(x: Array, w: Array, y: Array) -> Array:
    """Fused least-squares gradient 2 X^T (X w - y) (paper forward step)."""
    x32, w32, y32 = (a.astype(jnp.float32) for a in (x, w, y))
    return (2.0 * (x32.T @ (x32 @ w32 - y32))).astype(w.dtype)


def lstsq_grad_masked_ref(x: Array, w: Array, y: Array, n_t: Array) -> Array:
    """Ragged least-squares gradient: rows >= n_t masked out of the residual.

    `x` is a (n, d) PADDED row buffer of which only the first `n_t` (traced
    int) rows are real task data.  Zeroing the residual of the padded tail
    removes it from the X^T r contraction exactly; with n_t == n the
    all-true `where` passes the residual's bits through untouched, so the
    uniform case reproduces `lstsq_grad_ref` bitwise — the ragged path's
    equivalence anchor.
    """
    x32, w32, y32 = (a.astype(jnp.float32) for a in (x, w, y))
    rows = jnp.arange(x.shape[0])
    r = jnp.where(rows < n_t, x32 @ w32 - y32, 0.0)
    return (2.0 * (x32.T @ r)).astype(w.dtype)


# ------------------------------------------------ counter-based sampling ---
#
# The SGD engines generate their per-event minibatch selection from a
# 32-bit counter hash instead of a materialized index array: the minibatch
# is the EXACTLY-bsz rows whose hash(seed, i) ranks smallest (ties broken
# by row index — a strict total order, so the set is well defined even
# under hash collisions).  That rank cut is summarized by two uint32
# scalars, the bsz-th smallest (hash, row) pair: row i's keep bit is then
# the purely local expression
#
#     h_i < cut_h  or  (h_i == cut_h and i <= cut_i)
#
# which is what the Pallas kernel evaluates per (block_n, 1) strip in VMEM
# — no gather, no index array crosses HBM, only (seed, cut_h, cut_i).  The
# SAME uint32 expressions run in the jnp oracle below and inside the
# kernel bodies (plain jnp; the kernel imports these helpers), so
# selection bits agree exactly by construction: the CPU oracle path and
# the TPU kernel sample identical minibatches, and every shard of the
# sharded engine re-derives an event's selection locally from the
# replicated seed.  Exact-size selection (vs thresholded Bernoulli) is
# what buys the CPU oracle its FLOP win: knowing |S| = bsz statically, the
# oracle gathers the bsz rows and contracts O(bsz * d) instead of masking
# a dense O(n * d) product — the same uniform-without-replacement law as
# the float64 simulator's `rng.choice`.

def counter_hash(seed: Array, ctr: Array) -> Array:
    """uint32 hash of (seed, counter): lowbias32 finalizer over the pair.

    `seed` is a uint32 scalar (one per sampling event), `ctr` any uint32
    array of counters (row indices, or flattened (row, col) positions).
    Pure jnp uint32 arithmetic — multiplies, xors, logical shifts — so the
    expression lowers identically on the oracle path and inside a Pallas
    TPU kernel body.
    """
    x = ctr * jnp.uint32(0x9E3779B9) ^ seed
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def sample_cutoff(n: int, batch_size: int, seed: Array) -> tuple[Array, Array]:
    """(cut_h, cut_i) uint32 scalars: the bsz-th smallest (hash, row) pair.

    The minibatch S is the bsz = min(batch_size, n) rows of smallest
    counter_hash(seed, i), ties broken by row index (jnp.argsort is
    stable, so the sort order IS the (hash, row) lexicographic order).
    Row i is in S iff h_i < cut_h or (h_i == cut_h and i <= cut_i) — a
    per-row local predicate, which is how the Pallas kernel re-derives
    the selection in VMEM from just these two scalars.  batch_size >= n
    saturates the cutoff (every real row kept): the clamp that makes the
    saturated path degrade to the full gradient.  O(n log n) uint32 sort
    per event — noise next to the O(n d) (full) or O(bsz d) (sampled)
    gradient contraction it steers.
    """
    bsz = min(batch_size, n)
    if bsz >= n:
        return jnp.uint32(0xFFFFFFFF), jnp.uint32(n - 1)
    h = counter_hash(seed, jnp.arange(n, dtype=jnp.uint32))
    kth = jnp.argsort(h)[bsz - 1]
    return h[kth], kth.astype(jnp.uint32)


def sample_mask_ref(n: int, batch_size: int, seed: Array) -> Array:
    """(n,) bool keep/drop bits; exactly min(batch_size, n) are set."""
    cut_h, cut_i = sample_cutoff(n, batch_size, seed)
    idx = jnp.arange(n, dtype=jnp.uint32)
    h = counter_hash(seed, idx)
    return (h < cut_h) | ((h == cut_h) & (idx <= cut_i))


def lstsq_grad_sampled_ref(x: Array, w: Array, y: Array, seed: Array,
                           batch_size: int) -> Array:
    """Unbiased seeded-minibatch least-squares gradient.

        (n/bsz) * 2 X_S^T (X_S w - y_S),   S = rank-bsz selection

    with bsz = min(batch_size, n) — the simulator's `(n_t / bsz)` SGD-AMTL
    convention.  |S| = bsz is static, so the oracle GATHERS the selected
    rows (the argsort prefix — the same set `sample_mask_ref` flags) and
    contracts a (bsz, d) block: O(bsz * d) FLOPs where the full gradient
    pays O(n * d).  The kernel computes the identical quantity as a
    masked dense contraction (it may not gather), so kernel vs oracle
    agree to summation-order rounding, like every kernel pair here.  When
    batch_size >= n this IS `lstsq_grad_ref` — same call, bitwise.
    """
    n = x.shape[0]
    bsz = min(batch_size, n)
    if bsz >= n:
        return lstsq_grad_ref(x, w, y)
    h = counter_hash(seed, jnp.arange(n, dtype=jnp.uint32))
    sel = jnp.argsort(h)[:bsz]
    x32 = x[sel].astype(jnp.float32)
    y32 = y[sel].astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    r = x32 @ w32 - y32
    return ((2.0 * (n / bsz)) * (x32.T @ r)).astype(w.dtype)


def sample_cutoff_masked(n: int, batch_size: int, seed: Array,
                         n_t: Array) -> tuple[Array, Array]:
    """Ragged (cut_h, cut_i): bsz-th smallest (hash, row) among VALID rows.

    `n` is the static padded buffer height, `n_t` the traced count of real
    rows.  The selection law is `sample_cutoff` restricted to rows < n_t
    with bsz = min(batch_size, n_t): rank the stable (hash, row) order,
    walk it until bsz valid rows have been passed, and cut at that pair.
    The keep predicate gains the conjunct `i < n_t`, so padded rows that
    happen to hash under the cutoff stay dropped.  batch_size >= n_t
    saturates exactly like the uniform clamp (every valid row kept).  With
    n_t == n the cumulative-count walk lands on position bsz - 1 of the
    plain argsort — `sample_cutoff`'s pair, bitwise.
    """
    h = counter_hash(seed, jnp.arange(n, dtype=jnp.uint32))
    order = jnp.argsort(h)                     # stable: (hash, row) lex order
    valid_sorted = order < n_t
    bsz = jnp.minimum(jnp.int32(batch_size), n_t.astype(jnp.int32))
    pos = jnp.argmax(jnp.cumsum(valid_sorted.astype(jnp.int32)) >= bsz)
    kth = order[pos]
    sat = jnp.int32(batch_size) >= n_t.astype(jnp.int32)
    cut_h = jnp.where(sat, jnp.uint32(0xFFFFFFFF), h[kth])
    cut_i = jnp.where(sat, jnp.uint32(n - 1), kth.astype(jnp.uint32))
    return cut_h, cut_i


def sample_mask_masked_ref(n: int, batch_size: int, seed: Array,
                           n_t: Array) -> Array:
    """(n,) bool keep bits over a padded buffer; min(batch_size, n_t) set."""
    cut_h, cut_i = sample_cutoff_masked(n, batch_size, seed, n_t)
    idx = jnp.arange(n, dtype=jnp.uint32)
    h = counter_hash(seed, idx)
    keep = (h < cut_h) | ((h == cut_h) & (idx <= cut_i))
    return keep & (idx < n_t.astype(jnp.uint32))


def lstsq_grad_sampled_masked_ref(x: Array, w: Array, y: Array, seed: Array,
                                  batch_size: int, n_t: Array) -> Array:
    """Ragged unbiased minibatch gradient: (n_t/bsz) * 2 X_S^T (X_S w - y_S).

    S is `sample_mask_masked_ref`'s selection (rank cut over valid rows,
    bsz = min(batch_size, n_t) traced).  The gather stays static-shaped:
    bsz_max = min(batch_size, n) rows are gathered in (hash, row) rank
    order with valid rows partitioned first (stable argsort of the
    invalid flag), and rows at rank >= bsz are zero-masked out of the
    contraction.  The n_t/bsz scale is computed in f32 from traced
    scalars; both operands are integers < 2^24, where a single f32
    division rounds identically to the f64-then-f32 double rounding of
    the uniform path's Python-float constant — so with n_t == n the
    whole expression (selection, gather order, scale bits, contraction)
    reproduces `lstsq_grad_sampled_ref` bitwise.  n_t == 0 keeps zero
    rows and returns the zero vector (scale guard avoids 0/0).
    """
    n = x.shape[0]
    bsz_max = min(batch_size, n)
    if bsz_max >= n:
        return lstsq_grad_masked_ref(x, w, y, n_t)
    h = counter_hash(seed, jnp.arange(n, dtype=jnp.uint32))
    order = jnp.argsort(h)                     # stable: (hash, row) lex order
    valid_sorted = order < n_t
    sel = order[jnp.argsort(~valid_sorted, stable=True)[:bsz_max]]
    bsz = jnp.minimum(jnp.int32(batch_size), n_t.astype(jnp.int32))
    x32 = x[sel].astype(jnp.float32)
    y32 = y[sel].astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    # Mask the RESIDUAL of over-rank rows, not the gathered x: a zero
    # residual row contributes exactly zero to x^T r, and keeping the
    # first dot's operands select-free leaves its compiled reduction
    # identical to the uniform path's — masking x instead was observed to
    # change the dot's summation order under jit by a ulp.
    row_ok = jnp.arange(bsz_max) < bsz
    r = jnp.where(row_ok, x32 @ w32 - y32, 0.0)
    scale = 2.0 * (n_t.astype(jnp.float32)
                   / jnp.maximum(bsz, 1).astype(jnp.float32))
    return (scale * (x32.T @ r)).astype(w.dtype)


def gauss_from_counters(seed: Array, ctr: Array) -> Array:
    """f32 standard normals from uint32 counters (Box-Muller).

    Two counter hashes (2*ctr, 2*ctr + 1) feed one Box-Muller cosine
    branch.  The top 24 bits of each hash give an exact f32 uniform —
    u1 in (0, 1] (never 0, so the log is finite), u2 in [0, 1).  Same
    jnp expression in the oracle and the Pallas sketch kernel, so the
    unmaterialized Omega tiles carry the oracle's exact bits.
    """
    u1 = counter_hash(seed, ctr * jnp.uint32(2))
    u2 = counter_hash(seed, ctr * jnp.uint32(2) + jnp.uint32(1))
    # Mosaic has no uint32 -> f32 cast; 24-bit values fit int32 exactly.
    f1 = ((u1 >> 8).astype(jnp.int32).astype(jnp.float32) + 1.0) \
        * jnp.float32(2.0 ** -24)
    f2 = (u2 >> 8).astype(jnp.int32).astype(jnp.float32) \
        * jnp.float32(2.0 ** -24)
    return jnp.sqrt(-2.0 * jnp.log(f1)) * jnp.cos(
        jnp.float32(2.0 * 3.141592653589793) * f2)


def gauss_omega_ref(rows: int, p: int, seed: Array,
                    row_offset: Array | int = 0) -> Array:
    """(rows, p) f32 block of the counter-generated global sketch Omega.

    Entry (r, c) is gauss_from_counters(seed, (row_offset + r) * p + c):
    position-determined, so any row block of the global (T, p) Omega can
    be generated locally — the sharded prox re-derives ITS rows from the
    replicated seed and the partitioned-psum identity
    sum_s W_s @ Omega_s = W @ Omega holds over the same global matrix.
    """
    off = jnp.asarray(row_offset, jnp.uint32)
    r_idx = (off + jnp.arange(rows, dtype=jnp.uint32))[:, None]
    c_idx = jnp.arange(p, dtype=jnp.uint32)[None, :]
    return gauss_from_counters(seed, r_idx * jnp.uint32(p) + c_idx)


def gauss_sketch_ref(w: Array, seed: Array, row_offset: Array | int,
                     p: int) -> Array:
    """(d, p) f32 sketch W @ Omega over counter-generated normals.

    The oracle materializes its (rows, p) Omega block; the Pallas kernel
    generates the same bits tile-by-tile in VMEM without ever writing
    Omega to HBM.  `row_offset` is this block's first global Omega row
    (0 for the serial prox, t_off for a shard's column block).
    """
    omega = gauss_omega_ref(w.shape[1], p, seed, row_offset)
    return w.astype(jnp.float32) @ omega


def sliding_flash_attention_ref(q: Array, k: Array, v: Array, *,
                                window: int | None, causal: bool = True,
                                softcap: float | None = None) -> Array:
    """O(S^2) reference attention with optional sliding window + softcap.

    q,k,v: (S, H, D) single batch element; GQA is handled by the caller
    repeating kv heads.  Returns (S, H, D).
    """
    s, h, d = q.shape
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    pos_q = jnp.arange(s)[:, None]
    pos_k = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= pos_k <= pos_q
    if window is not None:
        mask &= pos_k > pos_q - window
    logits = jnp.where(mask[None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def rwkv6_scan_ref(r: Array, k: Array, v: Array, w: Array, u: Array) -> Array:
    """RWKV-6 (Finch) WKV recurrence, sequential reference.

    r,k,v: (S, H, D); w: (S, H, D) data-dependent per-step decay (in (0,1));
    u: (H, D) bonus for the current token.  State S_h in R^{D x D}:
        out_t = r_t . (S + u * k_t v_t^T);   S <- diag(w_t) S + k_t v_t^T
    Returns (S, H, D).
    """
    s, h, d = r.shape

    def step(state, inp):
        r_t, k_t, v_t, w_t = inp           # each (H, D)
        kv = k_t[:, :, None] * v_t[:, None, :]          # (H, D, D)
        out = jnp.einsum("hd,hde->he", r_t,
                         state + u[:, :, None] * kv)     # (H, D)
        state = w_t[:, :, None] * state + kv
        return state, out

    state0 = jnp.zeros((h, d, d), jnp.float32)
    _, outs = jax.lax.scan(
        step, state0,
        (r.astype(jnp.float32), k.astype(jnp.float32),
         v.astype(jnp.float32), w.astype(jnp.float32)))
    return outs.astype(r.dtype)
