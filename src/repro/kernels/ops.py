"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy: on a TPU (`jax.default_backend() == "tpu"`) every wrapper
runs its Pallas kernel.  On the CPU the pure-jnp oracle in `ref.py` runs
instead; that path exists for the tests and the fake-device dry runs, not
for deployment.  `interpret=True` runs a kernel in Pallas interpret mode
on the CPU, which is how the tests check kernels against the oracles.
`chip_smoke.py` asserts on the chip that each main-path wrapper compiles
to its Pallas kernel (`tpu_custom_call`), and tests/test_tpu_compile.py
compiles them for a described v5e without one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.amtl_event_batch import \
    amtl_event_batch as _amtl_event_batch_pallas
from repro.kernels.gauss_sketch import gauss_sketch as _gauss_sketch_pallas
from repro.kernels.km_update import km_update as _km_pallas
from repro.kernels.l21_prox import l21_prox as _l21_pallas
from repro.kernels.lstsq_grad import lstsq_grad as _lstsq_pallas
from repro.kernels.lstsq_grad_sampled import \
    lstsq_grad_sampled as _lstsq_sampled_pallas
from repro.kernels.lstsq_grad_sampled import \
    lstsq_grad_sampled_batch as _lstsq_sampled_batch_pallas
from repro.kernels.lstsq_grad_sampled import sample_mask as _sample_mask_pallas
from repro.kernels.svt_reconstruct import \
    svt_reconstruct as _svt_reconstruct_pallas

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pallas(use_pallas: bool | None, interpret: bool) -> bool:
    """Whether a wrapper runs its Pallas kernel: `use_pallas` if given,
    else on a TPU; `interpret=True` always does (in interpret mode)."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    return use_pallas or interpret


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def km_update(v: Array, p: Array, g: Array, eta: Array, eta_k: Array, *,
              use_pallas: bool | None = None,
              interpret: bool = False) -> Array:
    if _pallas(use_pallas, interpret):
        return _km_pallas(v, p, g, eta, eta_k, interpret=interpret)
    return ref.km_update_ref(v, p, g, eta, eta_k)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def amtl_event_batch(v: Array, p_cols: Array, g_cols: Array, tasks: Array,
                     eta: Array, eta_ks: Array, *,
                     use_pallas: bool | None = None,
                     interpret: bool = False) -> tuple[Array, Array]:
    """Batched multi-event update: returns (v_new, undo_cols (B, d)).

    Within-batch duplicate tasks serialize in event order (see
    `ref.amtl_event_batch_ref`).  On CPU the oracle path is also the batch
    engine's hot path — its per-event arithmetic is the dense engine's
    expression, which is what the bitwise equivalence tests rely on.

    The sharded engine passes shard-local `tasks` (from
    `ref.shard_local_tasks`), which carry the sentinel id T_local ==
    v.shape[1] for events owned by other shards.  Sentinel events are
    computed on clamped inputs and dropped at the scatter, leaving v's
    columns untouched; owned events issue bit-for-bit the arithmetic the
    unsharded batch op would, which is what makes the sharded engine's
    per-shard execution a masked replay of the global batch rather than a
    reimplementation.
    """
    if _pallas(use_pallas, interpret):
        return _amtl_event_batch_pallas(v, p_cols, g_cols, tasks, eta,
                                        eta_ks, interpret=interpret)
    return ref.amtl_event_batch_ref(v, p_cols, g_cols, tasks, eta, eta_ks)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def svt_reconstruct(qu: Array, s: Array, vt: Array, *,
                    use_pallas: bool | None = None,
                    interpret: bool = False) -> Array:
    """Thresholded low-rank SVT apply (QU * sigma) @ V^T: (d, m).

    The tail of both `prox.svt_randomized` and the rank-distributed
    `prox.svt_randomized_dist` — routing every randomized prox through the
    same dispatch keeps the serial and distributed refreshes on identical
    arithmetic per backend (the bitwise 1-shard contract on CPU; on TPU
    both take the fused Pallas kernel, so they stay mutually consistent).
    """
    if _pallas(use_pallas, interpret):
        return _svt_reconstruct_pallas(qu, s, vt, interpret=interpret)
    return ref.svt_reconstruct_ref(qu, s, vt)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def l21_prox(w: Array, t: Array, *, use_pallas: bool | None = None,
             interpret: bool = False) -> Array:
    if _pallas(use_pallas, interpret):
        return _l21_pallas(w, t, interpret=interpret)
    return ref.l21_prox_ref(w, t)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def lstsq_grad(x: Array, w: Array, y: Array, *, n_t: Array | None = None,
               use_pallas: bool | None = None,
               interpret: bool = False) -> Array:
    """Fused 2 X^T (X w - y); `n_t` (traced, optional) masks a ragged
    buffer's rows >= n_t out of the residual.  n_t=None is the original
    unmasked expression on both dispatch targets."""
    if _pallas(use_pallas, interpret):
        return _lstsq_pallas(x, w, y, n_t=n_t, interpret=interpret)
    if n_t is None:
        return ref.lstsq_grad_ref(x, w, y)
    return ref.lstsq_grad_masked_ref(x, w, y, n_t)


@functools.partial(jax.jit, static_argnames=("batch_size", "use_pallas",
                                             "interpret"))
def lstsq_grad_sampled(x: Array, w: Array, y: Array, seed: Array, *,
                       batch_size: int, n_t: Array | None = None,
                       use_pallas: bool | None = None,
                       interpret: bool = False) -> Array:
    """Unbiased seeded-minibatch gradient (n_t/bsz) * 2 X_S^T (X_S w - y_S).

    `seed` is the per-event uint32 sampling seed, `batch_size` static,
    `n_t` an optional TRACED valid-row count for ragged padded buffers
    (bsz = min(batch_size, n_t) clamp inside — the simulator's SGD-AMTL
    convention; selection restricted to rows < n_t).  S is the rank-bsz
    counter-hash selection of (seed, row): identical in the Pallas kernel
    and the jnp oracle, so the CPU oracle path and the TPU kernel sample
    the same minibatch, and every shard of the sharded engine re-derives
    an event's selection from the replicated seed.  The oracle gathers
    the static-size minibatch (O(bsz d) FLOPs on CPU); the kernel masks
    in VMEM and keeps its single O(n d) pass over X's strips.
    batch_size >= n_t degenerates to `lstsq_grad`'s masked expression
    bitwise per backend, and n_t == n keeps every bit of the uniform
    path.
    """
    if _pallas(use_pallas, interpret):
        return _lstsq_sampled_pallas(x, w, y, seed, batch_size=batch_size,
                                     n_t=n_t, interpret=interpret)
    if n_t is None:
        return ref.lstsq_grad_sampled_ref(x, w, y, seed, batch_size)
    return ref.lstsq_grad_sampled_masked_ref(x, w, y, seed, batch_size, n_t)


@functools.partial(jax.jit, static_argnames=("batch_size", "use_pallas",
                                             "interpret"))
def lstsq_grad_sampled_batch(xs: Array, ys: Array, ts: Array, ws: Array,
                             seeds: Array, *, batch_size: int,
                             row_counts: Array | None = None,
                             use_pallas: bool | None = None,
                             interpret: bool = False) -> Array:
    """(B, d) seeded-minibatch gradients of a batch of events, one pass.

    Event e's row is `lstsq_grad_sampled` of task ts[e]'s rows of the
    store xs (T, n, d), ys (T, n) at ws[e] with seed seeds[e] and n_t =
    row_counts[ts[e]] (n where row_counts is None).  On a TPU the batch's
    selection is one sort, its rows one gather and its gradients one
    Pallas call; the oracle path scans the events through
    `lstsq_grad_sampled`, bit for bit the per-event engine step.
    """
    if _pallas(use_pallas, interpret):
        return _lstsq_sampled_batch_pallas(xs, ys, ts, ws, seeds,
                                           batch_size=batch_size,
                                           row_counts=row_counts,
                                           interpret=interpret)

    def one(_, inp):
        t, w, seed = inp
        x_t = jax.lax.dynamic_index_in_dim(xs, t, axis=0, keepdims=False)
        y_t = jax.lax.dynamic_index_in_dim(ys, t, axis=0, keepdims=False)
        n_t = None if row_counts is None else jax.lax.dynamic_index_in_dim(
            row_counts, t, axis=0, keepdims=False)
        return None, lstsq_grad_sampled(x_t, w, y_t, seed,
                                        batch_size=batch_size, n_t=n_t,
                                        use_pallas=False)

    return jax.lax.scan(one, None, (ts, ws, seeds))[1]


@functools.partial(jax.jit, static_argnames=("n", "batch_size", "use_pallas",
                                             "interpret"))
def sample_mask(n: int, batch_size: int, seed: Array, *,
                n_t: Array | None = None,
                use_pallas: bool | None = None,
                interpret: bool = False) -> Array:
    """(n,) bool keep/drop bits of the seeded minibatch selection.

    The standalone view of `lstsq_grad_sampled`'s in-kernel sampler; both
    dispatch targets must agree exactly for every (n, batch_size, seed,
    n_t) (tests/test_sampling_properties.py pins this).  With ragged
    `n_t`, exactly min(batch_size, n_t) bits are set, all below n_t.
    """
    if _pallas(use_pallas, interpret):
        return _sample_mask_pallas(n, batch_size, seed, n_t=n_t,
                                   interpret=interpret)
    if n_t is None:
        return ref.sample_mask_ref(n, batch_size, seed)
    return ref.sample_mask_masked_ref(n, batch_size, seed, n_t)


@functools.partial(jax.jit, static_argnames=("p", "use_pallas", "interpret"))
def gauss_sketch(w: Array, seed: Array, row_offset: Array, *, p: int,
                 use_pallas: bool | None = None,
                 interpret: bool = False) -> Array:
    """(d, p) f32 randomized-SVT sketch W @ Omega, Omega unmaterialized.

    Omega's entry (r, c) is a Box-Muller normal over counter hashes of
    (seed, r, c) — the Pallas kernel generates tiles in VMEM (Omega never
    touches HBM), the oracle materializes the same bits.  `row_offset`
    (traced) is the block's first global Omega row: 0 for the serial
    prox, the shard's global column offset for the rank-distributed one —
    partitioning rows this way keeps sum_s W_s @ Omega_s = W @ Omega over
    one global Omega, the distributed prox's psum identity.
    """
    if _pallas(use_pallas, interpret):
        return _gauss_sketch_pallas(w, seed, row_offset, p=p,
                                    interpret=interpret)
    return ref.gauss_sketch_ref(w, seed, row_offset, p)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "use_pallas", "interpret"))
def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int | None = None,
                    softcap: float | None = None,
                    use_pallas: bool | None = None,
                    interpret: bool = False) -> Array:
    """q: (S, H, hd); k, v: (S, Hkv, hd) — GQA kv heads repeated here.
    Returns (S, H, hd).  Pads S to a 128 multiple and hd to a lane
    multiple before entering the kernel."""
    s, h, hd = q.shape
    hkv = k.shape[1]
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if not _pallas(use_pallas, interpret):
        return ref.sliding_flash_attention_ref(q, k, v, window=window,
                                               causal=causal,
                                               softcap=softcap)
    from repro.kernels.flash_attention import flash_attention as _fa
    blk = 128
    s_pad = (-s) % blk
    hd_pad = (-hd) % 128
    qt = jnp.pad(q, ((0, s_pad), (0, 0), (0, hd_pad))).transpose(1, 0, 2)
    kt = jnp.pad(k, ((0, s_pad), (0, 0), (0, hd_pad))).transpose(1, 0, 2)
    vt = jnp.pad(v, ((0, s_pad), (0, 0), (0, hd_pad))).transpose(1, 0, 2)
    out = _fa(qt, kt, vt, causal=causal, window=window, softcap=softcap,
              valid_len=s, true_hd=hd, interpret=interpret)
    return out.transpose(1, 0, 2)[:s, :, :hd]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def rwkv6_scan(r: Array, k: Array, v: Array, w: Array, u: Array, *,
               use_pallas: bool | None = None,
               interpret: bool = False) -> Array:
    """RWKV-6 WKV recurrence.  r,k,v,w: (S, H, D); u: (H, D)."""
    if not _pallas(use_pallas, interpret):
        return ref.rwkv6_scan_ref(r, k, v, w, u)
    from repro.kernels.rwkv6_scan import rwkv6_scan as _wkv
    s = r.shape[0]
    blk = 128
    pad = (-s) % blk
    if pad:
        pads = ((0, pad), (0, 0), (0, 0))
        # w=1 on padding keeps the (unused) state finite
        r2, k2, v2 = (jnp.pad(a, pads) for a in (r, k, v))
        w2 = jnp.pad(w, pads, constant_values=1.0)
        return _wkv(r2, k2, v2, w2, u, interpret=interpret)[:s]
    return _wkv(r, k, v, w, u, interpret=interpret)
