"""Pallas kernel validation: shape/dtype sweeps vs. the ref.py oracles,
executed in interpret mode on CPU (kernel bodies run exactly as written)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.km_update import km_update
from repro.kernels.l21_prox import l21_prox
from repro.kernels.lstsq_grad import lstsq_grad

DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- km_update
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(8, 4), (50, 20), (256, 128), (300, 130),
                                   (1000, 16), (7, 1)])
def test_km_update_matches_ref(shape, dtype):
    k = jax.random.PRNGKey(0)
    kv, kp, kg = jax.random.split(k, 3)
    v = jax.random.normal(kv, shape, dtype)
    p = jax.random.normal(kp, shape, dtype)
    g = jax.random.normal(kg, shape, dtype)
    eta, eta_k = jnp.asarray(0.05), jnp.asarray(0.8)
    got = km_update(v, p, g, eta, eta_k, interpret=True)
    want = ref.km_update_ref(v.astype(jnp.float32), p.astype(jnp.float32),
                             g.astype(jnp.float32), eta, eta_k)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, **_tol(dtype))


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 300), st.integers(1, 150),
       st.floats(0.0, 1.0), st.floats(0.0, 1.0))
def test_km_update_property(d, t, eta, eta_k):
    key = jax.random.PRNGKey(d * 1000 + t)
    kv, kp, kg = jax.random.split(key, 3)
    v = jax.random.normal(kv, (d, t))
    p = jax.random.normal(kp, (d, t))
    g = jax.random.normal(kg, (d, t))
    got = km_update(v, p, g, jnp.asarray(eta), jnp.asarray(eta_k),
                    interpret=True)
    want = ref.km_update_ref(v, p, g, jnp.asarray(eta), jnp.asarray(eta_k))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- l21_prox
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(8, 4), (50, 20), (512, 128), (600, 7),
                                   (1, 1), (1023, 3)])
def test_l21_prox_matches_ref(shape, dtype):
    w = jax.random.normal(jax.random.PRNGKey(1), shape, dtype) * 2.0
    t = jnp.asarray(0.5)
    got = l21_prox(w, t, interpret=True)
    want = ref.l21_prox_ref(w, t)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 600), st.integers(1, 40), st.floats(1e-3, 5.0))
def test_l21_prox_property(d, t_dim, thresh):
    w = jax.random.normal(jax.random.PRNGKey(d + t_dim), (d, t_dim)) * 3.0
    got = l21_prox(w, jnp.asarray(thresh), interpret=True)
    want = ref.l21_prox_ref(w, jnp.asarray(thresh))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_l21_prox_agrees_with_core_prox():
    from repro.core.prox import l21_prox as core_l21
    w = jax.random.normal(jax.random.PRNGKey(2), (100, 10))
    np.testing.assert_allclose(l21_prox(w, jnp.asarray(0.3), interpret=True),
                               core_l21(w, jnp.asarray(0.3)),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------- svt_reconstruct
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(8, 4, 4), (64, 24, 128), (300, 24, 16),
                                   (1000, 9, 130), (7, 1, 1), (256, 128, 256)])
def test_svt_reconstruct_matches_ref(shape, dtype):
    """(d, p, m) sweep incl. non-tile-aligned p/m and the engine's shapes
    (p = rank+8 = 24 against a full T and a shard's n_local block)."""
    d, p, m = shape
    from repro.kernels.svt_reconstruct import svt_reconstruct
    kq, ks, kv = jax.random.split(jax.random.PRNGKey(6), 3)
    qu = jax.random.normal(kq, (d, p), dtype)
    s = jax.random.uniform(ks, (p,), jnp.float32, 0.0, 3.0)
    vt = jax.random.normal(kv, (p, m), dtype)
    got = svt_reconstruct(qu, s, vt, interpret=True)
    want = ref.svt_reconstruct_ref(qu, s, vt)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_svt_reconstruct_zero_sigma_kills_directions():
    """A zeroed (thresholded-away) singular value must contribute exactly
    nothing, even when its qu/vt factors are wild — the padded-lane
    argument for the kernel relies on this."""
    from repro.kernels.svt_reconstruct import svt_reconstruct
    d, p, m = 40, 6, 10
    kq, kv = jax.random.split(jax.random.PRNGKey(7))
    qu = jax.random.normal(kq, (d, p)) * 1e3
    vt = jax.random.normal(kv, (p, m)) * 1e3
    s = jnp.asarray([1.0, 0.0, 2.0, 0.0, 0.0, 0.5], jnp.float32)
    got = svt_reconstruct(qu, s, vt, interpret=True)
    kept = jnp.asarray([0, 2, 5])
    want = (qu[:, kept] * s[kept][None, :]) @ vt[kept, :]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-2)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 300), st.integers(1, 40), st.integers(1, 150))
def test_svt_reconstruct_property(d, p, m):
    from repro.kernels.svt_reconstruct import svt_reconstruct
    kq, ks, kv = jax.random.split(jax.random.PRNGKey(d * 131 + p * 7 + m), 3)
    qu = jax.random.normal(kq, (d, p))
    s = jax.random.uniform(ks, (p,), jnp.float32, 0.0, 2.0)
    vt = jax.random.normal(kv, (p, m))
    got = svt_reconstruct(qu, s, vt, interpret=True)
    want = ref.svt_reconstruct_ref(qu, s, vt)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# -------------------------------------------------------------- lstsq_grad
@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("shape", [(16, 8), (100, 50), (512, 128), (700, 130),
                                   (1, 5), (1000, 28)])
def test_lstsq_grad_matches_ref(shape, dtype):
    n, d = shape
    kx, kw, ky = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(kx, (n, d), dtype) / np.sqrt(d)
    w = jax.random.normal(kw, (d,), dtype)
    y = jax.random.normal(ky, (n,), dtype)
    got = lstsq_grad(x, w, y, interpret=True)
    want = ref.lstsq_grad_ref(x, w, y)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_lstsq_grad_bf16_accumulates_fp32():
    n, d = 512, 128
    kx, kw, ky = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(kx, (n, d), jnp.bfloat16) / np.sqrt(d)
    w = jax.random.normal(kw, (d,), jnp.bfloat16)
    y = jax.random.normal(ky, (n,), jnp.bfloat16)
    got = np.asarray(lstsq_grad(x, w, y, interpret=True), np.float32)
    want = np.asarray(ref.lstsq_grad_ref(x, w, y), np.float32)
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 700), st.integers(1, 160))
def test_lstsq_grad_property(n, d):
    kx, kw, ky = jax.random.split(jax.random.PRNGKey(n * 7 + d), 3)
    x = jax.random.normal(kx, (n, d)) / np.sqrt(max(d, 1))
    w = jax.random.normal(kw, (d,))
    y = jax.random.normal(ky, (n,))
    got = lstsq_grad(x, w, y, interpret=True)
    want = ref.lstsq_grad_ref(x, w, y)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_lstsq_grad_is_true_gradient():
    """Oracle itself equals autodiff of ||Xw-y||^2."""
    n, d = 64, 32
    kx, kw, ky = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(kx, (n, d))
    w = jax.random.normal(kw, (d,))
    y = jax.random.normal(ky, (n,))
    auto = jax.grad(lambda ww: jnp.sum((x @ ww - y) ** 2))(w)
    np.testing.assert_allclose(ref.lstsq_grad_ref(x, w, y), auto,
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------ lstsq_grad_sampled
@pytest.mark.parametrize("seed", [0, 1, 0xDEADBEEF])
@pytest.mark.parametrize("shape,bsz", [((16, 8), 4), ((100, 50), 25),
                                       ((512, 128), 64), ((700, 130), 33),
                                       ((1, 5), 1), ((1000, 28), 512),
                                       ((30, 10), 30), ((30, 10), 99)])
def test_lstsq_grad_sampled_matches_ref(shape, bsz, seed):
    """Seeded-minibatch kernel vs oracle across block boundaries, bsz = n,
    and the saturated bsz > n clamp."""
    from repro.kernels.lstsq_grad_sampled import lstsq_grad_sampled
    n, d = shape
    kx, kw, ky = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(kx, (n, d), jnp.float32) / np.sqrt(d)
    w = jax.random.normal(kw, (d,), jnp.float32)
    y = jax.random.normal(ky, (n,), jnp.float32)
    s = jnp.asarray(seed, jnp.uint32)
    got = lstsq_grad_sampled(x, w, y, s, batch_size=bsz, interpret=True)
    want = ref.lstsq_grad_sampled_ref(x, w, y, s, bsz)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_lstsq_grad_sampled_saturated_equals_full_kernel():
    """batch_size >= n inside the KERNEL: all-ones mask and unit scale must
    reproduce the full-gradient kernel's arithmetic."""
    from repro.kernels.lstsq_grad_sampled import lstsq_grad_sampled
    n, d = 600, 40
    kx, kw, ky = jax.random.split(jax.random.PRNGKey(8), 3)
    x = jax.random.normal(kx, (n, d), jnp.float32) / np.sqrt(d)
    w = jax.random.normal(kw, (d,), jnp.float32)
    y = jax.random.normal(ky, (n,), jnp.float32)
    got = lstsq_grad_sampled(x, w, y, jnp.asarray(5, jnp.uint32),
                             batch_size=n, interpret=True)
    want = lstsq_grad(x, w, y, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 700), st.integers(1, 160), st.integers(1, 700),
       st.integers(0, 2**32 - 1))
def test_lstsq_grad_sampled_property(n, d, bsz, seed):
    from repro.kernels.lstsq_grad_sampled import lstsq_grad_sampled
    kx, kw, ky = jax.random.split(jax.random.PRNGKey(n * 7 + d), 3)
    x = jax.random.normal(kx, (n, d)) / np.sqrt(max(d, 1))
    w = jax.random.normal(kw, (d,))
    y = jax.random.normal(ky, (n,))
    s = jnp.asarray(seed, jnp.uint32)
    got = lstsq_grad_sampled(x, w, y, s, batch_size=bsz, interpret=True)
    want = ref.lstsq_grad_sampled_ref(x, w, y, s, bsz)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("ragged", (False, True), ids=("uniform", "ragged"))
@pytest.mark.parametrize("t,n,d,bsz", [(6, 40, 12, 8), (5, 520, 300, 32),
                                       (4, 64, 784, 32), (3, 9, 130, 8)])
def test_lstsq_grad_sampled_batch_matches_per_event_oracle(t, n, d, bsz,
                                                           ragged):
    """The batched kernel (interpret mode) against the per-event oracle,
    event by event: duplicate tasks, and events clamped onto task 0 as the
    sharded engine clamps another shard's events; ragged cohorts from
    empty through fewer than bsz rows to full."""
    from repro.kernels.lstsq_grad_sampled import lstsq_grad_sampled_batch
    kx, ky, kw, ks, kc = jax.random.split(jax.random.PRNGKey(n + d), 5)
    xs = jax.random.normal(kx, (t, n, d), jnp.float32) / np.sqrt(d)
    ys = jax.random.normal(ky, (t, n), jnp.float32)
    ts = jnp.asarray([0, t - 1, t - 1, 0, 1, 0, 2, 0], jnp.int32)
    ws = jax.random.normal(kw, (ts.shape[0], d), jnp.float32)
    seeds = jax.random.bits(ks, ts.shape, jnp.uint32)
    counts = None
    if ragged:
        counts = jax.random.randint(kc, (t,), 0, n + 1).at[:3].set(
            jnp.asarray([n, 0, min(bsz - 1, n)]))
    got = lstsq_grad_sampled_batch(xs, ys, ts, ws, seeds, batch_size=bsz,
                                   row_counts=counts, interpret=True)
    want = jnp.stack([
        ref.lstsq_grad_sampled_ref(xs[k], ws[e], ys[k], seeds[e], bsz)
        if counts is None else
        ref.lstsq_grad_sampled_masked_ref(xs[k], ws[e], ys[k], seeds[e], bsz,
                                          counts[k])
        for e, k in enumerate(np.asarray(ts))])
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * scale)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 1100), st.integers(1, 1100), st.integers(0, 2**32 - 1))
def test_sample_mask_kernel_bitwise(n, bsz, seed):
    """Selection bits are EXACT (pure uint32 arithmetic): kernel == oracle
    with array_equal, no tolerance."""
    from repro.kernels.lstsq_grad_sampled import sample_mask
    s = jnp.asarray(seed, jnp.uint32)
    got = sample_mask(n, bsz, s, interpret=True)
    want = ref.sample_mask_ref(n, bsz, s)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------------------- gauss_sketch
@pytest.mark.parametrize("d,t,p", [(8, 4, 4), (64, 24, 24), (300, 16, 9),
                                   (1024, 128, 24), (2000, 130, 130),
                                   (7, 1, 1)])
def test_gauss_sketch_matches_ref(d, t, p):
    """In-kernel counter-generated Omega vs the materializing oracle,
    incl. p > 128 (multi-lane-tile Omega) and non-aligned shapes."""
    from repro.kernels.gauss_sketch import gauss_sketch
    w = jax.random.normal(jax.random.PRNGKey(9), (d, t), jnp.float32)
    s = jnp.asarray(0xC0FFEE, jnp.uint32)
    off = jnp.zeros((), jnp.int32)
    got = gauss_sketch(w, s, off, p=p, interpret=True)
    want = ref.gauss_sketch_ref(w, s, off, p)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_gauss_sketch_row_offset_partitions_globally():
    """Shard semantics: row blocks of W sketched at their global offsets
    must sum to the full sketch — the psum identity of the distributed
    randomized SVT."""
    from repro.kernels.gauss_sketch import gauss_sketch
    d, t, p = 96, 12, 8
    w = jax.random.normal(jax.random.PRNGKey(10), (d, t), jnp.float32)
    s = jnp.asarray(1234, jnp.uint32)
    full = gauss_sketch(w, s, jnp.zeros((), jnp.int32), p=p, interpret=True)
    parts = sum(
        gauss_sketch(w[:, o:o + 4], s, jnp.asarray(o, jnp.int32), p=p,
                     interpret=True)
        for o in (0, 4, 8))
    np.testing.assert_allclose(parts, full, rtol=1e-5, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 500), st.integers(1, 140), st.integers(1, 140),
       st.integers(0, 2**32 - 1))
def test_gauss_sketch_property(d, t, p, seed):
    from repro.kernels.gauss_sketch import gauss_sketch
    w = jax.random.normal(jax.random.PRNGKey(d * 13 + t), (d, t))
    s = jnp.asarray(seed, jnp.uint32)
    off = jnp.zeros((), jnp.int32)
    got = gauss_sketch(w, s, off, p=p, interpret=True)
    want = ref.gauss_sketch_ref(w, s, off, p)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- ops layer
def test_ops_dispatch_cpu_uses_ref():
    from repro.kernels import ops
    v = jax.random.normal(jax.random.PRNGKey(6), (32, 8))
    out = ops.km_update(v, v, v, jnp.asarray(0.1), jnp.asarray(0.5))
    want = ref.km_update_ref(v, v, v, jnp.asarray(0.1), jnp.asarray(0.5))
    np.testing.assert_allclose(out, want, rtol=1e-6)


# ------------------------------------------------- flash attention kernel
@pytest.mark.parametrize("s,h,hkv,hd", [(64, 4, 4, 64), (200, 4, 2, 72),
                                        (256, 8, 1, 128), (100, 2, 2, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(s, h, hkv, hd, dtype):
    from repro.kernels import ops
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = (jax.random.normal(ks[0], (s, h, hd)) * 0.3).astype(dtype)
    k = (jax.random.normal(ks[1], (s, hkv, hd)) * 0.3).astype(dtype)
    v = (jax.random.normal(ks[2], (s, hkv, hd)) * 0.3).astype(dtype)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    kr = jnp.repeat(k, h // hkv, axis=1)
    vr = jnp.repeat(v, h // hkv, axis=1)
    want = ref.sliding_flash_attention_ref(q, kr, vr, window=None)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@settings(max_examples=8, deadline=None)
@given(s=st.integers(16, 180), window=st.integers(4, 64),
       softcap=st.one_of(st.none(), st.floats(10.0, 50.0)))
def test_flash_attention_window_softcap_property(s, window, softcap):
    from repro.kernels import ops
    ks = jax.random.split(jax.random.PRNGKey(s), 3)
    q = jax.random.normal(ks[0], (s, 2, 48)) * 0.3
    k = jax.random.normal(ks[1], (s, 2, 48)) * 0.3
    v = jax.random.normal(ks[2], (s, 2, 48)) * 0.3
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              softcap=softcap, interpret=True)
    want = ref.sliding_flash_attention_ref(q, k, v, window=window,
                                           softcap=softcap)
    np.testing.assert_allclose(out, want, atol=3e-5)


# ---------------------------------------------------- rwkv6 scan kernel
@pytest.mark.parametrize("s,h,d", [(64, 2, 64), (200, 3, 64), (128, 1, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_scan_shapes_dtypes(s, h, d, dtype):
    from repro.kernels import ops
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    r = (jax.random.normal(ks[0], (s, h, d)) * 0.3).astype(dtype)
    k = (jax.random.normal(ks[1], (s, h, d)) * 0.3).astype(dtype)
    v = (jax.random.normal(ks[2], (s, h, d)) * 0.3).astype(dtype)
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (s, h, d))).astype(dtype)
    u = (jax.random.normal(ks[4], (h, d)) * 0.3).astype(dtype)
    out = ops.rwkv6_scan(r, k, v, w, u, interpret=True)
    want = ref.rwkv6_scan_ref(r, k, v, w, u)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)
