"""Attention: GQA (full / sliding-window / softcap / encoder), gated
cross-attention (VLM), and DeepSeek MLA with an absorbed decode path.

Full-sequence attention is flash-style in pure jnp: an online-softmax
`lax.scan` over KV chunks, so the (S, S) logit matrix is never materialized
(required for prefill_32k to fit HBM).  The Pallas `flash_attention` kernel
mirrors this algorithm for TPU; the jnp path here is what the CPU dry-run
lowers (DESIGN.md §8).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import apply_norm, apply_rope, dense_init, init_norm

Array = jax.Array

NEG_INF = -1e30


class KVCache(NamedTuple):
    """Per-layer KV cache.  k/v: (B, S_max, Hkv, hd).

    With cfg.kv_cache_dtype == "int8", k/v are int8 and k_scale/v_scale
    hold per-(position, head) absmax dequant scales (B, S_max, Hkv) f32 —
    0.8% storage overhead for a 2x traffic cut; scores/outputs use
    q.(k_int*s) == (q.k_int)*s so the dot itself runs on int8 operands
    (MXU-native on TPU)."""
    k: Array
    v: Array
    k_scale: Optional[Array] = None
    v_scale: Optional[Array] = None


def quantize_kv(t: Array) -> tuple[Array, Array]:
    """t: (..., hd) -> int8 values + f32 absmax scale over hd."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.round(t.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


# ---------------------------------------------------------------------------
# core flash-style multi-head attention
# ---------------------------------------------------------------------------

def mha(q: Array, k: Array, v: Array, *, causal: bool,
        window: Optional[int] = None, softcap: Optional[float] = None,
        q_offset: Array | int = 0, kv_valid_len: Optional[Array] = None,
        kv_chunk: int = 1024) -> Array:
    """Online-softmax attention.

    q: (B, Sq, H, hd); k, v: (B, Skv, Hkv, hd); GQA via head grouping.
    q_offset: absolute position of q[0] (decode: current position).
    kv_valid_len: number of valid cache entries (decode with static cache).
    """
    b, sq, h, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    kc = min(kv_chunk, skv)
    n_chunks = (skv + kc - 1) // kc
    pad = n_chunks * kc - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    k = k.reshape(b, n_chunks, kc, hkv, hd).transpose(1, 0, 2, 3, 4)
    v = v.reshape(b, n_chunks, kc, hkv, hd).transpose(1, 0, 2, 3, 4)

    q_pos = jnp.asarray(q_offset) + jnp.arange(sq)          # (Sq,)

    def body(carry, inp):
        m, l, acc = carry
        k_c, v_c, c_idx = inp                               # (B,kc,Hkv,hd)
        kv_pos = c_idx * kc + jnp.arange(kc)                # (kc,)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                            k_c.astype(jnp.float32)) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        mask = jnp.ones((sq, kc), bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        mask &= kv_pos[None, :] < (skv if kv_valid_len is None
                                   else kv_valid_len)
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = (acc * corr[..., None]
                   + jnp.einsum("bhgqk,bkhd->bhgqd", p,
                                v_c.astype(jnp.float32)))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, hkv, g, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (k, v, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------

def init_attn(key: Array, cfg: ArchConfig, dtype) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], (d, h * hd), dtype),
         "wk": dense_init(ks[1], (d, hkv * hd), dtype),
         "wv": dense_init(ks[2], (d, hkv * hd), dtype),
         "wo": dense_init(ks[3], (h * hd, d), dtype)}
    if cfg.qk_norm:
        p["q_norm"] = init_norm("rmsnorm", hd, dtype)
        p["k_norm"] = init_norm("rmsnorm", hd, dtype)
    return p


def _project_qkv(p: dict, x: Array, cfg: ArchConfig,
                 positions: Array, theta: float):
    b, s, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, h, hd)
    k = (x @ p["wk"].astype(x.dtype)).reshape(b, s, hkv, hd)
    v = (x @ p["wv"].astype(x.dtype)).reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = apply_norm("rmsnorm", p["q_norm"], q)
        k = apply_norm("rmsnorm", p["k_norm"], k)
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    return q, k, v


def attn_forward(p: dict, x: Array, cfg: ArchConfig, *,
                 window: Optional[int] = None,
                 theta: Optional[float] = None,
                 return_cache: bool = False,
                 cache_len: Optional[int] = None, ctx=None):
    """Full-sequence attention (train / prefill).  x: (B, S, D)."""
    b, s, _ = x.shape
    theta = cfg.rope_theta if theta is None else theta
    positions = jnp.arange(s)[None, :]
    q, k, v = _project_qkv(p, x, cfg, positions, theta)
    # pin head-sharded TP when both H and Hkv divide the model axis
    if ctx is not None and ctx.mesh is not None:
        msize = ctx.axis_sizes.get(ctx.model_axis, 1)
        if msize > 1 and q.shape[2] % msize == 0 \
                and k.shape[2] % msize == 0:
            q, k, v = (_head_shard(q, ctx), _head_shard(k, ctx),
                       _head_shard(v, ctx))
    out = mha(q, k, v, causal=cfg.causal, window=window,
              softcap=cfg.attn_softcap)
    out = out.reshape(b, s, -1) @ p["wo"].astype(x.dtype)
    if not return_cache:
        return out
    cl = cache_len if cache_len is not None else s
    if window is not None:
        cl = min(cl, window)
    kf, vf = _fit_cache(k, cl), _fit_cache(v, cl)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = quantize_kv(kf)
        vq, vs = quantize_kv(vf)
        return out, KVCache(k=kq, v=vq, k_scale=ks, v_scale=vs)
    return out, KVCache(k=kf, v=vf)


def _fit_cache(k: Array, cache_len: int) -> Array:
    """Keep the last `cache_len` positions (ring semantics for local attn)."""
    s = k.shape[1]
    if s >= cache_len:
        return k[:, s - cache_len:]
    pad = cache_len - s
    return jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))


def attn_decode(p: dict, x: Array, cache: KVCache, pos: Array,
                cfg: ArchConfig, *, window: Optional[int] = None,
                theta: Optional[float] = None):
    """One-token decode.  x: (B, 1, D); pos: scalar int32 absolute position.

    Local (sliding-window) layers keep a ring cache of size `window`; global
    layers keep the full-length cache.  Returns (out, new_cache).
    """
    b = x.shape[0]
    theta = cfg.rope_theta if theta is None else theta
    positions = jnp.full((1, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, theta)

    s_max = cache.k.shape[1]
    if window is None:
        slot = jnp.minimum(pos, s_max - 1)
    else:
        slot = pos % s_max                     # ring cache for local layers
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new.astype(cache.k.dtype), slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new.astype(cache.v.dtype), slot, axis=1)

    if window is None:
        out = mha(q, k, v, causal=False, softcap=cfg.attn_softcap,
                  kv_valid_len=pos + 1, kv_chunk=4096)
    else:
        # Ring cache: all resident entries are within the window by
        # construction; mask only the unwritten tail early on.
        valid = jnp.minimum(pos + 1, s_max)
        out = mha(q, k, v, causal=False, softcap=cfg.attn_softcap,
                  kv_valid_len=valid, kv_chunk=4096)
    out = out.reshape(b, 1, -1) @ p["wo"].astype(x.dtype)
    return out, KVCache(k=k, v=v)


def attn_decode_sharded(p: dict, x: Array, cache: KVCache, pos: Array,
                        cfg: ArchConfig, ctx, *,
                        window: Optional[int] = None,
                        theta: Optional[float] = None):
    """One-token decode with the KV cache left sharded over `model`.

    Plain attn_decode performs a dynamic_update_slice at a runtime slot on
    the model-sharded seq dim; GSPMD cannot partition that and falls back
    to "involuntary full rematerialization" — it all-gathers the WHOLE
    cache every step (31 GB/device/step for gemma2 decode_32k; see
    EXPERIMENTS.md §Perf).  Here both the cache update and the attention
    run inside shard_map: the owning shard writes exactly ONE slot, every
    shard computes flash-decode partial stats over its local seq chunk,
    and only (B,H,hd)-sized stats cross the ICI via psum.
    """
    b = x.shape[0]
    theta = cfg.rope_theta if theta is None else theta
    positions = jnp.full((1, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, theta)

    s_max = cache.k.shape[1]
    if window is None:
        slot = jnp.minimum(pos, s_max - 1)
        valid = pos + 1
    else:
        slot = pos % s_max                     # ring cache for local layers
        valid = jnp.minimum(pos + 1, s_max)

    maxis = ctx.model_axis
    sizes = ctx.axis_sizes
    msize = sizes.get(maxis, 1)
    dsize = 1
    for a in ctx.data_axes:
        dsize *= sizes.get(a, 1)
    quant = cache.k.dtype == jnp.int8
    if quant:
        kq_new, ks_new = quantize_kv(k_new)    # (B,1,Hkv,hd), (B,1,Hkv)
        vq_new, vs_new = quantize_kv(v_new)

    if ctx.mesh is None or msize <= 1 or s_max % msize != 0:
        # degenerate mesh: the plain path has no resharding to avoid
        if quant:
            nk = jax.lax.dynamic_update_slice_in_dim(cache.k, kq_new, slot,
                                                     axis=1)
            nv = jax.lax.dynamic_update_slice_in_dim(cache.v, vq_new, slot,
                                                     axis=1)
            nks = jax.lax.dynamic_update_slice_in_dim(cache.k_scale, ks_new,
                                                      slot, axis=1)
            nvs = jax.lax.dynamic_update_slice_in_dim(cache.v_scale, vs_new,
                                                      slot, axis=1)
            k_f = nk.astype(jnp.float32) * nks[..., None]
            v_f = nv.astype(jnp.float32) * nvs[..., None]
            out = mha(q, k_f.astype(q.dtype), v_f.astype(q.dtype),
                      causal=False, softcap=cfg.attn_softcap,
                      kv_valid_len=valid, kv_chunk=4096)
            out = out.reshape(b, 1, -1) @ p["wo"].astype(x.dtype)
            return out, KVCache(k=nk, v=nv, k_scale=nks, v_scale=nvs)
        out = mha(q, cache_k := jax.lax.dynamic_update_slice_in_dim(
                      cache.k, k_new.astype(cache.k.dtype), slot, axis=1),
                  cache_v := jax.lax.dynamic_update_slice_in_dim(
                      cache.v, v_new.astype(cache.v.dtype), slot, axis=1),
                  causal=False, softcap=cfg.attn_softcap,
                  kv_valid_len=valid, kv_chunk=4096)
        out = out.reshape(b, 1, -1) @ p["wo"].astype(x.dtype)
        return out, KVCache(k=cache_k, v=cache_v)

    from jax.sharding import PartitionSpec as P
    dax = ctx.data_axes if len(ctx.data_axes) > 1 else ctx.data_axes[0]
    bspec = dax if (dsize > 1 and b % dsize == 0) else None
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // hkv
    softcap = cfg.attn_softcap

    def _one_slot_update(buf, new, safe, inb):
        """Write exactly one slot; keep the old value when not the owner."""
        cur = jax.lax.dynamic_slice_in_dim(buf, safe, 1, axis=1)
        up = jnp.where(jnp.reshape(inb, (1,) * cur.ndim),
                       new.astype(buf.dtype), cur)
        return jax.lax.dynamic_update_slice_in_dim(buf, up, safe, axis=1)

    def _flash(qg, kf, vf, kv_pos, valid_):
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf,
                            preferred_element_type=jnp.float32) / jnp.sqrt(
                                jnp.asarray(hd, jnp.float32))
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        logits = jnp.where((kv_pos < valid_)[None, None, None, None],
                           logits, NEG_INF)
        m = jnp.max(logits, axis=-1)                      # (B,hkv,g,1)
        gm = jax.lax.pmax(m, maxis)
        pr = jnp.exp(logits - gm[..., None])
        l_tot = jax.lax.psum(jnp.sum(pr, axis=-1), maxis)
        acc = jnp.einsum("bhgqk,bkhd->bhgqd", pr.astype(vf.dtype), vf,
                         preferred_element_type=jnp.float32)
        return jax.lax.psum(acc, maxis), l_tot

    def kernel(q_l, kn, vn, kc, vc, slot_, valid_):
        bl = q_l.shape[0]
        s_l = kc.shape[1]
        start = jax.lax.axis_index(maxis) * s_l
        loc = slot_ - start
        inb = (loc >= 0) & (loc < s_l)
        safe = jnp.clip(loc, 0, s_l - 1)
        kc = _one_slot_update(kc, kn, safe, inb)
        vc = _one_slot_update(vc, vn, safe, inb)
        # flash-decode over the local chunk (positions are slot indices).
        # bf16 caches feed the MXU directly (preferred_element_type=f32)
        # instead of materializing an fp32 copy of the whole chunk.
        kv_pos = start + jnp.arange(s_l)
        qg = q_l.reshape(bl, 1, hkv, g, hd).astype(kc.dtype)
        acc_tot, l_tot = _flash(qg, kc, vc, kv_pos, valid_)
        out = acc_tot / jnp.maximum(l_tot, 1e-30)[..., None]
        out = out.transpose(0, 3, 1, 2, 4).reshape(bl, 1, h, hd)
        return out.astype(q_l.dtype), kc, vc

    def kernel_q(q_l, kn, vn, ksn, vsn, kc, vc, ks, vs, slot_, valid_):
        """int8 cache: scores = (q . k_int) * s_k, acc = (p * s_v) . v_int —
        the dot operands stay int8 (MXU-native), scales applied on the
        (B,H,1,S)-sized score/prob tensors."""
        bl = q_l.shape[0]
        s_l = kc.shape[1]
        start = jax.lax.axis_index(maxis) * s_l
        loc = slot_ - start
        inb = (loc >= 0) & (loc < s_l)
        safe = jnp.clip(loc, 0, s_l - 1)
        kc = _one_slot_update(kc, kn, safe, inb)
        vc = _one_slot_update(vc, vn, safe, inb)
        ks = _one_slot_update(ks, ksn, safe, inb)
        vs = _one_slot_update(vs, vsn, safe, inb)

        kv_pos = start + jnp.arange(s_l)
        qg = q_l.reshape(bl, 1, hkv, g, hd).astype(jnp.float32)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                            kc.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
        logits = logits * ks.transpose(0, 2, 1)[:, :, None, None, :] \
            / jnp.sqrt(jnp.asarray(hd, jnp.float32))
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        logits = jnp.where((kv_pos < valid_)[None, None, None, None],
                           logits, NEG_INF)
        m = jnp.max(logits, axis=-1)
        gm = jax.lax.pmax(m, maxis)
        pr = jnp.exp(logits - gm[..., None])
        l_tot = jax.lax.psum(jnp.sum(pr, axis=-1), maxis)
        pv = pr * vs.transpose(0, 2, 1)[:, :, None, None, :]
        acc = jnp.einsum("bhgqk,bkhd->bhgqd", pv, vc.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        acc_tot = jax.lax.psum(acc, maxis)
        out = acc_tot / jnp.maximum(l_tot, 1e-30)[..., None]
        out = out.transpose(0, 3, 1, 2, 4).reshape(bl, 1, h, hd)
        return out.astype(q_l.dtype), kc, vc, ks, vs

    rep = P(bspec, None, None, None)
    cspec = P(bspec, maxis, None, None)
    if quant:
        rep3 = P(bspec, None, None)
        sspec = P(bspec, maxis, None)
        out, k, v, ks, vs = jax.shard_map(
            kernel_q, mesh=ctx.mesh,
            in_specs=(rep, rep, rep, rep3, rep3, cspec, cspec, sspec,
                      sspec, P(), P()),
            out_specs=(rep, cspec, cspec, sspec, sspec),
            check_vma=False)(
            q, kq_new, vq_new, ks_new, vs_new, cache.k, cache.v,
            cache.k_scale, cache.v_scale, slot, valid)
        out = out.reshape(b, 1, -1) @ p["wo"].astype(x.dtype)
        return out, KVCache(k=k, v=v, k_scale=ks, v_scale=vs)

    out, k, v = jax.shard_map(
        kernel, mesh=ctx.mesh,
        in_specs=(rep, rep, rep, cspec, cspec, P(), P()),
        out_specs=(rep, cspec, cspec), check_vma=False)(
        q, k_new, v_new, cache.k, cache.v, slot, valid)
    out = out.reshape(b, 1, -1) @ p["wo"].astype(x.dtype)
    return out, KVCache(k=k, v=v)


# ---------------------------------------------------------------------------
# gated cross-attention (Llama-3.2-Vision style)
# ---------------------------------------------------------------------------

def init_cross_attn(key: Array, cfg: ArchConfig, dtype) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 5)
    return {"wq": dense_init(ks[0], (d, h * hd), dtype),
            "wk": dense_init(ks[1], (d, hkv * hd), dtype),
            "wv": dense_init(ks[2], (d, hkv * hd), dtype),
            "wo": dense_init(ks[3], (h * hd, d), dtype),
            "gate": jnp.zeros((1,), dtype)}


def cross_attn_forward(p: dict, x: Array, kv_src: Array,
                       cfg: ArchConfig) -> Array:
    """x: (B, S, D) queries; kv_src: (B, Sv, D) vision embeddings."""
    b, s, _ = x.shape
    sv = kv_src.shape[1]
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, h, hd)
    k = (kv_src.astype(x.dtype) @ p["wk"].astype(x.dtype)).reshape(b, sv, hkv, hd)
    v = (kv_src.astype(x.dtype) @ p["wv"].astype(x.dtype)).reshape(b, sv, hkv, hd)
    out = mha(q, k, v, causal=False)
    out = out.reshape(b, s, -1) @ p["wo"].astype(x.dtype)
    return jnp.tanh(p["gate"].astype(jnp.float32)).astype(x.dtype) * out


# ---------------------------------------------------------------------------
# DeepSeek MLA (multi-head latent attention) + absorbed decode
# ---------------------------------------------------------------------------

class MLACache(NamedTuple):
    """Compressed cache: c_kv (B, S, kv_lora), k_rope (B, S, rope_dim)."""
    c_kv: Array
    k_rope: Array


def init_mla(key: Array, cfg: ArchConfig, dtype) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 7)
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": dense_init(ks[0], (d, m.q_lora_rank), dtype),
        "q_norm": init_norm("rmsnorm", m.q_lora_rank, dtype),
        "wq_b": dense_init(ks[1], (m.q_lora_rank, h * qk), dtype),
        "wkv_a": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                            dtype),
        "kv_norm": init_norm("rmsnorm", m.kv_lora_rank, dtype),
        "wk_b": dense_init(ks[3], (m.kv_lora_rank, h * m.qk_nope_head_dim),
                           dtype),
        "wv_b": dense_init(ks[4], (m.kv_lora_rank, h * m.v_head_dim), dtype),
        "wo": dense_init(ks[5], (h * m.v_head_dim, d), dtype),
    }


def _mla_q(p: dict, x: Array, cfg: ArchConfig, positions: Array):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    q_lat = apply_norm("rmsnorm", p["q_norm"], x @ p["wq_a"].astype(x.dtype))
    q = (q_lat @ p["wq_b"].astype(x.dtype)).reshape(b, s, h, qk)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_kv_latent(p: dict, x: Array, cfg: ArchConfig, positions: Array):
    m = cfg.mla
    kv = x @ p["wkv_a"].astype(x.dtype)
    c_kv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = apply_norm("rmsnorm", p["kv_norm"], c_kv)
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0]           # shared head
    return c_kv, k_rope


def _head_shard(t: Array, ctx, head_axis: int = 2) -> Array:
    """Pin a (B,S,H,hd) tensor to head-sharded TP (Megatron attention).

    Without this GSPMD may let a downstream seq-sharding constraint (the
    MoE dispatch spec) propagate back into attention, and then all-gathers
    the fully head-EXPANDED k/v every layer — for deepseek-v3 that is the
    difference between resharding the 576-dim latent (75 MB) and the
    128-head 192-dim expansion (6.4 GB) per layer (EXPERIMENTS.md §Perf).
    """
    if ctx is None or ctx.mesh is None:
        return t
    sizes = ctx.axis_sizes
    msize = sizes.get(ctx.model_axis, 1)
    if msize <= 1 or t.shape[head_axis] % msize != 0:
        return t
    dsize = 1
    for a in ctx.data_axes:
        dsize *= sizes.get(a, 1)
    dax = ctx.data_axes if len(ctx.data_axes) > 1 else ctx.data_axes[0]
    spec = [None] * t.ndim
    if t.shape[0] % dsize == 0 and dsize > 1:
        spec[0] = dax
    spec[head_axis] = ctx.model_axis
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(t, P(*spec))


def mla_forward(p: dict, x: Array, cfg: ArchConfig, *, ctx=None,
                return_cache: bool = False, cache_len: Optional[int] = None):
    """Training / prefill MLA: decompress K,V and run standard attention."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    positions = jnp.arange(s)[None, :]
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv, k_rope = _mla_kv_latent(p, x, cfg, positions)
    k_nope = (c_kv @ p["wk_b"].astype(x.dtype)).reshape(
        b, s, h, m.qk_nope_head_dim)
    v = (c_kv @ p["wv_b"].astype(x.dtype)).reshape(b, s, h, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, h, m.qk_rope_head_dim))], axis=-1)
    q, k, v = (_head_shard(q, ctx), _head_shard(k, ctx),
               _head_shard(v, ctx))
    # v_head_dim may differ from qk dim; mha handles hd from q/k, v dims own.
    out = _mha_mixed_dims(q, k, v, causal=cfg.causal)
    out = out.reshape(b, s, -1) @ p["wo"].astype(x.dtype)
    if not return_cache:
        return out
    cl = cache_len if cache_len is not None else s
    return out, MLACache(c_kv=_fit2(c_kv, cl), k_rope=_fit2(k_rope, cl))


def _fit2(a: Array, cl: int) -> Array:
    s = a.shape[1]
    if s >= cl:
        return a[:, s - cl:]
    return jnp.pad(a, ((0, 0), (0, cl - s), (0, 0)))


def _mha_mixed_dims(q, k, v, *, causal):
    """mha wrapper when v head_dim != qk head_dim (MLA)."""
    b, s, h, dq = q.shape
    dv = v.shape[-1]
    if dv == dq:
        return mha(q, k, v, causal=causal)
    pad = dq - dv
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
    out = mha(q, k, v_p, causal=causal)
    return out[..., :dv]


def mla_decode(p: dict, x: Array, cache: MLACache, pos: Array,
               cfg: ArchConfig):
    """Absorbed MLA decode: attend directly in the compressed latent space.

    score_t = q_nope^T (wk_b c_t) + q_rope^T kr_t
            = (wk_b^T q_nope)^T c_t + q_rope^T kr_t
    so K never needs decompression; output is combined in latent space and
    decompressed once through wv_b.  This is the TPU-native adaptation of
    DeepSeek's MLA serving optimization (MXU-friendly einsums).
    """
    m = cfg.mla
    b = x.shape[0]
    h = cfg.num_heads
    positions = jnp.full((1, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, x, cfg, positions)          # (B,1,H,*)
    c_new, kr_new = _mla_kv_latent(p, x, cfg, positions)   # (B,1,lora/rope)

    s_max = cache.c_kv.shape[1]
    slot = jnp.minimum(pos, s_max - 1)
    c = jax.lax.dynamic_update_slice_in_dim(cache.c_kv, c_new.astype(cache.c_kv.dtype), slot, axis=1)
    kr = jax.lax.dynamic_update_slice_in_dim(cache.k_rope, kr_new.astype(cache.k_rope.dtype), slot, axis=1)

    wk_b = p["wk_b"].astype(x.dtype).reshape(m.kv_lora_rank, h,
                                             m.qk_nope_head_dim)
    q_lat = jnp.einsum("bqhn,lhn->bqhl", q_nope, wk_b)     # (B,1,H,lora)
    scale = 1.0 / jnp.sqrt(jnp.asarray(
        m.qk_nope_head_dim + m.qk_rope_head_dim, jnp.float32))
    logits = (jnp.einsum("bqhl,bsl->bhqs", q_lat.astype(jnp.float32),
                         c.astype(jnp.float32))
              + jnp.einsum("bqhr,bsr->bhqs", q_rope.astype(jnp.float32),
                           kr.astype(jnp.float32))) * scale
    mask = jnp.arange(s_max)[None, None, None, :] <= pos
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out_lat = jnp.einsum("bhqs,bsl->bqhl", probs,
                         c.astype(jnp.float32))            # (B,1,H,lora)
    wv_b = p["wv_b"].astype(x.dtype).reshape(m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bqhl,lhv->bqhv", out_lat.astype(x.dtype), wv_b)
    out = out.reshape(b, 1, -1) @ p["wo"].astype(x.dtype)
    return out, MLACache(c_kv=c, k_rope=kr)


def mla_decode_sharded(p: dict, x: Array, cache: MLACache, pos: Array,
                       cfg: ArchConfig, ctx):
    """Absorbed MLA decode with the latent cache left seq-sharded over
    `model` — the MLA analogue of attn_decode_sharded: one-slot owner
    write + flash partial stats in LATENT space (so the psum payload is
    (B,H,kv_lora), never the decompressed per-head K/V)."""
    m = cfg.mla
    b = x.shape[0]
    h = cfg.num_heads
    positions = jnp.full((1, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, x, cfg, positions)          # (B,1,H,*)
    c_new, kr_new = _mla_kv_latent(p, x, cfg, positions)   # (B,1,lora/rope)
    s_max = cache.c_kv.shape[1]
    slot = jnp.minimum(pos, s_max - 1)

    maxis = ctx.model_axis
    sizes = ctx.axis_sizes
    msize = sizes.get(maxis, 1)
    if ctx.mesh is None or msize <= 1 or s_max % msize != 0:
        return mla_decode(p, x, cache, pos, cfg)

    wk_b = p["wk_b"].astype(x.dtype).reshape(m.kv_lora_rank, h,
                                             m.qk_nope_head_dim)
    q_lat = jnp.einsum("bqhn,lhn->bqhl", q_nope, wk_b)     # (B,1,H,lora)
    scale = 1.0 / jnp.sqrt(jnp.asarray(
        m.qk_nope_head_dim + m.qk_rope_head_dim, jnp.float32))

    from jax.sharding import PartitionSpec as P
    dsize = 1
    for a in ctx.data_axes:
        dsize *= sizes.get(a, 1)
    dax = ctx.data_axes if len(ctx.data_axes) > 1 else ctx.data_axes[0]
    bspec = dax if (dsize > 1 and b % dsize == 0) else None

    def kernel(ql, qr, cn, krn, cc, krc, slot_, pos_):
        bl, s_l = cc.shape[0], cc.shape[1]
        start = jax.lax.axis_index(maxis) * s_l
        loc = slot_ - start
        inb = (loc >= 0) & (loc < s_l)
        safe = jnp.clip(loc, 0, s_l - 1)
        cur_c = jax.lax.dynamic_slice_in_dim(cc, safe, 1, axis=1)
        cur_k = jax.lax.dynamic_slice_in_dim(krc, safe, 1, axis=1)
        cc = jax.lax.dynamic_update_slice_in_dim(
            cc, jnp.where(inb, cn.astype(cc.dtype), cur_c), safe, axis=1)
        krc = jax.lax.dynamic_update_slice_in_dim(
            krc, jnp.where(inb, krn.astype(krc.dtype), cur_k), safe, axis=1)

        kv_pos = start + jnp.arange(s_l)
        logits = (jnp.einsum("bqhl,bsl->bhqs", ql.astype(cc.dtype), cc,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhr,bsr->bhqs", qr.astype(krc.dtype), krc,
                               preferred_element_type=jnp.float32)) * scale
        logits = jnp.where((kv_pos <= pos_)[None, None, None, :],
                           logits, NEG_INF)
        mx = jnp.max(logits, axis=-1)                      # (B,H,1)
        gm = jax.lax.pmax(mx, maxis)
        pr = jnp.exp(logits - gm[..., None])
        l_tot = jax.lax.psum(jnp.sum(pr, axis=-1), maxis)
        acc = jnp.einsum("bhqs,bsl->bqhl", pr.astype(cc.dtype), cc,
                         preferred_element_type=jnp.float32)
        acc_tot = jax.lax.psum(acc, maxis)
        out_lat = acc_tot / jnp.maximum(l_tot, 1e-30).transpose(
            0, 2, 1)[..., None]
        return out_lat.astype(ql.dtype), cc, krc

    q4 = P(bspec, None, None, None)
    c3 = P(bspec, maxis, None)
    out_lat, c, kr = jax.shard_map(
        kernel, mesh=ctx.mesh,
        in_specs=(q4, q4, P(bspec, None, None), P(bspec, None, None),
                  c3, c3, P(), P()),
        out_specs=(q4, c3, c3), check_vma=False)(
        q_lat, q_rope, c_new, kr_new, cache.c_kv, cache.k_rope, slot, pos)

    wv_b = p["wv_b"].astype(x.dtype).reshape(m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bqhl,lhv->bqhv", out_lat.astype(x.dtype), wv_b)
    out = out.reshape(b, 1, -1) @ p["wo"].astype(x.dtype)
    return out, MLACache(c_kv=c, k_rope=kr)
