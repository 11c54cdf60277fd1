"""Mixture-of-Experts FFN with expert-parallel all_to_all dispatch.

Two execution paths sharing identical routing/capacity semantics:

* `dense` — dropless reference: every expert runs on every token, combined
  with the top-k mask.  Exact; used for smoke tests / single-host runs and
  as the oracle the EP path is tested against.

* `ep` (shard_map) — production path, two sharding modes:

  - full-EP (DeepSeek: 256 experts on a 16x16 pod slice): experts spread
    over ('data','model'); tokens are capacity-dispatched into per-chip
    buffers and exchanged with one `all_to_all` spanning both axes — the
    paper's server<->node star topology reincarnated as an ICI collective.
    Across pods, experts are replicated and gradients sync over the DCN
    'pod' axis.

  - model-EP + FSDP gather (DBRX: 16 experts, 16-wide model axis): the
    expert dim shards over 'model' and the expert FFN dim over 'data'
    (ZeRO-3 style); each chip all-gathers its resident experts' FFN shards
    over 'data' just-in-time, and the token all_to_all stays inside the
    'model' axis (zero cross-row token traffic).

Capacity-based dropping (cf=1.25 default): tokens over per-expert capacity
fall back to the shared-expert/residual path only.  The router is softmax
top-k with a Switch-style load-balance auxiliary loss (separable per task
shard => AMTL-compatible, DESIGN.md §4).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import activate, dense_init, is_gated

Array = jax.Array


class ParallelCtx(NamedTuple):
    """Mesh context threaded through model apply fns."""
    mesh: Optional[jax.sharding.Mesh] = None
    data_axes: tuple[str, ...] = ("data",)   # batch axes (may include 'pod')
    model_axis: str = "model"
    ep_data_axis: str = "data"               # intra-pod data axis for EP

    @property
    def axis_sizes(self) -> dict[str, int]:
        if self.mesh is None:
            return {}
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    def model_size(self) -> int:
        return self.axis_sizes.get(self.model_axis, 1)

    def ep_data_size(self) -> int:
        return self.axis_sizes.get(self.ep_data_axis, 1)


def moe_mode(cfg: ArchConfig, ctx: ParallelCtx) -> str:
    """'full' (experts over data+model) or 'model' (model-EP, FSDP over data)."""
    e = cfg.moe.num_experts
    n_full = ctx.ep_data_size() * ctx.model_size()
    if e % n_full == 0:
        return "full"
    if e % ctx.model_size() == 0:
        return "model"
    raise ValueError(f"{e} experts incompatible with mesh {ctx.axis_sizes}")


def init_moe(key: Array, cfg: ArchConfig, dtype) -> dict:
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_expert, m.num_experts
    ks = jax.random.split(key, 7)
    p = {"router": dense_init(ks[0], (d, e), jnp.float32),
         "w_in": dense_init(ks[1], (e, d, f), dtype),
         "w_out": dense_init(ks[2], (e, f, d), dtype)}
    if is_gated(cfg.activation):
        p["w_gate"] = dense_init(ks[3], (e, d, f), dtype)
    if m.num_shared:
        fs = f * m.num_shared
        p["shared_in"] = dense_init(ks[4], (d, fs), dtype)
        p["shared_out"] = dense_init(ks[5], (fs, d), dtype)
        if is_gated(cfg.activation):
            p["shared_gate"] = dense_init(ks[6], (d, fs), dtype)
    return p


def _router(router_w: Array, x2: Array, m) -> tuple[Array, Array, Array]:
    """x2: (N, D) -> (topk weights (N,k), topk idx (N,k), aux loss)."""
    logits = x2.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                   # (N, E)
    w, idx = jax.lax.top_k(probs, m.top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)                # renormalize
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    e = probs.shape[-1]
    occupancy = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=1), axis=0)
    importance = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(occupancy * importance)
    return w.astype(x2.dtype), idx, aux


def _expert_ffn(x: Array, w_in: Array, w_out: Array,
                w_gate: Optional[Array], activation: str) -> Array:
    """x: (E, C, D) tokens grouped per expert; weights (E, D, F)/(E, F, D)."""
    up = jnp.einsum("ecd,edf->ecf", x, w_in.astype(x.dtype))
    gate = (jnp.einsum("ecd,edf->ecf", x, w_gate.astype(x.dtype))
            if w_gate is not None else None)
    h = activate(activation, up, gate)
    return jnp.einsum("ecf,efd->ecd", h, w_out.astype(x.dtype))


def _shared_expert(sh_in: Array, sh_out: Array, sh_gate: Optional[Array],
                   x: Array, activation: str) -> Array:
    up = x @ sh_in.astype(x.dtype)
    gate = x @ sh_gate.astype(x.dtype) if sh_gate is not None else None
    h = activate(activation, up, gate)
    return h @ sh_out.astype(x.dtype)


# ---------------------------------------------------------------------------
# dense (dropless oracle) path
# ---------------------------------------------------------------------------

def moe_dense(p: dict, x: Array, cfg: ArchConfig) -> tuple[Array, Array]:
    """x: (B, S, D).  Every expert runs on every token (smoke/oracle)."""
    m = cfg.moe
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    w, idx, aux = _router(p["router"], x2, m)
    gates = jnp.zeros((b * s, m.num_experts), x.dtype)
    gates = jax.vmap(lambda g, i, ww: g.at[i].set(ww))(gates, idx, w)
    all_out = _expert_ffn(jnp.broadcast_to(x2[None], (m.num_experts, b * s, d)),
                          p["w_in"], p["w_out"], p.get("w_gate"),
                          cfg.activation)                     # (E, N, D)
    y = jnp.einsum("ne,end->nd", gates, all_out)
    if m.num_shared:
        y = y + _shared_expert(p["shared_in"], p["shared_out"],
                               p.get("shared_gate"), x2, cfg.activation)
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# capacity dispatch helpers
# ---------------------------------------------------------------------------

def _capacity(n_tokens: int, top_k: int, n_dest: int, cf: float) -> int:
    cap = int(math.ceil(n_tokens * top_k / n_dest * cf))
    return max(cap, 1)


def _dispatch_indices(dest: Array, n_dest: int, cap: int):
    """Slot assignment with capacity dropping.  dest: (N*k,) chip ids.
    Returns (slot (N*k,), keep (N*k,))."""
    onehot = jax.nn.one_hot(dest, n_dest, dtype=jnp.int32)    # (N*k, n_dest)
    ranks = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.take_along_axis(ranks, dest[:, None], axis=1)[:, 0]
    keep = slot < cap
    return slot, keep


# ---------------------------------------------------------------------------
# expert-parallel shard_map path
# ---------------------------------------------------------------------------

def moe_ep(p: dict, x: Array, cfg: ArchConfig, ctx: ParallelCtx,
           token_spec: P) -> tuple[Array, Array]:
    """Expert-parallel MoE via all_to_all.  x: (B, S, D) global view."""
    m = cfg.moe
    mesh = ctx.mesh
    sizes = ctx.axis_sizes
    msize = ctx.model_size()
    dsize = ctx.ep_data_size()
    mode = moe_mode(cfg, ctx)

    if mode == "full":
        ep_axes: tuple[str, ...] = (ctx.ep_data_axis, ctx.model_axis)
        n_chips = dsize * msize
        expert_spec = P((ctx.ep_data_axis, ctx.model_axis), None, None)
        fsdp_gather = False
    else:  # model-EP, FFN dim FSDP'd over data, gathered just-in-time
        ep_axes = (ctx.model_axis,)
        n_chips = msize
        fsdp_gather = dsize > 1 and m.d_expert % dsize == 0
        expert_spec = (P(ctx.model_axis, None, ctx.ep_data_axis)
                       if fsdp_gather else P(ctx.model_axis, None, None))
    e_loc = m.num_experts // n_chips
    out_fsdp_spec = (P(ctx.model_axis, ctx.ep_data_axis, None)
                     if (mode == "model" and fsdp_gather)
                     else expert_spec)

    has_gate = "w_gate" in p
    has_shared = bool(m.num_shared)
    has_shared_gate = "shared_gate" in p
    # shared expert: keep the model-axis TP sharding INSIDE the kernel
    # (partial FFN + psum) — a replicated in_spec would make GSPMD
    # all-gather the shared weights on every layer (57 ms/step of pure
    # weight gather on deepseek decode; EXPERIMENTS.md §Perf).
    fs = m.d_expert * m.num_shared if has_shared else 0
    # TP-psum is only valid when every model shard sees the SAME tokens —
    # with seq-sharded dispatch (train) each shard holds different tokens
    # and the partial-sum would mix them; fall back to the weight gather.
    toks_model_sharded = any(
        ctx.model_axis in ((e,) if not isinstance(e, tuple) else e)
        for e in (token_spec or ()) if e)
    shared_tp = (has_shared and msize > 1 and fs % msize == 0
                 and not toks_model_sharded)
    sh_in_spec = P(None, ctx.model_axis) if shared_tp else P(None, None)
    sh_out_spec = P(ctx.model_axis, None) if shared_tp else P(None, None)

    in_specs = [expert_spec, out_fsdp_spec, P(None, None), token_spec]
    args = [p["w_in"], p["w_out"], p["router"], x]
    if has_gate:
        in_specs.insert(1, expert_spec)
        args.insert(1, p["w_gate"])
    if has_shared:
        in_specs += [sh_in_spec, sh_out_spec]
        args += [p["shared_in"], p["shared_out"]]
        if has_shared_gate:
            in_specs.append(sh_in_spec)
            args.append(p["shared_gate"])

    def kernel(*ops):
        it = iter(ops)
        w_in = next(it)
        w_gate = next(it) if has_gate else None
        w_out = next(it)
        router = next(it)
        x_loc = next(it)
        sh_in = next(it) if has_shared else None
        sh_out = next(it) if has_shared else None
        sh_gate = next(it) if has_shared_gate else None

        bl, sl, d = x_loc.shape
        n_loc = bl * sl
        x2 = x_loc.reshape(n_loc, d)
        w, idx, aux = _router(router, x2, m)

        dest = (idx // e_loc).reshape(-1)                   # (N*k,) chip ids
        cap = _capacity(n_loc, m.top_k, n_chips, m.capacity_factor)

        # With FFN-dim-FSDP'd experts there are two ways to apply an
        # expert (EXPERIMENTS.md §Perf, dbrx decode):
        #   gather-weights: all_gather the (E_loc, D, F) shards, compute
        #     locally — right when the token batch outweighs the weights
        #     (training);
        #   tp-compute: keep the F-shard, compute the partial FFN, psum
        #     the (tokens, D) output over the data axis — right when the
        #     tokens are tiny (decode: ~3 MB of activations vs ~400 MB of
        #     gathered dbrx expert weights per layer).
        tp_compute = False
        if fsdp_gather:
            # tp-compute ships ~3x the data-gathered token set; weight
            # gather materializes the FULL (E_loc, D, F) weights on every
            # device — compare against that result, not the shard
            tok_bytes = n_chips * cap * d * 2
            wfull_bytes = (e_loc * d * m.d_expert
                           * (3 if has_gate else 2) * 2)
            tp_compute = 3 * dsize * tok_bytes < wfull_bytes
        if fsdp_gather and not tp_compute:  # ZeRO-3 weight gather
            w_in = jax.lax.all_gather(w_in, ctx.ep_data_axis, axis=2,
                                      tiled=True)
            if w_gate is not None:
                w_gate = jax.lax.all_gather(w_gate, ctx.ep_data_axis, axis=2,
                                            tiled=True)
            w_out = jax.lax.all_gather(w_out, ctx.ep_data_axis, axis=1,
                                       tiled=True)
        slot, keep = _dispatch_indices(dest, n_chips, cap)
        tok_idx = jnp.repeat(jnp.arange(n_loc), m.top_k)
        safe_slot = jnp.where(keep, slot, cap - 1)

        send = jnp.zeros((n_chips, cap, d), x2.dtype)
        send = send.at[dest, safe_slot].add(
            jnp.where(keep[:, None], x2[tok_idx], 0.0))
        local_eid = (idx % e_loc).reshape(-1)
        eid_send = jnp.zeros((n_chips, cap), jnp.int32)
        eid_send = eid_send.at[dest, safe_slot].max(
            jnp.where(keep, local_eid, 0))

        recv = jax.lax.all_to_all(send, ep_axes, split_axis=0,
                                  concat_axis=0, tiled=True)
        eid_recv = jax.lax.all_to_all(
            eid_send[..., None].astype(jnp.float32), ep_axes,
            split_axis=0, concat_axis=0, tiled=True)[..., 0]
        recv2 = recv.reshape(n_chips * cap, d)
        eid2 = eid_recv.reshape(n_chips * cap).astype(jnp.int32)

        if e_loc > 1:
            sel = jax.nn.one_hot(eid2, e_loc, dtype=recv2.dtype)
            grouped = jnp.einsum("md,me->emd", recv2, sel)
        else:
            grouped = recv2[None]
        if tp_compute:
            # tokens differ per data row in "model" mode: gather the
            # union over data, compute the F-shard partial FFN on it,
            # psum, then slice back this row's tokens.
            g2 = jax.lax.all_gather(grouped, ctx.ep_data_axis, axis=1,
                                    tiled=True)     # (E, dsize*M, D)
            o2 = _expert_ffn(g2, w_in, w_out, w_gate, cfg.activation)
            o2 = jax.lax.psum(o2, ctx.ep_data_axis)
            mstart = jax.lax.axis_index(ctx.ep_data_axis) \
                * grouped.shape[1]
            out_g = jax.lax.dynamic_slice_in_dim(o2, mstart,
                                                 grouped.shape[1], axis=1)
        else:
            out_g = _expert_ffn(grouped, w_in, w_out, w_gate,
                                cfg.activation)
        back2 = (jnp.einsum("emd,me->md", out_g, sel) if e_loc > 1
                 else out_g[0])

        ret = jax.lax.all_to_all(back2.reshape(n_chips, cap, d), ep_axes,
                                 split_axis=0, concat_axis=0, tiled=True)
        flat = ret.reshape(n_chips * cap, d)
        lin = dest * cap + safe_slot
        contrib = jnp.where(keep[:, None], flat[lin], 0.0)
        y = jnp.zeros((n_loc, d), x2.dtype)
        y = y.at[tok_idx].add(contrib * w.reshape(-1)[:, None])
        if has_shared:
            y_sh = _shared_expert(sh_in, sh_out, sh_gate, x2,
                                  cfg.activation)
            if shared_tp:   # partial over the F cut -> full output
                y_sh = jax.lax.psum(y_sh, ctx.model_axis)
            y = y + y_sh
        aux = jax.lax.pmean(aux, tuple(mesh.axis_names))
        return y.reshape(bl, sl, d), aux

    y, aux = jax.shard_map(kernel, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=(token_spec, P()), check_vma=False)(*args)
    return y, aux


def moe_apply(p: dict, x: Array, cfg: ArchConfig, ctx: ParallelCtx,
              token_spec: Optional[P] = None) -> tuple[Array, Array]:
    """Dispatch to dense oracle (no mesh) or EP shard_map path."""
    if ctx.mesh is None:
        return moe_dense(p, x, cfg)
    return moe_ep(p, x, cfg, ctx, token_spec if token_spec is not None
                  else P(ctx.data_axes, None, None))
