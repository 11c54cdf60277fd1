"""Param/activation/cache -> PartitionSpec rule engine.

Conventions (DESIGN.md §5):
  * tensor parallelism over 'model': projections feature-sharded, FFN
    hidden sharded, embeddings vocab-sharded, MoE experts expert-sharded
    (full-EP over ('data','model') when divisible, else model-EP with the
    expert FFN dim FSDP'd over 'data');
  * ZeRO-1: optimizer-state leaves additionally sharded over the data axes
    on the largest free divisible dim;
  * scanned groups carry a leading stack dim that is never sharded;
  * KV caches: batch over data axes, sequence over 'model';
  * recurrent states: heads over 'model' when divisible, else batch-only.

Every rule validates divisibility against the actual mesh and falls back to
replication per-dim, so the same engine serves the 1-CPU smoke tests and
the 512-chip dry run.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig


# ------------------------------------------------------- AMTL task axis ---

TASK_AXIS = "tasks"


def task_shard_specs(axis: str = TASK_AXIS) -> dict[str, P]:
    """PartitionSpecs for the task-sharded AMTL engine (engine='sharded').

    The engine partitions the T task columns of the (d, T) iterate over a
    1-D `axis` mesh; everything it touches falls into four placement
    classes (keys of the returned dict):

      per_task   — leading-dim-T leaves: xs (T, n, d), ys (T, n), the
                   delay-history rows (T, window)/(T,)
      columns    — (d, T) iterates: tasks on the trailing dim
      per_shard  — (n_shards, ...) leaves: each shard's private undo ring
      replicated — the serial PRNG chain state (key, ptr, event counter)
                   and the global-task-id ring every shard replays

    The rank-distributed randomized SVT (prox_mode='distributed',
    `prox.svt_randomized_dist`) adds no new placement class: its (d, p)
    sketch partial is psum'd to replicated INSIDE shard_map, its (p,
    n_local) projected-core block is gathered to replicated, and its
    reconstruction — like the prox cache that carries it between decoupled
    refreshes — is `columns` (see `prox_cache_spec`).
    """
    return {
        "per_task": P(axis),
        "columns": P(None, axis),
        "per_shard": P(axis),
        "replicated": P(),
    }


def prox_cache_spec(prox_mode: str, carried: bool,
                    axis: str = TASK_AXIS) -> P:
    """Placement of the sharded engine's prox cache (`p_cache`).

    The replicated prox broadcasts one (d, T) result to every shard, so
    its cache is replicated.  The rank-distributed prox never materializes
    the full result — each shard reconstructs only its own (d, n_local)
    columns — so a CARRIED cache (decoupled cadence, prox_every >
    event_batch) is column-sharded like the iterate.  At the aligned
    cadence nothing is carried and the (0, 0) stub stays replicated in
    either mode (sharding a 0-width stub buys nothing and the stub rides
    the loop carry untouched).
    """
    if prox_mode == "distributed" and carried:
        return P(None, axis)
    return P()


# leaf-name -> raw spec (for the *unstacked* trailing dims)
_COL = ("wq", "wk", "wv", "wg", "wr", "ck", "w_in", "w_gate", "shared_in",
        "shared_gate", "wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "in_proj",
        "conv_w", "feat_proj", "unembed", "proj")
_ROW = ("wo", "out_proj", "cv", "w_out", "shared_out")
_REPL = ("router", "scale", "bias", "mask_emb", "A_log", "D", "dt_bias",
         "u", "mix", "mix_ffn", "w0", "w_lora_a", "w_lora_b", "gate",
         "lora_q_a", "lora_q_b", "lora_o_a", "lora_o_b", "cr")


def _moe_specs(name: str, mode: str, fsdp: bool) -> tuple:
    """Expert-stacked weights (E, D, F) / (E, F, D)."""
    if mode == "full":
        return (("data", "model"), None, None)
    if name in ("w_in", "w_gate"):
        return ("model", None, "data" if fsdp else None)
    return ("model", "data" if fsdp else None, None)   # w_out


def moe_fsdp(cfg: ArchConfig, axis_sizes: dict[str, int]) -> bool:
    dsize = axis_sizes.get("data", 1)
    return (cfg.moe is not None and dsize > 1
            and cfg.moe.d_expert % dsize == 0)


def moe_sharding_mode(cfg: ArchConfig, axis_sizes: dict[str, int]) -> str:
    e = cfg.moe.num_experts
    n_full = axis_sizes.get("data", 1) * axis_sizes.get("model", 1)
    if e % n_full == 0:
        return "full"
    if e % axis_sizes.get("model", 1) == 0:
        return "model"
    raise ValueError(f"experts={e} incompatible with mesh {axis_sizes}")


def _validate(spec: tuple, shape: tuple[int, ...],
              axis_sizes: dict[str, int]) -> P:
    """Drop any axis assignment that does not divide the dim."""
    out = []
    for dim, ax in zip(shape, spec):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = math.prod(axis_sizes.get(a, 1) for a in axes)
        out.append(ax if (size > 1 and dim % size == 0) else None)
    # pad spec if shorter than shape
    out += [None] * (len(shape) - len(out))
    return P(*out)


def param_pspec(path: tuple[str, ...], shape: tuple[int, ...],
                cfg: ArchConfig, axis_sizes: dict[str, int]) -> P:
    stacked = bool(path) and path[0].startswith("group")
    names = set(path)
    leaf = path[-1]
    trailing = shape[1:] if stacked else shape

    if leaf == "embed":
        raw = ("model", None)
    elif "moe" in names and leaf in ("w_in", "w_gate", "w_out") \
            and len(trailing) == 3:
        mode = moe_sharding_mode(cfg, axis_sizes)
        raw = _moe_specs(leaf, mode, moe_fsdp(cfg, axis_sizes))
    elif leaf in _REPL or len(trailing) <= 1:
        raw = tuple(None for _ in trailing)
    elif leaf in _ROW:
        raw = ("model",) + (None,) * (len(trailing) - 1)
    elif leaf in _COL:
        raw = (None,) * (len(trailing) - 1) + ("model",)
    else:
        raw = tuple(None for _ in trailing)

    if stacked:
        raw = (None,) + tuple(raw)
    return _validate(raw, shape, axis_sizes)


def param_pspecs(params: Any, cfg: ArchConfig,
                 axis_sizes: dict[str, int]) -> Any:
    def rule(path, leaf):
        names = tuple(_key_str(k) for k in path)
        return param_pspec(names, leaf.shape, cfg, axis_sizes)
    return jax.tree_util.tree_map_with_path(rule, params)


def _key_str(k) -> str:
    return getattr(k, "key", getattr(k, "name", str(k)))


# ---------------------------------------------------------------- ZeRO-1 ---

def with_zero(spec: P, shape: tuple[int, ...], axis_sizes: dict[str, int],
              zero_axes: tuple[str, ...] = ("data",)) -> P:
    """Shard the largest free divisible dim over the data axes (ZeRO-1)."""
    zsize = math.prod(axis_sizes.get(a, 1) for a in zero_axes)
    if zsize <= 1:
        return spec
    used = set()
    for e in spec:
        for a in (e if isinstance(e, tuple) else (e,)):
            if a:
                used.add(a)
    if any(a in used for a in zero_axes):
        return spec
    best, best_dim = -1, -1
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, (dim, e) in enumerate(zip(shape, entries)):
        if e is None and dim % zsize == 0 and dim > best_dim:
            best, best_dim = i, dim
    if best < 0:
        return spec
    entries[best] = zero_axes if len(zero_axes) > 1 else zero_axes[0]
    return P(*entries)


def opt_state_pspecs(opt_state: Any, params: Any, cfg: ArchConfig,
                     axis_sizes: dict[str, int],
                     zero_axes: tuple[str, ...] = ("data",)) -> Any:
    """Derive optimizer-state pspecs from the param rules + ZeRO-1.

    Handles {master,mu,nu} (same shape as param) and adafactor {vr,vc}
    (row/col reductions of the param shape).
    """
    pspecs = param_pspecs(params, cfg, axis_sizes)
    flat_p = dict(_flatten_with_paths(pspecs))

    def rule(path, leaf):
        names = tuple(_key_str(k) for k in path)
        # first component is the optimizer-state kind for dict-of-trees
        # layouts ({master: {...}}); for adafactor it's the param path with
        # the kind as the LAST component.
        if names[0] in ("master", "mu", "nu"):
            base = flat_p.get(names[1:])
            kind = names[0]
        else:
            base = flat_p.get(names[:-1])
            kind = names[-1]
        if base is None:
            return P()
        entries = list(base) + [None] * (len(leaf.shape) - len(base))
        if kind in ("master", "mu", "nu", "v"):
            spec = P(*entries[:len(leaf.shape)])
            return with_zero(spec, leaf.shape, axis_sizes, zero_axes)
        if kind == "vr":       # param.shape[:-1]
            return P(*entries[:len(leaf.shape)])
        if kind == "vc":       # param.shape[:-2] + param.shape[-1:]
            ent = entries[:max(len(leaf.shape) - 1, 0)] + [entries[-1]] \
                if len(entries) >= 2 else entries
            ent = (ent + [None] * len(leaf.shape))[:len(leaf.shape)]
            return P(*ent)
        return P(*entries[:len(leaf.shape)])

    return jax.tree_util.tree_map_with_path(rule, opt_state)


def _flatten_with_paths(tree):
    flat = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, P))[0]
    return [(tuple(_key_str(k) for k in path), leaf) for path, leaf in flat]


# ----------------------------------------------------------- batch/caches --

def batch_pspec(name: str, shape: tuple[int, ...],
                axis_sizes: dict[str, int],
                data_axes: tuple[str, ...] = ("data",)) -> P:
    dsize = math.prod(axis_sizes.get(a, 1) for a in data_axes)
    daxis = data_axes if len(data_axes) > 1 else data_axes[0]
    b_ok = shape and shape[0] % dsize == 0 and dsize > 1
    first = daxis if b_ok else None
    return P(first, *([None] * (len(shape) - 1)))


def cache_pspec(path: tuple[str, ...], shape: tuple[int, ...],
                axis_sizes: dict[str, int],
                data_axes: tuple[str, ...] = ("data",)) -> P:
    """KV caches (n,B,S,H,hd)/(n,B,S,r): B->data, S->model.
    States conv/ssm/wkv/x_prev: B->data, heads->model when divisible."""
    leaf = path[-1]
    msize = axis_sizes.get("model", 1)
    dsize = math.prod(axis_sizes.get(a, 1) for a in data_axes)
    daxis = data_axes if len(data_axes) > 1 else data_axes[0]

    def dshard(dim):
        return daxis if (dsize > 1 and dim % dsize == 0) else None

    def mshard(dim):
        return "model" if (msize > 1 and dim % msize == 0) else None

    if leaf in ("k", "v", "c_kv", "k_rope",
                "k_scale", "v_scale"):             # (n, B, S, ...) stacked
        spec = [None, dshard(shape[1]), mshard(shape[2])]
        spec += [None] * (len(shape) - 3)
        return P(*spec)
    if leaf in ("ssm", "wkv"):                     # (n, B, H, ...)
        spec = [None, dshard(shape[1]), mshard(shape[2])]
        spec += [None] * (len(shape) - 3)
        return P(*spec)
    if leaf == "conv":                             # (n, B, kw, conv_dim)
        return P(None, dshard(shape[1]), None, mshard(shape[3]))
    if leaf.startswith("x_prev"):                  # (n, B, D)
        return P(None, dshard(shape[1]), mshard(shape[2]))
    return P(*([None] * len(shape)))


def cache_pspecs(cache: Any, axis_sizes: dict[str, int],
                 data_axes: tuple[str, ...] = ("data",)) -> Any:
    def rule(path, leaf):
        names = tuple(_key_str(k) for k in path)
        return cache_pspec(names, leaf.shape, axis_sizes, data_axes)
    return jax.tree_util.tree_map_with_path(rule, cache)


def to_named(tree_of_pspecs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_of_pspecs,
                        is_leaf=lambda x: isinstance(x, P))
