"""Seeded inputs: the writers' row counts, the store, the keys.

The store is made on the device in one jitted call from the seed (a copy
of `chip_smoke.py`'s `_lowrank_data`, with +-1 labels): rows x ~ N(0, 1/d),
labels y = sign(x . w*_t + noise) over a rank-`truth_rank` ground truth,
rows past a writer's count zeroed.  The row counts are one fixed multiset
per configuration (normal quantiles of the source's mean and spread,
clipped), which the seed only permutes over the writers: every seed does
the same amount of work.
"""
from __future__ import annotations

import functools
from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int) -> jax.Array:
    """PRNG key of a seed of any size (PRNGKey alone keeps 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def keys(seed: int) -> dict[str, jax.Array]:
    k = base_key(seed)
    return {name: jax.random.fold_in(k, i)
            for i, name in enumerate(("data", "engine"))}


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def row_count_multiset(cfg: dict) -> np.ndarray:
    """(T,) sorted row counts: normal quantiles, rounded and clipped."""
    t = cfg["num_tasks"]
    dist = NormalDist(cfg["rows_mean"], cfg["rows_std"])
    q = [dist.inv_cdf((i + 0.5) / t) for i in range(t)]
    return np.clip(np.rint(q), 1, cfg["max_rows_per_task"]).astype(np.int32)


def row_counts(cfg: dict, seed: int) -> np.ndarray:
    """The multiset, permuted over the writers by the seed."""
    return row_count_multiset(cfg)[host_rng(seed, 1).permutation(
        cfg["num_tasks"])]


@functools.partial(jax.jit, static_argnames=("t", "n", "d", "rank"))
def lowrank_store(key, counts, noise, *, t: int, n: int, d: int, rank: int):
    """xs (t, n, d), ys (t, n) with rows >= counts[t] zeroed, and w* (d, t)."""
    kx, ku, kv, ke = jax.random.split(key, 4)
    w_true = (jax.random.normal(ku, (d, rank)) @ jax.random.normal(kv, (rank, t))
              / jnp.sqrt(float(rank)))
    valid = jnp.arange(n)[None, :] < counts[:, None]
    xs = jnp.where(valid[..., None],
                   jax.random.normal(kx, (t, n, d)) / jnp.sqrt(float(d)), 0.0)
    score = (jnp.einsum("tnd,dt->tn", xs, w_true,
                        precision=jax.lax.Precision.HIGHEST)
             + noise * jax.random.normal(ke, (t, n)))
    ys = jnp.where(valid, jnp.where(score >= 0, 1.0, -1.0), 0.0)
    return xs, ys, w_true


def store(cfg: dict, seed: int):
    """(xs, ys, counts, w*) on the default device for the configuration."""
    counts = row_counts(cfg, seed)
    xs, ys, w_true = lowrank_store(
        keys(seed)["data"], jnp.asarray(counts), jnp.float32(cfg["label_noise"]),
        t=cfg["num_tasks"], n=cfg["capacity"], d=cfg["dim"],
        rank=cfg["truth_rank"])
    return xs, ys, counts, w_true


def eta_k(cfg: dict) -> float:
    """Theorem 1's KM relaxation cap c / (2 tau / sqrt(T) + 1)."""
    return cfg["eta_k_c"] / (2.0 * cfg["tau"] / cfg["num_tasks"] ** 0.5 + 1.0)

