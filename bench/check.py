"""The comparisons that decide `correct`, against bench/reference.py.

Learner cells: the program's iterate after each checked `engine.run`
call against the reference's after the same events.

    v_gap      max over the calls of max|V - R| / max|R|

The control (bench/control.py) is the reference itself in the program's
place, each product in three bfloat16 passes instead of f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, reference


def rel_gap(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def reference_iterates(cell, seed: int, store, k: int, calls: int,
                       precision: str = "highest") -> list[np.ndarray]:
    """The reference's iterate after each of `calls` calls of k events."""
    cfg = cell.config
    xs, ys, counts = store
    dev = jax.devices()[0]
    xs, ys = jax.device_put(xs, dev), jax.device_put(ys, dev)
    st = reference.init(jax.device_put(jnp.zeros((cfg["dim"],
                                                  cfg["num_tasks"]),
                                                 jnp.float32), dev),
                        data.keys(seed)["engine"], cfg["tau"])
    out = []
    for _ in range(calls):
        st = reference.run(xs, ys, counts, st, k, cfg, data.eta_k(cfg),
                           precision)
        out.append(np.asarray(st.v))
    return out


def compare(got: list, want: list) -> dict[str, float]:
    if not all(np.all(np.isfinite(g)) for g in got):
        return {"v_gap": float("inf")}
    return {"v_gap": max(rel_gap(g, w) for g, w in zip(got, want,
                                                        strict=True))}


def learn(cell, seed: int, k: int, iterates: list,
          store) -> dict[str, float]:
    want = reference_iterates(cell, seed, store, k, len(iterates))
    return compare(iterates, want)

