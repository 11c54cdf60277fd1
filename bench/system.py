"""Builds the system under test from a configuration, through the
program's public entry point `make_engine`."""
from __future__ import annotations

import jax.numpy as jnp

from bench import data


def solver(cfg: dict):
    """The program's AMTLConfig for the configuration."""
    from repro.core import AMTLConfig

    return AMTLConfig(
        eta=cfg["eta"], eta_k=data.eta_k(cfg), tau=cfg["tau"],
        engine=cfg["engine"], event_batch=cfg["event_batch"],
        prox_every=cfg["prox_every"], prox_rank=cfg["prox_rank"],
        batch_size=cfg["batch_size"])


def problem(cfg: dict, xs, ys, counts):
    from repro.core import MTLProblem

    return MTLProblem(xs, ys, cfg["loss"], cfg["reg"], cfg["lam"],
                      jnp.asarray(counts, jnp.int32))


def engine(cfg: dict, prob):
    from repro.core import make_engine

    return make_engine(prob, solver(cfg))


def zeros(cfg: dict):
    return jnp.zeros((cfg["dim"], cfg["num_tasks"]), jnp.float32)
