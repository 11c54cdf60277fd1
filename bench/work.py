"""Operations and bytes the algorithm requires, counted from shapes.

"Required" is what the mathematics of an event needs, whatever
implements it: the b selected rows of a writer, not the strip a kernel
reads; the B columns a batch of events touches, not all of V.  A later
change that stops reading more than this moves the shares honestly.
Counts are f32 (4 bytes an element); a multiply-add is two operations.
"""
from __future__ import annotations

from typing import NamedTuple

F32 = 4


class Work(NamedTuple):
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)

    def least_seconds(self, peaks: dict) -> float:
        """Roofline time: the larger of compute and HBM time at peak."""
        return max(self.flops / peaks["bf16_flops_per_s"],
                   self.bytes / peaks["hbm_bytes_per_s"])


def sampled_grad(b: int, d: int) -> Work:
    """One event's (n_t/b) 2 X_S^T (X_S w - y_S): X_S w and X_S^T r are
    2 b d each; reads X_S, y_S and w, writes g."""
    return Work(4.0 * b * d, F32 * (b * d + b + 2 * d))


def column_update(d: int, events: int) -> Work:
    """`events` fused KM column steps v + eta_k (p - eta g - v): 5
    operations an element; reads v, p and g columns, writes the new
    column and the undo-log column."""
    return Work(5.0 * d * events, F32 * 5 * d * events)


def prox_refresh(d: int, t: int, rank: int) -> Work:
    """Randomized SVT of a (d, t) iterate at sketch width p = rank + 8:
    sketch W Omega, core Q^T W and reconstruction (QU s) V^T at 2 d t p
    each; Householder QR of (d, p) 4 d p^2; SVD of the (p, t) core about
    14 t p^2 (Golub-Kahan); reads W twice, writes P once."""
    p = min(rank + 8, d, t)
    flops = 3 * 2.0 * d * t * p + 4.0 * d * p * p + 14.0 * t * p * p
    return Work(flops, F32 * (3 * d * t + 2 * d * p + 2 * p * t))


def engine_events(cfg: dict, events: int) -> Work:
    """All required work of `events` batch-engine events of one
    configuration: a prox refresh every `prox_every` events, a sampled
    gradient and a column update per event."""
    d, t = cfg["dim"], cfg["num_tasks"]
    refreshes = events / cfg["prox_every"]
    return (prox_refresh(d, t, cfg["prox_rank"]) * refreshes
            + sampled_grad(cfg["batch_size"], d) * events
            + column_update(d, events))
