"""Profiler trace of the measured window, reduced to plain numbers.

`capture()` wraps the window in `jax.profiler` and reads the `.xplane.pb`
back with `jax.profiler.ProfileData`; `events()` flattens it into plain
records, and everything after that works on those records alone, so a
small recorded trace checks the reduction without a chip.  A window in
which the profiler dropped a device's events (its buffer holds a few
million operations) is refused: busy time would read short.

  busy        union of the intervals in which an operation ran on a
              device, within the window, averaged over the devices used
  idle gaps   the holes in that union, each named by the benchmark's own
              host annotation (`bench.*`) that was open across it
  ops         device seconds by operation name, each less the time of
              the operations nested in it (a while loop's body)
"""
from __future__ import annotations

import contextlib
import shutil
from pathlib import Path
from typing import Iterable, NamedTuple

import jax

WINDOW = "bench.window"          # host annotation around the measured window
OPS_LINE = "XLA Ops"             # a device plane's line of executed operations
DROPPED = "Trace Buffers Dropped"  # a device plane's mark of events it lost
DEVICE_PREFIX = "/device:TPU:"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def is_device_plane(name: str) -> bool:
    return name.startswith(DEVICE_PREFIX)


def op_name(name: str) -> str:
    """A device operation's HLO instruction name (`fusion.12`,
    `amtl_event_batch.3`).  The TPU trace names an operation by the
    instruction's whole text (`%fusion.12 = f32[8]{0} fusion(...), ...`);
    the name is its first word."""
    if name.startswith("%"):
        return name[1:].split(" ", 1)[0]
    return name


def events(xplane: Path) -> list[Event]:
    """Device operations and `bench.*` host annotations of one trace."""
    from jax.profiler import ProfileData

    return flatten(ProfileData.from_file(str(xplane)))


def flatten(data) -> list[Event]:
    """The device operations and `bench.*` host annotations of a
    `jax.profiler.ProfileData`."""
    out = []
    for plane in data.planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            for ev in line.events:
                if device and line.name != OPS_LINE and ev.name != DROPPED:
                    continue
                if not device and not ev.name.startswith("bench."):
                    continue
                name = op_name(ev.name) if device else ev.name
                out.append(Event(plane.name, line.name, name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


@contextlib.contextmanager
def capture(directory: Path):
    """Trace the enclosed code; yields a list that receives the events."""
    shutil.rmtree(directory, ignore_errors=True)
    directory.mkdir(parents=True)
    found: list[Event] = []
    jax.profiler.start_trace(str(directory))
    try:
        yield found
    finally:
        jax.profiler.stop_trace()
        files = sorted(directory.rglob("*.xplane.pb"))
        if files:
            found.extend(events(files[-1]))
        shutil.rmtree(directory, ignore_errors=True)


def _merge(intervals: Iterable[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


class Reduced(NamedTuple):
    window_s: float
    busy_s: float                  # mean over the devices used
    busy_by_device: dict           # plane -> busy seconds
    op_seconds: dict               # op name -> self seconds (all devices)
    gaps: list                     # [(label, seconds)], longest first
    ops: list                      # the device-op events in the window

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_matching(self, pattern) -> float:
        """Device seconds of ops whose name matches `pattern` (a compiled
        regex), summed over the devices.  An op is named after its HLO
        instruction; a Pallas kernel's after the jitted function that
        wraps it (`amtl_event_batch.1`)."""
        return sum(e.dur_ns for e in self.ops if pattern.search(e.name)) * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def reduce(evs: list[Event]) -> Reduced:
    """Busy time, idle gaps and op totals inside the `bench.window`."""
    windows = [e for e in evs if e.name == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    w = max(windows, key=lambda e: e.dur_ns)
    lo, hi = w.start_ns, w.end_ns
    inside = [e for e in evs if is_device_plane(e.plane)
              and e.end_ns > lo and e.start_ns < hi]
    lost = [e for e in inside if e.name == DROPPED and e.line != OPS_LINE]
    if lost:
        raise ValueError(
            f"the profiler dropped {lost[0].plane}'s events for "
            f"{lost[0].dur_ns * 1e-9:.3f} s of the window: trace a shorter one")
    ops = [e for e in inside if e.line == OPS_LINE]
    planes = sorted({e.plane for e in ops})
    busy_by_device, op_seconds, gaps = {}, {}, []
    notes = [e for e in evs if not is_device_plane(e.plane)
             and e.name != WINDOW]
    for plane in planes:
        merged = _merge((max(e.start_ns, lo), min(e.end_ns, hi))
                        for e in ops if e.plane == plane)
        busy_by_device[plane] = sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for se in merged for x in se] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_label(notes, s, e), (e - s) * 1e-9))
    for plane in planes:
        stack: list[Event] = []       # the ops open at this op's start
        for e in sorted((e for e in ops if e.plane == plane),
                        key=lambda e: (e.start_ns, -e.dur_ns)):
            while stack and stack[-1].end_ns <= e.start_ns:
                stack.pop()
            sec = (min(e.end_ns, hi) - max(e.start_ns, lo)) * 1e-9
            op_seconds[e.name] = op_seconds.get(e.name, 0.0) + sec
            if stack and e.end_ns <= stack[-1].end_ns:
                op_seconds[stack[-1].name] -= sec
            stack.append(e)
    busy = (sum(busy_by_device.values()) / len(planes)) if planes else 0.0
    gaps.sort(key=lambda g: -g[1])
    return Reduced((hi - lo) * 1e-9, busy, busy_by_device, op_seconds, gaps,
                   ops)


def _label(notes: list[Event], s: float, e: float) -> str:
    """The innermost host annotation open over most of the gap [s, e]."""
    best, best_key = "host (no annotation)", None
    for n in notes:
        overlap = min(n.end_ns, e) - max(n.start_ns, s)
        if overlap <= 0.5 * (e - s):
            continue
        key = n.dur_ns
        if best_key is None or key < best_key:
            best, best_key = n.name, key
    return best
