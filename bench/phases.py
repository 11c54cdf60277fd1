"""Device time by engine phase, and the events the traced calls ran.

The engine names its phases with `jax.named_scope` (`repro.core.amtl`:
`amtl.sample`, `amtl.prox`, `amtl.grad`, `amtl.update`); a device
operation carries its scope path in the `tf_op` stat of the trace's event
metadata (`bench/xplane.py`).  `AMTLEngine.run` opens the host span
`amtl.run` with `num_events`, and the serving platform opens `serve.*`
spans with their counts.

  scope seconds  self seconds of the window's device operations, counted
                 as `trace.reduce` counts them, by the innermost `amtl.*`
                 scope of each operation, "" for none.  An operation with
                 no scope of its own (a while loop, a copy XLA put in)
                 takes the scope the operations nested in it share, else
                 the scope of the operation it is nested in.
  events         `num_events` summed over the `amtl.run` spans that start
                 in the window

A trace of a program without scopes or spans reads no events.

    python3 -m bench.phases --workload <cell> --seed <n> [--calls 8]
        [--tasks T] [--keep <file>.xplane.pb.gz]

traces `--calls` calls of a learner cell's `engine.run` (16 in flight, as
in the cell) on the chip, after as many untraced ones, and prints one
JSON line: µs per event by phase, busy time, events/s traced and
untraced, and the 20 ops with the most self time with their phase.  `--tasks` cuts the cell to T writers (capacity T rows each);
`--keep` writes the raw trace, gzipped.
"""
from __future__ import annotations

import re
import sys
from typing import NamedTuple

from bench import trace, xplane

SCOPES = ("amtl.sample", "amtl.prox", "amtl.grad", "amtl.update")
RUN_SPAN = "amtl.run"
SPAN_PREFIXES = ("amtl.", "serve.")

_SCOPE = re.compile(r"(?:^|/)(amtl\.[A-Za-z_]+)(?=[/:]|$)")


def scope_of(tf_op: str) -> str:
    """The innermost `amtl.*` component of a `tf_op` path, or ""."""
    found = _SCOPE.findall(tf_op)
    return found[-1] if found else ""


class Span(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float
    args: dict


class Phases(NamedTuple):
    scope_seconds: dict     # scope -> self seconds over the devices
    events: int             # num_events of the window's amtl.run spans
    spans: list             # the program's host spans in the window
    op_scope: dict          # op name (`trace.op_name`) -> its scope

    def us_per_event(self, scope: str) -> float | None:
        """Microseconds of device time per event in `scope` ("" for none);
        None where the trace holds no `amtl.run` events."""
        if self.events <= 0:
            return None
        return self.scope_seconds.get(scope, 0.0) * 1e6 / self.events


def spans(data) -> list[Span]:
    """The host spans named `amtl.*` or `serve.*` of a ProfileData, with
    their arguments."""
    out = []
    for plane in data.planes:
        if trace.is_device_plane(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIXES):
                    out.append(Span(ev.name, float(ev.start_ns),
                                    float(ev.duration_ns), dict(ev.stats)))
    return out


def _device_ops(data) -> list[trace.Event]:
    """Device operations under their whole HLO text (the metadata's key),
    and the window annotation."""
    out = []
    for plane in data.planes:
        device = trace.is_device_plane(plane.name)
        for line in plane.lines:
            for ev in line.events:
                if (device and line.name == trace.OPS_LINE) or (
                        not device and ev.name == trace.WINDOW):
                    out.append(trace.Event(plane.name, line.name, ev.name,
                                           float(ev.start_ns),
                                           float(ev.duration_ns)))
    return out


def _nesting(ops: list[trace.Event], lo: float, hi: float):
    """(self seconds, parent index or None) of each op of one plane, in
    `trace.reduce`'s order and by its rule: an op inside the one open
    before it is that op's child, and its time leaves the parent's."""
    self_s, parent, stack = [0.0] * len(ops), [None] * len(ops), []
    for i, e in enumerate(ops):
        while stack and ops[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        sec = (min(e.end_ns, hi) - max(e.start_ns, lo)) * 1e-9
        self_s[i] += sec
        if stack and e.end_ns <= ops[stack[-1]].end_ns:
            self_s[stack[-1]] -= sec
            parent[i] = stack[-1]
        stack.append(i)
    return self_s, parent


def _resolve(own: list[str], parent: list) -> list[str]:
    """Each op's scope: its own, else the one scope of the ops nested in
    it, else its parent's."""
    below: list[set] = [set() for _ in own]
    for i in reversed(range(len(own))):       # children after parents
        if parent[i] is not None:
            below[parent[i]] |= below[i] | ({own[i]} - {""})
    scope = [o or (next(iter(b)) if len(b) == 1 else "")
             for o, b in zip(own, below)]
    for i, p in enumerate(parent):
        if not scope[i] and p is not None:
            scope[i] = scope[p]
    return scope


def read(serialized: bytes) -> Phases:
    """Scope seconds and events of a serialized XSpace's `bench.window`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(serialized)
    evs = _device_ops(data)
    windows = [e for e in evs if e.name == trace.WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {trace.WINDOW!r} annotation")
    w = max(windows, key=lambda e: e.dur_ns)
    lo, hi = w.start_ns, w.end_ns
    tf_ops = xplane.tf_ops(serialized)
    seconds: dict[str, float] = {}
    op_scope: dict[str, str] = {}
    inside = trace.reduce(evs).ops
    for plane in sorted({e.plane for e in inside}):
        ops = sorted((e for e in inside if e.plane == plane),
                     key=lambda e: (e.start_ns, -e.dur_ns))
        self_s, parent = _nesting(ops, lo, hi)
        names = tf_ops.get(plane, {})
        own = [scope_of(names.get(e.name, "")) for e in ops]
        for e, scope, sec in zip(ops, _resolve(own, parent), self_s):
            seconds[scope] = seconds.get(scope, 0.0) + sec
            op_scope.setdefault(trace.op_name(e.name), scope)
    found = [s for s in spans(data) if lo <= s.start_ns < hi]
    events = sum(int(s.args.get("num_events", 0)) for s in found
                 if s.name == RUN_SPAN)
    return Phases(seconds, events, found, op_scope)


def _profile(workload: str, seed: int, calls: int, tasks: int | None,
             keep: str | None) -> dict:
    import gzip
    import shutil
    import time

    import jax
    from jax.profiler import ProfileData

    from bench import harness, spec
    from bench.loads import engine_loop

    cell = spec.cell(workload)
    if tasks:
        cell = cell._replace(config=dict(
            cell.config, num_tasks=tasks, capacity=tasks,
            max_rows_per_task=tasks))
    devices = harness.tpu_devices(cell.chips)
    harness.enable_cache()
    k = engine_loop.events_per_call(cell)
    ahead = cell.traffic["dispatch_ahead"]
    eng, state, _, _ = engine_loop.setup(cell, seed, 1)

    def calls_timed() -> float:
        nonlocal state
        inflight, t0 = [], time.perf_counter()
        for _ in range(calls):
            with jax.profiler.TraceAnnotation("bench.engine_run"):
                state = eng.run(state, None, k)
                inflight.append(state)
                if len(inflight) > ahead:
                    jax.block_until_ready(inflight.pop(0))
        jax.block_until_ready(inflight)
        return time.perf_counter() - t0

    untraced_s = calls_timed()
    out_dir = harness.TRACE_DIR
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(str(out_dir))
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            traced_s = calls_timed()
    finally:
        jax.profiler.stop_trace()
    raw = sorted(out_dir.rglob("*.xplane.pb"))[-1].read_bytes()
    shutil.rmtree(out_dir, ignore_errors=True)
    if keep:
        with open(keep, "wb") as f:
            f.write(gzip.compress(raw))
    ph = read(raw)
    reduced = trace.reduce(trace.flatten(
        ProfileData.from_serialized_xspace(raw)))
    return {
        "workload": workload, "seed": seed, "calls": calls,
        "events_per_call": k, "num_tasks": cell.config["num_tasks"],
        "device": devices[0].device_kind,
        "events": ph.events,
        "us_per_event": {s or "other": ph.us_per_event(s)
                         for s in SCOPES + ("",)},
        "busy_s": reduced.busy_s, "window_s": reduced.window_s,
        "ops_self_s": sum(reduced.op_seconds.values()),
        "events_per_s_untraced": calls * k / untraced_s,
        "events_per_s_traced": calls * k / traced_s,
        "device_ops": [[op, sec, ph.op_scope.get(op) or "other"]
                       for op, sec in reduced.breakdown(20)["device_ops"]],
        "idle_gaps": reduced.breakdown()["idle_gaps"],
    }


def main(argv=None) -> int:
    import argparse
    import json

    from bench import harness, spec

    sys.path.insert(0, str(spec.ROOT / "src"))
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--tasks", type=int, default=None)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    try:
        result = _profile(args.workload, args.seed, args.calls, args.tasks,
                          args.keep)
    except harness.NoAccelerator as e:
        print(f"bench.phases: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
