"""One benchmark run: device check, set-up, window, check, result line.

A load module (bench/loads/<traffic["load"]>.py) does the cell's
own work through the `Run` object it is handed: it builds the system
under test from the seed, calls `run.window_begins()` at the first timed
instant, measures, then records end-to-end values, the numbers compared
against their limits, and what the per-layer readers need.
"""
from __future__ import annotations

import contextlib
import importlib
import json
import math
import sys
import time
from types import SimpleNamespace
from typing import Any

import jax

from bench import spec, trace

TRACE_DIR = spec.ROOT / ".bench_trace"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def tpu_devices(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips; JAX found "
                            f"{len(devices)}")
    return devices[:chips]


def enable_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed path in
    the checkout, every program in it however fast it compiled, so that
    only a checkout's first run compiles."""
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts traces and backend compiles JAX reports, from `start()` on."""

    def __init__(self):
        self.traces = self.compiles = 0
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, _secs: float, **_kw) -> None:
        if not self._on:
            return
        if name.endswith("jaxpr_trace_duration"):
            self.traces += 1
        elif name.endswith("backend_compile_duration"):
            self.compiles += 1

    def start(self) -> None:
        self.traces = self.compiles = 0
        self._on = True

    def stop(self) -> None:
        self._on = False


class Run:
    """State of one run, shared between the harness and the load module."""

    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 traced: bool, devices: list, t_process: float,
                 control: bool = False):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.traced, self.devices = traced, devices
        self.control = control     # the control in the program's place
        self.t_process = t_process
        self.t_window: float | None = None
        self.values: dict[str, float] = {}     # end-to-end metric values
        self.checks: dict[str, tuple[float, float]] = {}  # name: (value, limit)
        self.info: dict[str, Any] = {}         # printed on an earlier line
        self.layer = SimpleNamespace()         # what per-layer readers need
        self.attempted = self.failed = 0
        self.trace_events: list = []
        self.compiles = CompileCounter()

    def window_begins(self) -> None:
        self.t_window = time.perf_counter()
        self.values["setup_s"] = self.t_window - self.t_process
        self.compiles.start()

    def window_ends(self) -> None:
        self.compiles.stop()
        self.info["window_traces"] = self.compiles.traces
        self.info["window_compiles"] = self.compiles.compiles

    def traced_window(self):
        """Context manager around the measured window: the profiler when
        the run is traced, and the `bench.window` annotation always."""
        stack = contextlib.ExitStack()
        if self.traced:
            self.trace_events = stack.enter_context(trace.capture(TRACE_DIR))
        stack.enter_context(jax.profiler.TraceAnnotation(trace.WINDOW))
        return stack

    def memory_peak(self) -> int:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks) if peaks else 0

    def check(self, name: str, value: float) -> None:
        """Record a compared number against the cell's limit for it."""
        self.checks[name] = (float(value),
                             float(self.cell.limits["limits"][name]))


def _load_module(cell: spec.Cell):
    return importlib.import_module(f"bench.loads.{cell.traffic['load']}")


def execute(cell: spec.Cell, seed: int, seconds: float, traced: bool,
            t_process: float, devices: list | None = None,
            control: bool = False) -> tuple[dict, dict]:
    """Run the cell; returns the result line's object and the run's info
    (printed on the line before).  With `control` the load module puts the
    control in the program's place (bench/control.py)."""
    devices = tpu_devices(cell.chips) if devices is None else devices
    run = Run(cell, seed, seconds, traced, devices, t_process, control)
    _load_module(cell).run(run)
    if run.t_window is None:
        raise RuntimeError("the load module never started its window")
    correct = bool(run.checks) and all(
        math.isfinite(v) and v <= lim for v, lim in run.checks.values())
    correct = correct and run.failed == 0
    dev = devices[0]
    result: dict[str, Any] = {
        "correct": correct,
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": run.info.get("memory_peak_bytes", 0)},
    }
    if traced:
        reduced = trace.reduce(run.trace_events)
        run.layer.trace = reduced
        run.layer.peaks = spec.peaks(dev.device_kind)
        result["device"]["busy_s"] = reduced.busy_s
        result["device"]["window_s"] = reduced.window_s
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"])(run.layer)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
            else:
                # The manifest lists this cell for the metric, so its reader
                # should have found something: a kernel renamed or taken off
                # the path.  Say so; the whole step's engine_mfu still bounds.
                run.info.setdefault("silent_metrics", []).append(m["name"])
                print(f"per-layer metric {m['name']} found nothing to read",
                      file=sys.stderr)
        result["breakdown"] = reduced.breakdown()
    else:
        for m in cell.end_to_end:
            if m["name"] not in run.values:
                raise RuntimeError(f"the load module did not measure {m['name']}")
            result["metrics"][m["name"]] = {"value": run.values[m["name"]],
                                            "unit": m["unit"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in run.checks.items()}
    run.info["correct"] = correct
    print(json.dumps({"info": run.info}), flush=True)
    for k, (v, lim) in run.checks.items():
        print(f"check {k}: {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    return result, run.info
