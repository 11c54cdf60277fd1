"""The reduction from a profiler trace to busy time, idle gaps, op totals
and kernel shares."""
import gzip
import re
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import spec, trace, work

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, name, start, dur, line=None):
    line = line or (trace.OPS_LINE if plane.startswith("/device") else "python")
    return trace.Event(plane, line, name, float(start), float(dur))


HAND = [
    ev(HOST, trace.WINDOW, 1_000, 10_000),
    ev(HOST, "bench.engine_run", 1_000, 6_000),
    ev(HOST, "bench.predict", 8_000, 2_000),
    # device 0: busy [1000, 3000) u [2500, 4000) u [6000, 7000) u [10500, 11000)
    ev(DEV0, "fusion.1", 1_000, 2_000),
    ev(DEV0, "amtl_event_batch.1", 2_500, 1_500),
    ev(DEV0, "fusion.1", 6_000, 1_000),
    ev(DEV0, "fusion.2", 10_500, 1_000),      # runs past the window's end
    ev(DEV0, "fusion.3", 100, 200),           # before the window
    # device 1: busy [1000, 2000)
    ev(DEV1, "fusion.1", 1_000, 1_000),
]


def test_busy_and_idle_on_a_hand_made_trace():
    red = trace.reduce(HAND)
    assert red.window_s == pytest.approx(10e-6)
    assert red.busy_by_device[DEV0] == pytest.approx((3_000 + 1_000 + 500) * 1e-9)
    assert red.busy_by_device[DEV1] == pytest.approx(1_000e-9)
    assert red.busy_s == pytest.approx((4_500 + 1_000) / 2 * 1e-9)
    assert red.idle_frac == pytest.approx(1 - 2_750 / 10_000)
    assert red.op_seconds["fusion.1"] == pytest.approx(4_000e-9)
    assert red.op_seconds["fusion.2"] == pytest.approx(500e-9)
    assert "fusion.3" not in red.op_seconds


def test_idle_gaps_are_named_by_the_host_annotation_open_across_them():
    red = trace.reduce(HAND)
    gaps = sorted((round(sec * 1e9), label) for label, sec in red.gaps)
    assert gaps == [
        (2_000, "bench.engine_run"),   # dev0 [4000, 6000), inside engine_run
        (3_500, "bench.predict"),      # dev0 [7000, 10500), mostly predict
        (9_000, "bench.engine_run"),   # dev1 [2000, 11000), mostly engine_run
    ]
    # every idle nanosecond of both devices is in some gap
    assert sum(sec for _, sec in red.gaps) == pytest.approx(
        20e-6 - (4_500 + 1_000) * 1e-9)
    assert red.gaps == sorted(red.gaps, key=lambda g: -g[1])
    br = red.breakdown(top=3)
    assert len(br["device_ops"]) == 3 and len(br["idle_gaps"]) == 3
    assert br["device_ops"][0][0] == "fusion.1"


def test_kernel_time_and_roofline_share():
    from bench.readers import roofline_share

    red = trace.reduce(HAND)
    pattern = re.compile(r"^amtl_event_batch(\.\d+)?$")
    assert red.seconds_matching(pattern) == pytest.approx(1_500e-9)
    # summed over the devices
    assert red.seconds_matching(re.compile(r"^fusion\.1$")) == pytest.approx(
        4_000e-9)
    peaks = spec.peaks("TPU v5 lite")
    w = work.column_update(784, 32)
    ctx = SimpleNamespace(trace=red, peaks=peaks,
                          kernel_calls={"amtl_event_batch": 2},
                          kernel_work={"amtl_event_batch": w})
    share = roofline_share(ctx, "amtl_event_batch", pattern)
    assert share == pytest.approx(100 * 2 * w.least_seconds(peaks) / 1_500e-9)
    # a kernel the trace never shows reads nothing, never 0
    ctx.kernel_calls["amtl_event_batch"] = 0
    assert roofline_share(ctx, "amtl_event_batch", pattern) is None
    assert roofline_share(ctx, "amtl_event_batch", re.compile("absent")) is None


@pytest.mark.parametrize("raw, name", [
    ("%amtl_event_batch.12 = (f32[1024,3456]{1,0:T(8,128)S(1)}, "
     "f32[1024,128]{1,0}) custom-call(s32[128]{0} %get-tuple-element.1924), "
     "custom_call_target=\"tpu_custom_call\"", "amtl_event_batch.12"),
    ("%sort.45 = (u32[512]{0:T(512)}, s32[512]{0}) sort(u32[512]{0} %x), "
     "dimensions={0}", "sort.45"),
    ("fusion.7", "fusion.7"),
])
def test_a_device_op_is_named_by_its_hlo_instruction(raw, name):
    assert trace.op_name(raw) == name
    pattern = re.compile(r"^amtl_event_batch(\.\d+)?$")
    assert bool(pattern.search(trace.op_name(raw))) == name.startswith("amtl")


def test_a_trace_without_the_window_annotation_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce([e for e in HAND if e.name != trace.WINDOW])


def test_a_window_in_which_the_profiler_dropped_events_is_refused():
    dropped = ev(DEV0, trace.DROPPED, 9_000, 5_000, line="XLA TraceMe")
    with pytest.raises(ValueError, match="dropped"):
        trace.reduce(HAND + [dropped])
    # a mark outside the window does no harm
    trace.reduce(HAND + [dropped._replace(start_ns=20_000.0)])


# A traced run on a TPU v5e of the learner cell cut to 64 writers (d = 784,
# capacity 64): one traced engine.run call of 64 events, so 64 sampled
# gradients and 2 event batches, as its result line read them.
RECORDED = Path(__file__).parent / "data" / "small_learn.xplane.pb.gz"


def test_a_recorded_chip_trace_reduces_to_what_its_run_read():
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(
        gzip.decompress(RECORDED.read_bytes()))
    red = trace.reduce(trace.flatten(data))
    assert red.busy_s == pytest.approx(0.002895838, rel=1e-9)
    assert red.window_s == pytest.approx(0.00613678, rel=1e-9)
    assert list(red.busy_by_device) == [DEV0]
    assert not any(name.startswith("%") for name in red.op_seconds)
    # a while loop's self time leaves out its body, so the ops' times add up
    # to the busy time (to the few ns of ops that overlap without nesting)
    assert sum(red.op_seconds.values()) == pytest.approx(red.busy_s, rel=1e-3)
    ctx = SimpleNamespace(
        trace=red, peaks=spec.peaks("TPU v5 lite"),
        kernel_calls={"lstsq_grad_sampled": 64, "amtl_event_batch": 2},
        kernel_work={"lstsq_grad_sampled": work.sampled_grad(32, 784),
                     "amtl_event_batch": work.column_update(784, 32)})
    for name, read in (("amtl_event_batch_roofline", 2.8501294347634274),
                       ("lstsq_grad_sampled_roofline", 6.098604118869351),
                       ("device_idle_frac.learn", 0.5281176773487073)):
        assert spec.metric_reader(name)(ctx) == pytest.approx(read, rel=1e-9)
