"""Device time by engine phase: the `tf_op` decoder, the scope rules, and
the recorded chip traces, with and without the engine's phase scopes."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import phases, spec, trace, work, xplane

DATA = Path(__file__).parent / "data"
DEV0 = "/device:TPU:0"


def _raw(name: str) -> bytes:
    return gzip.decompress((DATA / name).read_bytes())


def _reduced(raw: bytes) -> trace.Reduced:
    from jax.profiler import ProfileData

    return trace.reduce(trace.flatten(ProfileData.from_serialized_xspace(raw)))


# --- a hand-encoded XSpace -------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _len(number: int, payload: bytes) -> bytes:
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _int(number: int, value: int) -> bytes:
    return _varint(number << 3) + _varint(value)


def _entry(key: int, value: bytes) -> bytes:
    return _int(1, key) + _len(2, value)


def _plane(name: str, ops: dict) -> bytes:
    """A plane whose op metadata carry `tf_op` as a string (5), by
    reference (7), or not at all (None); plus a double stat (2) and a line
    (3), which the decoder skips."""
    stats = _len(5, _entry(1, _len(2, b"tf_op"))) + _len(5, _entry(
        2, _len(2, b"flops"))) + _len(5, _entry(3, _len(2, b"jit(f)/amtl.ref")))
    metas = b""
    for i, (op, how) in enumerate(ops.items()):
        body = _len(2, op.encode())
        body += _len(5, _int(1, 2) + b"\x11" + bytes(8))    # double_value
        if how == "str":
            body += _len(5, _int(1, 1) + _len(5, b"jit(f)/amtl.grad/dot:"))
        elif how == "ref":
            body += _len(5, _int(1, 1) + _int(7, 3))
        metas += _len(4, _entry(i + 1, body))
    return (_int(1, 7) + _len(2, name.encode()) + _len(3, b"\x08\x01")
            + metas + stats)


def test_tf_ops_decodes_string_and_reference_values():
    raw = (_len(1, _plane(DEV0, {"%dot.1 = f32[]": "str",
                                 "%while.2 = ()": "ref",
                                 "%copy.3 = f32[]": None}))
           + _len(1, _plane("/host:CPU", {"python": "str"})))
    assert xplane.tf_ops(raw) == {DEV0: {
        "%dot.1 = f32[]": "jit(f)/amtl.grad/dot:",
        "%while.2 = ()": "jit(f)/amtl.ref"}}


# --- the scope rules ---------------------------------------------------------

@pytest.mark.parametrize("tf_op, scope", [
    ("jit(_run_events)/while/body/closed_call/amtl.grad/while/body/"
     "jit(lstsq_grad_sampled)/pallas_call:", "amtl.grad"),
    ("jit(_run_events)/while/body/amtl.prox/jit(svd)/amtl.update/eigh:",
     "amtl.update"),
    ("jit(_run_events)/while/body/amtl.sample:", "amtl.sample"),
    ("jit(_run_events)/while/body/closed_call/squeeze:", ""),
    ("jit(amtl.runner)/x", ""),
    ("", ""),
])
def test_an_op_takes_its_innermost_engine_scope(tf_op, scope):
    assert phases.scope_of(tf_op) == scope


def test_an_op_without_a_scope_takes_its_nested_ops_else_its_parents():
    # 0 batch loop { 1 sample scan { 2, 3 }  4 grad scan { 5, 6 copy }
    #                7 unscoped }   8 a copy at the top
    own = ["", "", "amtl.sample", "amtl.sample", "", "amtl.grad", "",
           "", ""]
    parent = [None, 0, 1, 1, 0, 4, 4, 0, None]
    assert phases._resolve(own, parent) == [
        "", "amtl.sample", "amtl.sample", "amtl.sample", "amtl.grad",
        "amtl.grad", "amtl.grad", "", ""]


# --- the recorded chip traces -----------------------------------------------

# A traced run on a TPU v5e of the learner cell cut to 64 writers (d = 784,
# capacity 64), before the engine named its phases: one traced engine.run
# call of 64 events (see test_bench_trace.py).
UNSCOPED = "small_learn.xplane.pb.gz"


def test_the_unscoped_trace_names_ops_by_their_tf_op():
    ops = xplane.tf_ops(_raw(UNSCOPED))[DEV0]
    assert len(ops) == 182
    (text,) = [k for k in ops if trace.op_name(k) == "lstsq_grad_sampled.6"]
    assert ops[text] == (
        "jit(_run_events)/while/body/closed_call/while/body/closed_call/"
        "jit(lstsq_grad_sampled)/jit(lstsq_grad_sampled)/pallas_call:")


def test_the_unscoped_trace_still_reduces_to_the_same_numbers():
    red = _reduced(_raw(UNSCOPED))
    assert red.busy_s == pytest.approx(0.002895838, rel=1e-9)
    assert red.window_s == pytest.approx(0.00613678, rel=1e-9)
    assert len(red.op_seconds) == 227
    assert sum(red.op_seconds.values()) == pytest.approx(0.002896078,
                                                         rel=1e-9)
    for name, sec in (("while.218", 0.000931655), ("while.228", 0.000276166),
                      ("while.219", 0.000161923),
                      ("lstsq_grad_sampled.6", 0.000136786)):
        assert red.op_seconds[name] == pytest.approx(sec, rel=1e-6)
    assert [label for label, _ in red.gaps] == ["bench.engine_run"] * 12
    assert [round(s * 1e9) for _, s in red.gaps[:4]] == [2365835, 874401,
                                                         347, 346]
    cfg = dict(spec.cell("emnist62_writers.learn").config, num_tasks=64)
    ctx = SimpleNamespace(
        trace=red, peaks=spec.peaks("TPU v5 lite"), chips=1,
        window_s=red.window_s, work=work.engine_events(cfg, 64),
        kernel_calls={"lstsq_grad_sampled": 64, "amtl_event_batch": 2},
        kernel_work={"lstsq_grad_sampled": work.sampled_grad(32, 784),
                     "amtl_event_batch": work.column_update(784, 32)})
    for name, read in (("amtl_event_batch_roofline", 2.8501294347634274),
                       ("lstsq_grad_sampled_roofline", 6.098604118869351),
                       ("device_idle_frac.learn", 0.5281176773487073),
                       ("engine_mfu", 0.0021315234300936237)):
        assert spec.metric_reader(name)(ctx) == pytest.approx(read, rel=1e-9)


def test_a_trace_without_scopes_or_spans_reads_no_events():
    raw = _raw(UNSCOPED)
    ph = phases.read(raw)
    assert ph.events == 0 and ph.spans == []
    assert set(ph.scope_seconds) == {""}
    assert ph.scope_seconds[""] == pytest.approx(
        sum(_reduced(raw).op_seconds.values()), abs=1e-9)
    assert all(ph.us_per_event(s) is None for s in phases.SCOPES + ("",))


# The same cell cut to 64 writers, recorded on a TPU v5e after the engine
# named its phases, by `python3 -m bench.phases --tasks 64 --calls 1`; its
# result line beside it.
SCOPED = "small_learn_scoped.xplane.pb.gz"
SCOPED_LINE = DATA / "small_learn_scoped.json"


def test_the_scoped_trace_attributes_every_op_to_a_phase():
    raw = _raw(SCOPED)
    line = json.loads(SCOPED_LINE.read_text())
    ph = phases.read(raw)
    assert ph.events == line["calls"] * line["events_per_call"] == 64
    assert [s.args for s in ph.spans if s.name == phases.RUN_SPAN] == [
        {"num_events": 64}]
    for scope in phases.SCOPES + ("",):
        assert ph.scope_seconds[scope] > 0, scope
        assert ph.us_per_event(scope) == pytest.approx(
            line["us_per_event"][scope or "other"], rel=1e-9)
    assert set(ph.scope_seconds) == set(phases.SCOPES + ("",))
    total = sum(_reduced(raw).op_seconds.values())
    assert sum(ph.scope_seconds.values()) == pytest.approx(total, abs=1e-9)
    # the loops PERF.md names by hand: the sampling scan, the gradient
    # scan, the KM relaxation scan (not the batch loop)
    assert [ph.op_scope[op] for op in ("while.218", "while.228", "while.219",
                                       "lstsq_grad_sampled.6",
                                       "amtl_event_batch.12")] == [
        "amtl.sample", "amtl.grad", "amtl.update", "amtl.grad",
        "amtl.update"]
