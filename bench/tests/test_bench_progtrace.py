"""The program's spans and named programs in a trace: idle time apportioned
over the innermost program span, the per-layer quantities, and a trace of a
program without spans reading nothing."""
import json
import sys

import pytest

from tinytree import BENCH
from harness import progtrace as P
from harness import xplane

sys.path.insert(0, str(BENCH / "tools"))
from program_spans import report  # noqa: E402

MS = 1e6        # nanoseconds
RECORDED = BENCH / "tests" / "data" / "qwen3-0.6b.chat.xplane.pb"


def _span(name, a, b):
    return P.Span(f"repro.{name}", a * MS, b * MS)


def _decode_trace():
    """Two scheduler steps, each one decode call, the first also sampling
    after its call; the device runs the decode program inside each call."""
    spans = [_span("sched.step", 0, 60),
             _span("backend.decode_step", 5, 40),
             _span("backend.dispatch", 5, 10),
             _span("backend.fetch", 30, 40),
             _span("sched.sample", 40, 50),
             _span("sched.step", 60, 100),
             _span("backend.decode_step", 62, 90),
             _span("backend.dispatch", 62, 65),
             P.Span("bench.llm_step", 0, 100 * MS)]
    progs = [("jit_decode_step", 10 * MS, 30 * MS),
             ("jit_decode_step", 65 * MS, 85 * MS)]
    return P.build(spans, {0: progs}, {0: progs}, window=(0, 100 * MS))


def test_a_gap_is_apportioned_over_the_spans_it_crosses():
    t = _decode_trace()
    # the gap 30-65 runs from the logits fetch through sampling and the
    # rest of the step into the next step's dispatch
    assert P.apportion(t) == pytest.approx({
        "repro.backend.dispatch": 0.005 + 0.003,
        "repro.sched.step": 0.005 + 0.010 + 0.002 + 0.010,
        "repro.backend.fetch": 0.010,
        "repro.sched.sample": 0.010,
        "repro.backend.decode_step": 0.005})
    path = P.apportion(t, by_path=True)
    assert path["repro.sched.step/repro.backend.decode_step/"
                "repro.backend.fetch"] == pytest.approx(0.010)
    assert path["repro.sched.step/repro.sched.sample"] == pytest.approx(0.010)
    assert sum(path.values()) == pytest.approx(0.100 - 0.040)
    # the midpoint rule puts the whole 35 ms gap down to one label
    mid = xplane.reduce_events(
        {0: [("d", 10 * MS, 30 * MS), ("d", 65 * MS, 85 * MS)]},
        [(xplane.WINDOW, 0, 100 * MS)] +
        [(s.name, s.start, s.end) for s in t.spans])
    assert mid.idle_by_host["repro.sched.sample"] == pytest.approx(0.035)


def test_decode_quantities():
    t = _decode_trace()
    # steps of 60 and 40 ms held calls of 35 and 28 ms
    assert P.sched_self_ms(t) == pytest.approx((25 + 12) / 2)
    assert P.decode_device_ms(t) == pytest.approx(20.0)
    # calls 5-40 and 62-90 each ran the program 20 ms
    assert P.decode_idle_ms(t) == pytest.approx((15 + 8) / 2)
    assert P.prefill_device_ms(t) is None


def test_prefill_device_time_per_call():
    spans = [_span("sched.step", 0, 100), _span("sched.admit", 1, 90),
             _span("backend.prefill", 2, 80)]
    progs = {0: [("jit_prefill", 5 * MS, 35 * MS),
                 ("jit_prefill_scatter", 35 * MS, 40 * MS),
                 ("jit_broadcast_in_dim", 41 * MS, 42 * MS)]}
    t = P.build(spans, progs, progs, window=(0, 100 * MS))
    assert P.prefill_device_ms(t) == pytest.approx(35.0)


def test_chips_are_averaged_and_unnamed_spans_ignored():
    spans = [_span("sched.step", 0, 10), _span("sched.sample", 4, 4)]
    ops = {0: [("a", 0, 10 * MS)], 1: [("a", 0, 5 * MS)]}
    t = P.build(spans, {}, ops, window=(0, 10 * MS))
    assert P.apportion(t) == pytest.approx({"repro.sched.step": 0.0025})
    assert P.decode_device_ms(t) is None and P.decode_idle_ms(t) is None


def test_nothing_to_read_without_program_spans():
    ops = {0: [("fusion.1", 10 * MS, 30 * MS)]}
    t = P.build([P.Span("bench.decode_step", 0, 50 * MS)], {}, ops,
                window=(0, 50 * MS))
    assert [f(t) for f in (P.sched_self_ms, P.prefill_device_ms,
                           P.decode_device_ms, P.decode_idle_ms)] == \
        [None] * 4
    assert P.apportion(t) == pytest.approx({P.OUTSIDE: 0.030})


def test_recorded_trace_of_a_program_without_spans():
    """The recorded trace predates the program's spans and program names:
    every quantity reads None, and all idle time lies outside program
    spans, as much of it as the benchmark's own reduction finds."""
    t = P.read(str(RECORDED))
    assert t.spans == []
    assert [f(t) for f in (P.sched_self_ms, P.prefill_device_ms,
                           P.decode_device_ms, P.decode_idle_ms)] == \
        [None] * 4
    old = xplane.read(str(RECORDED))
    assert t.window[1] - t.window[0] == pytest.approx(old.window_s * 1e9)
    assert P.apportion(t) == pytest.approx(
        {P.OUTSIDE: old.window_s - old.busy_s[0]}, rel=1e-9)
    names = {n for n, _, _ in t.modules[0]}
    assert {"jit__decode", "jit__unknown", "jit__scatter_paged"} <= names
    rep = report(t)
    assert json.loads(json.dumps(rep))["metrics"]["decode_idle_ms"] is None
    assert rep["programs"]["jit__decode"]["n"] == 8


def test_recorded_trace_reduces_as_before():
    """The benchmark's reduction of the recorded trace, pinned: busy time,
    operation times and idle gaps by ``bench.*`` span."""
    t = xplane.read(str(RECORDED))
    assert t.busy_s == pytest.approx({0: 0.202516447}, rel=1e-9)
    assert len(t.op_s) == 185
    assert sum(t.op_s.values()) == pytest.approx(0.202489945, rel=1e-9)
    assert t.idle_by_host == pytest.approx(
        {"bench.decode_step": 0.028996984, "bench.prefill": 0.009191893},
        rel=1e-7)
    b = t.breakdown()
    assert [k for k, _ in b["device_ops"]] == [
        "paged_decode_attention.7", "copy.75", "copy.72",
        "bitcast_dynamic-update-slice_fusion.8",
        "bitcast_dynamic-update-slice_fusion.6",
        "dynamic-slice_bitcast_fusion.7", "dynamic-slice_bitcast_fusion.6",
        "bitcast_add_fusion.3", "fusion.86", "fusion.156"]
    assert b["device_ops"][0][1] == pytest.approx(0.075250288, rel=1e-7)
    assert [k for k, _ in b["idle_gaps"]] == ["bench.decode_step",
                                              "bench.prefill"]
