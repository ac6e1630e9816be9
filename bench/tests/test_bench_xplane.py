"""The trace reduction: busy union, idle gaps put down to the host's spans,
and operation times, on fixed events."""
import pytest

from tinytree import BENCH  # noqa: F401
from harness import xplane

MS = 1e6        # nanoseconds


def test_union_gaps_and_clip():
    busy = xplane.union([(5, 8), (0, 2), (1, 3), (8, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert xplane.gaps(busy, 0, 12) == [(3, 5), (9, 12)]
    assert xplane.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_reduce_events():
    host = [(xplane.WINDOW, 0, 100 * MS),
            ("bench.llm_step", 0, 60 * MS),
            ("bench.decode_step", 10 * MS, 40 * MS),
            ("bench.wait", 60 * MS, 100 * MS)]
    ops = {0: [("fusion.1", 10 * MS, 30 * MS),
               ("_paged_kernel", 30 * MS, 35 * MS),
               ("fusion.1", 70 * MS, 80 * MS),
               ("late", 95 * MS, 120 * MS)]}
    t = xplane.reduce_events(ops, host)
    assert t.window_s == pytest.approx(0.1)
    # busy: 10-35, 70-80 and the part of 95-120 inside the window
    assert t.busy_s[0] == pytest.approx(0.040)
    assert t.op_s["fusion.1"] == pytest.approx(0.030)
    assert t.op_s["late"] == pytest.approx(0.005)
    assert t.kernel_s(r"_paged_kernel") == pytest.approx(0.005)
    # gaps: 0-10 in llm_step, 35-70 mid 52.5 in llm_step, 80-95 in wait
    assert t.idle_by_host == pytest.approx(
        {"bench.llm_step": 0.045, "bench.wait": 0.015})
    b = t.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.030)]
    assert b["idle_gaps"][0][0] == "bench.llm_step"


def test_busy_is_averaged_over_chips():
    host = [(xplane.WINDOW, 0, 10 * MS)]
    ops = {0: [("a", 0, 10 * MS)], 1: [("a", 0, 5 * MS)]}
    t = xplane.reduce_events(ops, host)
    assert t.mean_busy_s == pytest.approx(0.0075)
    assert t.idle_by_host == pytest.approx({"host.outside_spans": 0.0025})


def test_window_is_required():
    with pytest.raises(ValueError):
        xplane.reduce_events({0: [("a", 0, 1)]}, [("bench.wait", 0, 1)])


def test_recorded_chip_trace():
    """A 0.24 s trace of qwen3-0.6b.chat recorded on a TPU v5 lite by
    ``bench/run.py --trace 1 --keep-trace <dir>``: one chip plane, the
    paged decode kernel found by its custom call, the layer loop kept out
    of the operations, and the idle time put down to the host's spans."""
    from tinytree import BENCH
    t = xplane.read(str(BENCH / "tests" / "data" / "qwen3-0.6b.chat.xplane.pb"))
    assert list(t.busy_s) == [0]
    assert t.window_s == pytest.approx(0.2407053, rel=1e-6)
    assert t.busy_s[0] == pytest.approx(0.2025164, rel=1e-6)
    assert t.kernel_s(r"^paged_decode_attention") == pytest.approx(
        0.0752503, rel=1e-5)
    assert not any(name.startswith("while") for name in t.op_s)
    top = t.breakdown()["device_ops"][0]
    assert top[0] == "paged_decode_attention.7"
    idle = dict(t.breakdown()["idle_gaps"])
    assert set(idle) <= {"bench.decode_step", "bench.prefill",
                         "bench.llm_step", "bench.submit", "bench.free_slot",
                         "bench.wait", "host.outside_spans"}
    assert sum(idle.values()) == pytest.approx(t.window_s - t.busy_s[0],
                                               rel=1e-6)
