"""The span reduction (``bench/spans.py``) on hand-made traces and on a
slimmed chip trace of the cell."""
import gzip
from pathlib import Path

import pytest

from bench import spans, trace

W = "/host:CPU#3"                    # the worker's line
OTHER = "/host:CPU#5"


def _trace(lines, ops, modules=()):
    return {"window": (10.0, 110.0), "lines": lines,
            "ops": {"/device:TPU:0": ops},
            "modules": {"/device:TPU:0": list(modules)}}


def test_innermost_segments():
    segs = spans.innermost([(0.0, 100.0, "a"), (10.0, 40.0, "b"),
                            (20.0, 30.0, "c"), (60.0, 70.0, "d"),
                            (120.0, 130.0, "e")])
    assert segs == [(0.0, 10.0, "a"), (10.0, 20.0, "b"),
                    (20.0, 30.0, "c"), (30.0, 40.0, "b"),
                    (40.0, 60.0, "a"), (60.0, 70.0, "d"),
                    (70.0, 100.0, "a"), (120.0, 130.0, "e")]


def test_idle_by_span_arithmetic():
    """Gaps labelled by the innermost worker span at their middle; the
    labels add up to the idle time; idle inside dispatches counted by
    overlap; launches split by program name."""
    d = spans.DISPATCH
    lines = {
        W: [(5.0, 60.0, d), (8.0, 20.0, "lilis.sched.form"),
            (20.0, 50.0, "lilis.exec.launch"),
            (50.0, 58.0, "lilis.sched.device_wait"),
            (70.0, 100.0, d), (72.0, 90.0, "lilis.exec.prep")],
        OTHER: [(0.0, 120.0, "lilis.sched.idle")],     # not the worker
    }
    ops = [(0.0, 15.0), (30.0, 55.0), (95.0, 130.0)]
    mods = [(12.0, 13.0, "jit_lilis_point_x_w1(123)"),
            (31.0, 32.0, "jit_convert_element_type(9)"),
            (33.0, 34.0, "jit_left_shift(8)"),
            (96.0, 97.0, "jit_lilis_knn_10_fused_256x16_w1(7)"),
            (5.0, 6.0, "jit_bitwise_or(1)")]           # before the window
    red = spans.reduce(_trace(lines, ops, mods))
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx((5 + 25 + 15) * 1e-9)
    # gaps: 15-30 (mid 22.5: launch), 55-95 (mid 75: prep)
    assert dict(red["idle_by_span"]) == pytest.approx(
        {"lilis.exec.launch": 15e-9, "lilis.exec.prep": 40e-9})
    assert sum(t for _, t in red["idle_by_span"]) == pytest.approx(
        red["window_s"] - red["busy_s"], abs=1e-15)
    # idle inside dispatches: 15-30, 55-60, 70-95
    assert red["idle_in_dispatch_share"] == pytest.approx(45.0)
    assert red["dispatches"] == 1            # one dispatch starts inside
    assert (red["lilis_launches"], red["eager_launches"]) == (2, 2)
    assert red["eager_launches_per_dispatch"] == 1.0
    worker = dict(red["worker_by_span"])
    assert worker[spans.DISPATCH] == pytest.approx((2 + 2 + 10) * 1e-9)
    assert worker["lilis.exec.prep"] == pytest.approx(18e-9)


def test_trace_without_program_spans():
    """A program without spans (the parent of this reduction) reduces to
    one label and no launch ratio, and does not raise."""
    red = spans.reduce(_trace({}, [(20.0, 30.0)],
                              [(21.0, 22.0, "jit_call(5)")]))
    assert red["idle_by_span"] == [[spans.OUTSIDE,
                                    pytest.approx(90e-9)]]
    assert red["idle_in_dispatch_share"] is None
    assert red["eager_launches_per_dispatch"] is None
    assert red["eager_launches"] == 1


FIXTURE = Path(__file__).parent / "fixtures" / "spans.xplane.pb.gz"


def test_spans_on_a_chip_trace(tmp_path):
    """The committed trace was recorded on a TPU v5e by an 8 s traced
    window of spider-gaussian.interactive-counts, with the program's
    spans (slimmed by fixtures/slim_spans.py). It reduces to the values
    read on the chip, every LiLIS program is named ``jit_lilis_*``, and
    its labels add up to the idle time within a microsecond."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    tr = spans.load(str(path))
    red = spans.reduce(tr)
    assert red["window_s"] == pytest.approx(7.994254491)
    assert red["busy_s"] == pytest.approx(0.620282717)
    assert dict(red["idle_by_span"]) == pytest.approx({
        "lilis.sched.idle": 4.375609356, "lilis.exec.prep": 2.743974004,
        spans.OUTSIDE: 0.148497842, "lilis.sched.device_wait": 0.045360908,
        "lilis.sched.coalesce": 0.033584202,
        "lilis.exec.launch": 0.011870724, "lilis.exec.post": 0.01121459,
        "lilis.sched.dispatch": 0.002484671,
        "lilis.sched.form": 0.001375477})
    assert abs(sum(t for _, t in red["idle_by_span"])
               - (red["window_s"] - red["busy_s"])) < 1e-6
    assert red["idle_in_dispatch_share"] == pytest.approx(39.14289504447051)
    assert (red["dispatches"], red["lilis_launches"],
            red["eager_launches"]) == (95, 95, 8020)
    names = {n[:n.rfind("(")] for evs in tr["modules"].values()
             for _, _, n in evs}
    assert not any(n.startswith("jit_call") for n in names)
    assert {"jit_lilis_point_x_w1", "jit_lilis_range_count_x_w1",
            "jit_lilis_knn_10_fused_256x8_w1"} <= names
    old = trace.reduce(trace.load(str(path)))
    assert old["busy_s"] == pytest.approx(red["busy_s"])
    assert old["window_s"] == pytest.approx(red["window_s"])
