import json
import os

import pytest

import devtrace

HERE = os.path.dirname(os.path.abspath(__file__))
KERNEL_OP = (
    '%body.5 = (f32[1,8,4]) custom-call(f32[1,4,8,8] %a), '
    'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}'
)


def hand_trace():
    """A window of 100 ns: device ops at [10, 30] (a while loop holding two
    kernel calls [12, 18] and [20, 28]) and [60, 70]; host annotations put a
    solve_many over [5, 40], a build inside it over [32, 38], and one pass
    over [0, 90]."""
    ops = [
        ("%while = (f32[1]) while(%t), body=%b", 10, 30),
        (KERNEL_OP, 12, 18),
        (KERNEL_OP, 20, 28),
        ("%fusion.3 = f32[8] fusion(%x)", 60, 70),
    ]
    marks = {
        "bench/window": [(0, 100)],
        "bench/pass": [(0, 90)],
        "bench/solve_many": [(5, 40)],
        "bench/build": [(32, 38)],
    }
    return {"devices": {"/device:TPU:0": ops}, "marks": marks}


def test_busy_idle_kernel_and_gap_labels():
    out = devtrace.reduce(hand_trace())
    # busy = [10, 30] + [60, 70] = 30 ns of the 100 ns window
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(30e-9)
    assert out["idle_share"] == pytest.approx(70.0)
    assert out["kernel_s"] == pytest.approx(14e-9)
    assert out["kernel_events"] == 2
    # the while loop holds the kernel calls: it counts for busy, not per op
    names = dict((n, s) for n, s in out["device_ops"])
    assert names == pytest.approx({"body.5 [tpu_custom_call]": 14e-9, "fusion.3": 10e-9})
    # gaps: [0,10] inside solve_many? no: its midpoint 5 is on the solve_many
    # edge -> solve_many; [30,60] midpoint 45 -> pass; [70,100] midpoint 85
    # -> pass
    totals = {n: s for n, s in out["idle_gaps"] if n.startswith("all: ")}
    assert totals == pytest.approx(
        {
            "all: engine host work (upload, rounding, Eq. 15)": 10e-9,
            "all: event loop, Algorithm 1, fleet driver": 60e-9,
        }
    )


def test_build_gap_is_named_build():
    trace = hand_trace()
    # a device op right after the build: the gap [30, 36] has midpoint 33,
    # inside the build annotation
    trace["devices"]["/device:TPU:0"].append(("%copy.1 = f32[8] copy(%y)", 36, 40))
    out = devtrace.reduce(trace)
    totals = {n: s for n, s in out["idle_gaps"] if n.startswith("all: ")}
    assert totals["all: program build (Yen paths, tensors)"] == pytest.approx(6e-9)


def test_no_window_or_no_device_reads_nothing():
    trace = hand_trace()
    assert devtrace.reduce({"devices": trace["devices"], "marks": {}}) is None
    assert devtrace.reduce({"devices": {}, "marks": trace["marks"]}) is None


def test_recorded_chip_trace():
    """An excerpt of a trace recorded on a TPU v5e (``small_trace.json``),
    reduced as recorded; the expected numbers were computed by hand from the
    excerpt's intervals."""
    with open(os.path.join(HERE, "small_trace.json")) as f:
        rec = json.load(f)
    out = devtrace.reduce(rec["trace"])
    for key, want in rec["expect"].items():
        assert out[key] == pytest.approx(want, rel=1e-9), key
