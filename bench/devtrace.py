"""Reduction of a profiler trace to device metrics: busy time (the union of
the intervals in which an operation ran on the device), the idle share of
the traced window, the kernel's device time, the operations that took most
time, and the idle gaps named by what the host was doing in them.

The host side of the trace carries the benchmark's own annotations
(``bench/window``, ``bench/pass``, ``bench/solve_many``, ``bench/build``),
written with ``jax.profiler.TraceAnnotation`` on the profiler's clock; a gap
is named by the innermost annotation around its midpoint.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os

# device op lines of a TPU plane in the profiler's trace
DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
# ops that contain other ops of the same line (a while loop spans its body's
# kernel calls): in the busy union, never in the per-op totals
CONTAINERS = ("while", "conditional", "call")
# the Pallas congestion kernel is the device's only custom call
KERNEL = 'custom_call_target="tpu_custom_call"'
# what the host was doing, innermost annotation first
GAP_LABELS = (
    ("bench/build", "program build (Yen paths, tensors)"),
    ("bench/solve_many", "engine host work (upload, rounding, Eq. 15)"),
    ("bench/pass", "event loop, Algorithm 1, fleet driver"),
    ("bench/window", "between passes (lane generation)"),
)


def load(trace_dir: str) -> dict:
    """Read the one ``.xplane.pb`` under ``trace_dir``: device op intervals
    per device and the host's ``bench/*`` annotation intervals, in ns."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    marks: dict[str, list] = collections.defaultdict(list)
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench/"):
                        marks[e.name].append((e.start_ns, e.start_ns + e.duration_ns))
    return {"devices": devices, "marks": dict(marks)}


def op_name(hlo: str) -> str:
    """Short name of an op from the trace's HLO text: its instruction name,
    with the custom-call target where there is one."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    if 'custom_call_target="' in hlo:
        name += " [" + hlo.split('custom_call_target="', 1)[1].split('"', 1)[0] + "]"
    return name


def union(intervals) -> list[tuple[float, float]]:
    """Merge intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _covering(marks, name, t) -> bool:
    spans = marks.get(name)
    if not spans:
        return False
    i = bisect.bisect_right(spans, (t, float("inf"))) - 1
    return i >= 0 and spans[i][0] <= t <= spans[i][1]


def label(marks, t) -> str:
    for name, text in GAP_LABELS:
        if _covering(marks, name, t):
            return text
    return "outside the window"


def reduce(trace: dict, kernel: str = KERNEL) -> dict | None:
    """Device metrics over the ``bench/window`` annotation. Returns None
    when the trace holds no window or no device operation."""
    marks = {k: sorted(map(tuple, v)) for k, v in trace["marks"].items()}
    win = marks.get("bench/window")
    if not win or not trace["devices"]:
        return None
    w0, w1 = win[0][0], win[-1][1]
    window_ns = w1 - w0
    busy = []
    per_op: collections.Counter = collections.Counter()
    kernel_ns = 0.0
    kernel_events = 0
    gaps: list[tuple[float, str]] = []
    by_label: collections.Counter = collections.Counter()
    for ops in trace["devices"].values():
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops if e > w0 and s < w1]
        for n, s, e in clipped:
            name = op_name(n)
            if not name.startswith(CONTAINERS):
                per_op[name] += e - s
            if kernel in n:
                kernel_ns += e - s
                kernel_events += 1
        merged = union((s, e) for _, s, e in clipped)
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                text = label(marks, (a + b) / 2)
                gaps.append((b - a, text))
                by_label[text] += b - a
    n_dev = len(trace["devices"])
    busy_ns = sum(busy) / n_dev
    top_ops = [[n, v / 1e9 / n_dev] for n, v in per_op.most_common(10)]
    idle = [[f"all: {t}", v / 1e9 / n_dev] for t, v in by_label.most_common(5)]
    for dur, text in sorted(gaps, reverse=True)[: 10 - len(idle)]:
        idle.append([f"longest: {text}", dur / 1e9])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 100.0 * (1.0 - busy_ns / window_ns),
        "kernel_s": kernel_ns / 1e9 / n_dev,
        "kernel_events": kernel_events,
        "device_ops": top_ops,
        "idle_gaps": idle,
    }
