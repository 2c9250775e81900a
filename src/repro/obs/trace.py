"""Span tracer with a Chrome trace-event (Perfetto-loadable) exporter and a
JAX profiler sink.

The scheduling stack is a host-side control plane: its latency story is told
by *spans* — where did the wall-clock of one event go? — not by aggregate
counters. :class:`Tracer` records begin/end (``B``/``E``) spans, explicit
complete (``X``) spans with caller-supplied timestamps (the fleet runtime
uses these to draw per-lane barrier-stall intervals it attributes
arithmetically rather than measures), and instant (``i``) markers, each on a
named *track* (one per fleet lane, one for the fleet driver, one for the
shared engine).

Two sinks, no switch:

* **In memory**, when ``enabled``: the events above, exported by
  :meth:`Tracer.to_chrome`.
* **The JAX profiler**, whenever a capture is active
  (``jax.profiler.start_trace``; checked per span, about 0.1 us): every
  ``span()``/``begin()``/``end()`` also opens and closes a
  ``jax.profiler.TraceAnnotation`` of the same name — on any tracer,
  :data:`NULL_TRACER` included — so a capture holds the program's spans on
  the profiler's clock, next to the device ops. ``X`` and ``i`` events are
  computed or instantaneous and stay in memory only.

Spans that stay open across a generator's ``yield`` (the event loop's
``event/*`` and ``sched/round`` spans of a stepper the fleet runtime drives)
would misnest on one thread; :meth:`Tracer.resume` wraps each resume of such
a generator, closing the track's open spans when the generator suspends and
reopening them when it resumes, so every span of every track nests on the
one thread the profiler records.

Design constraints:

* **Near-zero overhead when off.** With the tracer disabled and no capture,
  ``span()`` and ``resume()`` return one shared no-op context manager and
  every emit method returns after one attribute load and the capture check:
  instrumentation stays in the hot paths permanently and allocates nothing
  per span.
* **Measured cost when on.** On one TPU v5 lite chip, the benchmark cell
  ``ents-mesh.otfa`` (512 lanes, the async dispatcher) ran 502.8 and 490.8
  events/s with an enabled tracer against 495.3 and 499.3 with it off, and
  admitted at a median of 1.348 and 1.358 ms against 1.336 and 1.353 ms:
  within run-to-run noise. Under a capture with Python call tracing off,
  as the benchmark's traced run takes it, the cell ran 514.8 and 483.2
  events/s against 507.9 and 493.0 off (median 1.333 and 1.350 ms against
  1.326 and 1.336 ms). The profiler's default options, which also trace
  every Python call, halved the events/s (246.0) and quadrupled the
  admission median (5.27 ms).
* **Strict JSON out.** Both exporters serialize through
  :func:`dumps_strict` (non-finite floats -> ``null``, ``allow_nan=False``),
  the same sanitizer the fleet telemetry JSONL uses, so every artifact
  parses under RFC 8259 — ``chrome://tracing`` and Perfetto both reject the
  non-standard ``Infinity``/``NaN`` tokens.

Timestamps are seconds since the tracer's construction on the clock the
profiler stamps host events with (``CLOCK_REALTIME``, ``time.time_ns``);
``origin_ns`` is that construction time, written into the Chrome export so
its events line up with a capture's absolute host timestamps. Load the
exported file in https://ui.perfetto.dev ("Open trace file") or
``chrome://tracing``.
"""
from __future__ import annotations

import json
import math
import time

__all__ = [
    "NULL_SPAN",
    "NULL_TRACER",
    "Tracer",
    "annotate_instant",
    "capturing",
    "dumps_strict",
    "sanitize_nonfinite",
]

# the profiler sink; obs itself needs nothing beyond the stdlib.
# capturing() answers "is a JAX profiler capture recording host spans now?"
try:
    from jax.profiler import TraceAnnotation as _Annotation

    capturing = _Annotation.is_enabled
except ImportError:  # pragma: no cover - exercised only without JAX
    _Annotation = None

    def capturing() -> bool:
        return False


def annotate_instant(name: str, **args) -> None:
    """A zero-length profiler annotation (``args`` become its stats) when a
    capture is active; nothing otherwise."""
    if capturing():
        with _Annotation(name, **args):
            pass


def sanitize_nonfinite(obj):
    """Recursively replace non-finite floats (inf / -inf / nan) with None so
    the result serializes under RFC 8259 (which has no such literals)."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: sanitize_nonfinite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [sanitize_nonfinite(v) for v in obj]
    return obj


def dumps_strict(obj, **kwargs) -> str:
    """``json.dumps`` that can never emit a non-RFC-8259 token. Extra kwargs
    (``indent=``, ``sort_keys=``…) pass through to ``json.dumps``; the
    telemetry-strictness lint (TS401) makes this the repo's only
    serialization door outside this module."""
    return json.dumps(sanitize_nonfinite(obj), allow_nan=False, **kwargs)


class _NullSpan:
    """Shared no-op context manager the disabled tracer hands out."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


class _Span:
    """Context manager emitting a ``B``/``E`` pair on one track."""

    __slots__ = ("_tracer", "_name", "_track", "_cat", "_args")

    def __init__(self, tracer: "Tracer", name: str, track: str, cat: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._track = track
        self._cat = cat
        self._args = args

    def __enter__(self) -> "_Span":
        self._tracer.begin(self._name, track=self._track, cat=self._cat, **self._args)
        return self

    def __exit__(self, *exc) -> bool:
        self._tracer.end(self._name, track=self._track)
        return False


class _Resume:
    """Context manager of :meth:`Tracer.resume`: its own span around one
    resume of a generator, with the track's suspended spans reopened inside
    it and closed again before it ends."""

    __slots__ = ("_tracer", "_name", "_track", "_cat", "_ann")

    def __init__(self, tracer: "Tracer", name: str, track: str, cat: str):
        self._tracer = tracer
        self._name = name
        self._track = track
        self._cat = cat
        self._ann = None

    def __enter__(self) -> "_Resume":
        t = self._tracer
        live = capturing()
        if live:
            self._ann = _Annotation(self._name)
            self._ann.__enter__()
        if t.enabled:
            t._emit("B", self._name, self._track, self._cat, None)
        for entry in t._open.get(self._track, ()):
            if live:
                entry[3] = _Annotation(entry[0])
                entry[3].__enter__()
            if t.enabled:
                t._emit("B", entry[0], self._track, entry[1], entry[2])
        return self

    def __exit__(self, *exc) -> bool:
        t = self._tracer
        for entry in reversed(t._open.get(self._track, ())):
            if entry[3] is not None:
                entry[3].__exit__(None, None, None)
                entry[3] = None
            if t.enabled:
                t._emit("E", entry[0], self._track, "", None)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if t.enabled:
            t._emit("E", self._name, self._track, "", None)
        return False


class Tracer:
    """Event-level span recorder with one timeline track per component.

    The in-memory sink records only when ``enabled``; the profiler sink
    records whenever a capture is active (module docstring). ``ts``/``dur``
    are seconds on the tracer clock (:meth:`now`); events accumulate
    in-memory (a control-plane run emits thousands, not millions) and export
    via :meth:`to_chrome`.
    """

    def __init__(self, *, enabled: bool = True) -> None:
        self.enabled = enabled
        self.events: list[dict] = []
        self.origin_ns = time.time_ns()
        self._tracks: dict[str, int] = {}
        # per track, the spans begun and not yet ended, innermost last:
        # [name, cat, args, open profiler annotation or None]
        self._open: dict[str, list[list]] = {}

    @property
    def recording(self) -> bool:
        """Does a span begun now reach either sink?"""
        return self.enabled or capturing()

    # -- clock ----------------------------------------------------------------
    def now(self) -> float:
        """Seconds since tracer construction, on the profiler's host clock."""
        return (time.time_ns() - self.origin_ns) * 1e-9

    def _tid(self, track: str) -> int:
        tid = self._tracks.get(track)
        if tid is None:
            tid = self._tracks[track] = len(self._tracks)
        return tid

    def _emit(self, ph: str, name: str, track: str, cat: str, args: dict | None) -> None:
        ev = {"ph": ph, "name": name, "cat": cat, "tid": self._tid(track), "ts": self.now()}
        if ph == "B":
            ev["args"] = args or None
        self.events.append(ev)

    # -- emit -----------------------------------------------------------------
    def begin(self, name: str, *, track: str = "main", cat: str = "span", **args) -> None:
        """Open a span on ``track``; must be closed by :meth:`end` (stack
        discipline per track — the exporter test asserts balance)."""
        live = capturing()
        if not (self.enabled or live):
            return
        ann = None
        if live:
            ann = _Annotation(name)
            ann.__enter__()
        self._open.setdefault(track, []).append([name, cat, args, ann])
        if self.enabled:
            self._emit("B", name, track, cat, args)

    def end(self, name: str, *, track: str = "main") -> None:
        if self._open:
            stack = self._open.get(track)
            # a span begun while the tracer was off and no capture ran has
            # no entry: its end must not close an outer one
            if stack and stack[-1][0] == name:
                ann = stack.pop()[3]
                if not stack:
                    del self._open[track]
                if ann is not None:
                    ann.__exit__(None, None, None)
        if self.enabled:
            self._emit("E", name, track, "", None)

    def span(self, name: str, *, track: str = "main", cat: str = "span", **args):
        """Context manager measuring a span on the tracer clock."""
        if not (self.enabled or capturing()):
            return NULL_SPAN
        return _Span(self, name, track, cat, args)

    def resume(self, name: str, *, track: str = "main", cat: str = "span"):
        """Context manager around one resume of a generator whose spans on
        ``track`` stay open across its ``yield``: a span ``name`` with the
        track's open spans reopened inside it on entry and closed on exit,
        so they nest inside the resume instead of straddling other work."""
        if not (self.enabled or capturing()):
            return NULL_SPAN
        return _Resume(self, name, track, cat)

    def complete(
        self,
        name: str,
        *,
        track: str = "main",
        cat: str = "span",
        ts: float,
        dur: float,
        **args,
    ) -> None:
        """Explicit-interval span (``X`` event): the caller supplies start +
        duration in tracer-clock seconds. This is how *attributed* (computed,
        not measured) intervals are drawn — e.g. a lane's barrier-stall share
        of a batched dispatch."""
        if not self.enabled:
            return
        self.events.append(
            {
                "ph": "X",
                "name": name,
                "cat": cat,
                "tid": self._tid(track),
                "ts": ts,
                "dur": dur,
                "args": args or None,
            }
        )

    def instant(self, name: str, *, track: str = "main", cat: str = "span", **args) -> None:
        """Zero-duration marker (``i`` event)."""
        if not self.enabled:
            return
        self.events.append(
            {
                "ph": "i",
                "name": name,
                "cat": cat,
                "tid": self._tid(track),
                "ts": self.now(),
                "s": "t",
                "args": args or None,
            }
        )

    # -- export ---------------------------------------------------------------
    def to_chrome(self, path: str, *, process_name: str = "repro-scheduler") -> None:
        """Write the Chrome trace-event JSON (Perfetto / ``chrome://tracing``
        loadable): metadata rows naming the process and one thread per track
        (in registration order, so lane tracks sort stably), then every
        recorded event with timestamps converted to microseconds."""
        pid = 1
        trace_events: list[dict] = [
            {
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "name": "process_name",
                "args": {"name": process_name},
            }
        ]
        for track, tid in sorted(self._tracks.items(), key=lambda kv: kv[1]):
            trace_events.append(
                {
                    "ph": "M",
                    "pid": pid,
                    "tid": tid,
                    "name": "thread_name",
                    "args": {"name": track},
                }
            )
            trace_events.append(
                {
                    "ph": "M",
                    "pid": pid,
                    "tid": tid,
                    "name": "thread_sort_index",
                    "args": {"sort_index": tid},
                }
            )
        for ev in self.events:
            out = {"pid": pid, **ev}
            out["ts"] = ev["ts"] * 1e6
            if "dur" in ev:
                out["dur"] = ev["dur"] * 1e6
            if out.get("args") is None:
                out.pop("args", None)
            trace_events.append(out)
        doc = {
            "traceEvents": trace_events,
            "displayTimeUnit": "ms",
            # ts 0 of this file on the profiler's host clock (ns since epoch)
            "otherData": {"clock_origin_ns": self.origin_ns},
        }
        with open(path, "w") as f:
            f.write(dumps_strict(doc))


NULL_TRACER = Tracer(enabled=False)
