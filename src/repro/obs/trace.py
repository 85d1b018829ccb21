"""Host-side flight recorder — spans, events, and the run event log.

The engine's hot path is a single jitted ``lax.scan`` over whole chunks
of federated rounds (repro.fed.engine): deliberately opaque to Python.
Everything the host *does* observe — round boundaries, chunk dispatch,
wire emission, evaluation, pruning — goes through this module so one
run produces one machine-readable event stream instead of scattered
``time.perf_counter`` pairs and prints.

Three pieces:

``Recorder``
    An append-only event log.  Every event is one JSON-able dict with
    an ``ev`` kind and a monotonic ``ts`` (seconds since the recorder
    started).  ``write()`` dumps the whole log as JSONL — the
    ``events.jsonl`` format ``repro.obs.report`` renders (schema:
    docs/OBSERVABILITY.md, golden-tested in tests/test_obs.py).

``recording(...)`` / ``get_recorder()``
    The ambient-recorder contract: instrumentation calls ``event()`` /
    ``span()`` unconditionally, and they no-op (cheaply — one global
    read) when no recorder is active.  The driver, the engines and the
    benchmarks never need a recorder argument threaded through them.

``span(name)``
    A timed region.  ``elapsed`` is always measured (two
    ``perf_counter`` calls) so callers can use the span as their one
    wall-clock source whether or not a recorder is active — this is
    what replaced the hand-rolled timing blocks in ``core/scbf.py``.
    While a recorder is active every span is also a
    ``jax.profiler.TraceAnnotation`` of the same name, so a device
    profile (``jax.profiler.trace``) holds every span on its own clock,
    next to the device ops.

Everything here is host-only code: no jax arrays are touched, so the
module is trivially TL002/TL006-clean (docs/STATIC_ANALYSIS.md).
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Any, Dict, Iterable, List, Optional

# Version of the events.jsonl format, written into every log's leading
# ``meta`` event and checked by repro.obs.report.  Bump on any
# backwards-incompatible change to event kinds or required fields.
EVENT_SCHEMA = 1

EMITTER = f"repro.obs/{EVENT_SCHEMA}"


class Span:
    """One timed region.  ``elapsed`` is valid after the block exits."""

    __slots__ = ("name", "attrs", "t0", "elapsed")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.t0 = time.perf_counter()
        self.elapsed = 0.0

    def stop(self) -> float:
        self.elapsed = time.perf_counter() - self.t0
        return self.elapsed


class Recorder:
    """Append-only run event log with span/counter bookkeeping.

    ``path`` (optional) is where ``write()`` — and ``recording()`` on
    exit — dumps the JSONL stream.  Counters accumulate watchdog-style
    totals (events, spans, host offloads, compile deltas) that the
    driver folds into ``RunResult.telemetry`` at run end.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.events: List[Dict[str, Any]] = []
        self.counters: Dict[str, int] = {"events": 0, "spans": 0,
                                         "host_offloads": 0}
        self._t0 = time.perf_counter()
        self.events.append({"ev": "meta", "ts": 0.0,
                            "schema": EVENT_SCHEMA, "emitter": EMITTER})

    # ------------------------------------------------------------------
    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def event(self, kind: str, **fields) -> Dict[str, Any]:
        e = {"ev": kind, "ts": round(self._now(), 6), **fields}
        self.events.append(e)
        self.counters["events"] += 1
        return e

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        sp = Span(name, attrs)
        self.counters["spans"] += 1
        try:
            yield sp
        finally:
            sp.stop()
            self.event("span", name=name, dur=round(sp.elapsed, 6), **attrs)

    # ------------------------------------------------------------------
    def write(self, path: Optional[str] = None) -> str:
        """Dump the log as JSONL; returns the path written."""
        out = path or self.path
        if not out:
            raise ValueError("no output path: pass one to write() or to "
                             "the Recorder/recording() constructor")
        with open(out, "w") as fh:
            for e in self.events:
                fh.write(json.dumps(e) + "\n")
        return out


class _NullSpan(Span):
    """Span without an attached recorder — timing only."""


# The ambient recorder stack.  Plain module state, not a contextvar: the
# federated driver is single-threaded host code, and nesting (a bench
# recording around a run_federated recording) is LIFO by construction.
_STACK: List[Recorder] = []


def get_recorder() -> Optional[Recorder]:
    """The active recorder, or None when not recording."""
    return _STACK[-1] if _STACK else None


@contextlib.contextmanager
def recording(path: Optional[str] = None,
              recorder: Optional[Recorder] = None):
    """Activate a recorder for the block; write JSONL on exit if it has
    a path.  Yields the recorder."""
    rec = recorder if recorder is not None else Recorder(path)
    if path is not None and rec.path is None:
        rec.path = path
    _STACK.append(rec)
    try:
        yield rec
    finally:
        _STACK.pop()
        if rec.path:
            rec.write()


def event(kind: str, **fields) -> None:
    """Record an event on the active recorder; no-op when not recording."""
    rec = get_recorder()
    if rec is not None:
        rec.event(kind, **fields)


def count(name: str, n: int = 1) -> None:
    """Bump a watchdog counter on the active recorder (no-op inactive)."""
    rec = get_recorder()
    if rec is not None:
        rec.count(name, n)


@contextlib.contextmanager
def span(name: str, **attrs):
    """Timed region: always measures, records when a recorder is active.

    While recording, the region is also a ``jax.profiler.TraceAnnotation``
    of the same name, so profiles carry every span on the profiler's
    clock.  Without a recorder the span is two ``perf_counter`` calls
    and makes no jax call.
    """
    rec = get_recorder()
    if rec is None:
        sp = _NullSpan(name, attrs)
        try:
            yield sp
        finally:
            sp.stop()
        return
    import jax.profiler
    with jax.profiler.TraceAnnotation(name):
        with rec.span(name, **attrs) as sp:
            yield sp


# ---------------------------------------------------------------------------
# Chrome / Perfetto trace-event export
# ---------------------------------------------------------------------------

def to_chrome_trace(events: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Render an event stream as a Chrome trace-event JSON object.

    Loadable in Perfetto (ui.perfetto.dev) or chrome://tracing: spans
    become complete ('X') slices on one host track, everything else an
    instant ('i') event, timestamps in microseconds.  ``span`` events
    carry their end time in ``ts`` (they are emitted when the region
    closes), so the slice start is ``ts - dur``.
    """
    trace_events: List[Dict[str, Any]] = []
    for e in events:
        kind = e.get("ev")
        if kind == "meta":
            continue
        ts_us = float(e.get("ts", 0.0)) * 1e6
        args = {k: v for k, v in e.items() if k not in ("ev", "ts", "dur",
                                                        "name")}
        if kind == "span":
            dur_us = float(e.get("dur", 0.0)) * 1e6
            trace_events.append({
                "name": e.get("name", "span"), "ph": "X", "cat": "host",
                "ts": ts_us - dur_us, "dur": dur_us,
                "pid": 0, "tid": 0, "args": args})
        else:
            trace_events.append({
                "name": kind, "ph": "i", "s": "t", "cat": "event",
                "ts": ts_us, "pid": 0, "tid": 0, "args": args})
    return {"traceEvents": trace_events,
            "displayTimeUnit": "ms",
            "otherData": {"emitter": EMITTER}}
