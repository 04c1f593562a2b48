"""Flight recorder: Dapper-style spans for the serve path and train-step
phase attribution (docs/TELEMETRY.md "Tracing").

A *span* is a named, monotonic-clock interval tied to a ``trace_id`` (one
per request / one per training run) and a ``span_id``; child spans carry
``parent_id`` and a flush span additionally *links* the N request traces
it served.  Finished spans land in three places at once:

  - a bounded, lock-guarded ring buffer (crash forensics, ``/metrics``
    percentiles) — same discipline as :class:`~hydragnn_tpu.telemetry
    .logger.RingBuffer` but thread-safe, because serve handler threads
    record concurrently;
  - per-name duration reservoirs for p50/p95/p99 breakdowns (queue-wait
    vs pad vs predict — the number buckettune needs);
  - the telemetry JSONL as ``event=span`` records via an injected emit
    callable (the MetricsLogger's sink fan-out), so one ``events.jsonl``
    holds steps, health events AND the trace — teleview correlates them
    offline and :func:`chrome_trace` exports the Chrome-trace/Perfetto
    ``traceEvents`` JSON.

Everything here is host-side bookkeeping: recording a span never touches
jax, and the default-off path allocates nothing (call sites gate on the
recorder being present — asserted byte-identical the same way the PR-15
dtype policy proves default-off purity).

Header contract (serve): ``X-Request-Id: <token>`` adopts the client's id
as the trace_id; ``traceparent: 00-<32hex>-<16hex>-<2hex>`` (W3C) adopts
trace_id + parent span.  Malformed values are *ignored*, never a 4xx —
tracing must not be able to break serving.  Every answer — 200 or
shed/timeout/breaker error — echoes the id back (``X-Request-Id`` header
+ ``trace_id`` body field) so a client can quote the id that maps to the
server-side trace.
"""

from __future__ import annotations

import os
import re
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "SpanContext",
    "SpanRecorder",
    "RegionSpans",
    "Span",
    "extract_trace_context",
    "chrome_trace",
    "quantile",
]


def _hex_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


def new_trace_id() -> str:
    return _hex_id(16)  # 32 hex chars (W3C trace-id width)


def new_span_id() -> str:
    return _hex_id(8)  # 16 hex chars (W3C parent-id width)


@dataclass
class SpanContext:
    """Identity a request carries across threads, retries and processes."""

    trace_id: str = field(default_factory=new_trace_id)
    parent_id: str = ""  # client's span id when propagated via traceparent
    minted: bool = True  # False when adopted from an incoming header

    def traceparent(self) -> str:
        parent = self.parent_id or new_span_id()
        return f"00-{self.trace_id}-{parent}-01"


# X-Request-Id tokens: printable, no header-splitting, bounded — anything
# else is treated as absent (mint instead).  Deliberately permissive about
# *format* (uuid, ulid, "req-123") so callers keep their own id scheme.
_REQUEST_ID_RE = re.compile(r"^[A-Za-z0-9._:-]{1,128}$")
_TRACEPARENT_RE = re.compile(
    r"^[0-9a-f]{2}-([0-9a-f]{32})-([0-9a-f]{16})-[0-9a-f]{2}$")


def extract_trace_context(headers, obj=None) -> SpanContext:
    """Adopt-or-mint the trace identity for one request.

    Precedence mirrors :func:`~hydragnn_tpu.serve.server
    .extract_deadline_s`: the ``traceparent`` header wins (it carries a
    parent span id too), then ``X-Request-Id``, then the ``trace_id``
    body field; otherwise a fresh id is minted.  Malformed values fall
    through silently — a bad header must not shed the request.
    """
    headers = headers or {}
    tp = headers.get("Traceparent") or headers.get("traceparent")
    if tp:
        m = _TRACEPARENT_RE.match(tp.strip().lower())
        if m:
            return SpanContext(trace_id=m.group(1), parent_id=m.group(2),
                               minted=False)
    rid = headers.get("X-Request-Id") or headers.get("x-request-id")
    if not rid and isinstance(obj, dict):
        rid = obj.get("trace_id")
    if rid and isinstance(rid, str) and _REQUEST_ID_RE.match(rid.strip()):
        return SpanContext(trace_id=rid.strip(), minted=False)
    return SpanContext()


@dataclass
class Span:
    """One open interval; finished (and made visible) by the recorder."""

    name: str
    trace_id: str
    span_id: str
    parent_id: str = ""
    t0: float = 0.0  # perf_counter at start
    attrs: Dict[str, Any] = field(default_factory=dict)
    links: List[str] = field(default_factory=list)  # linked trace_ids


def quantile(sorted_vals, q: float) -> float:
    """Nearest-rank quantile over an already-sorted list (no numpy — this
    runs inside the serve /metrics handler)."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, int(q * len(sorted_vals))))
    return float(sorted_vals[idx])


class SpanRecorder:
    """Bounded, lock-guarded flight recorder for finished spans.

    ``ring`` caps both the span ring and the per-name duration
    reservoirs, so a long-lived server holds O(ring × names) floats no
    matter how many requests pass through.  All mutation happens in
    :meth:`_record_locked` under ``self._lock`` (LCK001: handler
    threads, the batcher thread and the /metrics reader all touch the
    same buffers).
    """

    def __init__(self, ring: int = 512,
                 emit: Optional[Callable[[Dict[str, Any]], None]] = None):
        self._lock = threading.Lock()
        self._ring_cap = max(1, int(ring))
        self._spans: List[Dict[str, Any]] = []  # ring of finished records
        self._next = 0  # ring write cursor
        self._durations: Dict[str, List[float]] = {}  # name -> ms reservoir
        self._count: Dict[str, int] = {}  # name -> lifetime finish count
        self._emit = emit
        self._origin = time.perf_counter()  # monotonic t=0 for exports

    # -- recording ---------------------------------------------------------

    def start(self, name: str, trace_id: Optional[str] = None,
              parent_id: str = "", **attrs) -> Span:
        return Span(name=name, trace_id=trace_id or new_trace_id(),
                    span_id=new_span_id(), parent_id=parent_id,
                    t0=time.perf_counter(), attrs=dict(attrs))

    def finish(self, sp: Span, **attrs) -> Dict[str, Any]:
        """Close an open span: compute its duration, push it into the ring
        and the per-name reservoir, and emit the JSONL record."""
        if attrs:
            sp.attrs.update(attrs)
        return self._finish_at(sp, time.perf_counter())

    def record_interval(self, name: str, t_start: float, t_end: float,
                        trace_id: Optional[str] = None, parent_id: str = "",
                        links: Optional[List[str]] = None,
                        **attrs) -> Dict[str, Any]:
        """Record a span whose boundaries are already known (both from
        ``time.perf_counter()``) — the batcher reconstructs queue-wait and
        pad/predict phases retroactively at flush time, when the phase
        boundaries are finally known."""
        sp = Span(name=name, trace_id=trace_id or new_trace_id(),
                  span_id=new_span_id(), parent_id=parent_id,
                  t0=float(t_start), attrs=dict(attrs),
                  links=list(links or []))
        return self._finish_at(sp, float(t_end))

    def _finish_at(self, sp: Span, t1: float) -> Dict[str, Any]:
        rec = {
            "event": "span",
            "name": sp.name,
            "trace_id": sp.trace_id,
            "span_id": sp.span_id,
            "t_start_s": round(sp.t0 - self._origin, 6),
            "dur_ms": round(max(t1 - sp.t0, 0.0) * 1e3, 4),
        }
        if sp.parent_id:
            rec["parent_id"] = sp.parent_id
        if sp.links:
            rec["links"] = list(sp.links)
        rec.update(sp.attrs)
        with self._lock:
            self._record_locked(rec)
        if self._emit is not None:
            self._emit(rec)
        return rec

    def _record_locked(self, rec: Dict[str, Any]) -> None:
        # bounded ring: overwrite-oldest once full (no unbounded growth
        # under a flood — the exact failure mode the shed path protects
        # the queue from applies to the recorder too)
        if len(self._spans) < self._ring_cap:
            self._spans.append(rec)
        else:
            self._spans[self._next % self._ring_cap] = rec
        self._next += 1
        res = self._durations.setdefault(rec["name"], [])
        if len(res) >= self._ring_cap:
            del res[0: len(res) - self._ring_cap + 1]
        res.append(rec["dur_ms"])
        self._count[rec["name"]] = self._count.get(rec["name"], 0) + 1

    @contextmanager
    def span(self, name: str, trace_id: Optional[str] = None,
             parent_id: str = "", **attrs):
        """``with rec.span("serve.predict", trace_id=...) as sp:`` — the
        span closes (and records) on exit, exceptions included."""
        sp = self.start(name, trace_id=trace_id, parent_id=parent_id,
                        **attrs)
        try:
            yield sp
        finally:
            self.finish(sp)

    # -- reading -----------------------------------------------------------

    def snapshot(self) -> List[Dict[str, Any]]:
        """Finished spans, oldest-first, bounded by the ring cap."""
        with self._lock:
            if self._next <= self._ring_cap:
                return list(self._spans)
            cut = self._next % self._ring_cap
            return self._spans[cut:] + self._spans[:cut]

    def percentiles(self) -> Dict[str, Dict[str, float]]:
        """{name: {count, p50_ms, p95_ms, p99_ms, max_ms}} over the
        per-name reservoirs — the /metrics span-breakdown block."""
        with self._lock:
            items = [(n, sorted(v), self._count.get(n, 0))
                     for n, v in self._durations.items() if v]
        return {
            n: {
                "count": c,
                "p50_ms": round(quantile(v, 0.50), 4),
                "p95_ms": round(quantile(v, 0.95), 4),
                "p99_ms": round(quantile(v, 0.99), 4),
                "max_ms": round(v[-1], 4),
            }
            for n, v, c in items
        }

    def summary(self) -> Dict[str, Any]:
        """Manifest block: recorded total + per-name percentiles."""
        with self._lock:
            total = self._next
        return {"recorded": total, "by_name": self.percentiles()}


class RegionSpans:
    """The ``utils/tracer`` tracer that turns the trainer's host regions
    (``train.dispatch``, ``epoch.fetch``, ``data.collate`` ...) into
    spans of one recorder, all on one ``trace_id``: the trainer registers
    it while ``Telemetry.trace`` is on.  It only reads a clock, so the
    traced trainer is the same program as the untraced one."""

    def __init__(self, recorder: SpanRecorder):
        from hydragnn_tpu.utils.tracer import OpenRegions

        self._rec = recorder
        self._trace_id = new_trace_id()
        self._open = OpenRegions()

    def start(self, name: str) -> None:
        self._open.push(name, time.perf_counter())

    def stop(self, name: str) -> None:
        t0 = self._open.pop(name)
        if t0 is not None:
            self._rec.record_interval(  # graftlint: disable=REG006 (a region's name is checked where the trainer opens it)
                name, t0, time.perf_counter(), trace_id=self._trace_id)

    def reset(self) -> None:
        pass


def chrome_trace(records) -> Dict[str, Any]:
    """Render ``event=span`` JSONL records as Chrome-trace JSON
    (``chrome://tracing`` / Perfetto "open trace file").

    Spans become complete (``ph="X"``) events; one pseudo-process per
    span-name family (``serve.*`` / ``train.*`` / ``comm.*``) and one
    pseudo-thread per trace_id keep concurrent requests on separate
    tracks.  Timestamps are microseconds from the recorder origin.
    """
    events = []
    tids: Dict[str, int] = {}
    for r in records:
        if r.get("event") != "span":
            continue
        fam = str(r.get("name", "")).split(".", 1)[0] or "span"
        tid = tids.setdefault(r.get("trace_id", ""), len(tids) + 1)
        args = {k: v for k, v in r.items()
                if k not in ("event", "name", "t_start_s", "dur_ms")}
        events.append({
            "name": r.get("name", "span"),
            "cat": fam,
            "ph": "X",
            "ts": round(float(r.get("t_start_s", 0.0)) * 1e6, 1),
            "dur": round(float(r.get("dur_ms", 0.0)) * 1e3, 1),
            "pid": fam,
            "tid": tid,
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}
