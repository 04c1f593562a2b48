"""The build record: what each program JAX builds in this process cost, and
what caused it (docs/TELEMETRY.md "Tracing").

JAX reports the moment a program is built itself.  ``jax.monitoring``
delivers, on the building thread and in this order, the time spans
``jaxpr_trace_duration`` (``fun_name="scan_step"``),
``jaxpr_to_mlir_module_duration`` and ``backend_compile_duration``
(``fun_name="jit(scan_step)"``), each with its start and end on
``time.time()``: the clock a profiler's file uses, so a build lies on a
device trace with no conversion.  Between the start and the end of the
backend compile the persistent compile cache says what it did:
``compile_requests_use_cache`` (it was asked), ``cache_hits`` and
``cache_retrieval_time_sec``.  :class:`ProgramRecorder` joins them into one
record per program:

    {"event": "program", "seq": 17, "name": "scan_step",
     "t_start": 1790736293.5603, "t": 1790736293.5936,
     "trace_s": 0.0021, "lower_s": 0.0042, "build_s": 0.0257,
     "cache": "hit" | "miss" | "off", "cache_load_s": 0.0012,
     "region": "train.dispatch", "epoch": 0, "step": 0}

``region`` is the innermost ``utils/tracer`` region open on the building
thread; ``epoch`` and ``step`` are the logger's at that moment, None
before the trainer began an epoch (or with no logger yet).  ``seq`` counts
the process's builds, so a gap shows what a full backlog dropped.

The listeners are installed once, when this package is first imported:
``create_train_state``, resident staging and a corpus's eager ops build
before any :class:`~hydragnn_tpu.telemetry.logger.MetricsLogger` exists.
Until a logger with sinks adopts the recorder (``attach``), finished
records wait in a bounded list; it takes that backlog in order, then later
records directly, and hands the recorder back at ``finalize``.  With
telemetry off nothing is written and the list stays bounded.  A listener
does nothing outside a build and never raises into JAX: tracing must not
be able to break a compile.
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Deque, Dict

from hydragnn_tpu.utils import tracer

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BUILD = "/jax/core/compile/backend_compile_duration"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
BACKLOG = 4096      # records kept while no logger takes them
_STALE = 64         # traced-only functions (a jitted callee) kept a thread


def _bare(fun_name: str) -> str:
    """``jit(scan_step)`` -> ``scan_step``."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


class ProgramRecorder:
    """Joins JAX's build events into ``program`` records (module docstring).
    What a thread's build has reported so far is that thread's own; the
    backlog and the adopting logger are shared, under one lock."""

    def __init__(self, backlog: int = BACKLOG):
        self._lock = threading.Lock()
        self._here = threading.local()
        self._backlog: Deque[Dict[str, Any]] = collections.deque(
            maxlen=max(1, int(backlog)))
        self._logger = None
        self._seq = 0

    # -- what JAX reports (the building thread) -------------------------

    def _thread(self):
        here = self._here
        if not hasattr(here, "traced"):
            here.traced = {}        # name -> (start, seconds)
            here.lowered = {}       # name -> the record so far
            here.cache, here.cache_load_s = "off", 0.0
        return here

    def on_time_span(self, event: str, t0: float, t1: float,
                     fun_name: str = "", **_kw) -> None:
        if event == _TRACE:
            self._thread().traced[fun_name] = (t0, t1 - t0)
        elif event == _LOWER:
            here, name = self._thread(), _bare(fun_name)
            t_start, trace_s = here.traced.pop(name, (t0, 0.0))
            here.lowered[name] = {
                "name": name, "t_start": t_start,
                "trace_s": round(trace_s, 6), "lower_s": round(t1 - t0, 6)}
        elif event == _BUILD:
            here, name = self._thread(), _bare(fun_name)
            rec = here.lowered.pop(name, None) or {
                "name": name, "t_start": t0, "trace_s": 0.0, "lower_s": 0.0}
            rec.update(t=t1, build_s=round(t1 - t0, 6), cache=here.cache,
                       cache_load_s=round(here.cache_load_s, 6),
                       region=tracer.current())
            here.cache, here.cache_load_s = "off", 0.0
            for left in (here.traced, here.lowered):
                if len(left) > _STALE:
                    left.clear()
            self._finish(rec)

    def on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_ASKED:
            self._thread().cache = "miss"       # until it says otherwise
        elif event == _CACHE_HIT:
            self._thread().cache = "hit"

    def on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == _CACHE_LOAD:
            self._thread().cache_load_s = seconds

    # -- where a finished record goes -----------------------------------

    def _finish(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            self._seq += 1
            logger = self._logger
            if logger is not None and not logger.sinks:
                logger = None       # closed without a finalize
            epoch, step = logger.position() if logger else (None, None)
            rec = {"event": "program", "seq": self._seq, **rec,
                   "epoch": epoch, "step": step}
            if logger is None:
                self._backlog.append(rec)
            else:
                logger.emit_threadsafe(rec)

    def attach(self, logger) -> None:
        """``logger`` (one with sinks) takes the backlog, in order, and
        every later record until it detaches or another attaches."""
        with self._lock:
            while self._backlog:
                logger.emit_threadsafe(self._backlog.popleft())
            self._logger = logger

    def detach(self, logger) -> None:
        with self._lock:
            if self._logger is logger:
                self._logger = None

    def backlog(self) -> list:
        """The records no logger has taken yet, oldest first."""
        with self._lock:
            return list(self._backlog)


RECORDER = ProgramRecorder()


def _guarded(method):
    def listener(*args, **kwargs):
        try:
            method(*args, **kwargs)
        except Exception:  # graftlint: disable=ROB001 (a listener runs inside jax's compile path: a fault of the record must not become a fault of the build)
            pass

    return listener


_LISTENERS = (_guarded(RECORDER.on_time_span), _guarded(RECORDER.on_event),
              _guarded(RECORDER.on_duration))
_installed = False


def install() -> None:
    """Register the recorder's listeners with ``jax.monitoring`` (once,
    however often it is called)."""
    global _installed
    if _installed:
        return
    from jax import monitoring

    on_time_span, on_event, on_duration = _LISTENERS
    monitoring.register_event_time_span_listener(on_time_span)
    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    _installed = True


def uninstall() -> None:
    """Take the listeners out again (a test's: the programs must lower
    the same with them and without)."""
    global _installed
    if not _installed:
        return
    from jax import monitoring

    on_time_span, on_event, on_duration = _LISTENERS
    monitoring.unregister_event_time_span_listener(on_time_span)
    monitoring.unregister_event_listener(on_event)
    monitoring.unregister_event_duration_listener(on_duration)
    _installed = False


install()
