"""Pluggable region tracer (parity: reference hydragnn/utils/tracer.py:40-155).

Module-level ``start``/``stop`` fan out to registered tracers.  The built-in
tracers are :class:`TimerTracer` (cumulative wall-clock regions, the GPTL
analog) and :class:`JaxProfilerTracer` (wraps regions in
``jax.profiler.TraceAnnotation`` so they show in TensorBoard/Perfetto traces).
A ``@profile`` decorator and ``timer`` contextmanager mirror the reference API.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Dict, List, Optional, Tuple

_tracers: Dict[str, "Tracer"] = {}
_enabled = True
# the regions open on each thread, outermost first: kept by ``start`` and
# ``stop`` themselves, whichever tracers are registered, so that a record
# made on a thread can name the region that caused it (``current``)
_open_here = threading.local()


class Tracer:
    def start(self, name: str):  # pragma: no cover - interface
        ...

    def stop(self, name: str):  # pragma: no cover - interface
        ...

    def reset(self):
        ...


class OpenRegions:
    """What a tracer holds per open region, kept as one stack per
    (thread, name): regions nest, the same name may be open twice, and a
    loader's prefetch thread cannot close the trainer thread's region."""

    def __init__(self):
        self._open: Dict[Tuple[int, str], List[object]] = {}

    def push(self, name: str, item) -> None:
        self._open.setdefault(
            (threading.get_ident(), name), []).append(item)

    def pop(self, name: str):
        """The innermost open ``name`` of this thread, or None."""
        stack = self._open.get((threading.get_ident(), name))
        return stack.pop() if stack else None

    def clear(self) -> None:
        self._open.clear()


class TimerTracer(Tracer):
    """Named cumulative wall-clock regions (GPTL-style)."""

    def __init__(self):
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self._open = OpenRegions()
        self._lock = threading.Lock()

    def start(self, name: str):
        self._open.push(name, time.perf_counter())

    def stop(self, name: str):
        t0 = self._open.pop(name)
        if t0 is None:
            return
        dt = time.perf_counter() - t0
        with self._lock:
            self.totals[name] = self.totals.get(name, 0.0) + dt
            self.counts[name] = self.counts.get(name, 0) + 1

    def reset(self):
        with self._lock:
            self.totals.clear()
            self.counts.clear()
        self._open.clear()

    def summary(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                k: {"total_s": v, "count": self.counts.get(k, 0)}
                for k, v in sorted(self.totals.items())
            }


class JaxProfilerTracer(Tracer):
    """Region names become jax.profiler trace annotations: the regions
    land in the ``/host:CPU`` plane of a profiler trace, on the clock the
    device planes use."""

    def __init__(self):
        self._open = OpenRegions()

    def start(self, name: str):
        import jax.profiler

        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        self._open.push(name, ann)

    def stop(self, name: str):
        ann = self._open.pop(name)
        if ann is not None:
            ann.__exit__(None, None, None)


def initialize(timer: bool = True, jax_annotations: bool = False) -> None:
    _tracers.clear()
    if timer:
        _tracers["timer"] = TimerTracer()
    if jax_annotations:
        _tracers["jax"] = JaxProfilerTracer()


def register(name: str, tracer: Tracer) -> None:
    """Plug ``tracer`` in under ``name`` (replacing that name's)."""
    _tracers[name] = tracer


def unregister(name: str) -> None:
    _tracers.pop(name, None)


def has(name: str) -> bool:
    return name in _tracers


def get(name: str) -> Optional[Tracer]:
    return _tracers.get(name)


def enable():
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def start(name: str):
    if _enabled:
        # a copy: another thread may register or unregister meanwhile
        for t in tuple(_tracers.values()):
            t.start(name)
    # after the tracers: one that refuses the region by raising (the
    # benchmark's clock ends the job so) leaves none open
    try:
        _open_here.stack.append(name)
    except AttributeError:
        _open_here.stack = [name]


def stop(name: str):
    stack = getattr(_open_here, "stack", ())
    # innermost first: as a rule the top; closed out of order, the
    # region's last opening; a stop too many finds none
    for i in range(len(stack) - 1, -1, -1):
        if stack[i] == name:
            del stack[i]
            break
    if _enabled:
        for t in tuple(_tracers.values()):
            t.stop(name)


def open_regions() -> Tuple[str, ...]:
    """The regions open on the calling thread, outermost first."""
    return tuple(getattr(_open_here, "stack", ()))


def current() -> Optional[str]:
    """The innermost region open on the calling thread, or None."""
    stack = getattr(_open_here, "stack", None)
    return stack[-1] if stack else None


def close_to(depth: int) -> None:
    """Close what the calling thread opened beyond its first ``depth``
    regions, innermost first: an epoch loop that an exception left closes
    the regions it was in, so that no later record names them."""
    for name in reversed(open_regions()[depth:]):
        stop(name)


def reset():
    for t in _tracers.values():
        t.reset()


def profile(name: str):
    """Decorator: trace the wrapped call (reference tracer.py:132-144)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            start(name)
            try:
                return fn(*args, **kwargs)
            finally:
                stop(name)

        return wrapped

    return deco


@contextlib.contextmanager
def timer(name: str):
    start(name)
    try:
        yield
    finally:
        stop(name)


def print_timers(verbosity: int = 0):
    t = _tracers.get("timer")
    if t is None:
        return
    from hydragnn_tpu.utils.print_utils import print_distributed

    for name, s in t.summary().items():
        print_distributed(
            verbosity,
            f"Timer {name}: total {s['total_s']:.4f}s over {int(s['count'])} calls",
        )


# default: timers on
initialize()
