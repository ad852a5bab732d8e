"""Host-span recorder (reference: `python/paddle/profiler/profiler.py:349` + C++
`fluid/platform/profiler/` HostTracer).

TPU-native: host spans are recorded by this module and forwarded as
`jax.profiler.TraceAnnotation`s, so they land on the device trace's clock;
device activity comes from `jax.profiler` (XPlane — the CudaTracer/CUPTI
analog).  What is kept is what has a reader: `RecordEvent` behind the
`is_recording()` gate (the engine's and the trainer's spans), `Profiler` as
the switch (`start` / `stop` / context manager; `timer_only=True` records host
spans alone, which is how the benchmark turns the program's spans on beside
its own device trace) and `dump_chrome_trace` (`LLMEngine.trace()`'s
`host_trace.json`).
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque


class _HostEvent:
    __slots__ = ("name", "start", "end", "tid")

    def __init__(self, name, start, end, tid):
        self.name = name
        self.start = start
        self.end = end
        self.tid = tid


# Host-span buffer cap: recording is a ring over the newest spans, like the
# serving engine's step-trace ring — a trace window left open over a soak run
# must not grow host memory without bound (~10 engine spans per serving step).
HOST_EVENT_CAP = 1_000_000

_events = deque(maxlen=HOST_EVENT_CAP)
_recording = False
_TRACE_ANNOTATION = None        # cached jax.profiler.TraceAnnotation lookup


def is_recording() -> bool:
    """Whether a Profiler is currently collecting host spans — callers with
    spans on a hot path (the serving engine's per-step phases) gate span
    construction on this instead of paying RecordEvent setup every step."""
    return _recording


def _trace_annotation():
    # resolve jax.profiler.TraceAnnotation once per process; False caches a
    # failed import so a jax-less environment doesn't retry on every span
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        try:
            import jax.profiler
            _TRACE_ANNOTATION = jax.profiler.TraceAnnotation
        except Exception:
            _TRACE_ANNOTATION = False
    return _TRACE_ANNOTATION


class RecordEvent:
    """Span annotation (reference `RecordEvent`); also forwards to jax named scopes so
    spans appear in the XLA device trace."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._t0 = None
        self._scope = None

    def begin(self):
        self._t0 = time.perf_counter_ns()
        cls = _trace_annotation()
        if cls:
            try:
                self._scope = cls(self.name)
                self._scope.__enter__()
            except Exception:
                self._scope = None

    def end(self):
        if self._scope is not None:
            self._scope.__exit__(None, None, None)
        if _recording and self._t0 is not None:
            _events.append(_HostEvent(self.name, self._t0, time.perf_counter_ns(),
                                      threading.get_ident()))

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def dump_chrome_trace(fname: str) -> None:
    """Serialize the host spans recorded so far (the module event buffer) as
    chrome-tracing JSON — usable mid-recording, so a capture window nested
    inside a longer-running Profiler can snapshot without stopping it."""
    traceEvents = [{
        "name": e.name, "ph": "X", "ts": e.start / 1000.0,
        "dur": (e.end - e.start) / 1000.0, "pid": 0, "tid": e.tid,
    } for e in _events]
    with open(fname, "w") as f:
        json.dump({"traceEvents": traceEvents}, f)


class Profiler:
    """Switches host-span recording on and off; with `timer_only=False` also
    a `jax.profiler` device capture under `log_dir`."""

    def __init__(self, timer_only: bool = False,
                 log_dir: str = "profiler_log"):
        self._timer_only = timer_only
        self._log_dir = log_dir
        self._jax_dir = None

    def start(self):
        global _recording, _events
        _events = deque(maxlen=HOST_EVENT_CAP)
        _recording = True
        if not self._timer_only:
            # a device capture that was asked for and cannot start raises:
            # the trace is the source of every device metric, and a
            # host-only trace under its name would read as an idle chip
            import jax.profiler
            jax_dir = os.path.join(self._log_dir, f"jaxtrace_{int(time.time())}")
            jax.profiler.start_trace(jax_dir)
            self._jax_dir = jax_dir

    def stop(self):
        global _recording
        _recording = False
        if self._jax_dir is not None:
            import jax.profiler
            self._jax_dir = None
            jax.profiler.stop_trace()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False
