"""Profiler (reference: `python/paddle/profiler/profiler.py:349` + C++
`fluid/platform/profiler/`).

TPU-native: host spans are recorded by this module (HostTracer parity); device activity
comes from `jax.profiler` (XPlane — the CudaTracer/CUPTI analog), exported as a
TensorBoard trace directory.  `export_chrome_tracing` writes the host span tree in
chrome-tracing JSON, like ChromeTracingLogger.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from enum import Enum
from typing import Callable, Iterable, Optional


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class SortedKeys(Enum):
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


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


def make_scheduler(closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        period = closed + ready + record
        if repeat > 0 and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


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


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        fname = os.path.join(dir_name, f"{worker_name or 'worker'}_trace.json")
        prof._export_chrome(fname)
        print(f"[profiler] chrome trace written to {fname}")
    return handler


def load_profiler_result(filename: str):
    with open(filename) as f:
        return json.load(f)


class Profiler:
    def __init__(self, targets: Optional[Iterable] = None, scheduler=None,
                 on_trace_ready=None, record_shapes=False, profile_memory=False,
                 timer_only=False, emit_nvtx=False, custom_device_types=None,
                 with_flops=False, log_dir="profiler_log"):
        self._scheduler = scheduler if callable(scheduler) else (
            make_scheduler(*scheduler) if scheduler else (lambda step: ProfilerState.RECORD))
        self._on_trace_ready = on_trace_ready
        self._step = 0
        self._timer_only = timer_only
        self._log_dir = log_dir
        self._jax_dir = None
        self._state = ProfilerState.CLOSED

    def start(self):
        global _recording, _events
        _events = deque(maxlen=HOST_EVENT_CAP)
        _recording = True
        self._state = self._scheduler(self._step)
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
        if self._on_trace_ready:
            self._on_trace_ready(self)

    def step(self, num_samples=None):
        self._step += 1
        self._state = self._scheduler(self._step)

    def step_info(self, unit=None):
        return f"step {self._step}"

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def _export_chrome(self, fname):
        dump_chrome_trace(fname)

    def export(self, path, format="json"):
        self._export_chrome(path)

    def summary(self, sorted_by=SortedKeys.CPUTotal, op_detail=True, thread_sep=False,
                time_unit="ms"):
        from collections import defaultdict
        agg = defaultdict(lambda: [0, 0.0])
        for e in _events:
            agg[e.name][0] += 1
            agg[e.name][1] += (e.end - e.start) / 1e6
        lines = [f"{'name':40s} {'calls':>8s} {'total(ms)':>12s}"]
        for name, (calls, total) in sorted(agg.items(), key=lambda kv: -kv[1][1]):
            lines.append(f"{name[:40]:40s} {calls:8d} {total:12.3f}")
        table = "\n".join(lines)
        print(table)
        return table
