"""The traced slice: the profiler on for the last seconds of the window, the
program's host spans switched on with it, and the harness's own
`bench.slice` span around it."""
from __future__ import annotations

import time

from . import xplane


class Tracer:
    wanted = True

    def __init__(self, out_dir: str, span_switch=None):
        self.out_dir = out_dir
        self.span_switch = span_switch    # program's span recorder (start/stop)
        self.t_start = self.t_stop = None
        self._span = None

    @property
    def on(self) -> bool:
        return self.t_start is not None and self.t_stop is None

    def start(self) -> None:
        import jax.profiler
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        if self.span_switch is not None:
            self.span_switch.start()
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(xplane.SLICE_SPAN)
        self._span.__enter__()
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        import jax.profiler
        self.t_stop = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        if self.span_switch is not None:
            self.span_switch.stop()

    def load(self) -> dict:
        return xplane.load(xplane.find_xplane(self.out_dir))


class NoTracer:
    wanted = on = False
    t_start = t_stop = None

    def start(self) -> None:
        pass

    def stop(self) -> None:
        pass
