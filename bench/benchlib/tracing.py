"""The profiler round a traced window, and the benchmark's host spans.

`span(name)` is a `jax.profiler.TraceAnnotation` named `bench:<name>`; it
costs next to nothing while no trace is being taken. `wrap(obj, attr, name)`
puts such a span round a bound method from the outside, so that the program
carries no name of the benchmark's."""

import functools
import shutil
import tempfile
import time

from benchlib.files import load_module

SPAN_PREFIX = "bench:"


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def wrap(obj, attr: str, name: str):
    fn = getattr(obj, attr)

    @functools.wraps(fn)
    def spanned(*args, **kwargs):
        with span(name):
            return fn(*args, **kwargs)

    setattr(obj, attr, spanned)


class TracedWindow:
    """start() ... stop() round the traced part of a run; `trace` is then the
    neutral structure bench/trace/reduce.py works on. The Python tracer is
    off (it hooks every call); TraceMe spans and the device are on."""

    def __init__(self):
        self.dir = None
        self.trace = None
        self.t0 = self.t1 = None  # time.monotonic() just inside the window span

    def start(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = span("window")
        self._window.__enter__()
        self.t0 = time.monotonic()

    def stop(self):
        import jax

        self.t1 = time.monotonic()
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        reduce = load_module("trace/reduce.py")
        try:
            self.trace = reduce.load_xplane(reduce.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.trace
