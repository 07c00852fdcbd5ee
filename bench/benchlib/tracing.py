"""The profiler round a traced window, and the benchmark's host spans.

`span(name)` is a `jax.profiler.TraceAnnotation` named `bench:<name>`; it
costs next to nothing while no trace is being taken. `wrap(obj, attr, name)`
puts such a span round a bound method from the outside (`--trace 1`; under
`--trace 2` the program's own `trlx:` spans take their place). The profiler
is started and stopped through the program's one control,
`trlx_tpu.observability.tracing`."""

import functools
import shutil
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


def _control():
    """The program's one control of the profiler (imported late: this module
    is loaded before the program is)."""
    from trlx_tpu.observability import tracing

    return tracing


class TracedWindow:
    """start() ... stop() round the traced part of a run; `trace` is then the
    neutral structure bench/trace/reduce.py works on. The Python tracer is
    off (it hooks every call); TraceMe spans and the device are on. The
    `bench:window` span is what the reduction takes as the traced window: it
    opens with start(), or later with open() where the run wants the
    profiler's start-up left out of it."""

    def __init__(self):
        self.trace = None
        self.t0 = self.t1 = None  # time.monotonic() just inside the window span
        self._window = None

    def start(self, open_window: bool = True):
        _control().start()  # into a temporary directory of its own
        if open_window:
            self.open()

    def open(self):
        self._window = span("window")
        self._window.__enter__()
        self.t0 = time.monotonic()

    def stop(self):
        self.t1 = time.monotonic()
        self._window.__exit__(None, None, None)
        trace_dir = _control().stop()
        reduce = load_module("trace/reduce.py")
        try:
            self.trace = reduce.load_xplane(reduce.find_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        return self.trace
