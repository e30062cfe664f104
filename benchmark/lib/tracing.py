"""A short profiler trace inside a window: start, stop, reduce, delete."""

from __future__ import annotations

import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, Optional

from benchmark.lib import trace_reduce


class WindowTrace:
    """Traces ``[at, at + length)`` seconds of a window that began at
    ``t0``.  Drive it with ``poll(now)`` between calls (or sleep on
    ``next_edge``) and call ``finish`` when the window has closed;
    ``reduce()`` then reads the trace (slow: never inside a window) and
    deletes it.  ``span`` is the traced interval on the host clock;
    ``cut`` is the wider one from before the profiler was started to
    after it had stopped, which a share of peak taken over the window
    leaves out (starting and stopping cost seconds no untraced run
    pays).

    ``count``, where given, is a counter of the program's that has to
    move inside the span (the dispatches of a program whose reader
    would otherwise find nothing to read): the trace then runs on past
    ``length`` until a poll has seen it move, and ``GRACE`` more for
    that dispatch to end, but stops ``at_most`` seconds after its start
    whatever the counter says."""

    GRACE = 0.25

    def __init__(self, enabled: bool, t0: float, at: float, length: float,
                 count: Optional[Callable[[], int]] = None,
                 at_most: Optional[float] = None):
        self.enabled = enabled
        self._start = t0 + at
        self._stop = t0 + at + length
        self._latest = t0 + at + max(length, at_most or length)
        self._count = count
        self._dir: Optional[str] = None
        self._annotation = None
        self.span: Optional[tuple] = None
        self.cut: Optional[tuple] = None
        self.done = not enabled

    def next_edge(self) -> Optional[float]:
        if self.done:
            return None
        if self._dir is None:
            return self._start
        return min(self._stop, self._latest)

    def poll(self, now: float) -> None:
        import jax

        if self.done:
            return
        if self._dir is None:
            if now < self._start:
                return
            self._t_cut = time.perf_counter()
            self._dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            self._annotation = jax.profiler.TraceAnnotation(
                trace_reduce.WINDOW_SPAN)
            self._annotation.__enter__()
            self._t_lo = time.perf_counter()
            self._count0 = self._count() if self._count else None
        elif self._count and now < self._latest:
            # not seen yet: look again then; seen: that long for it to end
            self._stop = max(self._stop, now + self.GRACE)
            if self._count() != self._count0:
                self._count = None
        elif now >= min(self._stop, self._latest):
            self.finish()

    def finish(self) -> None:
        """Stop the trace if it runs.  Safe to call twice."""
        import jax

        if self.done:
            return
        self.done = True
        if self._dir is None:
            return
        t_hi = time.perf_counter()
        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.span = (self._t_lo, t_hi)
        self.cut = (self._t_cut, time.perf_counter())

    def reduce(self) -> Optional[Dict[str, Any]]:
        """The trace's table (``None`` without a trace), the files gone."""
        if self.span is None:
            return None
        try:
            return trace_reduce.reduce_dir(self._dir)
        except Exception as e:  # noqa: BLE001 — a trace that cannot be read is reported, not fatal
            print(f"[trace] reduction failed: {e!r}", file=sys.stderr)
            return None
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
