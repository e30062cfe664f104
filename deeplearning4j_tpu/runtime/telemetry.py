"""Unified run telemetry: span tracer, event journal, metrics registry.

PRs 1-5 each grew an isolated counter dataclass (``compile_metrics``,
``resilience_metrics``, ``serving_metrics``, ``dp_metrics``) with no
shared run identity, no timeline, and no way to answer "where did this
fit/request spend its time" short of a full ``jax.profiler`` trace.
Production-scale TPU systems live on exactly this layer — TensorFlow's
timeline/summary machinery (Abadi et al., arXiv:1605.08695) and the
serving-side SLO accounting of arXiv:2605.25645 are the models — and the
remaining roadmap items (continuous-batching SLOs, async checkpointing,
elastic re-meshing) all need a trustworthy event record to be verifiable.

Three pieces, all HOST-side (nothing here ever runs inside a jitted
region, touches a tracer value, or forces a device sync):

- :func:`span` — the ONE way program code opens a span.  One pair of
  ``time.perf_counter()`` reads per span serves three readers: the span
  always enters a ``jax.profiler.TraceAnnotation(name)`` (a no-op in
  C++ while no profiler session runs), so any session shows it on
  ``/host:CPU`` beside PJRT's events and on one clock with the device
  lines; with a :class:`Tracer` enabled it lands in the ring buffer;
  and where the call site names a ``counter`` its duration is added to
  that cumulative-seconds counter on exit, so a span and the counter
  read from it cannot disagree and no interval is timed twice.
- :class:`Tracer` — a run-scoped, thread-safe span recorder.  Spans nest
  via a thread-local stack, carry per-span attributes, and land in a
  bounded ring buffer (oldest
  records drop first; ``dropped`` counts the loss so a truncated journal
  is self-announcing).  ``ts`` is monotonic seconds since tracer
  creation; ``t_unix_ns`` is the same instant on the clock the profiler
  stamps host events with (``time.time_ns()``: an ``.xplane.pb`` event
  starts at the ``Task Environment`` plane's ``profile_start_time`` plus
  its ``start_ns``), taken through one anchor at tracer creation, so a
  journal and a trace can be laid over each other.
- Two exporters over the same record stream: an append-only JSONL
  **event journal** (one object per line, machine-greppable, the
  ``cli.py telemetry`` input) and a ``chrome://tracing``/Perfetto
  **trace JSON** (complete "X" slices + instant "i" events) that loads
  directly in https://ui.perfetto.dev.
- :class:`MetricsRegistry` — registers the four counter singletons and
  emits ONE consistent ``snapshot()``: run id, wall span, every
  counter family, deltas since ``mark()``, and device memory stats.
  ``compile_delta_since_mark()`` is the overhead gate primitive: a
  telemetry-on run must show delta == 0 against a telemetry-off run.

Overhead contract (the reason instrumentation can stay in hot host
loops): the tracer is DISABLED by default.  A span then costs one small
object, the annotation's enter and exit and two clock reads — about a
microsecond, at sites that run a few times per device dispatch — and
nothing is recorded, locked or kept.  Enabling the tracer changes no
jitted program (asserted by the CI overhead gate via
``compile_delta_since_mark``).
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from deeplearning4j_tpu.runtime.metrics import (checkpoint_metrics,
                                                compile_metrics,
                                                decode_metrics,
                                                device_memory_stats,
                                                dp_metrics,
                                                ingest_metrics,
                                                mfu_metrics,
                                                multihost_metrics,
                                                peak_bytes_in_use,
                                                resilience_metrics,
                                                serving_metrics)

#: default directory journals land in (gitignored); override with
#: $DL4J_TPU_TELEMETRY_DIR
DEFAULT_JOURNAL_DIR = os.environ.get("DL4J_TPU_TELEMETRY_DIR",
                                     ".dl4j_telemetry")

#: ring-buffer bound — a week-long serving process must not grow the
#: record list without bound; 64k spans ≈ a few tens of MB journal
DEFAULT_CAPACITY = 65536


def _new_run_id() -> str:
    return "run-%s-%04x" % (
        time.strftime("%Y%m%dT%H%M%S"), os.getpid() & 0xFFFF)


class Span:
    """One live span, opened by :func:`span` (or ``Tracer.span``) as a
    context manager.  ``set(**attrs)`` adds attributes mid-flight (e.g.
    byte counts known only after the work ran); ``discard()`` says the
    interval turned out not to be what the name says (a loop pass that
    found nothing to do): no record is kept and no counter moves.  On
    a span that is already over it takes back what the exit booked,
    the counter's seconds and the record, so a parent that discards
    itself can take the children that ended inside it along."""

    __slots__ = ("_tracer", "_ann", "_kept", "_over", "name", "counter",
                 "sid", "parent", "tid", "t0", "dur_s", "attrs")

    def __init__(self, tracer: "Optional[Tracer]", name: str,
                 counter: Optional[Tuple[Any, str]],
                 attrs: Dict[str, Any]):
        self._tracer = tracer
        self._ann = None
        self._kept = True
        self._over = False
        self.name = name
        self.counter = counter
        self.sid = self.parent = None
        self.tid = 0
        self.t0 = 0.0
        self.dur_s = 0.0
        self.attrs = attrs

    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def discard(self) -> None:
        if self._kept and self._over:
            if self.counter is not None:
                source, key = self.counter
                source.add_seconds(key, -self.dur_s)
            if self._tracer is not None:
                self._tracer._drop(self)
        self._kept = False

    def __enter__(self) -> "Span":
        if self._tracer is not None:
            self._tracer._push(self)
        # the annotation stamps its start when it is made, not when it
        # is entered: make it here, beside the clock read
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.dur_s = time.perf_counter() - self.t0
        self._ann.__exit__(exc_type, exc, tb)
        if self.counter is not None and self._kept:
            source, key = self.counter
            source.add_seconds(key, self.dur_s)
        if self._tracer is not None:
            if exc_type is not None:
                self.attrs.setdefault("error", exc_type.__name__)
            self._tracer._pop(self)
        self._over = True
        return False


class Tracer:
    """Run-scoped span/event recorder.  Thread-safe: spans nest per
    thread (thread-local stack), records append under a lock into a
    bounded ring buffer.  ``ts`` is ``time.perf_counter()`` seconds
    since tracer creation; ``t_unix_ns`` the same instant through the
    ``wall0_ns`` anchor, the clock a profiler trace is stamped with (the
    two clocks part by what the system slews its wall clock, parts in a
    million: take a fresh tracer for a journal that is laid over a
    trace)."""

    def __init__(self, run_id: Optional[str] = None,
                 capacity: int = DEFAULT_CAPACITY):
        self.run_id = run_id or _new_run_id()
        self.capacity = int(capacity)
        self._buf: "collections.deque[Dict[str, Any]]" = \
            collections.deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._sids = itertools.count(1)
        self._t0 = time.perf_counter()
        self.wall0_ns = time.time_ns()
        self.wall0 = self.wall0_ns / 1e9
        self.dropped = 0

    # -- span / event API --------------------------------------------------
    def span(self, name: str, *, counter: Optional[Tuple[Any, str]] = None,
             **attrs: Any) -> Span:
        """Open a span bound to THIS tracer (use as ``with
        tracer.span("fit") as sp:``); :func:`span` is the form program
        code uses.  Nesting is automatic: the parent is whatever span
        this THREAD has open when the span is entered."""
        return Span(self, name, counter, attrs)

    def event(self, name: str, **attrs: Any) -> None:
        """Record a point event (no duration): worker joins, rejections,
        checkpoint markers, ..."""
        self._record("event", name, time.perf_counter(), attrs)

    def completed(self, name: str, t_start: float, t_end: float,
                  **attrs: Any) -> None:
        """Record a span that is already over, from its two
        ``time.perf_counter()`` stamps: a wait whose start was another
        thread's (a request's time in the queue, known when it ends)."""
        self._record("span", name, t_start, attrs, sid=next(self._sids),
                     dur_ms=(t_end - t_start) * 1e3)

    def _at(self, t: float) -> Dict[str, Any]:
        """A ``perf_counter`` reading on the journal's two clocks."""
        ts = t - self._t0
        return {"ts": ts, "t_unix_ns": self.wall0_ns + int(ts * 1e9)}

    def _record(self, kind: str, name: str, t: float,
                attrs: Dict[str, Any], **more: Any) -> None:
        stack = getattr(self._local, "stack", None)
        self._append({
            "type": kind, "name": name, **more, **self._at(t),
            "tid": threading.get_ident(),
            "parent": stack[-1].sid if stack else None,
            "attrs": attrs,
        })

    # -- internals ---------------------------------------------------------
    def _push(self, span: Span) -> None:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        span.sid = next(self._sids)
        span.parent = stack[-1].sid if stack else None
        span.tid = threading.get_ident()
        stack.append(span)

    def _pop(self, span: Span) -> None:
        stack = getattr(self._local, "stack", None)
        if stack and stack[-1] is span:
            stack.pop()
        elif stack and span in stack:       # mis-nested exit: heal
            stack.remove(span)
        if not span._kept:
            self._drop(span)
            return
        self._append({
            "type": "span", "name": span.name, "sid": span.sid,
            "parent": span.parent, "tid": span.tid, **self._at(span.t0),
            "dur_ms": span.dur_s * 1e3,
            "attrs": span.attrs,
        })

    def _drop(self, span: Span) -> None:
        """Take back what was recorded under a discarded span: its own
        record, where it ended before it was discarded, and those of
        the spans that ended inside it (events stay: they happened).  A
        child's record lies before its parent's, so one pass from the
        newest record back to the span's start finds the whole family."""
        gone = {span.sid}
        t_lo = span.t0 - self._t0 - 1e-6
        with self._lock:
            kept = []
            while self._buf:
                rec = self._buf[-1]
                if rec["ts"] + rec.get("dur_ms", 0.0) / 1e3 < t_lo:
                    break
                self._buf.pop()
                if rec["type"] == "span" and (rec["sid"] in gone
                                              or rec["parent"] in gone):
                    gone.add(rec["sid"])
                else:
                    kept.append(rec)
            self._buf.extend(reversed(kept))

    def _append(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(rec)

    # -- reading -----------------------------------------------------------
    def records(self) -> List[Dict[str, Any]]:
        """Point-in-time copy of the buffered records (journal order)."""
        with self._lock:
            return list(self._buf)

    def count(self) -> int:
        """Buffered record count without copying the ring buffer."""
        with self._lock:
            return len(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self.dropped = 0

    # -- exporters ---------------------------------------------------------
    def _header(self) -> Dict[str, Any]:
        return {"type": "run", "run_id": self.run_id, "wall0": self.wall0,
                "dropped": self.dropped, "capacity": self.capacity}

    def export_journal(self, path: str,
                       snapshot: Optional[Dict[str, Any]] = None) -> str:
        """Append the run header + every buffered record (+ an optional
        registry ``snapshot``) to ``path`` as JSONL.  Append-only by
        contract: re-exporting or exporting several runs into one file
        keeps earlier lines intact (each run re-announces itself with a
        ``run`` header line)."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(self._header()) + "\n")
            for rec in self.records():
                f.write(json.dumps(rec, default=str) + "\n")
            if snapshot is not None:
                f.write(json.dumps({"type": "snapshot", **snapshot},
                                   default=str) + "\n")
        return path


def chrome_trace(records: List[Dict[str, Any]],
                 run_id: str = "run") -> Dict[str, Any]:
    """Convert journal records (span/event dicts) to the chrome trace
    event format Perfetto loads: complete slices (``ph: "X"``, µs
    timestamps/durations) for spans, thread-scoped instants (``ph: "i"``)
    for events, plus process/thread metadata.  ``cli.py telemetry
    --export-trace`` and ``tools/telemetry_gate.py`` write it out; a
    caller that dumps live records passes ``default=str`` (an
    attribute may be a numpy scalar).

    Multi-run journals (append-only export contract) map each run
    SEGMENT to its own Perfetto process: runs restart both sids and
    relative timestamps near zero, so sharing one track would render
    their slices superimposed and mis-nested."""
    # segment records by the run headers that precede them
    seg = 0
    seg_names: Dict[int, str] = {0: run_id}
    tagged: List[tuple] = []
    for r in records:
        kind = r.get("type")
        if kind == "run":
            seg += 1
            seg_names[seg] = str(r.get("run_id") or f"{run_id}#{seg}")
        elif kind in ("span", "event"):
            tagged.append((seg, r))

    events: List[Dict[str, Any]] = []
    for s in sorted({s for s, _ in tagged}) or [0]:
        events.append({"ph": "M", "pid": s + 1, "tid": 0,
                       "name": "process_name",
                       "args": {"name": "dl4j-tpu "
                                + seg_names.get(s, run_id)}})
    tid_map: Dict[tuple, int] = {}
    for s, r in tagged:
        key = (s, r.get("tid"))
        if key not in tid_map:
            tid_map[key] = len([k for k in tid_map if k[0] == s]) + 1
            events.append({"ph": "M", "pid": s + 1, "tid": tid_map[key],
                           "name": "thread_name",
                           "args": {"name": f"thread-{r.get('tid')}"}})
    for s, r in tagged:
        tid = tid_map[(s, r.get("tid"))]
        if r["type"] == "span":
            events.append({
                "ph": "X", "pid": s + 1, "tid": tid,
                "name": r["name"], "cat": r["name"].split(".")[0],
                "ts": r["ts"] * 1e6, "dur": r["dur_ms"] * 1e3,
                "args": r.get("attrs") or {},
            })
        else:
            events.append({
                "ph": "i", "s": "t", "pid": s + 1, "tid": tid,
                "name": r["name"], "cat": r["name"].split(".")[0],
                "ts": r["ts"] * 1e6,
                "args": r.get("attrs") or {},
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def read_journal(path: str) -> List[Dict[str, Any]]:
    """Load a JSONL journal back into record dicts."""
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------------------
# Module-level tracer: the global every instrumentation site consults
# ---------------------------------------------------------------------------

_TRACER: Optional[Tracer] = None


def get_tracer() -> Optional[Tracer]:
    """The active tracer, or None when telemetry is off."""
    return _TRACER


def enabled() -> bool:
    return _TRACER is not None


def enable(run_id: Optional[str] = None,
           capacity: int = DEFAULT_CAPACITY) -> Tracer:
    """Install (and return) the process-wide tracer.  Re-enabling
    replaces the previous tracer — export it first if its records
    matter."""
    global _TRACER
    _TRACER = Tracer(run_id=run_id, capacity=capacity)
    return _TRACER


def disable() -> Optional[Tracer]:
    """Uninstall the tracer; returns it so callers can still export."""
    global _TRACER
    t, _TRACER = _TRACER, None
    return t


def span(name: str, *, counter: Optional[Tuple[Any, str]] = None,
         **attrs: Any) -> Span:
    """The span primitive: ``with telemetry.span("decode.fetch",
    counter=(decode_metrics, "fetch_s")):``.  Always a profiler
    annotation; a journal record while a tracer is enabled; and where
    ``counter`` names ``(source, key)`` the span's seconds go to
    ``source.add_seconds(key, seconds)`` on exit."""
    return Span(_TRACER, name, counter, attrs)


def event(name: str, **attrs: Any) -> None:
    t = _TRACER
    if t is not None:
        t.event(name, **attrs)


def completed(name: str, t_start: float, t_end: float,
              **attrs: Any) -> None:
    """A span already over (``Tracer.completed``); nothing while off."""
    t = _TRACER
    if t is not None:
        t.completed(name, t_start, t_end, **attrs)


# ---------------------------------------------------------------------------
# MetricsRegistry — one snapshot over every counter family
# ---------------------------------------------------------------------------

def _numeric_delta(cur: Any, base: Any) -> Any:
    """Recursive ``cur - base`` over matching numeric leaves; non-numeric
    or structurally new values pass through as their current value."""
    if isinstance(cur, dict) and isinstance(base, dict):
        return {k: _numeric_delta(v, base.get(k)) for k, v in cur.items()}
    if isinstance(cur, bool) or isinstance(base, bool):
        return cur
    if isinstance(cur, (int, float)) and isinstance(base, (int, float)):
        return round(cur - base, 6) if isinstance(cur, float) \
            or isinstance(base, float) else cur - base
    return cur


class MetricsRegistry:
    """Named sources (anything with ``.snapshot() -> dict``) rolled into
    ONE consistent snapshot.  ``mark()`` banks the current state;
    later snapshots carry ``since_mark`` counter deltas, so a bench row
    or soak assertion reads one dict instead of diffing four singletons
    by hand."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sources: "Dict[str, Any]" = {}
        self._marks: Optional[Dict[str, Dict[str, Any]]] = None
        self._mark_t: Optional[float] = None
        self._t0 = time.monotonic()
        self.wall0 = time.time()

    def register(self, name: str, source: Any) -> None:
        """Register/replace a counter source.  ``source.snapshot()`` must
        return a (possibly nested) dict of scalars."""
        if not callable(getattr(source, "snapshot", None)):
            raise TypeError(f"source {name!r} has no snapshot() method")
        with self._lock:
            self._sources[name] = source

    def sources(self) -> List[str]:
        with self._lock:
            return sorted(self._sources)

    def _collect(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            items = list(self._sources.items())
        return {name: src.snapshot() for name, src in items}

    def mark(self) -> None:
        """Bank the current counters; later ``snapshot()`` calls report
        ``since_mark`` deltas against this point (and
        :meth:`compile_delta_since_mark` becomes meaningful)."""
        marks = self._collect()
        with self._lock:
            self._marks = marks
            self._mark_t = time.monotonic()

    def compile_delta_since_mark(self) -> Optional[int]:
        """XLA traces performed since ``mark()`` — None before any mark.
        THE overhead-gate primitive: telemetry on or off, a warmed fit or
        serving path must keep this at zero."""
        with self._lock:
            marks = self._marks
        if marks is None or "compile" not in marks:
            return None
        return (compile_metrics.snapshot()["compile_count"]
                - marks["compile"]["compile_count"])

    def snapshot(self) -> Dict[str, Any]:
        """One self-describing dict: run identity, wall span, every
        registered counter family, deltas since the last ``mark()``, and
        per-device memory (peak bytes where the backend reports it, an
        ``unsupported`` marker where it doesn't)."""
        counters = self._collect()
        tracer = _TRACER
        with self._lock:
            marks, mark_t = self._marks, self._mark_t
        out: Dict[str, Any] = {
            "run_id": tracer.run_id if tracer is not None else None,
            "telemetry_enabled": tracer is not None,
            "wall0": self.wall0,
            "wall_s": round(time.monotonic() - self._t0, 3),
            "counters": counters,
        }
        if marks is not None:
            out["since_mark"] = {
                name: _numeric_delta(snap, marks.get(name, {}))
                for name, snap in counters.items()}
            out["since_mark_wall_s"] = round(
                time.monotonic() - mark_t, 3)
        mem = device_memory_stats()
        out["device_memory"] = {
            "peak_bytes_in_use": peak_bytes_in_use(mem),
            "devices": mem,
        }
        if tracer is not None:
            out["spans_recorded"] = tracer.count()
            out["spans_dropped"] = tracer.dropped
        return out


#: process-wide registry pre-wired with the counter singletons —
#: the one-stop snapshot bench rows and the CLI read
registry = MetricsRegistry()
registry.register("compile", compile_metrics)
registry.register("resilience", resilience_metrics)
registry.register("serving", serving_metrics)
registry.register("decode", decode_metrics)
registry.register("dp", dp_metrics)
registry.register("checkpoint", checkpoint_metrics)
registry.register("mfu", mfu_metrics)
registry.register("multihost", multihost_metrics)
registry.register("ingest", ingest_metrics)


# ---------------------------------------------------------------------------
# Journal summarization (the `cli.py telemetry` engine — kept here so
# tests and notebooks can call it without the CLI)
# ---------------------------------------------------------------------------

def summarize_journal(records: List[Dict[str, Any]],
                      top_k: int = 10) -> Dict[str, Any]:
    """Digest a journal's records into the summary the CLI renders:

    - ``runs``: run-header metadata lines;
    - ``tree``: spans aggregated by (depth, name) with count/total/mean,
      children nested under their parent NAME (two spans with the same
      name and parent aggregate into one node);
    - ``top``: the ``top_k`` longest individual spans;
    - ``events``: per-name event counts;
    - ``counter_deltas``: numeric delta of the LAST snapshot record
      against the FIRST (one snapshot: reported as-is under
      ``counters``)."""
    # sids restart at 1 per Tracer, and journals are append-only across
    # runs — resolve parent links within each run SEGMENT (the records
    # between consecutive `run` headers) so multi-run journals never
    # cross-contaminate span trees
    seg = 0
    seg_of: Dict[int, int] = {}
    spans, events, snaps, runs = [], [], [], []
    for r in records:
        kind = r.get("type")
        if kind == "run":
            seg += 1
            runs.append(r)
        elif kind == "span":
            seg_of[id(r)] = seg
            spans.append(r)
        elif kind == "event":
            events.append(r)
        elif kind == "snapshot":
            snaps.append(r)

    by_sid = {(seg_of[id(r)], r["sid"]): r for r in spans if "sid" in r}

    def name_path(rec: Dict[str, Any]) -> tuple:
        s = seg_of[id(rec)]
        path = [rec["name"]]
        seen = {(s, rec.get("sid"))}
        parent = rec.get("parent")
        while parent is not None and (s, parent) in by_sid \
                and (s, parent) not in seen:
            seen.add((s, parent))
            rec = by_sid[(s, parent)]
            path.append(rec["name"])
            parent = rec.get("parent")
        return tuple(reversed(path))

    tree: Dict[tuple, Dict[str, Any]] = {}
    for r in spans:
        key = name_path(r)
        node = tree.setdefault(key, {"count": 0, "total_ms": 0.0,
                                     "max_ms": 0.0})
        node["count"] += 1
        node["total_ms"] += r["dur_ms"]
        node["max_ms"] = max(node["max_ms"], r["dur_ms"])
    tree_rows = [{
        "path": list(k), "depth": len(k) - 1, "name": k[-1],
        "count": v["count"], "total_ms": round(v["total_ms"], 3),
        "mean_ms": round(v["total_ms"] / v["count"], 3),
        "max_ms": round(v["max_ms"], 3),
    } for k, v in sorted(tree.items())]

    top = sorted(spans, key=lambda r: r["dur_ms"], reverse=True)[:top_k]
    ev_counts: Dict[str, int] = {}
    for e in events:
        ev_counts[e["name"]] = ev_counts.get(e["name"], 0) + 1

    out: Dict[str, Any] = {
        "runs": runs, "n_spans": len(spans), "n_events": len(events),
        "tree": tree_rows,
        "top": [{"name": r["name"], "dur_ms": round(r["dur_ms"], 3),
                 "ts": round(r["ts"], 4), "attrs": r.get("attrs") or {}}
                for r in top],
        "events": ev_counts,
    }
    if len(snaps) >= 2:
        out["counter_deltas"] = _numeric_delta(
            snaps[-1].get("counters", {}), snaps[0].get("counters", {}))
    elif snaps:
        out["counters"] = snaps[-1].get("counters", {})
    return out
