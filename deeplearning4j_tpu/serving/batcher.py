"""DynamicBatcher — coalesce concurrent requests into micro-batches.

Serving heavy traffic from many small clients one request at a time
wastes the MXU: a 1-row forward costs the same dispatch as a 64-row
one.  The batcher is the standard
dynamic-batching policy: a background thread collects requests that
arrive within a ``max_delay_ms`` window (or until ``max_batch_size``
rows accumulate), concatenates them into ONE bucketed engine dispatch,
and resolves each caller's future with exactly its own result rows.

Policy knobs:
- ``max_batch_size``: flush as soon as this many rows are queued;
- ``max_delay_ms``: a lone request never waits longer than this — the
  latency bound traded for coalescing;
- per-request ``deadline_ms`` (optional): a request still queued past
  its deadline resolves with the typed ``DeadlineExceeded`` instead of
  spending MXU time on an answer nobody is waiting for.

Each request is an [n, ...] batch (or a single example of the model's
per-example shape, returned unbatched).  Results are host numpy: the
batcher syncs the device result before resolving futures, so a resolved
future is an honest end-to-end latency sample
(``runtime.metrics.serving_metrics`` records p50/p99, queue depth, and
batches formed).

Thread-safety: ``submit`` may be called from any number of threads; one
worker thread owns the queue drain and the engine dispatch order, so
per-thread result ordering is preserved by construction.  The lock
discipline (every shared mutation under ``self._cv``, no blocking wait
while holding it) is machine-checked by jaxlint's concurrency family
(``unlocked-shared-mutation``, ``blocking-under-lock``).
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Any, List, Optional

import numpy as np

from deeplearning4j_tpu.runtime import telemetry
from deeplearning4j_tpu.runtime.metrics import (decode_metrics,
                                                serving_metrics)
from deeplearning4j_tpu.serving.decode import BatcherClosed, DeadlineExceeded
from deeplearning4j_tpu.serving.engine import InferenceEngine


class _Request:
    __slots__ = ("x", "rows", "single", "future", "t_submit", "deadline")

    def __init__(self, x: np.ndarray, single: bool,
                 deadline_ms: Optional[float]):
        self.x = x
        self.rows = x.shape[0]
        self.single = single
        self.future: Future = Future()
        self.t_submit = time.perf_counter()
        self.deadline = None if deadline_ms is None \
            else self.t_submit + deadline_ms / 1e3


class DynamicBatcher:
    def __init__(self, engine: InferenceEngine, *,
                 max_batch_size: int = 64, max_delay_ms: float = 2.0,
                 params: Any = None):
        if max_batch_size < 1:
            raise ValueError("max_batch_size must be >= 1")
        self.engine = engine
        self.max_batch_size = max_batch_size
        self.max_delay_s = max(max_delay_ms, 0.0) / 1e3
        self._params = params
        self._cv = threading.Condition()
        self._pending: List[_Request] = []
        self._open = True
        self._thread = threading.Thread(
            target=self._loop, name="dl4j-serving-batcher", daemon=True)
        self._thread.start()

    # -- client side -------------------------------------------------------
    def submit(self, x, *, deadline_ms: Optional[float] = None) -> Future:
        """Enqueue one request; returns a Future resolving to its result
        rows (numpy).  A 1-D/example-shaped input (one rank below the
        first pending batch's rank is not knowable here, so: anything the
        caller flags by passing ``np.ndarray`` without a batch dim must
        be pre-batched — except scalars-per-example models; see
        ``submit_one``).  ``deadline_ms``: a request still queued past
        its deadline resolves with ``DeadlineExceeded`` instead of
        joining a cohort."""
        return self._submit(np.asarray(x), single=False,
                            deadline_ms=deadline_ms)

    def submit_one(self, example, *,
                   deadline_ms: Optional[float] = None) -> Future:
        """Enqueue a single UNBATCHED example; the future resolves to its
        unbatched result (row 0 of the model output)."""
        return self._submit(np.asarray(example)[None], single=True,
                            deadline_ms=deadline_ms)

    def _submit(self, x: np.ndarray, single: bool,
                deadline_ms: Optional[float] = None) -> Future:
        # reject against the engine's known input spec HERE, before the
        # request can ever join (and poison, or be poisoned by) a
        # coalescing window — with a warmed engine this is the authority
        # on what the model serves
        spec = self.engine.input_spec
        if spec is not None and (x.shape[1:], np.dtype(x.dtype)) != \
                (spec[0], np.dtype(spec[1])):
            raise ValueError(
                f"request per-example shape {x.shape[1:]}/{x.dtype} does "
                f"not match the engine's {spec[0]}/{spec[1]}")
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError("deadline_ms must be > 0")
        req = _Request(x, single, deadline_ms)
        with self._cv:
            if not self._open:
                raise BatcherClosed("DynamicBatcher is closed")
            self._pending.append(req)
            serving_metrics.note_request(req.rows)
            serving_metrics.note_queue_depth(len(self._pending))
            depth = len(self._pending)
            self._cv.notify()
        tr = telemetry.get_tracer()
        if tr is not None:
            tr.event("serving.enqueue", rows=req.rows, queue_depth=depth)
        return req.future

    def infer(self, x, timeout: Optional[float] = 30.0):
        """Blocking convenience: submit + wait."""
        return self.submit(x).result(timeout)

    def infer_one(self, example, timeout: Optional[float] = 30.0):
        return self.submit_one(example).result(timeout)

    # -- worker side -------------------------------------------------------
    def _take_batch(self) -> List[_Request]:
        """Block for the first request, then keep the window open until
        max_delay or max_batch_size rows; pop whole requests (the first
        is always taken, however large — the engine chunks oversize
        batches itself)."""
        with self._cv:
            while self._open and not self._pending:
                self._cv.wait()
            if not self._pending:
                return []                      # closed and drained
            deadline = self._pending[0].t_submit + self.max_delay_s
            while (sum(r.rows for r in self._pending) < self.max_batch_size
                   and self._open):
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                self._cv.wait(timeout=remaining)
            batch: List[_Request] = []
            rows = 0
            while self._pending:
                nxt = self._pending[0]
                if batch and rows + nxt.rows > self.max_batch_size:
                    break
                batch.append(self._pending.pop(0))
                rows += nxt.rows
            serving_metrics.note_queue_depth(len(self._pending))
            return batch

    def _reject_mismatched(self, batch: List[_Request]) -> List[_Request]:
        """Pre-warmup fallback for cohort protection (the authoritative
        check is submit-time validation against ``engine.input_spec``):
        split the window on the engine spec if it became known, else on
        the first request's trailing shape — in the worst un-warmed
        case a malformed FIRST request fails its cohort's window, which
        is why serving processes should ``warmup()`` before traffic."""
        spec = self.engine.input_spec
        head = (spec[0], np.dtype(spec[1])) if spec is not None \
            else (batch[0].x.shape[1:], batch[0].x.dtype)
        keep: List[_Request] = []
        for r in batch:
            if (r.x.shape[1:], np.dtype(r.x.dtype)) == head:
                keep.append(r)
            elif r.future.set_running_or_notify_cancel():
                r.future.set_exception(ValueError(
                    f"request shape {r.x.shape[1:]}/{r.x.dtype} does not "
                    f"match the batch's {head[0]}/{head[1]}"))
        return keep

    def _expire(self, batch: List[_Request]) -> List[_Request]:
        """Resolve requests whose deadline passed while queued with the
        typed ``DeadlineExceeded`` instead of spending a dispatch on
        rows nobody is waiting for; booked on the serving family's
        decode-shared failure counter."""
        now = time.perf_counter()
        keep: List[_Request] = []
        for r in batch:
            if r.deadline is None or now <= r.deadline:
                keep.append(r)
            elif r.future.set_running_or_notify_cancel():
                elapsed_ms = (now - r.t_submit) * 1e3
                deadline_ms = (r.deadline - r.t_submit) * 1e3
                r.future.set_exception(DeadlineExceeded(
                    deadline_ms=deadline_ms, elapsed_ms=elapsed_ms,
                    tokens_emitted=0))
                # fault-tolerance failure counters ride the decode
                # family (one serving-wide home; see runtime/metrics.py)
                decode_metrics.note_deadline_expiration()
                tr = telemetry.get_tracer()
                if tr is not None:
                    tr.event("serving.deadline_exceeded", rows=r.rows,
                             elapsed_ms=round(elapsed_ms, 3))
        return keep

    def _loop(self) -> None:
        import jax

        while True:
            batch = self._take_batch()
            if not batch:
                return
            batch = self._expire(self._reject_mismatched(batch))
            if not batch:
                continue
            # book only what actually dispatches: rejected requests (and
            # all-rejected windows) must not inflate the coalescing
            # evidence the bench row reports
            serving_metrics.note_batch(len(batch))
            tr = telemetry.get_tracer()
            cohort = {}
            if tr is not None:
                # queue age of the cohort = how long its OLDEST request
                # waited for the window to close (the coalescing latency
                # the max_delay_ms knob trades throughput against).
                # Computed ONLY under an active tracer: the disabled
                # path must stay free of per-cohort bookkeeping.
                age_ms = (time.perf_counter()
                          - min(r.t_submit for r in batch)) * 1e3
                cohort = {"n_requests": len(batch),
                          "rows": sum(r.rows for r in batch),
                          "queue_age_ms": round(age_ms, 3)}
                tr.event("serving.cohort_formed", **cohort)
            with telemetry.span("serving.cohort", **cohort):
                try:
                    xs = np.concatenate([r.x for r in batch], axis=0) \
                        if len(batch) > 1 else batch[0].x
                    # count_request=False: each client request was already
                    # counted at submit; the coalesced dispatch is not a
                    # new request
                    out = self.engine.infer(xs, params=self._params,
                                            sync=True, count_request=False)
                    # materialize once, leaf-wise: single-array models
                    # resolve to np arrays, pytree outputs keep their
                    # structure with each leaf row-sliced per request —
                    # host numpy results ARE this batcher's contract
                    # (module docstring), and nothing else waits on this
                    # thread while it fetches
                    out = jax.tree.map(np.asarray, out)  # jaxlint: disable=host-sync-on-serving-worker — resolved futures carry host numpy by contract
                except Exception as e:      # resolve, never wedge clients
                    for r in batch:
                        if not r.future.set_running_or_notify_cancel():
                            continue
                        r.future.set_exception(e)
                    continue
                now = time.perf_counter()
                off = 0
                try:
                    for r in batch:
                        a, b = off, off + r.rows
                        res = jax.tree.map(
                            lambda o: o[a] if r.single else o[a:b], out)
                        off += r.rows
                        lat_ms = (now - r.t_submit) * 1e3
                        serving_metrics.note_latency_ms(lat_ms)
                        if tr is not None:
                            tr.event("serving.complete", rows=r.rows,
                                     latency_ms=round(lat_ms, 3))
                        if r.future.set_running_or_notify_cancel():
                            r.future.set_result(res)
                except Exception as e:
                    # distribution failure (e.g. an apply_fn output leaf
                    # without a leading batch dim) must fail THIS batch's
                    # unresolved futures, never kill the worker — a dead
                    # worker wedges every later client until timeout
                    for r in batch:
                        if not r.future.done() and \
                                r.future.set_running_or_notify_cancel():
                            r.future.set_exception(e)

    # -- lifecycle ---------------------------------------------------------
    def close(self, timeout: float = 30.0) -> None:
        """Stop accepting requests, drain what's queued, join the
        worker."""
        with self._cv:
            self._open = False
            self._cv.notify_all()
        self._thread.join(timeout)

    def __enter__(self) -> "DynamicBatcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
