"""Observability: counter families, per-step metrics, throughput.

The reference's tracing story is a single ``ScoreIterationListener`` plus
coarse YARN metrics maps (SURVEY.md §5.1/§5.5).  The TPU upgrade budgeted
there: real per-step timing and a JSONL scalars sink (renders anywhere).
Spans, and their names in a ``jax.profiler`` trace, are
``runtime/telemetry.span``.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax


class CompileMetrics:
    """Process-wide compile/cache counters for the runtime compile engine
    (runtime/compile_cache.py).

    - ``compile_count``: XLA traces actually performed — one per unique
      (function, input shapes/dtypes) signature.  Two identically
      configured networks sharing one engine entry trace ONCE.
    - ``compile_ms``: wall-clock ms of engine calls that triggered a
      trace (trace + XLA compile dominate; the dispatch riding along is
      noise at compile timescales).
    - ``engine_builds`` / ``engine_hits``: keyed engine lookups that
      built a new compiled-step entry vs. reused an existing one.
    - ``cached_dispatches``: engine calls served entirely from the
      already-compiled executable (no trace).
    - ``traces``: per-label trace counts, e.g.
      ``{"multilayer.train_step": 1}``.
    - ``xla_compile_requests`` / ``persistent_cache_hits`` /
      ``persistent_cache_misses``: what XLA itself was asked for, from
      ``jax.monitoring`` — every lowering handed to the backend
      (persistent-cache hits included) by ANY jit in the process, and
      the persistent cache's hits and misses.  A recompile without a
      retrace shows here and nowhere above.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()
        # once per instance, never in reset(): jax.monitoring keeps its
        # listeners for the life of the process
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def reset(self) -> None:
        with self._lock:
            self.compile_count = 0
            self.compile_ms = 0.0
            self.engine_builds = 0
            self.engine_hits = 0
            self.cached_dispatches = 0
            self.traces: Dict[str, int] = {}
            self.xla_compile_requests = 0
            self.persistent_cache_hits = 0
            self.persistent_cache_misses = 0

    def _on_event(self, event: str, **kw: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.persistent_cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            with self._lock:
                self.persistent_cache_misses += 1

    def _on_duration(self, event: str, secs: float, **kw: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.xla_compile_requests += 1

    def note_trace(self, label: str) -> None:
        with self._lock:
            self.compile_count += 1
            self.traces[label] = self.traces.get(label, 0) + 1

    def note_compile_ms(self, ms: float) -> None:
        with self._lock:
            self.compile_ms += ms

    def note_engine(self, hit: bool) -> None:
        with self._lock:
            if hit:
                self.engine_hits += 1
            else:
                self.engine_builds += 1

    def note_cached_dispatch(self) -> None:
        with self._lock:
            self.cached_dispatches += 1

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "compile_count": self.compile_count,
                "compile_ms": round(self.compile_ms, 1),
                "engine_builds": self.engine_builds,
                "engine_hits": self.engine_hits,
                "cached_dispatches": self.cached_dispatches,
                "traces": dict(self.traces),
                "xla_compile_requests": self.xla_compile_requests,
                "persistent_cache_hits": self.persistent_cache_hits,
                "persistent_cache_misses": self.persistent_cache_misses,
            }


#: process-wide singleton the compile engine reports into
compile_metrics = CompileMetrics()


class ResilienceMetrics:
    """Process-wide counters for the self-healing layer
    (runtime/resilience.py) — every fault the stack absorbed instead of
    dying:

    - ``steps_skipped``: train/solver steps whose update was dropped by
      the in-step non-finite guard;
    - ``spikes_detected`` / ``rollbacks`` / ``retry_budget_exceeded``:
      loss-spike detector hits, checkpoint rollbacks performed, and runs
      that exhausted the retry budget;
    - ``checkpoints_saved``: auto-checkpoints written by ResilientFit;
    - ``updates_rejected``: non-finite/corrupt worker results refused by
      the hardened scaleout aggregator;
    - ``worker_join_retries``: worker-join RPC attempts that had to back
      off and retry.

    Keys are open-ended (``note`` accepts any name) so new guard sites
    don't need a schema change; ``snapshot`` returns a plain dict for
    bench rows and soak assertions."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}

    def reset(self) -> None:
        with self._lock:
            self._counters = {}

    def note(self, key: str, by: int = 1) -> None:
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + by

    def count(self, key: str) -> int:
        with self._lock:
            return self._counters.get(key, 0)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)


#: process-wide singleton every guard/rollback/rejection reports into
resilience_metrics = ResilienceMetrics()


class ServingMetrics:
    """Process-wide counters for the inference serving engine
    (serving/engine.py + serving/batcher.py):

    - ``requests`` / ``rows``: client requests accepted and the example
      rows they carried;
    - ``dispatches`` / ``rows_padded``: bucketed device dispatches and
      the TOTAL padded rows they ran (real + padding) — the
      padding-waste ratio in ``snapshot`` is ``1 - rows/rows_padded``;
    - ``batches_formed`` / ``requests_coalesced``: micro-batches the
      DynamicBatcher flushed and the requests they merged;
    - ``queue_depth`` / ``max_queue_depth``: live and high-water
      batcher queue occupancy;
    - request latency reservoir (bounded) -> ``latency_p50_ms`` /
      ``latency_p99_ms`` in ``snapshot``;
    - ``mark_compiles()`` banks the engine compile count so
      ``snapshot()['compile_delta_since_mark']`` gives the steady-state
      compile delta the acceptance criterion asserts to be zero after
      ``warmup()``.
    """

    #: latency reservoir bound — serving runs forever; percentiles come
    #: from the most recent window, not an unbounded list
    MAX_LATENCIES = 8192

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.requests = 0
            self.rows = 0
            self.dispatches = 0
            self.rows_padded = 0
            self.batches_formed = 0
            self.requests_coalesced = 0
            self.queue_depth = 0
            self.max_queue_depth = 0
            self._latencies_ms: List[float] = []
            self._compile_mark: Optional[int] = None

    def note_request(self, rows: int) -> None:
        with self._lock:
            self.requests += 1
            self.rows += rows

    def note_dispatch(self, bucket_rows: int) -> None:
        with self._lock:
            self.dispatches += 1
            self.rows_padded += bucket_rows

    def note_batch(self, n_requests: int) -> None:
        with self._lock:
            self.batches_formed += 1
            self.requests_coalesced += n_requests

    def note_queue_depth(self, depth: int) -> None:
        with self._lock:
            self.queue_depth = depth
            self.max_queue_depth = max(self.max_queue_depth, depth)

    def note_latency_ms(self, ms: float) -> None:
        with self._lock:
            self._latencies_ms.append(ms)
            if len(self._latencies_ms) > self.MAX_LATENCIES:
                del self._latencies_ms[:len(self._latencies_ms) // 2]

    def mark_compiles(self) -> None:
        """Bank the current engine compile count (call right after
        ``warmup()``); later snapshots report the delta."""
        with self._lock:
            self._compile_mark = compile_metrics.snapshot()["compile_count"]

    @staticmethod
    def _pct(sorted_ms: List[float], q: float) -> Optional[float]:
        if not sorted_ms:
            return None
        idx = min(int(q * len(sorted_ms)), len(sorted_ms) - 1)
        return round(sorted_ms[idx], 3)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            lat = sorted(self._latencies_ms)
            waste = (1.0 - self.rows / self.rows_padded) \
                if self.rows_padded else 0.0
            out = {
                "requests": self.requests,
                "rows": self.rows,
                "dispatches": self.dispatches,
                "rows_padded": self.rows_padded,
                "padding_waste_ratio": round(max(waste, 0.0), 4),
                "batches_formed": self.batches_formed,
                "requests_coalesced": self.requests_coalesced,
                "queue_depth": self.queue_depth,
                "max_queue_depth": self.max_queue_depth,
                "latency_p50_ms": self._pct(lat, 0.50),
                "latency_p99_ms": self._pct(lat, 0.99),
                "latency_samples": len(lat),
                "compile_mark": self._compile_mark,
            }
        if out["compile_mark"] is not None:
            out["compile_delta_since_mark"] = (
                compile_metrics.snapshot()["compile_count"]
                - out["compile_mark"])
        return out


#: process-wide singleton the serving engine + batcher report into
serving_metrics = ServingMetrics()


class DecodeMetrics:
    """Process-wide counters for the continuous-batching decode stack
    (serving/decode.py + serving/router.py):

    - ``requests`` / ``requests_completed`` / ``requests_shed``: decode
      requests accepted, finished (EOS or token budget), and rejected by
      the router's queue-depth load-shed bound;
    - ``prompt_tokens`` / ``tokens_out``: prompt tokens prefilled and
      continuation tokens streamed back;
    - ``prefill_dispatches`` / ``decode_dispatches``: device dispatches
      of the two slot executables; ``prefill_rows_dispatched`` /
      ``prefill_rows_valid``: summed over prefill dispatches, the rows
      a dispatch carried (``DecodeEngine.prefill_rows`` of its rung, a
      whole number of pages) and those of them that were prompt tokens:
      ``rows_dispatched / prefill_dispatches`` is the width a join
      took, ``1 - valid / dispatched`` the share that was padding;
    - ``decode_dispatches_ahead``: decode dispatches issued while the
      step (or self-draft round) before was still uncollected, their
      tokens taken from its output on the device
      (``DecodeEngine.dispatch_step(after=)``, ``dispatch_round(after=)``):
      over ``decode_dispatches`` the share of steps that overlapped the
      host's deliver, expire, admit and stage (0 for a second model's
      speculative rounds, which stay in series);
      ``decode_overshoot_steps``: slot steps whose tokens were discarded
      when they landed because the request had ended meanwhile (an
      ``eos_id`` is known one step late, an end inside a round's
      commits one round late; a deadline or an eviction with a step in
      flight);
    - ``joins``: requests that prefilled into a slot while OTHER slots
      were mid-decode (the continuous-batching event: nobody waited for
      a cohort to finish);
    - ``slot_steps`` / ``slot_capacity_steps``: active vs total slots
      summed over decode dispatches — ``snapshot()['slot_occupancy']``
      is their ratio (1.0 = every dispatch fully utilized);
    - ``decode_dispatch_rungs``: summed over decode dispatches, the
      DISTINCT rungs (``pick_bucket(prompt + max_tokens)``) of the
      requests a dispatch carried — the dispatches an engine with a
      slot table a rung would have made, so ``decode_dispatch_rungs /
      decode_dispatches`` is the factor one table saves (1.0 when one
      rung is live); ``decode_table_rows``: summed over decode
      dispatches, ``n_slots`` x the table width the dispatch took — the
      rows it gathered a layer, live or padding, to read against
      ``slot_steps`` and ``page_token_rows``;
    - ``queue_depth`` / ``max_queue_depth``: most recent and high-water
      PER-BATCHER pending depth (each batcher reports its own count;
      with multiple router replicas this is a replica-level gauge, not
      a fleet total — ``Router.depths()`` is the fleet view);
    - a time-to-first-token reservoir (bounded) -> ``ttft_p50_ms``/
      ``ttft_p99_ms``;
    - ``mark_compiles()`` / ``compile_delta_since_mark``: same
      steady-state zero-compile assertion primitive as ServingMetrics.

    Serving tier 2 (quantization + prefix reuse + autoscaling):

    - ``prefix_hits`` / ``prefix_misses`` / ``prefill_tokens_saved``:
      prompt prefixes served from the engine's content-hashed prefix
      store vs prefilled cold, and the prompt tokens whose prefill
      compute the hits skipped;
    - ``kv_bytes_per_slot``: gauge — KV-cache bytes per slot of the
      most recently constructed engine's largest bucket (int8 KV is
      the 'slots per chip' capacity lever);
    - ``replicas_added`` / ``replicas_removed``: autoscaling router
      scale events;
    - ``shed_by_policy``: requests shed by the AUTOSCALING router
      (already at max replicas and over the depth bound) — disjoint
      from ``requests_shed``-only sheds of the static router
      (``note_shed(by_policy=True)`` books both).

    Serving tier 3 (paged KV + speculative decoding + hot swap) — same
    ``"decode"`` family, no new registry source:

    - ``pages_in_use`` / ``pages_in_use_hw``: live KV pages allocated
      out of the paged engine's pool (gauge + high-water) — the paged
      analog of slot occupancy;
    - ``page_token_rows`` / ``page_capacity_rows``: live token rows vs
      rows the allocated pages could hold, summed over dispatches —
      ``snapshot()['page_utilization']`` is their ratio (how little of
      each page is padding; pinned slots would score
      live/bucket-length);
    - ``draft_proposed`` / ``draft_accepted``: speculative draft tokens
      proposed vs accepted by the target's verify —
      ``snapshot()['draft_accept_rate']``;
    - ``swaps_completed`` / ``requests_during_swap``: hot checkpoint
      swaps finished by ``AutoscalingRouter.swap_weights`` and requests
      accepted while one was in progress (the zero-downtime witness).

    Serving fault tolerance (deadlines + health-checked replacement +
    deterministic re-dispatch + brownout) — still the ``"decode"``
    family, no new registry source:

    - ``deadline_expirations``: requests freed (pages reclaimed, typed
      ``DeadlineExceeded`` on the future) because their ``deadline_ms``
      passed while queued or mid-decode;
    - ``replicas_replaced``: unhealthy replicas (dead worker thread,
      dispatch-exception streak, stall) retired and respawned from the
      factory by the router's health monitor;
    - ``requests_replayed``: in-flight requests deterministically
      re-dispatched — replayed as (prompt + tokens emitted so far) on a
      healthy replica, continuing bit-identically (sampling keys fold
      (seed, position), not step count);
    - ``brownout_transitions`` / ``brownout_level``: graceful-brownout
      ladder moves and the current level gauge (0 = normal, 1 =
      speculative decoding off, 2 = + prefix harvesting bypassed);
    - ``pages_leaked``: gauge — allocator page references not accounted
      for by any live slot or the resident-prefix registry after the
      last release (nonzero means a reclaim path missed pages).

    Where the worker thread's time goes — cumulative seconds, each the
    sum of the durations of the ``telemetry.span`` named beside it
    (``add_seconds`` is what a span's ``counter=`` calls on exit), and
    the counts their means are taken over:

    - ``rounds`` / ``round_s`` (``decode.round``): passes of the
      batcher's loop that admitted or dispatched anything (a pass that
      only lands the last step in flight adds its seconds, not a
      round);
    - ``admissions`` / ``queue_wait_s``: requests taken off the queue
      and the time each had spent since its submit, booked where the
      wait ends; ``prefill_s`` (``decode.prefill``): their joins, every
      chunk's dispatch and the wait for the first token, and
      ``prefill_sync_s`` (``decode.prefill.sync``) that wait alone;
    - ``advance_s`` (``decode.advance``): the engine's decode steps,
      one per ``decode_dispatches`` and, since the engine keeps one slot
      table, one a round.  A plain step is two spans of that name, its
      dispatch (``dispatch_step``) and, one step later under a batcher,
      its collection (``collect``); ``fetch_s`` (``decode.fetch``): the
      part of the second spent waiting for the step's tokens, which is
      what of the device's time the host's other work did not cover;
      ``stage_s`` (``decode.stage``) and ``dispatch_s``
      (``decode.dispatch``): the two parts of a dispatch, the host's
      tables and masks and the calls into the runtime, of a plain step
      and of a speculative round alike;
    - ``expire_s`` (``decode.expire``), ``admit_s`` (``decode.admit``:
      the queue's scan, every join, the first token's delivery),
      ``deliver_s`` (``decode.deliver``): the other children of a
      round, so ``round_s`` less ``expire_s + admit_s + advance_s +
      deliver_s`` is the time of a round that no named span holds (a
      pass that found nothing to do books none of them);
    - ``prefix_s`` (``decode.prefix.lookup`` and
      ``decode.prefix.register``): what prefix reuse costs the joins of
      a family that mounts prefixes, whether or not anything hit;
      ``pages_held_resident``: gauge, the pages in use that the
      resident-prefix registry alone holds (no slot's table has them,
      and ``can_admit`` cannot hand them out until the registry lets
      go), set at every join, release and ``drop_residents``;
    - ``slot_turnovers`` / ``slot_vacant_s`` / ``slot_vacant_queued_s``
      (``decode.slot_vacant``): placements on a slot that was released
      before, the time from that release to the placement, and the part
      of it in which the request placed was already submitted (the
      worker had not got to it), the rest being time in which no
      request existed for the slot.

    The tree a ``DecodeEngine`` holds for its executables
    (``serving.decode.hold_in_compute_dtype``, span
    ``decode.hold_params``):

    - ``params_held_casts``: times a held tree was made by casting the
      leaves a family names to the compute type — once per params tree
      an engine is given (construction, ``rebind_params``, a
      live-params callable's new tree), never inside a dispatch; 0 for
      a tree that arrives in the compute type;
      ``params_held_bytes``: bytes of the leaves so cast, as held.

    Expert layers on the decode path (a model family whose decode step
    returns them behind its tokens, ``models/deepseek_v2.py``), summed
    over decode dispatches and their expert layers:

    - ``moe_assignments``: (token, expert) choices the router made for
      active slots — tokens x layers x experts per token;
      ``moe_assignments_held``: those that fell on an expert this rank
      holds; ``moe_expert_hits``: distinct held experts some token
      chose, a layer a dispatch (what the step read of the experts);
      ``moe_layer_dispatches``: expert layers run.

    A SLOT STATE (an engine whose family declares one,
    ``models/ling_hybrid.py``'s KDA layers: a fixed matrix a sequence):

    - ``linear_state_resets``: joins whose first prefill dispatch started
      the slot's state from zero (one an admission in such an engine);
      ``state_bytes_held``: gauge, bytes of the engine's slab of
      ``n_slots`` slots' states.

    Kinds of page (an engine whose family declares them,
    ``models/mellum.py``: a slab and a page table for the full-attention
    layers, another for the sliding-window layers, whose table row is a
    ring no longer than the window):

    - ``pages_in_use_full`` / ``pages_in_use_window``: gauges, pages
      allocated of each kind; ``kv_rows_held_full`` /
      ``kv_rows_held_window``: summed over decode dispatches, the rows a
      layer of that kind holds for the slots that ran (all of a
      sequence; at most the ring's newest); ``window_pages_reused``:
      pages of the window kind a slot wrote its newest rows over, in
      decode steps and prefill chunks alike.
    """

    MAX_SAMPLES = 8192
    #: the cumulative-seconds counters a span may name
    SECONDS = ("round_s", "queue_wait_s", "prefill_s", "prefill_sync_s",
               "advance_s", "fetch_s", "expire_s", "admit_s", "stage_s",
               "dispatch_s", "deliver_s", "prefix_s", "slot_vacant_s",
               "slot_vacant_queued_s")
    #: counts a model family's decode step returns behind its tokens
    #: (``note_family_counts``)
    FAMILY_COUNTS = ("moe_assignments", "moe_assignments_held",
                     "moe_expert_hits", "moe_layer_dispatches")
    #: per KIND OF PAGE of an engine whose family declares its kinds
    #: (``note_page_kinds``): gauges, then counts
    KIND_GAUGES = ("pages_in_use_full", "pages_in_use_window")
    KIND_COUNTS = ("kv_rows_held_full", "kv_rows_held_window",
                   "window_pages_reused")

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.requests = 0
            self.requests_completed = 0
            self.requests_shed = 0
            self.prompt_tokens = 0
            self.tokens_out = 0
            self.prefill_dispatches = 0
            self.prefill_rows_dispatched = 0
            self.prefill_rows_valid = 0
            self.decode_dispatches = 0
            self.decode_dispatches_ahead = 0
            self.decode_overshoot_steps = 0
            self.joins = 0
            self.slot_steps = 0
            self.slot_capacity_steps = 0
            self.decode_dispatch_rungs = 0
            self.decode_table_rows = 0
            self.queue_depth = 0
            self.max_queue_depth = 0
            self.prefix_hits = 0
            self.prefix_misses = 0
            self.prefill_tokens_saved = 0
            self.kv_bytes_per_slot = 0
            self.replicas_added = 0
            self.replicas_removed = 0
            self.shed_by_policy = 0
            self.pages_in_use = 0
            self.pages_in_use_hw = 0
            self.page_token_rows = 0
            self.page_capacity_rows = 0
            self.draft_proposed = 0
            self.draft_accepted = 0
            self.swaps_completed = 0
            self.requests_during_swap = 0
            self.deadline_expirations = 0
            self.replicas_replaced = 0
            self.requests_replayed = 0
            self.brownout_transitions = 0
            self.brownout_level = 0
            self.pages_leaked = 0
            self.rounds = 0
            self.admissions = 0
            self.slot_turnovers = 0
            self.pages_held_resident = 0
            self.params_held_casts = 0
            self.params_held_bytes = 0
            self.linear_state_resets = 0
            self.state_bytes_held = 0
            for key in self.SECONDS:
                setattr(self, key, 0.0)
            for key in (self.FAMILY_COUNTS + self.KIND_GAUGES
                        + self.KIND_COUNTS):
                setattr(self, key, 0)
            self._ttft_ms: List[float] = []
            self._compile_mark: Optional[int] = None

    def add_seconds(self, key: str, seconds: float) -> None:
        if key not in self.SECONDS:
            raise KeyError(f"no cumulative-seconds counter {key!r}")
        with self._lock:
            setattr(self, key, getattr(self, key) + seconds)

    def note_family_counts(self, names, counts) -> None:
        """Add what one decode dispatch counted on the device: ``names``
        from ``FAMILY_COUNTS``, ``counts`` the integers fetched with the
        step's tokens."""
        for key in names:
            if key not in self.FAMILY_COUNTS:
                raise KeyError(f"no family counter {key!r}")
        with self._lock:
            for key, n in zip(names, counts):
                setattr(self, key, getattr(self, key) + int(n))

    def note_page_kinds(self, gauges: Dict[str, int],
                        counts: Dict[str, int]) -> None:
        """What an engine with several kinds of page says of them:
        ``gauges`` (from ``KIND_GAUGES``) are set, ``counts`` (from
        ``KIND_COUNTS``) added."""
        for key in gauges:
            if key not in self.KIND_GAUGES:
                raise KeyError(f"no page-kind gauge {key!r}")
        for key in counts:
            if key not in self.KIND_COUNTS:
                raise KeyError(f"no page-kind counter {key!r}")
        with self._lock:
            for key, n in gauges.items():
                setattr(self, key, int(n))
            for key, n in counts.items():
                setattr(self, key, getattr(self, key) + int(n))

    def note_params_held(self, nbytes: int) -> None:
        with self._lock:
            self.params_held_casts += 1
            self.params_held_bytes += int(nbytes)

    def note_linear_state_reset(self) -> None:
        with self._lock:
            self.linear_state_resets += 1

    def note_state_bytes_held(self, nbytes: int) -> None:
        with self._lock:
            self.state_bytes_held = int(nbytes)

    def note_round(self) -> None:
        with self._lock:
            self.rounds += 1

    def note_admission(self, queue_wait_s: float) -> None:
        with self._lock:
            self.admissions += 1
            self.queue_wait_s += queue_wait_s

    def note_slot_turnover(self, vacant_s: float, queued_s: float) -> None:
        with self._lock:
            self.slot_turnovers += 1
            self.slot_vacant_s += vacant_s
            self.slot_vacant_queued_s += queued_s

    def note_pages_held_resident(self, n: int) -> None:
        with self._lock:
            self.pages_held_resident = int(n)

    def note_request(self, prompt_tokens: int) -> None:
        with self._lock:
            self.requests += 1
            self.prompt_tokens += int(prompt_tokens)

    def note_join(self) -> None:
        with self._lock:
            self.joins += 1

    def note_shed(self, by_policy: bool = False) -> None:
        with self._lock:
            self.requests_shed += 1
            if by_policy:
                self.shed_by_policy += 1

    def note_prefix_hit(self, tokens_saved: int) -> None:
        with self._lock:
            self.prefix_hits += 1
            self.prefill_tokens_saved += int(tokens_saved)

    def note_prefix_miss(self) -> None:
        with self._lock:
            self.prefix_misses += 1

    def note_kv_bytes_per_slot(self, nbytes: int) -> None:
        with self._lock:
            self.kv_bytes_per_slot = int(nbytes)

    def note_replicas(self, added: int = 0, removed: int = 0) -> None:
        with self._lock:
            self.replicas_added += added
            self.replicas_removed += removed

    def note_pages(self, in_use: int, live_rows: int,
                   page_tokens: int) -> None:
        with self._lock:
            self.pages_in_use = int(in_use)
            self.pages_in_use_hw = max(self.pages_in_use_hw, int(in_use))
            self.page_token_rows += int(live_rows)
            self.page_capacity_rows += int(in_use) * int(page_tokens)

    def note_spec(self, proposed: int, accepted: int) -> None:
        with self._lock:
            self.draft_proposed += int(proposed)
            self.draft_accepted += int(accepted)

    def note_swap(self) -> None:
        with self._lock:
            self.swaps_completed += 1

    def note_request_during_swap(self) -> None:
        with self._lock:
            self.requests_during_swap += 1

    def note_deadline_expiration(self) -> None:
        with self._lock:
            self.deadline_expirations += 1

    def note_replica_replaced(self) -> None:
        with self._lock:
            self.replicas_replaced += 1

    def note_request_replayed(self) -> None:
        with self._lock:
            self.requests_replayed += 1

    def note_brownout(self, level: int) -> None:
        with self._lock:
            self.brownout_transitions += 1
            self.brownout_level = int(level)

    def note_pages_leaked(self, n: int) -> None:
        with self._lock:
            self.pages_leaked = int(n)

    def note_complete(self, tokens: int) -> None:
        with self._lock:
            self.requests_completed += 1
            self.tokens_out += int(tokens)

    def note_prefill(self, dispatches: int, rows_dispatched: int,
                     rows_valid: int) -> None:
        with self._lock:
            self.prefill_dispatches += int(dispatches)
            self.prefill_rows_dispatched += int(rows_dispatched)
            self.prefill_rows_valid += int(rows_valid)

    def note_decode_dispatch(self, active: int, capacity: int,
                             rungs: int, table_rows: int,
                             ahead: bool = False) -> None:
        with self._lock:
            self.decode_dispatches += 1
            self.decode_dispatches_ahead += bool(ahead)
            self.slot_steps += int(active)
            self.slot_capacity_steps += int(capacity)
            self.decode_dispatch_rungs += int(rungs)
            self.decode_table_rows += int(table_rows)

    def note_overshoot(self, slot_steps: int) -> None:
        with self._lock:
            self.decode_overshoot_steps += int(slot_steps)

    def note_queue_depth(self, depth: int) -> None:
        with self._lock:
            self.queue_depth = depth
            self.max_queue_depth = max(self.max_queue_depth, depth)

    def _push(self, buf: List[float], ms: float) -> None:
        buf.append(ms)
        if len(buf) > self.MAX_SAMPLES:
            del buf[:len(buf) // 2]

    def note_ttft_ms(self, ms: float) -> None:
        with self._lock:
            self._push(self._ttft_ms, ms)

    def mark_compiles(self) -> None:
        with self._lock:
            self._compile_mark = compile_metrics.snapshot()["compile_count"]

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            ttft = sorted(self._ttft_ms)
            occ = (self.slot_steps / self.slot_capacity_steps
                   if self.slot_capacity_steps else 0.0)
            out = {
                "requests": self.requests,
                "requests_completed": self.requests_completed,
                "requests_shed": self.requests_shed,
                "prompt_tokens": self.prompt_tokens,
                "tokens_out": self.tokens_out,
                "prefill_dispatches": self.prefill_dispatches,
                "prefill_rows_dispatched": self.prefill_rows_dispatched,
                "prefill_rows_valid": self.prefill_rows_valid,
                "decode_dispatches": self.decode_dispatches,
                "decode_dispatches_ahead": self.decode_dispatches_ahead,
                "decode_overshoot_steps": self.decode_overshoot_steps,
                "decode_dispatch_rungs": self.decode_dispatch_rungs,
                "decode_table_rows": self.decode_table_rows,
                "joins": self.joins,
                "slot_occupancy": round(occ, 4),
                "queue_depth": self.queue_depth,
                "max_queue_depth": self.max_queue_depth,
                "prefix_hits": self.prefix_hits,
                "prefix_misses": self.prefix_misses,
                "prefill_tokens_saved": self.prefill_tokens_saved,
                "kv_bytes_per_slot": self.kv_bytes_per_slot,
                "replicas_added": self.replicas_added,
                "replicas_removed": self.replicas_removed,
                "shed_by_policy": self.shed_by_policy,
                "pages_in_use": self.pages_in_use,
                "pages_in_use_hw": self.pages_in_use_hw,
                "page_utilization": round(
                    self.page_token_rows / self.page_capacity_rows, 4)
                if self.page_capacity_rows else 0.0,
                "draft_proposed": self.draft_proposed,
                "draft_accepted": self.draft_accepted,
                "draft_accept_rate": round(
                    self.draft_accepted / self.draft_proposed, 4)
                if self.draft_proposed else 0.0,
                "swaps_completed": self.swaps_completed,
                "requests_during_swap": self.requests_during_swap,
                "deadline_expirations": self.deadline_expirations,
                "replicas_replaced": self.replicas_replaced,
                "requests_replayed": self.requests_replayed,
                "brownout_transitions": self.brownout_transitions,
                "brownout_level": self.brownout_level,
                "pages_leaked": self.pages_leaked,
                "ttft_p50_ms": ServingMetrics._pct(ttft, 0.50),
                "ttft_p99_ms": ServingMetrics._pct(ttft, 0.99),
                "rounds": self.rounds,
                "admissions": self.admissions,
                "slot_turnovers": self.slot_turnovers,
                "pages_held_resident": self.pages_held_resident,
                "params_held_casts": self.params_held_casts,
                "params_held_bytes": self.params_held_bytes,
                "linear_state_resets": self.linear_state_resets,
                "state_bytes_held": self.state_bytes_held,
                **{key: getattr(self, key) for key in self.SECONDS},
                **{key: getattr(self, key) for key in (
                    self.FAMILY_COUNTS + self.KIND_GAUGES
                    + self.KIND_COUNTS)},
                "compile_mark": self._compile_mark,
            }
        if out["compile_mark"] is not None:
            out["compile_delta_since_mark"] = (
                compile_metrics.snapshot()["compile_count"]
                - out["compile_mark"])
        return out


#: process-wide singleton the continuous-batching decode stack reports into
decode_metrics = DecodeMetrics()


class DataParallelMetrics:
    """Process-wide counters for the sharded/scanned training paths
    (parallel/sharded_fit.py consumers: ``MultiLayerNetwork`` DP fits,
    ``DataParallelTrainer``) and the mesh-aware ingestion stage
    (datasets/iterator.py ``PrefetchIterator(sharding=...)``):

    - ``bytes_staged`` / ``batches_staged`` / ``stage_ms``: host->HBM
      transfers submitted by the sharded staging stage (``device_put``
      is async — ``stage_ms`` is submission wall time, i.e. what the
      training loop actually waits; the DMA itself overlaps compute);
    - ``dispatches`` / ``steps``: device dispatches vs train steps they
      carried — ``snapshot()['steps_per_dispatch']`` is the scanned-
      epoch win (1.0 = the old per-batch loop);
    - ``accum_factor`` / ``data_degree``: microbatch accumulation factor
      and data-parallel shard count of the most recent dispatch, so
      bench rows can report effective batch = micro x accum x degree.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.bytes_staged = 0
            self.batches_staged = 0
            self.stage_ms = 0.0
            self.dispatches = 0
            self.steps = 0
            self.accum_factor = 1
            self.data_degree = 1

    def note_staged(self, nbytes: int, ms: float, batches: int = 1) -> None:
        with self._lock:
            self.bytes_staged += int(nbytes)
            self.batches_staged += batches
            self.stage_ms += ms

    def note_dispatch(self, steps: int, accum: int, data_degree: int) -> None:
        with self._lock:
            self.dispatches += 1
            self.steps += int(steps)
            self.accum_factor = int(accum)
            self.data_degree = int(data_degree)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "bytes_staged": self.bytes_staged,
                "batches_staged": self.batches_staged,
                "stage_ms": round(self.stage_ms, 3),
                "dispatches": self.dispatches,
                "steps": self.steps,
                "steps_per_dispatch": round(self.steps / self.dispatches, 2)
                if self.dispatches else 0.0,
                "accum_factor": self.accum_factor,
                "data_degree": self.data_degree,
            }


#: process-wide singleton the sharded fit paths + ingestion stage report into
dp_metrics = DataParallelMetrics()


class CheckpointMetrics:
    """Process-wide counters for the async/elastic checkpoint layer
    (runtime/checkpoint.py ``AsyncCheckpointer`` + ``CheckpointManager``
    and the preemption/elastic machinery in runtime/resilience.py):

    - ``saves_async`` / ``saves_sync``: snapshots requested through the
      background writer vs written synchronously on the caller's thread;
    - ``snapshots_committed``: checkpoints whose manifest hit disk — the
      crash-safe commit point (``bytes_written`` / ``write_ms`` are the
      writer-side serialization+fsync cost, off the training thread);
    - ``in_flight`` / ``max_in_flight``: snapshots staged but not yet
      committed (live gauge + high-water) — bounded by the
      AsyncCheckpointer's backpressure semaphore;
    - ``bytes_staged`` / ``stage_ms``: device->host snapshot forking cost
      the TRAINING thread actually pays (device-side copy + async D2H
      submission; the blocking materialization happens on the writer);
    - ``write_behind_lag_ms``: request-to-commit latency of the most
      recent committed snapshot (how far the disk state trails the run);
    - ``backpressure_waits``: save requests that found ``max_in_flight``
      snapshots pending and had to block;
    - ``checksum_failures`` / ``restore_fallbacks``: manifest
      verification failures and restores that fell back to an older
      committed step because the newest was corrupt/uncommitted;
    - ``preemptions_requested`` / ``preemption_snapshots``: SIGTERM/
      SIGINT drills observed by a PreemptionGuard and the final
      boundary snapshots they produced;
    - ``device_losses`` / ``elastic_resumes``: device-loss faults seen
      and successful re-mesh-and-restore recoveries.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.saves_async = 0
            self.saves_sync = 0
            self.snapshots_committed = 0
            self.bytes_written = 0
            self.write_ms = 0.0
            self.in_flight = 0
            self.max_in_flight = 0
            self.bytes_staged = 0
            self.stage_ms = 0.0
            self.write_behind_lag_ms = 0.0
            self.backpressure_waits = 0
            self.checksum_failures = 0
            self.restore_fallbacks = 0
            self.preemptions_requested = 0
            self.preemption_snapshots = 0
            self.device_losses = 0
            self.elastic_resumes = 0

    def note_staged(self, nbytes: int, ms: float) -> None:
        """Async staging cost (training-thread side).  Sync saves never
        stage — ``CheckpointManager.save`` books them directly via
        ``note("saves_sync")`` + :meth:`note_committed`."""
        with self._lock:
            self.bytes_staged += int(nbytes)
            self.stage_ms += ms
            self.saves_async += 1
            self.in_flight += 1
            self.max_in_flight = max(self.max_in_flight, self.in_flight)

    def note_commit_failed(self) -> None:
        """An async snapshot's writer-side save raised: it is no longer
        pending, so the in-flight gauge must come down even though no
        commit happened."""
        with self._lock:
            self.in_flight = max(0, self.in_flight - 1)

    def note_committed(self, nbytes: int, write_ms: float,
                       lag_ms: float, *, was_async: bool) -> None:
        with self._lock:
            self.snapshots_committed += 1
            self.bytes_written += int(nbytes)
            self.write_ms += write_ms
            self.write_behind_lag_ms = round(lag_ms, 3)
            if was_async:
                self.in_flight = max(0, self.in_flight - 1)

    def note(self, key: str, by: int = 1) -> None:
        """Bump a plain counter field by name (backpressure_waits,
        checksum_failures, restore_fallbacks, preemptions_requested,
        preemption_snapshots, device_losses, elastic_resumes)."""
        with self._lock:
            setattr(self, key, getattr(self, key) + by)

    def count(self, key: str) -> int:
        with self._lock:
            return getattr(self, key)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "saves_async": self.saves_async,
                "saves_sync": self.saves_sync,
                "snapshots_committed": self.snapshots_committed,
                "bytes_written": self.bytes_written,
                "write_ms": round(self.write_ms, 3),
                "in_flight": self.in_flight,
                "max_in_flight": self.max_in_flight,
                "bytes_staged": self.bytes_staged,
                "stage_ms": round(self.stage_ms, 3),
                "write_behind_lag_ms": self.write_behind_lag_ms,
                "backpressure_waits": self.backpressure_waits,
                "checksum_failures": self.checksum_failures,
                "restore_fallbacks": self.restore_fallbacks,
                "preemptions_requested": self.preemptions_requested,
                "preemption_snapshots": self.preemption_snapshots,
                "device_losses": self.device_losses,
                "elastic_resumes": self.elastic_resumes,
            }


#: process-wide singleton the checkpoint/preemption/elastic layer reports into
checkpoint_metrics = CheckpointMetrics()


#: published bf16 peak FLOP/s per chip by device_kind substring — the
#: denominator of every MFU estimate (single source; the autotuner
#: consults it here).  Source: Google Cloud TPU
#: documentation, the per-version "System architecture" pages ("TPU
#: v5e": 197 TFLOP/s bf16 per chip; v5p 459, v6e/Trillium 918, v4 275,
#: v3 123, v2 45).  JAX reports a v5e chip as device_kind "TPU v5 lite".
#: HBM bytes/s and int8 OP/s columns are ROADMAP S2's.
TPU_PEAK_FLOPS = (
    ("v6", 918e12), ("trillium", 918e12),
    ("v5p", 459e12), ("v5e", 197e12), ("v5 lite", 197e12),
    ("v5litepod", 197e12), ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
)


def chip_peak_flops(device_kind: str) -> Optional[float]:
    """bf16 peak FLOP/s for a device kind (None when unknown, e.g. CPU)."""
    dk = (device_kind or "").lower()
    for sub, peak in TPU_PEAK_FLOPS:
        if sub in dk:
            return peak
    return None


def estimate_mfu(flops_per_step: float, step_s: float, device_kind: str,
                 n_dev: int = 1) -> Optional[float]:
    """Model FLOPs utilization: analytic FLOPs per step / measured step
    wall time / fleet bf16 peak.  None when the chip's peak is unknown
    or the timing is degenerate."""
    peak = chip_peak_flops(device_kind)
    if peak is None or step_s <= 0 or n_dev <= 0:
        return None
    return flops_per_step / step_s / (peak * n_dev)


class MfuMetrics:
    """Process-wide counters for the MFU campaign (runtime/autotune.py +
    the bench rows) — the counter family everything hardware-utilization
    reports into:

    - per-label MFU **estimates**: ``note_mfu(label, flops, step_s,
      kind, n_dev)`` books analytic-FLOPs / measured-step-time / device-
      peak for a training loop or bench row (last value per label, with
      the inputs kept so a reader can re-derive it);
    - open-ended autotune counters via ``note`` — the autotuner books
      ``sweeps`` / ``candidates_timed`` / ``winners_persisted`` /
      ``consults`` / ``cache_hits`` / ``cache_misses`` so "zero
      re-sweeps in a warmed process" is a machine-checkable assertion
      (tools/autotune_gate.py), not a claim.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._counters: Dict[str, int] = {}
            self._estimates: Dict[str, Dict[str, Any]] = {}

    def note(self, key: str, by: int = 1) -> None:
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + by

    def count(self, key: str) -> int:
        with self._lock:
            return self._counters.get(key, 0)

    def note_mfu(self, label: str, flops_per_step: float, step_s: float,
                 device_kind: str, n_dev: int = 1) -> Optional[float]:
        est = estimate_mfu(flops_per_step, step_s, device_kind, n_dev)
        with self._lock:
            self._estimates[label] = {
                "mfu": round(est, 4) if est is not None else None,
                "tflops_per_step": round(flops_per_step / 1e12, 4),
                "step_ms": round(step_s * 1e3, 3),
                "device_kind": device_kind,
                "n_devices": int(n_dev),
            }
        return est

    def estimate(self, label: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            e = self._estimates.get(label)
            return dict(e) if e else None

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = dict(self._counters)
            out["estimates"] = {k: dict(v)
                                for k, v in self._estimates.items()}
            return out


#: process-wide singleton the autotuner + MFU estimators report into
mfu_metrics = MfuMetrics()


class MultihostMetrics:
    """Process-wide counters for the multi-host runtime
    (``parallel/multihost.py`` + the cluster paths in
    runtime/{checkpoint,resilience}.py):

    - ``joins`` / ``join_retries`` / ``join_failures``: bounded-retry
      ``jax.distributed.initialize`` outcomes (the launcher);
    - ``barriers`` / ``barrier_wait_ms``: control-plane rendezvous count
      and cumulative wait (the cluster-commit and drain overhead the
      host side actually pays);
    - ``flag_syncs``: per-step cluster-wide preemption-flag ORs;
    - ``cluster_commits``: snapshots whose manifest was written by the
      coordinator AFTER the all-members barrier — the cluster-committed
      count ("a snapshot no host can restore from is never committed");
    - ``host_losses`` / ``evictions`` / ``heartbeat_stale_events``:
      host-level failures detected, members that exited because THEIR
      devices were lost, and heartbeat staleness observations.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.joins = 0
            self.join_retries = 0
            self.join_failures = 0
            self.barriers = 0
            self.barrier_wait_ms = 0.0
            self.flag_syncs = 0
            self.cluster_commits = 0
            self.host_losses = 0
            self.evictions = 0
            self.heartbeat_stale_events = 0

    def note(self, key: str, by: int = 1) -> None:
        with self._lock:
            setattr(self, key, getattr(self, key) + by)

    def note_wait(self, ms: float) -> None:
        with self._lock:
            self.barrier_wait_ms += ms

    def count(self, key: str) -> int:
        with self._lock:
            return getattr(self, key)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "joins": self.joins,
                "join_retries": self.join_retries,
                "join_failures": self.join_failures,
                "barriers": self.barriers,
                "barrier_wait_ms": round(self.barrier_wait_ms, 3),
                "flag_syncs": self.flag_syncs,
                "cluster_commits": self.cluster_commits,
                "host_losses": self.host_losses,
                "evictions": self.evictions,
                "heartbeat_stale_events": self.heartbeat_stale_events,
            }


#: process-wide singleton the multi-host launcher/control plane reports into
multihost_metrics = MultihostMetrics()


class IngestMetrics:
    """Process-wide counters for the distributed data service
    (``datasets/data_service.py`` — per-host shard readers feeding the
    mesh over DCN):

    - ``bytes_staged`` / ``batches_staged`` / ``stage_ms``: host->HBM
      bytes THIS process staged (per-host cost — under the read plan
      each host stages only its 1/n_hosts row slice, so this is the
      number the O(1/host) ingest contract is measured by) and the
      submission wall time the training loop actually paid;
    - ``depth_hw``: prefetch queue high-water mark (how deep the
      DCN-tuned staging pipeline actually ran);
    - ``reassignments``: read-plan recomputes — elastic re-shards after
      a cluster shrink plus explicit ``reshard()`` calls;
    - ``state_roundtrips``: reader-state trips through the checkpoint
      manifest (exports into a snapshot's meta + restores out of one);
    - ``seed_agreements``: per-epoch shuffle-seed agreement rounds over
      the cluster KV store.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.bytes_staged = 0
            self.batches_staged = 0
            self.stage_ms = 0.0
            self.depth_hw = 0
            self.reassignments = 0
            self.state_roundtrips = 0
            self.seed_agreements = 0

    def note(self, key: str, by: int = 1) -> None:
        with self._lock:
            setattr(self, key, getattr(self, key) + by)

    def note_staged(self, nbytes: int, ms: float, batches: int = 1) -> None:
        with self._lock:
            self.bytes_staged += int(nbytes)
            self.batches_staged += batches
            self.stage_ms += ms

    def note_depth(self, depth: int) -> None:
        with self._lock:
            self.depth_hw = max(self.depth_hw, int(depth))

    def count(self, key: str) -> int:
        with self._lock:
            return getattr(self, key)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "bytes_staged": self.bytes_staged,
                "batches_staged": self.batches_staged,
                "stage_ms": round(self.stage_ms, 3),
                "depth_hw": self.depth_hw,
                "reassignments": self.reassignments,
                "state_roundtrips": self.state_roundtrips,
                "seed_agreements": self.seed_agreements,
            }


#: process-wide singleton the distributed data service reports into
ingest_metrics = IngestMetrics()


def device_memory_stats() -> Dict[str, Any]:
    """Per-device HBM usage where the backend reports it.

    Backends without memory accounting (CPU, some plugin versions) get an
    explicit ``{"unsupported": <reason>}`` marker instead of ``None`` —
    a CPU run and a genuinely failed stats call must stay
    distinguishable in journals and bench rows (the error CLASS is the
    reason; a backend that returns nothing reports ``"unreported"``)."""
    stats = {}
    for d in jax.devices():
        try:
            s = d.memory_stats()
            stats[str(d)] = s if s is not None else {
                "unsupported": "unreported"}
        except Exception as e:  # noqa: BLE001 — backend-specific errors
            stats[str(d)] = {"unsupported": type(e).__name__}
    return stats


def peak_bytes_in_use(stats: Optional[Dict[str, Any]] = None
                      ) -> Dict[str, Optional[int]]:
    """Per-device ``peak_bytes_in_use`` pulled out of
    :func:`device_memory_stats` (None where the backend doesn't report
    memory) — the one number capacity planning actually wants."""
    if stats is None:
        stats = device_memory_stats()
    out: Dict[str, Optional[int]] = {}
    for dev, s in stats.items():
        if isinstance(s, dict) and "unsupported" not in s:
            peak = s.get("peak_bytes_in_use")
            out[dev] = int(peak) if peak is not None else None
        else:
            out[dev] = None
    return out


# This import sits BELOW the counter singletons and the memory-stats
# helpers on purpose: importing this module can re-enter it through the
# optimize/__init__ -> solver -> runtime.compile_cache cycle (and, since
# PR 6, solver -> resilience -> telemetry), and that re-entry needs
# ``compile_metrics``/``device_memory_stats`` & co. to already be bound.
from deeplearning4j_tpu.optimize.listeners import IterationListener  # noqa: E402


class ScalarsLogger:
    """Append-only JSONL scalars sink — one line per step:
    {"step": i, "wall": t, **scalars}.  The render-webapp parity surface
    (plot/dashboard.py reads these files)."""

    def __init__(self, path: str):
        self.path = path
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self._f = open(path, "a", buffering=1)
        self._t0 = time.time()

    def log(self, step: int, **scalars: float) -> None:
        rec = {"step": step, "wall": round(time.time() - self._t0, 4)}
        rec.update({k: float(v) for k, v in scalars.items()})
        self._f.write(json.dumps(rec) + "\n")

    def close(self) -> None:
        self._f.close()

    @staticmethod
    def read(path: str) -> List[Dict[str, Any]]:
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]


class MetricsListener(IterationListener):
    """IterationListener that records score + step wall-time to a
    ScalarsLogger (and optionally samples/sec given a batch size).

    The step timer resets per FIT: the fit entry points call
    ``on_fit_start`` (``optimize/listeners.py`` hook), so the first step
    of a second ``fit()`` on the same listener is never mislabeled with
    the inter-fit wall gap.  When the model exposes a ``guard_skips``
    counter (``MultiLayerNetwork`` does — cumulative in-step guard
    skips), it rides along in every record."""

    def __init__(self, logger: ScalarsLogger, batch_size: int = 0):
        self.logger = logger
        self.batch_size = batch_size
        self._last = None

    def reset(self) -> None:
        """Forget the previous step's timestamp (call between fits; the
        fit entry points do this via ``on_fit_start``)."""
        self._last = None

    def on_fit_start(self, model) -> None:
        self.reset()

    def iteration_done(self, model, iteration, score):
        now = time.perf_counter()
        scalars = {"score": score}
        if self._last is not None:
            dt = now - self._last
            scalars["step_seconds"] = dt
            if self.batch_size and dt > 0:
                scalars["samples_per_sec"] = self.batch_size / dt
        self._last = now
        skips = getattr(model, "guard_skips", None)
        if skips is not None:
            scalars["guard_skips"] = skips
        self.logger.log(iteration, **scalars)


class ThroughputMeter:
    """Windowed samples/sec; call tick(n_samples) once per step."""

    def __init__(self, window: int = 50):
        self.window = window
        self._events: List[tuple] = []

    def tick(self, n_samples: int) -> Optional[float]:
        now = time.perf_counter()
        self._events.append((now, n_samples))
        self._events = self._events[-self.window:]
        if len(self._events) < 2:
            return None
        dt = self._events[-1][0] - self._events[0][0]
        n = sum(s for _, s in self._events[1:])
        return n / dt if dt > 0 else None
