"""Replicated decode serving: least-depth routing + load-shedding.

One ``DecodeEngine`` saturates one device; production traffic wants N
replicas with a router in front — the fan-out half of the serving story
in arXiv:2605.25645 (replicated decode servers behind a dispatcher) and
the classic admission-control lesson: beyond a queue-depth bound,
REJECTING work keeps p99 bounded while accepting it melts every
client's latency.

- ``Router`` holds N ``ContinuousBatcher`` front-ends and submits each
  request to the least-loaded one (pending + in-flight depth).
- When even the least-loaded replica is at ``max_queue_depth``, the
  request is shed with the typed :class:`OverloadedError` (booked in
  ``runtime.metrics.decode_metrics.requests_shed`` and, when tracing,
  a ``decode.shed`` event) — clients see a clean, immediate, typed
  rejection they can retry against, not a timeout.
- ``Router.replicate(...)`` builds the replicas over DEVICE GROUPS:
  each replica is a ``model_degree``-sized group of chips with the
  engine's params model-sharded across the group (heads/MLP over
  ``model``, KV cache over heads) — replicas round-robin over groups,
  so a model bigger than one chip's HBM still replicates for
  throughput.  ``model_degree=1`` (default) is the original one
  -device-per-replica placement.

SERVING TIER 2 closes the telemetry loop the static bound leaves open:

- ``AutoscalePolicy`` is a pure hysteresis state machine over live
  signals (mean queue depth across replicas, the ``decode_metrics``
  TTFT p99 reservoir): scale up only after ``up_after`` consecutive
  hot observations, down only after ``down_after`` cold ones, with a
  cooldown between actions — so an oscillating load never flaps the
  fleet.  It is deliberately clock-injected (``observe(..., now=)``)
  and replica-count-aware, so the tier-1 tests drive it with synthetic
  load traces.
- ``AutoscalingRouter`` owns a replica FACTORY instead of a fixed
  list: it spawns/retires ``ContinuousBatcher`` replicas on the
  policy's verdicts (a clone's ``warmup()`` hits the shared compile
  cache — scale-up costs zero new XLA programs), drains retired
  replicas in the background, and only SHEDS (``shed_by_policy``)
  when it is already at ``max_replicas`` AND over the depth bound —
  load that a fixed fleet would reject becomes a scale-up instead.
  ``max_queue_depth`` is thereby reinterpreted as the per-replica
  pressure bound that triggers emergency scale-up (MIGRATION.md).

SERVING TIER 3 adds the zero-downtime weight swap:
``AutoscalingRouter.swap_weights(new_params)`` flips replicas one at a
time — drain (excluded from routing, fleet absorbs the traffic) →
``engine.rebind_params`` → requantize on the swapping thread → rejoin —
so a fleet rolls onto a new checkpoint with zero dropped requests and,
because shapes are unchanged, zero new XLA compiles.  Shared
``PrefixCache`` stores are cleared once at the end (their pages encode
the old weights).  Requests admitted while a swap is in flight are
counted in ``decode_metrics.requests_during_swap``.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from deeplearning4j_tpu.runtime import telemetry
from deeplearning4j_tpu.runtime.metrics import decode_metrics
from deeplearning4j_tpu.serving.decode import (BatcherClosed,
                                               ContinuousBatcher,
                                               DecodeEngine, DecodeRequest,
                                               _ReplayRequest)


class RouterClosed(RuntimeError):
    """Typed rejection for a submit racing router ``close()``: the
    closed flag flipped before the request could be routed.  Raised
    synchronously — a request is either accepted by a replica (and
    drains to completion) or rejected with this; never a hang."""


class SwapFailed(TimeoutError):
    """Typed ``swap_weights`` drain failure: a replica did not reach
    depth zero within the timeout.  Carries the per-replica drain
    states (depth, worker liveness, draining flag) captured at failure
    time, so operators can tell a WEDGED drain (depth pinned, worker
    dead or stalled) from a merely slow one.  Subclasses
    ``TimeoutError`` so pre-existing handlers keep working.  The fleet
    is left serving: already-swapped replicas keep the new weights,
    the rest the old."""

    def __init__(self, timeout: float,
                 drain_states: Dict[int, Dict[str, Any]],
                 swapped: int):
        super().__init__(
            f"weight swap failed: a replica did not drain within "
            f"{timeout}s ({swapped} replica(s) swapped); per-replica "
            f"drain states: {drain_states}")
        self.timeout = timeout
        self.drain_states = drain_states
        self.swapped = swapped


class ReplicaHealth:
    """Thresholds for the router's replica health monitor — all three
    detection signals are HOST-side reads (no device sync on the
    monitor thread; machine-checked by jaxlint):

    - ``worker_alive()`` False: the decode worker thread died — every
      accepted request is stranded;
    - ``dispatch_error_streak >= max_error_streak``: consecutive
      failed device dispatches without a successful advance;
    - ``progress_age() > stall_after_s`` while ``depth() > 0``: the
      worker has neither admitted nor advanced anything despite having
      work — a wedged dispatch or a livelocked loop."""

    def __init__(self, poll_interval_s: float = 0.25, *,
                 max_error_streak: int = 3,
                 stall_after_s: float = 5.0):
        if poll_interval_s <= 0:
            raise ValueError(
                f"poll_interval_s must be > 0: {poll_interval_s}")
        if max_error_streak < 1:
            raise ValueError(
                f"max_error_streak must be >= 1: {max_error_streak}")
        if stall_after_s <= 0:
            raise ValueError(
                f"stall_after_s must be > 0: {stall_after_s}")
        self.poll_interval_s = float(poll_interval_s)
        self.max_error_streak = int(max_error_streak)
        self.stall_after_s = float(stall_after_s)


class OverloadedError(RuntimeError):
    """Typed load-shed rejection: every replica is above the router's
    queue-depth bound.  Carries the observed depth so clients/backoff
    policies can reason about it."""

    def __init__(self, depth: int, bound: int, replicas: int):
        super().__init__(
            f"all {replicas} decode replica(s) at queue depth >= "
            f"{bound} (least-loaded: {depth}); request shed")
        self.depth = depth
        self.bound = bound
        self.replicas = replicas


class Router:
    """Least-depth dispatch over N ``ContinuousBatcher`` replicas with
    a hard queue-depth admission bound."""

    def __init__(self, batchers: Sequence[ContinuousBatcher], *,
                 max_queue_depth: int = 64):
        if not batchers:
            raise ValueError("Router needs at least one batcher")
        if max_queue_depth < 1:
            raise ValueError(
                f"max_queue_depth must be >= 1: {max_queue_depth}")
        self.batchers = list(batchers)
        self.max_queue_depth = int(max_queue_depth)

    # -- construction ------------------------------------------------------
    @classmethod
    def replicate(cls, cfg, params: Any, n_replicas: Optional[int] = None,
                  *, model_degree: int = 1,
                  devices: Optional[Sequence] = None,
                  max_queue_depth: int = 64,
                  n_slots: int = 8,
                  buckets: Optional[Sequence[int]] = None,
                  prefill_chunk: Optional[int] = None,
                  default_max_tokens: int = 64,
                  warmup: bool = True) -> "Router":
        """Build N engine+batcher replicas for one model over DEVICE
        GROUPS: each replica owns a ``model_degree``-sized consecutive
        group of ``devices`` (default: all local devices), its params
        laid out model-sharded over the group (``gpt.shard_specs``) and
        its KV cache sharded over heads — so a model bigger than one
        chip's HBM serves, each chip holding ~1/model_degree of the
        weights.  Replicas round-robin over the groups when
        ``n_replicas`` exceeds the group count; ``n_replicas=None``
        defaults to one replica per group.  ``model_degree=1`` keeps
        the original per-device placement byte-for-byte (groups of one
        device).  MIGRATION.md documents the signature change.

        A replica's engine is the default ``DecodeEngine``: it admits
        by free PAGES as well as free slots (the default pool is
        ``n_slots`` x the largest rung, shared by all rungs, where the
        pinned engine removed in PR 30 held a slab per slot per
        rung)."""
        from deeplearning4j_tpu.models import gpt
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
        from deeplearning4j_tpu.parallel.sharded_fit import named_shardings

        if model_degree < 1:
            raise ValueError(f"model_degree must be >= 1: {model_degree}")
        devices = list(devices) if devices is not None else jax.devices()
        n_groups = len(devices) // model_degree
        if n_groups < 1:
            raise ValueError(
                f"model_degree {model_degree} exceeds the {len(devices)} "
                f"available device(s): a replica needs one whole group")
        if n_replicas is None:
            n_replicas = n_groups
        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1: {n_replicas}")
        chunk = prefill_chunk or gpt.PREFILL_CHUNK
        batchers = []
        for i in range(n_replicas):
            if model_degree == 1:
                dev = devices[i % len(devices)]
                p = jax.device_put(params, dev)
                mesh = None
            else:
                group = devices[(i % n_groups) * model_degree:
                                (i % n_groups + 1) * model_degree]
                mesh = make_mesh(MeshSpec(data=1, model=model_degree),
                                 devices=group)
                p = jax.device_put(params, named_shardings(
                    mesh, gpt.shard_specs(cfg, model_degree=model_degree)))
            eng = DecodeEngine(cfg, p, n_slots=n_slots, buckets=buckets,
                               prefill_chunk=chunk, mesh=mesh)
            if warmup:
                eng.warmup()
            batchers.append(ContinuousBatcher(
                eng, default_max_tokens=default_max_tokens))
        return cls(batchers, max_queue_depth=max_queue_depth)

    # -- dispatch ----------------------------------------------------------
    def depths(self) -> list:
        return [b.depth() for b in self.batchers]

    def submit(self, prompt, **kw) -> DecodeRequest:
        """Route one request to the least-loaded replica; shed with
        :class:`OverloadedError` when every replica is at the bound."""
        depths = self.depths()
        i = int(np.argmin(depths))
        if depths[i] >= self.max_queue_depth:
            decode_metrics.note_shed()
            tr = telemetry.get_tracer()
            if tr is not None:
                tr.event("decode.shed", depth=depths[i],
                         bound=self.max_queue_depth,
                         replicas=len(self.batchers))
            raise OverloadedError(depths[i], self.max_queue_depth,
                                  len(self.batchers))
        return self.batchers[i].submit(prompt, **kw)

    def generate(self, prompt, timeout: Optional[float] = 120.0,
                 **kw) -> np.ndarray:
        return self.submit(prompt, **kw).result(timeout)

    # -- lifecycle ---------------------------------------------------------
    def close(self, timeout: float = 120.0) -> None:
        for b in self.batchers:
            b.close(timeout)

    def __enter__(self) -> "Router":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class AutoscalePolicy:
    """Hysteresis state machine turning live load signals into scale
    verdicts.  Pure host logic, clock-injected, no I/O — the synthetic
    load-trace tests drive it directly.

    An observation is HOT when the mean per-replica depth exceeds
    ``high_depth``, or when the TTFT p99 exceeds ``ttft_p99_slo_ms``
    (when set) WHILE there is live load (depth >= ``low_depth`` — the
    p99 reservoir is cumulative, and a past spike must not pin an idle
    fleet at max); COLD when the depth is under ``low_depth`` (and not
    hot).
    ``observe`` returns ``"up"`` only after ``up_after`` CONSECUTIVE
    hot observations, ``"down"`` after ``down_after`` consecutive cold
    ones — mixed observations reset both streaks — and never within
    ``cooldown_s`` of the previous action, so a load oscillating
    around a threshold holds the fleet steady instead of flapping it.
    Observations closer than ``interval_s`` apart are ignored (the
    router calls ``observe`` per submit; the interval turns that into
    a bounded sampling rate).  Replica bounds are enforced here too:
    ``"up"`` is never returned at ``max_replicas`` nor ``"down"`` at
    ``min_replicas``.

    ``ttft_p99_slo_ms`` reads the PROCESS-GLOBAL ``decode_metrics``
    TTFT reservoir (every counter family in this runtime is a
    process-wide singleton): with one router per process it is this
    router's own signal; a process hosting several routers/engines
    should scale on the depth thresholds, which are always computed
    from this router's own replicas."""

    def __init__(self, min_replicas: int = 1, max_replicas: int = 4, *,
                 high_depth: float = 8.0, low_depth: float = 1.0,
                 ttft_p99_slo_ms: Optional[float] = None,
                 up_after: int = 2, down_after: int = 6,
                 cooldown_s: float = 5.0, interval_s: float = 0.25):
        if not 1 <= min_replicas <= max_replicas:
            raise ValueError(
                f"need 1 <= min_replicas <= max_replicas: "
                f"{min_replicas}, {max_replicas}")
        if not 0 < low_depth < high_depth:
            # low_depth = 0 would make `cold` (depth < low) unreachable
            # — the fleet could never scale down, and the SLO signal's
            # live-load guard (depth >= low) would be vacuous at idle
            raise ValueError(
                f"need 0 < low_depth < high_depth: "
                f"{low_depth}, {high_depth}")
        if up_after < 1 or down_after < 1:
            raise ValueError("up_after/down_after must be >= 1")
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.high_depth = float(high_depth)
        self.low_depth = float(low_depth)
        self.ttft_p99_slo_ms = ttft_p99_slo_ms
        self.up_after = int(up_after)
        self.down_after = int(down_after)
        self.cooldown_s = float(cooldown_s)
        self.interval_s = float(interval_s)
        self._hot_streak = 0
        self._cold_streak = 0
        self._last_obs: Optional[float] = None
        self._last_action: Optional[float] = None

    def due(self, now: Optional[float] = None) -> bool:
        """Would :meth:`observe` consider an observation at ``now``?
        Read-only — the router's hot path checks this BEFORE paying
        for the metrics snapshot an observation consumes."""
        now = time.monotonic() if now is None else now
        return self._last_obs is None \
            or now - self._last_obs >= self.interval_s

    def observe(self, mean_depth: float,
                ttft_p99_ms: Optional[float],
                n_replicas: int,
                now: Optional[float] = None) -> str:
        """One load observation -> ``"up"`` / ``"down"`` / ``"hold"``.
        Not thread-safe on its own; the router serializes calls under
        its replica lock."""
        now = time.monotonic() if now is None else now
        if self._last_obs is not None \
                and now - self._last_obs < self.interval_s:
            return "hold"
        self._last_obs = now
        # the TTFT signal comes from a CUMULATIVE reservoir, so a past
        # spike would read hot forever; it only means "add replicas"
        # while there is live load for them to absorb — an idle fleet
        # must be able to go cold and scale down after a breach
        slo_hot = (self.ttft_p99_slo_ms is not None
                   and ttft_p99_ms is not None
                   and ttft_p99_ms > self.ttft_p99_slo_ms
                   and mean_depth >= self.low_depth)
        hot = mean_depth > self.high_depth or slo_hot
        cold = not hot and mean_depth < self.low_depth
        if hot:
            self._hot_streak += 1
            self._cold_streak = 0
        elif cold:
            self._cold_streak += 1
            self._hot_streak = 0
        else:
            self._hot_streak = self._cold_streak = 0
        cooled = self._last_action is None \
            or now - self._last_action >= self.cooldown_s
        if hot and self._hot_streak >= self.up_after and cooled \
                and n_replicas < self.max_replicas:
            self._hot_streak = self._cold_streak = 0
            self._last_action = now
            return "up"
        if cold and self._cold_streak >= self.down_after and cooled \
                and n_replicas > self.min_replicas:
            self._hot_streak = self._cold_streak = 0
            self._last_action = now
            return "down"
        return "hold"


class AutoscalingRouter(Router):
    """Least-depth dispatch over a DYNAMIC replica fleet: replicas are
    spawned from ``factory`` (a zero-arg callable returning a warmed
    ``ContinuousBatcher``) and retired on the policy's verdicts.

    - every ``submit`` feeds one (rate-limited) observation to the
      policy and applies its verdict;
    - a submit finding even the least-loaded replica at
      ``max_queue_depth`` triggers an EMERGENCY scale-up below
      ``max_replicas`` (the spawn happens on the submitting thread —
      later submitters wait on the replica lock rather than pile onto
      an overloaded fleet) and only sheds (``OverloadedError``, booked
      as ``shed_by_policy``) once the fleet is at its ceiling;
    - factory clones share the engine compile cache, so scale-up
      performs ZERO new XLA compiles after the first replica's warmup
      (asserted by the bench row);
    - scale-down pops the newest replica and drains it on a background
      thread (accepted requests run to completion; ``close()`` joins
      the drains).
    """

    def __init__(self, factory: Callable[[], ContinuousBatcher],
                 policy: Optional[AutoscalePolicy] = None, *,
                 max_queue_depth: int = 64,
                 health: Optional[ReplicaHealth] = None):
        self.factory = factory
        self.policy = policy or AutoscalePolicy()
        self.health = health
        self._lock = threading.RLock()
        self._drains: List[threading.Thread] = []
        self._closed = False
        self._spawning = False
        self._swapping = False
        # graceful-brownout ladder level (0 = normal, 1 = speculative
        # decoding off, 2 = + prefix harvesting bypassed): escalated
        # under pressure BEFORE shedding, de-escalated by tick() when
        # the fleet cools; every transition is booked and reversible
        self._brownout = 0
        # replicas temporarily excluded from routing (identity set):
        # swap_weights drains one replica at a time through here while
        # the rest keep serving — zero dropped requests
        self._draining: set = set()
        super().__init__([factory()
                          for _ in range(self.policy.min_replicas)],
                         max_queue_depth=max_queue_depth)
        self._monitor_stop = threading.Event()
        self._monitor: Optional[threading.Thread] = None
        if health is not None:
            self._monitor = threading.Thread(
                target=self._monitor_loop, name="dl4j-health-monitor",
                daemon=True)
            self._monitor.start()

    # -- construction ------------------------------------------------------
    @classmethod
    def replicate(cls, *a, **kw):
        """Not supported: the autoscaling router is built from a
        replica FACTORY (its constructor), not a fixed replica list —
        the inherited builder would crash confusingly."""
        raise TypeError(
            "AutoscalingRouter.replicate is not supported: construct "
            "AutoscalingRouter(factory, AutoscalePolicy(...)) with a "
            "zero-arg factory returning a warmed ContinuousBatcher "
            "(use Router.replicate for a fixed fleet)")

    # -- scaling -----------------------------------------------------------
    def n_replicas(self) -> int:
        with self._lock:
            return len(self.batchers)

    def depths(self) -> list:
        with self._lock:
            batchers = list(self.batchers)
        return [b.depth() for b in batchers]

    def tick(self, now: Optional[float] = None) -> str:
        """Feed one observation to the policy and apply its verdict.
        Called implicitly per submit; callable explicitly (e.g. by a
        drain loop) so a fleet scales DOWN after traffic stops."""
        now_v = time.monotonic() if now is None else now
        with self._lock:
            # interval gate FIRST: the common per-submit call returns
            # here without paying for the metrics snapshot (which
            # sorts the latency reservoirs under the global lock)
            if self._closed or not self.policy.due(now_v):
                return "hold"
            depths = [b.depth() for b in self.batchers]
            ttft = decode_metrics.snapshot()["ttft_p99_ms"]
            action = self.policy.observe(
                sum(depths) / len(depths), ttft, len(self.batchers),
                now=now_v)
            if action == "up":
                self._scale_up_async()
            elif action == "down":
                self._scale_down()
            if self._brownout and sum(depths) / len(depths) \
                    <= max(1.0, self.max_queue_depth / 4):
                # the fleet cooled well under the pressure bound: walk
                # the brownout ladder back one rung per (rate-limited)
                # observation — reversible, and each step is booked
                self._set_brownout(self._brownout - 1, "recovered")
        return action

    def _scale_up_async(self) -> None:
        """Policy-driven scale-up, OFF the replica lock: the factory's
        engine build + warmup take real time (device transfers; a cold
        compile-cache miss takes seconds), and holding the lock through
        them would stall every concurrent submit — including ones bound
        for healthy idle replicas.  One spawn in flight at a time; a
        spawn landing after close() closes its fresh replica instead of
        leaking it.  (The EMERGENCY path in submit stays synchronous on
        purpose: there the fleet is over-bound everywhere, and letting
        submitters pile on is worse than making them wait.)"""
        # under self._lock
        if self._spawning:
            return
        self._spawning = True

        def spawn():
            try:
                b = self.factory()
            except Exception:
                with self._lock:
                    self._spawning = False
                raise
            with self._lock:
                self._spawning = False
                # re-check BOTH gates at landing time: close() may have
                # run, and the emergency path may have filled the fleet
                # to the ceiling while this spawn was building
                if self._closed \
                        or len(self.batchers) >= self.policy.max_replicas:
                    doomed = b
                else:
                    self.batchers.append(b)  # jaxlint: disable=unlocked-shared-mutation — inside spawn's `with self._lock` above; the resolver does not model nested-def lock regions
                    self._apply_brownout(b)
                    decode_metrics.note_replicas(added=1)
                    tr = telemetry.get_tracer()
                    if tr is not None:
                        tr.event("decode.scale_up",
                                 replicas=len(self.batchers),
                                 reason="policy")
                    return
            doomed.close()

        t = threading.Thread(target=spawn, name="dl4j-replica-spawn",
                             daemon=True)
        with self._lock:            # re-entrant from tick's hold
            self._drains = [d for d in self._drains if d.is_alive()]
            self._drains.append(t)  # close() joins spawns like drains
        t.start()

    def _scale_up(self, reason: str) -> None:
        # re-entrant under the caller's self._lock hold (RLock): the
        # factory's engine construction + warmup() hit the shared
        # compile cache — no new XLA programs.
        with self._lock:
            self.batchers.append(self.factory())
            self._apply_brownout(self.batchers[-1])
        decode_metrics.note_replicas(added=1)
        tr = telemetry.get_tracer()
        if tr is not None:
            tr.event("decode.scale_up", replicas=self.n_replicas(),
                     reason=reason)

    def _scale_down(self) -> None:
        # re-entrant under the caller's self._lock hold (RLock); the
        # drained replica finishes its accepted requests on a
        # background thread
        with self._lock:
            b = self.batchers.pop()
        decode_metrics.note_replicas(removed=1)
        tr = telemetry.get_tracer()
        if tr is not None:
            tr.event("decode.scale_down", replicas=self.n_replicas())
        t = threading.Thread(target=b.close, name="dl4j-replica-drain",
                             daemon=True)
        t.start()
        # prune finished drains so a long-lived oscillating fleet
        # doesn't accumulate dead Thread objects without bound
        with self._lock:
            self._drains = [d for d in self._drains if d.is_alive()]
            self._drains.append(t)

    # -- replica health ----------------------------------------------------
    def _monitor_loop(self) -> None:
        """Replica health watchdog: poll HOST-side liveness signals and
        replace whatever fails diagnosis.  This thread must never touch
        device state — every signal it reads (thread liveness, error
        streaks, progress timestamps, queue depths) is a host field,
        and every wait is TIMED (machine-checked by jaxlint's
        blocking-in-health-monitor rule): a monitor blocked on a device
        sync or an unbounded join could itself be wedged by the very
        failure it exists to detect."""
        h = self.health
        while not self._monitor_stop.wait(h.poll_interval_s):
            with self._lock:
                if self._closed:
                    return
                replicas = [b for b in self.batchers
                            if b not in self._draining]
            for b in replicas:
                reason = self._diagnose(b, h)
                if reason is not None:
                    self.replace_replica(b, reason=reason)

    @staticmethod
    def _diagnose(b: ContinuousBatcher,
                  h: ReplicaHealth) -> Optional[str]:
        """One replica's health verdict — None (healthy) or the
        detection signal that tripped."""
        if not b.worker_alive():
            return "worker-dead"
        if b.dispatch_error_streak >= h.max_error_streak:
            return "error-streak"
        if b.depth() > 0 and b.progress_age() > h.stall_after_s:
            return "stalled"
        return None

    def replace_replica(self, batcher: ContinuousBatcher, *,
                        reason: str = "unhealthy") -> bool:
        """Retire an unhealthy replica and spawn its factory
        replacement — ZERO new compiles (the clone's warmup hits the
        shared compile cache, the autoscaling invariant).  Every
        unfinished request on the retired replica is evacuated and
        deterministically RE-DISPATCHED on the replacement: journaled
        as (prompt, seed, temperature, tokens emitted), each replays
        bit-identically from its last streamed token — replica death
        loses no request.  Returns False when the replica is already
        gone (or the router closed); True once the replacement serves.

        The spawn runs under the replica lock like the emergency
        scale-up: the fleet is degraded, and routing submits into a
        known-unhealthy replica while the replacement builds would be
        worse than making them wait."""
        with self._lock:
            if self._closed or batcher not in self.batchers:
                return False
            self.batchers.remove(batcher)
            decode_metrics.note_replicas(removed=1)
            self._scale_up(f"replace:{reason}")
            replacement = self.batchers[-1]
        decode_metrics.note_replica_replaced()
        tr = telemetry.get_tracer()
        if tr is not None:
            tr.event("decode.replica_replaced", reason=reason,
                     replicas=self.n_replicas())
        replayed = 0
        for r in batcher.evacuate():
            shadow = _ReplayRequest(r)
            decode_metrics.note_request_replayed()
            replayed += 1
            try:
                replacement.resubmit(shadow)
            except BatcherClosed:
                # the router closed mid-replacement: resolve the
                # client's handle rather than strand it
                r._force_finish(RouterClosed(
                    "router closed during replica replacement"))
        if tr is not None and replayed:
            tr.event("decode.requests_replayed", count=replayed,
                     reason=reason)
        # retire the carcass off-thread: close() joins a possibly
        # wedged worker — bounded, best-effort (the batcher is already
        # evacuated and out of routing; worst case its daemon thread
        # dies with the process)
        t = threading.Thread(target=lambda: batcher.close(timeout=5.0),
                             name="dl4j-replica-retire", daemon=True)
        with self._lock:
            self._drains = [d for d in self._drains if d.is_alive()]
            self._drains.append(t)
        t.start()
        return True

    # -- graceful brownout -------------------------------------------------
    def brownout_level(self) -> int:
        with self._lock:
            return self._brownout

    def _apply_brownout(self, b: ContinuousBatcher) -> None:
        # under self._lock; benign-race bools the worker reads per pass
        b.engine.spec_enabled = self._brownout < 1
        b.engine.harvest_enabled = self._brownout < 2

    def _set_brownout(self, level: int, reason: str) -> None:
        # under self._lock
        level = max(0, min(2, level))
        if level == self._brownout:
            return
        self._brownout = level
        for b in self.batchers:
            self._apply_brownout(b)
        decode_metrics.note_brownout(level)
        tr = telemetry.get_tracer()
        if tr is not None:
            tr.event("decode.brownout", level=level, reason=reason)

    # -- hot weight swap ---------------------------------------------------
    def swap_weights(self, params: Any, draft_params: Any = None, *,
                     timeout: float = 120.0) -> int:
        """Zero-downtime hot checkpoint swap: flip every replica to
        ``params`` one at a time, without dropping a request or
        compiling a new XLA program.

        Protocol per replica: exclude it from routing (``_draining``),
        poll its queue to zero (accepted requests finish on the OLD
        weights), ``engine.rebind_params`` + ``engine.current_params()``
        — the requantization cost lands HERE, on the swapping thread,
        never on a serving worker — then rejoin.  The rest of the fleet
        absorbs traffic throughout; a single-replica fleet first gains
        a temporary factory replica (old weights) so requests keep
        flowing while the real one drains — the temp is swapped too,
        then retired.  Afterwards each distinct shared
        :class:`~deeplearning4j_tpu.serving.decode.PrefixCache` is
        cleared once: its pages were computed under the old weights
        (``rebind_params`` already bumped the engine fingerprints, so
        stale hits were impossible; clearing reclaims the memory).

        Shapes are unchanged, so every rebound engine reuses its warmed
        executables — ``swap_compile_delta == 0`` is asserted by the
        bench drill.  Returns the number of replicas swapped.  Raises
        the typed :class:`SwapFailed` (a ``TimeoutError`` subclass,
        carrying per-replica drain states) if a replica fails to drain
        in ``timeout`` seconds — e.g. a fleet whose replicas are all
        unhealthy or wedged mid-drain — with the fleet left serving:
        swapped replicas keep the new weights, unswapped ones the
        old."""
        deadline = time.monotonic() + float(timeout)
        with self._lock:
            if self._closed:
                raise RouterClosed("AutoscalingRouter is closed")
            if self._swapping:
                raise RuntimeError("a weight swap is already in progress")
            self._swapping = True
        temp = None
        try:
            with self._lock:
                if len(self.batchers) == 1:
                    temp = self.factory()       # still the OLD weights
                    self.batchers.append(temp)
                    decode_metrics.note_replicas(added=1)
            swapped: set = set()                # id() of flipped replicas
            while True:
                with self._lock:
                    target = next((b for b in self.batchers
                                   if id(b) not in swapped), None)
                    if target is None:
                        break
                    self._draining.add(target)
                try:
                    while True:
                        if target.depth() == 0:
                            try:
                                target.engine.rebind_params(params,
                                                            draft_params)
                                break
                            except RuntimeError:
                                # depth hit 0 a beat before the worker
                                # released its last slot — retry
                                pass
                        if time.monotonic() > deadline:
                            raise SwapFailed(timeout,
                                             self._drain_states(),
                                             len(swapped))
                        time.sleep(0.005)
                    target.engine.current_params()
                    swapped.add(id(target))
                finally:
                    with self._lock:
                        self._draining.discard(target)
            with self._lock:
                batchers = list(self.batchers)
            seen: set = set()
            for b in batchers:
                store = getattr(b.engine, "_prefix", None)
                if store is not None and id(store) not in seen:
                    seen.add(id(store))
                    store.clear()
            if temp is not None:
                with self._lock:
                    if temp in self.batchers:
                        self.batchers.remove(temp)
                        decode_metrics.note_replicas(removed=1)
                    t = threading.Thread(target=temp.close,
                                         name="dl4j-replica-drain",
                                         daemon=True)
                    self._drains = [d for d in self._drains
                                    if d.is_alive()]
                    self._drains.append(t)
                t.start()
            decode_metrics.note_swap()
            tr = telemetry.get_tracer()
            if tr is not None:
                tr.event("decode.swap", replicas=len(swapped))
            return len(swapped)
        finally:
            with self._lock:
                self._swapping = False
                self._draining.clear()

    def _drain_states(self) -> Dict[int, Dict[str, Any]]:
        """Per-replica drain diagnostics for :class:`SwapFailed` —
        depth, worker liveness, and whether the replica is currently
        excluded from routing."""
        with self._lock:
            batchers = list(self.batchers)
            draining = set(self._draining)
        return {i: {"depth": b.depth(),
                    "worker_alive": b.worker_alive(),
                    "draining": b in draining}
                for i, b in enumerate(batchers)}

    # -- dispatch ----------------------------------------------------------
    def submit(self, prompt, **kw) -> DecodeRequest:
        self.tick()
        while True:
            with self._lock:
                if self._closed:
                    # closing must also stop SCALING: without this a
                    # racing submit could spawn a fresh replica close()
                    # never sees, leaking its worker thread
                    raise RouterClosed("AutoscalingRouter is closed")
                # replicas mid-swap-drain are excluded from routing;
                # the rest of the fleet absorbs their share (fall back
                # to the full list defensively if that empties it)
                live = [b for b in self.batchers
                        if b not in self._draining] or list(self.batchers)
                if self._swapping:
                    decode_metrics.note_request_during_swap()
                depths = [b.depth() for b in live]
                i = int(np.argmin(depths))
                if depths[i] >= self.max_queue_depth:
                    if len(self.batchers) < self.policy.max_replicas:
                        self._scale_up("pressure")
                        live.append(self.batchers[-1])
                        i = len(live) - 1
                    elif self._brownout < 2:
                        # graceful brownout BEFORE shedding: at the
                        # replica ceiling and over the depth bound,
                        # first trade throughput optimizations for
                        # headroom — speculative decoding off (draft
                        # dispatches freed), then prefix harvesting
                        # bypassed (reads + page refs freed) — and
                        # admit the request; only a fleet already at
                        # level 2 sheds.  tick() walks the ladder back
                        # down when the fleet cools.
                        self._set_brownout(self._brownout + 1,
                                           "pressure")
                    else:
                        decode_metrics.note_shed(by_policy=True)
                        tr = telemetry.get_tracer()
                        if tr is not None:
                            tr.event("decode.shed", depth=depths[i],
                                     bound=self.max_queue_depth,
                                     replicas=len(self.batchers),
                                     by_policy=True)
                        raise OverloadedError(depths[i],
                                              self.max_queue_depth,
                                              len(self.batchers))
                target = live[i]
            try:
                return target.submit(prompt, **kw)
            except RuntimeError:
                # the chosen replica was scaled down (and closed by its
                # drain) between our pick and the submit — it is no
                # longer in self.batchers, so re-pick from the live
                # fleet rather than leak the replica's closed error to
                # a client the fleet still has capacity for
                with self._lock:
                    if target in self.batchers:
                        raise       # genuinely closed: router shutdown

    # -- lifecycle ---------------------------------------------------------
    def close(self, timeout: float = 120.0) -> None:
        self._monitor_stop.set()         # health monitor exits first —
        with self._lock:                 # no replacement races close
            self._closed = True          # no more submits OR scale-ups
            batchers = list(self.batchers)
            drains = list(self._drains)
        if self._monitor is not None:
            self._monitor.join(timeout)
        for b in batchers:
            b.close(timeout)
        for t in drains:
            t.join(timeout)
