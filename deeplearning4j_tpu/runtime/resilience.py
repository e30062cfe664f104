"""Self-healing training: in-step anomaly guards + checkpoint-rollback.

The reference's fault story stops at the control plane — heartbeat
reaping and job requeue (MasterActor.java:139-169, SURVEY.md §5.3).
Nothing protects the *numerics* of a long run, which is where production
TPU jobs actually die: one bad batch produces a non-finite gradient, the
update writes NaN into every parameter, and hours of progress are gone
before a human looks at the loss curve.  Large-scale systems treat
detect-skip-rollback as a first-class training feature (TensorFlow's
fault-tolerant loop, arXiv:1605.08695; the preemption-heavy TPU operating
regime of arXiv:2605.25645); this module is that layer for the TPU port.

Three levels of defense, cheapest first:

1. **In-step guards** (device, zero extra dispatches): the donated
   train/solver steps call :func:`tree_all_finite` on (loss, grads) and
   :func:`where_ok`-select between the candidate update and the incoming
   state — a skipped step is a no-op that returns a ``skipped`` flag
   instead of silently propagating NaNs.  The select compiles into the
   SAME XLA program as the step (no ``lax.cond`` branch explosion, no
   extra compile on the steady-state path), and the guards run inside
   steps already routed through ``runtime/compile_cache.cached_jit`` so
   the stray-jit lint stays green and donation safety is untouched.
2. **Host-side rollback** (:class:`ResilientFit`): periodic
   auto-checkpoints of (params, updater state, step) through
   ``runtime/checkpoint.CheckpointManager``, a windowed
   :class:`LossSpikeDetector`, and on sustained anomaly a rollback to the
   last-good checkpoint with the run key re-folded — the retry sees a
   different batch order/noise stream — under a bounded retry budget
   with exponential backoff.
3. **Aggregation hardening** (host): :func:`result_all_finite` lets
   ``parallel/scaleout.WorkAccumulator`` reject non-finite/corrupt worker
   results instead of averaging them into the global params.

Every skip/rollback/reject increments ``runtime.metrics
.resilience_metrics`` so soak runs carry the fault-handling
evidence.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import os
import signal
import statistics
import threading
import time
from typing import Any, Deque, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.runtime import compile_cache, telemetry
from deeplearning4j_tpu.runtime.checkpoint import (AsyncCheckpointer,
                                                   CheckpointManager)
from deeplearning4j_tpu.runtime.metrics import (checkpoint_metrics,
                                                resilience_metrics)

log = logging.getLogger(__name__)

PyTree = Any


class DeviceLossError(RuntimeError):
    """A device (or slice) dropped out of the mesh mid-run.  Defined
    here (not in ``parallel/chaos.py``, which re-exports it) so the
    driver can catch it without importing the chaos/scaleout stack —
    that import path leads back into this module.  ``lost_ids`` names
    the failed devices; ``ResilientFit`` re-meshes over the survivors
    (``parallel.mesh.elastic_remesh``) and resumes from the last
    committed snapshot."""

    def __init__(self, lost_ids, message: Optional[str] = None):
        self.lost_ids = tuple(int(i) for i in lost_ids)
        super().__init__(
            message or f"device loss: ids {sorted(self.lost_ids)}")


# ---------------------------------------------------------------------------
# Preemption guard (SIGTERM/SIGINT -> final snapshot at a step boundary)
# ---------------------------------------------------------------------------

_GUARD_LOCK = threading.Lock()
_ACTIVE_GUARD: Optional["PreemptionGuard"] = None


def preemption_requested() -> bool:
    """One-global-read check the streaming fit loops poll at every step
    boundary: True when an installed :class:`PreemptionGuard` has seen
    a preemption signal (or a programmatic :meth:`PreemptionGuard
    .request`).  False when no guard is installed — plain fits keep
    their exact semantics."""
    g = _ACTIVE_GUARD
    return g is not None and g.requested()


class PreemptionGuard:
    """SIGTERM/SIGINT-driven preemption flag.

    Cloud preemption is a NOTICE, not a kill: the maintenance event
    delivers a signal and a grace window (arXiv 2605.25645's operating
    regime).  The handler only sets a flag — async-signal-safe by
    construction — and the training driver acts on it at the next STEP
    BOUNDARY: drain in-flight snapshots, write one final synchronous
    checkpoint, and return cleanly so the process exits 0 and a fresh
    process resumes with ``ResilienceConfig(resume=True)``.

    Use as a context manager (``ResilientFit.fit`` installs one around
    the loop when none is passed in).  Previous handlers are restored
    on exit; installation from a non-main thread — where Python forbids
    ``signal.signal`` — degrades to the programmatic :meth:`request`
    path instead of failing the fit.  A SECOND delivery of a guarded
    signal while the flag is already set restores the previous handler
    and re-raises — the graceful path is evidently stuck, and the run
    must stay killable without resorting to SIGKILL."""

    def __init__(self, signals: Sequence[int] = (signal.SIGTERM,
                                                 signal.SIGINT)):
        self.signals = tuple(signals)
        self._requested = threading.Event()
        self._old: dict = {}
        self._installed = False
        self._prev_active: Optional["PreemptionGuard"] = None
        self._depth = 0
        self._booked = False
        self._book_lock = threading.Lock()

    def request(self) -> None:
        """Flag a preemption (the handler's body; also the programmatic
        drill hook ``parallel.chaos.PreemptionChaos`` uses).

        The ONLY effect here is ``Event.set()``.  Metric/telemetry/log
        booking is deferred to :meth:`requested` because this body runs
        inside the SIGTERM/SIGINT handler: the metrics registry, the
        tracer, and the logging module all take non-reentrant locks, and
        the signal can land while the interrupted thread already holds
        one (e.g. mid ``note_staged``) — re-acquiring it from the
        handler would deadlock the process inside its grace window.
        This flag-only contract is machine-checked: jaxlint's
        ``impure-signal-handler`` rule resolves every callable
        registered through ``signal.signal`` (this class's ``_handler``
        included) and fails CI on locks/logging/metrics in its body."""
        self._requested.set()

    def requested(self) -> bool:
        r = self._requested.is_set()
        if r and not self._booked:
            # first observation, regular thread context — locks are
            # safe here, and every consumer (the fit loops, the module
            # check) routes through this method
            with self._book_lock:
                if not self._booked:
                    self._booked = True
                    checkpoint_metrics.note("preemptions_requested")
                    telemetry.event("resilience.preemption_requested")
                    log.warning("preemption requested — will snapshot "
                                "and stop at the next step boundary")
        return r

    def _handler(self, signum, frame) -> None:
        if self._requested.is_set():
            # second delivery: the graceful exit is evidently stuck
            # (wedged writer drain, hung dispatch) — hand the signal
            # back so the process stays killable instead of swallowing
            # every further Ctrl-C/SIGTERM behind the already-set flag.
            # Restoring the pre-guard handler and re-raising gives the
            # default action (SIGTERM kills, SIGINT raises
            # KeyboardInterrupt).  No locks here: handler context.
            prev = self._old.get(signum)
            try:
                signal.signal(signum, prev if prev is not None
                              else signal.SIG_DFL)
            except (ValueError, TypeError):
                signal.signal(signum, signal.SIG_DFL)
            signal.raise_signal(signum)
            return
        self.request()

    def __enter__(self) -> "PreemptionGuard":
        global _ACTIVE_GUARD
        with _GUARD_LOCK:
            self._depth += 1
            if self._depth > 1 and self._installed:
                # reentrant install: a caller-held guard handed back in
                # (ResilientFit.fit wraps its loop in `with guard:`
                # unconditionally).  Already live — re-registering would
                # capture OUR handler as the "previous" one and lose the
                # process originals on the way out.
                return self
            # depth > 1 but NOT installed: a shared guard first entered
            # from a worker thread (where signal.signal is forbidden)
            # degraded to programmatic-only — this entry may be the
            # first on the MAIN thread, i.e. the first that can
            # actually own the handlers.  Fall through and try again
            # rather than silently leaving this fit unguarded.
        with _GUARD_LOCK:
            if not self._installed:
                try:
                    for s in self.signals:
                        self._old[s] = signal.signal(s, self._handler)
                    self._installed = True
                except ValueError:
                    # non-main thread: signal delivery can't reach us;
                    # the request() path still works
                    self._old = {}
                    self._installed = False
            if _ACTIVE_GUARD is not self:
                self._prev_active = _ACTIVE_GUARD
                _ACTIVE_GUARD = self
        return self

    def __exit__(self, *exc) -> bool:
        global _ACTIVE_GUARD
        with _GUARD_LOCK:
            self._depth -= 1
            if self._depth > 0:
                return False    # outermost enter owns the teardown
        if self._installed:
            for s, h in self._old.items():
                try:
                    signal.signal(s, h)
                except ValueError:
                    # final exit on a non-main thread (overlapped
                    # shared-guard usage where the main thread
                    # installed): Python forbids restoring from here —
                    # the handlers stay until the process exits, a
                    # strictly safer leak than an unguarded fit
                    pass
            self._old = {}
            self._installed = False
        with _GUARD_LOCK:
            if _ACTIVE_GUARD is self:
                _ACTIVE_GUARD = self._prev_active
            else:
                # non-LIFO overlap (two concurrent fits on different
                # threads, each with its own guard): blindly restoring
                # our predecessor would hide the still-live newer guard
                # — or resurrect a dead one whose set flag silently
                # stops every later fit at batch 0.  Splice self out of
                # the chain instead.
                g = _ACTIVE_GUARD
                while g is not None and g._prev_active is not self:
                    g = g._prev_active
                if g is not None:
                    g._prev_active = self._prev_active
            self._prev_active = None
        return False


# ---------------------------------------------------------------------------
# In-graph guards (used INSIDE jitted steps — pure jnp, no dispatches)
# ---------------------------------------------------------------------------

def tree_all_finite(tree: PyTree) -> jax.Array:
    """Scalar bool: every inexact (float/complex) leaf is all-finite.

    Integer/bool leaves are skipped — they cannot hold NaN/Inf and
    ``isfinite`` on them is wasted work.  Safe under jit; the reduction
    fuses into the surrounding step program."""
    checks = [jnp.all(jnp.isfinite(leaf)) for leaf in jax.tree.leaves(tree)
              if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact)]
    if not checks:
        return jnp.bool_(True)
    ok = checks[0]
    for c in checks[1:]:
        ok = jnp.logical_and(ok, c)
    return ok


def where_ok(ok: jax.Array, new: PyTree, old: PyTree) -> PyTree:
    """Select ``new`` where ``ok`` (scalar bool) else ``old``, leafwise.

    This is the skip primitive: both trees are already materialized
    inside the step, so the select is a cheap elementwise op in the same
    program — unlike ``lax.cond``, it cannot introduce a second traced
    branch, and it composes with buffer donation (XLA still aliases the
    donated input into whichever value wins)."""
    return jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, old)


def guard_update(params: PyTree, ustate: PyTree, new_params: PyTree,
                 new_ustate: PyTree, *guard_values: PyTree):
    """The full in-step guard: check ``guard_values`` (typically
    ``(score, grads)``) for non-finites; on failure keep the incoming
    params/updater-state.  Returns ``(params, ustate, skipped)`` where
    ``skipped`` is an int32 scalar (1 = update dropped) so callers can
    sum skip counts on device without a host sync per step."""
    ok = tree_all_finite(guard_values)
    return (where_ok(ok, new_params, params),
            where_ok(ok, new_ustate, ustate),
            (~ok).astype(jnp.int32))


def note_skips(skips, where: str = "train") -> int:
    """Book guard-skipped steps into ``resilience_metrics`` with ONE
    device sync for a whole fit/optimize call.  ``skips`` is either a
    list of per-step device scalars (streaming loops) or a flag array
    (scan outputs); returns the count.  The single shared implementation
    for every guarded loop — multilayer, solver, data-parallel, api."""
    if skips is None:
        return 0
    if isinstance(skips, (list, tuple)):
        if not skips:
            return 0
        skips = jnp.stack(list(skips))
    n = int(jnp.sum(skips))
    if n:
        resilience_metrics.note("steps_skipped", n)
        telemetry.event("resilience.guard_skips", count=n, where=where)
        log.warning("non-finite loss/gradient: %d %s step update(s) "
                    "skipped by the in-step guard", n, where)
    return n


# ---------------------------------------------------------------------------
# Host-side checks (aggregation hardening, checkpoint validation)
# ---------------------------------------------------------------------------

def result_all_finite(result: PyTree) -> bool:
    """Host-side: a worker-posted result is a NUMERIC pytree whose every
    float leaf is finite.  Non-numeric leaves (strings, objects — a
    wrong-typed or truncated payload) count as corrupt, as does anything
    that fails to flatten or materialize: the caller averages results,
    so its only safe move is rejection either way.  Checking the type
    here (not just finiteness) matters for the FIRST result of a round —
    there is no previous aggregate to structurally mismatch against, so
    an unchecked corrupt first result would become the baseline that
    rejects every later healthy one."""
    try:
        for leaf in jax.tree.leaves(result):
            arr = np.asarray(leaf)
            if arr.dtype.kind not in "bifcu":
                return False
            if arr.dtype.kind in "fc" and not np.isfinite(arr).all():
                return False
        return True
    except Exception:  # noqa: BLE001 — corrupt payloads throw anything
        return False


def compiled_all_finite(tree: PyTree) -> bool:
    """Device-side all-finite reduction for HOST callers (e.g. validating
    restored checkpoints without pulling every leaf to host).  Compiled
    through the engine — instrument-only, no cross-instance key (the
    input structure varies per caller)."""
    fn = compile_cache.get_or_build(
        ("resilience_all_finite",),
        lambda: compile_cache.cached_jit(
            tree_all_finite, label="resilience.all_finite"))
    return bool(fn(tree))


# ---------------------------------------------------------------------------
# Loss-spike detection (host)
# ---------------------------------------------------------------------------

class LossSpikeDetector:
    """Windowed anomaly detector over the per-step loss stream.

    A step is *anomalous* when its loss is non-finite, or exceeds
    ``factor ×`` the median of the last ``window`` healthy losses (median,
    not mean — one spike must not drag the baseline up after itself).
    ``observe`` returns True only after ``patience`` CONSECUTIVE
    anomalies: transient bad batches are already neutralized by the
    in-step guard, so rollback is reserved for sustained divergence.
    The baseline needs ``min_history`` healthy samples before spikes can
    fire at all (early-training loss is legitimately wild)."""

    def __init__(self, window: int = 20, factor: float = 3.0,
                 patience: int = 5, min_history: int = 5):
        self.window = window
        self.factor = factor
        self.patience = patience
        self.min_history = min_history
        self._healthy: Deque[float] = collections.deque(maxlen=window)
        self._streak = 0

    def observe(self, loss: float) -> bool:
        """Feed one step's loss; True == sustained anomaly (roll back)."""
        anomalous = not np.isfinite(loss)
        if (not anomalous and self._healthy
                and len(self._healthy) >= self.min_history):
            baseline = statistics.median(self._healthy)
            # guard the degenerate all-zero baseline (|b| small): any
            # loss is "a spike" relative to 0 — require an absolute
            # floor so converged-to-zero runs don't false-positive
            anomalous = loss > max(abs(baseline) * self.factor, 1e-12) \
                and abs(baseline) > 0
        if anomalous:
            self._streak += 1
            resilience_metrics.note("spikes_detected")
        else:
            self._streak = 0
            self._healthy.append(loss)
        return self._streak >= self.patience

    def reset(self) -> None:
        """Forget the streak AND the baseline — after a rollback the run
        replays from an older loss regime; judging it against the
        diverged window would re-trigger immediately."""
        self._healthy.clear()
        self._streak = 0


# ---------------------------------------------------------------------------
# ResilientFit — checkpoint-rollback training driver
# ---------------------------------------------------------------------------

class RetryBudgetExceeded(RuntimeError):
    """Raised when sustained anomalies outlive the rollback budget —
    the run is genuinely diverging (or its data is poisoned) and needs a
    human, not another retry."""


@dataclasses.dataclass
class ResilienceConfig:
    """Knobs for :class:`ResilientFit` (README: "Self-healing training").

    ``checkpoint_every`` is in steps; ``max_rollbacks`` bounds the retry
    budget per fit call; ``backoff_s`` doubles per rollback.  ``resume``
    continues from the newest checkpoint in ``checkpoint_dir`` (the
    preemption-restart path); ``max_steps`` bounds how many steps THIS
    invocation runs before checkpointing and returning (bounded-slice
    training for preemptible capacity).  ``shuffle`` derives a
    deterministic per-epoch batch order from the run key — which the
    rollback path re-folds, so a retry sees different batch order.

    Cadence snapshots are ASYNC by default (``checkpoint.
    AsyncCheckpointer``: device->host copy forked off the step,
    serialization + commit on a writer thread, at most
    ``max_in_flight`` snapshots pending with backpressure);
    ``sync=True`` is the escape hatch back to blocking on-thread saves
    (MIGRATION.md)."""

    checkpoint_dir: str
    checkpoint_every: int = 50
    max_to_keep: int = 3
    spike_window: int = 20
    spike_factor: float = 3.0
    patience: int = 5
    min_history: int = 5
    max_rollbacks: int = 3
    backoff_s: float = 0.0
    resume: bool = False
    max_steps: Optional[int] = None
    shuffle: bool = True
    sync: bool = False
    max_in_flight: int = 2
    #: multi-host knobs (only read when a ``cluster`` with >1 member is
    #: passed to ResilientFit): control-plane op deadline, and the
    #: shared-filesystem heartbeat cadence/staleness threshold that
    #: turns a silent peer into a host-loss finding
    cluster_timeout_s: float = 120.0
    hb_interval_s: float = 2.0
    hb_timeout_s: float = 20.0
    #: distributed data service (``datasets.data_service``): None =
    #: auto (on when the mesh spans processes — each host then reads
    #: and stages only its 1/n_hosts slice instead of the whole global
    #: batch); True forces it (e.g. thread-"host" drills with
    #: mesh=None); False keeps the legacy identical-global-batch
    #: staging (MIGRATION.md — deprecated on spanning meshes)
    data_service: Optional[bool] = None

    def __post_init__(self) -> None:
        # fail at construction, not one `step % checkpoint_every` into
        # a paid-for fit; 0 is a natural misspelling of "no cadence
        # snapshots", which isn't a mode the driver offers (the
        # rollback/resume machinery needs at least the cadence saves)
        if self.checkpoint_every <= 0:
            raise ValueError(
                f"checkpoint_every must be a positive step count, "
                f"got {self.checkpoint_every}")
        if self.max_in_flight < 1:
            raise ValueError(
                f"max_in_flight must be >= 1, got {self.max_in_flight}")


class ResilientFit:
    """Self-healing supervised training over a ``MultiLayerNetwork``-style
    model: the streaming per-step loop of ``fit_backprop`` plus
    auto-checkpointing, loss-spike detection, and rollback-with-refold.

    The driver consumes the model's ENGINE step (``_backprop_machinery``)
    directly, so the in-step guard, donation contract, and cross-network
    compile sharing all apply unchanged; what it adds is host policy.
    Checkpoints carry ``(params, updater state)`` plus step/rollback
    counters in the sidecar meta, so a killed run resumes exactly —
    tested to be step-for-step equivalent to an uninterrupted run.

    ``detector`` is injectable for tests/soak harnesses; the default is
    a :class:`LossSpikeDetector` built from the config.

    ``mesh`` (a Mesh with a ``data`` axis) runs the driver on the
    SHARDED engine step: batch axis over ``data``, grads psum'd
    in-graph, guard skips decided collectively so replicas never
    diverge — checkpoints, rollback, and resume are unchanged host
    policy on top (resume is step-for-step equivalent to an
    uninterrupted sharded run; tested).  A data×model mesh (driving a
    model whose machinery lays params out with ``NamedSharding`` —
    ``models/lm_fit.CausalLM``) works identically: snapshots gather
    the logical arrays, restores re-shard through the engine step's
    pinned layouts, and resume stays bit-exact on the same mesh.
    Default None keeps the single-device step byte-for-byte as before.

    Robustness upgrades (ROADMAP item 4):

    - cadence snapshots run through an :class:`AsyncCheckpointer` by
      default (``config.sync=True`` opts out) — the step never waits
      for host I/O, in-flight snapshots are bounded, and every commit
      is crash-safe (manifest protocol);
    - a :class:`PreemptionGuard` is installed for the duration of the
      fit (pass ``preemption_guard=`` to share one across drivers):
      on SIGTERM/SIGINT the loop stops at the next step boundary,
      drains in-flight snapshots, writes one final SYNC snapshot, and
      returns cleanly with ``self.preempted = True``;
    - a :class:`DeviceLossError` — raised by an injected
      ``fault_hook(step)`` (``parallel.chaos.DeviceLossChaos``) or by
      caller code that translates a platform-specific backend failure
      into one (the repo ships no such translation; identifying the
      lost device ids is runtime-specific) — triggers ELASTIC resume:
      re-mesh over the surviving devices
      with ``grad_accum`` scaled to preserve the effective batch
      (``parallel.mesh.elastic_remesh`` — bit-exact vs the
      uninterrupted run), restore the last committed snapshot, and
      continue.

    Multi-host (``cluster=`` a ``parallel.multihost.Cluster`` with >1
    member — the launcher wires it from
    ``--coordinator/--num-processes/--process-id``): the same driver
    becomes the cluster runtime.  Snapshots are CLUSTER-committed (the
    coordinator writes the manifest only after a barrier proves every
    member's data durable — a snapshot no host can restore from is
    never "committed"); one member's SIGTERM propagates through a
    per-boundary cluster-wide flag OR so EVERY member drains at the
    same step and the final snapshot is one cluster-consistent state;
    guard-skip / loss-scale / rollback verdicts stay replica-consistent
    across hosts by construction (they derive from the psum'd
    collective score/grads).  A host LOSS — detected by the shared-fs
    :class:`~deeplearning4j_tpu.parallel.multihost.HostHeartbeat` when
    a control-plane sync times out, or reported as a
    :class:`DeviceLossError` naming the dead host's devices — is
    settled cluster-wide: survivors agree on the lost ids, shrink to a
    new cluster generation, ``elastic_remesh`` the device mesh if it
    contained the lost devices, restore the last cluster-committed
    snapshot, and continue; the member whose OWN devices were lost
    exits cleanly with ``self.evicted = True`` instead (the survivors
    carry the run)."""

    def __init__(self, net, config: ResilienceConfig,
                 detector: Optional[LossSpikeDetector] = None,
                 mesh=None, fault_hook=None,
                 preemption_guard: Optional[PreemptionGuard] = None,
                 cluster=None):
        self.net = net
        self.mesh = mesh
        self.config = config
        self.fault_hook = fault_hook
        self.preemption_guard = preemption_guard
        #: ``parallel.multihost.Cluster`` (or None = single-process).
        #: With >1 member the driver becomes the multi-host runtime:
        #: cluster-committed snapshots, per-step preemption-flag OR,
        #: and host-loss recovery (eviction / shrink-and-resume).
        #: Shrunk in place by ``_elastic_resume`` when a host dies.
        self.cluster = cluster
        self.manager = CheckpointManager(config.checkpoint_dir,
                                         max_to_keep=config.max_to_keep,
                                         cluster=cluster)
        self.async_ckpt = None if config.sync else AsyncCheckpointer(
            self.manager, max_in_flight=config.max_in_flight)
        self.detector = detector or LossSpikeDetector(
            window=config.spike_window, factor=config.spike_factor,
            patience=config.patience, min_history=config.min_history)
        #: filled by fit(): total steps run, rollbacks performed,
        #: preemption flag, elastic re-mesh count
        self.steps_run = 0
        self.rollbacks = 0
        self.preempted = False
        self.remeshes = 0
        #: True when THIS member's devices were the lost ones — the
        #: member exits the fit cleanly (exit 0; the survivors carry
        #: the run) instead of crashing the launcher
        self.evicted = False
        #: shared-fs heartbeat monitor, live only inside a multi-host
        #: fit (``_heartbeat``); consulted to translate control-plane
        #: timeouts into host-loss findings
        self._heartbeat = None
        #: driver-scoped grad_accum override set by elastic resume —
        #: the user's conf object is never left mutated
        self.elastic_accum: Optional[int] = None

    @property
    def _multi(self) -> bool:
        return (self.cluster is not None
                and self.cluster.process_count > 1)

    def _recycle_writer(self, suppress_errors: bool) -> None:
        """close() the async checkpointer — drain (committing every
        queued snapshot) and stop the writer thread — then stand up a
        fresh one so a later ``fit(resume=True)`` on this driver works.
        ``suppress_errors`` is the error-exit mode: an exception is
        already propagating out of fit(), so a drain failure here must
        be logged, never raised over the original error."""
        if self.async_ckpt is None:
            return
        try:
            self.async_ckpt.close()
        except Exception:
            if not suppress_errors:
                raise
            log.exception("checkpoint writer shutdown failed while "
                          "handling a fit error")
        finally:
            self.async_ckpt = AsyncCheckpointer(
                self.manager, max_in_flight=self.config.max_in_flight)

    @contextlib.contextmanager
    def _writer_guard(self):
        """Error exits out of the fit loop (RetryBudgetExceeded, a
        poisoned restore, a single-device device loss, ...) must not
        strand queued async snapshots uncommitted or leak the writer
        thread parked on its queue — MIGRATION.md promises every
        requested snapshot is committed before fit returns, raised or
        not."""
        try:
            yield
        except BaseException:
            self._recycle_writer(suppress_errors=True)
            raise

    def _drain(self) -> None:
        """Wait for every in-flight async snapshot to COMMIT — the
        precondition for any restore (rollback, elastic resume) and for
        the final/preemption snapshot's ordering guarantee."""
        if self.async_ckpt is not None:
            self.async_ckpt.wait_until_finished()

    @staticmethod
    def _check_restored(params: PyTree, at_step) -> None:
        """A rollback target or resume point must itself be healthy:
        restoring a NaN-poisoned checkpoint would put the run in a state
        no amount of retrying can heal (device-side check — one scalar
        sync instead of pulling every restored leaf to host)."""
        if not compiled_all_finite(params):
            raise RuntimeError(
                f"checkpoint at step {at_step} contains non-finite "
                "params — refusing to restore a poisoned state")

    # -- deterministic schedule -------------------------------------------
    def _epoch_order(self, run_key, seed: int, rollbacks: int, epoch: int,
                     n_batches: int) -> List[int]:
        """Batch visit order for one epoch — a pure function of
        (seed, rollbacks, epoch) so resume replays it exactly, while a
        rollback (which bumps ``rollbacks``) reshuffles the retry.
        Memoized per (seed, rollbacks, epoch): the driver asks once per
        STEP, and a device permutation dispatch per step would be pure
        waste.  ``seed`` must key the memo too — a second fit() on the
        same driver with a different seed must not replay the old order."""
        if not self.config.shuffle or n_batches <= 1:
            return list(range(n_batches))
        memo_key = (seed, rollbacks, epoch, n_batches)
        if getattr(self, "_order_memo_key", None) != memo_key:
            k = jax.random.fold_in(
                jax.random.fold_in(run_key, 7 + rollbacks), epoch)
            self._order_memo_key = memo_key
            self._order_memo = [int(i)
                                for i in jax.random.permutation(k, n_batches)]
        return self._order_memo

    # -- machinery ---------------------------------------------------------
    def _build_dispatch(self, net):
        """(dispatch, updaters) for the CURRENT ``self.mesh`` and
        effective grad_accum — rebuilt by the elastic-resume path after
        a re-mesh (new mesh signature + conf JSON = a fresh engine
        entry, never a cross-mesh cache hit).  The driver's
        ``elastic_accum`` override applies only for the build's
        duration: the accum is baked into the compiled step via the
        conf, but the USER's configuration object is never left
        mutated — a later independent fit on a healed fleet must see
        the accum the user set, not the recovery's."""
        orig_accum = net.conf.grad_accum
        if self.elastic_accum is not None:
            net.conf.grad_accum = self.elastic_accum
        try:
            train_step, _, updaters = net._backprop_machinery(self.mesh)
            # DP-mode steps take (x, y, n_valid) with zero-padded rows
            # masked out of loss/grad (parallel/mesh padding contract)
            dp_mode = getattr(train_step, "takes_n_valid", False)
            pad_chunk = net._pad_chunk(
                self.mesh, max(net.conf.grad_accum, 1)) if dp_mode else 1
            # ustate construction delegates to the model's own policy
            # (MultiLayerNetwork._init_ustate: the bundle's init_ustate
            # when it has one — mixed precision threads loss-scale state
            # through the updater slot — else the per-layer list); bound
            # here so fit/restore templates can never drift from it
            self._ustate_init = (
                lambda params, _ts=train_step, _u=updaters:
                net._init_ustate(_ts, _u, params))
        finally:
            net.conf.grad_accum = orig_accum

        # a mesh spanning processes needs multi-host staging: each
        # process contributes only ITS row slice of the global batch
        # (jax.make_array_from_process_local_data) — a host-local
        # device_put cannot address another host's devices
        spans_hosts = (self.mesh is not None and self._multi
                       and len({d.process_index
                                for d in self.mesh.devices.flat}) > 1)
        # geometry the data service binds to (``_configure_service``):
        # its pre-sharded staging must pad to the SAME target the
        # legacy path below computes, or the compiled step would see a
        # second shape (compile_delta != 0) and lose bit-exactness
        self._dispatch_dp_mode = dp_mode
        self._dispatch_pad_chunk = pad_chunk
        self._dispatch_spans = spans_hosts

        def dispatch(params, ustate, batch, key, at_step):
            if getattr(batch, "staged_global", False):
                # data-service batch: already padded + landed on the
                # mesh (pre-sharded across hosts when spanning) by the
                # prefetch producer — dispatch is a pure step call
                if not dp_mode:
                    return train_step(params, ustate, batch.features,
                                      batch.labels, key, at_step)
                return train_step(
                    params, ustate, (batch.features, batch.labels,
                                     jnp.int32(batch.n_valid)),
                    key, at_step)
            if not dp_mode:
                return train_step(params, ustate, batch.features,
                                  batch.labels, key, at_step)
            b = batch.features.shape[0]
            target = -(-b // pad_chunk) * pad_chunk
            x = net._pad_rows(batch.features, target)
            y = net._pad_rows(batch.labels, target)
            if spans_hosts:
                from deeplearning4j_tpu.parallel import multihost
                x, y = multihost.stage_global_batch(
                    x, y, self.mesh, self.cluster)
            return train_step(params, ustate, (x, y, jnp.int32(b)),
                              key, at_step)

        return dispatch, updaters

    def _make_ustate(self, updaters, params):
        """Fresh updater state matching the CURRENT dispatch's engine
        step (one policy — ``MultiLayerNetwork._init_ustate`` — bound in
        ``_build_dispatch``; plain per-layer fallback only before any
        dispatch exists)."""
        init = getattr(self, "_ustate_init", None)
        if init is not None:
            return init(params)
        return [u.init(p) for u, p in zip(updaters, params)]

    def _restore_latest(self, net, updaters):
        """Restore the newest COMMITTED checkpoint (corrupt/uncommitted
        steps fall back to the previous good one — CheckpointManager's
        manifest protocol) against fresh templates."""
        tpl_p = jax.tree.map(jnp.copy, net._require_params())
        tpl_u = self._make_ustate(updaters, tpl_p)
        (params, ustate), meta = self.manager.restore(like=(tpl_p, tpl_u))
        self._check_restored(params, meta.get("step"))
        # elastic resume reads the data-service reader state out of the
        # restored meta AFTER _elastic_resume returns — stash it here
        # (the one restore chokepoint) rather than widening every
        # return signature
        self._last_restore_meta = meta
        return params, ustate, meta

    def _configure_service(self, service) -> None:
        """Bind the data service to the CURRENT dispatch geometry
        (fresh build or elastic-resume rebuild): read plan for the
        current cluster generation, the dispatch's pad chunk so staged
        shapes match the legacy path bit-for-bit, and whether staging
        must pre-shard across processes."""
        service.configure(mesh=self.mesh, cluster=self.cluster,
                          pad_chunk=self._dispatch_pad_chunk,
                          dp_mode=self._dispatch_dp_mode,
                          spans=self._dispatch_spans)

    def _translate_sync_timeout(self, err) -> DeviceLossError:
        """A control-plane timeout on a LIVE cluster means a peer went
        silent.  The heartbeat monitor names it: stale members become a
        host-loss finding (their device ids); a timeout with every peer
        still beating is a genuine infrastructure fault and re-raises
        as-is — "recovering" from a slow-but-alive peer would fork the
        run."""
        hb = self._heartbeat
        stale = hb.stale_members() if hb is not None else ()
        if not stale:
            raise err
        lost = []
        for m in stale:
            lost.extend(self.cluster.devices_of(m))
        log.error(
            "cluster sync timed out and member(s) %s have stale "
            "heartbeats — treating as host loss (devices %s)",
            list(stale), lost)
        return DeviceLossError(
            lost, f"host loss: members {sorted(stale)} stopped "
            f"heartbeating ({err})")

    def _cluster_flag(self, flag: bool) -> bool:
        """Cluster-wide OR of this member's preemption flag — every
        member sees the verdict in the SAME round, so all of them stop
        at the same step boundary.  Control-plane timeouts translate to
        host loss like any other sync."""
        if not self._multi:
            return flag
        from deeplearning4j_tpu.parallel.multihost import \
            ClusterSyncTimeout

        try:
            return self.cluster.any_flag(
                flag, "preempt",
                timeout_s=self.config.cluster_timeout_s)
        except ClusterSyncTimeout as e:
            raise self._translate_sync_timeout(e) from e

    def _host_loss_update(self, err: DeviceLossError):
        """Cluster-level half of a loss event: agree on the lost ids
        with the responsive members, evict self if OUR devices are the
        lost ones, else shrink the cluster to the survivors (new
        generation — fresh barrier namespace, re-elected coordinator).
        Returns (lost_ids, evicted)."""
        from deeplearning4j_tpu.runtime.metrics import multihost_metrics

        cl = self.cluster
        hb = self._heartbeat
        suspects = tuple(hb.stale_members()) if hb is not None else ()
        # publish the WHOLE local view — dispatch-reported ids plus this
        # member's heartbeat findings — into the agreement round, so the
        # union every responsive member reads back is identical.  The
        # previous shape (agree on err.lost_ids alone, union the local
        # heartbeat findings AFTER) let two members with different
        # heartbeat-staleness views compute different lost sets, and a
        # divergent lost set is a divergent shrink(): a generation fork
        # whose next rendezvous deadlocks until timeout.  Found by
        # jaxlint's cluster-sync-in-divergent-branch rule when it
        # landed; regression-tested in test_multihost_runtime.py.
        local_ids = set(int(i) for i in err.lost_ids)
        if hb is not None:
            local_ids.update(hb.lost_device_ids())
        lost = set(cl.agree_lost_ids(
            sorted(local_ids), suspects=suspects,
            timeout_s=self.config.cluster_timeout_s))
        lost_members = list(cl.owners_of(lost))
        if suspects:
            lost_members = sorted(set(lost_members) | set(suspects))
        if cl.process_id in lost_members:
            multihost_metrics.note("evictions")
            telemetry.event("resilience.evicted",
                            lost=sorted(lost), member=cl.process_id)
            log.warning(
                "this member's devices are among the lost (%s) — "
                "exiting the fit cleanly; the survivors carry the run",
                sorted(lost))
            return tuple(sorted(lost)), True
        if lost_members:
            multihost_metrics.note("host_losses")
            # the residual divergence is the DESIGN: the evicted member
            # returned above and never rejoins a rendezvous, the lost
            # set is cluster-agreed (whole local views published into
            # the round), and a suspect-view skew between survivors
            # settles at the next sync timeout against the shared-fs
            # heartbeats
            survivors = cl.shrink(lost_members)  # jaxlint: disable=cluster-sync-in-divergent-branch — eviction/shrink divergence is the designed recovery protocol (agreed lost set; evicted member exits)
            log.warning(
                "host loss: member(s) %s evicted, surviving cluster "
                "%s (coordinator %d)", lost_members, survivors.members,
                survivors.coordinator)
            telemetry.event("resilience.host_loss",
                            lost_members=lost_members,
                            survivors=list(survivors.members))
            self.cluster = survivors
            self.manager.cluster = survivors
            if hb is not None:
                hb.cluster = survivors
        return tuple(sorted(lost)), False

    def _elastic_resume(self, err: DeviceLossError, net):
        """Device/host loss -> re-mesh over survivors (effective batch
        preserved via grad_accum scaling) -> restore last committed
        snapshot.  Returns (dispatch, updaters, params, ustate, step),
        or None when THIS member was evicted (its own devices are the
        lost ones — the caller exits the fit cleanly).

        Single-process single-device runs have nothing to shrink onto —
        the loss re-raises.  data×model meshes shrink their DATA axis
        only (``parallel.mesh.elastic_remesh`` keeps whole model groups
        intact — the tensor-parallel weight layout survives the re-mesh
        verbatim; too few survivors for one group raises with the
        surviving count and required divisor).  Under a multi-member
        cluster the loss is first settled at HOST level
        (``_host_loss_update``): survivors agree on the lost ids over
        the control plane, shrink to a new cluster generation, and only
        then shrink the device mesh — when the local mesh never
        contained the lost devices (they were another host's), the mesh
        survives verbatim and recovery is restore-and-continue."""
        from deeplearning4j_tpu.parallel import mesh as mesh_lib

        checkpoint_metrics.note("device_losses")
        # drain in-flight snapshots FIRST, while the old cluster
        # generation is still in place: lockstep pending saves
        # rendezvous among all members (an injected drill keeps every
        # process alive, so even the member about to be evicted
        # completes them); a genuinely dead host times the drain out,
        # the uncommitted snapshot is dropped, and the restore below
        # falls back one cadence — the documented cost of a mid-save
        # loss
        try:
            self._drain()
        except Exception:  # noqa: BLE001 — incl. ClusterSyncTimeout
            if not self._multi:
                raise
            log.warning("in-flight snapshot died with the lost host; "
                        "restoring the previous committed step")
            self._recycle_writer(suppress_errors=True)
        lost_ids = tuple(err.lost_ids)
        cluster_loss = False
        if self._multi:
            lost_ids, evicted = self._host_loss_update(err)
            if evicted:
                return None
            cluster_loss = True
        if self.mesh is None and not cluster_loss:
            raise err
        members = ({int(d.id) for d in self.mesh.devices.flat}
                   if self.mesh is not None else set())
        mesh_hit = bool(members & {int(i) for i in lost_ids})
        if not mesh_hit and not cluster_loss:
            # stale/foreign ids (a detector re-reporting an already-
            # evicted device): "recovering" would rebuild an identical
            # mesh and retry the same step forever.  Each genuine loss
            # strictly shrinks the mesh, so this check also bounds the
            # recovery loop by the initial device count.
            log.error(
                "device loss reports ids %s, none of which are in the "
                "current mesh %s — stale detector? re-raising",
                sorted(set(int(i) for i in lost_ids)),
                sorted(members))
            raise err
        old_accum = max(self.elastic_accum or net.conf.grad_accum, 1)
        if mesh_hit:
            old_degree = int(self.mesh.shape[mesh_lib.DATA_AXIS])
            m_degree = mesh_lib.model_degree(self.mesh)
            new_mesh, new_accum = mesh_lib.elastic_remesh(
                self.mesh, lost_ids, old_accum)
            new_degree = (int(new_mesh.shape[mesh_lib.DATA_AXIS])
                          if new_mesh is not None else 1)
            log.warning(
                "device loss (ids %s): re-meshing %d->%d data shards "
                "(model degree %d preserved), grad_accum %d->%d "
                "(effective batch preserved); restoring last committed "
                "snapshot", sorted(set(lost_ids)),
                old_degree, new_degree, m_degree, old_accum, new_accum)
            self.mesh = new_mesh
            self.elastic_accum = new_accum
        else:
            # the lost devices were another host's: this member's mesh
            # (and effective batch share) survives verbatim — recovery
            # is cluster shrink + restore from the last cluster commit
            new_degree = (int(self.mesh.shape[mesh_lib.DATA_AXIS])
                          if self.mesh is not None else 1)
            new_accum = old_accum
            log.warning(
                "host loss (ids %s) outside the local mesh: keeping "
                "the mesh, restoring last committed snapshot",
                sorted(set(lost_ids)))
        telemetry.event("resilience.device_loss",
                        lost=sorted(set(lost_ids)),
                        new_degree=new_degree, new_accum=new_accum,
                        cluster_loss=cluster_loss)
        dispatch, updaters = self._build_dispatch(net)
        with telemetry.span("resilience.restore", elastic=True):
            params, ustate, meta = self._restore_latest(net, updaters)
        self.detector.reset()
        self.remeshes += 1
        checkpoint_metrics.note("elastic_resumes")
        telemetry.event("resilience.elastic_resume",
                        step=int(meta["step"]), new_degree=new_degree)
        return dispatch, updaters, params, ustate, int(meta["step"])

    # -- driver ------------------------------------------------------------
    def fit(self, data, num_epochs: int = 1, seed: int = 2):
        """Train to completion (or ``max_steps``, or a preemption
        notice), healing as it goes.  Returns the network with trained
        params set; ``self.preempted`` reports a preemption stop."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.data_service import DataService

        cfg = self.config
        net = self.net
        service: Optional[DataService] = None
        if isinstance(data, DataService):
            service = data
            batches: List[DataSet] = []
            n_batches = len(service)
        else:
            batches = [data] if isinstance(data, DataSet) else list(data)
            n_batches = len(batches)
            spans = (self.mesh is not None and self._multi
                     and len({d.process_index
                              for d in self.mesh.devices.flat}) > 1)
            if cfg.data_service or (cfg.data_service is None and spans):
                # default ingest for spanning meshes: each host reads
                # and stages only its 1/n_hosts slice (ROADMAP item 4;
                # MIGRATION.md deprecates whole-batch staging here)
                service = DataService.from_batches(
                    batches, cluster=self.cluster, seed=seed)
        total_steps = num_epochs * n_batches
        # fit-entry listener hook — reuse the model's own dispatch when
        # it has one (MultiLayerNetwork._notify_fit_start) so the hook
        # semantics can't drift between direct and driver-run fits;
        # inline fallback for duck-typed models
        notify = getattr(net, "_notify_fit_start", None)
        if callable(notify):
            notify()
        else:
            for ls in getattr(net, "listeners", ()):
                hook = getattr(ls, "on_fit_start", None)
                if callable(hook):
                    hook(net)

        # donation guard: the engine step consumes its params/ustate
        # buffers; copy once at this API boundary (same contract as
        # fit_backprop)
        params = jax.tree.map(jnp.copy, net._require_params())
        dispatch, updaters = self._build_dispatch(net)
        if service is not None:
            self._configure_service(service)
        ustate = self._make_ustate(updaters, params)
        run_key = jax.random.key(seed)

        step = 0
        rollbacks = 0
        self.preempted = False
        self.evicted = False
        restored = False
        self._heartbeat = None
        if self._multi:
            # bound EVERY control-plane op by the config's deadline —
            # including the manager's commit barriers on the ASYNC
            # WRITER thread, which use the handle's default.  A dead
            # peer must fail a pending commit within cluster_timeout_s
            # so the recovery drain can drop it and restore, not sit
            # out a deadline sized for healthy-pod bring-up.
            self.cluster.timeout_s = cfg.cluster_timeout_s
            # shared-fs heartbeat: the detector that names a host which
            # died without saying goodbye (SIGKILL, panic, partition).
            # Started by the fit loop's with-block below (and stopped on
            # every exit path with it).
            from deeplearning4j_tpu.parallel.multihost import \
                HostHeartbeat
            self._heartbeat = HostHeartbeat(
                os.path.join(cfg.checkpoint_dir, "heartbeats"),
                self.cluster, interval_s=cfg.hb_interval_s,
                timeout_s=cfg.hb_timeout_s)
        if cfg.resume:
            latest = self.manager.latest_step()
            if latest is None:
                # library callers keep the resume-or-fresh pattern, but
                # loudly: an empty dir on a restart usually means an
                # unmounted volume or a mistyped path
                log.warning(
                    "resume=True but no checkpoints in %s — starting "
                    "from scratch (wrong path or unmounted volume?)",
                    cfg.checkpoint_dir)
            if latest is not None:
                params, ustate, meta = self._restore_latest(net, updaters)
                step = int(meta["step"])
                rollbacks = int(meta.get("rollbacks", 0))
                if service is not None:
                    # committed reader cursor must equal the resume
                    # step's — zero replayed, zero skipped samples
                    service.restore_state(
                        meta.get("data_service"), step)
                restored = True
                telemetry.event("resilience.resume", step=step,
                                rollbacks=rollbacks)
                log.info("resumed from checkpoint at step %d "
                         "(rollbacks=%d)", step, rollbacks)

        def save(at_step: int, sync: bool = False) -> None:
            """Cadence snapshot: async by default (the step never waits
            for serialization/fsync), synchronous for the preemption/
            bounded-slice final snapshot where the commit must be on
            disk before fit returns anyway."""
            meta = {"rollbacks": rollbacks}
            if service is not None:
                # reader state commits WITH the params: the manifest's
                # resume cursor can never disagree with the step
                meta["data_service"] = service.state(at_step)
            if self.async_ckpt is None or sync:
                with telemetry.span("resilience.checkpoint",
                                    step=at_step, mode="sync"):
                    self.manager.save(at_step, (params, ustate),
                                      meta=meta)
            else:
                with telemetry.span("resilience.checkpoint",
                                    step=at_step, mode="async"):
                    self.async_ckpt.save(at_step, (params, ustate),
                                         meta=meta)
            resilience_metrics.note("checkpoints_saved")

        if not restored:
            existing = self.manager.all_steps()
            if existing:
                # a fresh run CANNOT share a dir with another run's
                # snapshots — another process's, or a previous
                # non-resumed fit() of this very driver: retention GC
                # keys on step number, so this run's low-numbered saves
                # (including its rollback target and any preemption
                # snapshot) would be swept the moment they land next to
                # higher foreign steps — and a later --resume (or a
                # newest-committed rollback restore) would silently
                # adopt the stale params.  Refuse up front instead.
                raise ValueError(
                    f"checkpoint_dir {cfg.checkpoint_dir!r} already "
                    f"holds snapshots (steps {existing}); pass "
                    "resume=True to continue that run, or point at a "
                    "fresh directory")
        if self._multi:
            # rendezvous BETWEEN the fresh-dir check above and the
            # first save below: the coordinator's save lands data files
            # in the SHARED dir before its commit barrier, so without
            # this a slower member's check could read a faster member's
            # half-landed initial snapshot as "another run's" and
            # refuse — deadlocking the faster member at the commit
            # barrier.  After this barrier every member has finished
            # its check (or resume restore) before any member writes.
            self.cluster.barrier("fit_start",
                                 timeout_s=cfg.cluster_timeout_s)
        if not restored:
            # THIS run's rollback target exists before the first cadence
            save(step)

        # the step of the newest snapshot we REQUESTED (the initial
        # save above or the resume point) — tracked as an int, not read
        # back from disk, because an async save may not have committed
        # yet; every restore drains first
        last_good = step
        skips: List[jax.Array] = []
        steps_this_call = 0
        guard = self.preemption_guard or PreemptionGuard()

        def recover(e: DeviceLossError) -> bool:
            """Shared host/device-loss recovery for every loop site.
            True = resume the loop with rebuilt state; False = this
            member was EVICTED (its devices were the lost ones) and the
            fit must end cleanly."""
            nonlocal dispatch, updaters, params, ustate, step, \
                last_good, skips
            resumed = self._elastic_resume(e, net)
            if resumed is None:
                return False
            dispatch, updaters, params, ustate, step = resumed
            if service is not None:
                # re-shard for the surviving generation (the plan
                # change books a reassignment) and restart the stream
                # at the committed cursor — zero replay, zero skip
                self._configure_service(service)
                service.restore_state(
                    self._last_restore_meta.get("data_service"), step)
            # the restore may have fallen back below the newest
            # requested save (corrupt-latest case) — re-anchor
            # the rollback target to what is actually good
            last_good = step
            # skip flags booked so far live on the LOST mesh —
            # pull them to host now (one sync per loss event)
            # so the end-of-fit stack doesn't mix shardings
            skips = [np.asarray(jax.device_get(s)) for s in skips]
            return True

        with self._writer_guard(), guard, \
                (self._heartbeat or contextlib.nullcontext()), \
                (service or contextlib.nullcontext()):
            while step < total_steps:
                try:
                    # cluster-wide OR: one host's SIGTERM is every
                    # host's stop verdict, in the same round — so the
                    # whole cluster drains at the SAME step boundary
                    stop = self._cluster_flag(guard.requested())
                except DeviceLossError as e:
                    if recover(e):
                        continue
                    self.evicted = True
                    break
                if stop:
                    # preemption notice: drain in-flight snapshots, one
                    # final SYNC snapshot at this boundary (cluster-
                    # committed under a multi-host cluster), clean
                    # return on EVERY member
                    self._drain()
                    save(step, sync=True)
                    checkpoint_metrics.note("preemption_snapshots")
                    telemetry.event("resilience.preempted", step=step)
                    log.warning("preempted at step %d: final snapshot "
                                "committed, exiting cleanly", step)
                    self.preempted = True
                    break
                if cfg.max_steps is not None \
                        and steps_this_call >= cfg.max_steps:
                    # bounded slice: persist exactly where we stop
                    self._drain()
                    save(step, sync=True)
                    break
                epoch, pos = divmod(step, n_batches)
                order = self._epoch_order(run_key, seed, rollbacks, epoch,
                                          n_batches)
                batch = (service.staged(epoch, pos, order)
                         if service is not None else batches[order[pos]])
                # re-folded key: rollback bumps `rollbacks`, giving the
                # retry a fresh noise stream on top of the reshuffled
                # batch order
                eff_key = jax.random.fold_in(run_key, rollbacks)
                try:
                    if self.fault_hook is not None:
                        self.fault_hook(step)
                    params, ustate, score, skipped = dispatch(
                        params, ustate, batch, eff_key, step)
                except DeviceLossError as e:
                    if recover(e):
                        continue
                    self.evicted = True
                    break
                skips.append(skipped)
                loss = float(score)
                steps_this_call += 1
                if net.listeners:
                    for ls in net.listeners:
                        ls.iteration_done(net, step, loss)
                if self.detector.observe(loss):
                    if rollbacks >= cfg.max_rollbacks:
                        resilience_metrics.note("retry_budget_exceeded")
                        telemetry.event(
                            "resilience.retry_budget_exceeded",
                            step=step, rollbacks=rollbacks)
                        raise RetryBudgetExceeded(
                            f"loss anomaly survived {cfg.max_rollbacks} "
                            f"rollbacks (last-good step {last_good}); "
                            "refusing to burn more compute")
                    rollbacks += 1
                    resilience_metrics.note("rollbacks")
                    telemetry.event("resilience.rollback", step=step,
                                    to_step=int(last_good),
                                    rollbacks=rollbacks)
                    delay = cfg.backoff_s * (2 ** (rollbacks - 1))
                    log.warning(
                        "sustained loss anomaly at step %d; rolling back "
                        "to step %s (rollback %d/%d, backoff %.2fs)",
                        step, last_good, rollbacks, cfg.max_rollbacks,
                        delay)
                    if delay > 0:
                        time.sleep(delay)
                    self._drain()   # the rollback target must be on disk
                    # newest-committed restore, NOT restore(step=
                    # last_good): the explicit-step form never falls
                    # back, so a bit-rotted last_good would kill the
                    # run despite older verified snapshots.  After the
                    # drain the newest committed step IS last_good on
                    # the happy path; on corruption the manifest
                    # protocol walks back to the previous good one — a
                    # corrupt checkpoint costs one cadence, never the
                    # run.
                    with telemetry.span("resilience.restore",
                                        step=int(last_good)):
                        params, ustate, meta = self._restore_latest(
                            net, updaters)
                    step = int(meta["step"])
                    if service is not None:
                        # the retry's bumped `rollbacks` reshuffles the
                        # order — staged() restarts the stream at the
                        # rollback cursor under the new permutation
                        service.restore_state(
                            meta.get("data_service"), step)
                    last_good = step
                    self.detector.reset()
                    continue
                step += 1
                if step % cfg.checkpoint_every == 0 and step < total_steps:
                    save(step)
                    last_good = step

        n_skipped = note_skips(skips, where="resilient-fit")
        if n_skipped and hasattr(net, "guard_skips"):
            # keep the model's cumulative counter honest on driver-run
            # fits too — MetricsListener logs it per record
            net.guard_skips += n_skipped
        self.steps_run = steps_this_call
        self.rollbacks = rollbacks
        # trained params belong to the caller REGARDLESS of checkpoint-
        # writer health: assign before the final drain so a failed
        # background commit surfaces its error without discarding the
        # completed training
        net.params = params
        # every async snapshot committed before fit returns — a caller
        # reading manager.latest_step() (or getting killed next) must
        # see the disk state the counters claim.  close() drains AND
        # stops the writer thread (which would otherwise idle for the
        # life of the process, one per driver); a fresh checkpointer
        # takes its place so fit() can run again on this driver.  A
        # re-fit must pass resume=True (continuing from the final
        # snapshot): a non-resume refit over the now-populated dir is
        # refused above — this driver's own stale snapshots are exactly
        # as hazardous to the step-keyed GC and to newest-committed
        # restores as a foreign run's.
        self._recycle_writer(suppress_errors=False)
        return net
