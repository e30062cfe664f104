"""Fault injection for the scaleout runtime — chaos testing as a
first-class capability.

The reference's fault story is detection/recovery only (heartbeat reaper
``MasterActor.java:139-169``, job re-delivery, worker enable/disable);
SURVEY.md §5.3 notes it ships NO fault *injection* anywhere.  This module
adds it: deterministic, seedable failure wrappers so the recovery paths
(requeue, drop-after-retries, elastic rejoin) are exercised on purpose in
tests and soak runs rather than only when something really breaks.

``ChaosPerformer`` wraps any ``WorkerPerformer`` and injects, per
``perform`` call and independently per worker:
- crashes (raise) with probability ``p_fail``;
- stalls of ``stall_s`` seconds with probability ``p_stall`` (exercises
  the heartbeat/stale-reaper path when stalls exceed the reaper window);
- result corruption (the ``corrupt`` callable rewrites ``job.result``)
  with probability ``p_corrupt`` — the end-to-end exercise for the
  hardened aggregator's non-finite rejection path.

Failures are drawn from a counter-based hash of (seed, worker calls), so
a given seed produces the same fault schedule every run — flaky-test
debugging stays deterministic.

``ServingChaos`` extends the same philosophy to the serving fleet: it
arms one-shot faults against ONE decode replica (a ``ContinuousBatcher``
over a ``DecodeEngine``) — worker-thread death, dispatch poison, stalls,
KV page-pool exhaustion — each fired deterministically at the replica's
next step boundary ON its own worker thread (the engine and its page
allocator are single-driver by contract; chaos must not become the
second driver).  ``tools/serving_chaos_gate.py`` drives it in CI.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

from deeplearning4j_tpu.parallel.coordinator import Job
from deeplearning4j_tpu.parallel import scaleout as so
# DeviceLossError is DEFINED in runtime/resilience.py (the driver that
# catches it cannot import this module — chaos -> scaleout -> resilience
# would cycle) and re-exported here where the injectors that raise it
# live.
from deeplearning4j_tpu.runtime.resilience import DeviceLossError  # noqa: F401


class InjectedFault(RuntimeError):
    """Raised by ChaosPerformer for an injected crash."""


class DeviceLossChaos:
    """Step-boundary device-loss injector for ``ResilientFit``'s
    ``fault_hook``: raises :class:`DeviceLossError` for ``lost_ids``
    the first time the step counter reaches ``at_step`` (exactly once —
    the recovery path re-runs the boundary check after re-meshing, and
    a fault that re-fires forever would starve the resume instead of
    testing it)."""

    def __init__(self, at_step: int, lost_ids):
        self.at_step = at_step
        self.lost_ids = tuple(int(i) for i in lost_ids)
        self.fired = False

    def __call__(self, step: int) -> None:
        if not self.fired and step >= self.at_step:
            self.fired = True
            raise DeviceLossError(
                self.lost_ids,
                f"injected device loss at step {step}: ids "
                f"{sorted(self.lost_ids)}")


class HostLossChaos:
    """Step-boundary HOST-loss injector for ``ResilientFit``'s
    ``fault_hook``: raises :class:`DeviceLossError` for EVERY device of
    one host, exactly once.  The host's devices come from the real
    process topology when the fleet spans processes
    (``device.process_index == host_index``), else from partitioning
    the device list into ``n_hosts`` contiguous blocks — the
    virtual-host proxy that lets a single 8-device CPU process drill
    the "lost a whole host" recovery path (2 hosts x 4 devices).

    In a multi-member drill every member installs the SAME injector
    arguments, so all members raise at the same boundary and the
    cluster's lost-id agreement sees one consistent finding — the
    signal-free stand-in for a real host death (which the heartbeat
    detector covers instead)."""

    def __init__(self, at_step: int, host_index: int,
                 n_hosts: Optional[int] = None, devices=None):
        import jax

        self.at_step = at_step
        self.host_index = host_index
        self.fired = False
        devices = list(devices if devices is not None else jax.devices())
        by_proc = {d.process_index for d in devices}
        if len(by_proc) > 1:
            self.lost_ids = tuple(
                int(d.id) for d in devices
                if d.process_index == host_index)
        else:
            n_hosts = n_hosts or max(len(by_proc), 2)
            per = len(devices) // n_hosts
            if per < 1:
                raise ValueError(
                    f"{len(devices)} device(s) cannot form {n_hosts} "
                    "virtual hosts")
            block = devices[host_index * per:(host_index + 1) * per]
            self.lost_ids = tuple(int(d.id) for d in block)
        if not self.lost_ids:
            raise ValueError(
                f"host {host_index} owns no devices in this fleet")

    def __call__(self, step: int) -> None:
        if not self.fired and step >= self.at_step:
            self.fired = True
            raise DeviceLossError(
                self.lost_ids,
                f"injected loss of host {self.host_index} at step "
                f"{step}: device ids {sorted(self.lost_ids)}")


class PreemptionChaos:
    """Step-boundary preemption drill for ``ResilientFit``'s
    ``fault_hook``: flags the driver's PreemptionGuard at ``at_step`` —
    the signal-free way to exercise the final-snapshot-and-clean-exit
    path in benches and CI gates (the SIGTERM-driven path is tested via
    subprocess)."""

    def __init__(self, at_step: int, guard):
        self.at_step = at_step
        self.guard = guard
        self.fired = False

    def __call__(self, step: int) -> None:
        if not self.fired and step >= self.at_step:
            self.fired = True
            self.guard.request()


def _hash01(seed: int, n: int) -> float:
    """Deterministic uniform [0, 1) from (seed, call index)."""
    h = (seed * 2654435761 + n * 40503) & 0xFFFFFFFF
    h ^= h >> 16
    h = (h * 2246822519) & 0xFFFFFFFF
    h ^= h >> 13
    return (h & 0xFFFFFF) / float(1 << 24)


class ChaosPerformer(so.WorkerPerformer):
    """Wrap ``inner`` with a deterministic fault schedule."""

    def __init__(self, inner: so.WorkerPerformer, *, p_fail: float = 0.0,
                 p_stall: float = 0.0, stall_s: float = 0.0,
                 p_corrupt: float = 0.0,
                 corrupt: Optional[Callable] = None, seed: int = 0):
        self.inner = inner
        self.p_fail = p_fail
        self.p_stall = p_stall
        self.stall_s = stall_s
        self.p_corrupt = p_corrupt
        self.corrupt = corrupt
        self.seed = seed
        self._calls = 0
        self._lock = threading.Lock()
        #: observability: how many of each fault fired
        self.injected = {"fail": 0, "stall": 0, "corrupt": 0}

    def _next_call(self) -> int:
        with self._lock:
            self._calls += 1
            return self._calls

    def perform(self, job: Job) -> None:
        n = self._next_call()
        u = _hash01(self.seed, n)
        if u < self.p_fail:
            self.injected["fail"] += 1
            raise InjectedFault(
                f"injected crash (call {n}, u={u:.3f} < {self.p_fail})")
        if _hash01(self.seed + 1, n) < self.p_stall:
            self.injected["stall"] += 1
            time.sleep(self.stall_s)
        self.inner.perform(job)
        # p_corrupt gates the hook like the other faults (was a
        # hardcoded 0.5 — corruption fired on half of all calls the
        # moment a hook was supplied, with no way to tune the rate)
        if self.corrupt is not None \
                and _hash01(self.seed + 2, n) < self.p_corrupt:
            self.injected["corrupt"] += 1
            job.result = self.corrupt(job.result)

    def update(self, *args) -> None:
        self.inner.update(*args)


class WorkerKilled(BaseException):
    """Injected decode-worker death.  Deliberately a ``BaseException``:
    the batcher's dispatch-failure handler catches ``Exception`` (the
    replay path), and a KILL must sail past it and terminate the worker
    thread exactly like an interpreter-level death would — leaving
    ``worker_alive()`` False and the replica's in-flight requests
    stranded for the health monitor to evacuate."""


_orig_thread_excepthook: Optional[Callable] = None


def _install_kill_excepthook() -> None:
    """Silence ONLY :class:`WorkerKilled` escaping a thread — an
    injected death is the drill's expected outcome, and its traceback
    spew would make every chaos run look like a failing one.  All other
    thread exceptions still reach the previous hook.  Idempotent;
    installed on first ``ServingChaos`` construction."""
    global _orig_thread_excepthook
    if _orig_thread_excepthook is not None:
        return
    _orig_thread_excepthook = threading.excepthook

    def hook(args) -> None:
        if args.exc_type is not WorkerKilled:
            _orig_thread_excepthook(args)

    threading.excepthook = hook


class ServingChaos:
    """Deterministic fault injection for ONE serving replica.

    Every injector ARMS a fault rather than performing it: the fault
    fires at the replica's next touch of an engine step-boundary entry
    point (``dispatch_step`` / ``dispatch_round``, the plain step and the
    self-draft round a batcher runs one ahead of their fetch, /
    ``advance_spec``, plus ``can_admit`` for the
    faults that are legal under the batcher's condition variable), so
    the mutation happens on the replica's OWN worker thread — the
    engine and its ``PageAllocator`` are single-driver by contract, and
    chaos must not become a second driver racing it.

    - :meth:`kill_worker`: next step raises :class:`WorkerKilled`
      (a BaseException — escapes the replay handler, thread dies);
    - :meth:`poison_dispatch`: next ``n`` decode dispatches raise
      :class:`InjectedFault` — exercises the donated-state poison reset
      and bit-exact request replay;
    - :meth:`stall_dispatch`: next decode dispatch sleeps first — trips
      the monitor's ``progress_age`` stall detector while the zombie
      worker later wakes into detached request handles;
    - :meth:`exhaust_pages` / :meth:`release_pages`: grab (then return)
      the replica's free KV pages — admissions stall, then shed with
      the typed ``KVPagesExhausted``.

    ``injected`` counts what actually fired; :meth:`restore` disarms
    anything still pending (a dead worker never fires armed faults).
    """

    #: entry points legal for faults that may fire under the batcher's
    #: condition variable (can_admit is called inside the admit scan)
    _ANY = ("dispatch_step", "dispatch_round", "advance_spec", "can_admit")
    #: entry points for faults that must fire OUTSIDE every lock
    #: (sleeps) or that only make sense for a decode dispatch (poison)
    _DISPATCH = ("dispatch_step", "dispatch_round", "advance_spec")

    def __init__(self, batcher) -> None:
        self.batcher = batcher
        self.engine = batcher.engine
        self.injected = {"kill": 0, "poison": 0, "stall": 0,
                         "exhaust": 0, "release": 0}
        self._held_pages: list = []
        # RLock: page-bookkeeping hooks fire INSIDE the lock region
        # (atomic with the fire decision) yet keep their own ``with``
        self._lock = threading.RLock()
        self._restores: list = []
        self._exhaust_restores: list = []
        _install_kill_excepthook()

    # -- arming machinery --------------------------------------------------
    def _arm(self, hook: Callable, methods, times: int = 1, *,
             locked_hook: bool = False) -> Callable:
        """Wrap ``methods`` on the engine so the next ``times`` calls
        (across all of them) run ``hook(name)`` first — on the calling
        (worker) thread — then restore the originals and delegate.  A
        raising hook still restores first: an injected fault must fire
        its scheduled count, never forever.  Returns the disarm
        closure (idempotent; a no-op once the fault has fired).

        Every setattr — install, fire-restore, disarm — happens under
        ``self._lock``: arming runs on the host thread while faults
        fire on the worker thread, and an unsynchronized disarm racing
        a fire could resurrect a wrapper that was already retired.
        ``locked_hook=True`` additionally runs the hook inside the
        lock region, making the fire ATOMIC with the fire decision —
        required for page bookkeeping, where a disarm racing a
        half-fired grab would mis-read what is held.  Blocking hooks
        (sleeps) must keep the default and fire outside the lock."""
        eng = self.engine
        state = {"left": int(times)}
        with self._lock:
            origs = {m: getattr(eng, m) for m in methods}

        def restore() -> None:
            with self._lock:
                if state["left"] == 0:
                    return
                state["left"] = 0
                for m, o in origs.items():
                    setattr(eng, m, o)

        def make(name: str, orig: Callable) -> Callable:
            def wrapped(*a, **kw):
                with self._lock:
                    fire = state["left"] > 0
                    if fire:
                        state["left"] -= 1
                        if state["left"] == 0:
                            for m, o in origs.items():
                                setattr(eng, m, o)
                        if locked_hook:
                            hook(name)
                if fire and not locked_hook:
                    hook(name)
                return orig(*a, **kw)
            return wrapped

        with self._lock:
            for m, o in origs.items():
                setattr(eng, m, make(m, o))
        self._restores.append(restore)
        return restore

    def restore(self) -> None:
        """Disarm every armed-but-unfired fault (fired ones already
        restored themselves) and return any held pages.  Call only when
        the replica's worker is dead or quiescent — see
        :meth:`release_pages` for the held-page caveat."""
        for r in self._restores:
            r()
        self._restores = []
        self.release_pages(armed=False)

    # -- injectors ---------------------------------------------------------
    def kill_worker(self) -> None:
        """Arm a one-shot :class:`WorkerKilled` on the replica's next
        step boundary."""
        def hook(name: str) -> None:
            self.injected["kill"] += 1
            raise WorkerKilled(f"injected worker death (at {name})")
        self._arm(hook, self._ANY)

    def poison_dispatch(self, n: int = 1) -> None:
        """Arm :class:`InjectedFault` on the next ``n`` decode
        dispatches (an ordinary RuntimeError — the batcher's replay
        handler owns it)."""
        if n < 1:
            raise ValueError(f"poison count must be >= 1: {n}")

        def hook(name: str) -> None:
            self.injected["poison"] += 1
            raise InjectedFault(f"injected dispatch poison (at {name})")
        self._arm(hook, self._DISPATCH, times=n)

    def stall_dispatch(self, seconds: float) -> None:
        """Arm a one-shot pre-dispatch sleep — long enough and the
        health monitor's ``progress_age`` detector replaces the
        replica while this worker is still inside the sleep."""
        if seconds <= 0:
            raise ValueError(f"stall must be > 0 s: {seconds}")

        def hook(name: str) -> None:
            self.injected["stall"] += 1
            time.sleep(seconds)
        self._arm(hook, self._DISPATCH)

    def exhaust_pages(self, leave: int = 0) -> None:
        """Arm a one-shot grab of the replica's free KV pages (leaving
        ``leave``), held by this injector: admissions stall, then shed
        with the typed ``KVPagesExhausted``."""
        if leave < 0:
            raise ValueError(f"leave must be >= 0: {leave}")

        def hook(name: str) -> None:
            alloc = self.engine._kinds[0].alloc
            n = max(alloc.n_free() - int(leave), 0)
            if n:
                with self._lock:
                    self._held_pages.extend(alloc.alloc(n))
            self.injected["exhaust"] += 1
        self._exhaust_restores.append(
            self._arm(hook, self._ANY, locked_hook=True))

    def release_pages(self, armed: bool = True) -> None:
        """End the exhaustion episode and return every held page.

        A still-ARMED (unfired) exhaust is disarmed first: without
        this, a release racing a slow-to-wake worker would free
        nothing, then the pending grab would fire AFTER it and hold
        the pool forever.  ``armed=True`` (default) frees on the
        worker thread at the replica's next step boundary — the
        allocator's single-driver contract.  ``armed=False`` frees
        from the calling thread immediately; legal only when the
        worker is dead or parked (e.g. auditing occupancy after a
        drill)."""
        for r in self._exhaust_restores:
            r()
        self._exhaust_restores = []

        def hook(name: str) -> None:
            with self._lock:
                held, self._held_pages = self._held_pages, []
            alloc = self.engine._kinds[0].alloc
            if alloc is not None and held:
                alloc.free(held)
                self.injected["release"] += 1
        with self._lock:
            holding = bool(self._held_pages)
        if not holding:
            return                       # the grab never fired: no-op
        if armed:
            self._arm(hook, self._ANY, locked_hook=True)
        else:
            hook("direct")


def chaos_factory(inner_factory: Callable[[], so.WorkerPerformer], *,
                  p_fail: float = 0.0, p_stall: float = 0.0,
                  stall_s: float = 0.0, p_corrupt: float = 0.0,
                  corrupt: Optional[Callable] = None, seed: int = 0
                  ) -> Callable[[], so.WorkerPerformer]:
    """Performer factory wrapper for ``DistributedRunner``: each worker
    gets its own ChaosPerformer with a distinct derived seed, so faults
    are spread across workers but stay reproducible.  The returned
    factory records every performer it makes on ``factory.instances`` so
    soak tests can sum the per-worker ``injected`` counters afterwards."""
    counter = {"n": 0}
    lock = threading.Lock()
    instances = []

    def make() -> ChaosPerformer:
        with lock:
            counter["n"] += 1
            worker_seed = seed + 1000 * counter["n"]
        perf = ChaosPerformer(inner_factory(), p_fail=p_fail,
                              p_stall=p_stall, stall_s=stall_s,
                              p_corrupt=p_corrupt, corrupt=corrupt,
                              seed=worker_seed)
        with lock:
            instances.append(perf)
        return perf

    make.instances = instances
    return make
