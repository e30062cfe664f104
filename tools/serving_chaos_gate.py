"""CI chaos drill for serving fleet fault tolerance (PR 17).

Machine-checks the failure contract of the tier-3 serving fleet: under
injected faults — a poisoned dispatch, a killed decode worker, a stalled
replica, an exhausted KV page pool — every submitted request must still
complete with tokens BIT-IDENTICAL to an undisturbed run, replacement
replicas must compile ZERO new programs (shared compile cache, the
autoscaling invariant), and the page allocator must account for every
page after the drill (no leaks from any recovery path).

Why bit-exactness is even possible: sampling keys fold (seed, POSITION),
so a request journaled as (prompt, seed, temperature, tokens-emitted)
replays on any identically-configured replica and continues exactly
where it died — replica death loses no request and changes no token.

Drill phases (deterministic; each fault armed via
``parallel.chaos.ServingChaos`` and fired at a step boundary on the
victim's own worker thread):

1. POISON — one dispatch raises ``InjectedFault``: the batcher frees the
   affected slots, reclaims their pages, and replays the requests
   in-place (no replacement — the error streak stays under the bound);
2. KILL — the worker thread dies mid-traffic (``WorkerKilled``): the
   health monitor sees ``worker_alive() == False``, spawns a factory
   replacement, and re-dispatches every journaled request onto it;
3. STALL — a dispatch sleeps past ``stall_after_s``: the monitor's
   progress-age detector replaces the replica while the zombie worker
   is still asleep; mid-decode requests replay from their last token;
4. EXHAUST — the free page pool is held hostage: admissions stall (no
   deadlock, no shed — the prompts fit the pool), a deadline probe
   queued behind the exhaustion expires with the typed
   ``DeadlineExceeded``, and releasing the pages lets the wave finish.

Run by ``tools/ci.sh`` after the telemetry gate; exits non-zero on any
violation.
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

N_REQUESTS = 12


def _prompts():
    import numpy as np

    r = np.random.RandomState(17)
    return [r.randint(1, 48, size=r.randint(2, 12)).astype(np.int32)
            for _ in range(N_REQUESTS)]


def _make_factory(cfg, params):
    from deeplearning4j_tpu.serving.decode import (ContinuousBatcher,
                                                   DecodeEngine)

    def factory():
        eng = DecodeEngine(cfg, params, n_slots=3, buckets=(16, 32),
                           prefill_chunk=8,
                           label="chaos-gate")
        eng.warmup()
        return ContinuousBatcher(eng, default_max_tokens=5)
    return factory


def _submit(target, prompt, i):
    # per-request (seed, temperature) pairs make bit-exactness a claim
    # about SAMPLED decode, not just greedy argmax
    return target.submit(prompt, max_tokens=5, temperature=0.7,
                         seed=100 + i)


def main() -> int:
    import numpy as np

    from deeplearning4j_tpu.models import gpt
    from deeplearning4j_tpu.parallel.chaos import ServingChaos
    from deeplearning4j_tpu.runtime import telemetry
    from deeplearning4j_tpu.runtime.metrics import decode_metrics
    from deeplearning4j_tpu.serving.decode import DeadlineExceeded
    from deeplearning4j_tpu.serving.router import (AutoscalePolicy,
                                                   AutoscalingRouter,
                                                   ReplicaHealth)
    import jax

    registry = telemetry.registry
    cfg = gpt.gpt_tiny(vocab_size=48, max_len=32)
    params = gpt.init_params(jax.random.key(0), cfg)
    factory = _make_factory(cfg, params)
    prompts = _prompts()

    # -- 1) undisturbed baseline: the bit-exact reference -------------------
    base = factory()
    try:
        handles = [_submit(base, p, i) for i, p in enumerate(prompts)]
        expect = [h.result(120) for h in handles]
    finally:
        base.close()

    # -- 2) the chaos fleet --------------------------------------------------
    decode_metrics.reset()
    router = AutoscalingRouter(
        factory, AutoscalePolicy(min_replicas=2, max_replicas=3),
        max_queue_depth=64,
        health=ReplicaHealth(poll_interval_s=0.02, max_error_streak=3,
                             stall_after_s=0.6))
    got: dict = {}
    probe = None
    try:
        # every program is warmed (baseline + factory warmups); from
        # here on — including the replacement spawns the faults will
        # force — the fleet must not compile ONE new program
        registry.mark()

        # phase 1: POISON one dispatch — in-place replay, no replacement
        b0 = router.batchers[0]
        ServingChaos(b0).poison_dispatch(1)
        wave = [(i, _submit(b0, prompts[i], i)) for i in range(0, 4)]
        for i, h in wave:
            got[i] = h.result(120)

        # phase 2: KILL a worker — monitor replaces, requests replay
        victim = router.batchers[1]
        ServingChaos(victim).kill_worker()
        wave = [(i, _submit(victim, prompts[i], i)) for i in range(4, 8)]
        for i, h in wave:
            got[i] = h.result(120)
        if victim in router.batchers:
            print("[serving-chaos-gate] FAIL: killed replica was never "
                  "replaced — the health monitor missed a dead worker")
            return 1

        # phase 3: STALL a replica mid-decode — progress-age detector
        # replaces it; the requests replay from their last token
        stalled = router.batchers[0]
        ServingChaos(stalled).stall_dispatch(1.5)
        wave = [(i, _submit(stalled, prompts[i], i)) for i in range(8, 10)]
        for i, h in wave:
            got[i] = h.result(120)
        if stalled in router.batchers:
            print("[serving-chaos-gate] FAIL: stalled replica was never "
                  "replaced — the progress-age detector missed it")
            return 1

        # phase 4: EXHAUST the page pool — admissions stall (never
        # deadlock/shed), a deadline probe behind the exhaustion
        # expires typed, releasing the pages completes the wave
        host = router.batchers[0]
        chaos = ServingChaos(host)
        chaos.exhaust_pages()
        wave = [(i, _submit(host, prompts[i], i)) for i in range(10, 12)]
        probe = host.submit(prompts[0], max_tokens=5, temperature=0.7,
                            seed=100, deadline_ms=80)
        time.sleep(0.3)                  # let the probe expire queued
        chaos.release_pages()
        for i, h in wave:
            got[i] = h.result(120)

        live_engines = [b.engine for b in router.batchers]
    finally:
        router.close()

    # -- 3) verdicts ---------------------------------------------------------
    bad = [i for i in range(N_REQUESTS)
           if not np.array_equal(got[i], expect[i])]
    if bad:
        print(f"[serving-chaos-gate] FAIL: request(s) {bad} completed "
              "with tokens differing from the undisturbed run — replay "
              "is not bit-exact")
        return 1

    delta = registry.compile_delta_since_mark()
    if delta != 0:
        print(f"[serving-chaos-gate] FAIL: the drill compiled {delta} "
              "new program(s) — replica replacement must reuse the "
              "shared compile cache")
        return 1

    try:
        probe.result(1)
        print("[serving-chaos-gate] FAIL: the deadline probe completed "
              "instead of expiring behind the exhausted pool")
        return 1
    except DeadlineExceeded:
        pass

    for eng in live_engines:
        # pool-resident prefix pages are a CACHE (registry-held refs),
        # not occupancy — evict them (workers are joined; the engine is
        # quiescent) so in_use() == 0 is the honest leak audit
        eng.drop_residents()
        if eng._alloc.in_use() != 0 or eng.pages_unaccounted() != 0:
            print(f"[serving-chaos-gate] FAIL: pages leaked after "
                  f"drain: in_use={eng._alloc.in_use()} "
                  f"unaccounted={eng.pages_unaccounted()}")
            return 1

    snap = decode_metrics.snapshot()
    for key, floor in (("replicas_replaced", 2),
                       ("requests_replayed", 1),
                       ("deadline_expirations", 1)):
        if snap[key] < floor:
            print(f"[serving-chaos-gate] FAIL: {key}={snap[key]} "
                  f"(expected >= {floor}) — the drill did not exercise "
                  "its fault path")
            return 1

    print(f"[serving-chaos-gate] ok: {N_REQUESTS} requests bit-exact "
          f"under poison/kill/stall/exhaust, compile_delta={delta}, "
          f"replaced={snap['replicas_replaced']}, "
          f"replayed={snap['requests_replayed']}, pages_leaked=0")
    return 0


if __name__ == "__main__":
    sys.exit(main())
