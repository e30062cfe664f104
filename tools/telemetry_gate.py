"""CI overhead gate for the run-telemetry layer (runtime/telemetry.py).

Machine-checks the tentpole's overhead contract on a real (tiny) fit:

1. warm the engine with one fit, ``registry.mark()``;
2. a second, tracer-OFF fit must show ``compile_delta_since_mark == 0``
   (telemetry plumbing at rest adds no trace);
3. a tracer-ON fit must ALSO show ``compile_delta_since_mark == 0``
   (enabling spans changes no jitted program — the tracer is host-side
   by construction) and must produce a journal whose chrome-trace
   conversion is valid Perfetto JSON with the fit span present;
4. the same off/on zero-compile contract for a warmed ``ResilientFit``
   with BACKGROUND snapshots (runtime/checkpoint.py
   ``AsyncCheckpointer``, the PR 8 default): staging copies, writer
   commits, and drains must never trace a new program;
5. the same off/on zero-compile contract for a warmed MIXED-PRECISION
   fit (``MultiLayerConfiguration.mixed_precision="bf16"``): the
   dynamic loss scale is a traced value threading the scanned epochs,
   so its transitions must never retrace;
6. the same off/on zero-compile contract for the continuous-batching
   decode loop (serving/decode.py): after ``DecodeEngine.warmup()``, a
   concurrent request mix — joins, EOS recycling, varied prompt
   lengths — must dispatch only cached programs with the tracer off AND
   on (the decode path's spans are host-side only: each is also a
   ``jax.profiler.TraceAnnotation``, so a profiler session shows them
   beside the device lines), and the tracer-on journal must hold the
   loop's ``decode.round`` span;
6b. the same off/on zero-compile contract for the SERVING TIER 2
   decode loop: a warmed int8-weight + int8-KV engine with a prefix
   store must serve a mix of prefix MISSES (which read + store pages)
   and prefix HITS (which write cached pages into a slot) without a
   single new program — the dequant-fused executables, the page
   read/write pair, and every hit length are covered by ``warmup()``;
6c. the same off/on zero-compile contract for the SERVING TIER 3
   loop: a warmed PAGED + SPECULATIVE replica fleet behind the
   autoscaling router serving mixed traffic — page allocation/release,
   draft propose/verify rounds, prefix mounts — with a zero-downtime
   ``swap_weights`` in the MIDDLE of each pass: the swap drains,
   rebinds, and requantizes without tracing one new program;
6d. the same off/on zero-compile contract for a warmed DATA-SERVICE
   fit (``datasets/data_service.py``, the ISSUE 20 ingest layer) on an
   8-way data mesh with a RAGGED final batch: the per-host shard
   reads, prefetch staging, pad-to-chunk shapes, and reader-state
   checkpointing must dispatch only cached programs — tracer off AND
   on;
7. the same off/on zero-compile contract for a warmed DATA×MODEL fit
   (``models/lm_fit.CausalLM`` on a 2×4 mesh through the sharded_fit
   GSPMD builders): the model-sharded scanned dispatch, its staging
   device_puts, and the loss-scale/guard state threading must never
   retrace — the gate process forces 8 virtual CPU devices so the
   real sharded program runs.

Run by ``tools/ci.sh`` before the test tiers; exits non-zero on any
violation.  (jaxlint runs separately in ci.sh and must also stay clean —
the instrumentation sites live in linted packages.)
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the data×model gate needs a real multi-device mesh; force the virtual
# 8-device CPU platform BEFORE any backend initializes (same pattern as
# tests/conftest.py)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()


def _net_and_data():
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import (LayerKind,
                                            NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder()
            .n_in(4).lr(0.1).num_iterations(1).activation("tanh")
            .list(2).hidden_layer_sizes(8)
            .override(1, kind=LayerKind.OUTPUT, n_out=3,
                      activation="softmax", loss_function="mcxent")
            .pretrain(False).backward(True).build())
    rng = np.random.RandomState(0)
    batches = [DataSet(rng.randn(16, 4).astype(np.float32),
                       np.eye(3, dtype=np.float32)[
                           rng.randint(0, 3, 16)])
               for _ in range(3)]
    return MultiLayerNetwork(conf).init(seed=1), batches


def _decode_requests(cb, np, n: int, seed: int) -> None:
    rng = np.random.RandomState(seed)
    handles = [cb.submit(rng.randint(1, 48, size=rng.randint(2, 12)),
                         max_tokens=4 + i % 4)
               for i in range(n)]
    for h in handles:
        h.result(120)


def _checkpoint_gate(registry, telemetry, net, batches) -> int:
    """Async-checkpoint loop gate: a WARMED ResilientFit with background
    snapshots (the PR 8 default) must dispatch only cached programs —
    the AsyncCheckpointer's device-side staging copies and its writer
    thread are outside every jitted region — with the tracer off AND
    on."""
    import tempfile

    from deeplearning4j_tpu.runtime.resilience import (ResilienceConfig,
                                                       ResilientFit)

    def one_fit(seed):
        with tempfile.TemporaryDirectory() as ckdir:
            ResilientFit(net, ResilienceConfig(
                checkpoint_dir=ckdir, checkpoint_every=2,
                patience=10 ** 6)).fit(batches, num_epochs=2, seed=seed)

    one_fit(0)              # warm (same engine step as the fit gate,
    registry.mark()         # but snapshots + drain now ride along)

    assert not telemetry.enabled()
    one_fit(1)
    delta_off = registry.compile_delta_since_mark()
    if delta_off != 0:
        print(f"[telemetry-gate] FAIL: tracer-off async-checkpoint fit "
              f"compiled {delta_off} new program(s)")
        return 1

    telemetry.enable("telemetry-gate-ckpt")
    registry.mark()
    one_fit(2)
    delta_on = registry.compile_delta_since_mark()
    telemetry.disable()
    if delta_on != 0:
        print(f"[telemetry-gate] FAIL: tracer-on async-checkpoint fit "
              f"compiled {delta_on} new program(s) — checkpoint "
              "instrumentation leaked into a jitted region")
        return 1
    print(f"[telemetry-gate] ok: async-checkpoint loop compile_delta "
          f"off={delta_off} on={delta_on}")
    return 0


def _data_service_gate(registry, telemetry) -> int:
    """Data-service loop gate (ISSUE 20): a WARMED ResilientFit fed by
    the distributed data service on an 8-way data mesh — per-host shard
    reads, depth-k prefetch staging, a ragged final batch padding to
    the dispatch chunk, reader-state riding every snapshot — must
    dispatch only cached programs with the tracer off AND on.  The
    staged shapes must equal the legacy pad path's exactly; one extra
    shape here IS the regression this gate exists to catch."""
    import tempfile

    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import (LayerKind,
                                            NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    from deeplearning4j_tpu.runtime.metrics import ingest_metrics
    from deeplearning4j_tpu.runtime.resilience import (ResilienceConfig,
                                                       ResilientFit)

    conf = (NeuralNetConfiguration.builder()
            .n_in(4).lr(0.1).num_iterations(1).activation("tanh")
            .list(2).hidden_layer_sizes(8)
            .override(1, kind=LayerKind.OUTPUT, n_out=3,
                      activation="softmax", loss_function="mcxent")
            .pretrain(False).backward(True).build())
    rng = np.random.RandomState(3)

    def batch(n):
        return DataSet(rng.randn(n, 4).astype(np.float32),
                       np.eye(3, dtype=np.float32)[rng.randint(0, 3, n)])

    batches = [batch(16) for _ in range(3)] + [batch(12)]   # ragged tail
    net = MultiLayerNetwork(conf).init(seed=4)
    mesh = make_mesh(MeshSpec(data=8))

    def one_fit(seed):
        with tempfile.TemporaryDirectory() as ckdir:
            ResilientFit(net, ResilienceConfig(
                checkpoint_dir=ckdir, checkpoint_every=3,
                patience=10 ** 6, data_service=True),
                mesh=mesh).fit(batches, num_epochs=2, seed=seed)

    one_fit(0)              # warm (full + ragged staged shapes)
    registry.mark()

    assert not telemetry.enabled()
    one_fit(1)
    delta_off = registry.compile_delta_since_mark()
    if delta_off != 0:
        print(f"[telemetry-gate] FAIL: tracer-off data-service fit "
              f"compiled {delta_off} new program(s)")
        return 1

    telemetry.enable("telemetry-gate-ingest")
    registry.mark()
    one_fit(2)
    delta_on = registry.compile_delta_since_mark()
    telemetry.disable()
    if delta_on != 0:
        print(f"[telemetry-gate] FAIL: tracer-on data-service fit "
              f"compiled {delta_on} new program(s) — ingest "
              "instrumentation leaked into a jitted region")
        return 1
    snap = ingest_metrics.snapshot()
    if snap["batches_staged"] == 0 or snap["seed_agreements"] == 0:
        print("[telemetry-gate] FAIL: data-service fit booked no ingest "
              f"counters ({snap}) — the service was not in the loop")
        return 1
    print(f"[telemetry-gate] ok: data-service loop compile_delta "
          f"off={delta_off} on={delta_on}, "
          f"{snap['batches_staged']} batch(es) staged, depth_hw="
          f"{snap['depth_hw']}")
    return 0


def _mixed_precision_gate(registry, telemetry) -> int:
    """Mixed-precision loop gate: a WARMED bf16 fit (dynamic loss scale
    threading through the scanned epochs) must dispatch only cached
    programs with the tracer off AND on — the scale is a traced value in
    the updater-state slot, so its per-step transitions must never cost
    a retrace."""
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import (LayerKind,
                                            NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder()
            .n_in(4).lr(0.1).num_iterations(1).activation("tanh")
            .list(2).hidden_layer_sizes(8)
            .override(1, kind=LayerKind.OUTPUT, n_out=3,
                      activation="softmax", loss_function="mcxent")
            .pretrain(False).backward(True)
            .mixed_precision("bf16").build())
    rng = np.random.RandomState(1)
    batches = [DataSet(rng.randn(16, 4).astype(np.float32),
                       np.eye(3, dtype=np.float32)[rng.randint(0, 3, 16)])
               for _ in range(3)]
    net = MultiLayerNetwork(conf).init(seed=2)

    net.fit_backprop(batches, num_epochs=1)      # warm the mp engine step
    registry.mark()

    assert not telemetry.enabled()
    net.fit_backprop(batches, num_epochs=1)
    delta_off = registry.compile_delta_since_mark()
    if delta_off != 0:
        print(f"[telemetry-gate] FAIL: tracer-off mixed-precision fit "
              f"compiled {delta_off} new program(s)")
        return 1

    telemetry.enable("telemetry-gate-mp")
    registry.mark()
    net.fit_backprop(batches, num_epochs=1)
    delta_on = registry.compile_delta_since_mark()
    telemetry.disable()
    if delta_on != 0:
        print(f"[telemetry-gate] FAIL: tracer-on mixed-precision fit "
              f"compiled {delta_on} new program(s) — loss-scale state "
              "leaked a retrace")
        return 1
    print(f"[telemetry-gate] ok: mixed-precision loop compile_delta "
          f"off={delta_off} on={delta_on}")
    return 0


def _model_parallel_gate(registry, telemetry) -> int:
    """data×model loop gate: a WARMED 2×4 GSPMD fit (CausalLM through
    the sharded_fit builders — model-sharded params, donated scanned
    dispatch, guard + loss-scale state threading) must dispatch only
    cached programs with the tracer off AND on."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models import gpt
    from deeplearning4j_tpu.models.lm_fit import CausalLM
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

    if len(jax.devices()) < 8:
        print("[telemetry-gate] skip: model-parallel loop needs 8 "
              f"devices, have {len(jax.devices())}")
        return 0
    cfg = dataclasses.replace(gpt.gpt_tiny(vocab_size=64, max_len=16),
                              hidden=32, n_layers=2, n_heads=4,
                              ffn_dim=64, compute_dtype="float32")
    rng = np.random.RandomState(0)
    batches = [DataSet(jnp.asarray(rng.randint(0, 64, (8, 16)),
                                   jnp.int32),
                       jnp.asarray(rng.randint(0, 64, (8, 16)),
                                   jnp.int32))
               for _ in range(3)]
    mesh = make_mesh(MeshSpec(data=2, model=4),
                     devices=jax.devices()[:8])
    lm = CausalLM(cfg, lr=0.05)

    def one_fit(seed):
        lm.init(seed=1)
        lm.fit_backprop(batches, num_epochs=1, seed=seed, mesh=mesh)

    one_fit(0)              # warm the data×model engine entry
    registry.mark()

    assert not telemetry.enabled()
    one_fit(1)
    delta_off = registry.compile_delta_since_mark()
    if delta_off != 0:
        print(f"[telemetry-gate] FAIL: tracer-off data×model fit "
              f"compiled {delta_off} new program(s)")
        return 1

    telemetry.enable("telemetry-gate-mp-mesh")
    registry.mark()
    one_fit(2)
    delta_on = registry.compile_delta_since_mark()
    telemetry.disable()
    if delta_on != 0:
        print(f"[telemetry-gate] FAIL: tracer-on data×model fit "
              f"compiled {delta_on} new program(s) — model-parallel "
              "instrumentation leaked into a jitted region")
        return 1
    print(f"[telemetry-gate] ok: data×model loop compile_delta "
          f"off={delta_off} on={delta_on}")
    return 0


def _decode_gate(registry, telemetry) -> int:
    import numpy as np

    from deeplearning4j_tpu.models import gpt
    from deeplearning4j_tpu.serving.decode import (ContinuousBatcher,
                                                   DecodeEngine)

    cfg = gpt.gpt_tiny(vocab_size=48, max_len=32)
    params = gpt.init_params(__import__("jax").random.key(0), cfg)
    eng = DecodeEngine(cfg, params, n_slots=3, buckets=(16, 32),
                       prefill_chunk=8)
    eng.warmup()
    with ContinuousBatcher(eng, default_max_tokens=4) as cb:
        registry.mark()

        # tracer OFF
        assert not telemetry.enabled()
        _decode_requests(cb, np, 6, seed=0)
        delta_off = registry.compile_delta_since_mark()
        if delta_off != 0:
            print(f"[telemetry-gate] FAIL: tracer-off decode loop "
                  f"compiled {delta_off} new program(s)")
            return 1

        # tracer ON
        telemetry.enable("telemetry-gate-decode")
        registry.mark()
        _decode_requests(cb, np, 6, seed=1)
        delta_on = registry.compile_delta_since_mark()
        journal = telemetry.disable().records()
        if delta_on != 0:
            print(f"[telemetry-gate] FAIL: tracer-on decode loop "
                  f"compiled {delta_on} new program(s) — decode "
                  "instrumentation leaked into a jitted region")
            return 1
        rounds = sum(r["name"] == "decode.round" for r in journal)
        if not rounds:
            print("[telemetry-gate] FAIL: no decode.round span in the "
                  "tracer-on decode loop's journal")
            return 1
    print(f"[telemetry-gate] ok: decode loop compile_delta "
          f"off={delta_off} on={delta_on}, {rounds} decode.round spans")
    return 0


def _tier2_decode_gate(registry, telemetry) -> int:
    """Serving-tier-2 loop gate: a warmed int8-quantized + int8-KV +
    prefix-cached engine must serve misses (page harvest) and hits
    (page copy) compile-free with the tracer off AND on."""
    import numpy as np

    from deeplearning4j_tpu.models import gpt
    from deeplearning4j_tpu.serving.decode import (ContinuousBatcher,
                                                   DecodeEngine)

    cfg = gpt.gpt_tiny(vocab_size=48, max_len=32)
    params = gpt.init_params(__import__("jax").random.key(0), cfg)
    eng = DecodeEngine(cfg, params, n_slots=3, buckets=(16, 32),
                       prefill_chunk=8, quantize="int8",
                       kv_dtype="int8", prefix_cache=True,
                       label="gate-tier2")
    eng.warmup()
    rng = np.random.RandomState(3)
    shared = rng.randint(1, 48, size=16).astype(np.int32)

    def mixed_requests(cb, seed):
        r = np.random.RandomState(seed)
        handles = []
        for i in range(6):
            if i % 2:                     # prefix-sharing requests
                tail = r.randint(1, 48, size=r.randint(1, 6))
                prompt = np.concatenate([shared, tail.astype(np.int32)])
            else:                         # fresh prompts (misses)
                prompt = r.randint(1, 48, size=r.randint(2, 12))
            handles.append(cb.submit(prompt, max_tokens=3 + i % 3))
        for h in handles:
            h.result(120)

    with ContinuousBatcher(eng, default_max_tokens=4) as cb:
        mixed_requests(cb, seed=7)        # seed the store
        eng.flush_harvests()              # async harvests land first
        registry.mark()

        assert not telemetry.enabled()
        mixed_requests(cb, seed=8)
        delta_off = registry.compile_delta_since_mark()
        if delta_off != 0:
            print(f"[telemetry-gate] FAIL: tracer-off tier-2 decode "
                  f"loop compiled {delta_off} new program(s)")
            return 1

        telemetry.enable("telemetry-gate-tier2")
        registry.mark()
        mixed_requests(cb, seed=9)
        delta_on = registry.compile_delta_since_mark()
        telemetry.disable()
        if delta_on != 0:
            print(f"[telemetry-gate] FAIL: tracer-on tier-2 decode "
                  f"loop compiled {delta_on} new program(s) — "
                  "quantized/prefix instrumentation leaked into a "
                  "jitted region")
            return 1
    from deeplearning4j_tpu.runtime.metrics import decode_metrics
    hits = decode_metrics.snapshot()["prefix_hits"]
    if hits < 2:
        print(f"[telemetry-gate] FAIL: tier-2 loop recorded only "
              f"{hits} prefix hit(s) — the gate did not exercise the "
              "hit path")
        return 1
    print(f"[telemetry-gate] ok: tier-2 decode loop compile_delta "
          f"off={delta_off} on={delta_on}, {hits} prefix hit(s)")
    return 0


def _tier3_decode_gate(registry, telemetry) -> int:
    """Serving-tier-3 loop gate: a warmed PAGED + SPECULATIVE fleet
    behind the autoscaling router — prefix misses and hits, draft
    propose/verify rounds, and a mid-loop zero-downtime weight swap —
    must dispatch only cached programs with the tracer off AND on.
    The swap itself is part of the contract: same shapes, same
    executables, zero new programs."""
    import dataclasses

    import jax
    import numpy as np

    from deeplearning4j_tpu.models import gpt
    from deeplearning4j_tpu.runtime.metrics import decode_metrics
    from deeplearning4j_tpu.serving.decode import (ContinuousBatcher,
                                                   DecodeEngine,
                                                   PrefixCache)
    from deeplearning4j_tpu.serving.router import (AutoscalePolicy,
                                                   AutoscalingRouter)

    cfg = gpt.gpt_tiny(vocab_size=48, max_len=32)
    dcfg = dataclasses.replace(cfg, hidden=16, n_layers=1, n_heads=2,
                               ffn_dim=32)
    params = gpt.init_params(jax.random.key(0), cfg)
    dp = gpt.init_params(jax.random.key(1), dcfg)
    p_new = gpt.init_params(jax.random.key(5), cfg)
    store = PrefixCache()

    def factory():
        eng = DecodeEngine(cfg, params, n_slots=3, buckets=(16, 32),
                           prefill_chunk=8,
                           draft=(dcfg, dp), draft_k=3,
                           prefix_cache=store, label="gate-tier3")
        eng.warmup()
        return ContinuousBatcher(eng, default_max_tokens=4)

    shared = np.random.RandomState(3).randint(1, 48, size=16) \
        .astype(np.int32)

    def mixed_requests(router, seed):
        r = np.random.RandomState(seed)
        handles = []
        for i in range(6):
            if i % 2:                     # prefix-sharing requests
                tail = r.randint(1, 48, size=r.randint(1, 6))
                prompt = np.concatenate([shared, tail.astype(np.int32)])
            else:                         # fresh prompts (misses)
                prompt = r.randint(1, 48, size=r.randint(2, 12))
            handles.append(router.submit(prompt, max_tokens=3 + i % 3))
        for h in handles:
            h.result(120)

    router = AutoscalingRouter(
        factory, AutoscalePolicy(min_replicas=2, max_replicas=2))
    try:
        mixed_requests(router, seed=7)    # warm joins + seed the store
        for b in router.batchers:
            b.engine.flush_harvests()
        registry.mark()

        assert not telemetry.enabled()
        mixed_requests(router, seed=8)
        router.swap_weights(p_new)        # mid-loop hot swap
        mixed_requests(router, seed=9)
        delta_off = registry.compile_delta_since_mark()
        if delta_off != 0:
            print(f"[telemetry-gate] FAIL: tracer-off tier-3 decode "
                  f"loop compiled {delta_off} new program(s)")
            return 1

        telemetry.enable("telemetry-gate-tier3")
        registry.mark()
        mixed_requests(router, seed=10)
        router.swap_weights(params)       # and back, tracer on
        mixed_requests(router, seed=11)
        delta_on = registry.compile_delta_since_mark()
        telemetry.disable()
        if delta_on != 0:
            print(f"[telemetry-gate] FAIL: tracer-on tier-3 decode "
                  f"loop compiled {delta_on} new program(s) — paged/"
                  "speculative/swap instrumentation leaked into a "
                  "jitted region")
            return 1
    finally:
        router.close()
    snap = decode_metrics.snapshot()
    if snap["draft_proposed"] < 1:
        print("[telemetry-gate] FAIL: tier-3 loop proposed no draft "
              "tokens — the speculative path did not run")
        return 1
    if snap["swaps_completed"] < 2:
        print(f"[telemetry-gate] FAIL: tier-3 loop completed only "
              f"{snap['swaps_completed']} swap(s), expected 2")
        return 1
    print(f"[telemetry-gate] ok: tier-3 decode loop compile_delta "
          f"off={delta_off} on={delta_on}, accept_rate="
          f"{snap['draft_accept_rate']}, {snap['swaps_completed']} "
          "swap(s)")
    return 0


def main() -> int:
    from deeplearning4j_tpu.runtime import telemetry

    registry = telemetry.registry
    net, batches = _net_and_data()

    # 1) warm every program this gate will dispatch
    net.fit_backprop(batches, num_epochs=1)
    registry.mark()

    # 2) tracer OFF: zero compile delta
    assert not telemetry.enabled()
    net.fit_backprop(batches, num_epochs=1)
    delta_off = registry.compile_delta_since_mark()
    if delta_off != 0:
        print(f"[telemetry-gate] FAIL: tracer-off fit compiled "
              f"{delta_off} new program(s)")
        return 1

    # 3) tracer ON: still zero compile delta, and a valid trace export
    tracer = telemetry.enable("telemetry-gate")
    registry.mark()
    net.fit_backprop(batches, num_epochs=1)
    delta_on = registry.compile_delta_since_mark()
    if delta_on != 0:
        print(f"[telemetry-gate] FAIL: tracer-on fit compiled "
              f"{delta_on} new program(s) — instrumentation leaked into "
              "a jitted region")
        return 1

    with tempfile.TemporaryDirectory() as d:
        journal = tracer.export_journal(
            os.path.join(d, "gate.jsonl"), snapshot=registry.snapshot())
        records = telemetry.read_journal(journal)
        payload = telemetry.chrome_trace(records, run_id=tracer.run_id)
        # valid Perfetto input: a traceEvents list that survives a JSON
        # round-trip, with the fit span among the complete slices
        payload = json.loads(json.dumps(payload))
        slices = [e for e in payload["traceEvents"] if e.get("ph") == "X"]
        if not any(e["name"] == "multilayer.fit" for e in slices):
            print("[telemetry-gate] FAIL: no multilayer.fit span in the "
                  "exported trace")
            return 1
    telemetry.disable()
    print(f"[telemetry-gate] ok: compile_delta off={delta_off} "
          f"on={delta_on}, {len(records)} journal record(s)")
    rc = _checkpoint_gate(registry, telemetry, net, batches)
    if rc:
        return rc
    rc = _data_service_gate(registry, telemetry)
    if rc:
        return rc
    rc = _mixed_precision_gate(registry, telemetry)
    if rc:
        return rc
    rc = _model_parallel_gate(registry, telemetry)
    if rc:
        return rc
    rc = _decode_gate(registry, telemetry)
    if rc:
        return rc
    rc = _tier2_decode_gate(registry, telemetry)
    if rc:
        return rc
    return _tier3_decode_gate(registry, telemetry)


if __name__ == "__main__":
    sys.exit(main())
