"""TPU-native inference serving: jitted bucketed forward + dynamic
micro-batching + continuous-batching autoregressive decode.

One-shot forwards (classification, scoring):

- ``InferenceEngine`` (engine.py): donated, jitted forward through the
  runtime compile engine, shape-bucketed so the compile count is bounded
  by the bucket ladder, with AOT ``warmup()``.
- ``DynamicBatcher`` (batcher.py): background coalescing of concurrent
  requests into micro-batches under a max_batch_size / max_delay_ms
  policy.

Autoregressive decode (models/gpt.py causal LMs):

- ``DecodeEngine`` (decode.py): one page pool and one table of
  ``n_slots`` slots for every length, ONE donated decode-step
  executable per table width of the cache-length ladder advancing all
  occupied slots per dispatch; new requests prefill into free slots
  mid-flight (continuous batching).
- ``ContinuousBatcher`` (decode.py): streaming per-request front-end
  over one engine (token streams, EOS/budget slot recycling, drain on
  close).
- ``Router`` (router.py): N replicas behind least-depth dispatch with a
  queue-depth load-shed bound (typed ``OverloadedError``).

Serving tier 2 (per-chip economics; runtime/quantize.py holds the
weight quantization itself):

- ``DecodeEngine(quantize=, kv_dtype=, prefix_cache=)`` /
  ``InferenceEngine(quantize=)``: per-channel int8 (or bf16) weights
  with dequant fused into the jitted programs, an int8 KV cache
  (~4x/2x slots per chip), and content-hashed prompt-prefix KV reuse
  (``PrefixCache``) — hits skip re-prefill bit-exactly.
- ``AutoscalingRouter`` + ``AutoscalePolicy`` (router.py): replica
  scale-up/down and load-shedding driven by live queue-depth/TTFT
  telemetry with hysteresis, instead of the static bound.

Serving tier 3 (live tokens, live weights, raw tokens/s):

- ``DecodeEngine(n_pages=)``: the KV cache is ONE pool
  of fixed-size pages (``KV_PAGE_TOKENS`` rows each) with per-slot
  page tables — slots/chip bounded by LIVE tokens, not bucket length;
  prefix hits mount pool-resident pages BY REFERENCE (refcounted
  ``PageAllocator``); pool exhaustion stalls, then sheds with the
  typed ``KVPagesExhausted``.
- ``AutoscalingRouter.swap_weights(params)`` + engine
  ``rebind_params``: zero-downtime hot checkpoint swap — drain one
  replica at a time, requantize off the serving workers, zero dropped
  requests, zero new compiles.
- ``DecodeEngine(draft=(cfg_d, params_d), draft_k=)``: draft-model
  speculative decoding — k proposed tokens verified in ONE target
  dispatch, bit-identical to plain decode at any temperature.

Serving fault tolerance (behavior under partial failure):

- ``DecodeRequest(deadline_ms=)``: per-request deadlines — expired
  requests free their slot, reclaim their KV pages, and resolve with
  the typed ``DeadlineExceeded`` instead of occupying capacity.
- ``AutoscalingRouter(health=ReplicaHealth(...))``: a health monitor
  thread detects dead workers, dispatch-error streaks, and stalls,
  then retires the replica and spawns a factory replacement (zero new
  compiles); every in-flight request is journaled (prompt, seed,
  temperature, tokens emitted) and replayed BIT-identically on the
  replacement — sampling keys fold (seed, position), so replica death
  loses no request.
- Graceful brownout: under pressure at the replica ceiling the router
  first disables speculative decoding, then bypasses prefix
  harvesting — booked, reversible — and only sheds from level 2.
- ``SwapFailed`` / ``RouterClosed`` / ``BatcherClosed``: typed errors
  for wedged swap drains and submit-vs-close races.
- ``parallel.chaos.ServingChaos`` + ``tools/serving_chaos_gate.py``:
  fault-injection drill asserting bit-exact completion, zero new
  compiles, and zero leaked pages under replica kill / dispatch
  poison / stall / pool exhaustion.

``MultiLayerNetwork.output/predict/score`` and ``Evaluation.eval`` route
through this layer; the per-model adapters live next to each model
(``models/*.make_serving_apply``).  Metrics:
``runtime.metrics.serving_metrics`` (one-shot) and
``runtime.metrics.decode_metrics`` (decode).
"""

from deeplearning4j_tpu.serving.batcher import DynamicBatcher  # noqa: F401
from deeplearning4j_tpu.serving.decode import (  # noqa: F401
    KV_PAGE_TOKENS, BatcherClosed, ContinuousBatcher, DeadlineExceeded,
    DecodeEngine, DecodeRequest, KVPagesExhausted, PageAllocator,
    PrefixCache, default_length_buckets,
)
from deeplearning4j_tpu.serving.engine import (  # noqa: F401
    InferenceEngine, default_buckets, pad_rows, pick_bucket,
)
from deeplearning4j_tpu.serving.router import (  # noqa: F401
    AutoscalePolicy, AutoscalingRouter, OverloadedError, ReplicaHealth,
    Router, RouterClosed, SwapFailed,
)
