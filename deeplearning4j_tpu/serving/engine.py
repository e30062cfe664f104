"""InferenceEngine — jitted, shape-bucketed TPU inference serving.

Every inference entry point in the reference runs eagerly, op by op,
with a fresh dispatch per call (``MultiLayerNetwork.output`` /
``predict`` / ``score``, the ``Evaluation`` pipeline).  Each eager op
pays its own host dispatch, and a naively jitted forward
recompiles for every distinct batch size a client sends — unbounded
compile count under real traffic.  This module is the serving recipe
TensorFlow's large-scale serving story (Abadi et al., arXiv:1605.08695)
and TPU serving practice both land on:

- the forward pass is ONE XLA program, compiled through the runtime
  compile engine (``runtime/compile_cache.cached_jit``) so identically
  configured replicas share a single compile and every trace is counted;
- incoming batches are padded up to a fixed **bucket ladder** and the
  result rows sliced back out, so the total compile count is bounded by
  the bucket set no matter what sizes clients send;
- ``warmup()`` pre-traces every bucket ahead of traffic (AOT), after
  which a sustained mixed-size request stream causes ZERO new XLA
  compilations — asserted via ``runtime.metrics.compile_metrics`` /
  ``serving_metrics.mark_compiles()``;
- the padded input buffer is engine-owned and DONATED to the jitted
  forward, so its HBM is reused in place (params are NOT donated — they
  serve every request).

Request data is normalized to host numpy for padding (serving requests
arrive host-side; a device-resident input pays one fetch).  Padding and
slicing happen outside the engine-counted program on purpose: a new
request size must never cost a forward-pass compile.

``DynamicBatcher`` (serving/batcher.py) sits in front of this engine to
coalesce many small concurrent requests into one MXU dispatch.

This engine serves ONE-SHOT forwards.  Autoregressive decode traffic —
where a request is a sequence of dependent dispatches, one per generated
token — is a different shape with its own engine: the slot-structured
continuous-batching ``DecodeEngine`` in serving/decode.py.
"""

from __future__ import annotations

import time
import warnings
from typing import Any, Callable, Hashable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.runtime import compile_cache, telemetry
from deeplearning4j_tpu.runtime.metrics import serving_metrics

Array = jax.Array

#: default ladder: powers of two — log2(max) + 1 programs bound the
#: compile count for any request size up to max_batch_size
DEFAULT_MAX_BATCH = 256


def default_buckets(max_batch_size: int = DEFAULT_MAX_BATCH) -> Tuple[int, ...]:
    """Powers-of-two ladder 1, 2, 4, ... up to (and including) the
    smallest power >= max_batch_size."""
    if max_batch_size < 1:
        raise ValueError(f"max_batch_size must be >= 1: {max_batch_size}")
    ladder = [1]
    while ladder[-1] < max_batch_size:
        ladder.append(ladder[-1] * 2)
    return tuple(ladder)


def pick_bucket(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n; callers chunk by the largest bucket first,
    so n <= max(buckets) always holds here."""
    for b in buckets:
        if b >= n:
            return b
    raise ValueError(f"no bucket >= {n} in {buckets}")


def pad_rows(x: np.ndarray, bucket: int) -> np.ndarray:
    """Zero-pad the leading (batch) dim up to ``bucket``.  Host-side on
    purpose: device-side padding would compile a tiny program per
    (n, bucket) pair, re-introducing the unbounded compile count the
    ladder exists to remove."""
    n = x.shape[0]
    if n == bucket:
        return x
    buf = np.zeros((bucket,) + x.shape[1:], dtype=x.dtype)
    buf[:n] = x
    return buf


class InferenceEngine:
    """Donated, jitted, bucketed forward for any model.

    ``apply_fn(params, x) -> out`` must be a pure forward whose output
    rows depend only on the matching input rows (true of per-example
    inference: dense/conv/attention stacks with inference-mode batch
    norm); padded rows then cannot perturb real rows, and the sliced
    result is bit-identical to the same compiled forward run unpadded.
    (Under reduced-precision compute the JITTED forward may differ from
    an op-by-op eager chain at rounding level — fusion skips
    intermediate roundings; that is a property of jitting, not of the
    bucket padding.)

    ``params`` may be the pytree itself or a zero-arg callable returning
    it (so a live network's current params are always served).  With
    ``cache_key`` the jitted forward is shared module-wide through the
    runtime compile engine — N engines for identically-configured
    replicas compile once.  ``apply_fn`` may also already be an
    engine-wrapped callable (``cached_jit`` result); it is then used
    as-is.

    ``quantize="int8"|"bf16"`` enables post-training weight
    quantization (runtime/quantize.py): params are quantized ONCE
    (memoized per raw-tree identity) and the dequant is fused into the
    jitted forward, which becomes a NEW compile-cache entry keyed on
    the mode — a quantized replica never hits a full-precision
    replica's executable.  Accuracy deltas are the caller's contract
    (``Evaluation.assert_accuracy_within`` is the assertion helper).
    """

    def __init__(self, apply_fn: Callable, params: Any = None, *,
                 buckets: Optional[Sequence[int]] = None,
                 max_batch_size: int = DEFAULT_MAX_BATCH,
                 cache_key: Optional[Hashable] = None,
                 label: str = "serving.forward",
                 quantize: Optional[str] = None):
        from deeplearning4j_tpu.runtime import quantize as qz

        self.buckets = tuple(sorted(set(
            buckets if buckets is not None
            else default_buckets(max_batch_size))))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"bad bucket ladder: {self.buckets}")
        self._params = params
        self.quantize = qz.check_mode(quantize)
        self._qmemo = qz.QuantMemo()
        self._static_quantized = False
        #: (per-example shape, dtype) the engine serves — set by
        #: warmup() / the first successful infer; lets front-ends
        #: (DynamicBatcher) reject mismatched requests at submit time
        self.input_spec: Optional[Tuple[Tuple[int, ...], Any]] = None
        if getattr(apply_fn, "engine_label", None) is not None:
            if self.quantize is not None:
                raise ValueError(
                    "quantize= needs a raw apply_fn: an already "
                    "engine-wrapped callable's traced program cannot "
                    "be rekeyed on the quantization mode")
            self._forward = apply_fn        # already engine-wrapped
        else:
            if self.quantize is not None:
                raw_apply = apply_fn

                def apply_fn(params, x):
                    return raw_apply(qz.dequantize_tree(params), x)
                if cache_key is not None:
                    cache_key = (cache_key, "quantize", self.quantize)
            # donate the padded input (arg 1): engine-owned buffer, fresh
            # per dispatch, never seen again — params (arg 0) serve every
            # request and must survive
            self._forward = compile_cache.cached_jit(
                apply_fn, key=cache_key, label=label, donate_argnums=(1,))
        self.label = getattr(self._forward, "engine_label", label)

    # -- params ------------------------------------------------------------
    def current_params(self, params: Any = None) -> Any:
        from deeplearning4j_tpu.runtime import quantize as qz

        if params is None and not callable(self._params):
            # static params + quantization: quantize once and DROP the
            # raw fp32 tree — resident memory holds only int8 + scales
            # once the caller releases theirs
            if self.quantize is not None and self._params is not None \
                    and not self._static_quantized:
                self._params = qz.quantize_tree(self._params,
                                                self.quantize)
                self._static_quantized = True
            return self._params
        p = self._params if params is None else params
        if callable(p):
            p = p()
        if self.quantize is None or p is None:
            return p
        return self._qmemo.get(
            p, lambda raw: qz.quantize_tree(raw, self.quantize))

    # -- AOT warmup --------------------------------------------------------
    def warmup(self, input_shape: Optional[Sequence[int]] = None,
               dtype: Any = np.float32, example: Any = None,
               params: Any = None) -> dict:
        """Pre-trace every bucket before traffic arrives.

        ``input_shape`` is the per-example shape (no batch dim), or pass
        ``example`` (a representative batch) to take shape/dtype from
        it.  Returns {"buckets": n, "compiles": traces performed,
        "warmup_ms": wall} — steady state after this is compile-free for
        any request size (chunked above the ladder), which
        ``serving_metrics.mark_compiles()`` + ``snapshot()`` assert.
        """
        if example is not None:
            ex = np.asarray(example)
            input_shape, dtype = ex.shape[1:], ex.dtype
        if input_shape is None:
            raise ValueError("warmup needs input_shape=... or example=...")
        self.input_spec = (tuple(input_shape), np.dtype(dtype))
        from deeplearning4j_tpu.runtime.metrics import compile_metrics
        before = compile_metrics.snapshot()["traces"].get(self.label, 0)
        p = self.current_params(params)
        t0 = time.perf_counter()
        with telemetry.span("serving.warmup", buckets=len(self.buckets)):
            outs = []
            for b in self.buckets:
                x = np.zeros((b,) + tuple(input_shape), dtype=dtype)
                outs.append(self._call_forward(p, x))
            for o in outs:
                jax.block_until_ready(o)
        wall_ms = (time.perf_counter() - t0) * 1e3
        compiles = (compile_metrics.snapshot()["traces"].get(self.label, 0)
                    - before)
        serving_metrics.mark_compiles()
        return {"buckets": len(self.buckets), "compiles": compiles,
                "warmup_ms": round(wall_ms, 1)}

    def _call_forward(self, params: Any, x: np.ndarray):
        """The jitted forward with the best-effort-donation warning
        scoped out: XLA warns per TRACE when no output can alias the
        donated padded input (e.g. logits smaller than features) — the
        engine owns that buffer by contract, so the warning is noise,
        but the filter must not be installed globally where it would
        also hide failed-donation diagnostics from the TRAINING engine.
        (catch_warnings touches interpreter-global filter state; the
        exposure window is only the rare compiling call, so a
        concurrent trace at worst mis-scopes one cosmetic warning.)"""
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return self._forward(params, x)

    # -- inference ---------------------------------------------------------
    def _dispatch(self, x: np.ndarray, params: Any):
        """One bucketed forward: pad -> jitted apply -> slice rows out."""
        n = x.shape[0]
        bucket = pick_bucket(n, self.buckets)
        serving_metrics.note_dispatch(bucket)
        with telemetry.span("serving.dispatch", bucket=bucket, rows=n):
            out = self._call_forward(params, pad_rows(x, bucket))
        if bucket == n:
            return out
        return jax.tree.map(lambda o: o[:n], out)

    def infer(self, x, params: Any = None, sync: bool = False,
              count_request: bool = True):
        """Serve one request batch [n, ...]: bucket-pad, run the jitted
        forward, slice the n real rows back out.  Requests larger than
        the ladder are chunked by the largest bucket.  ``sync=True``
        blocks until the result is materialized (honest latency for the
        batcher); the recorded latency covers this call either way."""
        t0 = time.perf_counter()
        x = np.asarray(x)
        if x.ndim == 0:
            raise ValueError("infer expects a batched input [n, ...]")
        n = x.shape[0]
        if count_request:
            serving_metrics.note_request(n)
        with telemetry.span("serving.infer", rows=n):
            p = self.current_params(params)
            cap = self.buckets[-1]
            if n <= cap:
                out = self._dispatch(x, p)
            else:
                parts = [self._dispatch(x[i:i + cap], p)
                         for i in range(0, n, cap)]
                out = jax.tree.map(lambda *ls: jnp.concatenate(ls, axis=0),
                                   *parts)
            if sync:
                jax.block_until_ready(out)
        if self.input_spec is None:
            self.input_spec = (x.shape[1:], x.dtype)
        if count_request:
            # batcher-routed traffic records END-TO-END request latency
            # itself (submit -> resolved future); recording the inner
            # dispatch too would double-count into the same reservoir
            serving_metrics.note_latency_ms(
                (time.perf_counter() - t0) * 1e3)
        return out

    __call__ = infer
