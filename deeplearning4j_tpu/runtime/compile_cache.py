"""Shared compile/donation engine for the core training stack.

The reference DL4J recompiles nothing (the JVM interprets ND4J ops), but
the TPU port's hot loop is a jitted XLA program — and before this module
every ``MultiLayerNetwork``/``Solver`` INSTANCE built its own jitted step,
so N identical worker replicas (``parallel/scaleout.py`` performers
rebuilding nets from conf JSON, ``parallel/data_parallel.py`` shards)
paid N full XLA compiles for one program.  That is exactly the dispatch/
compile overhead TensorFlow's single-dataflow-program design (Abadi et
al., arXiv:1605.08695) and the Julia-to-TPU full-compilation work
(arXiv:1810.09868) identify as dominant for small-step workloads.

Two services, both instrumented into
``runtime.metrics.compile_metrics``:

- ``cached_jit(fn, ...)`` — ``jax.jit`` through the engine.  Every trace
  bumps ``compile_count`` (per ``label``), and wall-time of compiling
  calls accumulates into ``compile_ms``.  With ``key=`` the jitted
  callable is shared MODULE-WIDE: the first caller builds it, later
  callers with an equal key get the same callable, so XLA compiles once
  per input-shape signature across all instances.  Only pass ``key``
  when the traced computation is fully determined by the key (e.g. a
  canonical conf JSON) — never when the function closes over data.
- ``get_or_build(key, builder)`` — same sharing for arbitrary engine
  bundles (e.g. the multilayer (train_step, train_epochs, updaters)
  triple).

Donation contract: engine-level steps declare ``donate_argnums`` for
params/updater-state so updates reuse HBM in place (no 2x param traffic,
no doubled peak memory).  The RAW cached callables therefore invalidate
those argument buffers — the PYTHON API boundary (``fit_backprop``,
``Solver.optimize``, ...) is responsible for the copy-on-entry guard
(one ``jnp.copy`` of caller-held arrays per call) so user code never
sees a deleted buffer.  ``tools/jaxlint`` (the ``stray-jit`` rule;
``tools/check_no_stray_jit.py`` shims into it) lints the hot-path
packages so future code goes through this engine, and its
``use-after-donate`` rule catches scope-local reads of donated buffers.

The persistent ON-DISK compilation cache (skipping XLA compiles across
processes) is placed separately by ``runtime.ensure_compile_cache``.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Hashable, Optional

import jax

from deeplearning4j_tpu.runtime.metrics import compile_metrics

#: LRU bound — a long-lived serving process cycling through many distinct
#: confs must not grow the engine without bound (each entry pins its
#: traced closure + XLA executables)
MAX_ENTRIES = 256

_LOCK = threading.RLock()
_ENGINES: "OrderedDict[Hashable, Any]" = OrderedDict()


def _instrument(fn: Callable, label: str, **jit_kwargs) -> Callable:
    """jax.jit ``fn`` with trace counting + compile-wall-time metering."""
    # per-callable, per-THREAD trace counter: a trace always runs on the
    # thread whose call triggered it, so thread-local attribution books a
    # compile to exactly that call — a global (or even per-callable
    # shared) counter would book thread A's cached dispatch as a compile
    # whenever thread B happens to be tracing concurrently (the
    # multi-worker scaleout case the engine exists for)
    local = threading.local()

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        # runs at TRACE time only — one bump per (shapes, dtypes) signature
        local.traces = getattr(local, "traces", 0) + 1
        compile_metrics.note_trace(label)
        return fn(*args, **kwargs)

    # the engine implementation is the one legitimate jax.jit site;
    # everything else routes through it
    jitted = jax.jit(traced, **jit_kwargs)  # jaxlint: disable=stray-jit — the engine itself

    @functools.wraps(fn)
    def call(*args, **kwargs):
        before = getattr(local, "traces", 0)
        t0 = time.perf_counter()
        out = jitted(*args, **kwargs)
        if getattr(local, "traces", 0) > before:
            compile_metrics.note_compile_ms((time.perf_counter() - t0) * 1e3)
        else:
            compile_metrics.note_cached_dispatch()
        return out

    call.engine_label = label
    call.jitted = jitted      # escape hatch for .lower()/AOT inspection
    return call


def cached_jit(fn: Callable, *, key: Optional[Hashable] = None,
               label: Optional[str] = None, **jit_kwargs) -> Callable:
    """``jax.jit`` through the engine (see module docstring).

    ``jit_kwargs`` pass straight through (``donate_argnums``,
    ``static_argnums``, ...).  Without ``key`` the callable is private to
    the caller but still instrumented; with ``key`` it is shared
    module-wide and the lookup counts as an engine hit/build.
    """
    label = label or getattr(fn, "__name__", "jit")
    if key is None:
        return _instrument(fn, label, **jit_kwargs)
    return get_or_build(("jit", key),
                        lambda: _instrument(fn, label, **jit_kwargs))


def get_or_build(key: Hashable, builder: Callable[[], Any]) -> Any:
    """Shared engine entry: first caller's ``builder()`` result wins;
    every later caller with an equal key gets the SAME object."""
    with _LOCK:
        entry = _ENGINES.get(key)
        if entry is not None:
            _ENGINES.move_to_end(key)
            compile_metrics.note_engine(hit=True)
            return entry
    # build outside the lock.  Builders only CONSTRUCT closures/jit
    # wrappers — jax.jit is lazy, so the expensive trace+XLA compile
    # happens at first CALL of the one entry setdefault keeps; threads
    # racing a cold key waste microseconds of closure building, never a
    # duplicate compile.
    built = builder()
    with _LOCK:
        entry = _ENGINES.setdefault(key, built)
        compile_metrics.note_engine(hit=entry is not built)
        _ENGINES.move_to_end(key)
        while len(_ENGINES) > MAX_ENTRIES:
            _ENGINES.popitem(last=False)
        return entry


def clear() -> None:
    """Drop every SHARED entry (primarily for tests).  Counters in
    ``compile_metrics`` are reset separately.  Note this does NOT reach
    per-network memos of already-handed-out bundles (e.g. an existing
    ``MultiLayerNetwork`` keeps its machinery): mutating a live
    network's conf still requires a fresh network, same as always."""
    with _LOCK:
        _ENGINES.clear()


def size() -> int:
    with _LOCK:
        return len(_ENGINES)
