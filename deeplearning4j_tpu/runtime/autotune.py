"""Persistent kernel autotuner (ROADMAP item 3, the MFU campaign).

The repo's biggest measured win — the Pallas flash-attention kernel,
5.4x over XLA at T8192 — used to sit behind a static ``FLASH_MIN_SEQ``
heuristic and hardcoded 128x128 blocks.  TPUs reward exactly this
shape/layout tuning (arxiv 2309.08918, arxiv 2112.09017), and the right
answer is per (device kind, shape bucket), not per repo: the crossover
and the winning block sizes differ between a v5e and a v6e, and between
T=4096 and T=32768.

This module is the small harness that settles those questions ONCE per
fleet and remembers the answers:

- :func:`sweep_attention` times the XLA attention against the Pallas
  kernel at a grid of ``(block_q, block_k)`` candidates (fwd+bwd — the
  training shape of the op), picks the winner, and persists it;
- winners land in an on-disk JSON cache (``$DL4J_TPU_AUTOTUNE_CACHE``,
  default ``~/.cache/dl4j_tpu_autotune/attention.json``) keyed like
  ``runtime/compile_cache.py`` entries — a canonical string that fully
  determines the kernel family: device kind, power-of-two shape buckets,
  head dim, causality.  Writes are atomic (tmp + ``os.replace``) and
  merge with concurrent writers;
- :func:`lookup_attention` is what the training-path attention dispatch
  (``ops/pallas_attention.make_attn_fn``) consults at TRACE time: a
  cached winner overrides the static crossover and supplies the block
  sizes.  A warmed second process re-sweeps NOTHING — consults are pure
  host-side JSON reads, so the steady-state compile delta stays zero
  (tools/autotune_gate.py machine-checks this).

Every sweep/consult books into the ``mfu`` counter family
(``runtime/metrics.mfu_metrics``), the same family the analytic-MFU
estimates ride in, so bench rows carry the full evidence chain.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.runtime import compile_cache
from deeplearning4j_tpu.runtime.metrics import mfu_metrics

AUTOTUNE_CACHE_ENV = "DL4J_TPU_AUTOTUNE_CACHE"

#: (block_q, block_k) preferences swept on TPU; ``_pick_block`` inside
#: the kernel degrades each to the largest divisor of the actual T, so
#: candidates never fail on divisibility — only Mosaic can reject them
DEFAULT_BLOCK_CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (128, 128), (128, 256), (256, 128), (256, 256), (512, 128))

_LOCK = threading.RLock()
#: per-path in-process record memo: {path: {key: record}}; a warmed
#: process consults this dict, never the disk twice
_MEMO: Dict[str, Dict[str, Dict[str, Any]]] = {}


def cache_dir() -> Optional[str]:
    """Resolved autotune cache dir (same env grammar as the persistent
    XLA cache): unset/empty -> the default under ``~/.cache``;
    '0'/'false'/'off' -> disabled (None); anything else is the dir."""
    v = (os.environ.get(AUTOTUNE_CACHE_ENV) or "").strip()
    if v.lower() in ("0", "false", "off"):
        return None
    if not v or v.lower() in ("1", "true", "on"):
        return os.path.join(os.path.expanduser("~"), ".cache",
                            "dl4j_tpu_autotune")
    return os.path.expanduser(v)


def cache_path() -> Optional[str]:
    d = cache_dir()
    return os.path.join(d, "attention.json") if d else None


def reset_memo() -> None:
    """Drop the in-process record memo (tests; a fresh process starts
    empty anyway)."""
    with _LOCK:
        _MEMO.clear()


def shape_bucket(n: int) -> int:
    """Power-of-two shape bucket (floor 128 — below that blocks degrade
    to the sequence length anyway and the verdict is shape-insensitive).
    Same ladder philosophy as the serving engine's batch buckets: a
    bounded key space over an unbounded shape space."""
    return max(128, 1 << max(0, math.ceil(math.log2(max(n, 1)))))


def device_kind() -> str:
    d = jax.devices()[0]
    return getattr(d, "device_kind", "") or d.platform


def attn_key(kind: str, q_bucket: int, k_bucket: int, head_dim: int,
             causal: bool) -> str:
    """Canonical cache key — like a ``compile_cache`` engine key, it is
    exactly the information that determines the traced kernel family."""
    return (f"attn|{kind}|q{q_bucket}|k{k_bucket}|d{head_dim}|"
            f"{'causal' if causal else 'full'}")


def _load(path: str) -> Dict[str, Dict[str, Any]]:
    """Read the cache file once per process (memoized).  A corrupt or
    missing file is an empty cache — tuning state must never be able to
    break training."""
    with _LOCK:
        if path in _MEMO:
            return _MEMO[path]
    try:
        with open(path) as f:
            data = json.load(f)
        records = {k: v for k, v in data.items()
                   if isinstance(v, dict) and "impl" in v} \
            if isinstance(data, dict) else {}
    except (OSError, json.JSONDecodeError, ValueError):
        records = {}
    with _LOCK:
        return _MEMO.setdefault(path, records)


def _persist(path: str, key: str, record: Dict[str, Any]) -> None:
    """Merge one winner into the on-disk cache atomically: re-read the
    current file, write tmp, ``os.replace``.  The read-merge-replace is
    serialized across PROCESSES by a sidecar flock (two concurrent
    sweeps banking different keys must not overwrite each other's
    winner) and across threads by the module lock; where flock is
    unavailable the write degrades to lockless — worst case one lost
    winner re-sweeps in the next cold process, never a torn file."""
    with _LOCK:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        lockf = None
        try:
            try:
                import fcntl

                lockf = open(path + ".lock", "a")
                fcntl.flock(lockf, fcntl.LOCK_EX)
            except (ImportError, OSError):
                if lockf is not None:   # flock itself failed (e.g. NFS)
                    lockf.close()
                lockf = None
            try:
                with open(path) as f:
                    on_disk = json.load(f)
                if not isinstance(on_disk, dict):
                    on_disk = {}
            except (OSError, json.JSONDecodeError, ValueError):
                on_disk = {}
            on_disk[key] = record
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(on_disk, f, indent=2, sort_keys=True)
            os.replace(tmp, path)
        finally:
            if lockf is not None:
                lockf.close()        # closing drops the flock
        _MEMO.setdefault(path, {}).update(on_disk)
    mfu_metrics.note("winners_persisted")


def lookup_attention(q_len: int, k_len: int, head_dim: int, causal: bool,
                     kind: Optional[str] = None
                     ) -> Optional[Dict[str, Any]]:
    """The trace-time consult: the persisted winner for this (device
    kind, shape bucket), or None when nothing was swept.  Pure host-side
    read — a cached dispatch re-running the compiled step never gets
    here, so consults cost zero steady-state compiles."""
    path = cache_path()
    if path is None:
        return None
    mfu_metrics.note("consults")
    rec = _load(path).get(attn_key(kind or device_kind(),
                                   shape_bucket(q_len), shape_bucket(k_len),
                                   head_dim, causal))
    mfu_metrics.note("cache_hits" if rec else "cache_misses")
    return rec


def measured_crossover(head_dim: int, causal: bool,
                       kind: Optional[str] = None) -> Optional[int]:
    """The measured flash/XLA crossover for a device kind: the smallest
    swept key-length bucket at which the Pallas kernel won.  None until
    a sweep has found a pallas win (bench rows then report the static
    heuristic with its provenance instead)."""
    path = cache_path()
    if path is None:
        return None
    kind = kind or device_kind()
    want_tail = f"|d{head_dim}|{'causal' if causal else 'full'}"
    wins: List[int] = []
    for key, rec in _load(path).items():
        if (key.startswith(f"attn|{kind}|") and key.endswith(want_tail)
                and rec.get("impl") == "pallas"):
            try:
                wins.append(int(key.split("|")[3][1:]))   # "k<bucket>"
            except (IndexError, ValueError):
                continue
    return min(wins) if wins else None


def _sync(x) -> float:
    """Force completion by fetching a value: a host read cannot return
    before the device has produced it."""
    return float(np.asarray(x).ravel()[0])


def _time_candidate(fn, args, repeats: int) -> float:
    """Median wall seconds of ``fn(*args)`` fwd+bwd dispatches after one
    warmup (the warmup call carries the compile; the timed calls are
    cached dispatches)."""
    out = fn(*args)
    _sync(jax.tree.leaves(out)[0])
    times = []
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        out = fn(*args)
        _sync(jax.tree.leaves(out)[0])
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def sweep_attention(q_len: int, k_len: int, head_dim: int, causal: bool,
                    *, batch: int = 1, n_heads: int = 1,
                    dtype=jnp.bfloat16,
                    blocks: Sequence[Tuple[int, int]] = None,
                    include_xla: bool = True, repeats: int = 3,
                    interpret: Optional[bool] = None,
                    persist: bool = True) -> Dict[str, Any]:
    """Time Pallas block-size variants against XLA attention (fwd+bwd)
    and bank the winner.

    ``interpret=None`` auto-selects the Pallas interpreter off-TPU —
    that keeps the harness exercisable on the CPU CI gate (tiny shapes),
    though interpreted timings are only meaningful as plumbing evidence,
    which the record marks via ``interpreted: true``.  Returns the
    winner record (also persisted unless ``persist=False`` or the cache
    is disabled)."""
    from deeplearning4j_tpu.models import transformer as tfm
    from deeplearning4j_tpu.ops import pallas_attention as pa

    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    blocks = tuple(blocks) if blocks else DEFAULT_BLOCK_CANDIDATES
    kind = device_kind()
    key = attn_key(kind, shape_bucket(q_len), shape_bucket(k_len),
                   head_dim, causal)
    mfu_metrics.note("sweeps")

    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    shape = (batch, q_len, n_heads, head_dim)
    kshape = (batch, k_len, n_heads, head_dim)
    q = jax.random.normal(kq, shape, dtype)
    k = jax.random.normal(kk, kshape, dtype)
    v = jax.random.normal(kv, kshape, dtype)

    def grad_fn(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v, None, causal).astype(jnp.float32))
        return compile_cache.cached_jit(
            jax.grad(loss, argnums=(0, 1, 2)), label="autotune.probe")

    candidates: Dict[str, Dict[str, Any]] = {}
    if include_xla:
        mfu_metrics.note("candidates_timed")
        try:
            t = _time_candidate(grad_fn(tfm.attention), (q, k, v), repeats)
            candidates["xla"] = {"impl": "xla", "block_q": 0, "block_k": 0,
                                 "step_ms": round(t * 1e3, 3)}
        except Exception as e:  # noqa: BLE001 — XLA OOMs at very long T
            candidates["xla"] = {"impl": "xla", "error": repr(e)[:200]}
    for bq, bk in blocks:
        mfu_metrics.note("candidates_timed")
        name = f"pallas_q{bq}_k{bk}"
        try:
            fn = grad_fn(lambda q, k, v, m, c, _bq=bq, _bk=bk:
                         pa.flash_attention(q, k, v, m, c, block_q=_bq,
                                            block_k=_bk,
                                            interpret=interpret))
            t = _time_candidate(fn, (q, k, v), repeats)
            candidates[name] = {"impl": "pallas", "block_q": bq,
                                "block_k": bk,
                                "step_ms": round(t * 1e3, 3)}
        except Exception as e:  # noqa: BLE001 — Mosaic rejects are data
            candidates[name] = {"impl": "pallas", "block_q": bq,
                                "block_k": bk, "error": repr(e)[:200]}

    timed = [c for c in candidates.values() if "step_ms" in c]
    if not timed:
        raise RuntimeError(
            f"autotune sweep {key}: every candidate failed "
            f"({ {n: c.get('error') for n, c in candidates.items()} })")
    best = min(timed, key=lambda c: c["step_ms"])
    record = {
        "key": key, "impl": best["impl"], "block_q": best["block_q"],
        "block_k": best["block_k"], "step_ms": best["step_ms"],
        "device_kind": kind, "head_dim": head_dim, "causal": causal,
        "q_bucket": shape_bucket(q_len), "k_bucket": shape_bucket(k_len),
        "interpreted": bool(interpret),
        "swept_at": time.time(),
        "candidates": candidates,
    }
    path = cache_path()
    if persist and path is not None:
        _persist(path, key, record)
    else:
        with _LOCK:
            if path is not None:
                _MEMO.setdefault(path, {})[key] = record
    return record


def ensure_attention(q_len: int, k_len: int, head_dim: int, causal: bool,
                     **sweep_kwargs) -> Dict[str, Any]:
    """Consult-or-sweep: the cached winner when one exists, else one
    sweep (persisted).  The warmed-process contract rides on this: call
    sites that ensure at startup never sweep twice for a shape."""
    rec = lookup_attention(q_len, k_len, head_dim, causal)
    if rec is not None:
        return rec
    return sweep_attention(q_len, k_len, head_dim, causal, **sweep_kwargs)
