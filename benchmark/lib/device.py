"""The device a run is on: what JAX reports, the table of peaks, memory."""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict

from benchmark.lib.spec import BENCH_DIR


class NoAccelerator(SystemExit):
    """No TPU, or fewer chips than the cell asks for: exit code 3, no
    result line."""


def describe(chips: int, rehearse: bool) -> Dict[str, Any]:
    """``{"platform", "kind", "count"}`` as JAX reports them.  Without
    ``--rehearse-cpu`` anything but ``chips`` or more TPU devices ends the
    process before a single compile."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"[device] jax={jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind!r} device_count={len(devices)} "
          f"asked={chips}", file=sys.stderr, flush=True)
    if rehearse:
        if dev.platform == "tpu":
            raise SystemExit("--rehearse-cpu on a TPU: run the cell itself")
        return device
    if dev.platform != "tpu" or len(devices) < chips:
        print(f"benchmark: no accelerator: platform={dev.platform} "
              f"devices={len(devices)}, the cell asks for {chips} TPU "
              f"chip(s); nothing was run (--rehearse-cpu is the explicit "
              f"CPU rehearsal)", file=sys.stderr, flush=True)
        raise NoAccelerator(3)
    return device


def compile_cache() -> str:
    """The persistent compile cache, where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), with
    every program kept: JAX's default leaves out what compiled in under
    a second, and a run's many small programs would compile again in
    every later run's set-up."""
    import jax

    from deeplearning4j_tpu.runtime import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def peaks(kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``.  A kind that is
    not in ``peaks.json`` is an error, never a default."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table or kind.startswith("_"):
        raise KeyError(
            f"device_kind {kind!r} is not in benchmark/peaks.json "
            f"({[k for k in table if not k.startswith('_')]}): add its "
            f"published peaks with their source")
    return table[kind]


def memory_peak_bytes(n_used: int) -> int:
    """Peak bytes in use on the fullest of the chips used, 0 where the
    backend does not report it (the CPU rehearsal)."""
    import jax

    peak = 0
    for dev in jax.devices()[:n_used]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
