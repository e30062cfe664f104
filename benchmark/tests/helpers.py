from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def run_cli(args: List[str], cwd: str = ROOT, cpu: bool = True,
            extra_env: Optional[Dict[str, str]] = None
            ) -> Tuple[int, Optional[Dict[str, Any]], str]:
    """``python3 benchmark/run.py <args>`` -> (exit code, last stdout line
    as JSON or None, stderr)."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    p = subprocess.run([sys.executable, "benchmark/run.py", *args], cwd=cwd,
                       env=env, capture_output=True, text=True, timeout=600)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    try:
        last = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        last = None
    return p.returncode, last, p.stderr


def execute(workload: str, seed: int = 11, seconds: float = 0.5,
            trace: bool = False) -> Dict[str, Any]:
    """The rest of a run with the look for a chip skipped: the rehearsal
    sizes, in this process."""
    from benchmark import run as runner
    from benchmark.lib import spec

    cell = spec.load_cell(workload, rehearse=True)
    return runner.execute(cell, dict(CPU_DEVICE), seed, seconds, trace)
