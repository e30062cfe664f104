"""Device time by ``jax.named_scope``, from the same profiler trace
``trace_reduce.py`` reads: for the dispatches of one program, the self
seconds of the device ops whose ``op_name`` lies under each scope.

    scope_seconds(profile, scopes, program, hlo_texts) ->
        {"dispatches": n, "seconds": {scope: device seconds}}

A TPU trace names a device op by its HLO instruction (``%fusion.905 =
bf16[5120,1536]{...} fusion(...), kind=kLoop, calls=...``) and carries
no metadata; the ``op_name`` XLA keeps for the instruction
(``jit(decode_fn)/jit(main)/moe_experts/while/body/dot_general``) is in
the program's optimized HLO text.  The two are joined on the
instruction's name and result type (``hlo_texts``: the text of every
program whose dispatches are read, e.g. one decode step a rung; rungs
number their instructions alike, and where two programs give one name
and type to ops of different scopes the op is counted under neither).
A fusion has ONE ``op_name``, its root's: what XLA fused across a
scope's edge is counted on the root's side, so a scope's seconds are the
device's, its edges XLA's.  Ops are counted by SELF time (a ``while``
encloses its body's ops).

Without ``hlo_texts`` (a program that cannot give them, the CPU
backend's trace) every scope reads nothing and a metric over it is left
out.
"""

from __future__ import annotations

import bisect
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark.lib import trace_reduce

_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\(.*?\)|\S+) [\w\-]+\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instruction_key(line: str) -> Optional[Tuple[str, str]]:
    """(instruction name, result type without layouts) of one HLO line,
    as the trace's event name and the program's text both print it."""
    m = _INSTRUCTION.match(_LAYOUT.sub("", line.split(", metadata=")[0]))
    return (m.group(1), m.group(2)) if m else None


def scope_of(op_name: str, scopes: Sequence[str]) -> Optional[str]:
    """The first of ``scopes`` that is a component of the path."""
    parts = op_name.split("/")
    return next((s for s in scopes if s in parts), None)


def scope_map(hlo_texts: Iterable[str], scopes: Sequence[str]
              ) -> Dict[Tuple[str, str], str]:
    """{instruction key: scope} over the programs' texts; "" for an
    instruction under none of the scopes or claimed by two."""
    out: Dict[Tuple[str, str], str] = {}
    for text in hlo_texts:
        for line in text.splitlines():
            key = instruction_key(line)
            if key is None:
                continue
            m = _OP_NAME.search(line)
            tag = (scope_of(m.group(1), scopes) if m else None) or ""
            if out.setdefault(key, tag) != tag:
                out[key] = ""
    return out


def scope_seconds(profile, scopes: Sequence[str], program: str,
                  hlo_texts: Iterable[str]) -> Optional[Dict[str, Any]]:
    """``None`` where there is no text to join on, the trace has no
    device plane, or no dispatch of ``program`` (a regular expression
    over XLA module names) lies wholly inside the traced window."""
    tags = scope_map(hlo_texts, scopes)
    if not any(tags.values()):
        return None
    rx = re.compile(program)
    window = next(
        ((e.start_ns, e.start_ns + e.duration_ns)
         for plane in profile.planes if plane.name == trace_reduce.HOST_PLANE
         for line in plane.lines for e in line.events
         if e.name == trace_reduce.WINDOW_SPAN), None)
    chips = 0
    dispatches = 0.0
    seconds: Dict[str, float] = {s: 0.0 for s in scopes}
    for plane in profile.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        modules: List[Tuple[float, float]] = []
        ops: List[Tuple[float, float, str]] = []
        for line in plane.lines:
            if line.name == trace_reduce.MODULES_LINE:
                modules = sorted(
                    (e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if rx.search(trace_reduce.module_name(e.name))
                    and (window is None
                         or (e.start_ns >= window[0]
                             and e.start_ns + e.duration_ns <= window[1])))
            elif line.name == trace_reduce.OPS_LINE:
                cache: Dict[str, str] = {}
                for e in line.events:
                    tag = cache.get(e.name)
                    if tag is None:
                        tag = cache[e.name] = tags.get(
                            instruction_key(e.name), "")
                    ops.append((e.start_ns, e.start_ns + e.duration_ns, tag))
        if not modules:
            continue
        chips += 1
        dispatches += len(modules)
        starts = [m[0] for m in modules]
        inside = []
        for lo, hi, tag in ops:
            i = bisect.bisect_right(starts, lo) - 1
            if i >= 0 and hi <= modules[i][1]:
                inside.append((lo, hi, tag))
        for tag, s in trace_reduce.self_times(inside).items():
            if tag:
                seconds[tag] += s
    if not chips:
        return None
    return {"dispatches": dispatches / chips,
            "seconds": {k: v / chips for k, v in seconds.items()}}
