"""From a profiler trace (``.xplane.pb``) to the table the per-layer
metrics read.  One reduction for every cell and every PR:

    window_s      length of the traced window (the host's
                  ``bench.traced_window`` annotation; the device events'
                  extent where a trace has none)
    busy_s        seconds in which an operation ran on the device: the union
                  of the device-op intervals inside the window, averaged over
                  the chips that have a plane
    programs      {XLA module name: {"count": dispatches, "seconds": device time}},
                  of the dispatches that lie wholly inside the window
    device_ops    [[op name, self seconds]], the 10 largest, averaged over chips
    idle_gaps     [[what the host was doing, idle seconds]], the 10 largest:
                  every gap between device ops goes to the innermost host span
                  that covers its middle ("unattributed" where none does)

Planes: a TPU trace has one plane per chip (``/device:TPU:<n>``) with the
lines ``XLA Modules`` (one event per program dispatch) and ``XLA Ops``
(one event per HLO op, a ``while`` enclosing its body's ops, which is why
op time is SELF time).  The CPU backend has no device plane: there the
events that carry an ``hlo_module`` stat stand in, so that a rehearsal
runs this same code; its numbers are not device numbers.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.traced_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rt") as f:
            return ProfileData.from_text_proto(f.read())
    if path.endswith((".txt", ".textproto")):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def module_name(event_name: str) -> str:
    """``jit_epochs(123456789)`` -> ``jit_epochs``."""
    return re.sub(r"\(\d+\)$", "", event_name)


_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_label(event_name: str, width: int = 120) -> str:
    """A device op's event name is its whole HLO line
    (``%fusion.5 = (f32[8,16]{1,0:T(8,128)}, ...) fusion(...), kind=...``).
    The label keeps the op's name, its kind and what it produces, with
    the layouts dropped: ``fusion.5 kOutput (f32[8,16], ...)``."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name[:width]
    rest = _LAYOUT.sub("", rest)
    m = re.match(r"(\(.*?\)|\S+) ([\w\-]+)\(", rest)
    produces, opcode = (m.group(1), m.group(2)) if m else ("", "")
    kind = re.search(r"kind=(\w+)", rest)
    what = kind.group(1) if kind else opcode
    return f"{head.lstrip('%')} {what} {produces}"[:width].rstrip()


def union_seconds(intervals: Iterable[Interval]) -> Tuple[float,
                                                          List[Interval]]:
    """Total length of the union (ns in, seconds out) and the merged
    intervals."""
    merged: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return (sum(hi - lo for lo, hi in merged) / 1e9,
            [(lo, hi) for lo, hi in merged])


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Self seconds by name for events of one line, where an event may
    enclose others (a ``while`` and its body)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List[Any]] = []          # [end, name, self_ns]
    for lo, hi, name in sorted(events, key=lambda e: (e[0], -(e[1] - e[0]))):
        while stack and stack[-1][0] <= lo:
            end, nm, self_ns = stack.pop()
            out[nm] += self_ns / 1e9
        if stack:
            stack[-1][2] -= min(hi, stack[-1][0]) - lo
        stack.append([hi, name, hi - lo])
    for end, nm, self_ns in stack:
        out[nm] += self_ns / 1e9
    return out


def _clip(events, lo: float, hi: float):
    for a, b, name in events:
        a2, b2 = max(a, lo), min(b, hi)
        if b2 > a2:
            yield a2, b2, name


def _device_lines(profile) -> List[Dict[str, List[Tuple[float, float, str]]]]:
    """Per chip: {"modules": [...], "ops": [...]} as (start, end, name)."""
    chips = []
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {"modules": [], "ops": []}
        for line in plane.lines:
            key = {MODULES_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
            if key is None:
                continue
            lines[key] = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events]
        chips.append(lines)
    if chips:
        return chips
    # the CPU backend: ops are the host events with an hlo_module stat,
    # and a program's dispatches are the runs of ops of one module
    ops, modules = [], []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_module" in stats:
                    ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
                    modules.append((e.start_ns, e.start_ns + e.duration_ns,
                                    str(stats["hlo_module"])))
    return [{"modules": modules, "ops": ops}] if ops else []


def _host_lines(profile, skip_hlo: bool
                ) -> List[List[Tuple[float, float, str]]]:
    """The host's spans, one list per thread line, sorted by start with
    an enclosing span before what it encloses."""
    lines = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for e in line.events if e.duration_ns > 0
                     and not (skip_hlo and "hlo_module" in dict(e.stats))]
            if spans:
                spans.sort(key=lambda s: (s[0], -(s[1] - s[0])))
                lines.append(spans)
    return lines


def _attribute(gaps: List[Interval],
               lines: List[List[Tuple[float, float, str]]]
               ) -> Dict[str, float]:
    """Idle seconds by the innermost host span over each gap's middle:
    one sweep over each thread line, whose spans nest."""
    gaps = sorted(gaps)
    mids = [(lo + hi) / 2.0 for lo, hi in gaps]
    best: List[Optional[Tuple[float, str]]] = [None] * len(gaps)
    for spans in lines:
        stack: List[Tuple[float, float, str]] = []
        i = 0
        for g, mid in enumerate(mids):
            while i < len(spans) and spans[i][0] <= mid:
                while stack and stack[-1][1] <= spans[i][0]:
                    stack.pop()
                stack.append(spans[i])
                i += 1
            while stack and stack[-1][1] <= mid:
                stack.pop()
            if stack and stack[-1][2] != WINDOW_SPAN:
                width = stack[-1][1] - stack[-1][0]
                if best[g] is None or width < best[g][0]:
                    best[g] = (width, stack[-1][2])
    out: Dict[str, float] = defaultdict(float)
    for (lo, hi), b in zip(gaps, best):
        out[b[1] if b else "unattributed"] += (hi - lo) / 1e9
    return out


def _top(table: Dict[str, float], n: int = 10) -> List[List[Any]]:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


def reduce_profile(profile) -> Optional[Dict[str, Any]]:
    """The table, or ``None`` where no operation ran on a device."""
    chips = _device_lines(profile)
    if not chips:
        return None
    on_tpu = any(DEVICE_PLANE.match(p.name) for p in profile.planes)
    lines = _host_lines(profile, skip_hlo=not on_tpu)
    window = next(((a, b) for spans in lines for a, b, n in spans
                   if n == WINDOW_SPAN), None)
    if window is None:
        starts = [a for c in chips for a, _, _ in c["ops"] + c["modules"]]
        ends = [b for c in chips for _, b, _ in c["ops"] + c["modules"]]
        window = (min(starts), max(ends))
    lo, hi = window
    n = float(len(chips))
    busy = 0.0
    programs: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"count": 0.0, "seconds": 0.0})
    op_self: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    for chip in chips:
        ops = list(_clip(chip["ops"] or chip["modules"], lo, hi))
        seconds, merged = union_seconds((a, b) for a, b, _ in ops)
        busy += seconds / n
        for name, s in self_times(ops).items():
            op_self[op_label(name)] += s / n
        for a, b, name in chip["modules"]:
            if a < lo or b > hi:
                continue        # a dispatch cut by the window's edge
            p = programs[module_name(name)]
            p["count"] += 1.0 / n
            p["seconds"] += (b - a) / 1e9 / n
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for label, s in _attribute(idle, lines).items():
            gaps[label] += s / n
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy, "chips": int(n),
            "programs": {k: dict(v) for k, v in programs.items()},
            "device_ops": _top(op_self), "idle_gaps": _top(gaps)}


def reduce_dir(trace_dir: str) -> Optional[Dict[str, Any]]:
    return reduce_profile(load(find_xplane(trace_dir)))


def program_time(table: Dict[str, Any], pattern: str
                 ) -> Optional[Tuple[float, float]]:
    """(dispatches, device seconds) of the programs whose module name
    matches ``pattern``; ``None`` where none ran in the trace."""
    rx = re.compile(pattern)
    hit = [v for k, v in table.get("programs", {}).items() if rx.search(k)]
    if not hit:
        return None
    return sum(v["count"] for v in hit), sum(v["seconds"] for v in hit)


def to_text_proto(profile, seconds: float = 0.5, skip: float = 0.0,
                  host_lines=("python",)) -> str:
    """A cut of a trace small enough to keep as a recorded fixture:
    the events of the device lines the reduction reads, and of the
    named host threads, that lie wholly inside ``seconds`` from
    ``skip`` seconds after the traced window opened, as an XSpace text
    proto that ``load`` reads back.  Device ops are named by
    ``op_label`` (their HLO text runs to kilobytes); the window's own
    span is cut to the same interval."""
    window = next(((e.start_ns, e.start_ns + e.duration_ns)
                   for plane in profile.planes if plane.name == HOST_PLANE
                   for line in plane.lines for e in line.events
                   if e.name == WINDOW_SPAN), None)
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN} span")
    lo = window[0] + skip * 1e9
    hi = min(window[1], lo + seconds * 1e9)
    out = []
    for plane in profile.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        keep = (MODULES_LINE, OPS_LINE) if device else host_lines
        meta: Dict[str, int] = {}
        body = []
        for line in plane.lines:
            if not any(line.name.startswith(k) for k in keep):
                continue
            events = [(e.start_ns, e.duration_ns,
                       op_label(e.name) if line.name == OPS_LINE else e.name)
                      for e in line.events
                      if e.name != WINDOW_SPAN and e.start_ns >= lo
                      and e.start_ns + e.duration_ns <= hi]
            if not device and any(e.name == WINDOW_SPAN
                                  for e in line.events):
                events.append((lo, hi - lo, WINDOW_SPAN))
            if not events:
                continue
            t0 = int(lo)
            body.append(f'  lines {{ name: "{line.name}" timestamp_ns: {t0}')
            for start, dur, name in sorted(events):
                mid = meta.setdefault(name, len(meta) + 1)
                body.append(
                    f"    events {{ metadata_id: {mid} "
                    f"offset_ps: {int((start - t0) * 1000)} "
                    f"duration_ps: {int(dur * 1000)} }}")
            body.append("  }")
        if not body:
            continue
        out.append(f'planes {{ name: "{plane.name}"')
        out.extend(body)
        for name, mid in meta.items():
            safe = name.replace("\\", "\\\\").replace('"', '\\"')
            out.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                       f'name: "{safe}" }} }}')
        out.append("}")
    return "\n".join(out) + "\n"


def describe(profile) -> str:
    """Planes, lines, event counts and the first names: what to look at
    by hand before trusting a pattern."""
    out = []
    for plane in profile.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            names = sorted({e.name for e in events[:2000]})[:12]
            out.append(f"  LINE {line.name!r} events={len(events)} "
                       f"names={names}")
    return "\n".join(out)


if __name__ == "__main__":
    import json
    import sys

    prof = load(sys.argv[1])
    print(describe(prof))
    print(json.dumps(reduce_profile(prof), indent=1)[:6000])
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(to_text_proto(prof, *map(float, sys.argv[3:5])))
