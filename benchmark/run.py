"""One cell, once, in one new process:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name (``lib/spec.py``); this
file knows no cell.  Order of a run: the device (no TPU, or fewer chips
than the cell asks for, is exit code 3 before anything compiles), the
compile cache, weights on the device from ``--seed``, the driver's
set-up and warm-up, the window, ``memory_peak_bytes``, then the plain
reference and the comparison that decides ``correct``.  The last line of
standard output is the one JSON object of the contract; the numbers
compared stand beside their limits there (last key) and as the last
lines of standard error.

``--rehearse-cpu`` runs the same control flow on the CPU backend at the
sizes of ``rehearse.json``.  It prints ``platform=cpu``; nothing it
prints is a device number.  The script never sets ``JAX_PLATFORMS``.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, NamedTuple  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Context(NamedTuple):
    cell: Any
    seed: int
    seconds: float
    trace: bool
    t_process: float
    ledger: Any
    keep_check: bool = False     # controls.py: hand back what was compared


def per_layer_metrics(cell, table: Dict[str, Any]) -> Dict[str, Any]:
    """Each per-layer metric of the cell through its own reader; one
    that finds nothing to read is left out."""
    from benchmark.lib import spec

    out = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"])(table)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def build_table(cell, res: Dict[str, Any], device: Dict[str, Any]
                ) -> Dict[str, Any]:
    """What the readers read: the driver's counts, the trace reduction
    with the cell's programs resolved by name, the peaks, the sizes."""
    from benchmark.lib import device as devlib, trace_reduce

    table = dict(res["table"])
    table["chips"] = cell.chips
    table["config"] = {k: v for k, v in cell.config.items()
                       if isinstance(v, (int, float))}
    try:
        table["peaks"] = devlib.peaks(device["kind"])
    except KeyError:
        if device["platform"] == "tpu":
            raise
        table["peaks"] = {}        # the CPU rehearsal has no peaks
    trace = res.get("trace")
    if trace:
        table["trace"] = {"busy_s": trace["busy_s"],
                          "window_s": trace["window_s"]}
        table["programs"] = {}
        for alias, pattern in cell.traffic.get("programs", {}).items():
            hit = trace_reduce.program_time(trace, pattern)
            if hit is not None:
                table["programs"][alias] = {"count": hit[0],
                                            "seconds": hit[1]}
    return table


def execute(cell, device: Dict[str, Any], seed: int, seconds: float,
            trace: bool) -> Dict[str, Any]:
    """Everything of a run after the look for a chip: the driver, the
    comparison, the metrics.  Returns the result line as a dict."""
    from benchmark.lib import compare, spec
    from benchmark.lib.compile_ledger import CompileLedger

    ctx = Context(cell, seed, seconds, trace, T_PROCESS, CompileLedger())
    res = spec.driver(cell.traffic["driver"]).run(ctx)
    ok, rows = compare.verdict(res["compared"], cell.limits)
    correct = ok and not res["violations"]
    table = build_table(cell, res, device)
    if trace:
        metrics = per_layer_metrics(cell, table)
    else:
        values = {**res["end_to_end"], "setup_s": res["setup_s"]}
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end
                   if values.get(m["name"]) is not None
                   and math.isfinite(values[m["name"]])}
    dev = {**device, "memory_peak_bytes": res["memory_peak_bytes"]}
    line: Dict[str, Any] = {"correct": bool(correct),
                            "attempted": int(res["attempted"]),
                            "failed": int(res["failed"]),
                            "metrics": metrics, "device": dev}
    reduced = res.get("trace")
    if trace and reduced:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["compared"] = {**{k: {"value": _num(v), "limit": lim}
                           for k, (v, lim) in rows.items()},
                        "violations": res["violations"]}
    if table.get("stats"):
        print(f"[stats] {json.dumps(table['stats'])}", file=sys.stderr)
    print(f"[run] window_s={table['window_s']:.3f} "
          f"reference_s={table.get('reference_s', 0.0):.2f} "
          f"compiles={ctx.ledger.snapshot()}", file=sys.stderr)
    return line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()

    from benchmark.lib import device as devlib, spec

    cell = spec.load_cell(args.workload, rehearse=args.rehearse_cpu)
    device = devlib.describe(cell.chips, args.rehearse_cpu)

    cache_dir = devlib.compile_cache()
    print(f"[setup] workload={cell.name} seed={args.seed} "
          f"seconds={args.seconds} trace={args.trace} "
          f"compile_cache={cache_dir}", file=sys.stderr, flush=True)
    line = execute(cell, device, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(line), flush=True)
    compared = line["compared"]
    for v in compared["violations"]:
        print(f"[correct] violation: {v}", file=sys.stderr)
    for k, row in compared.items():
        if k != "violations":
            over = row["value"] == "inf" or row["value"] > row["limit"]
            print(f"[correct] {k}={row['value']} limit={row['limit']} "
                  f"{'OVER' if over else 'ok'}", file=sys.stderr)
    print(f"[correct] {line['correct']}", file=sys.stderr, flush=True)
    return 0


def _num(v: float):
    return v if math.isfinite(v) else "inf"


if __name__ == "__main__":
    sys.exit(main())
