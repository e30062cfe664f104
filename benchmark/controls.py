"""The readings a limit is set from, taken on the chip at the cell's own
size, many seeds to a process (set-up is long, the readings are short):

    python3 benchmark/controls.py --workload <cell> --seeds 1,2,3 --seconds 2 \\
        [--control-seeds 1,2,3] [--out chiprun_out/<file>.jsonl]

For every seed the cell's own driver runs a short window and its
compared numbers are the PROGRAM's reading (the lower one).  For the
control seeds the cell's driver (its ``readings``) puts the plain
reference in the program's place:

* computed in float8 (``precision="fp8"``), the step below the bfloat16
  the configurations state: the CONTROL, which has to read over a limit;
* a training cell also with half of every batch left out and the mean
  taken over the rest: a FAULT the numbers have to catch.  (A step that
  returns its state unchanged reads 1 by construction and needs no run.)

The benchmark's own runs never run this; ``tests/test_controls.py`` runs
it at the rehearsal size.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    from benchmark import run as runner
    from benchmark.lib import device as devlib, spec
    from benchmark.lib.compile_ledger import CompileLedger

    cell = spec.load_cell(args.workload, rehearse=args.rehearse_cpu)
    device = devlib.describe(cell.chips, args.rehearse_cpu)
    devlib.compile_cache()
    ledger = CompileLedger()
    driver = spec.driver(cell.traffic["driver"])
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx = runner.Context(cell, seed, args.seconds, False,
                             time.perf_counter(), ledger,
                             keep_check=seed in control_seeds)
        res = driver.run(ctx)
        row: Dict[str, Any] = {"workload": cell.name, "seed": seed,
                               "platform": device["platform"],
                               "program": res["compared"],
                               "violations": res["violations"],
                               "end_to_end": res["end_to_end"],
                               "memory_peak_bytes": res["memory_peak_bytes"]}
        row.update(driver.readings(cell, seed, res, seed in control_seeds))
        del res
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
