"""The per-layer metrics that read the program's own spans and counters
(``telemetry.span(..., counter=)`` -> ``decode_metrics`` -> the window's
``counters`` delta): each is a data file over the driver's table, and
the traced rehearsal of the serving cell prints them all."""

import json
import math
import os

import pytest

from benchmark.lib import spec
from benchmark.tests import helpers

SERVE = "serve_gpt2l_closed8_decode"
METRICS = ("dispatches_per_round", "queue_wait_mean_ms",
           "join_stall_mean_ms", "dispatch_overhead_ms", "loop_host_share")


def needs(name):
    with open(os.path.join(helpers.ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        return json.load(f)["needs"]


def test_traced_rehearsal_prints_the_program_span_metrics():
    rc, line, err = helpers.run_cli(
        ["--workload", SERVE, "--seed", "3000000023", "--seconds", "1.5",
         "--trace", "1", "--rehearse-cpu"])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True
    got = {k: line["metrics"][k]["value"] for k in METRICS}
    assert all(math.isfinite(v) for v in got.values()), got
    assert got["dispatches_per_round"] >= 1
    assert 0 <= got["loop_host_share"] <= 100
    assert got["queue_wait_mean_ms"] > 0 and got["join_stall_mean_ms"] > 0
    # the loop names its own idle time beside PJRT's
    idle = [name for name, _ in line["breakdown"]["idle_gaps"]]
    assert any(name.startswith("decode.") for name in idle), idle


@pytest.mark.parametrize("name", METRICS)
def test_every_needed_key_is_one_the_drivers_table_has(name):
    from benchmark.drivers import closed_loop
    from deeplearning4j_tpu.runtime.metrics import decode_metrics

    cell = spec.load_cell(SERVE)
    assert name in {m["name"] for m in cell.per_layer}
    counters = {k for k, v in closed_loop.counters_now(decode_metrics).items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}
    programs = {f"programs.{alias}.{key}"
                for alias in cell.traffic["programs"]
                for key in ("count", "seconds")}
    for key in needs(name):
        family, _, rest = key.partition(".")
        assert (rest in counters if family == "counters"
                else key in programs), key
