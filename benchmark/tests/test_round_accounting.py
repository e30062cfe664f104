"""The per-layer metrics that price the worker's round and a slot's
vacancy from counters every run carries (PR 37): each is a data file
over the window's ``counters`` delta, listed for the serving cells that
open its spans and no other, and a traced rehearsal of the GPT-2
serving cell prints all ten."""

import json
import math
import os

import pytest

from benchmark.lib import spec
from benchmark.tests import helpers

SERVING = ("serve_gpt2l_closed8_decode", "serve_dsv2_ep8_closed16_decode",
           "serve_gpt2l_closed8_prefill", "serve_mellum2_closed16_mixed8k",
           "serve_kexaone_ep8_closed32_reason")
#: the families that mount prefixes: one unbounded kind of page
MOUNTING = SERVING[:3]
CELLS = {"host_round_ms": SERVING, "fetch_wait_ms": SERVING,
         "host_stage_ms": SERVING, "host_dispatch_ms": SERVING,
         "host_deliver_ms": SERVING, "host_admit_ms": SERVING,
         "prefix_host_ms": MOUNTING, "slot_vacant_mean_ms": SERVING,
         "slot_vacant_queued_share": SERVING,
         "round_unspanned_share": SERVING}


def metric_file(name):
    with open(os.path.join(helpers.ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_every_needed_key_is_a_counter_of_the_program(name):
    from benchmark.drivers import closed_loop
    from deeplearning4j_tpu.runtime.metrics import decode_metrics

    counters = {k for k, v in closed_loop.counters_now(decode_metrics).items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}
    data = metric_file(name)
    assert data["what"] and data["needs"]
    for key in data["needs"]:
        family, _, rest = key.partition(".")
        assert family == "counters" and rest in counters, key
        assert key.replace(".", "_") in data["value"], key


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_metric_is_listed_for_its_cells_and_no_other(name):
    entry = next(m for m in spec.benchmark()["per_layer"]
                 if m["name"] == name)
    assert entry["source"] == "program_span"
    assert entry["moves"] == "serve_tok_s"
    assert tuple(entry["workloads"]) == CELLS[name]
    for w in spec.benchmark()["workloads"]:
        listed = name in {m["name"]
                          for m in spec.load_cell(w["name"]).per_layer}
        assert listed == (w["name"] in CELLS[name]), w["name"]


def test_a_program_without_the_counters_reports_none_of_them():
    """The parent commit's table has none of the new keys: every reader
    returns nothing and raises nothing, so its line leaves them out."""
    old = {"counters": {"round_s": 1.0, "fetch_s": 0.5, "rounds": 10,
                        "prefill_sync_s": 0.1, "advance_s": 0.6,
                        "admissions": 3, "decode_dispatches": 10}}
    got = {name: spec.metric_reader(name)(old) for name in CELLS}
    # the two whose keys the parent has say the same thing there
    assert got.pop("host_round_ms") == pytest.approx(40.0)
    assert got.pop("fetch_wait_ms") == pytest.approx(50.0)
    assert set(got.values()) == {None}


def test_traced_rehearsal_prints_all_ten():
    rc, line, err = helpers.run_cli(
        ["--workload", SERVING[0], "--seed", "3000003701", "--seconds",
         "1.5", "--trace", "1", "--rehearse-cpu"])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True
    got = {k: line["metrics"][k]["value"] for k in CELLS}
    assert all(math.isfinite(v) for v in got.values()), got
    assert 0 <= got["round_unspanned_share"] < 50
    assert 0 <= got["slot_vacant_queued_share"] <= 100
    for name in ("host_round_ms", "host_stage_ms", "host_dispatch_ms",
                 "host_deliver_ms", "host_admit_ms", "prefix_host_ms",
                 "slot_vacant_mean_ms"):
        assert got[name] > 0, name
    assert got["fetch_wait_ms"] >= 0
    # decode.admit is opened up: its children are names a gap can take
    idle = [name for name, _ in line["breakdown"]["idle_gaps"]]
    assert any(name.startswith("decode.") for name in idle), idle
