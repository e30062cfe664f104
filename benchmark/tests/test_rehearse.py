"""The command end to end: the contract's last line at the rehearsal
sizes for each driver, and the ways it must refuse to run."""

import json
import os
import shutil

import numpy as np
import pytest

from benchmark.tests import helpers

TRAIN = "train_gpt2m_b8x1024"
SERVE = "serve_gpt2l_closed8_decode"


def bench():
    with open(os.path.join(helpers.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def names(kind, workload):
    return {m["name"] for m in bench()[kind]
            if workload in m.get("workloads", [workload])}


@pytest.mark.parametrize("workload", [TRAIN, SERVE])
def test_rehearsal_prints_the_contract_line(workload):
    rc, line, err = helpers.run_cli(
        ["--workload", workload, "--seed", "3000000019", "--seconds", "1",
         "--trace", "0", "--rehearse-cpu"])
    assert rc == 0, err[-2000:]
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == names("end_to_end", workload)
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    tail = err.strip().splitlines()[-1]
    assert tail == "[correct] True"
    for key, row in line["compared"].items():
        if key != "violations":
            assert f"[correct] {key}=" in err and "limit" in row


@pytest.mark.parametrize("workload", [TRAIN, SERVE])
def test_traced_rehearsal_reads_the_layers(workload):
    rc, line, err = helpers.run_cli(
        ["--workload", workload, "--seed", "7", "--seconds", "1.5",
         "--trace", "1", "--rehearse-cpu"])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True
    dev = line["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    # every metric reported is one of the cell's, none is 0; the shares of
    # a peak have nothing to read on the CPU and are left out, never 0
    assert set(line["metrics"]) <= names("per_layer", workload)
    assert not any("mfu" in k or "roofline" in k for k in line["metrics"])
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
    assert 1 <= len(line["breakdown"]["idle_gaps"]) <= 10


def test_no_chip_and_no_switch_exits_nonzero_before_compiling():
    rc, line, err = helpers.run_cli(
        ["--workload", TRAIN, "--seed", "1", "--seconds", "1", "--trace",
         "0"])
    assert rc != 0 and line is None
    assert "no accelerator" in err and "[setup]" not in err


def test_unknown_workload_exits_nonzero():
    rc, line, _ = helpers.run_cli(
        ["--workload", "no_such_cell", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearse-cpu"])
    assert rc != 0 and line is None


def test_benchmark_alone_in_a_directory_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(helpers.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(helpers.ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, line, _ = helpers.run_cli(
        ["--workload", TRAIN, "--seed", "1", "--seconds", "1", "--trace",
         "0", "--rehearse-cpu"], cwd=str(tmp_path),
        extra_env={"PYTHONPATH": ""})
    assert rc != 0 and line is None


def test_same_seed_same_inputs():
    from benchmark.lib import spec, traffic

    cell = spec.load_cell(SERVE)
    def first(tr, seed, n=16):
        reqs = traffic.requests(tr, 50257, seed)
        assert len(reqs) == n
        return [reqs[i] for i in range(n)]

    a = first(cell.traffic, 3000000019)
    b = first(cell.traffic, 3000000019)
    c = first(cell.traffic, 3000000020)
    assert all((x.prompt == y.prompt).all() and x.max_tokens == y.max_tokens
               for x, y in zip(a, b))
    # another seed: the same multiset of sizes in another order, with
    # other ids
    size = lambda r: (len(r.prompt), r.max_tokens)  # noqa: E731
    assert sorted(map(size, a)) == sorted(map(size, c))
    assert list(map(size, a)) != list(map(size, c))
    assert not any(np.array_equal(x.prompt, y.prompt) for x in a for y in c)
    # past the end of the list: the same sizes again, never the same
    # prompt (a repeated prompt would hit the prefix cache)
    again = traffic.requests(cell.traffic, 50257, 3000000019)[16]
    assert size(again) == size(a[0])
    assert not (again.prompt == a[0].prompt).all()
    assert min(len(r.prompt) for r in a) >= 32
    assert max(len(r.prompt) + r.max_tokens for r in a) <= 896
