"""A later PR adds a configuration, a traffic mix, a cell and an
expression-type per-layer metric as NEW files and entries only.  Shown
on a temporary copy: ``closed16_prefill`` (the first Open question of
PERF.md) and its cell, rehearsed on the CPU, with no file of the
benchmark edited."""

import hashlib
import json
import os
import shutil

from benchmark.tests import helpers


def digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_is_files_and_entries_only(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(helpers.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(helpers.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(root)
    bdir = os.path.join(root, "benchmark")

    # a configuration: its file of sizes (here a copy under a new name)
    with open(os.path.join(bdir, "configs", "gpt2-large.json")) as f:
        config = json.load(f)
    config["name"] = "gpt2-large-b"
    with open(os.path.join(bdir, "configs", "gpt2-large-b.json"), "w") as f:
        json.dump(config, f)
    # a traffic mix: a data file for the one general generator
    with open(os.path.join(bdir, "traffic", "closed8_decode.json")) as f:
        mix = json.load(f)
    mix.update({"callers": 16, "n_slots": 16,
                "prompt_len": {"dist": "uniform", "min": 512, "max": 960,
                               "step": 32},
                "output_len": {"dist": "uniform", "min": 8, "max": 32}})
    mix["rehearse"].update(
        {"prompt_len": {"dist": "uniform", "min": 32, "max": 96, "step": 8},
         "output_len": {"dist": "uniform", "min": 2, "max": 6}})
    with open(os.path.join(bdir, "traffic", "closed16_prefill.json"),
              "w") as f:
        json.dump(mix, f)
    # the cell's limits, and an expression-type per-layer metric
    cell = "serve_gpt2l_closed16_prefill"
    shutil.copy(os.path.join(bdir, "limits",
                             "serve_gpt2l_closed8_decode.json"),
                os.path.join(bdir, "limits", cell + ".json"))
    with open(os.path.join(bdir, "metrics", "prefill_share.json"), "w") as f:
        json.dump({"needs": ["programs.prefill.seconds", "trace.busy_s"],
                   "value": "100.0 * programs_prefill_seconds / trace_busy_s"
                   }, f)
    # entries
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "gpt2-large-b", "source": config["source"],
         "file": "benchmark/configs/gpt2-large-b.json", "reduced": [],
         "why": "stands for a new configuration"})
    bench["workloads"].append(
        {"name": cell, "config": "gpt2-large-b",
         "traffic": "closed16_prefill", "chips": 1,
         "why": "long prompts in, short answers out"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "serve_gpt2l_closed8_decode" in m.get("workloads", []):
            m["workloads"].append(cell)
    bench["per_layer"].append(
        {"name": "prefill_share", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "prefill step (gpt.paged_prefill)",
         "moves": "serve_tok_s", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    env = {"PYTHONPATH": helpers.ROOT}
    args = ["--workload", cell, "--seed", "5", "--seconds", "1.5",
            "--rehearse-cpu"]
    rc, line, err = helpers.run_cli(args + ["--trace", "0"], cwd=root,
                                    extra_env=env)
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["attempted"] > 0
    assert {"serve_tok_s", "itl_p50_ms", "setup_s"} == set(line["metrics"])
    rc, line, err = helpers.run_cli(args + ["--trace", "1"], cwd=root,
                                    extra_env=env)
    assert rc == 0, err[-2000:]
    assert line["correct"] is True
    assert 0 < line["metrics"]["prefill_share"]["value"] <= 100.0
    assert {"prefill_dev_ms", "ttft_p90_ms", "itl_p99_ms"} <= set(
        line["metrics"])

    after = digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "benchmark/configs/gpt2-large-b.json",
        f"benchmark/limits/{cell}.json",
        "benchmark/metrics/prefill_share.json",
        "benchmark/traffic/closed16_prefill.json"]
