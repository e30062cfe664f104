"""``BENCHMARK.json`` against the rules a driver refuses a file over
before any run: keys, names, lengths, where files lie, which cell
reports what."""

import json
import os
import re

from benchmark.lib import spec
from benchmark.tests import helpers

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|n_embd|"
                   r"n_inner|head|expan|experts_per")


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def load():
    path = os.path.join(helpers.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def test_top_level():
    b = load()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(b["paths"]) <= 16 and len(b["command"]) <= 32
    assert all(line(w) and not w.startswith("/") and ".." not in w
               for w in b["command"])
    assert any(w.startswith(tuple(p + "/" for p in b["paths"]))
               for w in b["command"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    cells = len(b["workloads"])
    # a full check at the full 24 cells has to fit into 43200 s
    full = (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert full <= 43200, full
    assert 1 <= cells <= 24


def test_configs_and_cells():
    b = load()
    names = [c["name"] for c in b["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["why"]) and line(c["source"])
        assert c["source"].startswith("https://")
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        assert len(c["reduced"]) <= 16
        assert not any(WIDTH.search(k) for k in c["reduced"])
        with open(os.path.join(helpers.ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        assert body["n_inner"] == 4 * body["n_embd"]
        assert body["n_embd"] % body["n_head"] == 0
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {w["config"] for w in b["workloads"]} == set(names)
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"])
        cell = spec.load_cell(w["name"])      # every file it names is there
        assert cell.limits and all(v > 0 for v in cell.limits.values())


def test_metrics():
    b = load()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert len(e2e) == len(b["end_to_end"]) <= 16 and "setup_s" in e2e
    assert "workloads" not in e2e["setup_s"]
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    layers = [m["name"] for m in b["per_layer"]]
    assert len(set(layers)) == len(layers) <= 128
    assert not set(layers) & set(e2e)
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and line(m["layer"])
        moved = e2e[m["moves"]]
        # each cell that reads it reports the end-to-end metric it moves
        for w in m.get("workloads", cells):
            assert w in moved.get("workloads", cells)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            # the whole step's share of the peak stands beside it
            assert any("mfu" in re.split(r"[._]", o["name"])
                       and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in b["per_layer"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in {"lower", "higher"}
        assert set(m.get("workloads", cells)) <= cells
    for w in cells:
        mine = [m["name"] for m in b["end_to_end"]
                if w in m.get("workloads", cells)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(w in m.get("workloads", cells) for m in b["per_layer"])
        # exactly the end-to-end metrics its per-layer metrics move
        moved = {m["moves"] for m in b["per_layer"]
                 if w in m.get("workloads", cells)}
        assert moved == set(mine) - {"setup_s"}


def test_files_under_paths_are_named_from_a_name():
    b = load()
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in b["paths"]:
        for d, dirs, files in os.walk(os.path.join(helpers.ROOT, path)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), helpers.ROOT)
                assert ok.match(rel) and len(rel) <= 200, rel
