"""What PR 32 added as data and as one family module: the Mellum 2
cell's rehearsal, its family's counts against hand sums, and the new
entries held to the rules ``test_contract.py`` states (that file's
``test_configs_and_cells`` stops at GPT-2's keys, and
``test_family.py::test_new_cells_are_appended_entries`` pins the last
cells to PR 28's: PERF.md, section 7)."""

import json
import os

import pytest

from benchmark.families import mellum as fam
from benchmark.lib import spec
from benchmark.tests import helpers
from benchmark.tests.test_contract import NAME, WIDTH, line

CELL = "serve_mellum2_closed16_mixed8k"
CONFIG = "mellum2-12b-a2.5b"
NEW_METRICS = ["win_attn_dev_ms", "full_attn_dev_ms",
               "window_rows_held_share"]
FILLED_FOR_A_FAMILY = [
    "slot_occupancy", "ttft_p50_ms", "ttft_p90_ms", "prefill_dev_ms",
    "decode_step_roofline", "serve_mfu", "device_idle.serve",
    "queue_wait_mean_ms", "join_stall_mean_ms", "loop_host_share",
    "moe_expert_hits_per_layer", "moe_dev_ms", "moe_experts_roofline"]


def bench():
    with open(os.path.join(helpers.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def names(kind, workload):
    return {m["name"] for m in bench()[kind]
            if workload in m.get("workloads", [workload])}


def test_rehearsal_prints_the_contract_line():
    rc, out, err = helpers.run_cli(
        ["--workload", CELL, "--seed", "3000000019", "--seconds", "1",
         "--trace", "0", "--rehearse-cpu"])
    assert rc == 0, err[-2000:]
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "compared"
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == names("end_to_end", CELL) == {
        "serve_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert err.strip().splitlines()[-1] == "[correct] True"


def test_traced_rehearsal_reads_the_kinds_of_page():
    cell = spec.load_cell(CELL, rehearse=True)
    # the rehearsal's window is shorter than its longest prompt
    assert cell.traffic["config"]["sliding_window"] == 16 \
        < cell.traffic["prompt_len"]["max"]
    rc, out, err = helpers.run_cli(
        ["--workload", CELL, "--seed", "7", "--seconds", "1.5", "--trace",
         "1", "--rehearse-cpu"])
    assert rc == 0, err[-2000:]
    assert out["correct"] is True
    assert set(out["metrics"]) <= names("per_layer", CELL)
    # prompts of up to 88 tokens on a window of 16: a window layer holds
    # a share of what a full one does
    assert 0 < out["metrics"]["window_rows_held_share"]["value"] < 100
    # 8 experts a layer, 2 of a token's
    assert 0 < out["metrics"]["moe_expert_hits_per_layer"]["value"] <= 8
    # the CPU trace has no op_names and no peaks: the scope metrics and
    # the shares are left out, never 0
    assert not {"moe_dev_ms", "win_attn_dev_ms", "full_attn_dev_ms",
                "moe_experts_roofline", "serve_mfu",
                "decode_step_roofline"} & set(out["metrics"])


def test_counts_against_hand_sums():
    c = spec.load_cell(CELL).config
    # W_q 2304x4096 + W_k, W_v 2x2304x512 + W_o 4096x2304
    assert fam.attention_params(c) == 21233664
    assert fam.expert_params(c) == 3 * 2304 * 896 == 6193152
    assert fam.expert_bytes(c) == 12386304                # 12.39 MB
    # + router 2304x64 + gains 2x2304 + 2x128, + 64 experts
    assert fam.layer_params(c) == 21385984 + 396361728 == 417747712
    # twelve layers + embedding + untied head (+ the final gain): the
    # issue's arithmetic to the unit, 10.93 GB at 2 bytes
    assert fam.total_params(c) == 5465959680
    assert fam.total_params(c) == 12 * 417747712 + 452984832 + 2304
    assert round(2 * fam.total_params(c) / 1e7) == 1093
    assert fam.cache_bytes_row(c) == 2048
    # the default pool at 16 slots, pages of 128, ladder to 8,192: the
    # full kind 3 layers x 16 x 64 pages (+ trash), the window kind 9
    # layers x 16 x 9 pages (+ trash)
    pool = fam.pages_bytes(c, (16 * 64 + 1, 16 * 9 + 1), 128)
    assert pool == 128 * 2048 * (3 * 1025 + 9 * 145)
    assert round(128 * 2048 * 3 * 1024 / 1e6) == 805      # 0.805 GB
    assert round(128 * 2048 * 9 * 144 / 1e6) == 340       # 0.340 GB
    nonrouted = 12 * (21233664 + 147456) + 2304 * 98304
    assert fam.nonrouted_params(c) == nonrouted


def test_decode_needed_prices_a_window_layer_at_its_window():
    c = spec.load_cell(CELL).config
    per_row = 4.0 * 32 * 128
    # 10 tokens of 300 rows each: inside the window, 12 layers alike
    short = fam.decode_needed(c, contexts_sum=3000.0, n_tokens=10,
                              dispatches=2, expert_hits=9,
                              assignments_held=12)
    nonrouted = fam.nonrouted_params(c)
    assert short["bytes"] == (2 * nonrouted * 2 + 9 * 12386304
                              + 12 * 3000 * 2048)
    assert short["flops"] == (2.0 * nonrouted * 10 + 2.0 * 12 * 6193152
                              + per_row * 12 * 3000.0)
    assert short["expert_bytes"] == 9 * 12386304 + 2.0 * 12 * 2304 * 4.0
    # 10 tokens of 5,000 rows: min(context, 1024) rows on 9 of 12 layers
    long = fam.decode_needed(c, contexts_sum=50000.0, n_tokens=10,
                             dispatches=2, expert_hits=9,
                             assignments_held=12)
    rows = 3 * 50000 + 9 * 1024 * 10
    assert long["bytes"] == (2 * nonrouted * 2 + 9 * 12386304
                             + rows * 2048)
    assert long["flops"] == (2.0 * nonrouted * 10 + 2.0 * 12 * 6193152
                             + per_row * rows)
    # a token's own context, exactly
    at = fam.forward_flops_token(c, 5000.0, 8.0, True)
    assert at == (2.0 * nonrouted + 2.0 * 12 * 8 * 6193152
                  + per_row * (3 * 5000 + 9 * 1024))
    assert fam.forward_flops_token(c, 300.0, 8.0, True) == (
        2.0 * nonrouted + 2.0 * 12 * 8 * 6193152 + per_row * 12 * 300)
    # a prompt of 2,000: rows 1..2000 on a full layer, 1..1024 then 1024
    # each on a window layer
    full = 2000 * 2001 / 2
    window = 1024 * 1025 / 2 + 976 * 1024
    assert fam.sequence_forward_flops(c, 2000, 8.0) == pytest.approx(
        2000 * (2.0 * nonrouted + 2.0 * 12 * 8 * 6193152)
        + per_row * (3 * full + 9 * window))


def test_program_config_is_the_files_cut():
    cell = spec.load_cell(CELL)
    cfg = fam.program_config(cell.config)
    assert (cfg.n_layers, cfg.n_periods, cfg.vocab_size, cfg.max_len,
            cfg.sliding_window) == (12, 3, 98304, 8192, 1024)
    assert (cfg.hidden, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok) == (2304, 32, 4, 128, 896, 64, 8)
    assert cfg.family == "mellum" and cfg.kv_width == 512
    assert fam.engine_kwargs(cell.config, cell.traffic) == {
        "prefill_chunk": 128, "buckets": [1024, 2048, 4096, 8192]}
    from deeplearning4j_tpu.models import mellum as ml
    assert fam.param_shapes(cell.config) == ml.param_shapes(cfg)
    assert ml.pages_bytes(cfg, (1025, 145), 128) == fam.pages_bytes(
        cell.config, (1025, 145), 128)
    with pytest.raises(ValueError, match="whole"):
        fam.program_config({**cell.config, "layers": 10})


def test_the_reference_keeps_a_rows_last_positions():
    tail = fam.Tail(70, __import__("numpy").arange(30)[:, None])
    assert tail[75:78].ravel().tolist() == [5, 6, 7]
    assert len(tail[70:100]) == 30
    with pytest.raises(IndexError):
        tail[69:80]


def test_the_published_widths_are_untouched():
    """Every number of the catalog's ``config`` stands in the file under
    its own key, but the keys ``reduced`` lists (the depth under the
    catalog's ``layers``)."""
    published = {
        "head_dim": 128, "hidden_size": 2304, "intermediate_size": 7168,
        "max_window_layers": 0, "moe_intermediate_size": 896,
        "num_attention_heads": 32, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 28,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "sliding_window": 1024, "vocab_size": 98304,
        "attention_bias": False, "norm_topk_prob": True,
        "tie_word_embeddings": False, "use_sliding_window": True,
        "hidden_act": "silu", "model_type": "mellum"}
    c = spec.load_cell(CELL).config
    assert {k: c[k] for k in published} == published
    assert c["layer_types"] == (["sliding_attention"] * 3
                                + ["full_attention"]) * 7
    assert c["mlp_layer_types"] == ["sparse"] * 28
    assert c["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}}
    assert c["reduced"] == ["layers", "max_position_embeddings"]
    assert (c["layers"], c["max_position_embeddings"]) == (12, 8192)
    assert c["published"] == {"layers": 28,
                              "max_position_embeddings": 131072}
    assert set(c["reduced_how"]) == set(c["reduced"])
    assert {"weights", "qk_norm", "rope_layout", "mtp"} <= set(c["assumed"])
    assert c["deployment"] and c["family"] == "mellum" \
        and c["reference"] == "mellum2"


def test_the_new_entries_keep_the_contracts_rules():
    b = bench()
    entry = b["configs"][-1]
    assert entry["name"] == CONFIG
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and line(entry["why"]) \
        and line(entry["source"])
    assert entry["source"].startswith("https://")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert not any(WIDTH.search(k) for k in entry["reduced"])
    with open(os.path.join(helpers.ROOT, entry["file"])) as f:
        body = json.load(f)
    assert body["source"] == entry["source"]
    assert body["reduced"] == entry["reduced"]
    cell = b["workloads"][-1]
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "closed16_mixed_8k", "chips": 1,
                    "why": cell["why"]}
    assert line(cell["why"]) and "12 of 28 layers" in cell["why"]
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    loaded = spec.load_cell(CELL)
    assert loaded.limits and all(v > 0 for v in loaded.limits.values())
    assert set(loaded.limits) == {"served_gap_mean"}
    # what the cell reports, and what every metric that lists it moves
    assert names("end_to_end", CELL) == {"serve_tok_s", "setup_s"}
    assert names("per_layer", CELL) == set(FILLED_FOR_A_FAMILY
                                           + NEW_METRICS)
    for m in b["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] == "serve_tok_s", m["name"]
    new = b["per_layer"][-3:]
    assert [m["name"] for m in new] == NEW_METRICS
    assert all(m["workloads"] == [CELL] for m in new)
    # the shares of a roofline and of the step's peak list it together
    for name in ("serve_mfu", "decode_step_roofline",
                 "moe_experts_roofline"):
        m = next(m for m in b["per_layer"] if m["name"] == name)
        assert m["workloads"][-1] == CELL


def test_the_traffic_is_the_issues():
    tr = spec.load_cell(CELL).traffic
    assert tr["driver"] == "closed_loop_family"
    assert (tr["callers"], tr["n_slots"], tr["n_shapes"],
            tr["check_requests"]) == (16, 16, 16, 6)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                "sigma": 1.0, "min": 128, "max": 7168}
    assert tr["output_len"] == {"dist": "lognormal", "median": 192,
                                "sigma": 0.5, "min": 48, "max": 640}
    assert tr["buckets"] == [1024, 2048, 4096, 8192]
    assert (tr["prefill_chunk"], tr["stagger_seconds"], tr["ramp_seconds"],
            tr["temperature"], tr["prefix_hits"]) == (128, 4.0, 8.0, 0.0,
                                                      "none")
    assert tr["scopes"] == ["window_attention", "full_attention",
                            "moe_route", "moe_experts"]
    # every request fits the longest rung
    assert tr["prompt_len"]["max"] + tr["output_len"]["max"] <= 8192
