"""What PR 39 added: the Ling-3.0-flash cell's rehearsal with and without
a trace, its family's counts against the issue's arithmetic (the
parameters, the slot state, the constants of ``kda_roofline``), the
configuration against the catalog's published numbers, the new entries
held to the rules ``test_contract.py`` states, and every file the
benchmark had before left as it was
(``data/accepted_digests_pr38.json``)."""

import hashlib
import json
import os

import numpy as np

from benchmark.families import ling_hybrid as fam
from benchmark.lib import spec
from benchmark.tests import helpers
from benchmark.tests.test_contract import NAME, WIDTH, line

CELL = "serve_ling3_ep4_closed32_long16k"
CELL_D = "serve_kexaone_ep8_closed32_reason"
CONFIG = "ling-3.0-flash-ep4"
NEW_METRICS = ["kda_dev_ms", "kda_roofline"]
#: the catalog's ``config`` numbers (architectures.jsonl, Ling-3.0-flash)
#: that no cut may touch
PUBLISHED = {
    "first_k_dense_replace": 2, "head_dim": 128, "hidden_size": 2560,
    "intermediate_size": 6144, "kda_lower_bound": -5, "kv_lora_rank": 512,
    "layer_group_size": 6, "moe_intermediate_size": 768,
    "moe_shared_expert_intermediate_size": 768, "n_group": 8,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 42, "num_key_value_heads": 32,
    "num_kv_heads_for_linear_attn": 0, "num_shared_experts": 1,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 6000000,
    "routed_scaling_factor": 2.5, "rotary_dim": 64,
    "short_conv_kernel_size": 4, "topk_group": 4, "v_head_dim": 128,
    "partial_rotary_factor": 0.5, "group_norm_size": 1,
    "max_window_layers": 20, "mtp_loss_scaling_factor": 0}


def bench():
    with open(os.path.join(helpers.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config():
    return spec.load_cell(CELL).config


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
    assert out["correct"] is True and out["failed"] == 0, out["compared"]
    assert set(out["metrics"]) == names("end_to_end", CELL) == {
        "serve_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert err.strip().splitlines()[-1] == "[correct] True"


def test_traced_rehearsal_reads_the_cells_layers():
    rc, out, err = helpers.run_cli(
        ["--workload", CELL, "--seed", "7", "--seconds", "1.5", "--trace",
         "1", "--rehearse-cpu"])
    assert rc == 0, err[-2000:]
    assert out["correct"] is True, out["compared"]
    assert set(out["metrics"]) <= names("per_layer", CELL)
    # 4 experts held of 16, 4 of a token's
    assert 0 < out["metrics"]["moe_expert_hits_per_layer"]["value"] <= 4
    assert 0 < out["metrics"]["slot_occupancy"]["value"] <= 100
    # the CPU trace has no op_names and no peaks: the scope metrics and
    # the shares are left out, never 0
    assert not {"kda_dev_ms", "kda_roofline", "mla_attn_dev_ms",
                "moe_dev_ms", "shared_expert_dev_ms", "serve_mfu",
                "decode_step_roofline"} & set(out["metrics"])


def test_the_counts_are_the_issues_arithmetic():
    c = config()
    s = fam.sizes(c)
    assert (s["L"], s["L_kda"], s["L_mla"], s["dense"], s["sparse"]) == (
        7, 6, 1, 1, 6)
    assert fam.kda_attention_params(c) == 63_049_888          # 63.05 M
    assert fam.mla_attention_params(c) == 31_965_696          # 31.97 M
    assert fam.expert_params(c) == 5_898_240                  # 5.90 M
    assert [round(fam.layer_params(c, i) / 1e6, 2) for i in c["layers"]] \
        == [110.24] + [825.24] * 5 + [794.16]
    assert round(fam.total_params(c) / 1e6, 1) == 5231.8      # 10.46 GB
    assert round(2 * fam.total_params(c) / 1e9, 2) == 10.46
    # a slot's KDA state: S [32, 128, 128] float32 and three conv rows of
    # 3 x 4096 bfloat16, six layers
    assert fam.kda_state_bytes(c) == 6 * (32 * 128 * 128 * 4
                                          + 3 * 12288 * 2) == 13_025_280
    assert fam.cache_bytes_row(c) == 1152
    # the program's own count of the slot it holds agrees
    from deeplearning4j_tpu.models import ling_hybrid as lh
    assert lh.state_bytes_per_slot(fam.program_config(c)) \
        == fam.kda_state_bytes(c)
    # the latent read and the state's round trip of a 16k step, 32 slots
    assert round(32 * 16384 * fam.cache_bytes_row(c) / 1e9, 2) == 0.60
    assert round(32 * fam.kda_step_bytes(c) / 1e9, 2) == 0.83


def test_the_kda_roofline_constant_is_the_familys():
    with open(os.path.join(helpers.ROOT, "benchmark", "metrics",
                           "kda_roofline.json")) as f:
        m = json.load(f)
    c = config()
    step = fam.kda_matrix_step_bytes(c)
    assert step == 6 * 2 * 2_097_152
    assert f"traced_counters_slot_steps * {step}" in m["value"]
    table = {"traced_counters": {"slot_steps": 320, "decode_dispatches": 10},
             "scopes": {"dispatches": 8, "seconds": {"state_update": 0.02,
                                                     "kda_attention": 0.01}},
             "peaks": {"hbm_bytes_per_s": 819e9}}
    want = 100.0 * 320 * step * 8 / 10 / 819e9 / 0.02
    assert np.isclose(spec.metric_reader("kda_roofline")(table), want)
    assert np.isclose(spec.metric_reader("kda_dev_ms")(table), 1e3 * 0.03 / 8)
    table["scopes"]["seconds"] = {}
    assert spec.metric_reader("kda_roofline")(table) is None
    assert spec.metric_reader("kda_dev_ms")(table) is None


def test_the_published_numbers_are_untouched():
    c = config()
    assert {k: c[k] for k in PUBLISHED} == PUBLISHED
    assert c["expert_swiglu_limit_list"] == [0] * 35 + [4] * 7
    assert c["share_expert_swiglu_limit_list"] == [0] * 34 + [5] * 6 + [7, 7]
    assert c["reduced"] == ["layers", "num_experts", "vocab_size",
                            "max_position_embeddings",
                            "num_nextn_predict_layers"]
    assert (c["layers"], c["num_experts"], c["vocab_size"],
            c["max_position_embeddings"], c["num_nextn_predict_layers"]) \
        == ([0, 36, 37, 38, 39, 40, 41], 128, 39296, 16384, 0)
    assert c["published"] == {"layers": 42, "num_experts": 512,
                              "vocab_size": 157184,
                              "max_position_embeddings": 262144,
                              "num_nextn_predict_layers": 1}
    assert set(c["reduced_how"]) == set(c["reduced"])
    assert not any(WIDTH.search(k) for k in c["reduced"])
    # the kept period's limits are the ones the program is given
    cfg = fam.program_config(c)
    assert cfg.expert_swiglu_limits == (0.0,) + (4.0,) * 6
    assert cfg.shared_swiglu_limits == (0.0, 5.0, 5.0, 5.0, 5.0, 7.0, 7.0)
    assert (cfg.num_experts, cfg.held_experts) == (512, (0, 128))


def test_the_new_entries_are_appended_and_keep_the_contracts_rules():
    b = bench()
    assert b["configs"][-1]["name"] == CONFIG
    cfg = b["configs"][-1]
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and line(cfg["why"])
    assert cfg["source"] == config()["source"]
    assert b["workloads"][-1]["name"] == CELL
    w = b["workloads"][-1]
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "closed32_long_16k", 1)
    assert line(w["why"]) and "four times its share" in w["why"]
    assert len(b["workloads"]) == 7
    assert sum(x["chips"] == 4 for x in b["workloads"]) == 0
    new = b["per_layer"][-2:]
    assert [m["name"] for m in new] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
               for m in new)
    assert [m["layer"] for m in new] == ["linear attention (ling KDA)",
                                         "kernels"]
    assert [(m["unit"], m["better"], m["source"]) for m in new] == [
        ("ms", "lower", "device_trace"), ("%", "higher", "device_trace")]
    for m in b["per_layer"][:-2] + b["end_to_end"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL
            assert m["workloads"].count(CELL) == 1
    for name in ("serve_mfu", "decode_step_roofline",
                 "moe_experts_roofline", "mla_attn_dev_ms",
                 "shared_expert_dev_ms", "host_round_ms"):
        m = next(m for m in b["per_layer"] if m["name"] == name)
        assert m["workloads"][-1] == CELL
    assert CELL not in next(m for m in b["per_layer"]
                            if m["name"] == "prefix_host_ms")["workloads"]
    assert os.path.getsize(os.path.join(helpers.ROOT,
                                        "BENCHMARK.json")) < 64 * 1024


def test_no_file_the_benchmark_had_was_edited():
    data = os.path.join(helpers.ROOT, "benchmark", "tests", "data")
    with open(os.path.join(data, "accepted_digests_pr38.json")) as f:
        accepted = json.load(f)["files"]
    assert len(accepted) > 100
    for rel, want in accepted.items():
        with open(os.path.join(helpers.ROOT, rel), "rb") as fh:
            assert hashlib.sha256(fh.read()).hexdigest() == want, rel
    b = bench()
    assert b["run_seconds"] == 51 and b["paths"] == ["benchmark"]
    assert {m["name"]: m["bound"] for m in b["end_to_end"]} == {
        "train_tok_s": 0.01, "serve_tok_s": 0.1, "itl_p50_ms": 0.01,
        "setup_s": 0.1}
    assert [w["name"] for w in b["workloads"]][:6] == [
        "train_gpt2m_b8x1024", "serve_gpt2l_closed8_decode",
        "serve_dsv2_ep8_closed16_decode", "serve_gpt2l_closed8_prefill",
        "serve_mellum2_closed16_mixed8k", CELL_D]


def test_the_traffic_is_the_issues():
    tr = spec.load_cell(CELL).traffic
    base = spec.load_cell(CELL_D).traffic
    assert tr["driver"] == "closed_loop_family"
    assert (tr["callers"], tr["n_slots"], tr["n_shapes"],
            tr["check_requests"]) == (32, 32, 16, 6)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                "sigma": 1.0, "min": 256, "max": 12288}
    assert tr["output_len"] == {"dist": "lognormal", "median": 1024,
                                "sigma": 0.6, "min": 256, "max": 4096}
    assert tr["buckets"] == [2048, 4096, 8192, 16384]
    assert (tr["prefill_chunk"], tr["temperature"], tr["prefix_hits"]) == (
        128, 0.0, "none")
    assert "draft" not in tr and "n_pages" not in tr
    for key in ("stagger_seconds", "ramp_seconds", "drain_seconds",
                "trace_at_seconds", "trace_seconds", "trace_most_seconds"):
        assert tr[key] == base[key], key
    assert tr["programs"] == {"prefill": "^jit_prefill_fn",
                              "decode": "^jit_decode_fn"}
    # the state update first, so that what lies under it is counted there
    assert tr["scopes"] == ["state_update", "kda_attention", "mla_attention",
                            "moe_route", "moe_experts", "shared_expert"]
    # every request fits the longest rung
    assert tr["prompt_len"]["max"] + tr["output_len"]["max"] <= 16384
