"""What PR 35 added as data, one family module and one reference: the
K-EXAONE cell's rehearsal with and without a trace, its family's counts
against the issue's arithmetic, the new entries held to the rules
``test_contract.py`` states, and every file the benchmark had before
left as it was (``data/accepted_digests_pr33.json``)."""

import hashlib
import json
import os

import pytest

from benchmark.families import exaone_moe as fam
from benchmark.lib import spec
from benchmark.tests import helpers
from benchmark.tests.test_contract import NAME, WIDTH, line

CELL = "serve_kexaone_ep8_closed32_reason"
CELL_C = "serve_mellum2_closed16_mixed8k"
CONFIG = "k-exaone-236b-a23b-ep8"
NEW_METRICS = ["mtp_accept_rate", "mtp_dev_ms", "shared_expert_dev_ms"]


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


def test_traced_rehearsal_reads_the_speculative_round():
    cell = spec.load_cell(CELL, rehearse=True)
    assert cell.traffic["draft"] == "self" and cell.traffic["draft_k"] == 1
    # the rehearsal's window is shorter than its prompts, its ring 2 pages
    assert cell.traffic["config"]["sliding_window"] == 8 \
        == cell.traffic["prefill_chunk"] < cell.traffic["prompt_len"]["max"]
    rc, out, err = helpers.run_cli(
        ["--workload", CELL, "--seed", "7", "--seconds", "1.5", "--trace",
         "1", "--rehearse-cpu"])
    assert rc == 0, err[-2000:]
    assert out["correct"] is True
    assert set(out["metrics"]) <= names("per_layer", CELL)
    # a draft a running slot a round, accepted at chance or a little over
    assert 0 <= out["metrics"]["mtp_accept_rate"]["value"] < 50
    assert 0 < out["metrics"]["window_rows_held_share"]["value"] < 100
    # 4 experts held of 16, 4 of a token's: the counts are of the main
    # stack's layers, both rows of a slot
    assert 0 < out["metrics"]["moe_expert_hits_per_layer"]["value"] <= 4
    # the round is the one dispatch a round makes: the program the
    # traffic file names ran (the spans' idle gaps name it on the CPU)
    assert any("spec_fn" in name for name, _ in
               out["breakdown"]["idle_gaps"] + out["breakdown"]["device_ops"]
               ) or out["device"]["busy_s"] > 0
    # the CPU trace has no op_names and no peaks: the scope metrics and
    # the shares are left out, never 0
    assert not {"moe_dev_ms", "win_attn_dev_ms", "full_attn_dev_ms",
                "mtp_dev_ms", "shared_expert_dev_ms",
                "moe_experts_roofline", "serve_mfu",
                "decode_step_roofline"} & set(out["metrics"])


def test_one_dispatch_a_round_on_the_rehearsal():
    """``decode_dispatches / rounds`` of the window: 1 but for the rounds
    that only admitted (the cell does not list ``dispatches_per_round``:
    cell C does not)."""
    res = helpers.execute(CELL, seed=11, seconds=1.0)
    assert res["correct"] is True
    from deeplearning4j_tpu.runtime.metrics import decode_metrics
    snap = decode_metrics.snapshot()
    assert 0 < snap["decode_dispatches"] <= snap["rounds"]
    assert snap["draft_proposed"] > 0
    assert snap["moe_layer_dispatches"] == 4 * snap["decode_dispatches"]


def test_counts_against_the_issues_arithmetic():
    c = spec.load_cell(CELL).config
    # W_q 6144x8192 + W_o 8192x6144 + W_k, W_v 2x6144x1024: 113.25 M
    assert fam.attention_params(c) == 2 * 6144 * 8192 + 2 * 6144 * 1024 \
        == 113246208
    assert fam.expert_params(c) == 3 * 6144 * 2048 == 37748736
    assert fam.expert_bytes(c) == 75497472                # 75.50 MB
    # + router 6144x128 (0.79 M) + its bias + gains + shared + 16 experts
    assert fam.sparse_layer_params(c) == (
        113246208 + 786432 + 128 + 2 * 6144 + 2 * 128 + 37748736
        + 16 * 37748736)
    assert round(fam.sparse_layer_params(c) / 1e5) == 7558      # 755.8 M
    assert fam.dense_layer_params(c) == 113246208 + 3 * 6144 * 18432 \
        + 2 * 6144 + 2 * 128
    assert round(fam.dense_layer_params(c) / 1e5) == 4530       # 453.0 M
    assert round(fam.mtp_params(c) / 1e5) == 8313               # 831.3 M
    assert fam.mtp_params(c) == fam.sparse_layer_params(c) \
        + 2 * 6144 * 6144 + 3 * 6144
    # 453.0 + 4 x 755.8 + 235.9 + 831.3 = 4,543 M parameters, 9.09 GB
    total = fam.total_params(c)
    assert total == (fam.dense_layer_params(c)
                     + 4 * fam.sparse_layer_params(c) + fam.mtp_params(c)
                     + 2 * 19200 * 6144 + 6144)
    assert round(total / 1e6) == 4543
    assert round(2 * total / 1e7) == 909                        # 9.09 GB
    assert fam.cache_bytes_row(c) == 4096
    # the default pool at 32 slots, pages of 128, ladder to 4,096: the
    # full kind 2 layers (layer 3 and the MTP block's) x 32 x 32 pages (+
    # trash), the window kind 4 layers x 32 x 2 pages (+ trash)
    pool = fam.pages_bytes(c, (32 * 32 + 1, 32 * 2 + 1), 128)
    assert pool == 128 * 4096 * (2 * 1025 + 4 * 65)
    assert round(128 * 4096 * 2 * 1024 / 1e7) == 107            # 1.07 GB
    assert round(128 * 4096 * 4 * 64 / 1e7) == 13               # 0.13 GB
    # every token: five layers' attention, the dense feed-forward, four
    # routers and shared experts, the head; the MTP block is no part
    nonrouted = (5 * 113246208 + 3 * 6144 * 18432
                 + 4 * (6144 * 128 + 37748736) + 6144 * 19200)
    assert fam.nonrouted_params(c) == nonrouted
    # two whole periods would not fit beside a pool: 13.6 GB
    eight = (total + 3 * fam.sparse_layer_params(c)) * 2
    assert round(eight / 1e8) == 136


def test_decode_needed_is_the_main_models_for_committed_tokens():
    c = spec.load_cell(CELL).config
    per_row = 4.0 * 64 * 128
    nonrouted = fam.nonrouted_params(c)
    # 10 tokens of 100 rows each: inside the window, 5 layers alike
    short = fam.decode_needed(c, contexts_sum=1000.0, n_tokens=10,
                              dispatches=2, expert_hits=9,
                              assignments_held=50)
    assert short["bytes"] == (2 * nonrouted * 2 + 9 * 75497472
                              + 5 * 1000 * 4096)
    # each of 4 expert layers at the rank's share of a token's 8: 16/128
    assert short["flops"] == (2.0 * nonrouted * 10
                              + 2.0 * 4 * 1.0 * 10 * 37748736
                              + per_row * 5 * 1000.0)
    assert short["expert_bytes"] == 9 * 75497472 + 2.0 * 50 * 6144 * 4.0
    # 10 tokens of 3,000 rows: min(context, 128) rows on 4 of 5 layers
    long = fam.decode_needed(c, contexts_sum=30000.0, n_tokens=10,
                             dispatches=2, expert_hits=9,
                             assignments_held=50)
    rows = 1 * 30000 + 4 * 128 * 10
    assert long["bytes"] == 2 * nonrouted * 2 + 9 * 75497472 + rows * 4096
    # the MTP block's weights and cache are in none of it: a share of a
    # roofline can only read lower for the draft
    assert long["bytes"] < 2 * 2 * (fam.total_params(c) - 19200 * 6144) \
        + rows * 4096
    at = fam.forward_flops_token(c, 3000.0, 1.0, True)
    assert at == (2.0 * nonrouted + 2.0 * 4 * 37748736
                  + per_row * (3000 + 4 * 128))
    full = 500 * 501 / 2
    window = 128 * 129 / 2 + 372 * 128
    assert fam.sequence_forward_flops(c, 500, 1.0) == pytest.approx(
        500 * (2.0 * nonrouted + 2.0 * 4 * 37748736)
        + per_row * (full + 4 * window))


def test_program_config_is_the_files_cut():
    cell = spec.load_cell(CELL)
    cfg = fam.program_config(cell.config)
    assert (cfg.n_layers, cfg.vocab_size, cfg.max_len, cfg.sliding_window,
            cfg.n_mtp) == (5, 19200, 4096, 128, 1)
    assert (cfg.hidden, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.num_experts, cfg.num_experts_per_tok, cfg.held_experts,
            cfg.routed_scaling_factor, cfg.rope_theta) == (
                6144, 64, 8, 128, 18432, 2048, 128, 8, (0, 16), 2.5, 1e6)
    assert cfg.family == "exaone_moe" and cfg.kv_width == 1024
    assert [cfg.kind_of(l) for l in range(5)] \
        == cell.config["layer_types"][:5]
    assert fam.engine_kwargs(cell.config, cell.traffic) == {
        "prefill_chunk": 128, "buckets": [512, 1024, 2048, 4096],
        "draft": "self", "draft_k": 1}
    from deeplearning4j_tpu.models import exaone_moe as ex
    assert fam.param_shapes(cell.config) == ex.param_shapes(cfg)
    assert ex.pages_bytes(cfg, (1025, 65), 128) == fam.pages_bytes(
        cell.config, (1025, 65), 128)
    assert ex.page_kinds(cfg, 128) == (("full", None), ("window", 2, 1))
    with pytest.raises(ValueError, match="period"):
        fam.program_config({**cell.config, "layer_types":
                            ["full_attention"] * 48})
    with pytest.raises(ValueError, match="no code for"):
        fam.program_config({**cell.config, "scoring_func": "softmax"})


def test_the_published_widths_are_untouched():
    """Every number of the catalog's ``config`` stands in the file under
    its own key, but the keys ``reduced`` lists (the depth under the
    catalog's ``layers``); nested groups are copied whole."""
    published = {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 6144, "intermediate_size": 18432,
        "model_type": "exaone_moe", "moe_intermediate_size": 2048,
        "n_group": 1, "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 8, "num_nextn_predict_layers": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "sliding_window": 128, "sliding_window_pattern": "LLLG",
        "tie_word_embeddings": False, "topk_group": 1}
    c = spec.load_cell(CELL).config
    assert {k: c[k] for k in published} == published
    assert c["layer_types"] == (["sliding_attention"] * 3
                                + ["full_attention"]) * 12
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert c["sliding_windows"] == [128, 128, 128, 0] * 12
    assert c["mtp_layer_types"] == ["full_attention"] \
        and c["mtp_sliding_windows"] == [0]
    assert c["rope_parameters"] == {"rope_theta": 1000000,
                                    "rope_type": "default"}
    assert c["reduced"] == ["layers", "num_experts", "vocab_size",
                            "max_position_embeddings"]
    assert (c["layers"], c["num_experts"], c["vocab_size"],
            c["max_position_embeddings"], c["router_width"],
            c["held_experts_first"]) == (5, 16, 19200, 4096, 128, 0)
    assert c["published"] == {"layers": 48, "num_experts": 128,
                              "vocab_size": 153600,
                              "max_position_embeddings": 262144}
    assert set(c["reduced_how"]) == set(c["reduced"])
    assert {"weights", "qk_norm", "nope_on_full_layers", "post_norm",
            "router", "router_bias", "mtp", "rope_layout"} <= set(
                c["assumed"])
    assert "modeling_exaone4.py" in c["assumed"]["qk_norm"]
    assert "modeling_deepseek_v3.py" in c["assumed"]["router"]
    assert c["deployment"] and c["family"] == "exaone_moe" \
        and c["reference"] == "exaone_moe"
    # the floors of a cut: a whole period and four layers behind the
    # dense one, 8 experts or more, an eighth of the vocabulary
    assert c["layers"] - c["first_k_dense_replace"] >= 4
    assert c["num_experts"] >= 8 and c["vocab_size"] * 8 >= 153600


def test_the_new_entries_are_appended_and_keep_the_contracts_rules():
    b = bench()
    entry = b["configs"][-1]
    assert entry["name"] == CONFIG and len(b["configs"]) == 5
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and line(entry["why"]) \
        and line(entry["source"])
    assert entry["source"] == ("https://huggingface.co/LGAI-EXAONE/"
                               "K-EXAONE-236B-A23B/blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert not any(WIDTH.search(k) for k in entry["reduced"])
    with open(os.path.join(helpers.ROOT, entry["file"])) as f:
        body = json.load(f)
    assert body["source"] == entry["source"]
    assert body["reduced"] == entry["reduced"]
    cell = b["workloads"][-1]
    assert len(b["workloads"]) == 6
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "closed32_reason_4k", "chips": 1,
                    "why": cell["why"]}
    assert line(cell["why"]) and "5 of 48 layers" in cell["why"]
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert all(w["chips"] == 1 for w in b["workloads"])
    loaded = spec.load_cell(CELL)
    assert set(loaded.limits) == {"served_gap_mean"}
    assert 0 < loaded.limits["served_gap_mean"] < 1
    # what the cell reports: cell C's metrics (their expressions read
    # the table, not a family) and the three new ones
    assert names("end_to_end", CELL) == {"serve_tok_s", "setup_s"}
    assert names("per_layer", CELL) == names("per_layer", CELL_C) | set(
        NEW_METRICS)
    for m in b["end_to_end"] + b["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL
            assert m["workloads"].count(CELL) == 1
            if m in b["per_layer"]:
                assert m["moves"] == "serve_tok_s", m["name"]
    new = b["per_layer"][-3:]
    assert [m["name"] for m in new] == NEW_METRICS
    assert all(m["workloads"] == [CELL] for m in new)
    assert [m["layer"] for m in new] == [
        "decode step (gpt.paged_decode)",
        "multi-token prediction (exaone_moe MTP block)",
        "expert layer (deepseek_v2.moe_routed)"]
    assert [(m["unit"], m["better"], m["source"]) for m in new] == [
        ("%", "higher", "program_counter"), ("ms", "lower", "device_trace"),
        ("ms", "lower", "device_trace")]
    # the shares of a roofline and of the step's peak list it
    for name in ("serve_mfu", "decode_step_roofline",
                 "moe_experts_roofline"):
        m = next(m for m in b["per_layer"] if m["name"] == name)
        assert m["workloads"][-1] == CELL
    assert os.path.getsize(os.path.join(helpers.ROOT,
                                        "BENCHMARK.json")) < 64 * 1024


def test_no_file_the_benchmark_had_was_edited_and_no_entry_moved():
    data = os.path.join(helpers.ROOT, "benchmark", "tests", "data")
    with open(os.path.join(data, "accepted_digests_pr33.json")) as f:
        accepted = json.load(f)["files"]
    assert len(accepted) > 80
    for rel, want in accepted.items():
        with open(os.path.join(helpers.ROOT, rel), "rb") as fh:
            assert hashlib.sha256(fh.read()).hexdigest() == want, rel
    here = set()
    for d, _, files in os.walk(os.path.join(helpers.ROOT, "benchmark")):
        if "__pycache__" not in d:
            here |= {os.path.relpath(os.path.join(d, f), helpers.ROOT)
                     for f in files if not f.endswith(".pyc")}
    assert here - set(accepted) == {
        f"benchmark/configs/{CONFIG}.json",
        "benchmark/families/exaone_moe.py",
        "benchmark/reference/exaone_moe.py",
        "benchmark/traffic/closed32_reason_4k.json",
        f"benchmark/limits/{CELL}.json",
        "benchmark/metrics/mtp_accept_rate.json",
        "benchmark/metrics/mtp_dev_ms.json",
        "benchmark/metrics/shared_expert_dev_ms.json",
        "benchmark/tests/test_family_exaone.py",
        "benchmark/tests/data/accepted_digests_pr33.json"}
    # the accepted entries stand where they stood, each as it was but for
    # the new cell's name at the end of a ``workloads`` list
    b = bench()
    assert [c["name"] for c in b["configs"]][:4] == [
        "gpt2-medium", "gpt2-large", "deepseek-v2-ep8", "mellum2-12b-a2.5b"]
    assert [w["name"] for w in b["workloads"]][:5] == [
        "train_gpt2m_b8x1024", "serve_gpt2l_closed8_decode",
        "serve_dsv2_ep8_closed16_decode", "serve_gpt2l_closed8_prefill",
        CELL_C]
    assert b["run_seconds"] == 51 and b["paths"] == ["benchmark"]
    assert {m["name"]: m["bound"] for m in b["end_to_end"]} == {
        "train_tok_s": 0.01, "serve_tok_s": 0.1, "itl_p50_ms": 0.01,
        "setup_s": 0.1}
    assert len(b["per_layer"]) == 25 + 3
    for m in b["per_layer"][:25]:
        assert CELL not in m.get("workloads", [])[:-1]


def test_the_traffic_is_the_issues():
    tr = spec.load_cell(CELL).traffic
    base = spec.load_cell(CELL_C).traffic
    assert tr["driver"] == "closed_loop_family"
    assert (tr["callers"], tr["n_slots"], tr["n_shapes"],
            tr["check_requests"]) == (32, 32, 16, 6)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 256,
                                "sigma": 1.0, "min": 64, "max": 2048}
    assert tr["output_len"] == {"dist": "lognormal", "median": 512,
                                "sigma": 0.6, "min": 128, "max": 2048}
    assert tr["buckets"] == [512, 1024, 2048, 4096]
    assert (tr["prefill_chunk"], tr["temperature"], tr["prefix_hits"],
            tr["draft"], tr["draft_k"]) == (128, 0.0, "none", "self", 1)
    assert "n_pages" not in tr                      # the default pool
    # stagger, ramp, drain, trace and check parameters as cell C's
    for key in ("stagger_seconds", "ramp_seconds", "drain_seconds",
                "trace_at_seconds", "trace_seconds", "trace_most_seconds",
                "check_requests", "reference_rows_per_block",
                "reference_pad", "reference_q_block"):
        assert tr[key] == base[key], key
    # the program a round dispatches, and the scopes read in it: the MTP
    # block first, so that what lies under it is counted there alone
    assert tr["programs"] == {"prefill": "^jit_prefill_fn",
                              "decode": "^jit_spec_fn"}
    assert tr["scopes"] == ["mtp_block", "window_attention",
                            "full_attention", "moe_route", "moe_experts",
                            "shared_expert"]
    # every request fits the longest rung
    assert tr["prompt_len"]["max"] + tr["output_len"]["max"] <= 4096
