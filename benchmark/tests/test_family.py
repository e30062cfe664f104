"""The ``closed_loop_family`` driver and what PR 28 added as data: the
DeepSeek-V2 cell's rehearsal, its family's counts against hand sums,
the scope reader, and the two cells added as files and entries with no
accepted file edited."""

import hashlib
import json
import os

import pytest

from benchmark.families import deepseek_v2 as fam
from benchmark.lib import scope_reduce, spec
from benchmark.tests import helpers

DSV2 = "serve_dsv2_ep8_closed16_decode"
PREFILL = "serve_gpt2l_closed8_prefill"


def bench():
    with open(os.path.join(helpers.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def names(kind, workload):
    return {m["name"] for m in bench()[kind]
            if workload in m.get("workloads", [workload])}


@pytest.mark.parametrize("workload", [DSV2, PREFILL])
def test_rehearsal_prints_the_contract_line(workload):
    rc, line, err = helpers.run_cli(
        ["--workload", workload, "--seed", "3000000019", "--seconds", "1",
         "--trace", "0", "--rehearse-cpu"])
    assert rc == 0, err[-2000:]
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == names("end_to_end", workload)
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert err.strip().splitlines()[-1] == "[correct] True"


def test_traced_rehearsal_reads_the_expert_counters():
    rc, line, err = helpers.run_cli(
        ["--workload", DSV2, "--seed", "7", "--seconds", "1.5", "--trace",
         "1", "--rehearse-cpu"])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True
    assert set(line["metrics"]) <= names("per_layer", DSV2)
    # 4 experts held, 3 of a token's experts from 2 of 4 groups
    assert 0 < line["metrics"]["moe_expert_hits_per_layer"]["value"] <= 4
    # the CPU trace has no op_names and no peaks: the scope metrics and
    # the shares are left out, never 0
    assert not {"moe_dev_ms", "mla_attn_dev_ms", "moe_experts_roofline",
                "serve_mfu", "decode_step_roofline"} & set(line["metrics"])


def test_counts_against_hand_sums():
    c = spec.load_cell(DSV2).config
    # attention: 5120x1536 + 1536x128x192 + 5120x576 + 512x128x256
    # + 128x128x5120
    assert fam.attention_params(c) == (7864320 + 37748736 + 2949120
                                       + 16777216 + 83886080)
    assert fam.expert_params(c) == 3 * 5120 * 1536 == 23592960
    assert fam.expert_bytes(c) == 47185920               # 47.19 MB
    assert fam.cache_bytes_row(c) == 7 * 1152
    # outside the routed experts: 7 attentions, one dense feed-forward,
    # 6 routers and shared-expert pairs, the head's slice
    nonrouted = (7 * 149225472 + 3 * 5120 * 12288
                 + 6 * (5120 * 160 + 3 * 5120 * 3072) + 5120 * 12800)
    assert fam.nonrouted_params(c) == nonrouted
    # the issue's arithmetic: 4,484 M parameters, 8.97 GB at 2 bytes
    total = fam.total_params(c)
    assert round(total / 1e6) == 4484 and round(2 * total / 1e7) == 897
    need = fam.decode_needed(c, contexts_sum=1000.0, n_tokens=10,
                             dispatches=2, expert_hits=9,
                             assignments_held=12)
    assert need["bytes"] == (2 * nonrouted * 2 + 9 * 47185920
                             + 1000 * 7 * 1152)
    assert need["flops"] == (2.0 * nonrouted * 10 + 2.0 * 12 * 23592960
                             + 7 * 128 * (2 * 576 + 2 * 512) * 1000.0)
    # a prompt token expands its own row (in the 2 a parameter) and
    # attends as heads do: 2 x 192 + 2 x 128 a head a position
    assert fam.attention_flops_per_position(c, False) == 7 * 128 * 640
    assert fam.sequence_forward_flops(c, 2, 0.75) == pytest.approx(
        2 * (2.0 * nonrouted + 2.0 * 6 * 0.75 * 23592960)
        + 7 * 128 * 640 * 3)


def test_program_config_is_the_files_cut():
    cell = spec.load_cell(DSV2)
    cfg = fam.program_config(cell.config)
    assert (cfg.n_layers, cfg.held_experts, cfg.n_routed_experts,
            cfg.vocab_size, cfg.max_len) == (7, (0, 20), 160, 12800, 2048)
    assert cfg.cache_width == 576 and cfg.hidden == 5120
    assert fam.engine_kwargs(cell.config, cell.traffic) == {
        "paged": True, "buckets": [256, 512, 1024, 2048]}
    shapes = fam.param_shapes(cell.config)
    from deeplearning4j_tpu.models import deepseek_v2 as ds
    assert shapes == ds.param_shapes(cfg)


def test_scope_map_joins_instructions_to_scopes():
    scopes = ["mla_attention", "moe_route", "moe_experts"]
    assert scope_reduce.scope_of(
        "jit(decode_fn)/jit(main)/moe_experts/while/body/dot_general",
        scopes) == "moe_experts"
    assert scope_reduce.scope_of("jit(f)/mla_attention/page_read/gather",
                                 scopes) == "mla_attention"
    assert scope_reduce.scope_of("jit(f)/moe_experts_other/dot",
                                 scopes) is None
    rung_a = """
  %fusion.9 = bf16[16,5120]{1,0:T(8,128)(2,1)} fusion(%p.1, %p.2), kind=kLoop, calls=%fc.1, metadata={op_name="jit(decode_fn)/moe_experts/while/body/dot_general" stack_frame_id=3}
  ROOT %while.3 = (s32[]{:T(128)}, bf16[16,5120]{1,0}) while(%tuple.1), condition=%c, body=%b, metadata={op_name="jit(decode_fn)/moe_experts/while"}
  %gather.2 = bf16[16,256,576]{2,1,0} gather(%pool, %idx), metadata={op_name="jit(decode_fn)/mla_attention/page_read/gather"}
  %copy.4 = bf16[8]{0} copy(%p.3)
"""
    rung_b = rung_a.replace("bf16[16,256,576]", "bf16[16,512,576]").replace(
        'moe_experts/while/body/dot_general', 'moe_route/dot_general')
    tags = scope_reduce.scope_map([rung_a, rung_b], scopes)
    # the trace's event names: the instruction with its operands' types,
    # no metadata
    event = ("%gather.2 = bf16[16,512,576]{2,1,0:T(8,128)(2,1)} "
             "gather(bf16[7,1025,32,576]{3,2,1,0} %pool, s32[16,16,2] %idx)")
    assert tags[scope_reduce.instruction_key(event)] == "mla_attention"
    assert tags[("%while.3", "(s32[], bf16[16,5120])")] == "moe_experts"
    assert tags[("%copy.4", "bf16[8]")] == ""
    # two programs gave one name and type to ops of two scopes: neither
    assert tags[("%fusion.9", "bf16[16,5120]")] == ""
    assert scope_reduce.scope_map([], scopes) == {}


def test_no_accepted_file_was_edited():
    with open(os.path.join(helpers.ROOT, "benchmark", "tests", "data",
                           "accepted_digests.json")) as f:
        accepted = json.load(f)["files"]
    for rel, want in accepted.items():
        with open(os.path.join(helpers.ROOT, rel), "rb") as fh:
            assert hashlib.sha256(fh.read()).hexdigest() == want, rel


def test_new_cells_are_appended_entries():
    b = bench()
    assert [w["name"] for w in b["workloads"]][-2:] == [DSV2, PREFILL]
    assert b["configs"][-1]["name"] == "deepseek-v2-ep8"
    assert all(w["chips"] == 1 for w in b["workloads"])
    cell = spec.load_cell(PREFILL)
    assert cell.traffic["driver"] == "closed_loop"
    assert cell.traffic["prompt_len"] == {"dist": "uniform", "min": 512,
                                          "max": 960, "step": 32}
    assert cell.traffic["output_len"] == {"dist": "uniform", "min": 8,
                                          "max": 32}
    base = spec.load_cell("serve_gpt2l_closed8_decode").traffic
    same = set(base) - {"what", "prompt_len", "output_len", "rehearse"}
    assert all(cell.traffic[k] == base[k] for k in same)


def test_configs_and_cells_without_the_gpt_keys():
    """``test_contract.test_configs_and_cells`` with its two assertions
    on GPT-2's own keys (``n_inner``, ``n_embd``/``n_head``) left out:
    that test stops at them on a configuration of another family, and
    no PR that adds a cell may edit it (PERF.md, section 7)."""
    from benchmark.tests.test_contract import NAME, WIDTH, line, load

    b = load()
    names = [c["name"] for c in b["configs"]]
    files = [c["file"] for c in b["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
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
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {w["config"] for w in b["workloads"]} == set(names)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 4)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"])
        cell = spec.load_cell(w["name"])
        assert cell.limits and all(v > 0 for v in cell.limits.values())


def test_the_published_widths_are_untouched():
    """Every number of the catalog's ``config`` stands in the file under
    its own key, but the keys ``reduced`` lists (the depth under the
    catalog's ``layers``)."""
    published = {
        "first_k_dense_replace": 1, "hidden_size": 5120,
        "intermediate_size": 12288, "kv_lora_rank": 512,
        "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8,
        "n_shared_experts": 2, "num_attention_heads": 128,
        "num_experts_per_tok": 6, "num_hidden_layers": 60,
        "num_key_value_heads": 128, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "routed_scaling_factor": 16, "topk_group": 3, "v_head_dim": 128}
    c = spec.load_cell(DSV2).config
    assert {k: c[k] for k in published} == published
    assert c["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert c["reduced"] == ["layers", "n_routed_experts", "vocab_size",
                            "max_position_embeddings"]
    assert (c["layers"], c["n_routed_experts"], c["vocab_size"],
            c["max_position_embeddings"]) == (7, 20, 12800, 2048)
    assert c["published"] == {"layers": 60, "n_routed_experts": 160,
                              "vocab_size": 102400,
                              "max_position_embeddings": 163840}
