"""DeepSeek-V2 (models/deepseek_v2.py) against the plain reference
(benchmark/reference/deepseek_v2.py) at small widths on the CPU, and
through the serving spine: the latent page pool, one expert-parallel
rank's routed layer, ``DecodeEngine`` by family.

Tolerances.  With float32 weights and ``compute_dtype="float32"`` the
program and the reference do the same arithmetic in another order
(folded products, fused scans), and the CPU backend's float32 products
are exact to rounding: logits of magnitude ~5 agree to 2e-4.  In
bfloat16 (the type the cell runs) every product's operands are rounded
to 8 bits, 2^-9 relative a rounding, through 3 layers of ~10 products:
2e-2 of the logits' largest magnitude at most positions, and a chosen
expert may differ where two scores tie to rounding; the float32
comparisons have no such room to hide in.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import deepseek_v2 as ref  # noqa: E402
from deeplearning4j_tpu.models import deepseek_v2 as ds  # noqa: E402
from deeplearning4j_tpu.parallel import expert  # noqa: E402
from deeplearning4j_tpu.runtime.metrics import decode_metrics  # noqa: E402
from deeplearning4j_tpu.serving.decode import (ContinuousBatcher,  # noqa: E402
                                               DecodeEngine, model_family)

F32_TOL = 2e-4


def published_keys(cfg):
    """The reference reads a dict with the published key names."""
    return {"hidden_size": cfg.hidden, "num_attention_heads": cfg.n_heads,
            "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "n_group": cfg.n_group,
            "topk_group": cfg.topk_group,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "rope_scaling": {
                "factor": cfg.rope_factor, "beta_fast": cfg.rope_beta_fast,
                "beta_slow": cfg.rope_beta_slow, "mscale": cfg.rope_mscale,
                "mscale_all_dim": cfg.rope_mscale_all_dim,
                "original_max_position_embeddings":
                    cfg.rope_original_max_len}}


def model(dtype="float32", seed=0, **over):
    cfg = ds.tiny_config(compute_dtype=dtype, **over)
    return cfg, ds.init_params(jax.random.key(seed), cfg, std=0.3)


def reference_logits(cfg, params, ids):
    return np.asarray(ref.logits(params, jnp.asarray(ids),
                                 config=published_keys(cfg),
                                 held=cfg.held_experts))


def some_ids(cfg, shape, seed=1):
    return np.asarray(jax.random.randint(jax.random.key(seed), shape, 0,
                                         cfg.vocab_size), np.int32)


def test_full_forward_matches_reference():
    cfg, params = model(held_experts=(4, 8))
    ids = some_ids(cfg, (2, 32))
    got = np.asarray(ds.forward_logits(cfg, params, jnp.asarray(ids)))
    want = reference_logits(cfg, params, ids)
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() <= F32_TOL * np.abs(want).max()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bfloat16_forward_is_the_reference_to_rounding(seed):
    """The type the cell runs in.  A position's error is rounding (the
    median over positions: 2e-2 of the largest logit, see the module's
    docstring) unless two router scores tie to rounding and another
    expert is taken there, which moves that position by an expert's
    whole output: such positions are few (under a tenth here) and are
    not held to the rounding bound."""
    cfg, params = model("bfloat16", seed=seed, held_experts=(4, 8))
    ids = some_ids(cfg, (2, 32))
    got = np.asarray(ds.forward_logits(cfg, params, jnp.asarray(ids)))
    want = reference_logits(cfg, params, ids)
    err = np.abs(got - want).max(axis=-1) / np.abs(want).max()
    assert np.median(err) <= 2e-2
    assert np.mean(err > 5e-2) < 0.1


def test_folded_and_expanded_attention_agree():
    cfg, params = model()
    ids = jnp.asarray(some_ids(cfg, (2, 24)))
    a = np.asarray(ds.forward_logits(cfg, params, ids))
    b = np.asarray(ds.forward_logits(cfg, params, ids, folded=True))
    assert np.abs(a - b).max() <= F32_TOL * np.abs(a).max()


def paged_logits(cfg, params, row, n_prompt, C=8):
    """Logits at every position of ``row`` as the serving path computes
    them: the prompt in chunks of C through ``_paged_stack`` as
    ``paged_prefill`` calls it, then a token a step as ``paged_decode``
    does, the sequence in slot 1 of 3 with scattered pages."""
    S, TBL = 3, -(-len(row) // C)
    pool = ds.init_pages(cfg, 1 + S * TBL, C)
    ptab = np.zeros((S, TBL), np.int32)
    ptab[1] = 1 + np.random.default_rng(0).permutation(S * TBL)[:TBL]
    out = []
    at = np.arange(C, dtype=np.int32)
    for lo in range(0, n_prompt, C):
        n_valid = min(C, n_prompt - lo)
        chunk = np.zeros((C,), np.int32)
        chunk[:n_valid] = row[lo:lo + n_valid]
        pool, x, _ = ds._paged_stack(cfg, params, pool, ptab[1][None],
                                     chunk[None], (lo + at)[None],
                                     (at < n_valid)[None])
        out.append(np.asarray(ds._readout(cfg, params, x[0, :n_valid])))
    active = np.array([False, True, False])
    for t in range(n_prompt, len(row)):
        toks = np.array([0, row[t], 0], np.int32)[:, None]
        pos = np.array([0, t, 0], np.int32)[:, None]
        pool, x, counts = ds._paged_stack(cfg, params, pool, ptab, toks,
                                          pos, active[:, None])
        out.append(np.asarray(ds._readout(cfg, params, x[1])))
    return np.concatenate(out), np.asarray(counts), pool


def test_chunked_prefill_then_decode_matches_reference_logits():
    cfg, params = model(held_experts=(0, 4))
    row = some_ids(cfg, (30,), seed=3)
    got, counts, pool = paged_logits(cfg, params, row, n_prompt=19)
    want = reference_logits(cfg, params, np.pad(row, (0, 2))[None])[0, :30]
    assert np.abs(got - want).max() <= F32_TOL * np.abs(want).max()
    # one active slot, two expert layers, three experts a token: the
    # idle slots were routed nowhere
    assert counts[0] == 2 * cfg.num_experts_per_tok and counts[3] == 2
    assert 0 <= counts[2] <= counts[1] <= counts[0]
    # nothing but the sequence's pages (and the trash page) was written
    assert pool.rows.shape[-1] == cfg.cache_width == 20


def test_pages_written_are_the_pages_read():
    """The family's page read and write (what a prefix store would move)
    and the pool's sizes; a latent pool has no int8 form."""
    cfg, _ = model()
    pool = ds.init_pages(cfg, n_pages=6, page_tokens=4)
    assert pool.rows.shape == (3, 6, 4, 20)
    assert ds.pages_bytes(cfg, 6, 4) == pool.rows.size * 4
    pids = jnp.asarray([4, 2, 0], jnp.int32)
    pages = jax.random.normal(jax.random.key(2), (3, 3, 4, 20), jnp.float32)
    pool = ds.paged_write_pages(cfg, pool, pids, pages)
    (back,) = ds.paged_read_pages(cfg, pool, pids)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(pages))
    assert not np.asarray(pool.rows[:, [1, 3, 5]]).any()
    assert ds.paged_specs(cfg).rows == jax.sharding.PartitionSpec()
    with pytest.raises(ValueError, match="int8"):
        ds.init_pages(cfg, 6, 4, kv_dtype="int8")


def test_group_limited_routing_by_hand():
    # 8 experts in 4 groups of 2; top 2 groups, top 3 experts
    scores = jnp.asarray([[0.30, 0.02,   # group 0: best 0.30
                           0.20, 0.18,   # group 1: best 0.20
                           0.19, 0.01,   # group 2: best 0.19 -> dropped
                           0.05, 0.05]], jnp.float32)
    w, chosen = expert.route_group_limited(scores, 4, 2, 3, 16.0)
    # 0.19 is the third-largest score of all, but its group is third:
    # not taken; 0.18 of group 1 is
    assert np.asarray(chosen)[0].tolist() == [True, False, True, True,
                                              False, False, False, False]
    np.testing.assert_allclose(
        np.asarray(w)[0], [4.8, 0, 3.2, 2.88, 0, 0, 0, 0], rtol=1e-6)
    # the reference's routing, written another way, takes the same
    c = {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 3,
         "routed_scaling_factor": 16.0}
    np.testing.assert_allclose(np.asarray(ref.route(c, scores)),
                               np.asarray(w), rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    cfg, params = model()
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.key(5), (12, cfg.hidden), jnp.float32)
    whole, counts = ds._ffn(cfg, layer, x, None)
    shared = expert.gated_ffn(x, **layer["moe"]["shared"])
    parts = jnp.zeros_like(whole)
    held_total = 0
    per_rank = cfg.n_routed_experts // cfg.n_group
    for rank in range(cfg.n_group):
        c_r, p_r = ds.hold_experts(cfg, params, rank * per_rank, per_rank)
        y, c = ds.moe_routed(c_r, p_r["layers"][1]["moe"], x)
        parts = parts + y
        held_total += int(c[1])
        assert int(c[0]) == 12 * cfg.num_experts_per_tok
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    # every assignment fell on exactly one rank, and no token reached
    # more than topk_group ranks
    assert held_total == 12 * cfg.num_experts_per_tok == int(counts[1])
    # against the reference given every expert
    want = ref.moe(published_keys(cfg), layer["moe"], x, (0, 16), "f32")
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_engine_takes_the_family_from_the_config():
    from deeplearning4j_tpu.models import gpt

    cfg, _ = model()
    assert model_family(cfg) is ds
    assert model_family(gpt.gpt_tiny()) is gpt


def greedy_reference(cfg, params, prompt, n):
    row = list(prompt)
    for _ in range(n):
        ids = np.zeros((1, 64), np.int32)
        ids[0, :len(row)] = row
        logits = reference_logits(cfg, params, ids)[0, len(row) - 1]
        row.append(int(np.argmax(logits)))
    return row[len(prompt):]


def test_continuous_batcher_serves_it_and_returns_every_page():
    cfg, params = model(held_experts=(0, 8))
    eng = DecodeEngine(cfg, params, n_slots=3, buckets=(16, 32, 64),
                       prefill_chunk=8)
    assert eng.kv_bytes_per_slot == 3 * 64 * 20 * 4
    eng.warmup()
    before = decode_metrics.snapshot()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 21, 9, 30, 13, 3, 17)]
    budgets = [6, 9, 4, 12, 7, 10, 5]
    with ContinuousBatcher(eng) as batcher:
        # seven requests on three slots: they join and leave mid-decode
        handles = [batcher.submit(p, max_tokens=m, temperature=0.0,
                                  eos_id=None)
                   for p, m in zip(prompts, budgets)]
        outs = [h.result(timeout=120.0) for h in handles]
    after = decode_metrics.snapshot()
    for p, m, out in zip(prompts[:3], budgets, outs):
        assert out.tolist() == greedy_reference(cfg, params, p, m)
    assert [len(o) for o in outs] == budgets
    # what is left are the prompts' whole pages kept pool-resident for a
    # later request with the same prefix (the page table's business, not
    # the family's): every other page came back
    assert eng.pages_unaccounted() == 0
    eng.drop_residents()
    assert eng._alloc.in_use() == 0 and eng.pages_unaccounted() == 0
    assert after["compile_delta_since_mark"] == 0
    made = after["moe_assignments"] - before["moe_assignments"]
    layers = after["moe_layer_dispatches"] - before["moe_layer_dispatches"]
    steps = after["decode_dispatches"] - before["decode_dispatches"]
    assert layers == 2 * steps and made % cfg.num_experts_per_tok == 0
    held = after["moe_assignments_held"] - before["moe_assignments_held"]
    hits = after["moe_expert_hits"] - before["moe_expert_hits"]
    assert 0 < hits <= held < made


def test_three_rungs_share_a_dispatch_and_its_expert_counts():
    """Requests of three rungs in the engine's one slot table: every
    dispatch carries all three, its expert layers route the three rows
    together (the counts behind the tokens say so), and each stream is
    the plain reference's."""
    cfg, params = model(held_experts=(0, 8))
    eng = DecodeEngine(cfg, params, n_slots=3, buckets=(16, 32, 64),
                       prefill_chunk=8)
    eng.warmup()
    rng = np.random.default_rng(3)
    n = 6
    prompts = [rng.integers(0, cfg.vocab_size, t).astype(np.int32)
               for t in (4, 20, 45)]
    assert [eng.pick_bucket(p.size + n) for p in prompts] == [16, 32, 64]
    placed = [eng.start(p, max_tokens=n) for p in prompts]
    outs = [[first] for _, first in placed]
    keys = ("decode_dispatches", "decode_dispatch_rungs",
            "decode_table_rows", "moe_assignments", "moe_layer_dispatches")
    for _ in range(n - 1):
        before = decode_metrics.snapshot()
        toks = eng.advance()
        after = decode_metrics.snapshot()
        # one dispatch, three rungs in it, as wide as the longest needs;
        # both expert layers saw all three rows
        assert [after[k] - before[k] for k in keys] == [
            1, 3, 3 * 64, 3 * 2 * cfg.num_experts_per_tok, 2]
        for out, (slot, _) in zip(outs, placed):
            out.append(int(toks[slot]))
    for slot, _ in placed:
        eng.release(slot)
    for p, out in zip(prompts, outs):
        assert out == greedy_reference(cfg, params, p, n)
    eng.drop_residents()
    assert eng._alloc.in_use() + eng.pages_unaccounted() == 0


@pytest.mark.parametrize("option", [
    {"paged": False}, {"kv_dtype": "int8"}, {"quantize": "int8"},
    {"prefix_cache": True}, {"mesh": "a mesh"},
    {"draft": ("a config", "a tree")}])
def test_unsupported_engine_options_raise(option):
    cfg, params = model()
    kwargs = {"buckets": (16,), "prefill_chunk": 8, **option}
    # the pinned engine is no family's to refuse: the engine itself
    # says it was removed
    match = "removed in PR 30" if "paged" in option else "deepseek_v2"
    with pytest.raises(ValueError, match=match):
        DecodeEngine(cfg, params, n_slots=2, **kwargs)


def test_config_rejects_a_share_outside_the_experts():
    with pytest.raises(ValueError, match="held_experts"):
        ds.tiny_config(held_experts=(12, 8))
    cfg = ds.tiny_config()
    assert dataclasses.replace(cfg, held_experts=(12, 4)).held_experts == (
        12, 4)
