"""K-EXAONE (models/exaone_moe.py) against the plain reference
(benchmark/reference/exaone_moe.py) at small widths on the CPU, and
through the serving spine: sigmoid routing with a selection bias over a
rank's share of the experts plus a shared expert, a leading dense layer,
a 2-page window ring beside the full table, and the model's own MTP
block as the draft of a speculative round in ``DecodeEngine``.

Tolerance.  With float32 weights and ``compute_dtype="float32"`` the
program and the reference do the same arithmetic in another order, and
the CPU backend's float32 products are exact to rounding: logits of
magnitude ~5 agree to 2e-4 of their largest, as in
``tests/test_mellum.py``.
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

from benchmark.reference import exaone_moe as ref  # noqa: E402
from deeplearning4j_tpu.models import exaone_moe as ex  # noqa: E402
from deeplearning4j_tpu.models import mellum as ml  # noqa: E402
from deeplearning4j_tpu.parallel import expert  # noqa: E402
from deeplearning4j_tpu.runtime import telemetry  # noqa: E402
from deeplearning4j_tpu.runtime.metrics import decode_metrics  # noqa: E402
from deeplearning4j_tpu.serving.decode import (ContinuousBatcher,  # noqa: E402
                                               DecodeEngine, model_family)

F32_TOL = 2e-4
WINDOW = 8           # tiny_config's
C = 8                # page and prefill chunk of the engines here
RING = 2             # ceil((WINDOW - 1) / C) + 1


def published_keys(cfg):
    """The reference reads a dict with the published key names."""
    return {"hidden_size": cfg.hidden, "layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "intermediate_size": cfg.intermediate_size,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "sliding_window": cfg.sliding_window,
            "layer_types": [cfg.kind_of(l) for l in range(cfg.n_layers)],
            "mtp_layer_types": ["full_attention"],
            "rms_norm_eps": cfg.rms_norm_eps,
            "rope_parameters": {"rope_type": "default",
                                "rope_theta": cfg.rope_theta}}


def model(seed=0, **over):
    cfg = ex.tiny_config(compute_dtype="float32", **over)
    return cfg, ex.init_params(jax.random.key(seed), cfg, std=0.3)


def reference_logits(cfg, params, ids):
    """Rows padded to whole blocks of 16 (causal: never attended)."""
    ids = np.atleast_2d(ids)
    n = ids.shape[1]
    padded = np.pad(ids, ((0, 0), (0, -n % 16)))
    return np.asarray(ref.logits(
        params, jnp.asarray(padded), config=published_keys(cfg),
        held=cfg.held_experts, q_block=16))[:, :n]


def reference_drafts(cfg, params, ids):
    """Entry i: the MTP block's logits over the token at i + 2."""
    ids = np.atleast_2d(ids)
    n = ids.shape[1]
    padded = np.pad(ids, ((0, 0), (0, -n % 16)))
    return np.asarray(ref.draft_logits(
        params, jnp.asarray(padded), config=published_keys(cfg),
        held=cfg.held_experts, q_block=16))[:, :n - 1]


def some_ids(cfg, shape, seed=1):
    return np.asarray(jax.random.randint(jax.random.key(seed), shape, 0,
                                         cfg.vocab_size), np.int32)


def close(got, want):
    return np.abs(got - want).max() <= F32_TOL * np.abs(want).max()


# ---------------------------------------------------------------------------
# The mathematics, no cache
# ---------------------------------------------------------------------------

def test_full_forward_matches_reference():
    cfg, params = model()
    ids = some_ids(cfg, (2, 40))                # five windows long
    got = np.asarray(ex.forward_logits(cfg, params, jnp.asarray(ids)))
    want = reference_logits(cfg, params, ids)
    assert np.abs(want).max() > 1.0
    assert close(got, want)


def test_mtp_block_matches_reference():
    cfg, params = model()
    ids = some_ids(cfg, (2, 33))
    got = np.asarray(ex.forward_drafts(cfg, params, jnp.asarray(ids)))
    want = reference_drafts(cfg, params, ids)
    assert got.shape == want.shape == (2, 32, cfg.vocab_size)
    assert np.abs(want).max() > 1.0
    assert close(got, want)


def test_the_published_config_and_its_cut():
    cfg = ex.ExaoneMoeConfig()
    assert [cfg.kind_of(l) for l in range(5)] == [
        ex.WINDOW, ex.WINDOW, ex.WINDOW, ex.FULL, ex.WINDOW]
    assert (cfg.main_layers_of(ex.FULL), cfg.layers_of(ex.FULL),
            cfg.layers_of(ex.WINDOW), cfg.kv_width) == (12, 13, 36, 1024)
    cut = dataclasses.replace(cfg, n_layers=5, held_experts=(0, 16),
                              vocab_size=19200, max_len=4096)
    # layer 3 of the main stack and the MTP block behind it; four window
    # layers
    assert (cut.layers_of(ex.FULL), cut.layers_of(ex.WINDOW)) == (2, 4)
    shapes = ex.param_shapes(cut)
    assert "mlp" in shapes["layers"][0] and "moe" in shapes["layers"][1]
    assert shapes["layers"][1]["moe"]["router"] == (6144, 128)
    assert shapes["layers"][1]["moe"]["experts"]["w_gate"] == (16, 6144, 2048)
    assert shapes["mtp"]["w_eh"] == (12288, 6144)
    # a window of 128 keys on pages of 128: a ring of 2 pages, with room
    # for ONE row ahead of the committed frontier
    assert ex.page_kinds(cfg, 128) == (("full", None), ("window", 2, 1))
    assert ex.page_kinds(cfg, 32)[1] == ("window", 5, 1)
    assert ex.page_kinds(dataclasses.replace(cfg, sliding_window=100),
                         128)[1] == ("window", 2, 29)
    assert ex.self_draft_depth(cfg) == 1
    with pytest.raises(ValueError, match="MTP"):
        ex.ExaoneMoeConfig(n_mtp=2)
    with pytest.raises(ValueError, match="held_experts"):
        ex.ExaoneMoeConfig(held_experts=(120, 16))


def test_route_sigmoid_bias_chooses_by_s_plus_b_and_weighs_by_s():
    key_s, key_b = jax.random.split(jax.random.key(4))
    s = np.asarray(jax.nn.sigmoid(jax.random.normal(key_s, (37, 32))))
    b = np.asarray(0.5 * jax.random.normal(key_b, (32,)))
    w, chosen = expert.route_sigmoid_bias(jnp.asarray(s), jnp.asarray(b),
                                          4, 2.5)
    w, chosen = np.asarray(w), np.asarray(chosen)
    assert (chosen.sum(axis=1) == 4).all() and ((w > 0) == chosen).all()
    np.testing.assert_allclose(w.sum(axis=1), 2.5, rtol=1e-6)
    moved = 0
    for row in range(37):
        by_biased = set(np.argsort(-(s[row] + b))[:4].tolist())
        assert set(np.flatnonzero(chosen[row]).tolist()) == by_biased
        moved += by_biased != set(np.argsort(-s[row])[:4].tolist())
        np.testing.assert_allclose(
            w[row, chosen[row]],
            2.5 * s[row, chosen[row]] / s[row, chosen[row]].sum(),
            rtol=1e-6)
    # the bias took part in the choice, and in nothing else
    assert moved > 10
    w0, chosen0 = expert.route_sigmoid_bias(jnp.asarray(s), jnp.zeros(32),
                                            4, 2.5)
    assert not (np.asarray(chosen0) == chosen).all()
    # the reference's routing, written another way, takes the same
    r = np.asarray(ref.route({"num_experts_per_tok": 4,
                              "routed_scaling_factor": 2.5},
                             jnp.asarray(s), jnp.asarray(b)))
    np.testing.assert_allclose(r, w, rtol=1e-6)


def test_eight_ranks_parts_and_the_shared_expert_once_make_the_layer():
    """One expert layer cut over 8 ranks of 2 experts each: the routed
    parts of all ranks, and what every rank computes alike (the shared
    expert) counted once, are the uncut reference layer."""
    cfg, params = model(held_experts=(0, 16))
    layer = params["layers"][2]
    x = 1.5 * jax.random.normal(jax.random.key(5), (24, cfg.hidden))
    whole = np.asarray(ref.moe(published_keys(cfg), layer["moe"], x,
                               (0, 16), "f32"))
    parts = []
    for rank in range(8):
        mine, held = ex.hold_experts(cfg, params, 2 * rank, 2)
        assert mine.held_experts == (2 * rank, 2)
        p = held["layers"][2]["moe"]
        assert p["experts"]["w_gate"].shape[0] == 2
        assert held["mtp"]["block"]["moe"]["experts"]["w_up"].shape[0] == 2
        y, counts = ex.moe_routed(mine, p, x)
        parts.append(np.asarray(y))
        assert counts.tolist()[0] == 24 * 3 and counts[1] <= 24 * 3
        # a rank's share alone is what the reference gives for it
        alone = np.asarray(ref.moe(published_keys(cfg), p, x,
                                   (2 * rank, 2), "f32"))
        shared = np.asarray(ref.gated(x, p["shared"], "f32"))
        assert close(parts[-1] + shared, alone)
    shared = np.asarray(ref.gated(x, layer["moe"]["shared"], "f32"))
    assert np.abs(sum(parts)).max() > 0.1
    assert close(sum(parts) + shared, whole)
    # the shared expert counted once, not eight times
    assert not close(sum(parts) + 8 * shared, whole)


# ---------------------------------------------------------------------------
# The paged paths, tables made by hand: prefill, then speculative rounds
# ---------------------------------------------------------------------------

def rounds_logits(cfg, params, row, n_prompt, wrong):
    """Logits of the main model and of the MTP block at every position
    of ``row`` as the speculative path computes them: the prompt a page
    at a time with the MTP block's cache filled behind it, then ROUNDS of
    two rows (the current token and a draft), the sequence in slot 1 of
    3 with scattered pages.  Round r's draft is the row's own next token
    (accepted: two positions commit) unless ``wrong(r)``, when it is
    another token (rejected: its rows are junk in every layer's cache
    and the next round writes the position again)."""
    S, TBL = 3, -(-len(row) // C)
    ring = ex.page_kinds(cfg, C)[1][1]
    pool = ex.init_pages(cfg, (1 + S * TBL, 1 + S * ring), C)
    ptab_f = np.zeros((S, TBL), np.int32)
    ptab_w = np.zeros((S, ring), np.int32)
    rng = np.random.default_rng(0)
    ptab_f[1] = 1 + rng.permutation(S * TBL)[:TBL]
    ptab_w[1] = 1 + rng.permutation(S * ring)[:ring]

    def dispatch(pool, tabs, toks, posw, ok, nxt):
        slabs, x, counts, r = ex._paged_stack(cfg, params, pool, tabs, toks,
                                              posw, ok)
        slabs, u = ex._paged_mtp(cfg, params, slabs, r, x, nxt)
        return (ex._pool_of(slabs), ex._readout(cfg, params, x),
                ex._draft_logits(cfg, params, u), counts)

    dispatch = jax.jit(dispatch)
    main, mtp = {}, {}
    at = np.arange(C, dtype=np.int32)
    for lo in range(0, n_prompt, C):
        n_valid = min(C, n_prompt - lo)
        chunk = np.zeros((C,), np.int32)
        chunk[:n_valid] = row[lo:lo + n_valid]
        nxt = np.zeros((C,), np.int32)
        nxt[:n_valid] = row[lo + 1:lo + n_valid + 1]
        pool, lg, dl, _ = dispatch(
            pool, (ptab_f[1][None], ptab_w[1][None]), chunk[None],
            (lo + at)[None], (at < n_valid)[None], nxt[None])
        for i in range(n_valid):
            main[lo + i], mtp[lo + i] = np.asarray(lg[0, i]), \
                np.asarray(dl[0, i])
    active = np.array([False, True, False])
    p, r_no, commits = n_prompt, 0, []
    while p + 2 < len(row):
        bad = wrong(r_no)
        draft = (row[p + 1] + 1) % cfg.vocab_size if bad else row[p + 1]
        toks = np.zeros((S, 2), np.int32)
        toks[1] = [row[p], draft]
        pos = np.zeros((S, 2), np.int32)
        pos[1] = [p, p + 1]
        nxt = np.zeros((S, 2), np.int32)
        nxt[1] = [row[p + 1], row[p + 2]]
        pool, lg, dl, counts = dispatch(pool, (ptab_f, ptab_w), toks, pos,
                                        np.broadcast_to(active[:, None],
                                                        (S, 2)), nxt)
        n_c = 1 if bad else 2
        for w in range(n_c):
            main[p + w], mtp[p + w] = np.asarray(lg[1, w]), \
                np.asarray(dl[1, w])
        commits.append(n_c)
        p += n_c
        r_no += 1
    n = p
    return (np.stack([main[i] for i in range(n)]),
            np.stack([mtp[i] for i in range(n)]), np.asarray(counts),
            commits, pool)


@pytest.mark.parametrize("length,n_prompt,wrong", [
    (14, 5, lambda r: False),        # inside one page and the window
    (40, 11, lambda r: False),       # every draft accepted: two rows a round
    (40, 11, lambda r: True),        # every draft rejected
    (59, 16, lambda r: r % 3 == 1),  # mixed: page edges met by both rows
    (60, 3, lambda r: r % 2 == 0),
    (75, 53, lambda r: r % 4 == 3),  # the ring goes round in the prompt
], ids=["short", "accepted", "rejected", "mixed3", "mixed2", "long-prompt"])
def test_prefill_then_speculative_rounds_match_reference_logits(
        length, n_prompt, wrong):
    cfg, params = model()
    row = some_ids(cfg, (length,), seed=3)
    main, mtp, counts, commits, pool = rounds_logits(cfg, params, row,
                                                     n_prompt, wrong)
    n = len(main)
    assert n >= length - 3
    want = reference_logits(cfg, params, row)[0][:n]
    assert close(main, want)
    # the MTP block over its own cache: entry i from (h_i, row[i + 1]),
    # rejected rounds' rows written over before anything read them
    want_d = reference_drafts(cfg, params, row)[0][:n]
    assert close(mtp, want_d)
    # one active slot, two rows, four expert layers of the main stack,
    # three experts a row: the idle slots were routed nowhere, and the
    # MTP block's layer is not in the counts
    assert counts.tolist()[:2] == [2 * 4 * 3, 2 * 4 * 3] and counts[3] == 4
    assert pool.window_k.shape == (4, 1 + 3 * RING, C, 16)
    assert pool.full_k.shape == (2, 1 + 3 * -(-length // C), C, 16)


# ---------------------------------------------------------------------------
# Through DecodeEngine
# ---------------------------------------------------------------------------

def engine(cfg, params, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("buckets", (32, 64, 128))
    return DecodeEngine(cfg, params, prefill_chunk=C, **kw)


def test_engine_takes_the_family_and_its_draft_from_the_config():
    cfg, params = model()
    assert model_family(cfg) is ex
    eng = engine(cfg, params, draft="self")
    assert eng.draft_k == 1 and eng._self_draft
    assert [(k.name, k.bounded, k.cap, k.ahead) for k in eng._kinds] == [
        ("full", False, 16, 0), ("window", True, RING, 1)]
    # one page a prefill dispatch (a bounded kind), no second pool
    assert eng.prefill_rows(128) == C and eng._dpool is None
    assert eng.pool_bytes == ex.pages_bytes(cfg, eng.n_kv_pages, C)
    assert eng.n_kv_pages == (3 * 16 + 1, 3 * RING + 1)
    # the draft is the model's own block or nothing
    with pytest.raises(ValueError, match="self"):
        engine(cfg, params, draft=(cfg, params))
    with pytest.raises(ValueError, match="draft_k"):
        engine(cfg, params, draft="self", draft_k=2)
    with pytest.raises(ValueError, match="no draft of its own"):
        engine(cfg, params, draft="mtp")
    for option in ({"kv_dtype": "int8"}, {"quantize": "int8"},
                   {"prefix_cache": True}):
        with pytest.raises(ValueError, match="not supported"):
            engine(cfg, params, **option)
    # the families without a draft of their own still say so
    m_cfg = ml.tiny_config(compute_dtype="float32")
    with pytest.raises(ValueError, match="not supported"):
        DecodeEngine(m_cfg, ml.init_params(jax.random.key(0), m_cfg),
                     n_slots=2, buckets=(32,), prefill_chunk=C, draft="self")


@pytest.mark.parametrize("window,chunk,ahead", [(9, 8, 0), (13, 4, 0),
                                                (16, 8, 1), (8, 8, 1)])
def test_the_ring_rule_raises_when_broken(window, chunk, ahead):
    """A round writes one row ahead of the committed frontier: a ring
    whose pages leave no row over what the window reads back cannot take
    it, and the engine says so instead of reading an overwritten row."""
    cfg, params = model(sliding_window=window)
    kind = ex.page_kinds(cfg, chunk)[1]
    cap = -(-(window - 1) // chunk) + 1
    assert kind == ("window", cap, ahead) \
        and ahead == (cap - 1) * chunk - (window - 1)
    make = lambda **kw: DecodeEngine(cfg, params, n_slots=2,  # noqa: E731
                                     buckets=(32, 64),
                                     prefill_chunk=chunk, **kw)
    assert make()._kinds[1].ahead == ahead       # no draft: nothing to ask
    if ahead >= 1:
        assert make(draft="self").draft_k == 1
    else:
        with pytest.raises(ValueError, match="ring"):
            make(draft="self")


def serve(eng, prompts, budgets, temperature=0.0):
    with ContinuousBatcher(eng) as batcher:
        handles = [batcher.submit(p, max_tokens=m, temperature=temperature,
                                  seed=i, eos_id=None)
                   for i, (p, m) in enumerate(zip(prompts, budgets))]
        return [h.result(timeout=300.0) for h in handles]


def greedy_gaps(cfg, params, prompt, tokens):
    """By how much each served token's reference logit lies under the
    reference's best, the row teacher-forced with the served tokens."""
    row = np.concatenate([prompt, tokens[:-1]])
    logits = reference_logits(cfg, params, row)[0][len(prompt) - 1:]
    return logits.max(axis=-1) - logits[np.arange(len(tokens)), tokens]


@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "sampled"])
def test_the_stream_with_the_self_draft_is_the_stream_without(temperature):
    """Token for token, across window wraps (every request passes 8
    keys), rung changes (a dispatch widens 32 -> 64 -> 128 as the longest
    slot grows) and joins mid-flight; one dispatch a round; every page
    back."""
    cfg, params = model()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 16, 70, 33, 3)]
    budgets = [20, 14, 25, 30, 60, 100]
    plain = serve(engine(cfg, params), prompts, budgets, temperature)
    eng = engine(cfg, params, draft="self")
    eng.warmup()
    before = decode_metrics.snapshot()
    tr = telemetry.enable("exaone-self-draft")
    try:
        spec = serve(eng, prompts, budgets, temperature)
    finally:
        telemetry.disable()
    after = decode_metrics.snapshot()
    for p, m, a, b in zip(prompts, budgets, plain, spec):
        assert len(a) == len(b) == m
        np.testing.assert_array_equal(a, b)
        if temperature == 0.0:
            assert greedy_gaps(cfg, params, p, b).max() <= 1e-3
    assert after["compile_delta_since_mark"] == 0
    assert eng._alloc.in_use() == 0 and eng.pages_unaccounted() == 0
    d = {k: after[k] - before[k] for k in (
        "decode_dispatches", "rounds", "admissions", "draft_proposed",
        "draft_accepted", "moe_assignments", "moe_assignments_held",
        "moe_layer_dispatches", "kv_rows_held_full", "kv_rows_held_window",
        "window_pages_reused", "tokens_out")}
    slot_steps = decode_metrics.slot_steps
    # ONE dispatch a round that advanced anything (a round may only
    # admit), one draft a running slot a round
    assert 0 < d["decode_dispatches"] <= d["rounds"]
    assert d["draft_proposed"] > 0
    assert 0 <= d["draft_accepted"] <= d["draft_proposed"]
    # the family counters are of a round's TWO rows a slot, over the four
    # expert layers of the main stack, three experts a row
    assert d["moe_layer_dispatches"] == 4 * d["decode_dispatches"]
    assert d["moe_assignments"] == d["moe_assignments_held"] \
        == 2 * 4 * 3 * d["draft_proposed"]
    assert 0 < d["kv_rows_held_window"] < d["kv_rows_held_full"]
    assert d["window_pages_reused"] >= sum(
        -(-(len(p) + m - 1) // C) - RING for p, m in zip(prompts, budgets))
    assert slot_steps > 0
    spans = [r for r in tr.records() if r["type"] == "span"
             and r["name"] == "decode.advance"]
    assert len(spans) == d["decode_dispatches"]
    for r in spans:
        attrs = r["attrs"]
        assert attrs["k"] == 1 and attrs["committed"] >= 1
        assert attrs["width"] in (32, 64, 128)
        assert attrs["width_window"] == RING * C
    assert {r["attrs"]["width"] for r in spans} == {32, 64, 128}
    assert sum(r["attrs"]["committed"] for r in spans) \
        == sum(budgets) - len(budgets)


def live_rows(eng, slot):
    """The cached rows a later query of ``slot`` can still read, a kind:
    every position below the frontier on the main stack's full layers
    (the MTP block's layer, which only a drafting engine fills, left
    out), the window's last ``WINDOW - 1`` on the sliding layers."""
    pos = int(eng._slots.pos_h[slot])
    full, window = eng._kinds
    pool = eng._pool
    n_main = eng.cfg.main_layers_of(ex.FULL)

    def rows(arr, kind, positions):
        pages = kind.ptab[slot, (positions // C) % kind.cap]
        return np.asarray(arr)[:, pages, positions % C]

    at_f = np.arange(pos)
    at_w = np.arange(max(0, pos - WINDOW + 1), pos)
    return {"full_k": rows(pool.full_k, full, at_f)[:n_main],
            "full_v": rows(pool.full_v, full, at_f)[:n_main],
            "window_k": rows(pool.window_k, window, at_w),
            "window_v": rows(pool.window_v, window, at_w)}, pos


@pytest.mark.parametrize("adversarial", [False, True],
                         ids=["oracle-drafts", "adversarial-drafts"])
def test_injected_drafts_commit_two_or_one_and_leave_the_plain_rows(
        adversarial):
    """Drafts from the target's own continuation: every round commits 2.
    Drafts that are never the target's token: every round commits 1.
    After either, the live rows of both kinds of page are the plain
    engine's at the same frontier (a rejected draft's rows were written
    over, or lie past every mask)."""
    cfg, params = model()
    prompt = some_ids(cfg, (21,), seed=9)
    n_out = 41                    # the window ring goes round five times
    plain = engine(cfg, params)
    slot_p, first = plain.start(prompt, max_tokens=100)
    stream = [first]
    for _ in range(n_out - 1):
        stream.append(int(plain.advance()[slot_p]))
    eng = engine(cfg, params, draft="self")
    slot, first_s = eng.start(prompt, max_tokens=100)
    assert first_s == first
    got, rounds = [first_s], 0
    while len(got) < n_out:
        nxt = stream[len(got)]
        eng._drafts_h[slot, 0] = (nxt + 1) % cfg.vocab_size \
            if adversarial else nxt
        out, n_c = eng.advance_spec()
        assert int(n_c[slot]) == (1 if adversarial else 2)
        got += out[slot, :int(n_c[slot])].tolist()
        rounds += 1
        # the stalled/idle slots committed nothing
        assert int(n_c.sum()) == int(n_c[slot])
    assert got == stream[:len(got)]
    assert rounds == (n_out - 1 if adversarial else (n_out - 1) // 2)
    mine, pos = live_rows(eng, slot)
    theirs, pos_p = live_rows(plain, slot_p)
    assert pos == pos_p == len(prompt) + n_out - 1
    for name in mine:
        assert mine[name].shape == theirs[name].shape
        # (float32 rounding apart: a row of a two-row dispatch and of a
        # one-row one are the same products in another blocking; a row
        # left from a rejected draft would differ by its whole size)
        np.testing.assert_allclose(mine[name], theirs[name], rtol=1e-4,
                                   atol=1e-5, err_msg=name)
        assert np.abs(theirs[name]).max() > 0.5
    assert mine["window_k"].shape[1] == WINDOW - 1
    for e in (eng, plain):
        e.release(0)
        assert e._alloc.in_use() == 0 and e.pages_unaccounted() == 0


def test_the_rounds_program_carries_the_scopes_and_is_what_decode_hlo_gives():
    cfg, params = model()
    eng = engine(cfg, params, draft="self", n_slots=2, buckets=(32,))
    text = eng.decode_hlo(32)
    for scope in ("mtp_block", "shared_expert", "window_attention",
                  "full_attention", "moe_route", "moe_experts"):
        assert f"/{scope}/" in text, scope
    assert "spec_fn" in text
    # without the draft the program is the plain step, with no MTP block
    plain = engine(cfg, params, n_slots=2, buckets=(32,)).decode_hlo(32)
    assert "decode_fn" in plain and "/mtp_block/" not in plain
    assert "/shared_expert/" in plain


def test_a_slot_short_of_pages_stalls_a_speculative_round_and_resumes():
    """Admission and growth are the engine's for every kind of page: in
    a pool too small for both slots' next pages one stalls, commits
    nothing that round, and goes on when the other has left."""
    cfg, params = model()
    eng = engine(cfg, params, draft="self", n_slots=2, buckets=(32, 64),
                 n_pages=8)
    plain = engine(cfg, params, n_slots=1, buckets=(32, 64))
    prompts = [some_ids(cfg, (15,), seed=20), some_ids(cfg, (14,), seed=21)]
    lengths = [11, 31]
    want = []
    for p, n in zip(prompts, lengths):
        s, first = plain.start(p, max_tokens=40)
        want.append([first] + [int(plain.advance()[s])
                               for _ in range(n - 1)])
        plain.release(s)
    slots = [eng.start(p, max_tokens=40) for p in prompts]
    got = [[first] for _, first in slots]
    stalled = 0
    for _ in range(80):
        out, n_c = eng.advance_spec()
        ran = eng.last_ran()
        for i, (s, _) in enumerate(slots):
            if eng._slots.owners[s] is None:
                continue
            if not ran[s]:
                stalled += 1
                assert n_c[s] == 0
                continue
            got[i] += out[s, :int(n_c[s])].tolist()
            if len(got[i]) >= lengths[i]:
                eng.release(s)
        if all(eng._slots.owners[s] is None for s, _ in slots):
            break
    assert stalled > 0
    for g, w, n in zip(got, want, lengths):
        assert g[:n] == w
    assert eng._alloc.in_use() == 0 and eng.pages_unaccounted() == 0
