"""A prefill dispatch carries several pages of prompt (PR 33): the rows
of a dispatch are derived by ``DecodeEngine`` (``prefill_rows``: a whole
number of pages, at most the rung, at most ``PREFILL_ROWS_MAX``, ONE
page for a family with a bounded kind of page); the page width stays
what the ``prefill_chunk`` keyword gives.  Every test takes the family
as a parameter and compares an engine whose dispatches carry ``M``
pages with one whose dispatches carry one (the module constant set to
the page width): the same rows at the same pages and offsets, the same
greedy tokens, every page back."""

import hashlib
import json
import os

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.models import deepseek_v2 as ds, gpt, mellum as ml
from deeplearning4j_tpu.runtime import telemetry
from deeplearning4j_tpu.runtime.metrics import compile_metrics, decode_metrics
from deeplearning4j_tpu.serving import decode
from deeplearning4j_tpu.serving.decode import DecodeEngine

C = 8                       # the page width
M = 4                       # pages a dispatch, where the family takes them
FAMILIES = ["gpt", "deepseek_v2", "mellum"]
MANY = ["gpt", "deepseek_v2"]           # families with no bounded kind
#: rungs (48 is not a whole number of 4-page dispatches: a last dispatch
#: can reach past its end with no prefix hit at all)
LADDER = (16, 48, 64, 128)


def model(family):
    """Toy sizes in float32; weights wide enough that greedy tokens
    differ from one position to the next."""
    if family == "gpt":
        cfg = gpt.gpt_tiny()
        params = gpt.init_params(jax.random.key(0), cfg)
        blocks = {k: v * 8.0 if k[0] == "w" else v
                  for k, v in params["blocks"].items()}
        return cfg, {**params, "blocks": blocks}
    if family == "deepseek_v2":
        cfg = ds.tiny_config(compute_dtype="float32", max_len=128)
        return cfg, ds.init_params(jax.random.key(0), cfg, std=0.3)
    cfg = ml.tiny_config(compute_dtype="float32")       # window 16
    return cfg, ml.init_params(jax.random.key(0), cfg, std=0.3)


def engine(family, pages, monkeypatch, **kw):
    """An engine whose prefill dispatches carry at most ``pages``
    pages."""
    monkeypatch.setattr(decode, "PREFILL_ROWS_MAX", pages * C)
    cfg, params = model(family)
    kw.setdefault("n_slots", 3)
    kw.setdefault("buckets", LADDER)
    return cfg, DecodeEngine(cfg, params, prefill_chunk=C,
                             label=f"rows-{family}-{pages}", **kw)


def live_rows(eng, slot, n):
    """The first ``n`` rows the slot holds on every slab of the kind
    whose table never wraps (the first declared), read through its page
    table: [slabs, L, n, F]."""
    kind = eng._kinds[0]
    pids = kind.ptab[slot, :-(-n // C)]
    slabs = jax.tree.leaves(eng._pool_state())
    slabs = slabs[:2] if len(eng._kinds) > 1 else slabs
    out = []
    for a in slabs:
        a = np.asarray(a)[:, pids]                       # [L, n_p, C, F]
        out.append(a.reshape(a.shape[0], -1, a.shape[-1])[:, :n])
    return np.stack(out)


def prompt_of(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        1, cfg.vocab_size, n).astype(np.int32)


def serve(eng, prompt, max_tokens, steps=6):
    slot, first = eng.start(prompt, max_tokens=max_tokens)
    toks = [first]
    for _ in range(min(steps, max_tokens - 1)):
        toks.append(int(eng.advance()[slot]))
    return slot, toks


def all_back(eng):
    for s in np.flatnonzero(eng._slots.active):
        eng.release(int(s))
    eng.drop_residents()
    eng.close()
    assert eng._alloc.in_use() == 0 and eng.pages_unaccounted() == 0
    assert all(not k.ptab.any() and not k.n_pages.any() for k in eng._kinds)


@pytest.mark.parametrize("family", FAMILIES)
def test_the_width_is_derived_from_page_rung_and_kinds(family, monkeypatch):
    cfg, eng = engine(family, M, monkeypatch)
    assert eng.page_tokens == eng.prefill_chunk == C
    want = ({t: C for t in LADDER} if family == "mellum"
            else {16: 16, 48: 32, 64: 32, 128: 32})
    assert {t: eng.prefill_rows(t) for t in LADDER} == want
    # a page wider than the limit: one page a dispatch, never less
    monkeypatch.setattr(decode, "PREFILL_ROWS_MAX", 4)
    cfg, params = model(family)
    wide = DecodeEngine(cfg, params, prefill_chunk=C, buckets=LADDER)
    assert {t: wide.prefill_rows(t) for t in LADDER} == {t: C
                                                         for t in LADDER}
    # no new argument: the keyword is the page width and nothing else
    import inspect
    assert "prefill_rows" not in inspect.signature(DecodeEngine).parameters


#: (prompt tokens, max_tokens): the prompt ends inside the first page,
#: on a page edge, on a dispatch edge, inside a later dispatch, and —
#: rung 48, dispatches of 32 rows — in a last dispatch that starts at
#: row 32 and reaches 16 rows past the rung's end
SHAPES = [(5, 6), (16, 20), (32, 20), (61, 3), (41, 7)]


@pytest.mark.parametrize("n,max_tokens", SHAPES)
@pytest.mark.parametrize("family", FAMILIES)
def test_m_pages_a_dispatch_leave_what_one_page_a_dispatch_does(
        family, n, max_tokens, monkeypatch):
    """The pool's live rows and the greedy tokens of a prompt prefilled
    ``M`` pages a dispatch are those of one page a dispatch, and a
    neighbour slot's rows are untouched by the join."""
    got = {}
    for pages in (1, M):
        cfg, eng = engine(family, pages, monkeypatch)
        neighbour = prompt_of(cfg, 21, seed=9)
        s0, _ = eng.start(neighbour, max_tokens=4)
        before = live_rows(eng, s0, 21)
        decode_metrics.reset()
        prompt = prompt_of(cfg, n, seed=n)
        slot, first = eng.start(prompt, max_tokens=max_tokens)
        rows = eng.prefill_rows(eng.pick_bucket(n + max_tokens))
        snap = decode_metrics.snapshot()
        assert snap["prefill_dispatches"] == -(-n // rows)
        assert snap["prefill_rows_dispatched"] == rows * -(-n // rows)
        assert snap["prefill_rows_valid"] == n
        np.testing.assert_array_equal(live_rows(eng, s0, 21), before)
        written = live_rows(eng, slot, n)
        toks = [first] + [int(eng.advance()[slot])
                          for _ in range(max_tokens - 1)]
        got[pages] = (written, toks)
        all_back(eng)
    (rows_1, toks_1), (rows_m, toks_m) = got[1], got[M]
    # the same arithmetic row for row; only how many rows share a
    # product changes, which float32 on the CPU may round differently
    np.testing.assert_allclose(rows_m, rows_1, rtol=2e-4, atol=2e-5)
    assert toks_m == toks_1
    assert len(set(toks_1)) > 1 or max_tokens < 4


@pytest.mark.parametrize("total", [60, 124])
@pytest.mark.parametrize("family", MANY)
def test_a_prefix_hit_between_dispatch_edges(family, total, monkeypatch):
    """A resident hit of 3 pages (not a multiple of ``M``): the join
    starts at page 3.  60 tokens: its dispatches cover pages 3-6 and
    7-10 of a rung of 8, so the last one reaches two pages past the
    table's end; 124 tokens: the last of four starts at row 120 of the
    longest rung and reaches 24 rows past the MODEL's positions too."""
    cfg, _ = model(family)
    n_disp = -(-(total - 3 * C) // (M * C))
    head = prompt_of(cfg, 3 * C + 2, seed=1)
    tail = prompt_of(cfg, total - 3 * C, seed=2)
    long_prompt = np.concatenate([head[:3 * C], tail])
    want = {}
    for pages in (1, M):
        cfg, eng = engine(family, pages, monkeypatch)
        decode_metrics.reset()
        s0, _ = serve(eng, head, max_tokens=4, steps=0)
        held = live_rows(eng, s0, len(head))
        slot, toks = serve(eng, long_prompt, max_tokens=4)
        snap = decode_metrics.snapshot()
        assert snap["prefix_hits"] == 1
        assert snap["prefill_tokens_saved"] == 3 * C
        # mounted by reference: the first three pages are the same pages
        assert (eng._kinds[0].ptab[slot, :3]
                == eng._kinds[0].ptab[s0, :3]).all()
        np.testing.assert_array_equal(live_rows(eng, s0, len(head)), held)
        want[pages] = (live_rows(eng, slot, total), toks)
        if pages == M:
            # the first join is one dispatch of 32 rows, 26 of them
            # prompt; the second prefills what the hit left
            assert snap["prefill_dispatches"] == 1 + n_disp
            assert snap["prefill_rows_valid"] == 26 + total - 3 * C
        all_back(eng)
    # and a cold engine, one page a dispatch, no hit: the same tokens
    cfg, cold = engine(family, 1, monkeypatch)
    slot, toks = serve(cold, long_prompt, max_tokens=4)
    np.testing.assert_allclose(want[M][0], live_rows(cold, slot, total),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(want[M][0], want[1][0], rtol=2e-4, atol=2e-5)
    assert want[M][1] == want[1][1] == toks
    all_back(cold)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_gpt_rows_past_the_tables_end_go_to_the_trash_page(kv_dtype):
    """``gpt.paged_prefill`` alone, a dispatch of 4 pages that starts at
    the LAST page of its table: the page it owns is written, the three
    beyond the table's end land in the trash page, and no other page of
    the pool changes (``dynamic_update_slice`` would have clamped the
    slab three pages back onto live rows)."""
    cfg, params = model("gpt")
    pool = gpt.init_pages(cfg, 9, C, kv_dtype)
    ptab = np.array([3, 5, 7, 2], np.int32)             # a rung of 32
    fill = jax.jit(lambda pool, toks, start, n_valid: gpt.paged_prefill(
        cfg, params, pool, ptab, toks, start, n_valid,
        np.float32(0.0), np.uint32(0)))
    # three pages one at a time, then the last page in a 4-page dispatch
    prompt = prompt_of(cfg, 29, seed=4)
    one = pool
    for c in range(4):
        toks = np.zeros((C,), np.int32)
        n_valid = min(C, 29 - c * C)
        toks[:n_valid] = prompt[c * C:c * C + n_valid]
        one, first_1 = fill(one, toks, np.int32(c * C), np.int32(n_valid))
        if c == 2:
            three = one
    toks = np.zeros((M * C,), np.int32)
    toks[:5] = prompt[24:]
    many, first_m = fill(three, toks, np.int32(24), np.int32(5))
    assert int(first_m) == int(first_1)
    for a_m, a_1, a_3 in zip(jax.tree.leaves(many), jax.tree.leaves(one),
                             jax.tree.leaves(three)):
        a_m, a_1, a_3 = (np.asarray(a, np.float32) for a in (a_m, a_1, a_3))
        # the slot's earlier pages and every page it does not own: as
        # they were before the dispatch
        for pid in (3, 5, 7, 1, 4, 6, 8):
            np.testing.assert_array_equal(a_m[:, pid], a_3[:, pid])
        # (an int8 pool's rows may round one step apart)
        np.testing.assert_allclose(a_m[:, 2, :5], a_1[:, 2, :5], rtol=2e-4,
                                   atol=1.0 if a_m.ndim == 4 and kv_dtype
                                   else 2e-5)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_warmed_engine_joins_with_zero_traces_at_every_rung(
        family, monkeypatch):
    cfg, eng = engine(family, M, monkeypatch)
    stats = eng.warmup()
    assert stats["buckets"] == len(LADDER)
    assert stats["compiles"] <= 2 * len(LADDER)  # one prefill a rung
    before = compile_metrics.snapshot()["compile_count"]
    for n, max_tokens in [(3, 5), (41, 7), (50, 14), (100, 28), (61, 3)]:
        slot, _ = serve(eng, prompt_of(cfg, n, seed=n), max_tokens, steps=2)
        eng.release(slot)
    assert compile_metrics.snapshot()["compile_count"] == before
    all_back(eng)


def test_counters_and_span_of_a_known_prompt(monkeypatch):
    """41 tokens into the rung of 64, dispatches of 32 rows: two
    dispatches, 64 rows sent, 41 of them prompt; the span says 6 pages
    (``chunks``) and 32 rows a dispatch."""
    cfg, eng = engine("gpt", M, monkeypatch)
    eng.warmup()
    decode_metrics.reset()
    tr = telemetry.enable("prefill-rows")
    try:
        slot, _ = eng.start(prompt_of(cfg, 41), max_tokens=20)
    finally:
        telemetry.disable()
    snap = decode_metrics.snapshot()
    assert (snap["prefill_dispatches"], snap["prefill_rows_dispatched"],
            snap["prefill_rows_valid"]) == (2, 64, 41)
    span, = [r for r in tr.records()
             if r["type"] == "span" and r["name"] == "decode.prefill"]
    assert (span["attrs"]["rows"], span["attrs"]["chunks"],
            span["attrs"]["prompt_tokens"]) == (32, 6, 41)
    # a second join adds to both; the drivers take the window's delta
    eng.start(prompt_of(cfg, 9, seed=3), max_tokens=4)      # rung 16
    snap = decode_metrics.snapshot()
    assert (snap["prefill_dispatches"], snap["prefill_rows_dispatched"],
            snap["prefill_rows_valid"]) == (3, 80, 50)
    decode_metrics.reset()
    snap = decode_metrics.snapshot()
    assert snap["prefill_rows_dispatched"] == snap["prefill_rows_valid"] == 0
    all_back(eng)


def test_the_draft_takes_the_same_stride(monkeypatch):
    """A speculative engine: the draft's pool is prefilled in the
    target's dispatches (every page, hit or not), and the committed
    chain is the plain engine's token for token."""
    cfg, params = model("gpt")
    dcfg = gpt.gpt_tiny()
    dparams = gpt.init_params(jax.random.key(5), dcfg)
    prompt = prompt_of(cfg, 41, seed=6)
    _, plain = engine("gpt", M, monkeypatch)
    _, want = serve(plain, prompt, max_tokens=12, steps=11)
    monkeypatch.setattr(decode, "PREFILL_ROWS_MAX", M * C)
    spec = DecodeEngine(cfg, params, n_slots=3, buckets=LADDER,
                        prefill_chunk=C, draft=(dcfg, dparams), draft_k=3,
                        label="rows-draft")
    calls = []
    inner = spec._draft_prefill
    spec._draft_prefill = lambda p, pool, ptab, toks, start, n_valid: (
        calls.append((toks.shape[0], int(start), int(n_valid)))
        or inner(p, pool, ptab, toks, start, n_valid))
    slot, first = spec.start(prompt, max_tokens=12)
    assert calls == [(32, 0, 32), (32, 32, 9)]
    got = [first]
    while len(got) < 12:
        toks, n_commit = spec.advance_spec()
        got.extend(int(t) for t in toks[slot, :n_commit[slot]])
    assert got[:12] == want
    all_back(spec)
    all_back(plain)


def lowered_prefill(eng):
    """Each rung's prefill program as the engine's warm-up lowers it."""
    out = {}
    for t in eng.buckets:
        ptab = eng._idle_step_args(t)[0]
        out[str(t)] = hashlib.sha256(eng._prefill.jitted.lower(
            eng.current_params(), eng._pool_state(),
            jax.tree.map(lambda a: a[0], ptab),
            np.zeros((eng.prefill_rows(t),), np.int32), np.int32(0),
            np.int32(1), np.float32(0), np.uint32(0)
        ).as_text().encode()).hexdigest()
    return out


def test_a_bounded_kind_family_lowers_the_parents_prefill_program():
    """``tests/data/prefill_programs_pr32.json``: the PARENT commit's
    prefill programs of this engine (one page a dispatch), lowered on
    this CPU.  Mellum's must still be those, whatever the limit; the
    one-page programs of DeepSeek-V2, whose ``paged_prefill`` changed
    in its docstring only, too."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "prefill_programs_pr32.json")) as f:
        parent = json.load(f)
    if jax.__version__ != parent["jax"]:
        pytest.skip(f"the parent's programs were lowered by jax "
                    f"{parent['jax']}")
    cfg, params = model("mellum")
    eng = DecodeEngine(cfg, params, n_slots=3, buckets=LADDER,
                       prefill_chunk=C)
    assert decode.PREFILL_ROWS_MAX > C
    assert lowered_prefill(eng) == parent["mellum"]
    assert eng.pool_bytes == parent["mellum_pool_bytes"]
    cfg, params = model("deepseek_v2")
    eng = DecodeEngine(cfg, params, n_slots=3, buckets=(16,),
                       prefill_chunk=16)
    assert eng.prefill_rows(16) == 16
    assert lowered_prefill(eng) == parent["deepseek_v2"]
