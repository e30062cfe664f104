"""A prefill dispatch carries several pages of prompt (PR 33): the rows
of a dispatch are derived by ``DecodeEngine`` (``prefill_rows``: a whole
number of pages, at most the rung, at most ``PREFILL_ROWS_MAX``, and for
a family with a bounded kind of page at most the ``1 + ahead // C``
pages its ring leaves room for: two for Mellum, one for K-EXAONE); the
page width stays what the ``prefill_chunk`` keyword gives.  Every test
takes the family as a parameter and compares an engine whose dispatches
carry ``M`` pages (or what the ring leaves of them) with one whose
dispatches carry one (the module constant set to the page width): the
same rows at the same pages and offsets, the same greedy tokens, every
page back."""

import hashlib
import json
import os

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.models import (deepseek_v2 as ds, exaone_moe as ex,
                                       gpt, mellum as ml)
from deeplearning4j_tpu.runtime import telemetry
from deeplearning4j_tpu.runtime.metrics import compile_metrics, decode_metrics
from deeplearning4j_tpu.serving import decode
from deeplearning4j_tpu.serving.decode import DecodeEngine

C = 8                       # the page width
M = 4                       # pages a dispatch, where the family takes them
#: ``mellum``: a window of 16, a ring of 4 columns; ``mellum-w20``: a
#: window of 20, a ring of 5, so that the two pages of a dispatch can
#: straddle the ring's lap (pages 4 and 5 in columns 4 and 0)
FAMILIES = ["gpt", "deepseek_v2", "mellum", "mellum-w20"]
MANY = ["gpt", "deepseek_v2"]           # families with no bounded kind
#: pages a dispatch where ``PREFILL_ROWS_MAX`` gives ``M``: what a
#: bounded kind's ring leaves room for (``exaone_moe``: a ring of 2)
PAGES = {"gpt": M, "deepseek_v2": M, "mellum": 2, "mellum-w20": 2,
         "exaone_moe": 1}
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
    if family == "exaone_moe":
        cfg = ex.tiny_config(compute_dtype="float32")   # window 8
        return cfg, ex.init_params(jax.random.key(0), cfg, std=0.3)
    name, _, window = family.partition("-w")
    assert name == "mellum"
    cfg = ml.tiny_config(compute_dtype="float32",
                         sliding_window=int(window or 16))
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
    """The rows the slot holds of a sequence of ``n``, read through its
    page tables: every one on the slabs of the kind whose table never
    wraps (the first declared), [slabs, L, n, F]; with a bounded kind
    beside it, a tuple of that and the rows of the pages its ring holds
    (page ``j`` in column ``j % cap``), oldest first."""
    n_pages = -(-n // C)
    slabs = jax.tree.leaves(eng._pool_state())

    def read(slabs, pids, first_row):
        out = []
        for a in slabs:
            a = np.asarray(a)[:, pids]                   # [L, n_p, C, F]
            out.append(a.reshape(a.shape[0], -1, a.shape[-1]
                                 )[:, :n - first_row])
        return np.stack(out)

    full = eng._kinds[0]
    if len(eng._kinds) == 1:
        return read(slabs, full.ptab[slot, :n_pages], 0)
    ring = eng._kinds[1]
    oldest = max(0, n_pages - ring.cap)
    return (read(slabs[:2], full.ptab[slot, :n_pages], 0),
            read(slabs[2:], ring.ptab[slot, np.arange(oldest, n_pages)
                                      % ring.cap], oldest * C))


def assert_rows(got, want, exact=False):
    for a, b in zip(*(x if isinstance(x, tuple) else (x,)
                      for x in (got, want))):
        if exact:
            np.testing.assert_array_equal(a, b)
        else:
            # the same arithmetic row for row; only how many rows share
            # a product changes, which float32 on the CPU may round
            # differently
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


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


@pytest.mark.parametrize("family", FAMILIES + ["exaone_moe"])
def test_the_width_is_derived_from_page_rung_and_kinds(family, monkeypatch):
    cfg, eng = engine(family, M, monkeypatch)
    assert eng.page_tokens == eng.prefill_chunk == C
    # as many pages as the rung and the limit give, and no more than
    # the ring of a bounded kind leaves room for: two for Mellum at
    # every rung that has them, one for K-EXAONE's ring of 2
    room = [1 + k.ahead // C for k in eng._kinds if k.bounded]
    assert room == ([] if family in MANY else [PAGES[family]])
    want = {t: C * min(PAGES[family], t // C, M) for t in LADDER}
    assert want[16] == min(2, PAGES[family]) * C
    assert want[128] == PAGES[family] * C
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
#: what a ring makes new (dispatches of 16 rows, two pages), for the
#: families that have one: the prompt ends in the FIRST page of its last
#: dispatch, so that dispatch's second page is padding and is never
#: written, at the ring's first lap (37 tokens: pages 4 and 5; in a ring
#: of 5 they straddle the lap, page 5's column still holding page 0) and
#: past ``cap + 2`` pages after two whole laps (115 tokens: pages 14 and
#: 15, columns 4 and 0 of 5, 2 and 3 of 4); and in its second page past
#: two laps (93 tokens: pages 10 and 11)
RING_SHAPES = [(37, 9), (115, 9), (93, 12)]


def forward_greedy(cfg, params, prompt, toks):
    """What Mellum's cache-less forward at float32 says the greedy
    tokens behind ``prompt`` are, given the served ones before each:
    the served chain is the model's own exactly if they are the same."""
    row = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    logits = np.asarray(ml.forward_logits(cfg, params, row[None]))[0]
    return logits[len(prompt) - 1:].argmax(-1).tolist()


@pytest.mark.parametrize("family,n,max_tokens", [
    (family, *shape) for family in FAMILIES
    for shape in SHAPES + RING_SHAPES * (family not in MANY)])
def test_m_pages_a_dispatch_leave_what_one_page_a_dispatch_does(
        family, n, max_tokens, monkeypatch):
    """The pool's live rows (of both kinds of page where there are two)
    and the greedy tokens of a prompt prefilled ``M`` pages a dispatch
    (what the ring leaves of them) are those of one page a dispatch
    and, for Mellum, of the cache-less forward; a neighbour slot's rows
    are untouched by the join."""
    got = {}
    for pages in (1, M):
        cfg, eng = engine(family, pages, monkeypatch)
        neighbour = prompt_of(cfg, 21, seed=9)
        s0, _ = eng.start(neighbour, max_tokens=4)
        before = live_rows(eng, s0, 21)
        decode_metrics.reset()
        prompt = prompt_of(cfg, n, seed=n)
        slot, first = eng.start(prompt, max_tokens=max_tokens)
        bucket = eng.pick_bucket(n + max_tokens)
        rows = eng.prefill_rows(bucket)
        assert rows == C * min(pages, PAGES[family], bucket // C)
        snap = decode_metrics.snapshot()
        assert snap["prefill_dispatches"] == -(-n // rows)
        assert snap["prefill_rows_dispatched"] == rows * -(-n // rows)
        assert snap["prefill_rows_valid"] == n
        assert_rows(live_rows(eng, s0, 21), before, exact=True)
        written = live_rows(eng, slot, n)
        toks = [first] + [int(eng.advance()[slot])
                          for _ in range(max_tokens - 1)]
        got[pages] = (written, toks)
        all_back(eng)
    (rows_1, toks_1), (rows_m, toks_m) = got[1], got[M]
    assert_rows(rows_m, rows_1)
    assert toks_m == toks_1
    assert len(set(toks_1)) > 1 or max_tokens < 4
    if family.startswith("mellum"):
        cfg, params = model(family)
        assert toks_m == forward_greedy(cfg, params, prompt, toks_m)


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


@pytest.mark.parametrize("family", ["deepseek_v2", "mellum"])
def test_a_family_held_to_one_page_lowers_the_parents_prefill_program(
        family, monkeypatch):
    """``tests/data/prefill_programs_pr32.json``: the PARENT commit's
    prefill programs of this engine (one page a dispatch), lowered on
    this CPU.  The one-page programs of DeepSeek-V2, whose
    ``paged_prefill`` changed in its docstring only, are still those;
    and Mellum's, and its pool's bytes, wherever its ring is declared
    with room for ONE page a dispatch as the parent's was (3 columns
    here): two pages a dispatch are the ring's own choice and nothing
    else in the family or the engine moved."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "prefill_programs_pr32.json")) as f:
        parent = json.load(f)
    if jax.__version__ != parent["jax"]:
        pytest.skip(f"the parent's programs were lowered by jax "
                    f"{parent['jax']}")
    assert decode.PREFILL_ROWS_MAX > C
    cfg, params = model(family)
    if family == "mellum":
        monkeypatch.setattr(ml, "RING_PREFILL_PAGES", 1)
        assert ml.page_kinds(cfg, C)[1] == ("window", 3, 1)
        eng = DecodeEngine(cfg, params, n_slots=3, buckets=LADDER,
                           prefill_chunk=C)
        assert {eng.prefill_rows(t) for t in LADDER} == {C}
        assert eng.pool_bytes == parent["mellum_pool_bytes"]
    else:
        eng = DecodeEngine(cfg, params, n_slots=3, buckets=(16,),
                           prefill_chunk=16)
        assert eng.prefill_rows(16) == 16
    assert lowered_prefill(eng) == parent[family]
