"""Kinds of page under one ``DecodeEngine`` (PR 32), over the three
families: ``gpt`` and ``deepseek_v2`` have one kind, a rung's worth a
slot, exactly PR 31's one table; ``mellum`` has a full kind and a
window kind whose table row is a ring.  The same engine code runs all
three, so every test here takes the family as a parameter.

``tests/data/decode_engine_pr31.json`` is what the PARENT commit's
engine gave for ``fixed_run`` below on this CPU (tokens, the pool's
bytes, the counters, the lowered programs): the one-kind families must
still give it, bit for bit."""

import hashlib
import json
import os

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.models import deepseek_v2 as ds, gpt, mellum as ml
from deeplearning4j_tpu.runtime.metrics import decode_metrics
from deeplearning4j_tpu.serving.decode import (AllocatorSet, DecodeEngine,
                                               KVPagesExhausted,
                                               PageAllocator)

C = 8
FAMILIES = ["gpt", "deepseek_v2", "mellum"]


def model(family, max_len=None):
    """``max_len``: room for 128 positions where the tiny preset has
    less (the parent's run used the presets as they are)."""
    if family == "gpt":
        cfg = gpt.gpt_tiny()
        return cfg, gpt.init_params(jax.random.key(0), cfg)
    if family == "deepseek_v2":
        cfg = ds.tiny_config(compute_dtype="float32",
                             **({"max_len": max_len} if max_len else {}))
        return cfg, ds.init_params(jax.random.key(0), cfg, std=0.3)
    cfg = ml.tiny_config(compute_dtype="float32")       # window 16
    return cfg, ml.init_params(jax.random.key(0), cfg, std=0.3)


def engine(family, **kw):
    cfg, params = model(family, max_len=128)
    kw.setdefault("n_slots", 3)
    kw.setdefault("buckets", (16, 32, 64, 128))
    return cfg, DecodeEngine(cfg, params, prefill_chunk=C, **kw)


def held(eng, slot):
    return {k.name: int(k.n_pages[slot]) for k in eng._kinds}


@pytest.mark.parametrize("family", FAMILIES)
def test_a_slot_holds_a_rungs_pages_or_a_ring(family):
    """A run 6 windows long (96 positions of mellum's 16): a page of an
    unbounded kind every C positions, never more than the ring
    (``window / C`` pages and those of a prefill dispatch) of a bounded
    one."""
    cfg, eng = engine(family)
    bounds = {k.name: k.cap for k in eng._kinds if k.bounded}
    assert bounds == ({"window": 16 // C + ml.RING_PREFILL_PAGES}
                      if family == "mellum" else {})
    prompt = np.arange(1, 42, dtype=np.int32) % cfg.vocab_size
    slot, _ = eng.start(prompt, max_tokens=56)
    for _ in range(55):
        eng.advance()
        pos = int(eng._slots.pos_h[slot])
        for k in eng._kinds:
            want = -(-pos // C)
            assert int(k.n_pages[slot]) == min(want, k.cap)
            # the table names that many distinct pages and nothing else
            row = k.ptab[slot]
            assert len(set(row[row > 0].tolist())) == int(k.n_pages[slot])
    assert pos == 96
    assert eng._alloc.in_use() == sum(held(eng, slot).values())
    eng.release(slot)
    eng.drop_residents()            # a family that mounts: the prompt's
    assert eng._alloc.in_use() == 0 and eng.pages_unaccounted() == 0
    assert all(k.alloc.n_free() == k.alloc.n_pages - 1 for k in eng._kinds)


@pytest.mark.parametrize("family", FAMILIES)
def test_admission_counts_every_kind_and_a_stalled_slot_resumes(family):
    cfg, eng = engine(family, n_slots=2, n_pages=5)   # 4 pages a kind
    assert all(k.alloc.n_pages == 5 for k in eng._kinds)
    assert eng.can_admit(3 * C - 1)
    assert not eng.can_admit(4 * C)               # 4 pages + the next
    with pytest.raises(KVPagesExhausted):
        eng.check_capacity(5 * C)
    a, _ = eng.start(np.ones(2 * C - 1, np.int32), max_tokens=40)
    # one kind short is short: each in turn
    for k in eng._kinds:
        grabbed = k.alloc.alloc(k.alloc.n_free())
        assert not eng.can_admit(C - 1)
        k.alloc.free(grabbed)
        assert eng.can_admit(C - 1)
    b, _ = eng.start(np.ones(C - 1, np.int32), max_tokens=40)
    # a has 2 pages a kind, b has 1: one page is left of each kind.  The
    # first step fills their last rows; at the second both want their
    # next page at once: the first takes it, the second stalls
    eng.advance()
    assert eng.last_ran().sum() == 2
    eng.advance()
    ran = eng.last_ran()
    assert ran.sum() == 1 and eng._slots.active.sum() == 2
    stalled = int(np.flatnonzero(~ran & eng._slots.active)[0])
    at = int(eng._slots.pos_h[stalled])
    eng.advance()
    assert int(eng._slots.pos_h[stalled]) == at       # still stalled
    eng.release(int(np.flatnonzero(ran)[0]))
    eng.advance()
    assert eng.last_ran()[stalled]
    assert int(eng._slots.pos_h[stalled]) == at + 1   # resumed
    eng.release(stalled)
    eng.drop_residents()
    assert eng._alloc.in_use() == 0 and eng.pages_unaccounted() == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_every_page_of_every_kind_comes_back(family):
    from deeplearning4j_tpu.serving.decode import ContinuousBatcher

    cfg, eng = engine(family)
    rng = np.random.default_rng(3)
    with ContinuousBatcher(eng) as batcher:
        handles = [batcher.submit(
            rng.integers(0, cfg.vocab_size, n).astype(np.int32),
            max_tokens=m, temperature=0.0, eos_id=None)
            for n, m in [(5, 9), (50, 30), (17, 20), (33, 6), (70, 40)]]
        assert [len(h.result(timeout=120.0)) for h in handles] == [
            9, 30, 20, 6, 40]
    eng.drop_residents()
    one_kind = len(eng._kinds) == 1
    assert isinstance(eng._alloc, PageAllocator if one_kind
                      else AllocatorSet)
    assert eng._alloc.in_use() == 0 and eng.pages_unaccounted() == 0
    assert all(k.alloc.in_use() == 0 and not k.n_pages.any()
               and not k.ptab.any() for k in eng._kinds)
    assert decode_metrics.snapshot()["pages_leaked"] == 0


def sha(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()


def fixed_run(family):
    """Three requests on three slots, 19 rounds, one released half way:
    the run the parent's numbers were taken from."""
    cfg, params = model(family)
    eng = DecodeEngine(cfg, params, n_slots=3, buckets=(16, 32, 64),
                       prefill_chunk=C)
    decode_metrics.reset()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 21, 40)]
    toks, slots = [], []
    for p in prompts:
        s, first = eng.start(p, max_tokens=20)
        slots.append(s)
        toks.append([first])
    for step in range(19):
        out = eng.advance()
        for i, s in enumerate(slots):
            toks[i].append(int(out[s]))
        if step == 9:
            eng.release(slots[0])
    snap = {k: v for k, v in decode_metrics.snapshot().items()
            if isinstance(v, (int, float)) and not k.endswith("_s")
            and "ttft" not in k}
    text = {str(t): eng._lower_decode(t).as_text() for t in eng.buckets}
    prefill = eng._prefill.jitted.lower(
        eng.current_params(), eng._pool_state(), np.zeros((2,), np.int32),
        np.zeros((8,), np.int32), np.int32(0), np.int32(1), np.float32(0),
        np.uint32(0)).as_text()
    return {"tokens": toks,
            "pool": sha(*jax.tree.leaves(eng._pool_state())),
            "snapshot": snap,
            "decode_hlo": {t: hashlib.sha256(x.encode()).hexdigest()
                           for t, x in text.items()},
            "prefill_hlo": hashlib.sha256(prefill.encode()).hexdigest(),
            "in_use": eng._alloc.in_use(),
            "unaccounted": eng.pages_unaccounted()}


@pytest.mark.parametrize("family", ["gpt", "deepseek_v2"])
def test_one_kind_families_run_as_the_parent_did(family, monkeypatch):
    """Since PR 33 a prefill dispatch carries as many pages as the
    engine derives; held to ONE page (the limit set to the page width)
    the engine is the parent's, page for page."""
    from deeplearning4j_tpu.serving import decode

    monkeypatch.setattr(decode, "PREFILL_ROWS_MAX", C)
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "decode_engine_pr31.json")) as f:
        parent = json.load(f)
    got = fixed_run(family)
    want = parent[family]
    assert got["tokens"] == want["tokens"]
    assert (got["in_use"], got["unaccounted"]) == (want["in_use"],
                                                   want["unaccounted"])
    # every key the parent's counters had reads the same; the kinds'
    # own counters stay at 0 for a family that declares none, and the
    # rows of the 9 one-page dispatches are counted (PR 33); a step that
    # is collected before the next is dispatched is never ahead of its
    # fetch and never a step past a request's end (PR 36); no batcher
    # places a request here and every registered page is still in a
    # slot's table at the last join (PR 37); a family with no slot state
    # resets none and holds none (PR 39)
    assert {k: got["snapshot"][k] for k in want["snapshot"]} \
        == want["snapshot"]
    new = set(got["snapshot"]) - set(want["snapshot"])
    rows = {"prefill_rows_dispatched", "prefill_rows_valid"}
    ahead = {"decode_dispatches_ahead", "decode_overshoot_steps"}
    vacancy = {"slot_turnovers", "pages_held_resident"}
    state = {"linear_state_resets", "state_bytes_held"}
    assert new == rows | ahead | vacancy | state | set(
        decode_metrics.KIND_GAUGES + decode_metrics.KIND_COUNTS)
    assert not any(got["snapshot"][k] for k in new - rows)
    assert (got["snapshot"]["prefill_rows_dispatched"],
            got["snapshot"]["prefill_rows_valid"]) == (9 * C, 5 + 21 + 40)
    if jax.__version__ != parent["jax"]:
        pytest.skip(f"the parent's programs were lowered by jax "
                    f"{parent['jax']}")
    # the same traced programs (the page table reaches both dispatches
    # as the bare array), so the same compile-cache entries, and the
    # same bytes in the pool; GPT's prefill program persists a run of
    # pages since PR 33 and is no longer the parent's text
    assert got["decode_hlo"] == want["decode_hlo"]
    if family != "gpt":
        assert got["prefill_hlo"] == want["prefill_hlo"]
    assert got["pool"] == want["pool"]


def test_per_kind_counters_are_what_the_tables_hold():
    cfg, eng = engine("mellum")
    decode_metrics.reset()
    slot, _ = eng.start(np.ones(40, np.int32), max_tokens=30)
    snap = decode_metrics.snapshot()
    # 5 pages into a ring of 4: one written over
    assert (snap["pages_in_use_full"], snap["pages_in_use_window"],
            snap["window_pages_reused"]) == (5, 4, 1)
    for _ in range(9):
        eng.advance()
    snap = decode_metrics.snapshot()
    # positions 40..48 written: a full layer holds 41..49 rows; position
    # 40 opens page 5 over page 1 (the ring holds rows from 16) and
    # position 48 page 6 over page 2 (rows from 24)
    assert snap["kv_rows_held_full"] == sum(range(41, 50))
    assert snap["kv_rows_held_window"] == sum(range(25, 33)) + 25
    assert snap["window_pages_reused"] == 3
    assert (snap["pages_in_use_full"], snap["pages_in_use_window"]) == (7, 4)
    eng.release(slot)
    snap = decode_metrics.snapshot()
    assert snap["pages_in_use_full"] == snap["pages_in_use_window"] == 0


def _overwritten_and_read(window, c, cap, m):
    """A prefill dispatch of ``m`` pages from the page-aligned frontier
    ``p`` into a ring of ``cap`` columns, every ``p`` of two laps: does a
    page it opens lie over a page that a row of the dispatch reads?
    Counted on the ring itself, no formula: column ``j % cap`` holds page
    ``j``, and the dispatch's row at ``x`` reads positions ``x - (window
    - 1) .. x``."""
    for q in range(cap, 3 * cap + 1):
        opened = range(q, q + m)
        lost = {j - cap for j in opened}
        # the oldest page any row of the dispatch reads is the
        # frontier's own row's; every page from there to the newest
        read = set(range(max(0, q * c - (window - 1)) // c, q + m))
        if lost & read:
            return True
    return False


@pytest.mark.parametrize("c", [1, 3, 8, 32, 128])
def test_the_width_the_ring_rule_gives_is_the_widest_that_is_safe(c):
    """``1 + ahead // C`` pages a prefill dispatch (``DecodeEngine
    .prefill_rows``), ``ahead`` as the families state it: over a sweep
    of windows and rings no page such a dispatch opens lies over a page
    its rows read, and with one page more one always does."""
    swept = 0
    for window in sorted({1, 2, c - 1, c, c + 1, 2 * c, 5 * c - 1, 5 * c,
                          5 * c + 1, 5 * c + 2, 1024} - {0, -1}):
        least = -(-(window - 1) // c) + 1       # the ring of one page
        for cap in range(least, least + 4):
            ahead = (cap - 1) * c - (window - 1)
            assert ahead >= 0
            m = 1 + ahead // c
            assert m == cap - least + 1
            assert not _overwritten_and_read(window, c, cap, m)
            assert _overwritten_and_read(window, c, cap, m + 1)
            swept += 1
    assert swept >= 28


@pytest.mark.parametrize("family,page,cap,ahead,pages", [
    ("mellum", 128, 10, 129, 2), ("mellum", 64, 18, 65, 2),
    ("exaone_moe", 128, 2, 1, 1), ("exaone_moe", 32, 5, 1, 1)])
def test_what_the_families_declare_and_the_pages_it_gives(
        family, page, cap, ahead, pages):
    """The published configurations: Mellum's ring leaves room for two
    pages a prefill dispatch at either page width, K-EXAONE's stays
    ``("window", 2, 1)`` and gives one."""
    from deeplearning4j_tpu.models import exaone_moe as ex

    fam, cfg = ((ml, ml.MellumConfig()) if family == "mellum"
                else (ex, ex.ExaoneMoeConfig()))
    assert fam.page_kinds(cfg, page) == (("full", None),
                                         ("window", cap, ahead))
    assert 1 + ahead // page == pages
    assert not _overwritten_and_read(cfg.sliding_window, page, cap, pages)
    assert _overwritten_and_read(cfg.sliding_window, page, cap, pages + 1)
