"""Continuous-batching decode serving (serving/decode.py + router.py).

The load-bearing property is SLOT PARITY: a request decoded inside a
busy continuous batch — including one that JOINS mid-flight while other
slots are mid-decode — must be token-identical to a solo
``gpt.generate()`` run (greedy, float32).  Plus: chunked-prefill logits
parity against the dense forward, EOS slot recycling, sampling
reproducibility across placements, router least-depth dispatch and
load-shedding, and the zero-steady-state-compile contract.
"""

import dataclasses
import threading
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import deepseek_v2 as ds, gpt
from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.runtime.metrics import (compile_metrics,
                                                decode_metrics)
from deeplearning4j_tpu.serving.decode import (ContinuousBatcher,
                                               DecodeEngine, PageAllocator,
                                               default_length_buckets)
from deeplearning4j_tpu.serving.router import OverloadedError, Router

CFG = TransformerConfig(vocab_size=64, max_len=64, hidden=32, n_layers=2,
                        n_heads=2, ffn_dim=64, dropout=0.0,
                        compute_dtype="float32", causal=True,
                        type_vocab_size=1)


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(jax.random.key(7), CFG)


def _every_page_is_back(eng):
    """Nothing in flight holds a page and no reclaim path leaked one:
    with the resident-prefix registry dropped, the pool is all free."""
    eng.drop_residents()
    return (eng.n_active() == 0
            and eng._alloc.in_use() + eng.pages_unaccounted() == 0)


@pytest.fixture(scope="module")
def engine(params):
    eng = DecodeEngine(CFG, params, n_slots=4, buckets=(32, 64))
    eng.warmup()
    yield eng
    assert _every_page_is_back(eng)


def _solo(params, prompt, n_tokens):
    """Reference: solo greedy generate() (same chunked prefill path)."""
    out = gpt.generate(CFG, params, np.asarray(prompt, np.int32)[None, :],
                       n_tokens, jax.random.key(0), temperature=0.0)
    return np.asarray(out)[0]


# -- bucket ladder ----------------------------------------------------------

def test_default_length_buckets():
    assert default_length_buckets(128) == (32, 64, 128)
    assert default_length_buckets(48) == (32, 48)
    assert default_length_buckets(16) == (16,)
    with pytest.raises(ValueError):
        default_length_buckets(0)


def test_bucket_chunk_divisibility(params):
    # the chunk shrinks to the largest width dividing every rung —
    # default construction must work for ANY ladder (e.g. a max_len=48
    # model yields the (32, 48) ladder)
    eng = DecodeEngine(CFG, params, buckets=(24, 64), prefill_chunk=16)
    assert eng.prefill_chunk == 8
    eng = DecodeEngine(CFG, params, buckets=(32, 48))
    assert eng.prefill_chunk == 16
    with pytest.raises(ValueError, match="exceeds the model"):
        DecodeEngine(CFG, params, buckets=(128,))


def test_paged_selects_nothing_and_false_names_its_removal(params):
    """One KV storage scheme: the default engine IS the page-pooled one
    (``paged=True`` builds the same engine on the same compile-cache
    entries), and asking for the pinned slot engine says where it
    went."""
    with pytest.raises(ValueError, match="removed in PR 30"):
        DecodeEngine(CFG, params, paged=False)
    kw = dict(n_slots=3, buckets=(32,), prefill_chunk=16)
    a = DecodeEngine(CFG, params, **kw)
    b = DecodeEngine(CFG, params, paged=True, **kw)
    assert isinstance(a._alloc, PageAllocator)
    assert a.n_kv_pages == b.n_kv_pages == 3 * (32 // 16) + 1
    assert b._prefill is a._prefill and b._decode is a._decode


# -- chunked dense prefill --------------------------------------------------

def test_chunked_prefill_logits_parity(params):
    """prefill_cache (slab-written K/V, any chunk width) reproduces the
    dense forward's last-position logits for prompts off/on chunk
    boundaries."""
    rng = np.random.RandomState(0)
    for t_p in (3, 8, 9, 17, 32):
        prompt = rng.randint(1, CFG.vocab_size, size=(2, t_p))
        prompt = prompt.astype(np.int32)
        ref = gpt.forward_logits(CFG, params, prompt)[:, -1]
        cache = gpt.init_cache(CFG, 2, 64)
        _, logits = gpt.prefill_cache(CFG, params, cache, prompt, chunk=8)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


# -- slot parity (the acceptance test) --------------------------------------

def test_mid_flight_join_token_parity(params, engine):
    """Engine-level continuous batching: A decodes alone for several
    steps, B JOINS the running batch (prefill into a free slot while A's
    state rides along), both run to budget — and both are
    token-identical to their solo greedy runs."""
    rng = np.random.RandomState(1)
    pa = rng.randint(1, CFG.vocab_size, size=7).astype(np.int32)
    pb = rng.randint(1, CFG.vocab_size, size=11).astype(np.int32)
    n_a, n_b = 12, 9

    slot_a, first_a = engine.start(pa, max_tokens=n_a, owner="A")
    toks_a = [first_a]
    for _ in range(4):                       # A decodes alone ...
        toks_a.append(int(engine.advance()[slot_a]))

    joins_before = decode_metrics.snapshot()["joins"]
    assert engine.n_active() == 1
    slot_b, first_b = engine.start(pb, max_tokens=n_b, owner="B")
    assert slot_b != slot_a                          # joined, mid-flight
    toks_b = [first_b]
    while len(toks_a) < n_a or len(toks_b) < n_b:    # ... then together
        out = engine.advance()
        if len(toks_a) < n_a:
            toks_a.append(int(out[slot_a]))
        if len(toks_b) < n_b:
            toks_b.append(int(out[slot_b]))
    engine.release(slot_a)
    engine.release(slot_b)

    np.testing.assert_array_equal(toks_a, _solo(params, pa, n_a))
    np.testing.assert_array_equal(toks_b, _solo(params, pb, n_b))
    assert joins_before == decode_metrics.snapshot()["joins"]  # engine-level


def test_busy_batcher_token_parity(params, engine):
    """Batcher-level: requests submitted concurrently into a busy batch
    (later ones join mid-flight) all match their solo runs."""
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, CFG.vocab_size, size=n).astype(np.int32)
               for n in (5, 9, 3, 14)]
    n_tok = 16
    refs = [_solo(params, p, n_tok) for p in prompts]

    joins_before = decode_metrics.snapshot()["joins"]
    with ContinuousBatcher(engine, default_max_tokens=n_tok) as cb:
        first_wave = [cb.submit(p, max_tokens=n_tok) for p in prompts[:3]]
        # wait until the first wave is actually decoding ...
        for r in first_wave:
            next(r.stream(30))
        # ... then join a probe mid-flight
        probe = cb.submit(prompts[3], max_tokens=n_tok)
        outs = [r.result(60) for r in first_wave] + [probe.result(60)]
    for ref, out in zip(refs, outs):
        np.testing.assert_array_equal(out, ref)
    assert decode_metrics.snapshot()["joins"] > joins_before


def test_sampling_reproducible_across_placement(params, engine):
    """temperature>0 sampling keys fold (seed, position) — NOT the slot
    or the step — so the same request resampled in a different batch
    context yields the identical continuation."""
    rng = np.random.RandomState(3)
    p = rng.randint(1, CFG.vocab_size, size=6).astype(np.int32)
    with ContinuousBatcher(engine, default_max_tokens=10) as cb:
        solo_run = cb.submit(p, max_tokens=10, temperature=0.8,
                             seed=42).result(60)
        # same request again, this time racing three other streams
        others = [cb.submit(rng.randint(1, CFG.vocab_size, size=4),
                            max_tokens=12, temperature=0.5, seed=i)
                  for i in range(3)]
        busy_run = cb.submit(p, max_tokens=10, temperature=0.8,
                             seed=42).result(60)
        for o in others:
            o.result(60)
    np.testing.assert_array_equal(solo_run, busy_run)


# -- EOS + slot recycling ---------------------------------------------------

def test_eos_ends_early_and_recycles_slots(params, engine):
    rng = np.random.RandomState(4)
    p = rng.randint(1, CFG.vocab_size, size=5).astype(np.int32)
    ref = _solo(params, p, 8)
    eos = int(ref[3])
    stop = int(np.argmax(ref == eos))        # first occurrence ends it
    with ContinuousBatcher(engine, default_max_tokens=8) as cb:
        out = cb.submit(p, max_tokens=20, eos_id=eos).result(60)
        # stopped AT the first (included) eos token, well under budget
        np.testing.assert_array_equal(out, ref[:stop + 1])
        assert out[-1] == eos and len(out) < 20

        # recycling: 3x more requests than slots all complete, and the
        # engine ends fully drained
        prompts = [rng.randint(1, CFG.vocab_size, size=4 + i % 5)
                   for i in range(12)]
        outs = [cb.submit(q.astype(np.int32), max_tokens=5)
                for q in prompts]
        for r in outs:
            assert r.result(120).shape == (5,)
    assert engine.n_active() == 0
    assert engine.free_slot() == 0


def test_request_streaming_matches_result(params, engine):
    rng = np.random.RandomState(5)
    p = rng.randint(1, CFG.vocab_size, size=4).astype(np.int32)
    with ContinuousBatcher(engine, default_max_tokens=6) as cb:
        r = cb.submit(p, max_tokens=6)
        streamed = list(r.stream(30))
        np.testing.assert_array_equal(streamed, r.result(1))
        assert r.ttft_ms is not None and r.ttft_ms >= 0.0


def test_oversize_prompt_rejected_synchronously(params, engine):
    with ContinuousBatcher(engine) as cb:
        with pytest.raises(ValueError, match="largest bucket"):
            cb.submit(np.ones(60, np.int32), max_tokens=32)
        with pytest.raises(ValueError, match="empty prompt"):
            cb.submit(np.zeros(0, np.int32), max_tokens=4)


# -- steady-state compile freedom -------------------------------------------

def test_zero_steady_state_compiles(params, engine):
    """After warmup, ANY mix of prompt lengths, joins, EOS exits and
    slot reuse across both buckets dispatches only cached programs."""
    decode_metrics.mark_compiles()
    rng = np.random.RandomState(6)
    with ContinuousBatcher(engine, default_max_tokens=6) as cb:
        handles = [cb.submit(rng.randint(1, CFG.vocab_size,
                                         size=rng.randint(2, 40)),
                             max_tokens=int(rng.randint(3, 12)))
                   for _ in range(10)]
        for h in handles:
            h.result(120)
    assert decode_metrics.snapshot()["compile_delta_since_mark"] == 0


def test_warmup_compile_count_bounded_by_buckets(params):
    """A fresh engine geometry pre-traces exactly 2 executables per
    bucket (prefill + step), then serves compile-free."""
    eng = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                       prefill_chunk=16, label="decode-warmup-test")
    stats = eng.warmup()
    assert stats["buckets"] == 1
    assert stats["compiles"] == 2
    # warming again is free — both programs are cached
    assert eng.warmup()["compiles"] == 0


# -- router -----------------------------------------------------------------

def test_router_least_depth_dispatch(params, engine):
    """Two replicas: concurrent submissions spread by queue depth."""
    eng2 = DecodeEngine(CFG, params, n_slots=4, buckets=(32, 64))
    eng2.warmup()                            # cache-hit, no new compiles
    b1 = ContinuousBatcher(engine, default_max_tokens=12)
    b2 = ContinuousBatcher(eng2, default_max_tokens=12)
    router = Router([b1, b2], max_queue_depth=8)
    rng = np.random.RandomState(7)
    with router:
        h1 = router.submit(rng.randint(1, 64, size=4), max_tokens=12)
        h2 = router.submit(rng.randint(1, 64, size=4), max_tokens=12)
        depths = router.depths()
        assert sorted(depths) == [1, 1] or sum(depths) < 2  # may finish
        h1.result(60), h2.result(60)


def test_router_load_shed(params, engine):
    """Above the queue-depth bound every submit is shed with the typed
    error (booked in decode_metrics), and in-flight work still
    completes."""
    b = ContinuousBatcher(engine, default_max_tokens=24)
    router = Router([b], max_queue_depth=1)
    shed_before = decode_metrics.snapshot()["requests_shed"]
    rng = np.random.RandomState(8)
    with router:
        # 56 tokens (the max_len=64 budget): the in-flight window must
        # comfortably outlast a scheduler stall between the two submits
        # on a loaded 1-core CI host — 24 tokens was observed flaky
        keep = router.submit(rng.randint(1, 64, size=4), max_tokens=56)
        with pytest.raises(OverloadedError) as ei:
            # depth >= 1 until `keep` finishes: decode of 56 tokens is
            # far slower than this submit
            router.submit(rng.randint(1, 64, size=4), max_tokens=4)
        assert ei.value.bound == 1 and ei.value.replicas == 1
        assert keep.result(60).shape == (56,)
    assert decode_metrics.snapshot()["requests_shed"] == shed_before + 1


def test_router_replicate_serves_the_measured_engine(params):
    """``Router.replicate`` builds the engine the benchmark's cells
    measure: replicas with a page pool, greedy streams equal to the
    unbatched ``generate`` on both rungs, every page back after
    ``close()``."""
    router = Router.replicate(CFG, params, 2, n_slots=2, buckets=(32, 64),
                              prefill_chunk=8)
    rng = np.random.RandomState(12)
    # the third prompt spans five pages and lands on the 64 rung
    prompts = [rng.randint(1, CFG.vocab_size, size=n).astype(np.int32)
               for n in (5, 19, 40)]
    with router:
        handles = [router.submit(p, max_tokens=8) for p in prompts]
        outs = [h.result(120) for h in handles]
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _solo(params, p, 8))
    assert len(router.batchers) == 2
    for b in router.batchers:
        assert isinstance(b.engine._alloc, PageAllocator)
        assert _every_page_is_back(b.engine)


def test_router_validation():
    with pytest.raises(ValueError):
        Router([], max_queue_depth=4)
    with pytest.raises(ValueError):
        Router.replicate(CFG, {}, 0)


# -- concurrency ------------------------------------------------------------

def test_many_concurrent_clients(params, engine):
    """8 client threads x 2 requests against 4 slots: all complete,
    all match solo refs (greedy f32), occupancy is booked."""
    n_tok = 6
    rng = np.random.RandomState(9)
    prompts = [rng.randint(1, CFG.vocab_size, size=3 + i % 7)
               .astype(np.int32) for i in range(16)]
    refs = [_solo(params, p, n_tok) for p in prompts]
    outs = [None] * 16
    errs = []
    with ContinuousBatcher(engine, default_max_tokens=n_tok) as cb:
        def client(i):
            try:
                outs[i] = cb.submit(prompts[i], max_tokens=n_tok
                                    ).result(120)
            except Exception as e:          # pragma: no cover
                errs.append(e)
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
    assert not errs
    for ref, out in zip(refs, outs):
        np.testing.assert_array_equal(out, ref)
    snap = decode_metrics.snapshot()
    assert 0.0 < snap["slot_occupancy"] <= 1.0


def test_close_drains_accepted_requests(params, engine):
    rng = np.random.RandomState(10)
    cb = ContinuousBatcher(engine, default_max_tokens=10)
    h = cb.submit(rng.randint(1, 64, size=5), max_tokens=10)
    cb.close()
    assert h.result(1).shape == (10,)        # ran to completion
    with pytest.raises(RuntimeError, match="closed"):
        cb.submit(rng.randint(1, 64, size=5))


# -- one slot table, one dispatch a round -----------------------------------
#
# A rung of the ladder is a compiled page-table WIDTH: requests of every
# rung sit in the engine's one table of ``n_slots`` slots and advance in
# ONE dispatch, at the narrowest width that covers the longest running
# one.  Both model families, one engine each for the whole section.

LADDER = (32, 64, 128)


class _Family(NamedTuple):
    name: str
    cfg: Any
    engine: DecodeEngine
    solo: Callable            # (prompt, n) -> the n greedy tokens


def _dense_greedy(forward, vocab_rows, prompt, n):
    """Greedy continuation by the family's cache-less forward over the
    whole row, padded to the model's positions (causal: the padding is
    never attended), one token at a time."""
    row = [int(t) for t in prompt]
    for _ in range(n):
        ids = np.zeros((1, LADDER[-1]), np.int32)
        ids[0, :len(row)] = row
        logits = np.asarray(forward(jnp.asarray(ids)))[0, len(row) - 1]
        row.append(int(np.argmax(logits[:vocab_rows])))
    return row[len(prompt):]


@pytest.fixture(scope="module", params=["gpt", "deepseek_v2"])
def family(request):
    if request.param == "gpt":
        cfg = dataclasses.replace(CFG, max_len=LADDER[-1])
        params = gpt.init_params(jax.random.key(7), cfg)

        def solo(prompt, n):
            out = gpt.generate(cfg, params,
                               np.asarray(prompt, np.int32)[None, :], n,
                               jax.random.key(0), temperature=0.0)
            return [int(t) for t in np.asarray(out)[0]]
    else:
        cfg = ds.tiny_config(max_len=LADDER[-1], compute_dtype="float32")
        params = ds.init_params(jax.random.key(0), cfg, std=0.3)
        forward = jax.jit(lambda ids: ds.forward_logits(cfg, params, ids))

        def solo(prompt, n):
            return _dense_greedy(forward, cfg.vocab_size, prompt, n)
    eng = DecodeEngine(cfg, params, n_slots=3, buckets=LADDER,
                       prefill_chunk=8, label=f"one-table-{request.param}")
    eng.warmup()
    yield _Family(request.param, cfg, eng, solo)
    assert _every_page_is_back(eng)


def _prompt(fam, n, seed):
    return np.random.RandomState(seed).randint(
        1, fam.cfg.vocab_size, size=n).astype(np.int32)


def _counts():
    snap = decode_metrics.snapshot()
    return {k: snap[k] for k in ("decode_dispatches", "decode_dispatch_rungs",
                                 "decode_table_rows", "rounds")}


def _delta(before):
    return {k: v - before[k] for k, v in _counts().items()}


def test_three_rungs_advance_in_one_dispatch(family):
    """Requests of three different rungs run together: ONE dispatch
    carries all three (the parent made three), and each request's
    tokens are its stand-alone reference stream bit for bit — by hand
    on the engine, then through the batcher, a round a dispatch."""
    eng, n = family.engine, 12
    prompts = [_prompt(family, t, 20 + t) for t in (5, 40, 90)]
    assert [eng.pick_bucket(p.size + n) for p in prompts] == list(LADDER)
    refs = [family.solo(p, n) for p in prompts]

    before = _counts()
    placed = [eng.start(p, max_tokens=n) for p in prompts]
    outs = [[first] for _, first in placed]
    for _ in range(n - 1):
        toks = eng.advance()
        assert eng.last_ran().all()
        for out, (slot, _) in zip(outs, placed):
            out.append(int(toks[slot]))
    for slot, _ in placed:
        eng.release(slot)
    assert outs == refs
    got = _delta(before)
    assert got["decode_dispatches"] == n - 1
    assert got["decode_dispatch_rungs"] == 3 * (n - 1)

    before = _counts()
    with ContinuousBatcher(eng) as cb:
        handles = [cb.submit(p, max_tokens=n) for p in prompts]
        served = [h.result(120).tolist() for h in handles]
    assert served == refs
    got = _delta(before)
    assert got["decode_dispatches"] == got["rounds"] > 0
    assert got["decode_dispatch_rungs"] > got["decode_dispatches"]


def test_width_follows_the_longest_running_slot(family):
    """A request that crosses a rung edge mid-flight (its step at
    position 63 reads a 64-wide table, the one at 64 a 128-wide one)
    widens the dispatch at that step and the dispatch narrows again
    when it leaves; tokens are the references', and nothing is traced
    or compiled across the changes."""
    eng = family.engine
    long_p, short_p = _prompt(family, 60, 1), _prompt(family, 5, 2)
    n_long, n_short = 8, 14
    ref_long = family.solo(long_p, n_long)
    ref_short = family.solo(short_p, n_short)
    compiles = compile_metrics.snapshot()["compile_count"]

    s_long, first = eng.start(long_p, max_tokens=n_long)
    out_long = [first]
    s_short, first = eng.start(short_p, max_tokens=n_short)
    out_short = [first]
    widths = []
    while len(out_short) < n_short:
        before = _counts()
        toks = eng.advance()
        widths.append(_delta(before)["decode_table_rows"] // eng.n_slots)
        if len(out_long) < n_long:
            out_long.append(int(toks[s_long]))
            if len(out_long) == n_long:
                eng.release(s_long)
        out_short.append(int(toks[s_short]))
    eng.release(s_short)
    # the long request feeds positions 60..66, then the short one is
    # alone at positions 12..17
    assert widths == [64] * 4 + [128] * 3 + [32] * 6
    assert out_long == ref_long and out_short == ref_short
    assert compile_metrics.snapshot()["compile_count"] == compiles


def test_n_slots_bounds_the_engine_not_a_rung(family):
    """``n_slots`` requests of mixed rungs fill the engine: one more,
    of any rung, waits in the queue and is admitted when any slot
    frees; every page is back after ``close()``."""
    eng = family.engine
    prompts = [_prompt(family, t, 40 + t) for t in (4, 36, 80)]
    n = 30
    fourth = _prompt(family, 6, 9)
    refs = [family.solo(p, n) for p in prompts] + [family.solo(fourth, 5)]
    with ContinuousBatcher(eng) as cb:
        handles = [cb.submit(p, max_tokens=n) for p in prompts]
        streams = [h.stream(60) for h in handles]
        for st in streams:
            next(st)                         # all three are placed
        assert eng.free_slot() is None and not eng.can_admit(1)
        with pytest.raises(RuntimeError, match="no free slot"):
            eng.start(fourth, max_tokens=5)
        late = cb.submit(fourth, max_tokens=5)        # the smallest rung
        for _ in range(3):                   # rounds pass, it still waits:
            next(streams[0])                 # no slot frees before token 30
        assert len(late._tokens) == 0 and cb.depth() == 4
        outs = [h.result(120).tolist() for h in handles]
        outs.append(late.result(120).tolist())
    assert outs == refs
    assert _every_page_is_back(eng)


def test_dispatch_counters_against_a_hand_count(family):
    """``decode_dispatch_rungs`` sums the distinct rungs a dispatch
    carried, ``decode_table_rows`` ``n_slots`` x its table width."""
    eng = family.engine
    S = eng.n_slots
    before = _counts()
    occupancy = decode_metrics.slot_steps
    a, _ = eng.start(_prompt(family, 5, 3), max_tokens=4)       # rung 32
    b, _ = eng.start(_prompt(family, 70, 4), max_tokens=6)      # rung 128
    for _ in range(3):
        eng.advance()               # both: 2 rungs, b at 70..72 -> 128
    eng.release(a)
    for _ in range(2):
        eng.advance()               # b alone, still 128 wide
    eng.release(b)
    c, _ = eng.start(_prompt(family, 3, 5), max_tokens=3)
    for _ in range(2):
        eng.advance()               # a lone short request: 32 wide
    eng.release(c)
    assert _delta(before) == {
        "decode_dispatches": 7, "decode_dispatch_rungs": 2 * 3 + 2 + 2,
        "decode_table_rows": S * 128 * 5 + S * 32 * 2, "rounds": 0}
    assert decode_metrics.slot_steps - occupancy == 2 * 3 + 2 + 2


def test_speculative_rounds_share_the_one_table(params):
    """The speculative path on the one table: mixed-rung requests get
    one draft + verify pair a round, and every stream is the
    non-speculative engine's bit for bit."""
    cfg = dataclasses.replace(CFG, max_len=LADDER[-1])
    dcfg = dataclasses.replace(cfg, hidden=16, n_layers=1, ffn_dim=32)
    tparams = gpt.init_params(jax.random.key(7), cfg)
    dparams = gpt.init_params(jax.random.key(8), dcfg)
    kw = dict(n_slots=3, buckets=LADDER, prefill_chunk=8)
    plain = DecodeEngine(cfg, tparams, label="one-table-plain", **kw)
    spec = DecodeEngine(cfg, tparams, label="one-table-spec",
                        draft=(dcfg, dparams), draft_k=3, **kw)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, cfg.vocab_size, size=t).astype(np.int32)
               for t in (6, 38, 85, 20)]
    budgets = [20, 24, 30, 9]
    assert {plain.pick_bucket(p.size + m)
            for p, m in zip(prompts, budgets)} == set(LADDER)
    streams = []
    for eng in (plain, spec):
        eng.warmup()
        before = _counts()
        with ContinuousBatcher(eng) as cb:
            handles = [cb.submit(p, max_tokens=m, temperature=0.7, seed=i)
                       for i, (p, m) in enumerate(zip(prompts, budgets))]
            streams.append([h.result(120).tolist() for h in handles])
        got = _delta(before)
        assert got["decode_dispatches"] == got["rounds"] > 0
        assert decode_metrics.snapshot()["compile_delta_since_mark"] == 0
        assert _every_page_is_back(eng)
    assert streams[0] == streams[1]
    assert [len(o) for o in streams[1]] == budgets
    assert decode_metrics.snapshot()["draft_proposed"] > 0


# -- one step ahead of the fetch ---------------------------------------------
# A batcher dispatches step n+1 from step n's tokens ON THE DEVICE, then
# fetches and delivers step n while n+1 runs.  The streams are the ones
# ``advance()`` gives token by token, collected at once (the same two
# halves back to back), for every family that serves.

AHEAD_LADDER = (32, 64)


class _Ahead(NamedTuple):
    name: str
    cfg: Any
    params: Any
    engine: DecodeEngine
    make: Callable            # (**engine keywords) -> an engine like it


def _toy_model(name):
    from deeplearning4j_tpu.models import exaone_moe as ex, mellum as ml

    if name == "gpt":
        return CFG, gpt.init_params(jax.random.key(7), CFG)
    mod = {"deepseek_v2": ds, "mellum": ml, "exaone_moe": ex}[name]
    cfg = mod.tiny_config(compute_dtype="float32",
                          **({"max_len": 64} if name == "deepseek_v2"
                             else {}))
    return cfg, mod.init_params(jax.random.key(0), cfg, std=0.3)


@pytest.fixture(scope="module",
                params=["gpt", "deepseek_v2", "mellum", "exaone_moe"])
def ahead(request):
    cfg, params = _toy_model(request.param)

    def make(**kw):
        eng = DecodeEngine(cfg, params, n_slots=3, buckets=AHEAD_LADDER,
                           prefill_chunk=8,
                           label=f"ahead-{request.param}", **kw)
        eng.warmup()
        return eng
    eng = make()
    yield _Ahead(request.param, cfg, params, eng, make)
    assert _every_page_is_back(eng)


def _sync_stream(eng, prompt, n, temperature=0.0, seed=0):
    """The reference: ``n`` tokens of one request alone, every step
    collected before the next is dispatched."""
    slot, first = eng.start(prompt, max_tokens=n, temperature=temperature,
                            seed=seed)
    toks = [first] + [int(eng.advance()[slot]) for _ in range(n - 1)]
    eng.release(slot)
    return toks


_AHEAD_KEYS = ("decode_dispatches", "decode_dispatches_ahead",
               "decode_overshoot_steps", "requests_replayed",
               "deadline_expirations", "rounds")


def _ahead_counts():
    snap = decode_metrics.snapshot()
    return {**{k: snap[k] for k in _AHEAD_KEYS},
            "slot_steps": decode_metrics.slot_steps}


def _ahead_delta(before):
    return {k: v - before[k] for k, v in _ahead_counts().items()}


_backend_compiles = []


def _xla_compiles():
    """Lowerings handed to the backend so far (a recompile without a
    retrace shows only here; the listener is registered once)."""
    if not _backend_compiles:
        import jax.monitoring as mon

        _backend_compiles.append(0)

        def on_duration(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _backend_compiles[0] += 1
        mon.register_event_duration_secs_listener(on_duration)
    return _backend_compiles[0]


@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "sampled"])
def test_streams_one_step_ahead_are_the_synchronous_ones(ahead, temperature):
    """Mixed lengths across both rungs, more requests than slots, budgets
    of one and two tokens among them: every stream is, token for token,
    what ``advance()`` gives one step at a time; no slot runs a step past
    its budget; nothing traces or compiles."""
    eng = ahead.engine
    lengths = (5, 20, 9, 30, 12, 3, 17, 26)
    budgets = (10, 30, 4, 25, 2, 1, 12, 7)
    prompts = [_prompt(ahead, n, 40 + i)
               for i, n in enumerate(lengths)]
    assert {eng.pick_bucket(n + m) for n, m in zip(lengths, budgets)} \
        == set(AHEAD_LADDER)
    decode_metrics.mark_compiles()
    xla, before = _xla_compiles(), _ahead_counts()
    with ContinuousBatcher(eng) as cb:
        handles = [cb.submit(p, max_tokens=m, temperature=temperature,
                             seed=100 + i)
                   for i, (p, m) in enumerate(zip(prompts, budgets))]
        got = [h.result(120).tolist() for h in handles]
    d = _ahead_delta(before)
    assert decode_metrics.snapshot()["compile_delta_since_mark"] == 0
    assert _xla_compiles() == xla
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        assert got[i] == _sync_stream(eng, p, m, temperature, 100 + i), i
    # the first token is the prefill's: a request of m tokens is m - 1
    # slot steps, and not one more for a slot whose budget has ended
    assert d["slot_steps"] == sum(m - 1 for m in budgets)
    assert d["decode_overshoot_steps"] == 0
    assert 0 < d["decode_dispatches_ahead"] < d["decode_dispatches"]
    assert eng.pages_unaccounted() == 0 and _every_page_is_back(eng)


def test_a_budget_that_ends_frees_the_slot_for_its_successor(ahead):
    """Nine requests of five tokens queued on three slots: a slot is
    released when its last step is DISPATCHED, so its successor joins
    while that token is in flight and no slot sits out a round: three
    waves of four steps are twelve dispatches, every one full.  The
    successor's first token and stream are the reference's."""
    eng = ahead.engine
    prompts = [_prompt(ahead, 6 + i, 60 + i) for i in range(9)]
    before = _ahead_counts()
    with ContinuousBatcher(eng) as cb:
        with cb._cv:        # all nine queued before the worker's first pass
            handles = [cb.submit(p, max_tokens=5) for p in prompts]
        got = [h.result(120).tolist() for h in handles]
    d = _ahead_delta(before)
    want = [_sync_stream(eng, p, 5) for p in prompts]
    assert [g[0] for g in got] == [w[0] for w in want]
    assert got == want
    assert d["decode_dispatches"] == 12 and d["slot_steps"] == 36
    assert _every_page_is_back(eng)


def test_eos_ends_the_stream_at_the_eos_token_one_step_late(ahead):
    """An end by ``eos_id`` is known when the token lands, one step
    after the next was dispatched: the stream ends AT the eos token, the
    one step past it is counted and its token dropped, and its row costs
    no page."""
    eng = ahead.engine
    prompt = _prompt(ahead, 11, 71)
    # a sampled stream, so that some token past the second is its first
    # of that value (the toy models' greedy streams repeat one token)
    ref = _sync_stream(eng, prompt, 14, 1.0, 3)
    k = next(k for k in range(2, 13) if ref[k] not in ref[:k])
    before = _ahead_counts()
    with ContinuousBatcher(eng) as cb:
        got = cb.submit(prompt, max_tokens=14, temperature=1.0, seed=3,
                        eos_id=ref[k]).result(120)
    d = _ahead_delta(before)
    assert got.tolist() == ref[:k + 1]
    assert d["decode_overshoot_steps"] == 1
    assert d["slot_steps"] == k + 1
    assert eng.pages_unaccounted() == 0 and _every_page_is_back(eng)


class _Gate:
    """Holds the worker at the entry of its ``n``-th ``dispatch_step``
    (the step before it dispatched and not collected) until the test
    has done what it came to do."""

    def __init__(self, eng, n):
        self.eng, self.n, self.calls = eng, n, 0
        self.reached, self.go = threading.Event(), threading.Event()
        real = eng.dispatch_step

        def gated(*a, **kw):
            self.calls += 1
            if self.calls == self.n:
                self.reached.set()
                assert self.go.wait(60)
            return real(*a, **kw)
        eng.dispatch_step = gated

    def open(self):
        self.go.set()

    def remove(self):
        self.go.set()
        self.eng.__dict__.pop("dispatch_step", None)


@pytest.mark.parametrize("what", ["deadline", "evacuate",
                                  "dispatch_failure", "collect_failure"])
def test_an_end_with_a_step_in_flight_loses_no_page_and_no_token(ahead,
                                                                 what):
    """With step 4 dispatched and not collected: a deadline frees the
    slot and drops that step's token; an ``evacuate()`` hands every
    request over, the last token in flight with it; a dispatch that
    fails (at the call, or on the device, where it now shows at the
    fetch) replays every request.  Whatever is delivered is the
    reference's, token for token, and every page comes back."""
    from deeplearning4j_tpu.parallel.chaos import ServingChaos
    from deeplearning4j_tpu.serving.decode import (DeadlineExceeded,
                                                   _ReplayRequest)

    eng = ahead.engine
    served = ahead.make() if what == "evacuate" else eng
    pa, pb = _prompt(ahead, 9, 81), _prompt(ahead, 21, 82)
    ref_a = _sync_stream(eng, pa, 16, 0.8, 5)
    ref_b = _sync_stream(eng, pb, 12, 0.0, 6)
    before = _ahead_counts()
    gate = _Gate(served, 5)
    try:
        with ContinuousBatcher(served) as cb:
            a = cb.submit(pa, max_tokens=16, temperature=0.8, seed=5)
            b = cb.submit(pb, max_tokens=12, seed=6)
            assert gate.reached.wait(60)
            if what == "deadline":
                a._deadline = time.perf_counter() - 1.0
                a.deadline_ms = 1.0
            elif what == "dispatch_failure":
                ServingChaos(cb).poison_dispatch(1)
            elif what == "collect_failure":
                def failing_once(out):
                    del served._fetch
                    raise RuntimeError("injected: the step failed on "
                                       "the device")
                served._fetch = failing_once
            else:
                moved = cb.evacuate()
                assert {r.rid for r in moved} == {a.rid, b.rid}
            gate.open()
            if what == "evacuate":
                with ContinuousBatcher(eng) as adopter:
                    for r in moved:
                        adopter.resubmit(_ReplayRequest(r))
                    got_a, got_b = a.result(120), b.result(120)
            elif what == "deadline":
                with pytest.raises(DeadlineExceeded):
                    a.result(120)
                got_a, got_b = a._snapshot_tokens(), b.result(120)
            else:
                got_a, got_b = a.result(120), b.result(120)
    finally:
        gate.remove()
    d = _ahead_delta(before)
    if what == "deadline":
        assert 4 <= len(got_a) < 16 and d["deadline_expirations"] == 1
        assert d["decode_overshoot_steps"] == 1     # the step in flight
        assert got_a.tolist() == ref_a[:len(got_a)]
    else:
        assert got_a.tolist() == ref_a
    assert got_b.tolist() == ref_b
    if what.endswith("failure"):
        assert d["requests_replayed"] == 2
    assert eng.pages_unaccounted() == 0 and _every_page_is_back(eng)


def test_a_steady_run_is_ahead_and_two_steps_ahead_raises(ahead):
    """Three long requests in step: every dispatch but the first takes
    its tokens from the step before it on the device.  The engine lets a
    step run ONE ahead of its fetch, never two."""
    eng = ahead.engine
    prompts = [_prompt(ahead, 8, 90 + i) for i in range(3)]
    before = _ahead_counts()
    with ContinuousBatcher(eng) as cb:
        for h in [cb.submit(p, max_tokens=40) for p in prompts]:
            assert len(h.result(120)) == 40
    d = _ahead_delta(before)
    assert d["decode_dispatches_ahead"] > 0.9 * d["decode_dispatches"] > 0
    assert d["decode_dispatches"] == d["rounds"]

    slot, _ = eng.start(prompts[0], max_tokens=8)
    first = eng.dispatch_step()
    second = eng.dispatch_step(after=first)
    with pytest.raises(RuntimeError, match="ONE step ahead"):
        eng.dispatch_step(after=second)
    one = int(eng.collect(first)[slot])
    third = eng.dispatch_step(after=second)
    two, three = int(eng.collect(second)[slot]), int(eng.collect(third)[slot])
    eng.release(slot)
    assert [one, two, three] == _sync_stream(eng, prompts[0], 4)[1:]


def test_a_speculative_round_runs_one_round_ahead():
    """``draft="self"``: a round's commit count is data, but the device
    merges it into the next round, so every round but the first is
    dispatched before the one before it is fetched, and the streams are
    ``advance_spec()``'s in series."""
    cfg, params = _toy_model("exaone_moe")
    eng = DecodeEngine(cfg, params, n_slots=2, buckets=(32,),
                       prefill_chunk=8, draft="self",
                       label="ahead-self-draft")
    eng.warmup()
    prompts = [np.arange(1, 8, dtype=np.int32),
               np.arange(3, 14, dtype=np.int32)]
    want = []
    for p in prompts:
        slot, first = eng.start(p, max_tokens=12)
        toks = [first]
        while len(toks) < 12:
            out, n_c = eng.advance_spec()
            toks += out[slot, :int(n_c[slot])].tolist()
        eng.release(slot)
        want.append(toks[:12])
    before = _ahead_counts()
    with ContinuousBatcher(eng) as cb:
        outs = [h.result(120).tolist() for h in
                [cb.submit(p, max_tokens=12) for p in prompts]]
    d = _ahead_delta(before)
    assert outs == want
    assert d["decode_dispatches_ahead"] > 0.9 * d["decode_dispatches"] > 0
    assert _every_page_is_back(eng)


# -- the shared engine, last ------------------------------------------------

def test_shared_engine_has_every_page_back(params, engine):
    """Whatever this module's tests put through the shared default
    engine before this one (joins, EOS recycling, sampled placements,
    routers, 16 concurrent clients, a draining close), and a prompt of
    two pages after them: no slot is left active and no page is held
    or unaccounted for.  The fixture asserts the same at teardown."""
    prompt = np.arange(1, 41, dtype=np.int32)
    with ContinuousBatcher(engine, default_max_tokens=6) as cb:
        out = cb.submit(prompt).result(120)
    np.testing.assert_array_equal(out, _solo(params, prompt, 6))
    assert engine._resident                  # the prompt's first page
    assert _every_page_is_back(engine)
    assert engine._alloc.n_free() == engine.n_kv_pages - 1
