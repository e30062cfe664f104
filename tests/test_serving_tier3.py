"""Serving tier 3: paged KV cache, speculative decoding, and the
zero-downtime weight swap.

The load-bearing properties:

- the ``PageAllocator`` never double-assigns a page, reclaims freed
  pages, is all-or-nothing (typed :class:`KVPagesExhausted` on
  shortfall), and keeps EXACT occupancy under a randomized
  admit/extend/free schedule;
- the engine is BIT-identical to the plain references that know no
  pages (``gpt.generate``; a dense forward a token with
  ``sample_token`` under the position key ``_slot_key``) — greedy and
  sampled, fp32 and int8 — because paging only indexes KV storage,
  never changes a single matmul;
- a pool-resident prefix hit mounts pages BY REFERENCE (refcounts, no
  copy) and a released slot returns its pages to the pool;
- speculative decoding is bit-identical to plain decode at ANY
  temperature (position-keyed sampling), proposes/accepts are booked,
  and the whole stack composes: pages + draft + int8 + batcher;
- oversize admits fail SYNCHRONOUSLY with the typed error;
- ``rebind_params`` requires an idle engine and flips outputs to the
  new checkpoint with zero new compiles; the router's
  ``swap_weights`` rolls a live fleet with zero dropped requests;
- every tier-3 path preserves the zero-steady-state-compile contract.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import gpt
from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.parallel.chaos import ServingChaos
from deeplearning4j_tpu.runtime import quantize as qz, telemetry
from deeplearning4j_tpu.runtime.metrics import decode_metrics
from deeplearning4j_tpu.serving.decode import (KV_PAGE_TOKENS,
                                               BatcherClosed,
                                               ContinuousBatcher,
                                               DeadlineExceeded,
                                               DecodeEngine,
                                               KVPagesExhausted,
                                               PageAllocator, PrefixCache)
from deeplearning4j_tpu.serving.router import (AutoscalePolicy,
                                               AutoscalingRouter,
                                               OverloadedError,
                                               ReplicaHealth, RouterClosed,
                                               SwapFailed)

CFG = TransformerConfig(vocab_size=64, max_len=64, hidden=32, n_layers=2,
                        n_heads=2, ffn_dim=64, dropout=0.0,
                        compute_dtype="float32", causal=True,
                        type_vocab_size=1)
DCFG = TransformerConfig(vocab_size=64, max_len=64, hidden=16, n_layers=1,
                         n_heads=2, ffn_dim=32, dropout=0.0,
                         compute_dtype="float32", causal=True,
                         type_vocab_size=1)


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(jax.random.key(7), CFG)


@pytest.fixture(scope="module")
def dparams():
    return gpt.init_params(jax.random.key(3), DCFG)


def _solo(p, prompt, n_tokens, cfg=CFG):
    out = gpt.generate(cfg, p, np.asarray(prompt, np.int32)[None, :],
                       n_tokens, jax.random.key(0), temperature=0.0)
    return list(np.asarray(out)[0])


def _reference(p, prompt, n_tokens, temperature=0.0, seed=0, cfg=CFG,
               int8_kv=False):
    """The plain reference for ANY temperature, sharing no cache
    plumbing with the engine: every token from a dense pass over the
    whole sequence so far (``forward_logits``; for an int8 cache
    ``_prefill_chunk`` over a fresh ``QKVCache``, which quantizes each
    row as it writes it and attends over the rows dequantized) and
    ``sample_token`` under the key of the position that produced the
    logits, ``_slot_key(seed, pos)`` — what makes a stream independent
    of slot, rung, round and replica."""
    seq = [int(t) for t in prompt]
    for _ in range(n_tokens):
        toks = jnp.asarray(seq, jnp.int32)[None, :]
        if int8_kv:
            rows = (cfg.n_layers, 1, len(seq))
            kv = jnp.zeros(rows + (cfg.n_heads, cfg.head_dim), jnp.int8)
            sc = jnp.zeros(rows, jnp.float32)
            _, logits = gpt._prefill_chunk(
                cfg, p, gpt.QKVCache(kv, kv, sc, sc), toks, jnp.int32(0))
        else:
            logits = gpt.forward_logits(cfg, p, toks)
        key = gpt._slot_key(jnp.uint32(seed), jnp.int32(len(seq) - 1))
        seq.append(int(gpt.sample_token(logits[0, -1], key,
                                        jnp.float32(temperature))))
    return seq[len(prompt):]


def _engine_tokens(eng, prompt, n, temperature=0.0, seed=0):
    """Drive one request to n tokens through plain or speculative
    advance, honoring the ran-mask contract."""
    slot, first = eng.start(np.asarray(prompt, np.int32), max_tokens=n,
                            temperature=temperature, seed=seed)
    out = [first]
    while len(out) < n:
        if eng.draft is not None:
            toks, n_c = eng.advance_spec()
            for j in range(int(n_c[slot])):
                out.append(int(toks[slot, j]))
                if len(out) >= n:
                    break
        else:
            toks = eng.advance()
            if eng.last_ran()[slot]:
                out.append(int(toks[slot]))
    eng.release(slot)
    return out[:n]


# -- page allocator ---------------------------------------------------------

def test_kv_page_tokens_matches_prefill_chunk():
    """Drift guard: the page size IS the prefill chunk — prefix-cache
    chunks, pool pages, and prefill writes must stay aligned or the
    mount-by-reference path silently corrupts."""
    assert KV_PAGE_TOKENS == gpt.PREFILL_CHUNK


def test_page_allocator_properties():
    a = PageAllocator(8)                    # page 0 reserved: 7 usable
    assert a.n_free() == 7 and a.in_use() == 0
    ids = a.alloc(3)
    assert len(set(ids)) == 3 and 0 not in ids
    ids2 = a.alloc(4)
    assert not set(ids) & set(ids2)         # never double-assigned
    assert a.in_use() == 7
    with pytest.raises(KVPagesExhausted) as ei:
        a.alloc(1)                          # all-or-nothing
    assert ei.value.needed == 1 and ei.value.free == 0
    a.free(ids)
    assert set(a.alloc(3)) == set(ids)      # freed pages reusable
    # refcounted sharing: a shared page survives one free
    p = ids2[0]
    a.share([p])
    assert a.refcount(p) == 2
    a.free([p])
    assert a.refcount(p) == 1 and p not in a._free
    a.free([p])
    assert a.refcount(p) == 0
    with pytest.raises(ValueError):
        a.free([p])                         # double-free is typed
    with pytest.raises(ValueError):
        a.share([0])                        # reserved page never shared
    with pytest.raises(ValueError):
        PageAllocator(1)                    # nothing left after reserve


def test_page_allocator_randomized_schedule():
    """Exact occupancy under a randomized admit/free interleaving: no
    page ever lives in two requests, in_use tracks the live sum, and a
    fully-drained pool is fully free again."""
    rng = np.random.default_rng(0)
    a = PageAllocator(33)
    live = {}
    next_id = 0
    for _ in range(300):
        if live and (rng.random() < 0.4 or a.n_free() < 4):
            rid = list(live)[int(rng.integers(len(live)))]
            a.free(live.pop(rid))
        else:
            n = int(rng.integers(1, 5))
            if n > a.n_free():
                with pytest.raises(KVPagesExhausted):
                    a.alloc(n)
                continue
            ids = a.alloc(n)
            held = [p for ids_ in live.values() for p in ids_]
            assert not set(ids) & set(held)
            live[next_id] = ids
            next_id += 1
        assert a.in_use() == sum(len(v) for v in live.values())
    for ids in live.values():
        a.free(ids)
    assert a.in_use() == 0 and a.n_free() == 32


# -- the engine == the plain references -------------------------------------

def test_paged_greedy_bit_exact_and_pages_released(params):
    eng = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                       prefill_chunk=8)
    eng.warmup()
    prompt = np.arange(1, 7, dtype=np.int32)    # < one chunk: no harvest
    got = _engine_tokens(eng, prompt, 10)
    assert got == _solo(params, prompt, 10)
    assert eng._alloc.in_use() == 0             # release returned them
    snap = decode_metrics.snapshot()
    assert snap["pages_in_use"] == 0
    assert snap["pages_in_use_hw"] >= 2         # prompt page + growth


def test_int8_pool_matches_the_int8_cache_reference(params):
    """int8 weights and an int8 pool against the dequantized tree over
    a plain int8 ``QKVCache``: same rows, same scales, no pages."""
    eng = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                       prefill_chunk=8, quantize="int8", kv_dtype="int8")
    eng.warmup()
    prompt = np.arange(1, 13, dtype=np.int32)
    dq = qz.dequantize_tree(qz.quantize_tree(params, "int8"))
    got = _engine_tokens(eng, prompt, 10)
    assert got == _reference(dq, prompt, 10, int8_kv=True)
    assert eng._pool is not None and eng._pool.k.dtype == np.int8
    eng.drop_residents()                # the prompt's first page
    assert eng._alloc.in_use() + eng.pages_unaccounted() == 0


def test_sampled_matches_the_dense_reference(params):
    """A sampled stream is the dense forward's, key for key, and does
    not depend on where the request was placed: alone in a fresh
    one-slot engine, or second into another engine's second slot."""
    kw = dict(buckets=(32,), prefill_chunk=8)
    prompt = np.arange(1, 10, dtype=np.int32)
    want = _reference(params, prompt, 12, temperature=0.8, seed=5)
    assert len(set(want)) > 4           # sampled, not one token repeated
    alone = DecodeEngine(CFG, params, n_slots=1, **kw)
    alone.warmup()
    assert _engine_tokens(alone, prompt, 12, temperature=0.8,
                          seed=5) == want
    eng = DecodeEngine(CFG, params, n_slots=2, **kw)
    eng.warmup()
    other = eng.start(np.arange(20, 31, dtype=np.int32), max_tokens=20,
                      temperature=0.8, seed=1)
    assert _engine_tokens(eng, prompt, 12, temperature=0.8,
                          seed=5) == want
    eng.release(other[0])
    eng.drop_residents()                # each prompt's first page
    assert eng._alloc.in_use() + eng.pages_unaccounted() == 0


def _batched_streams(eng, prompts, budgets, temperature):
    """Every request through one ContinuousBatcher: more requests than
    slots, so streams join and leave the running rungs mid-flight."""
    bat = ContinuousBatcher(eng)
    try:
        reqs = [bat.submit(p, max_tokens=n, temperature=temperature, seed=i)
                for i, (p, n) in enumerate(zip(prompts, budgets))]
        return [list(r.result(180.0)) for r in reqs]
    finally:
        bat.close()


@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "sampled"])
def test_multi_rung_joins_and_leaves_match_the_references(params,
                                                          temperature):
    """Three rungs in flight, two slots a rung, seven requests: a round
    dispatches several rungs' programs against the ONE pool, slots fill
    and free between them, and every stream still equals the dense
    reference's under its own seed (and, greedy, the unbatched
    ``generate``) token for token."""
    # a config of its own: engines of one config and geometry share
    # their jitted programs, and other tests count their own traces
    cfg = dataclasses.replace(CFG, layer_norm_eps=2e-5)
    kw = dict(n_slots=2, buckets=(16, 32, 64), prefill_chunk=8)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 64, size=n).astype(np.int32)
               for n in (3, 20, 9, 40, 14, 27, 5)]
    budgets = [6, 10, 20, 12, 4, 30, 9]
    eng = DecodeEngine(cfg, params, **kw)
    assert {eng.pick_bucket(len(p) + n)
            for p, n in zip(prompts, budgets)} == {16, 32, 64}
    eng.warmup()
    got = _batched_streams(eng, prompts, budgets, temperature)
    assert got == [_reference(params, p, n, temperature, seed=i, cfg=cfg)
                   for i, (p, n) in enumerate(zip(prompts, budgets))]
    assert [len(g) for g in got] == budgets
    if temperature == 0.0:
        assert got == [_solo(params, p, n, cfg)
                       for p, n in zip(prompts, budgets)]
    eng.drop_residents()
    assert eng._alloc.in_use() == 0


def _random_pool(kv_dtype, n_pages=6, page_tokens=8):
    """A pool with no two rows alike, so a row that was written shows."""
    shapes = jax.eval_shape(
        lambda: gpt.init_pages(CFG, n_pages, page_tokens, kv_dtype))
    keys = iter(jax.random.split(jax.random.key(21), 4))

    def fill(a):
        x = jax.random.normal(next(keys), a.shape)
        return (x * 40).astype(a.dtype) if a.dtype == np.int8 \
            else (abs(x) + 0.1).astype(a.dtype)

    return gpt.PagedKV(*(None if a is None else fill(a) for a in shapes))


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["fp32", "int8"])
@pytest.mark.parametrize("fn", ["decode", "verify"])
def test_stale_and_inactive_writes_land_in_the_trash_page(params, fn,
                                                          kv_dtype):
    """Slot 1 is inactive and its stale table still names page 2, which
    was freed and handed to live slot 0; slot 2 is live but runs past
    its table.  Only slot 0's fresh rows (and slot 2's one in-range row)
    change the pool outside page 0 — every other row of every array,
    page 2's among them, stays bit-identical."""
    C = 8
    pool = _random_pool(kv_dtype, page_tokens=C)
    ptab = np.array([[1, 2], [2, 0], [3, 4]], np.int32)
    tokens = np.array([5, 6, 7], np.int32)
    active = np.array([True, False, True])
    temps = np.zeros((3,), np.float32)
    seeds = np.zeros((3,), np.uint32)
    if fn == "decode":
        pos = np.array([9, 5, 16], np.int32)
        new, _ = jax.jit(lambda *a: gpt.paged_decode(CFG, *a))(
            params, pool, ptab, tokens, pos, active, temps, seeds)
        want = {(2, 1), (0, 5), (0, 7)}
    else:
        pos = np.array([9, 5, 15], np.int32)
        drafts = np.array([[1, 2], [3, 4], [5, 6]], np.int32)
        new, _, n_commit = jax.jit(lambda *a: gpt.paged_verify(CFG, *a))(
            params, pool, ptab, tokens, pos, active, temps, seeds, drafts)
        assert int(n_commit[1]) == 0
        want = {(2, 1), (2, 2), (2, 3), (0, 5), (0, 6), (0, 7), (4, 7)}
    for old, got in zip(pool, new):
        if old is None:
            assert got is None
            continue
        diff = np.asarray(old != got)
        diff = diff.reshape(diff.shape[:3] + (-1,)).any(axis=(0, 3))
        assert {(int(p), int(o)) for p, o in zip(*np.nonzero(diff))} \
            == want


@pytest.mark.parametrize("fn", ["decode", "verify", "draft"])
def test_paged_dispatch_updates_the_donated_pool_in_place(fn):
    """Many pages, two slots, a two-page table: the pool dwarfs what a
    dispatch reads of it.  The compiled program aliases every pool
    array input -> output and its temporaries stay under ONE pool
    array's bytes — a whole-pool copy, view or re-layout on any backend
    fails this."""
    params = jax.eval_shape(lambda: gpt.init_params(jax.random.key(0), CFG))
    pool = jax.eval_shape(lambda: gpt.init_pages(CFG, 2049, 8))
    S, TBL = 2, 2
    sd = jax.ShapeDtypeStruct
    i32 = lambda *shape: sd(shape, np.int32)  # noqa: E731
    args = [params, pool, i32(S, TBL), i32(S), i32(S), sd((S,), np.bool_)]
    if fn == "draft":
        def f(*a):
            return gpt.paged_draft_propose(CFG, *a, 3)
    else:
        f = {"decode": gpt.paged_decode, "verify": gpt.paged_verify}[fn]
        f = (lambda g: lambda *a: g(CFG, *a))(f)
        args += [sd((S,), np.float32), sd((S,), np.uint32)]
        if fn == "verify":
            args.append(i32(S, 3))
    compiled = jax.jit(f, donate_argnums=(1,)).lower(*args).compile()
    one_array = int(np.prod(pool.k.shape)) * pool.k.dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * one_array
    assert mem.temp_size_in_bytes < one_array, (
        mem.temp_size_in_bytes, one_array)
    head = compiled.as_text().split("\n", 1)[0]
    assert head.count("-alias)") == 2, head[:300]   # pool.k, pool.v


def test_paged_engine_on_a_model_mesh_matches_replicated(params):
    """Heads over ``model``: the pool's NH*D rows split in whole heads,
    page gathers and row scatters stay shard-local, and the tokens are
    the replicated paged engine's."""
    from deeplearning4j_tpu.parallel.mesh import (MODEL_AXIS, MeshSpec,
                                                  make_mesh)

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = make_mesh(MeshSpec(data=1, model=2), devices=jax.devices()[:2])
    cfg = dataclasses.replace(CFG, layer_norm_eps=3e-5)     # own programs
    kw = dict(n_slots=2, buckets=(16, 32), prefill_chunk=8)
    eng_r = DecodeEngine(cfg, params, label="t3-pg-repl", **kw)
    eng_s = DecodeEngine(cfg, params, mesh=mesh, label="t3-pg-shard", **kw)
    eng_r.warmup()
    eng_s.warmup()
    prompt = np.arange(1, 14, dtype=np.int32)
    assert _engine_tokens(eng_s, prompt, 10) \
        == _engine_tokens(eng_r, prompt, 10)
    assert MODEL_AXIS in eng_s._pool_state().k.sharding.spec


def test_resident_prefix_mounts_by_reference(params):
    """Second request sharing a chunk-aligned head mounts the FIRST
    request's pages: refcount > 1 while mounted, a prefix hit is
    booked, output stays bit-exact, and release only decrefs."""
    eng = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                       prefill_chunk=8)
    eng.warmup()
    head = np.arange(1, 17, dtype=np.int32)             # two full chunks
    p1 = np.concatenate([head, [20, 21]])
    p2 = np.concatenate([head, [30]])
    assert _engine_tokens(eng, p1, 8) == _solo(params, p1, 8)
    held = eng._alloc.in_use()
    assert held >= 2                                    # registry pins
    before = decode_metrics.snapshot()["prefix_hits"]
    slot, first = eng.start(p2, max_tokens=8)
    assert decode_metrics.snapshot()["prefix_hits"] == before + 1
    shared = [int(x) for x in eng._kinds[0].ptab[slot, :2]]
    assert all(eng._alloc.refcount(p) >= 2 for p in shared)
    out = [first]
    while len(out) < 8:
        toks = eng.advance()
        out.append(int(toks[slot]))
    eng.release(slot)
    assert out == _solo(params, p2, 8)
    assert all(eng._alloc.refcount(p) >= 1 for p in shared)
    assert eng._alloc.in_use() >= held                  # only decrefs


def test_oversize_paged_admit_is_typed_and_sync(params):
    eng = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                       prefill_chunk=8, n_pages=4)
    eng.warmup()
    with pytest.raises(KVPagesExhausted):
        eng.check_capacity(25)              # needs 4+1 pages, pool has 3
    bat = ContinuousBatcher(eng)
    try:
        with pytest.raises(KVPagesExhausted):
            bat.submit(np.arange(1, 26, dtype=np.int32), max_tokens=4)
    finally:
        bat.close()


# -- speculative decoding ---------------------------------------------------

def test_spec_greedy_bit_identical_and_booked(params, dparams):
    eng = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                       prefill_chunk=8, draft=(DCFG, dparams), draft_k=3)
    eng.warmup()
    before = decode_metrics.snapshot()
    prompt = np.arange(1, 10, dtype=np.int32)
    assert _engine_tokens(eng, prompt, 12) == _solo(params, prompt, 12)
    after = decode_metrics.snapshot()
    proposed = after["draft_proposed"] - before["draft_proposed"]
    accepted = after["draft_accepted"] - before["draft_accepted"]
    assert proposed > 0 and 0 <= accepted <= proposed


def test_spec_sampled_matches_plain(params, dparams):
    """Position-keyed sampling makes speculative decoding token
    -identical to plain decode at ANY temperature — the engine with a
    draft vs the engine without one, and vs the dense reference."""
    spec = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                        prefill_chunk=8,
                        draft=(DCFG, dparams), draft_k=3)
    plain = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                         prefill_chunk=8)
    spec.warmup()
    plain.warmup()
    prompt = np.arange(1, 8, dtype=np.int32)
    a = _engine_tokens(spec, prompt, 12, temperature=0.7, seed=9)
    b = _engine_tokens(plain, prompt, 12, temperature=0.7, seed=9)
    assert a == b == _reference(params, prompt, 12, temperature=0.7, seed=9)


def test_batcher_composes_spec_int8_prefix(params, dparams):
    """The whole tier-3 stack at once: continuous batching over a
    speculative, int8-weight engine with a shared prefix store — every
    request bit-matches the plain int8 engine serving it alone."""
    store = PrefixCache()
    eng = DecodeEngine(CFG, params, n_slots=4, buckets=(32,),
                       prefill_chunk=8, quantize="int8",
                       draft=(DCFG, dparams), draft_k=3,
                       prefix_cache=store)
    ref = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                       prefill_chunk=8, quantize="int8")
    eng.warmup()
    ref.warmup()
    rng = np.random.default_rng(1)
    bat = ContinuousBatcher(eng)
    try:
        prompts = [rng.integers(1, 64, size=int(rng.integers(4, 18)))
                   for _ in range(6)]
        reqs = [bat.submit(p, max_tokens=8) for p in prompts]
        outs = [list(r.result(120.0)) for r in reqs]
    finally:
        bat.close()
    for p, o in zip(prompts, outs):
        assert o == _engine_tokens(ref, p, 8), p


def test_tier3_zero_steady_state_compiles(params, dparams):
    eng = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                       prefill_chunk=8,
                       draft=(DCFG, dparams), draft_k=3)
    eng.warmup()                            # marks the compile baseline
    for start in (1, 5):
        prompt = np.arange(start, start + 9, dtype=np.int32)
        _engine_tokens(eng, prompt, 10)
    assert decode_metrics.snapshot()["compile_delta_since_mark"] == 0


# -- hot weight swap --------------------------------------------------------

def test_rebind_params_requires_idle_then_flips(params):
    p_new = gpt.init_params(jax.random.key(11), CFG)
    eng = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                       prefill_chunk=8)
    eng.warmup()
    prompt = np.arange(1, 8, dtype=np.int32)
    slot, _ = eng.start(prompt, max_tokens=4)
    with pytest.raises(RuntimeError, match="busy"):
        eng.rebind_params(p_new)
    eng.release(slot)
    eng.rebind_params(p_new)
    assert _engine_tokens(eng, prompt, 10) == _solo(p_new, prompt, 10)
    assert decode_metrics.snapshot()["compile_delta_since_mark"] == 0


def test_rebind_invalidates_resident_prefix(params):
    """Pages harvested under the old weights must never satisfy a hit
    after a swap: rebinding bumps the engine's prefix fingerprint and
    drops the resident registry."""
    p_new = gpt.init_params(jax.random.key(12), CFG)
    eng = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                       prefill_chunk=8)
    eng.warmup()
    head = np.arange(1, 17, dtype=np.int32)
    _engine_tokens(eng, np.concatenate([head, [20]]), 6)
    assert eng._alloc.in_use() > 0          # resident registry pins
    eng.rebind_params(p_new)
    assert eng._alloc.in_use() == 0         # registry flushed
    p2 = np.concatenate([head, [30]])
    before = decode_metrics.snapshot()["prefix_hits"]
    assert _engine_tokens(eng, p2, 8) == _solo(p_new, p2, 8)
    assert decode_metrics.snapshot()["prefix_hits"] == before


def test_router_swap_weights_zero_drops(params):
    """Live fleet rolls onto a new checkpoint: no request is dropped
    or shed, requests during the swap are counted, the swap books its
    counter, steady-state compiles stay at zero, and post-swap output
    comes from the NEW weights."""
    p_new = gpt.init_params(jax.random.key(13), CFG)
    store = PrefixCache()

    def factory():
        eng = DecodeEngine(CFG, params, n_slots=4, buckets=(32,),
                           prefill_chunk=8,
                           prefix_cache=store)
        eng.warmup()
        return ContinuousBatcher(eng, default_max_tokens=6)

    router = AutoscalingRouter(
        factory, AutoscalePolicy(min_replicas=2, max_replicas=2))
    before = decode_metrics.snapshot()
    stop = threading.Event()
    errors = []

    def traffic():
        rng = np.random.default_rng(2)
        while not stop.is_set():
            try:
                router.generate(rng.integers(1, 64, size=9), timeout=60.0)
            except Exception as e:          # any drop = failure
                errors.append(e)

    t = threading.Thread(target=traffic)
    t.start()
    try:
        time.sleep(0.2)
        assert router.swap_weights(p_new, timeout=60.0) == 2
        time.sleep(0.2)
    finally:
        stop.set()
        t.join()
    prompt = np.arange(1, 8, dtype=np.int32)
    out = list(router.generate(prompt, timeout=60.0, max_tokens=8))
    router.close()
    assert not errors, errors[:3]
    assert out == _solo(p_new, prompt, 8)
    after = decode_metrics.snapshot()
    assert after["swaps_completed"] == before["swaps_completed"] + 1
    assert after["compile_delta_since_mark"] == 0
    assert router._draining == set() and not router._swapping


def test_swap_single_replica_spawns_temp(params):
    """A one-replica fleet can still swap without downtime: a
    temporary factory replica keeps serving while the only real one
    drains, is swapped too, then retired."""
    p_new = gpt.init_params(jax.random.key(14), CFG)

    def factory():
        eng = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                           prefill_chunk=8)
        eng.warmup()
        return ContinuousBatcher(eng, default_max_tokens=6)

    router = AutoscalingRouter(
        factory, AutoscalePolicy(min_replicas=1, max_replicas=2))
    assert router.swap_weights(p_new, timeout=60.0) == 2
    assert router.n_replicas() == 1         # temp retired
    prompt = np.arange(1, 6, dtype=np.int32)
    out = list(router.generate(prompt, timeout=60.0, max_tokens=6))
    router.close()
    assert out == _solo(p_new, prompt, 6)


# -- PR 17: serving fleet fault tolerance -----------------------------------
# Deadlines, health-checked replica replacement, deterministic replay,
# the brownout ladder, and the page-accounting invariants of every
# recovery path.  Faults are injected with parallel.chaos.ServingChaos,
# which arms on the host and fires at a step boundary on the victim's
# own worker thread (the allocator's single-driver contract).

def _ft_batcher(params, *, n_slots=2, default_max_tokens=6):
    eng = DecodeEngine(CFG, params, n_slots=n_slots, buckets=(32,),
                       prefill_chunk=8)
    eng.warmup()
    return ContinuousBatcher(eng, default_max_tokens=default_max_tokens)


def _audit_zero_pages(eng):
    """Post-drain leak audit: evict the pool-resident prefix registry
    (cache refs, not occupancy) — then every page must be free and
    every refcount accounted for."""
    eng.drop_residents()
    assert eng._alloc.in_use() == 0
    assert eng.pages_unaccounted() == 0


def test_deadline_ms_validation(params):
    b = _ft_batcher(params)
    try:
        with pytest.raises(ValueError):
            b.submit(np.arange(1, 5, dtype=np.int32), deadline_ms=0)
        with pytest.raises(ValueError):
            b.submit(np.arange(1, 5, dtype=np.int32), deadline_ms=-10)
    finally:
        b.close()


def test_queued_deadline_expires_typed_and_reclaims(params):
    """A request expiring while QUEUED (page pool held hostage) fails
    with the typed DeadlineExceeded, frees no-longer-needed capacity,
    and leaves the batcher fully serviceable."""
    b = _ft_batcher(params)
    eng = b.engine
    prompt = np.arange(1, 6, dtype=np.int32)
    before = decode_metrics.snapshot()["deadline_expirations"]
    chaos = ServingChaos(b)
    try:
        chaos.exhaust_pages()
        probe = b.submit(prompt, max_tokens=4, deadline_ms=80)
        time.sleep(0.3)                  # expire while inadmissible
        chaos.release_pages()
        with pytest.raises(DeadlineExceeded) as ei:
            probe.result(30)
        err = ei.value
        assert err.deadline_ms == 80
        assert err.elapsed_ms >= 80
        assert err.tokens_emitted == 0   # never admitted
        after = decode_metrics.snapshot()["deadline_expirations"]
        assert after - before >= 1
        # the batcher is not poisoned: a fresh request still completes
        out = list(b.submit(prompt, max_tokens=4).result(60))
        assert out == _solo(params, prompt, 4)
    finally:
        chaos.restore()
        b.close()
    _audit_zero_pages(eng)


def test_placed_deadline_expires_mid_decode(params):
    """A PLACED request whose budget elapses mid-decode is cut off with
    the typed error (partial stream length attached) and its slot and
    pages are reclaimed for live traffic."""
    b = _ft_batcher(params)
    eng = b.engine
    prompt = np.arange(1, 6, dtype=np.int32)
    chaos = ServingChaos(b)
    try:
        chaos.stall_dispatch(0.4)        # hold the worker past the budget
        r = b.submit(prompt, max_tokens=8, deadline_ms=100)
        with pytest.raises(DeadlineExceeded) as ei:
            r.result(30)
        assert ei.value.tokens_emitted < 8
        out = list(b.submit(prompt, max_tokens=4).result(60))
        assert out == _solo(params, prompt, 4)
    finally:
        chaos.restore()
        b.close()
    _audit_zero_pages(eng)


def test_failed_dispatch_returns_pages_and_replays(params):
    """Satellite regression: a dispatch failure mid-flight must return
    the affected slots' KV pages to the pool and replay the requests
    in place — bit-exact, no leak, no stranded client."""
    b = _ft_batcher(params)
    eng = b.engine
    prompt = np.arange(2, 9, dtype=np.int32)
    expect = np.asarray(
        b.submit(prompt, max_tokens=6, temperature=0.8, seed=11).result(60))
    before = decode_metrics.snapshot()["requests_replayed"]
    ServingChaos(b).poison_dispatch(1)
    got = np.asarray(
        b.submit(prompt, max_tokens=6, temperature=0.8, seed=11).result(60))
    assert np.array_equal(got, expect)   # position-keyed sampling replays
    assert decode_metrics.snapshot()["requests_replayed"] - before >= 1
    assert b.worker_alive()              # poison is survivable in place
    b.close()
    _audit_zero_pages(eng)


def test_killed_worker_replaced_and_replayed_bit_exact(params):
    """A dead decode worker is detected by the health monitor, the
    replica is replaced from the factory with ZERO new compiles, and
    every journaled request re-dispatches bit-exactly."""
    prompts = [np.arange(1, 6, dtype=np.int32),
               np.arange(3, 11, dtype=np.int32),
               np.arange(2, 7, dtype=np.int32)]

    def factory():
        return _ft_batcher(params, n_slots=3)

    base = factory()
    expect = [np.asarray(base.submit(p, max_tokens=5, temperature=0.7,
                                     seed=40 + i).result(60))
              for i, p in enumerate(prompts)]
    base.close()

    before = decode_metrics.snapshot()["replicas_replaced"]
    router = AutoscalingRouter(
        factory, AutoscalePolicy(min_replicas=1, max_replicas=2),
        health=ReplicaHealth(poll_interval_s=0.02, max_error_streak=3,
                             stall_after_s=5.0))
    try:
        telemetry.registry.mark()
        victim = router.batchers[0]
        ServingChaos(victim).kill_worker()
        handles = [victim.submit(p, max_tokens=5, temperature=0.7,
                                 seed=40 + i)
                   for i, p in enumerate(prompts)]
        got = [np.asarray(h.result(120)) for h in handles]
        assert victim not in router.batchers       # replaced, not revived
        assert all(np.array_equal(g, e) for g, e in zip(got, expect))
        assert telemetry.registry.compile_delta_since_mark() == 0
        assert decode_metrics.snapshot()["replicas_replaced"] - before >= 1
    finally:
        router.close()


def test_brownout_ladder_escalates_before_shedding_and_recovers(params):
    """At the replica ceiling and over the depth bound the router walks
    the brownout ladder (spec off, then harvest bypass) BEFORE shedding,
    books every transition, and tick() walks it back down when the
    fleet cools — the engine flags flip both ways."""
    def factory():
        return _ft_batcher(params)

    before = decode_metrics.snapshot()["brownout_transitions"]
    router = AutoscalingRouter(
        factory, AutoscalePolicy(min_replicas=1, max_replicas=1),
        max_queue_depth=1)
    b = router.batchers[0]
    eng = b.engine
    chaos = ServingChaos(b)
    prompt = np.arange(1, 6, dtype=np.int32)
    try:
        chaos.exhaust_pages()            # pin depth: nothing can admit
        handles = [router.submit(prompt, max_tokens=4)]
        assert router.brownout_level() == 0
        handles.append(router.submit(prompt, max_tokens=4))
        assert router.brownout_level() == 1
        assert eng.spec_enabled is False          # rung 1: spec off
        assert eng.harvest_enabled is True
        handles.append(router.submit(prompt, max_tokens=4))
        assert router.brownout_level() == 2
        assert eng.harvest_enabled is False       # rung 2: + harvest off
        with pytest.raises(OverloadedError):      # only level 2 sheds
            router.submit(prompt, max_tokens=4)
        chaos.release_pages()
        for h in handles:                # admitted requests all complete
            assert list(h.result(60)) == _solo(params, prompt, 4)
        now = time.monotonic()
        assert router.tick(now=now + 10.0) is not None
        assert router.brownout_level() == 1       # one rung per tick
        router.tick(now=now + 20.0)
        assert router.brownout_level() == 0
        assert eng.spec_enabled is True and eng.harvest_enabled is True
        after = decode_metrics.snapshot()["brownout_transitions"]
        assert after - before == 4       # 0->1->2->1->0, each booked
    finally:
        chaos.restore()
        router.close()
    _audit_zero_pages(eng)


def test_submit_racing_close_gets_typed_error_never_hangs(params):
    """A submit racing close() either lands (and its request completes
    during the drain) or fails with the typed RouterClosed — never a
    hang, never an unexplained RuntimeError."""
    def factory():
        return _ft_batcher(params)

    router = AutoscalingRouter(
        factory, AutoscalePolicy(min_replicas=1, max_replicas=1))
    prompt = np.arange(1, 6, dtype=np.int32)
    accepted, outcome = [], {}

    def hammer():
        try:
            for _ in range(500):
                accepted.append(router.submit(prompt, max_tokens=3))
                time.sleep(0.002)
            outcome["end"] = "exhausted"
        except RouterClosed:
            outcome["end"] = "typed"
        except BaseException as e:       # the failure this test exists for
            outcome["end"] = repr(e)

    t = threading.Thread(target=hammer)
    t.start()
    time.sleep(0.1)
    router.close()
    t.join(30)
    assert not t.is_alive()              # the race must never hang
    assert outcome["end"] == "typed"
    for h in accepted:                   # accepted before close: completes
        assert list(h.result(60)) == _solo(params, prompt, 3)
    # closed-fleet submits stay typed afterwards too
    with pytest.raises(RouterClosed):
        router.submit(prompt)
    b = factory()
    b.close()
    with pytest.raises(BatcherClosed):
        b.submit(prompt)


def test_swap_failed_typed_with_drain_states_on_wedged_fleet(params):
    """swap_weights on a fleet that cannot drain (dead worker, pinned
    depth) raises the typed SwapFailed carrying per-replica drain
    states, with the fleet left on the old weights."""
    def factory():
        return _ft_batcher(params)

    p_new = gpt.init_params(jax.random.key(21), CFG)
    router = AutoscalingRouter(            # no health monitor: the wedge
        factory, AutoscalePolicy(min_replicas=1, max_replicas=2))
    victim = router.batchers[0]
    try:
        ServingChaos(victim).kill_worker()
        victim.submit(np.arange(1, 6, dtype=np.int32), max_tokens=8)
        deadline = time.monotonic() + 10.0
        while victim.worker_alive() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not victim.worker_alive()
        with pytest.raises(SwapFailed) as ei:
            router.swap_weights(p_new, timeout=0.5)
        err = ei.value
        assert isinstance(err, TimeoutError)       # handler compatible
        assert err.swapped == 0
        states = err.drain_states
        assert any(s["depth"] > 0 and not s["worker_alive"]
                   for s in states.values())
        assert any(s["draining"] for s in states.values())
    finally:
        router.close(timeout=5.0)


def test_serving_chaos_drill(params):
    """The full chaos drill — poison, kill, stall, exhaust — completes
    every request bit-exactly with zero new compiles and zero leaked
    pages.  Runs the CI gate in-process so the acceptance invariant is
    asserted in the tier-1 suite too, not only in tools/ci.sh."""
    from tools import serving_chaos_gate

    assert serving_chaos_gate.main() == 0
