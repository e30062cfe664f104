"""A self-draft speculative round one round ahead of its fetch
(``DecodeEngine.dispatch_round`` / ``collect_round``, ordered one round
apart by ``ContinuousBatcher``): every stream is, token for token, what
``advance_spec()`` gives in series, and every page comes back.

Most tests run a COUNTING family (``spec_stub`` below) whose "model" is
a function of the position and of the sum of every token the slot's
page table holds before it, read back from the pool: a row written at
a wrong position or into a wrong page, or a wrong position fed, changes
every token after it.  Its draft is the model's own next token but at
every third position, so rounds commit two tokens or one, as a trained
draft's would; K-EXAONE's toy model (``tests/test_exaone_moe.py``) is
accepted at chance and commits one."""

import dataclasses
import hashlib
import json
import os
import sys
import types
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from deeplearning4j_tpu.runtime.metrics import (compile_metrics,  # noqa: E402
                                                decode_metrics)
from deeplearning4j_tpu.serving.decode import (ContinuousBatcher,  # noqa: E402
                                               DecodeEngine, model_family)

# -- the counting family -----------------------------------------------------

STUB = "spec_stub"


@dataclasses.dataclass(frozen=True)
class StubConfig:
    vocab_size: int = 97
    max_len: int = 64
    compute_dtype: str = "float32"
    #: the draft of every position ``q`` with ``q % miss == 0`` is wrong
    miss: int = 3
    family: ClassVar[str] = STUB


def _model(cfg, q, s):
    """The token at ``q + 1`` after tokens that sum to ``s`` at 0..q."""
    return (7 * s + 3 * q + 1) % cfg.vocab_size


def _draft(cfg, q, s):
    """The draft of the token at ``q + 1``: the model's, but at every
    ``miss``-th position one off."""
    good = _model(cfg, q, s)
    return jnp.where((q + 1) % cfg.miss == 0, (good + 1) % cfg.vocab_size,
                     good)


def _write(pool, ptab, posw, toks, ok):
    """Token ``toks[s, w]`` into the row of position ``posw[s, w]`` of
    slot s's pages; rows not ``ok`` or past the table into the trash
    page."""
    C = pool.shape[1]
    T = ptab.shape[1] * C
    ok = ok & (posw >= 0) & (posw < T)
    col = jnp.clip(posw, 0, T - 1) // C
    page = jnp.where(ok, jnp.take_along_axis(ptab, col, axis=1), 0)
    return pool.at[page, posw % C].set(toks)


def _history(pool, ptab, below):
    """Per slot, the sum of the tokens its pages hold at positions
    below ``below[s]``."""
    rows = pool[ptab].reshape(ptab.shape[0], -1)
    at = jnp.arange(rows.shape[1])
    return jnp.sum(jnp.where(at[None, :] < below[:, None], rows, 0), axis=1)


def _init_pages(cfg, n_pages, page_tokens, kv_dtype=None):
    return jnp.zeros((n_pages, page_tokens), jnp.int32)


def _pages_bytes(cfg, n_pages, page_tokens, kv_dtype=None):
    return n_pages * page_tokens * 4


def _slots_bytes_per_slot(cfg, length, kv_dtype=None):
    return length * 4


def _prefill_rows(cfg, pool, ptab_s, toks, start, n_valid):
    at = jnp.arange(toks.shape[0])
    pool = _write(pool, ptab_s[None], (start + at)[None], toks[None],
                  (at < n_valid)[None])
    s = _history(pool, ptab_s[None], (start + n_valid)[None])[0]
    return pool, s, start + n_valid - 1


def _paged_prefill(cfg, params, pool, ptab_s, toks, start, n_valid,
                   temperature, seed):
    pool, s, q = _prefill_rows(cfg, pool, ptab_s, toks, start, n_valid)
    return pool, _model(cfg, q, s)


def _paged_self_draft_prefill(cfg, params, pool, ptab_s, toks, nxt, start,
                              n_valid, temperature, seed):
    pool, s, q = _prefill_rows(cfg, pool, ptab_s, toks, start, n_valid)
    first = _model(cfg, q, s)
    return pool, jnp.stack([first, _draft(cfg, q + 1, s + first)])


def _paged_decode(cfg, params, pool, ptab, tokens, pos, active, temperature,
                  seeds):
    pool = _write(pool, ptab, pos[:, None], tokens[:, None], active[:, None])
    nxt = _model(cfg, pos, _history(pool, ptab, pos + 1))
    return pool, jnp.where(active, nxt, tokens)


def _paged_self_draft_round(cfg, params, pool, ptab, tokens, pos, active,
                            temperature, seeds, drafts):
    toks_w = jnp.concatenate([tokens[:, None], drafts], axis=1)
    posw = pos[:, None] + jnp.arange(2)
    pool = _write(pool, ptab, posw, toks_w,
                  jnp.broadcast_to(active[:, None], posw.shape))
    s0 = _history(pool, ptab, pos + 1)          # through the current row
    s1 = _history(pool, ptab, pos + 2)          # and the draft's row
    g = jnp.stack([_model(cfg, pos, s0), _model(cfg, pos + 1, s1)], axis=1)
    n_commit = jnp.where(active, 1 + (g[:, 0] == drafts[:, 0]), 0)
    nxt = jnp.where(n_commit == 2, _draft(cfg, pos + 2, s1 + g[:, 1]),
                    _draft(cfg, pos + 1, s0 + g[:, 0]))
    return pool, jnp.concatenate([
        jnp.where(active[:, None], g, toks_w).reshape(-1), n_commit,
        nxt]).astype(jnp.int32)


_stub = types.ModuleType(f"deeplearning4j_tpu.models.{STUB}")
_stub.__dict__.update(
    UNSUPPORTED_ENGINE_OPTIONS=("mesh", "kv_dtype", "quantize",
                                "prefix_cache"),
    init_pages=_init_pages, pages_bytes=_pages_bytes,
    slots_bytes_per_slot=_slots_bytes_per_slot,
    paged_prefill=_paged_prefill, paged_decode=_paged_decode,
    paged_self_draft_prefill=_paged_self_draft_prefill,
    paged_self_draft_round=_paged_self_draft_round,
    self_draft_depth=lambda cfg: 1)
sys.modules[_stub.__name__] = _stub


# -- helpers -----------------------------------------------------------------

def _every_page_is_back(eng):
    eng.drop_residents()
    return (eng.n_active() == 0
            and eng._alloc.in_use() + eng.pages_unaccounted() == 0)


_KEYS = ("decode_dispatches", "decode_dispatches_ahead",
         "decode_overshoot_steps", "draft_proposed", "draft_accepted",
         "rounds")


def _counts():
    snap = decode_metrics.snapshot()
    return {k: snap[k] for k in _KEYS}


def _delta(before):
    return {k: v - before[k] for k, v in _counts().items()}


_backend_compiles = []


def _xla_compiles():
    """Lowerings handed to the backend so far (the listener is
    registered once a process)."""
    if not _backend_compiles:
        import jax.monitoring as mon

        _backend_compiles.append(0)

        def on_duration(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _backend_compiles[0] += 1
        mon.register_event_duration_secs_listener(on_duration)
    return _backend_compiles[0]


@pytest.fixture(scope="module")
def stub():
    eng = DecodeEngine(StubConfig(), {"unused": jnp.zeros((1,))}, n_slots=3,
                       buckets=(32, 64), prefill_chunk=8, draft="self",
                       label="spec-stub")
    eng.warmup()
    yield eng
    assert _every_page_is_back(eng)


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(
        1, StubConfig.vocab_size, size=n).astype(np.int32)


def _series(eng, prompt, n):
    """The reference: ``n`` tokens of one request alone, each round
    collected before the next is dispatched.  Also says whether a round
    committed two tokens where one was left of the budget."""
    slot, first = eng.start(prompt, max_tokens=n)
    toks, past_budget = [first], False
    while len(toks) < n:
        out, n_c = eng.advance_spec()
        past_budget |= int(n_c[slot]) > n - len(toks)
        toks += out[slot, :int(n_c[slot])].tolist()
    eng.release(slot)
    return toks[:n], past_budget


def _serve(eng, prompts, budgets, **kw):
    with ContinuousBatcher(eng) as cb:
        handles = [cb.submit(p, max_tokens=m, **kw)
                   for p, m in zip(prompts, budgets)]
        return [h.result(120).tolist() for h in handles]


class _Gate:
    """Runs ``hook(n)`` on the worker thread at the entry of the
    engine's ``name`` call n (1, 2, ...), before the call itself."""

    def __init__(self, eng, name, hook):
        self.eng, self.name, self.calls = eng, name, 0
        real = getattr(eng, name)

        def gated(*a, **kw):
            self.calls += 1
            hook(self.calls)
            return real(*a, **kw)
        setattr(eng, name, gated)

    def remove(self):
        self.eng.__dict__.pop(self.name, None)


# -- the tests ---------------------------------------------------------------

def test_the_counting_family_commits_two_and_one_in_series(stub):
    """The family the tests below lean on: its plain step and its
    rounds give one stream, its rounds commit two tokens and one."""
    prompt = _prompt(11, 1)
    slot, first = stub.start(prompt, max_tokens=20)
    plain = [first] + [int(stub.advance()[slot]) for _ in range(19)]
    stub.release(slot)
    before = _counts()
    spec, _ = _series(stub, prompt, 20)
    d = _delta(before)
    assert spec == plain
    assert 0 < d["draft_accepted"] < d["draft_proposed"]
    assert d["decode_dispatches_ahead"] == 0
    # some budget ends inside a round that commits two tokens
    assert any(_series(stub, _prompt(6, 7), m)[1] for m in range(2, 10))
    assert _every_page_is_back(stub)


def test_streams_a_round_ahead_are_the_series_ones(stub):
    """Mixed lengths over both rungs, nine requests on three slots (the
    later ones join under a round in flight), budgets from one token up,
    rounds of two commits and one: every stream is ``advance_spec()``'s
    in series; nothing traces or compiles; rounds run ahead."""
    lengths = (5, 20, 9, 30, 12, 3, 17, 26, 7)
    budgets = (10, 30, 4, 25, 2, 1, 12, 7, 3)
    prompts = [_prompt(n, 40 + i) for i, n in enumerate(lengths)]
    assert {stub.pick_bucket(n + m) for n, m in zip(lengths, budgets)} \
        == {32, 64}
    want = [_series(stub, p, m)[0] for p, m in zip(prompts, budgets)]
    decode_metrics.mark_compiles()
    xla, before = _xla_compiles(), _counts()
    got = _serve(stub, prompts, budgets)
    d = _delta(before)
    assert decode_metrics.snapshot()["compile_delta_since_mark"] == 0
    assert _xla_compiles() == xla
    assert got == want
    assert 0 < d["draft_accepted"] < d["draft_proposed"]
    assert 0 < d["decode_dispatches_ahead"] < d["decode_dispatches"]
    assert _every_page_is_back(stub)


@pytest.mark.parametrize("budget", range(2, 10))
def test_a_round_that_commits_past_the_budget_ends_the_stream(stub, budget):
    """A round may commit two tokens where one is left: the request ends
    when that round lands with the budget's first tokens, and the slot's
    round behind it, dispatched when the end was not yet certain, is
    dropped and counted: at most one a request."""
    prompt = _prompt(6, 7)
    want, _ = _series(stub, prompt, budget)
    before = _counts()
    got = _serve(stub, [prompt], [budget])
    d = _delta(before)
    assert got == [want]
    assert d["decode_overshoot_steps"] <= 1
    assert _every_page_is_back(stub)


def test_eos_is_seen_one_round_late(stub):
    """An end by ``eos_id`` is known when its round lands, one round
    after the next was dispatched: the stream ends AT the eos token, and
    the slot's round in flight is dropped and counted."""
    prompt = _prompt(9, 11)
    ref, _ = _series(stub, prompt, 24)
    k = next(k for k in range(3, 20) if ref[k] not in ref[:k])
    before = _counts()
    with ContinuousBatcher(stub) as cb:
        got = cb.submit(prompt, max_tokens=24, eos_id=ref[k]).result(120)
    d = _delta(before)
    assert got.tolist() == ref[:k + 1]
    assert d["decode_overshoot_steps"] == 1
    assert _every_page_is_back(stub)


def test_a_slot_restarted_between_dispatch_and_collect(stub):
    """With a round dispatched and not collected, its slot is released
    and a new sequence started in it: the next round, dispatched behind
    the first, takes that slot's token, position and draft from the
    host and the other slots' from the round in flight; both streams are
    the series ones.  Two rounds ahead raise."""
    pa, pb, pc = _prompt(13, 21), _prompt(5, 22), _prompt(22, 23)
    want_c, _ = _series(stub, pc, 12)
    want_b, _ = _series(stub, pb, 12)
    sa, first_a = stub.start(pa, max_tokens=12)
    sc, first_c = stub.start(pc, max_tokens=12)
    got = {sa: [first_a], sc: [first_c]}
    r1 = stub.dispatch_round()
    stub.release(sa)
    sb, first_b = stub.start(pb, max_tokens=12)
    assert sb == sa
    r2 = stub.dispatch_round(after=r1)
    assert r2.taken.tolist() == [s == sc for s in range(3)]
    with pytest.raises(RuntimeError, match="ONE round ahead"):
        stub.dispatch_round(after=r2)
    with pytest.raises(RuntimeError, match="collected after"):
        stub.collect_round(r2)
    out, n_c = stub.collect_round(r1)
    got[sc] += out[sc, :n_c[sc]].tolist()       # slot sa's went to A
    got[sb] = [first_b]
    prev = r2
    while min(len(got[sb]), len(got[sc])) < 12:
        nxt = stub.dispatch_round(after=prev)
        out, n_c = stub.collect_round(prev)
        for s in (sb, sc):
            got[s] += out[s, :n_c[s]].tolist()
        prev = nxt
    stub.collect_round(prev)
    assert got[sb][:12] == want_b and got[sc][:12] == want_c
    stub.release(sb)
    stub.release(sc)
    assert _every_page_is_back(stub)


@pytest.mark.parametrize("first", ["rounds", "steps"])
def test_a_brownout_flip_lands_what_is_in_flight(stub, first):
    """``spec_enabled`` flipped off and on again from another thread
    while rounds (or steps) run ahead: what is in flight lands in series
    before the other kind of dispatch goes out, and every stream is the
    series one."""
    prompts = [_prompt(n, 60 + n) for n in (7, 15, 4)]
    budgets = (30, 26, 34)
    want = [_series(stub, p, m)[0] for p, m in zip(prompts, budgets)]
    stub.spec_enabled = first == "rounds"
    flips = []

    def flip(n):
        if n in (3, 8):
            stub.spec_enabled = not stub.spec_enabled
            flips.append(n)
    gates = [_Gate(stub, name, flip)
             for name in ("dispatch_round", "dispatch_step")]
    before = _counts()
    try:
        got = _serve(stub, prompts, budgets)
    finally:
        for g in gates:
            g.remove()
        stub.spec_enabled = True
    d = _delta(before)
    assert got == want
    assert len(flips) >= 3      # off and on again, whichever came first
    assert d["draft_proposed"] > 0
    assert d["decode_dispatches_ahead"] > 0
    assert _every_page_is_back(stub)


def test_warmup_compiles_one_program_more_and_serving_none():
    """Beside each rung's prefill, plain step and round, and the plain
    step's token merge, warm-up compiles ONE program: the round's
    merge (its rungs' rounds were already warmed with device-array
    tokens, positions and drafts).  Serving then compiles nothing."""
    cfg = StubConfig(max_len=48)        # a geometry no other engine shares
    eng = DecodeEngine(cfg, {"unused": jnp.zeros((1,))}, n_slots=2,
                       buckets=(16, 32, 48), prefill_chunk=8, draft="self",
                       label="spec-stub-warm")
    labels = [f"spec-stub-warm.{p}" for p in ("prefill", "step", "spec",
                                               "tokens_ahead", "round_ahead")]
    before = compile_metrics.snapshot()["traces"]
    eng.warmup()
    after = compile_metrics.snapshot()["traces"]
    traced = [after.get(k, 0) - before.get(k, 0) for k in labels]
    # prefill, step and round a rung; ``jit_tokens_ahead_fn`` and
    # ``jit_round_ahead_fn`` once
    assert traced == [3, 3, 3, 1, 1]
    decode_metrics.mark_compiles()
    xla = _xla_compiles()
    prompts = [_prompt(n, n) for n in (3, 12, 25, 9)]
    budgets = (20, 9, 22, 30)
    got = _serve(eng, prompts, budgets)
    assert [len(g) for g in got] == list(budgets)
    assert decode_metrics.snapshot()["compile_delta_since_mark"] == 0
    assert _xla_compiles() == xla
    assert _every_page_is_back(eng)


def cell_d_round_programs():
    """sha256 of the text of ``jit_spec_fn`` as the engine of
    ``serve_kexaone_ep8_closed32_reason`` lowers it at each of its rungs
    (the cell's config, slots and pages; its bfloat16 weights and its
    pool as shapes)."""
    from benchmark.families import exaone_moe as fam
    from benchmark.lib import spec

    cell = spec.load_cell("serve_kexaone_ep8_closed32_reason")
    config = cell.config
    cfg = fam.program_config(config)
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16),
                          fam.param_shapes(config),
                          is_leaf=lambda x: isinstance(x, tuple))
    eng = DecodeEngine(cfg, params, n_slots=int(cell.traffic["n_slots"]),
                       **fam.engine_kwargs(config, cell.traffic))
    pool = jax.eval_shape(lambda: model_family(cfg).init_pages(
        cfg, eng.n_kv_pages, eng.page_tokens, None))
    return {str(t): hashlib.sha256(eng._spec.jitted.lower(
        params, pool, *eng._idle_step_args(t), eng._drafts_h
    ).as_text().encode()).hexdigest() for t in eng.buckets}


def test_the_round_program_keeps_its_text_at_every_rung_of_cell_d():
    """``tests/data/spec_round_programs.json``: the round program as it
    was before the round ran ahead, at cell D's four rungs.  Running
    ahead adds a program beside it and changes nothing in it."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "spec_round_programs.json")) as f:
        before = json.load(f)
    if jax.__version__ != before["jax"]:
        pytest.skip(f"the programs were lowered by jax {before['jax']}")
    assert list(before["programs"]) == ["512", "1024", "2048", "4096"]
    assert cell_d_round_programs() == before["programs"]
