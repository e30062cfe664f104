"""The decode worker's round and a slot's vacancy, accounted from inside
(PR 37): every child of ``decode.round`` feeds a cumulative-seconds
counter of ``decode_metrics``, ``decode.admit`` is opened up (the
queue's scan, the prefix lookup and registry, the first token), and a
slot's vacancy is a span that knows whether a request was waiting.

Two toy engines serve the same few requests with the tracer off and
on: GPT (plain steps, one step ahead; one unbounded kind of page, so
its joins look prefixes up and register them) and K-EXAONE with its own
draft (every round speculative and in series; a bounded kind of page,
so it mounts no prefix).  On the CPU: counts and bookkeeping, no
time is a measurement here."""

import collections
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.models import exaone_moe as ex, gpt
from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.runtime import telemetry
from deeplearning4j_tpu.runtime.metrics import decode_metrics
from deeplearning4j_tpu.serving.decode import ContinuousBatcher, DecodeEngine

C = 8
FAMILIES = ["gpt", "exaone_moe"]
ROUND_CHILDREN = {"decode.expire", "decode.admit", "decode.advance",
                  "decode.deliver"}
#: a new counter, and the spans whose durations it sums
COUNTERS = [("expire_s", ("decode.expire",)),
            ("admit_s", ("decode.admit",)),
            ("stage_s", ("decode.stage",)),
            ("dispatch_s", ("decode.dispatch",)),
            ("deliver_s", ("decode.deliver",)),
            ("prefix_s", ("decode.prefix.lookup", "decode.prefix.register")),
            ("slot_vacant_s", ("decode.slot_vacant",))]


@pytest.fixture(autouse=True)
def _no_global_tracer():
    telemetry.disable()
    yield
    telemetry.disable()


def make_engine(family, **kw):
    if family == "gpt":
        cfg = TransformerConfig(vocab_size=64, max_len=64, hidden=32,
                                n_layers=2, n_heads=2, ffn_dim=64,
                                dropout=0.0, compute_dtype="float32",
                                causal=True, type_vocab_size=1)
        params = gpt.init_params(jax.random.key(7), cfg)
    else:
        cfg = ex.tiny_config(compute_dtype="float32")
        params = ex.init_params(jax.random.key(0), cfg, std=0.3)
        kw["draft"] = "self"
    kw.setdefault("n_slots", 2)
    eng = DecodeEngine(cfg, params, buckets=(32,), prefill_chunk=C,
                       label=f"accounting-{family}", **kw)
    eng.warmup()
    return eng


#: one pass of requests through a batcher: token streams, the counters'
#: delta, the journal's span records, the requests
Served = collections.namedtuple("Served", "outs delta spans reqs")


def numbers():
    return {k: v for k, v in decode_metrics.snapshot().items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def serve(eng, prompts, trace, max_tokens=6, pause_s=0.0):
    """The requests through a new batcher over ``eng``, all at once (or
    one by one, ``pause_s`` after each is done)."""
    tr = telemetry.enable("accounting") if trace else None
    before = numbers()
    with ContinuousBatcher(eng, default_max_tokens=max_tokens) as b:
        if pause_s:
            reqs, outs = [], []
            for i, p in enumerate(prompts):
                reqs.append(b.submit(p, seed=i))
                outs.append(reqs[-1].result(timeout=120))
                time.sleep(pause_s)
        else:
            reqs = [b.submit(p, seed=i) for i, p in enumerate(prompts)]
            outs = [r.result(timeout=120) for r in reqs]
    telemetry.disable()
    after = numbers()
    spans = [r for r in tr.records() if r["type"] == "span"] if tr else []
    return Served([o.tolist() for o in outs],
                  {k: after[k] - before.get(k, 0) for k in after}, spans,
                  reqs)


def total_ms(spans, *names):
    return sum(r["dur_ms"] for r in spans if r["name"] in names)


@pytest.fixture(scope="module", params=FAMILIES)
def sessions(request):
    """One family's engine, and the same five requests served with the
    tracer off, then on, after a compile mark."""
    eng = make_engine(request.param)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 60, size=n) for n in (5, 9, 12, 17, 7)]
    telemetry.registry.mark()
    off = serve(eng, prompts, trace=False)
    compiles_off = telemetry.registry.compile_delta_since_mark()
    on = serve(eng, prompts, trace=True)
    compiles_on = telemetry.registry.compile_delta_since_mark()
    return {"family": request.param, "off": off, "on": on,
            "compiles": (compiles_off, compiles_on)}


# -- (i) a counter and its spans are one measurement --------------------

@pytest.mark.parametrize("key,names", COUNTERS,
                         ids=[key for key, _ in COUNTERS])
def test_a_new_counter_is_the_sum_of_its_spans(sessions, key, names):
    delta, spans = sessions["on"].delta, sessions["on"].spans
    assert delta[key] == pytest.approx(total_ms(spans, *names) / 1e3,
                                       abs=1e-6)
    mounts = sessions["family"] == "gpt"
    if key != "prefix_s" or mounts:
        assert delta[key] > 0
    else:       # a family with a bounded kind opens neither span
        assert delta[key] == 0 and not total_ms(spans, *names)


def test_the_queued_part_of_a_vacancy_is_counted_with_it(sessions):
    delta, spans = sessions["on"].delta, sessions["on"].spans
    vacant = [r for r in spans if r["name"] == "decode.slot_vacant"]
    assert delta["slot_turnovers"] == len(vacant) > 0
    assert delta["slot_vacant_queued_s"] == pytest.approx(
        sum(r["attrs"]["queued_ms"] for r in vacant) / 1e3, abs=1e-6)
    for r in vacant:
        assert 0 <= r["attrs"]["queued_ms"] <= r["dur_ms"] + 1e-9


def test_a_rounds_children_are_the_four_and_fit_inside_it(sessions):
    delta, spans = sessions["on"].delta, sessions["on"].spans
    rounds = {r["sid"]: r for r in spans if r["name"] == "decode.round"}
    assert rounds
    inside = dict.fromkeys(rounds, 0.0)
    for r in spans:
        if r["parent"] in rounds:
            assert r["name"] in ROUND_CHILDREN, r["name"]
            inside[r["parent"]] += r["dur_ms"]
    for sid, ms in inside.items():
        assert ms <= rounds[sid]["dur_ms"] + 1e-6
    assert (delta["expire_s"] + delta["admit_s"] + delta["advance_s"]
            + delta["deliver_s"]) <= delta["round_s"] + 1e-9
    # both halves of a dispatch lie inside the step's span
    assert delta["stage_s"] + delta["dispatch_s"] <= delta["advance_s"]
    assert all("n_run" in r["attrs"] for r in spans
               if r["name"] == "decode.advance" and "width" in r["attrs"])


def test_the_untraced_run_books_the_same_counters(sessions):
    off, on = sessions["off"].delta, sessions["on"].delta
    for key, _ in COUNTERS:
        assert (off[key] > 0) == (on[key] > 0), key
    # the engine was new: its two slots' first placements book nothing
    assert (off["slot_turnovers"], on["slot_turnovers"]) == (3, 5)
    assert (off["expire_s"] + off["admit_s"] + off["advance_s"]
            + off["deliver_s"]) <= off["round_s"] + 1e-9


# -- (iv) tracing changes no program and no token ------------------------

def test_tracing_changes_no_token_and_compiles_nothing(sessions):
    off, on = sessions["off"], sessions["on"]
    assert off.spans == [] and on.spans
    assert sessions["compiles"] == (0, 0)
    assert off.outs == on.outs
    assert all(len(o) == 6 for o in on.outs)


# -- (iii) decode.admit opened up ----------------------------------------

def test_a_joins_prefix_work_is_named_where_the_family_mounts(sessions):
    _, delta, spans, reqs = sessions["on"]
    by_sid = {r["sid"]: r for r in spans}
    opened = {r["name"] for r in spans}
    assert {"decode.admit.pick", "decode.first_token"} <= opened
    for name in ("decode.admit.pick", "decode.first_token",
                 "decode.prefix.lookup", "decode.prefix.register"):
        for r in (r for r in spans if r["name"] == name):
            assert by_sid[r["parent"]]["name"] == "decode.admit"
    picks = [r for r in spans if r["name"] == "decode.admit.pick"]
    assert all("pending" in r["attrs"] for r in picks)
    assert {r["attrs"]["rid"] for r in picks if "rid" in r["attrs"]} \
        == {q.rid for q in reqs}
    prefix = ("decode.prefix.lookup", "decode.prefix.register")
    if sessions["family"] != "gpt":
        assert not opened & set(prefix) and delta["prefix_s"] == 0
        return
    # every join looks up; one registers where whole pages of its
    # prompt were not hit (the untraced session left some resident)
    looked = {r["attrs"]["rid"]: r["attrs"] for r in spans
              if r["name"] == prefix[0]}
    assert set(looked) == {q.rid for q in reqs}
    assert all(looked[q.rid]["pages"] == (q.prompt.size - 1) // C
               for q in reqs)
    registered = [r["attrs"] for r in spans if r["name"] == prefix[1]]
    assert {a["rid"] for a in registered} == {
        rid for rid, a in looked.items()
        if a["hit_tokens"] < a["pages"] * C} != set()
    for a in registered:
        assert a["pages"] >= 1 and a["entries"] >= 1
        assert {"pages_held", "evicted"} <= set(a)


def test_the_registrys_own_pages_show_in_the_gauge():
    """A pool with room for two prompts' registrations: what the first
    left in the registry is what the second's join finds held by the
    registry alone."""
    eng = make_engine("gpt", n_slots=1, n_pages=17)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 60, size=17) for _ in range(2)]
    _, delta, spans, _ = serve(eng, prompts, trace=True, pause_s=0.001)
    regs = [r["attrs"] for r in spans
            if r["name"] == "decode.prefix.register"]
    assert [a["pages"] for a in regs] == [2, 2]
    assert [a["pages_held"] for a in regs] == [0, 2]
    assert [a["entries"] for a in regs] == [2, 4]
    # both requests are done: all four pages are the registry's alone
    assert decode_metrics.snapshot()["pages_held_resident"] == 4
    assert delta["prefix_s"] == pytest.approx(
        total_ms(spans, "decode.prefix.lookup",
                 "decode.prefix.register") / 1e3, abs=1e-6)
    eng.drop_residents()
    assert decode_metrics.snapshot()["pages_held_resident"] == 0


# -- (ii) a vacant slot knows whether a request was waiting --------------

def test_queued_successors_make_the_whole_vacancy_queued():
    eng = make_engine("gpt", n_slots=1)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, 60, size=n) for n in (5, 9, 12)]
    _, delta, spans, reqs = serve(eng, prompts, trace=True)
    vacant = [r for r in spans if r["name"] == "decode.slot_vacant"]
    # the first placement is of a slot that never was released
    assert len(vacant) == 2 == delta["slot_turnovers"]
    assert [r["attrs"]["rid"] for r in vacant] == [q.rid for q in reqs[1:]]
    for r in vacant:
        assert r["attrs"]["slot"] == 0
        assert r["attrs"]["queued_ms"] == pytest.approx(r["dur_ms"],
                                                        abs=1e-9)
    assert delta["slot_vacant_queued_s"] == pytest.approx(
        delta["slot_vacant_s"], abs=1e-9)


def test_a_late_successor_leaves_the_vacancy_unqueued():
    eng = make_engine("gpt", n_slots=1)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 60, size=n) for n in (5, 9)]
    _, delta, spans, _ = serve(eng, prompts, trace=True, pause_s=0.05)
    (vacant,) = [r for r in spans if r["name"] == "decode.slot_vacant"]
    assert delta["slot_turnovers"] == 1
    assert vacant["dur_ms"] >= 50
    # the 50 ms in which no request existed: a loaded machine makes the
    # pause longer, never shorter
    assert vacant["dur_ms"] - vacant["attrs"]["queued_ms"] >= 45


# -- a pass that found nothing to do books nothing ----------------------

def test_an_idle_pass_books_neither_the_round_nor_its_children(monkeypatch):
    eng = make_engine("gpt", n_slots=1)
    tr = telemetry.enable("idle")
    with ContinuousBatcher(eng, default_max_tokens=4) as b:
        monkeypatch.setattr(eng, "can_admit", lambda n: False)
        before = numbers()
        req = b.submit(np.arange(1, 6))
        time.sleep(0.05)            # some ten capacity-stalled passes
        idle = {k: numbers()[k] - before[k]
                for k in ("round_s", "expire_s", "admit_s", "rounds")}
        names = {r["name"] for r in tr.records() if r["type"] == "span"}
        monkeypatch.undo()
        out = req.result(timeout=120)
    assert idle == {"round_s": 0, "expire_s": 0, "admit_s": 0, "rounds": 0}
    assert names <= {"decode.wait"}
    assert len(out) == 4


def test_a_discarded_span_takes_what_ended_inside_it_along():
    class Seconds:
        def __init__(self):
            self.s = {}

        def add_seconds(self, key, seconds):
            self.s[key] = self.s.get(key, 0.0) + seconds

    src = Seconds()
    tr = telemetry.enable("discard")
    with telemetry.span("kept", counter=(src, "kept")):
        pass
    with telemetry.span("outer", counter=(src, "outer")) as outer:
        with telemetry.span("child", counter=(src, "child")) as child:
            with telemetry.span("grandchild"):
                pass
        telemetry.event("happened")
        child.discard()             # over already: its booking is undone
        outer.discard()
    assert src.s["child"] == pytest.approx(0.0, abs=1e-12)
    assert "outer" not in src.s and src.s["kept"] > 0
    assert [(r["type"], r["name"]) for r in tr.records()] \
        == [("span", "kept"), ("event", "happened")]
