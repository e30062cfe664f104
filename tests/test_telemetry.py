"""Unified run-telemetry tests (runtime/telemetry.py tentpole).

Covers the acceptance criteria:
- tracer mechanics: nesting via the thread-local stack, per-thread
  isolation, attributes, decorator form, bounded ring buffer with a
  dropped counter, near-free disabled path;
- exporters: append-only JSONL journal round-trip and
  chrome://tracing/Perfetto trace JSON validity;
- MetricsRegistry: one snapshot over all four counter families, mark/
  since_mark deltas, compile_delta_since_mark;
- the instrumented REAL paths: a sharded fit() whose journal's nested
  spans cover >= 95% of measured wall time, a concurrent DynamicBatcher
  run with the full request lifecycle (enqueue -> cohort-formed ->
  dispatch -> complete with queue-age), sharded PrefetchIterator staging
  events, ResilientFit checkpoint/rollback events;
- the overhead contract: tracer OFF and ON, a warmed fit shows
  compile_delta_since_mark == 0;
- the `cli.py telemetry` summarizer (text + --export-trace).
"""

import json
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import LayerKind, NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.runtime import telemetry
from deeplearning4j_tpu.runtime.metrics import compile_metrics
from deeplearning4j_tpu.runtime.telemetry import (MetricsRegistry, Tracer,
                                                  chrome_trace,
                                                  read_journal, registry,
                                                  summarize_journal)


@pytest.fixture(autouse=True)
def _no_global_tracer():
    """Telemetry is process-global; never leak an enabled tracer into
    other tests."""
    telemetry.disable()
    yield
    telemetry.disable()


def _mlp_conf():
    return (NeuralNetConfiguration.builder()
            .n_in(4).lr(0.1).momentum(0.5).use_adagrad(False)
            .num_iterations(1).activation("tanh")
            .list(2).hidden_layer_sizes(8)
            .override(1, kind=LayerKind.OUTPUT, n_out=3,
                      activation="softmax", loss_function="mcxent")
            .pretrain(False).backward(True).build())


def _batches(n=4, rows=32, seed=0):
    rng = np.random.RandomState(seed)
    return [DataSet(rng.randn(rows, 4).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.randint(0, 3, rows)])
            for _ in range(n)]


# -- tracer mechanics -------------------------------------------------------

def test_span_nesting_and_attributes():
    t = Tracer(run_id="t1")
    with t.span("outer", a=1) as outer:
        with t.span("inner") as inner:
            inner.set(rows=7)
        t.event("tick", n=3)
    recs = t.records()
    spans = {r["name"]: r for r in recs if r["type"] == "span"}
    assert spans["inner"]["parent"] == outer.sid
    assert spans["outer"]["parent"] is None
    assert spans["inner"]["attrs"] == {"rows": 7}
    assert spans["outer"]["attrs"] == {"a": 1}
    ev = next(r for r in recs if r["type"] == "event")
    assert ev["parent"] == outer.sid and ev["attrs"] == {"n": 3}
    # inner closed before outer: journal order is completion order
    assert [r["name"] for r in recs if r["type"] == "span"] == \
        ["inner", "outer"]
    assert spans["outer"]["dur_ms"] >= spans["inner"]["dur_ms"]


def test_span_records_error_attribute():
    t = Tracer()
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("x")
    (rec,) = t.records()
    assert rec["attrs"]["error"] == "ValueError"


def test_threads_get_independent_span_stacks():
    t = Tracer()
    ready = threading.Event()

    def worker():
        with t.span("child_thread"):
            ready.wait(1.0)

    with t.span("main_thread"):
        th = threading.Thread(target=worker)
        th.start()
        time.sleep(0.01)
        ready.set()
        th.join()
    spans = {r["name"]: r for r in t.records()}
    # the worker's span must NOT be parented under the main thread's
    assert spans["child_thread"]["parent"] is None
    assert spans["child_thread"]["tid"] != spans["main_thread"]["tid"]


def test_ring_buffer_bounds_and_counts_drops():
    t = Tracer(capacity=10)
    for i in range(25):
        t.event("e", i=i)
    recs = t.records()
    assert len(recs) == 10
    assert t.dropped == 15
    # oldest dropped first
    assert [r["attrs"]["i"] for r in recs] == list(range(15, 25))
    assert t._header()["dropped"] == 15


def test_disabled_module_api_records_nothing():
    """Off means: a span is still a span (it times itself, feeds its
    counter and is a profiler annotation), but no tracer exists, none is
    made, and nothing is kept anywhere."""
    assert telemetry.get_tracer() is None
    assert not telemetry.enabled()
    sp = telemetry.span("anything", k=1)
    with sp:
        sp.set(more=2)
    assert sp.dur_s >= 0.0 and sp.sid is None and sp.parent is None
    telemetry.event("nothing", x=1)       # no tracer: swallowed
    telemetry.completed("nothing", 0.0, 1.0)
    assert telemetry.get_tracer() is None
    tr = telemetry.enable("on")
    assert tr.count() == 0                # nothing from before it was on
    with telemetry.span("real"):
        pass
    assert [r["name"] for r in tr.records()] == ["real"]
    assert telemetry.disable() is tr
    with telemetry.span("after"):
        pass
    assert tr.count() == 1 and telemetry.get_tracer() is None


class _Seconds:
    def __init__(self):
        self.got = []

    def add_seconds(self, key, seconds):
        self.got.append((key, seconds))


def test_span_counter_gets_the_spans_own_duration():
    """One pair of clock reads: what the counter is given IS the
    journal's duration, tracer on or off; a discarded span feeds
    neither."""
    sink = _Seconds()
    with telemetry.span("off", counter=(sink, "a_s")) as off:
        time.sleep(0.002)
    assert sink.got == [("a_s", off.dur_s)] and off.dur_s >= 0.002
    tr = telemetry.enable("counted")
    with telemetry.span("on", counter=(sink, "b_s")):
        pass
    with telemetry.span("idle", counter=(sink, "b_s")) as idle:
        idle.discard()
    (rec,) = tr.records()
    assert rec["name"] == "on"
    assert sink.got[1:] == [("b_s", rec["dur_ms"] / 1e3)]
    # the real family refuses a name it does not have
    from deeplearning4j_tpu.runtime.metrics import decode_metrics
    with pytest.raises(KeyError):
        with telemetry.span("typo", counter=(decode_metrics, "fetchs")):
            pass


def test_completed_record_starts_where_it_is_told():
    tr = telemetry.enable("done")
    t1 = time.perf_counter()
    with telemetry.span("outer") as outer:
        telemetry.completed("waited", t1 - 0.25, t1, rid=7)
    waited, _ = tr.records()
    assert waited["type"] == "span" and waited["parent"] == outer.sid
    assert waited["attrs"] == {"rid": 7}
    assert waited["dur_ms"] == pytest.approx(250.0)
    assert waited["ts"] == pytest.approx(t1 - 0.25 - tr._t0)
    assert waited["t_unix_ns"] == tr.wall0_ns + int(waited["ts"] * 1e9)


# -- the profiler's clock ---------------------------------------------------

def _profiled(tmp_path, body):
    """Run ``body()`` under a ``jax.profiler`` session; returns {name:
    start in unix ns} of the ``/host:CPU`` plane's events."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    planes = {p.name: p for p in ProfileData.from_file(path).planes}
    t0 = dict(planes["Task Environment"].stats)["profile_start_time"]
    return {e.name: t0 + e.start_ns
            for line in planes["/host:CPU"].lines for e in line.events}


def test_span_reaches_the_profiler_with_the_tracer_off(tmp_path):
    def body():
        with telemetry.span("probe.off", k=1):
            time.sleep(0.001)

    assert telemetry.get_tracer() is None
    assert "probe.off" in _profiled(tmp_path, body)


def test_journal_and_profiler_agree_on_a_spans_start(tmp_path):
    tr = telemetry.enable("clocks")

    def body():
        with telemetry.span("probe.on"):
            time.sleep(0.001)

    in_trace = _profiled(tmp_path, body)["probe.on"]
    (rec,) = [r for r in tr.records() if r["name"] == "probe.on"]
    assert abs(rec["t_unix_ns"] - in_trace) < 1_000_000


# -- exporters --------------------------------------------------------------

def test_journal_export_is_append_only_and_round_trips(tmp_path):
    path = str(tmp_path / "runs" / "j.jsonl")
    t1 = Tracer(run_id="r1")
    with t1.span("a", k=1):
        pass
    t1.export_journal(path)
    t2 = Tracer(run_id="r2")
    t2.event("joined")
    t2.export_journal(path, snapshot={"counters": {"c": 1}})
    recs = read_journal(path)
    headers = [r for r in recs if r["type"] == "run"]
    assert [h["run_id"] for h in headers] == ["r1", "r2"]  # both runs kept
    assert any(r["type"] == "span" and r["name"] == "a" for r in recs)
    assert any(r["type"] == "event" and r["name"] == "joined"
               for r in recs)
    assert recs[-1]["type"] == "snapshot"
    assert recs[-1]["counters"] == {"c": 1}


def test_chrome_trace_is_valid_perfetto_json(tmp_path):
    t = Tracer(run_id="viz")
    with t.span("outer"):
        with t.span("inner", rows=4):
            pass
        t.event("mark", n=1)
    out = str(tmp_path / "trace.json")
    with open(out, "w") as f:
        json.dump(chrome_trace(t.records(), run_id=t.run_id), f)
    with open(out) as f:
        payload = json.load(f)            # valid JSON by construction
    events = payload["traceEvents"]
    assert isinstance(events, list) and events
    slices = [e for e in events if e.get("ph") == "X"]
    instants = [e for e in events if e.get("ph") == "i"]
    metas = [e for e in events if e.get("ph") == "M"]
    assert {e["name"] for e in slices} == {"outer", "inner"}
    assert instants[0]["name"] == "mark" and instants[0]["s"] == "t"
    assert any(m["name"] == "process_name" for m in metas)
    for e in slices:
        # µs timestamps, µs durations, args carry the attrs
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["args"], dict)
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    inner = next(e for e in slices if e["name"] == "inner")
    assert inner["args"] == {"rows": 4}


def test_chrome_trace_export_survives_numpy_attrs(tmp_path):
    """Both exports accept the same attr values: a numpy scalar span
    attribute must not crash the Perfetto export of live records
    (``default=str``, as export_journal stringifies them)."""
    t = Tracer()
    with t.span("np.block", n=np.int32(3), f=np.float32(1.5)):
        pass
    jpath = t.export_journal(str(tmp_path / "np.jsonl"))
    tpath = str(tmp_path / "np_trace.json")
    with open(tpath, "w") as f:
        json.dump(chrome_trace(t.records()), f, default=str)
    with open(tpath) as f:
        payload = json.load(f)
    (sl,) = [e for e in payload["traceEvents"] if e.get("ph") == "X"]
    assert sl["name"] == "np.block"
    assert read_journal(jpath)


def test_cli_train_telemetry_flag_defaults():
    """Bare `--telemetry` resolves to the default journal dir; an
    explicit DIR is preserved; omitted stays off."""
    from deeplearning4j_tpu.cli import build_parser

    base = ["train", "--input", "x.csv", "--conf", "c.json",
            "--output", "m.bin"]
    p = build_parser()
    assert p.parse_args(base).telemetry is None
    assert p.parse_args(base + ["--telemetry"]).telemetry is True
    assert p.parse_args(base + ["--telemetry", "mydir"]).telemetry == \
        "mydir"


# -- MetricsRegistry --------------------------------------------------------

def test_registry_snapshot_structure_and_deltas():
    class FakeCounter:
        def __init__(self):
            self.n = 0

        def snapshot(self):
            return {"n": self.n, "label": "x", "nested": {"m": self.n * 2}}

    reg = MetricsRegistry()
    fake = FakeCounter()
    reg.register("fake", fake)
    with pytest.raises(TypeError):
        reg.register("bad", object())
    fake.n = 3
    reg.mark()
    fake.n = 10
    snap = reg.snapshot()
    assert snap["counters"]["fake"]["n"] == 10
    assert snap["since_mark"]["fake"]["n"] == 7
    assert snap["since_mark"]["fake"]["nested"]["m"] == 14
    assert snap["since_mark"]["fake"]["label"] == "x"   # non-numeric as-is
    assert snap["wall_s"] >= 0 and "wall0" in snap
    assert "peak_bytes_in_use" in snap["device_memory"]
    assert snap["telemetry_enabled"] is False and snap["run_id"] is None


def test_process_registry_has_all_counter_families():
    snap = registry.snapshot()
    assert set(registry.sources()) == {"compile", "resilience", "serving",
                                       "decode", "dp", "checkpoint", "mfu",
                                       "multihost", "ingest"}
    assert "compile_count" in snap["counters"]["compile"]
    assert "requests" in snap["counters"]["serving"]
    assert "tokens_out" in snap["counters"]["decode"]
    # tier-3 counters ride the existing "decode" family — NO new family
    for key in ("pages_in_use", "pages_in_use_hw", "page_utilization",
                "draft_proposed", "draft_accepted", "draft_accept_rate",
                "swaps_completed", "requests_during_swap"):
        assert key in snap["counters"]["decode"], key
    # PR 17 fault-tolerance counters ALSO ride "decode" — still no new
    # family (deadline expiry, replica replacement, deterministic
    # replay, brownout ladder, and the pages-leaked gauge)
    for key in ("deadline_expirations", "replicas_replaced",
                "requests_replayed", "brownout_transitions",
                "brownout_level", "pages_leaked"):
        assert key in snap["counters"]["decode"], key
    assert "dispatches" in snap["counters"]["dp"]
    assert "snapshots_committed" in snap["counters"]["checkpoint"]
    assert "estimates" in snap["counters"]["mfu"]
    assert "cluster_commits" in snap["counters"]["multihost"]
    # PR 20 distributed data service counters: the "ingest" family
    for key in ("bytes_staged", "batches_staged", "stage_ms", "depth_hw",
                "reassignments", "state_roundtrips", "seed_agreements"):
        assert key in snap["counters"]["ingest"], key


def test_registry_reports_run_id_and_span_counts_when_enabled():
    tr = telemetry.enable("runid-test")
    with telemetry.span("s"):
        pass
    snap = registry.snapshot()
    assert snap["run_id"] == "runid-test"
    assert snap["telemetry_enabled"] is True
    assert snap["spans_recorded"] == 1 and snap["spans_dropped"] == 0
    assert tr is telemetry.get_tracer()


# -- overhead contract ------------------------------------------------------

def test_warmed_fit_has_zero_compile_delta_tracer_off_and_on():
    """THE overhead gate: after one warming fit, repeat fits — tracer
    off and tracer on — must add ZERO XLA compiles (telemetry is host-
    side only and never changes a jitted program)."""
    net = MultiLayerNetwork(_mlp_conf()).init(seed=1)
    batches = _batches()
    net.fit_backprop(batches, num_epochs=1)       # warm every program
    registry.mark()
    net.fit_backprop(batches, num_epochs=1)       # tracer OFF
    assert registry.compile_delta_since_mark() == 0
    telemetry.enable("overhead")
    registry.mark()
    net.fit_backprop(batches, num_epochs=1)       # tracer ON
    assert registry.compile_delta_since_mark() == 0


# -- instrumented real paths ------------------------------------------------

def test_sharded_fit_journal_covers_wall_time(tmp_path, devices):
    """A sharded (auto-mesh, 8 virtual devices) fit under the tracer
    produces a journal whose Perfetto conversion is valid and whose
    nested spans cover >= 95% of the measured fit wall time."""
    from deeplearning4j_tpu.parallel.mesh import auto_data_mesh

    assert auto_data_mesh() is not None           # 8-device test platform
    net = MultiLayerNetwork(_mlp_conf()).init(seed=2)
    batches = _batches(rows=32)
    net.fit_backprop(batches, num_epochs=2)       # warm compiles first
    tr = telemetry.enable("sharded-fit")
    t0 = time.perf_counter()
    net.fit_backprop(batches, num_epochs=2)
    wall_s = time.perf_counter() - t0
    path = str(tmp_path / "fit.jsonl")
    tr.export_journal(path, snapshot=registry.snapshot())
    recs = read_journal(path)
    spans = [r for r in recs if r["type"] == "span"]
    fit = next(r for r in spans if r["name"] == "multilayer.fit")
    assert fit["attrs"]["path"] == "dp"           # it actually sharded
    # >= 95% of measured wall time inside the root span
    assert fit["dur_ms"] >= 0.95 * wall_s * 1e3
    # nesting: dispatch under fit, engine dispatch under that
    disp = next(r for r in spans if r["name"] == "multilayer.dispatch")
    assert disp["parent"] == fit["sid"]
    assert disp["attrs"]["data_degree"] == 8
    dp = next(r for r in spans if r["name"] == "dp.dispatch")
    assert dp["parent"] == disp["sid"] and dp["attrs"]["scanned"]
    stage = next(r for r in spans if r["name"] == "multilayer.stage")
    assert stage["parent"] == fit["sid"] and stage["attrs"]["bytes"] > 0
    # the Perfetto conversion round-trips as JSON with every span
    payload = json.loads(json.dumps(chrome_trace(recs)))
    names = {e["name"] for e in payload["traceEvents"]
             if e.get("ph") == "X"}
    assert {"multilayer.fit", "multilayer.dispatch",
            "dp.dispatch"} <= names
    # the embedded registry snapshot names this run
    snap = next(r for r in recs if r["type"] == "snapshot")
    assert snap["run_id"] == "sharded-fit"


def test_prefetch_staging_emits_ingest_events(devices):
    from deeplearning4j_tpu.datasets.iterator import (ListDataSetIterator,
                                                      PrefetchIterator)
    from deeplearning4j_tpu.parallel import sharded_fit
    from deeplearning4j_tpu.parallel.mesh import auto_data_mesh

    mesh = auto_data_mesh()
    tr = telemetry.enable("ingest")
    inner = ListDataSetIterator(_batches(3, rows=16), batch_size=16)
    it = PrefetchIterator(inner, depth=2,
                          sharding=sharded_fit.batch_sharding(mesh),
                          pad_rows_to=8)
    n = 0
    while it.has_next():
        it.next()
        n += 1
    assert n == 3
    events = [r for r in tr.records() if r["type"] == "event"
              and r["name"] == "ingest.stage"]
    assert len(events) == 3
    for e in events:
        assert e["attrs"]["bytes"] > 0
        assert e["attrs"]["rows"] == 16
        assert e["attrs"]["stage_ms"] >= 0


def test_resilient_fit_emits_checkpoint_events(tmp_path):
    from deeplearning4j_tpu.runtime.resilience import (ResilienceConfig,
                                                       ResilientFit)

    tr = telemetry.enable("resilient")
    net = MultiLayerNetwork(_mlp_conf()).init(seed=3)
    cfg = ResilienceConfig(checkpoint_dir=str(tmp_path / "ckpt"),
                           checkpoint_every=2, shuffle=False)
    ResilientFit(net, cfg, mesh=None).fit(_batches(4, rows=16),
                                          num_epochs=1)
    spans = [r for r in tr.records() if r["type"] == "span"]
    ckpts = [r for r in spans if r["name"] == "resilience.checkpoint"]
    assert ckpts and all("step" in r["attrs"] for r in ckpts)


def test_resilient_fit_accumulates_model_guard_skips(tmp_path):
    """Driver-run fits must keep the model's cumulative guard_skips
    counter honest (MetricsListener logs it per record)."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.runtime.resilience import (ResilienceConfig,
                                                       ResilientFit)

    net = MultiLayerNetwork(_mlp_conf()).init(seed=5)
    batches = _batches(2, rows=16)
    feats = np.asarray(batches[0].features).copy()
    feats[0, 0] = np.nan
    batches[0] = DataSet(feats, batches[0].labels)
    cfg = ResilienceConfig(checkpoint_dir=str(tmp_path / "ck"),
                           checkpoint_every=100, shuffle=False,
                           min_history=100)     # no spike rollbacks
    ResilientFit(net, cfg, mesh=None).fit(batches, num_epochs=1)
    assert net.guard_skips >= 1


def test_batcher_journal_has_request_lifecycle(tmp_path):
    """Concurrent DynamicBatcher traffic under the tracer: the journal
    carries the full lifecycle (enqueue -> cohort_formed -> dispatch
    span -> complete with latency) with a queue-age attribute, and the
    Perfetto conversion stays valid."""
    from deeplearning4j_tpu.serving import DynamicBatcher

    net = MultiLayerNetwork(_mlp_conf()).init(seed=4)
    eng = net.serving_engine(buckets=(2, 4, 8, 16))
    eng.warmup(input_shape=(4,))
    tr = telemetry.enable("serving-run")
    registry.mark()
    rng = np.random.RandomState(0)
    results = {}

    with DynamicBatcher(eng, max_batch_size=16, max_delay_ms=5.0) as b:
        def client(cid):
            x = rng.randn(1 + cid % 3, 4).astype(np.float32)
            results[cid] = (x, b.submit(x).result(timeout=30))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    for cid, (x, out) in results.items():
        ref = np.asarray(net.feed_forward(net.params, x)[-1])
        np.testing.assert_array_equal(np.asarray(out), ref)
    # zero steady-state compiles under tracing (engine was warmed)
    assert registry.compile_delta_since_mark() == 0

    recs = tr.records()
    events = [r for r in recs if r["type"] == "event"]
    spans = [r for r in recs if r["type"] == "span"]
    enq = [e for e in events if e["name"] == "serving.enqueue"]
    formed = [e for e in events if e["name"] == "serving.cohort_formed"]
    done = [e for e in events if e["name"] == "serving.complete"]
    assert len(enq) == 8 and len(done) == 8
    assert formed and all(e["attrs"]["queue_age_ms"] >= 0 for e in formed)
    assert sum(e["attrs"]["n_requests"] for e in formed) == 8
    assert all(e["attrs"]["latency_ms"] > 0 for e in done)
    cohorts = [s for s in spans if s["name"] == "serving.cohort"]
    infers = [s for s in spans if s["name"] == "serving.infer"]
    dispatches = [s for s in spans if s["name"] == "serving.dispatch"]
    assert cohorts and infers and dispatches
    # nesting on the worker thread: dispatch < infer < cohort
    by_sid = {s["sid"]: s for s in spans}
    for d in dispatches:
        assert by_sid[d["parent"]]["name"] == "serving.infer"
    for i in infers:
        assert by_sid[i["parent"]]["name"] == "serving.cohort"
    # valid Perfetto trace JSON out of the journal
    path = str(tmp_path / "serving.jsonl")
    tr.export_journal(path, snapshot=registry.snapshot())
    payload = json.loads(json.dumps(chrome_trace(read_journal(path))))
    assert any(e.get("ph") == "X" and e["name"] == "serving.cohort"
               for e in payload["traceEvents"])


def test_decode_loop_counts_its_phases_where_they_happen():
    """A warmed tiny paged batcher: every phase counter moves, the
    enclosing ones are the larger, and one request's records share its
    ``rid``."""
    import jax

    from deeplearning4j_tpu.models import gpt
    from deeplearning4j_tpu.models.transformer import TransformerConfig
    from deeplearning4j_tpu.runtime.metrics import decode_metrics
    from deeplearning4j_tpu.serving.decode import (ContinuousBatcher,
                                                   DecodeEngine)

    cfg = TransformerConfig(vocab_size=64, max_len=64, hidden=32,
                            n_layers=2, n_heads=2, ffn_dim=64, dropout=0.0,
                            compute_dtype="float32", causal=True,
                            type_vocab_size=1)
    eng = DecodeEngine(cfg, gpt.init_params(jax.random.key(7), cfg),
                       n_slots=2, buckets=(32,), prefill_chunk=8)
    eng.warmup()
    decode_metrics.reset()
    tr = telemetry.enable("decode-loop")
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 64, size=n) for n in (5, 9, 12, 17, 7)]
    with ContinuousBatcher(eng, default_max_tokens=6) as b:
        reqs = [b.submit(p, seed=i) for i, p in enumerate(prompts)]
        outs = [r.result(timeout=120) for r in reqs]
    assert all(len(o) == 6 for o in outs)

    snap = decode_metrics.snapshot()
    for key in ("rounds", "admissions", "queue_wait_s", "prefill_s",
                "prefill_sync_s", "advance_s", "fetch_s", "round_s"):
        assert snap[key] > 0, key
    assert snap["round_s"] >= snap["advance_s"] >= snap["fetch_s"]
    assert snap["prefill_s"] >= snap["prefill_sync_s"]
    assert snap["admissions"] == len(prompts) == snap["requests_completed"]
    assert 1 <= snap["decode_dispatches"] / snap["rounds"] <= 1 + 1e-9
    decode_metrics.reset()
    assert decode_metrics.snapshot()["round_s"] == 0.0

    recs = tr.records()
    spans = [r for r in recs if r["type"] == "span"]
    by_sid = {r["sid"]: r for r in spans}
    counted = {n: sum(r["dur_ms"] for r in spans if r["name"] == n) / 1e3
               for n in ("decode.round", "decode.advance", "decode.fetch",
                         "decode.prefill", "decode.prefill.sync",
                         "decode.queue_wait")}
    # the counter and the journal are one measurement
    for name, key in (("decode.round", "round_s"),
                      ("decode.advance", "advance_s"),
                      ("decode.fetch", "fetch_s"),
                      ("decode.prefill", "prefill_s"),
                      ("decode.prefill.sync", "prefill_sync_s"),
                      ("decode.queue_wait", "queue_wait_s")):
        assert counted[name] == pytest.approx(snap[key], rel=1e-6), name
    # nesting on the worker thread
    parent = {r["name"]: by_sid[r["parent"]]["name"] for r in spans
              if r["parent"] is not None}
    assert parent["decode.fetch"] == "decode.advance"
    assert parent["decode.dispatch"] == "decode.advance"
    assert parent["decode.stage"] == "decode.advance"
    assert parent["decode.advance"] == parent["decode.admit"] \
        == parent["decode.deliver"] == "decode.round"
    assert parent["decode.prefill"] == parent["decode.queue_wait"] \
        == "decode.admit"
    assert any(r["name"] == "decode.wait" for r in spans)
    # one request, one rid, on every record of its life
    for req in reqs:
        mine = [r["name"] for r in recs
                if r["attrs"].get("rid") == req.rid]
        # a join registers only pages no prefix hit covered, and a
        # slot's first placement ends no vacancy
        always = {"decode.complete", "decode.join", "decode.prefill",
                  "decode.queue_wait", "decode.admit.pick",
                  "decode.prefix.lookup", "decode.first_token"}
        assert always <= set(mine) <= always | {
            "decode.prefix.register", "decode.slot_vacant"}, mine
    assert len({r.rid for r in reqs}) == len(reqs)


def test_paged_decode_names_its_scopes_in_the_lowered_program():
    """Metadata only: what asks for an op is readable from its
    ``op_name`` (``compiled.as_text()`` / a trace's device ops)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import gpt

    cfg = gpt.gpt_tiny()
    params = jax.eval_shape(lambda: gpt.init_params(jax.random.key(0), cfg))
    pool = jax.eval_shape(lambda: gpt.init_pages(cfg, 9, 32))
    S, TBL = 2, 4
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    text = jax.jit(lambda *a: gpt.paged_decode(cfg, *a)).lower(
        params, pool, i32(S, TBL), i32(S), i32(S),
        jax.ShapeDtypeStruct((S,), jnp.bool_),
        jax.ShapeDtypeStruct((S,), jnp.float32),
        jax.ShapeDtypeStruct((S,), jnp.uint32)).as_text(debug_info=True)
    for scope in ("page_read", "row_write", "attention", "readout"):
        assert f"/{scope}/" in text, scope
    text = jax.jit(lambda *a: gpt.paged_prefill(cfg, *a)).lower(
        params, pool, i32(TBL), i32(32), i32(), i32(),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.uint32)).as_text(debug_info=True)
    for scope in ("prefill_page_io", "attention", "readout"):
        assert f"/{scope}/" in text, scope


def test_compile_metrics_counts_xla_compile_requests():
    """``compile_count`` counts traces through the engine; a plain
    ``jax.jit`` — or a recompile without a retrace — shows only in what
    XLA was asked for."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x) * 3.0 + 0.4321)
    x = jnp.arange(7, dtype=jnp.float32)
    before = compile_metrics.snapshot()
    f(x).block_until_ready()
    first = compile_metrics.snapshot()
    f(x).block_until_ready()
    second = compile_metrics.snapshot()
    assert first["xla_compile_requests"] > before["xla_compile_requests"]
    assert second["xla_compile_requests"] == first["xla_compile_requests"]
    assert first["compile_count"] == before["compile_count"]
    for key in ("persistent_cache_hits", "persistent_cache_misses"):
        assert isinstance(second[key], int)


# -- journal summarizer + CLI -----------------------------------------------

def _sample_journal(tmp_path):
    tr = Tracer(run_id="sum")
    with tr.span("fit"):
        for i in range(3):
            with tr.span("epoch", epoch=i):
                time.sleep(0.002)
        tr.event("resilience.guard_skips", count=2)
    path = str(tmp_path / "sum.jsonl")
    tr.export_journal(path, snapshot={"counters": {"compile":
                                                   {"compile_count": 5}}})
    # a second snapshot so the summarizer reports deltas
    with open(path, "a") as f:
        f.write(json.dumps({"type": "snapshot",
                            "counters": {"compile":
                                         {"compile_count": 9}}}) + "\n")
    return path


def test_summarize_multi_run_journal_keeps_trees_separate(tmp_path):
    """sids restart at 1 per Tracer; an appended two-run journal must
    resolve parents within each run segment, never across them."""
    path = str(tmp_path / "two_runs.jsonl")
    t1 = Tracer(run_id="r1")
    with t1.span("alpha"):          # r1: sid 1 = alpha, child beta
        with t1.span("beta"):
            pass
    t1.export_journal(path)
    t2 = Tracer(run_id="r2")
    with t2.span("gamma"):          # r2: sid 1 = gamma, child delta
        with t2.span("delta"):
            pass
    t2.export_journal(path)
    s = summarize_journal(read_journal(path))
    paths = {tuple(r["path"]) for r in s["tree"]}
    # each child sits under ITS OWN run's root — no cross-run grafting
    assert ("alpha", "beta") in paths and ("gamma", "delta") in paths
    assert not any(p[0] == "gamma" and "beta" in p for p in paths)
    # the Perfetto conversion keeps the runs on separate process tracks
    # (each run's relative timestamps restart near zero — one shared
    # track would superimpose them)
    payload = chrome_trace(read_journal(path))
    pid_of = {e["name"]: e["pid"] for e in payload["traceEvents"]
              if e.get("ph") == "X"}
    assert pid_of["alpha"] == pid_of["beta"]
    assert pid_of["gamma"] == pid_of["delta"]
    assert pid_of["alpha"] != pid_of["gamma"]
    run_names = {e["args"]["name"] for e in payload["traceEvents"]
                 if e.get("name") == "process_name"}
    assert run_names == {"dl4j-tpu r1", "dl4j-tpu r2"}


def test_summarize_journal_tree_top_and_deltas(tmp_path):
    path = _sample_journal(tmp_path)
    s = summarize_journal(read_journal(path), top_k=2)
    assert s["n_spans"] == 4 and s["n_events"] == 1
    tree = {tuple(r["path"]): r for r in s["tree"]}
    assert tree[("fit",)]["count"] == 1
    assert tree[("fit", "epoch")]["count"] == 3   # aggregated by name
    assert tree[("fit", "epoch")]["depth"] == 1
    assert len(s["top"]) == 2
    assert s["top"][0]["dur_ms"] >= s["top"][1]["dur_ms"]
    assert s["events"] == {"resilience.guard_skips": 1}
    assert s["counter_deltas"]["compile"]["compile_count"] == 4


def test_cli_telemetry_subcommand(tmp_path, capsys):
    from deeplearning4j_tpu.cli import main

    path = _sample_journal(tmp_path)
    out_trace = str(tmp_path / "out_trace.json")
    rc = main(["telemetry", "--journal", path, "--top", "3",
               "--export-trace", out_trace])
    assert rc == 0
    out = capsys.readouterr().out
    assert "run sum" in out
    assert "fit" in out and "epoch" in out
    assert "counter deltas" in out and '"compile_count": 4' in out
    with open(out_trace) as f:
        payload = json.load(f)
    assert any(e.get("ph") == "X" for e in payload["traceEvents"])
    # --json mode emits machine-readable summary
    rc = main(["telemetry", "--journal", path, "--json"])
    assert rc == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["n_spans"] == 4
