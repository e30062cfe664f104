"""Driver ``closed_loop_family``: ``closed_loop``'s callers, window and
comparison for a model family other than the GPT block.  Everything
model-specific comes from the module the configuration's file names
(``"family"``: a module under ``benchmark/families/``): weights from the
seed, the program's config, the engine's arguments, what the algorithm
needs in operations and bytes, the reference's call.  The table it
fills has ``closed_loop``'s keys, so the metrics that read that one
(``serve_mfu``, ``decode_step_roofline``, ``decode_step_dev_ms``,
``prefill_dev_ms``, the spans') read this one unchanged; beside them
``scopes``: device seconds of the decode dispatches by
``jax.named_scope`` (``lib/scope_reduce.py``), and the program's
counters at the traced span's two ends (a family's needed bytes follow
what its router chose, which only the program counts).

The traffic file may carry, in its ``rehearse`` block, ``config``: the
sizes that replace the configuration's on the CPU rehearsal
(``load_cell`` merges the block; ``rehearse.json`` holds the GPT
block's).
"""

from __future__ import annotations

import importlib
import math
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark.drivers.closed_loop import (Served, check_rows, counters_now,
                                           pick_sample)
from benchmark.lib import device as devlib, scope_reduce, traffic
from benchmark.lib import trace_reduce
from benchmark.lib.stats import percentile
from benchmark.lib.tracing import WindowTrace


def family_of(config: Dict[str, Any]):
    return importlib.import_module(f"benchmark.families.{config['family']}")


def sized(cell) -> Dict[str, Any]:
    """The configuration at the sizes of this run: its own, or on a
    rehearsal the traffic file's ``config`` over them."""
    return {**cell.config, **cell.traffic.get("config", {})}


class ScopedTrace(WindowTrace):
    """A ``WindowTrace`` that also notes the program's counters where
    the traced span starts and ends, and reduces the trace a second way:
    device seconds by named scope."""

    def __init__(self, *args, snapshot, scopes, program, hlo_texts,
                 **kwargs):
        super().__init__(*args, **kwargs)
        self._snapshot = snapshot
        self._scopes, self._program = scopes, program
        self._hlo_texts = hlo_texts
        self.counters_lo: Optional[Dict[str, Any]] = None
        self.counters_hi: Optional[Dict[str, Any]] = None
        self.scopes: Optional[Dict[str, Any]] = None

    def poll(self, now: float) -> None:
        started = self._dir is not None
        super().poll(now)
        if not started and self._dir is not None:
            self.counters_lo = self._snapshot()

    def finish(self) -> None:
        if not self.done and self._dir is not None:
            self.counters_hi = self._snapshot()
        super().finish()

    def reduce(self) -> Optional[Dict[str, Any]]:
        if self.span is not None:
            try:
                profile = trace_reduce.load(trace_reduce.find_xplane(
                    self._dir))
                self.scopes = scope_reduce.scope_seconds(
                    profile, self._scopes, self._program, self._hlo_texts)
            except Exception as e:  # noqa: BLE001 — a scope that cannot be read is left out, not fatal
                print(f"[trace] scope reduction failed: {e!r}",
                      file=sys.stderr)
        return super().reduce()


def token_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Token by token, how far the token's reference logit lies under
    the reference's best: ``ref_logits`` [n, V] at the positions that
    predicted ``tokens`` [n]."""
    got = np.take_along_axis(ref_logits, tokens[:, None].astype(np.int64),
                             axis=-1)[:, 0]
    return ref_logits.max(axis=-1) - got


def gap_numbers(logits: List[np.ndarray], spans: List[tuple],
                tokens: List[np.ndarray]) -> Dict[str, float]:
    """Over every checked token of every sampled request: the MEAN gap
    (``served_gap_mean``, what ``correct`` compares) and the widest
    (``served_gap_max``, cell 2's ``served_gap``, reported only).

    An expert layer's choice among near-equal router scores turns on
    rounding, and a turned choice moves that token's logits by an
    expert's whole output: the widest gap over thousands of tokens is
    then the same few logits wide in bfloat16 and in float8 (PERF.md,
    section 6, PR 28), while the mean, which no single token moves,
    differs by an order of magnitude."""
    gaps = np.concatenate([token_gaps(lg[a:b], np.asarray(t))
                           for lg, (a, b), t in zip(logits, spans, tokens)])
    return {"served_gap_mean": float(gaps.mean()),
            "served_gap_max": float(gaps.max())}


def delta(hi: Dict[str, Any], lo: Dict[str, Any]) -> Dict[str, float]:
    return {k: hi[k] - lo[k] for k in hi
            if isinstance(hi[k], (int, float))
            and not isinstance(hi[k], bool)
            and isinstance(lo.get(k), (int, float))}


def run(ctx) -> Dict[str, Any]:
    from deeplearning4j_tpu.runtime.metrics import (compile_metrics,
                                                    decode_metrics)
    from deeplearning4j_tpu.serving.decode import (ContinuousBatcher,
                                                   DecodeEngine)

    cell, tr = ctx.cell, ctx.cell.traffic
    config = sized(cell)
    fam = family_of(config)
    n_slots = int(tr["n_slots"])
    vocab = fam.vocab(config)
    reqs = traffic.requests(tr, vocab, ctx.seed)

    def req_of(s: Served) -> traffic.Request:
        return reqs[s.index]

    # the program's config first: a program without this family fails
    # here, before a byte of weights is made
    program_config = fam.program_config(config)
    params = fam.make_params(config, ctx.seed)
    eng = DecodeEngine(program_config, params, n_slots=n_slots,
                       **fam.engine_kwargs(config, tr))
    eng.warmup()
    # a traced run joins the trace's ops to the scopes they were traced
    # under through each rung's optimized HLO (set-up time a traced run
    # alone pays; its setup_s is not reported)
    scopes = list(tr.get("scopes", []))
    hlo_texts: List[str] = []
    if ctx.trace and scopes and hasattr(eng, "decode_hlo"):
        hlo_texts = [eng.decode_hlo(t) for t in eng.buckets]
    batcher = ContinuousBatcher(eng)

    lock = threading.Lock()
    served: List[Served] = []
    cursor = [0]
    stop = threading.Event()

    def caller(i: int) -> None:
        time.sleep(i * float(tr["stagger_seconds"]) / int(tr["callers"]))
        while not stop.is_set():
            with lock:
                index = cursor[0]
                cursor[0] += 1
            r = reqs[index]
            s = Served(index, time.perf_counter())
            try:
                handle = batcher.submit(
                    r.prompt, max_tokens=r.max_tokens,
                    temperature=float(tr["temperature"]),
                    seed=index % (2 ** 31), eos_id=None)
                for tok in handle.stream(timeout=120.0):
                    s.stamps.append(time.perf_counter())
                    s.tokens.append(int(tok))
            except Exception as e:  # noqa: BLE001 — a failed request is counted as failed, not raised
                s.error = e
            with lock:
                served.append(s)

    threads = [threading.Thread(target=caller, args=(i,), daemon=True,
                                name=f"bench-caller-{i}")
               for i in range(int(tr["callers"]))]
    for t in threads:
        t.start()
    time.sleep(float(tr["ramp_seconds"]))

    # the window
    def snapshot() -> Dict[str, Any]:
        return counters_now(decode_metrics)

    xla0 = ctx.ledger.requests
    traces0 = compile_metrics.snapshot()["compile_count"]
    snap0 = snapshot()
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_process
    wt = ScopedTrace(ctx.trace, t0, float(tr["trace_at_seconds"]),
                     float(tr["trace_seconds"]),
                     count=lambda: decode_metrics.prefill_dispatches,
                     at_most=float(tr["trace_most_seconds"]),
                     snapshot=snapshot, scopes=scopes,
                     program=tr["programs"]["decode"], hlo_texts=hlo_texts)
    late = 0.0
    while True:
        now = time.perf_counter()
        wt.poll(now)
        edge = min(x for x in (t0 + ctx.seconds, wt.next_edge())
                   if x is not None)
        if now >= t0 + ctx.seconds:
            break
        before = time.perf_counter()
        nap = max(0.0, min(edge - before, 0.25))
        time.sleep(nap)
        late = max(late, time.perf_counter() - before - nap)
    stop.set()
    t1 = time.perf_counter()
    snap1 = snapshot()
    xla = ctx.ledger.requests - xla0
    traces = compile_metrics.snapshot()["compile_count"] - traces0
    wt.finish()
    window_s = t1 - t0

    deadline = time.perf_counter() + float(tr["drain_seconds"])
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()))
    never = sum(t.is_alive() for t in threads)
    batcher.close()
    snap2 = snapshot()
    eng.drop_residents()
    pages_left = eng._alloc.in_use() + eng.pages_unaccounted()
    peak = devlib.memory_peak_bytes(cell.chips)

    with lock:
        everything = list(served)

    def whole(s: Served) -> bool:
        return (s.error is None and len(s.tokens) == req_of(s).max_tokens
                and all(0 <= t < vocab for t in s.tokens))

    sent = [s for s in everything if t0 <= s.t_send < t1]
    ok = [s for s in sent if whole(s)]
    failed = len(sent) - len(ok) + never

    ttft = [(s.stamps[0] - s.t_send) * 1e3 if whole(s) else math.inf
            for s in sent]
    gaps = [(b - a) * 1e3 for s in sent
            for a, b in zip(s.stamps, s.stamps[1:])]
    in_window = sum(t0 <= x <= t1 for s in everything for x in s.stamps)
    latency = {"ttft_p50_ms": percentile(ttft, 50.0),
               "ttft_p90_ms": percentile(ttft, 90.0),
               "itl_p50_ms": percentile(gaps, 50.0),
               "itl_p95_ms": percentile(gaps, 95.0),
               "itl_p99_ms": percentile(gaps, 99.0)}
    metrics = {"serve_tok_s": in_window / window_s, **latency}

    counters = delta(snap1, snap0)
    # of a token's experts a layer, how many this rank held: the
    # window's own ratio, for prompt tokens too (the counts are of
    # decode steps; the ids of both are uniform over the same slice)
    made = counters.get("moe_assignments", 0)
    held_per_token_layer = (
        config.get("num_experts_per_tok", 0)
        * counters.get("moe_assignments_held", 0) / made if made else 0.0)

    def processed(spans: List[tuple], counted: Dict[str, float]
                  ) -> Dict[str, float]:
        """What the program processed in ``spans`` (by the callers'
        stamps) and needed for it; ``counted`` the program's counters
        over the same time."""
        flops = 0.0
        n_dec = 0
        ctx_sum = 0.0
        n_prefills = 0
        for s in everything:
            p = len(req_of(s).prompt)
            for j, x in enumerate(s.stamps):
                if not any(lo <= x <= hi for lo, hi in spans):
                    continue
                if j == 0:          # the prefill's token
                    flops += fam.sequence_forward_flops(
                        config, p, held_per_token_layer)
                    n_prefills += 1
                else:               # fed at position p + j - 1
                    flops += fam.forward_flops_token(
                        config, p + j, held_per_token_layer, True)
                    n_dec += 1
                    ctx_sum += p + j
        need = fam.decode_needed(
            config, ctx_sum, n_dec, counted.get("decode_dispatches", 0),
            counted.get("moe_expert_hits", 0),
            counted.get("moe_assignments_held", 0))
        return {"model_flops": flops, "decode_tokens": n_dec,
                "prefills": n_prefills, "decode_flops": need["flops"],
                "decode_bytes": need["bytes"],
                "expert_bytes": need["expert_bytes"],
                "seconds": sum(hi - lo for lo, hi in spans)}

    cut_lo, cut_hi = wt.cut or (t1, t1)
    uncut = [(t0, min(cut_lo, t1)), (min(cut_hi, t1), t1)]
    in_trace = (delta(wt.counters_hi, wt.counters_lo)
                if wt.counters_lo and wt.counters_hi else {})
    outside = {k: v - in_trace.get(k, 0) for k, v in counters.items()}

    every_stamp = sorted(x for s in everything for x in s.stamps
                         if t0 <= x <= t1)
    silence, silence_at = max(
        ((b - a, a - t0) for a, b in zip(every_stamp, every_stamp[1:])),
        default=(None, None))

    reduced = wt.reduce()
    table = {"window_s": window_s, "n_slots": n_slots,
             "counters": counters,
             "window": processed([(t0, t1)], counters),
             "uncut": processed(uncut, outside),
             "traced": processed([wt.span], in_trace) if wt.span else {},
             "traced_counters": in_trace,
             "scopes": wt.scopes or {},
             "stats": {"requests_sent": len(sent), "gaps": len(gaps),
                       **latency,
                       "ttft_max_ms": max(ttft, default=None),
                       "itl_max_ms": max(gaps, default=None),
                       "silence_max_ms": silence and silence * 1e3,
                       "silence_at_s": silence_at,
                       "poll_late_max_ms": late * 1e3,
                       "tokens_in_window": in_window,
                       "held_per_token_layer": held_per_token_layer,
                       "prefix_hits": counters.get("prefix_hits")}}

    # the program's state goes before the reference comes
    sample = pick_sample(ok, req_of, int(tr["check_requests"]), ctx.seed)
    del batcher, eng, params
    compared: Dict[str, float] = {}
    t_ref = time.perf_counter()
    if sample:
        rows, spans = check_rows(sample, req_of)
        logits = fam.reference_logits(
            config, fam.make_params(config, ctx.seed), rows, tr)
        numbers = gap_numbers(logits, spans, [s.tokens for s in sample])
        compared["served_gap_mean"] = numbers["served_gap_mean"]
        table["stats"]["served_gap_max"] = numbers["served_gap_max"]
        table["checked_tokens"] = sum(len(s.tokens) for s in sample)
        check = {"rows": rows, "spans": spans,
                 "tokens": [np.asarray(s.tokens) for s in sample],
                 "ref_logits": logits if ctx.keep_check else None}
    else:
        check = None
        compared["served_gap_mean"] = math.inf
    table["reference_s"] = time.perf_counter() - t_ref

    violations = []
    if xla or traces:
        violations.append(f"{traces} trace(s) and {xla} XLA compile(s) "
                          f"inside the window")
    replayed = snap2["requests_replayed"] - snap0["requests_replayed"]
    if replayed:
        violations.append(f"{replayed} request(s) replayed after a failed "
                          f"dispatch")
    if tr.get("prefix_hits") == "none" and counters.get("prefix_hits"):
        violations.append(f"{counters['prefix_hits']} prefix hit(s) in a mix "
                          f"that shares no prefix")
    if pages_left:
        violations.append(f"{pages_left} KV page(s) still allocated after "
                          f"close()")
    if failed:
        violations.append(f"{failed} of {len(sent)} request(s) failed, "
                          f"came back short or never came back")
    if not sample:
        violations.append("no request finished inside the window")

    return {"attempted": len(sent), "failed": failed, "setup_s": setup_s,
            "end_to_end": metrics, "compared": compared,
            "violations": violations, "memory_peak_bytes": peak,
            "trace": reduced, "table": table, "check": check}


def readings(cell, seed: int, res: Dict[str, Any], control: bool
             ) -> Dict[str, Any]:
    """For ``controls.py``, on a control seed: at every position of the
    same prompts and served tokens, the gaps of the tokens that float8
    (the CONTROL) puts first.  On every seed: the program's widest gap,
    which ``correct`` does not compare."""
    check = res.get("check")
    out = {"served_gap_max": res["table"]["stats"].get("served_gap_max")}
    if not control or not check:
        return out
    config = sized(cell)
    fam = family_of(config)
    low = fam.reference_logits(config, fam.make_params(config, seed),
                               check["rows"], cell.traffic, precision="fp8")
    return {**out, "control_fp8": gap_numbers(
        check["ref_logits"], check["spans"],
        [np.argmax(lo[a:b], axis=-1) for lo, (a, b) in zip(low,
                                                           check["spans"])])}
