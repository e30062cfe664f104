"""Driver ``closed_loop``: ``callers`` threads, each sending its next
request through ``ContinuousBatcher.submit`` when its last one finished,
and stamping every token on its own side as ``DecodeRequest.stream()``
yields it.  The serving spine underneath: admission, chunked
``paged_prefill`` into pool pages, ``paged_decode``, sampling.

The callers start (staggered) before the window and run on through it,
so the window sees the steady loop and not sixteen prefills at once;
the ramp counts as set-up.  When the window closes no caller sends
again, and every request in flight is awaited.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark.lib import compare, counts, device as devlib, spec, traffic
from benchmark.lib import weights
from benchmark.lib.stats import percentile
from benchmark.lib.tracing import WindowTrace


class Served:
    """One request as its caller saw it."""

    __slots__ = ("index", "t_send", "stamps", "tokens", "error")

    def __init__(self, index: int, t_send: float):
        self.index = index
        self.t_send = t_send
        self.stamps: List[float] = []
        self.tokens: List[int] = []
        self.error: Optional[Exception] = None


def reference_logits(ref_mod, config: Dict[str, Any], params: Any,
                     rows: List[np.ndarray], tr: Dict[str, Any],
                     precision: str = "f32") -> List[np.ndarray]:
    """Reference logits [len(row), V] for each token row, rows padded
    to the model's positions (causal: the padding is never attended)
    and taken ``reference_rows_per_block`` at a time."""
    import jax.numpy as jnp

    T = int(config["n_positions"])
    step = int(tr["reference_rows_per_block"])
    eps = float(config["layer_norm_epsilon"])
    out: List[np.ndarray] = []
    for lo in range(0, len(rows), step):
        block = rows[lo:lo + step]
        ids = np.zeros((step, T), np.int32)
        for i, r in enumerate(block):
            ids[i, :len(r)] = r
        logits = np.asarray(ref_mod.logits(params, jnp.asarray(ids),
                                           eps=eps, precision=precision))
        out.extend(logits[i, :len(r)] for i, r in enumerate(block))
    return out


def check_rows(sample: List[Served], req_of):
    """For each sampled request: the row the reference reads (prompt +
    served tokens but the last) and where its predictions of the served
    tokens sit."""
    rows, spans = [], []
    for s in sample:
        prompt = req_of(s).prompt
        toks = np.asarray(s.tokens, np.int32)
        rows.append(np.concatenate([prompt, toks[:-1]]))
        spans.append((len(prompt) - 1, len(prompt) - 1 + len(toks)))
    return rows, spans


def pick_sample(done: List[Served], req_of, n: int, seed: int
                ) -> List[Served]:
    """``n`` finished requests drawn from the seed, the longest among
    them."""
    if not done:
        return []
    longest = max(done, key=lambda s: len(req_of(s).prompt) + len(s.tokens))
    rest = [s for s in done if s is not longest]
    gen = traffic.rng(seed, 4)
    take = gen.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[int(i)] for i in sorted(take)]


def counters_now(decode_metrics) -> Dict[str, Any]:
    """The program's exact counts: its snapshot, and the two tallies the
    snapshot only gives as a ratio since process start."""
    return {**decode_metrics.snapshot(),
            "slot_steps": decode_metrics.slot_steps,
            "slot_capacity_steps": decode_metrics.slot_capacity_steps}


def run(ctx) -> Dict[str, Any]:
    from deeplearning4j_tpu.runtime.metrics import (compile_metrics,
                                                    decode_metrics)
    from deeplearning4j_tpu.serving.decode import (ContinuousBatcher,
                                                   DecodeEngine)

    cell, tr, config = ctx.cell, ctx.cell.traffic, ctx.cell.config
    n_slots = int(tr["n_slots"])
    reqs = traffic.requests(tr, config["vocab_size"], ctx.seed)

    def req_of(s: Served) -> traffic.Request:
        return reqs[s.index]

    params = weights.make_params(config, ctx.seed)
    eng = DecodeEngine(weights.program_config(config), params,
                       n_slots=n_slots, paged=True)
    eng.warmup()
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
    xla0 = ctx.ledger.requests
    traces0 = compile_metrics.snapshot()["compile_count"]
    snap0 = counters_now(decode_metrics)
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_process
    # the traced span runs on until it has held a join: the prefill
    # program's reader finds nothing to read in a span without one
    wt = WindowTrace(ctx.trace, t0, float(tr["trace_at_seconds"]),
                     float(tr["trace_seconds"]),
                     count=lambda: decode_metrics.prefill_dispatches,
                     at_most=float(tr["trace_most_seconds"]))
    # this thread only sleeps and polls; how late it wakes says whether
    # a silence on every stream was the whole process's or the loop's
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
    snap1 = counters_now(decode_metrics)
    xla = ctx.ledger.requests - xla0
    traces = compile_metrics.snapshot()["compile_count"] - traces0
    wt.finish()
    window_s = t1 - t0

    # every request in flight is awaited: late is late, not wrong
    deadline = time.perf_counter() + float(tr["drain_seconds"])
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()))
    never = sum(t.is_alive() for t in threads)
    batcher.close()
    snap2 = counters_now(decode_metrics)
    eng.drop_residents()
    pages_left = eng._alloc.in_use() + eng.pages_unaccounted()
    peak = devlib.memory_peak_bytes(cell.chips)

    with lock:
        everything = list(served)

    def whole(s: Served) -> bool:
        return (s.error is None and len(s.tokens) == req_of(s).max_tokens
                and all(0 <= t < config["vocab_size"] for t in s.tokens))

    sent = [s for s in everything if t0 <= s.t_send < t1]
    ok = [s for s in sent if whole(s)]
    failed = len(sent) - len(ok) + never

    # a request that failed or came back short misses every limit
    ttft = [(s.stamps[0] - s.t_send) * 1e3 if whole(s) else math.inf
            for s in sent]
    gaps = [(b - a) * 1e3 for s in sent
            for a, b in zip(s.stamps, s.stamps[1:])]
    in_window = sum(t0 <= x <= t1 for s in everything for x in s.stamps)
    # every number a caller feels; BENCHMARK.json says which of them a
    # cell reports end to end and which stand beside as per-layer
    latency = {"ttft_p50_ms": percentile(ttft, 50.0),
               "ttft_p90_ms": percentile(ttft, 90.0),
               "itl_p50_ms": percentile(gaps, 50.0),
               "itl_p95_ms": percentile(gaps, 95.0),
               "itl_p99_ms": percentile(gaps, 99.0)}
    metrics = {"serve_tok_s": in_window / window_s, **latency}

    # what the window and the traced span processed, for the yardsticks
    def processed(*spans: tuple) -> Dict[str, float]:
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
                    flops += counts.sequence_forward_flops(config, 0, p)
                    n_prefills += 1
                else:               # fed at position p + j - 1
                    flops += counts.forward_flops_token(config, p + j)
                    n_dec += 1
                    ctx_sum += p + j
        need = counts.decode_needed(config, ctx_sum, n_dec, n_slots)
        return {"model_flops": flops, "decode_tokens": n_dec,
                "prefills": n_prefills, "decode_flops": need["flops"],
                "decode_bytes": need["bytes"],
                "seconds": sum(hi - lo for lo, hi in spans)}

    # the window but the seconds from before the profiler's start to
    # after its stop: what a share of peak over the window is taken from
    cut_lo, cut_hi = wt.cut or (t1, t1)
    uncut = [(t0, min(cut_lo, t1)), (min(cut_hi, t1), t1)]

    # the longest time in which no stream got a token, and where it fell
    every_stamp = sorted(x for s in everything for x in s.stamps
                         if t0 <= x <= t1)
    silence, silence_at = max(
        ((b - a, a - t0) for a, b in zip(every_stamp, every_stamp[1:])),
        default=(None, None))

    counters = {k: snap1[k] - snap0[k] for k in snap1
                if isinstance(snap1[k], (int, float))
                and not isinstance(snap1[k], bool)
                and isinstance(snap0.get(k), (int, float))}
    table = {"window_s": window_s, "n_slots": n_slots,
             "counters": counters, "window": processed((t0, t1)),
             "uncut": processed(*uncut),
             "traced": processed(wt.span) if wt.span else {},
             # what the callers saw, for the latency metrics and for
             # the reader of a run's log
             "stats": {"requests_sent": len(sent), "gaps": len(gaps),
                       **latency,
                       "ttft_max_ms": max(ttft, default=None),
                       "itl_max_ms": max(gaps, default=None),
                       "silence_max_ms": silence and silence * 1e3,
                       "silence_at_s": silence_at,
                       "poll_late_max_ms": late * 1e3,
                       "tokens_in_window": in_window,
                       "prefix_hits": counters.get("prefix_hits")}}

    # the program's state goes before the reference comes
    sample = pick_sample(ok, req_of, int(tr["check_requests"]), ctx.seed)
    del batcher, eng, params
    compared: Dict[str, float] = {}
    t_ref = time.perf_counter()
    if sample:
        ref_mod = spec.reference(config["reference"])
        rows, spans = check_rows(sample, req_of)
        ref_params = weights.make_params(config, ctx.seed)
        logits = reference_logits(ref_mod, config, ref_params, rows, tr)
        compared["served_gap"] = max(
            compare.served_gap(lg[a:b], np.asarray(s.tokens))
            for lg, (a, b), s in zip(logits, spans, sample))
        table["checked_tokens"] = sum(len(s.tokens) for s in sample)
        check = {"rows": rows, "spans": spans,
                 "tokens": [np.asarray(s.tokens) for s in sample],
                 "ref_logits": logits if ctx.keep_check else None}
    else:
        check = None
        compared["served_gap"] = math.inf
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
            "trace": wt.reduce(), "table": table, "check": check}


def readings(cell, seed: int, res: Dict[str, Any], control: bool
             ) -> Dict[str, Any]:
    """For ``controls.py``, on a control seed: at every position of the
    same prompts and served tokens, the gap of the token that float8
    (the CONTROL) puts first."""
    check = res.get("check")
    if not control or not check:
        return {}
    tr, config = cell.traffic, cell.config
    low = reference_logits(spec.reference(config["reference"]), config,
                           weights.make_params(config, seed),
                           check["rows"], tr, precision="fp8")
    gap = max(compare.served_gap(ref[a:b], np.argmax(lo[a:b], axis=-1))
              for ref, lo, (a, b) in zip(check["ref_logits"], low,
                                         check["spans"]))
    return {"control_fp8": {"served_gap": gap}}
