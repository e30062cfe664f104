"""Driver ``train_calls``: repeated calls of ``CausalLM.fit_backprop``
(the training spine: ``lm_fit`` -> ``sharded_fit``'s scanned epoch) on
one chip, ``steps_per_call`` batches to a call.

Set-up builds ONE ``CausalLM`` with weights made from the seed, drives
it through its first call by the window's own call and feed, and hands
that same object to the window.  That call's steps are what ``correct``
compares with the plain reference once the window has closed: every
step's loss and the parameters' change after the call.  A call hands
back only its last state, so the first gradient as the optimizer got it
cannot be read where a call holds more than one step.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark.lib import compare, counts, device as devlib, spec, traffic
from benchmark.lib import weights
from benchmark.lib.tracing import WindowTrace


class ScoreLog:
    """IterationListener collecting the per-step scores of a fit."""

    def __init__(self) -> None:
        self.scores: List[float] = []

    def iteration_done(self, model: Any, iteration: int,
                       score: float) -> None:
        self.scores.append(score)


def follow_reference(ref_mod, config: Dict[str, Any], seed: int,
                     batches: List[np.ndarray], tr: Dict[str, Any],
                     precision: str = "f32", rows=None) -> Dict[str, Any]:
    """The plain reference over the first steps: weights from the same
    seed, plain SGD, float32.  ``rows`` (a slice) leaves part of every
    batch out — the half-batch fault, planted in the reference."""
    import jax.numpy as jnp

    eps = float(config["layer_norm_epsilon"])
    out: Dict[str, Any] = {"losses": [], "grad": None}

    def on_step(i, loss, grads, params_after):
        out["losses"].append(float(loss))
        if i == 0:      # names the leaves whose gradient is nought
            out["grad"] = compare.tree_norms(grads)

    feed = [jnp.asarray(b if rows is None else b[rows]) for b in batches]
    params = ref_mod.sgd_steps(
        weights.make_params(config, seed), feed, lr=float(tr["lr"]),
        eps=eps, precision=precision,
        rows_per_block=int(tr["reference_rows_per_block"]), on_step=on_step)
    out["change"] = compare.diff_norms(weights.make_params(config, seed),
                                       params)
    return out


def run(ctx) -> Dict[str, Any]:
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.lm_fit import CausalLM
    from deeplearning4j_tpu.runtime.metrics import compile_metrics

    cell, tr, config = ctx.cell, ctx.cell.traffic, ctx.cell.config
    if float(tr["momentum"]) != 0.0:
        raise SystemExit("train_calls: the reference is plain SGD; "
                         "momentum needs a reference that carries it")
    spc = int(tr["steps_per_call"])
    batches = traffic.train_batches(tr, config["vocab_size"], ctx.seed)
    cursor = 0

    def next_call() -> List[Any]:
        nonlocal cursor
        rows = [batches[(cursor + i) % len(batches)] for i in range(spc)]
        cursor += spc
        return [DataSet(r, r) for r in rows]

    lm = CausalLM(weights.program_config(config), lr=float(tr["lr"]),
                  momentum=float(tr["momentum"]),
                  mixed_precision=tr["mixed_precision"])
    lm.params = weights.make_params(config, ctx.seed)
    log = ScoreLog()
    lm.listeners = [log]

    # the first steps, through the window's own call and feed
    n_check_calls = -(-int(tr["check_steps"]) // spc)
    for _ in range(n_check_calls):
        lm.fit_backprop(next_call(), mesh=None)
    # the first tree is made again for this, not kept beside the steps
    prog: Dict[str, Any] = {
        "losses": list(log.scores),
        "change": compare.diff_norms(
            weights.make_params(config, ctx.seed), lm.params)}
    n_check_steps = n_check_calls * spc
    lm.listeners = []
    jax.block_until_ready(lm.params)

    # the window
    xla0 = ctx.ledger.requests
    traces0 = compile_metrics.snapshot()["compile_count"]
    skips0 = lm.guard_skips
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_process
    wt = WindowTrace(ctx.trace, t0, float(tr["trace_at_seconds"]),
                     float(tr["trace_seconds"]))
    call_ends: List[float] = []
    while True:
        now = time.perf_counter()
        wt.poll(now)
        if now - t0 >= ctx.seconds:
            break
        with jax.profiler.TraceAnnotation("bench.fit_backprop"):
            lm.fit_backprop(next_call(), mesh=None)
        call_ends.append(time.perf_counter())
    jax.block_until_ready(lm.params)
    t1 = time.perf_counter()
    wt.finish()
    window_s = t1 - t0
    steps = len(call_ends) * spc
    # the calls between the profiler's start and its stop (the poll sits
    # between calls, so they are whole calls) are left out of the share
    # of peak, with the seconds they and the start and stop took
    cut_lo, cut_hi = wt.cut or (t1, t1)
    cut_calls = sum(cut_lo < x <= cut_hi for x in call_ends)
    xla = ctx.ledger.requests - xla0
    traces = compile_metrics.snapshot()["compile_count"] - traces0
    skips = lm.guard_skips - skips0
    finite = bool(np.isfinite(
        list(compare.tree_norms(lm.params).values())).all())
    peak = devlib.memory_peak_bytes(cell.chips)

    # the program's state goes before the reference comes
    del lm
    ref_mod = spec.reference(config["reference"])
    t_ref = time.perf_counter()
    ref = follow_reference(ref_mod, config, ctx.seed,
                           [batches[i % len(batches)]
                            for i in range(n_check_steps)], tr)
    ref_s = time.perf_counter() - t_ref
    compared = compare.train_numbers(prog, ref)

    violations = []
    if xla or traces:
        violations.append(f"{traces} trace(s) and {xla} XLA compile(s) "
                          f"inside the window")
    if skips or skips0:
        violations.append(f"the non-finite guard skipped {skips0} step(s) "
                          f"before and {skips} inside the window")
    if not finite:
        violations.append("non-finite parameters after the window")
    if len(prog["losses"]) != n_check_steps:
        violations.append(f"{len(prog['losses'])} scores for "
                          f"{n_check_steps} first steps")

    T = int(tr["seq_len"])
    tokens = steps * int(tr["rows"]) * T
    uncut_tokens = (steps - cut_calls * spc) * int(tr["rows"]) * T
    return {
        "attempted": steps, "failed": int(skips),
        "setup_s": setup_s,
        "end_to_end": {"train_tok_s": tokens / window_s},
        "compared": compared, "violations": violations,
        "memory_peak_bytes": peak,
        "trace": wt.reduce(),
        "check": {"prog": prog, "ref": ref},
        "table": {
            "window_s": window_s, "steps": steps, "tokens": tokens,
            "steps_per_call": spc,
            "uncut": {"tokens": uncut_tokens,
                      "seconds": window_s - (cut_hi - cut_lo)},
            "train_flops_token": counts.train_flops_token(config, T),
            "reference_s": ref_s,
        },
    }


def readings(cell, seed: int, res: Dict[str, Any], control: bool
             ) -> Dict[str, Any]:
    """For ``controls.py``: the look behind a run's numbers (every
    leaf's gap) and, for a control seed, the reference put in the
    program's place: computed in float8 (the CONTROL), and with half of
    every batch left out and the mean taken over the rest (a FAULT)."""
    tr, config = cell.traffic, cell.config
    out: Dict[str, Any] = {"leaves": {"program": leaves(
        res["check"]["prog"], res["check"]["ref"])}}
    if not control:
        return out
    ref_mod = spec.reference(config["reference"])
    spc = int(tr["steps_per_call"])
    n = -(-int(tr["check_steps"]) // spc) * spc
    batches = traffic.train_batches(tr, config["vocab_size"], seed)
    batches = [batches[i % len(batches)] for i in range(n)]
    ref = res["check"]["ref"]
    for name, kw in (("control_fp8", {"precision": "fp8"}),
                     ("fault_half_batch",
                      {"rows": slice(0, int(tr["rows"]) // 2)})):
        got = follow_reference(ref_mod, config, seed, batches, tr, **kw)
        out[name] = compare.train_numbers(got, ref)
        out["leaves"][name] = leaves(got, ref)
    return out


def leaves(got: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """Every leaf's gap, the reference's norms they are measured
    against, every step's loss."""
    return {"losses": got["losses"], "ref_losses": ref["losses"],
            "ref_grad": ref["grad"], "ref_change": ref["change"],
            "change": compare.leaf_gaps(got["change"], ref["change"])}
