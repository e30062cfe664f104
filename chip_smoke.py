"""The quickest proof that the system still starts on the chip.

One process, no children, exit code is the verdict.  Drives the repo's
two spines at the full width of GPT-2 small (``gpt.gpt_config()``: vocab
50257, 1024 positions, hidden 768, 12 layers, 12 heads) with random
weights made from a seed:

0. device    — versions, platform, device kind and count.  No TPU is
               exit != 0 before anything compiles; the script never sets
               ``JAX_PLATFORMS`` and never falls back.
1. train     — ``CausalLM.fit_backprop`` (``sharded_fit``'s scanned
               epoch), a cold call then a warmed call with equal shapes.
2. serve     — the trained parameters through ``DecodeEngine``
               and ``ContinuousBatcher``: warm-up, then requests spread
               over the bucket ladder, one checked against the unbatched
               ``gpt.generate``.
3. kernels   — flash attention (forward + backward), the word2vec and the
               GloVe chunk kernels, compiled by Mosaic
               (``interpret=False``), each against its XLA reference.
4. four_chip — only when JAX reports >= 4 devices: the train phase on a
               data=2 x model=2 mesh and the serve phase through
               ``Router.replicate``, one replica per device.

A phase that fails is printed with its traceback, the phases that do not
need its result still run, and the exit code is 1.  On success the last
line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Wall times printed here are smoke information, not benchmark numbers.

``python chip_smoke.py --tiny`` under ``JAX_PLATFORMS=cpu`` rehearses the
control flow at ``gpt_tiny`` with the kernels interpreted; it prints
``platform=cpu`` and is not what the chip check runs.  Finding no chip
never selects the tiny size.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import sys
import time
import traceback
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

#: stated bf16 tolerances.  Kernel outputs are compared with the same
#: rtol/atol tests/test_pallas_attention.py uses for bf16; two greedy
#: decodes may part ways only where the reference's own top two logits
#: sit within four bf16 ulps (2**-8 relative each) of one another.
BF16_RTOL = BF16_ATOL = 3e-2
BF16_TIE = 4 * 2.0 ** -8
#: scores of the same fit on one chip and on a 2x2 mesh (other reduction
#: order, bf16 compute)
MESH_SCORE_RTOL = 2e-2
#: the chip check allows 1200 s: past this the process dumps every
#: thread's stack and exits 1 rather than be killed mute (a Mosaic
#: compile has hung before)
DEADLINE_S = 1150.0


class Sizes(NamedTuple):
    batch: int                      # rows per training batch
    n_batches: int
    prompt_lens: Tuple[int, ...]    # serve requests, one per entry
    ref_request: int                # index checked against gpt.generate
    max_tokens: int
    n_slots: int
    flash_T: int
    w2v: Tuple[int, int, int, int, int]   # vocab, dim, Huffman depth, neg, pairs
    glove: Tuple[int, int, int]           # vocab, dim, triples
    router_buckets: Tuple[int, ...]       # phase 4's shorter ladder


#: GPT-2 small at full width, depth and length; batch is what fits one
#: v5e chip beside the fp32 [8, 1023, 50257] logits.  The word2vec/GloVe
#: shapes: vocab 2000, dim 100, batch 16384 / 4096.
FULL = Sizes(batch=8, n_batches=4,
             prompt_lens=(16, 40, 100, 230, 480, 900, 300),
             ref_request=2, max_tokens=32, n_slots=8, flash_T=4096,
             w2v=(2000, 100, 16, 5, 16384), glove=(2000, 100, 4096),
             router_buckets=(128, 1024))
TINY = Sizes(batch=4, n_batches=2, prompt_lens=(5, 20, 60, 90),
             ref_request=1, max_tokens=8, n_slots=4, flash_T=256,
             w2v=(64, 32, 7, 3, 256), glove=(64, 32, 128),
             router_buckets=(32, 128))


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond: Any, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(tag: str, /, **facts: Any) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in facts.items()),
          flush=True)


def xla_compiles() -> Dict[str, int]:
    """XLA-level compile counts (``compile_metrics``, from
    ``jax.monitoring``): compile requests (every lowering handed to the
    backend, persistent-cache hits included), persistent-cache hits and
    misses.  They stand beside the TRACE counts so a retrace-free
    recompile cannot hide."""
    from deeplearning4j_tpu.runtime.metrics import compile_metrics

    snap = compile_metrics.snapshot()
    return {k: snap[k] for k in ("xla_compile_requests",
                                 "persistent_cache_hits",
                                 "persistent_cache_misses")}


def xla_requests() -> int:
    return xla_compiles()["xla_compile_requests"]


# ---------------------------------------------------------------------------
# phase 0: device
# ---------------------------------------------------------------------------

def phase_device(tiny: bool) -> Dict[str, Any]:
    import jax
    import jaxlib

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say("device", jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, python=sys.version.split()[0],
        platform=dev.platform, device_kind=repr(dev.device_kind),
        device_count=device["count"])
    if dev.platform != "tpu" and not tiny:
        sys.exit(f"chip_smoke: no accelerator: platform={dev.platform} "
                 f"device_kind={dev.device_kind!r} — nothing was run "
                 f"(--tiny is the explicit CPU rehearsal)")
    return device


def phase_peaks(device: Dict[str, Any]) -> None:
    from deeplearning4j_tpu.runtime import ensure_compile_cache
    from deeplearning4j_tpu.runtime.metrics import chip_peak_flops

    cache_dir = ensure_compile_cache()
    peak = chip_peak_flops(device["kind"])
    say("device", compile_cache_dir=cache_dir, peak_bf16_flops=peak)
    if device["platform"] == "tpu":
        check(peak is not None,
              f"chip_peak_flops has no entry for {device['kind']!r}")


# ---------------------------------------------------------------------------
# phase 1: train
# ---------------------------------------------------------------------------

def token_stream(vocab: int, n_rows: int, T: int, seed: int):
    """A learnable stream: a fixed successor map over 64 token ids spread
    across the whole vocabulary (so the full-width embedding and readout
    are exercised), followed nine times in ten."""
    import numpy as np

    rng = np.random.RandomState(seed)
    ids = np.unique(np.linspace(1, vocab - 1, 64).astype(np.int64))
    succ = rng.permutation(len(ids))
    x = np.empty((n_rows, T), np.int64)
    x[:, 0] = rng.randint(0, len(ids), n_rows)
    noise = rng.rand(n_rows, T) < 0.1
    rand = rng.randint(0, len(ids), (n_rows, T))
    for t in range(1, T):
        x[:, t] = np.where(noise[:, t], rand[:, t], succ[x[:, t - 1]])
    return ids[x].astype(np.int32)


class ScoreLog:
    """IterationListener collecting the per-step scores of a fit."""

    def __init__(self) -> None:
        self.scores: List[float] = []

    def iteration_done(self, model: Any, iteration: int,
                       score: float) -> None:
        self.scores.append(score)


def fit_once(lm: Any, batches: List[Any], mesh: Any) -> Dict[str, Any]:
    """One ``fit_backprop`` call, timed to ``block_until_ready``, with
    the trace and XLA compile counts it cost."""
    import jax

    from deeplearning4j_tpu.runtime.metrics import compile_metrics

    log = ScoreLog()
    lm.listeners = [log]
    traces0 = compile_metrics.snapshot()["compile_count"]
    xla0 = xla_requests()
    t0 = time.perf_counter()
    lm.fit_backprop(batches, num_epochs=1, seed=2, mesh=mesh)
    jax.block_until_ready(lm.params)
    return {"scores": log.scores,
            "wall_s": round(time.perf_counter() - t0, 3),
            "traces": compile_metrics.snapshot()["compile_count"] - traces0,
            "xla_compile_requests": xla_requests() - xla0}


def phase_train(cfg: Any, sz: Sizes, device: Dict[str, Any],
                mesh: Any = None, phase: str = "train"
                ) -> Tuple[Any, Dict[str, Any]]:
    import jax
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.lm_fit import CausalLM

    T = cfg.max_len
    rows = token_stream(cfg.vocab_size, sz.batch * sz.n_batches, T, seed=1)
    batches = [DataSet(rows[i * sz.batch:(i + 1) * sz.batch],
                       rows[i * sz.batch:(i + 1) * sz.batch])
               for i in range(sz.n_batches)]
    lm = CausalLM(cfg, mixed_precision="bf16").init(0)
    say(phase, params_M=round(lm.num_param_bytes() / 4e6, 1),
        batch=f"{sz.batch}x{T}", steps_per_call=sz.n_batches,
        mesh=None if mesh is None else dict(mesh.shape))

    calls = {"cold": fit_once(lm, batches, mesh)}
    say(phase, call="cold", **calls["cold"])
    if mesh is None:
        # the warmed call is a one-chip check: on a mesh the second call
        # meets mesh-placed parameters where the first met unplaced ones
        # and retraces once (ROADMAP D0), which is not this script's to
        # pay four chips for
        calls["warm"] = warm = fit_once(lm, batches, mesh)
        say(phase, call="warm", **warm)
        check(warm["traces"] == 0,
              f"the warmed call traced {warm['traces']} program(s)")
        check(warm["xla_compile_requests"] == 0,
              f"the warmed call compiled {warm['xla_compile_requests']} "
              f"program(s) without tracing any")

    scores = np.asarray([s for c in calls.values() for s in c["scores"]])
    check(scores.size == len(calls) * sz.n_batches,
          f"expected {len(calls) * sz.n_batches} scores, got {scores.size}")
    check(np.all(np.isfinite(scores)), f"non-finite scores: {scores}")
    check(scores[-1] < scores[0] and scores[len(scores) // 2:].mean()
          < scores[:len(scores) // 2].mean(),
          f"scores are not falling: {scores}")
    check(lm.guard_skips == 0,
          f"the non-finite guard skipped {lm.guard_skips} step(s)")
    leaves = jax.tree.leaves(lm.params)
    platforms = {d.platform for leaf in leaves for d in leaf.devices()}
    check(platforms == {device["platform"]},
          f"parameters live on {platforms}")
    check(all(np.all(np.isfinite(np.asarray(leaf))) for leaf in leaves),
          "non-finite parameters after training")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    say(phase, guard_skips=lm.guard_skips, peak_bytes_in_use=peak,
        bytes_limit=stats.get("bytes_limit"))
    if device["platform"] == "tpu":
        check(peak, "memory_stats() reports no peak_bytes_in_use")
    return lm, {**calls, "peak_bytes_in_use": peak}


# ---------------------------------------------------------------------------
# phase 2: serve
# ---------------------------------------------------------------------------

def near_tie_or_equal(cfg: Any, params: Any, prompt: Any, got: Any,
                      ref: Any) -> Optional[str]:
    """None when ``got`` agrees with ``ref`` to the stated bf16
    tolerance: equal, or parting at a position where the dense forward
    over (prompt + the common prefix) puts the two candidates within
    ``BF16_TIE`` of one another — a rounding tie-break, after which two
    greedy decodes legitimately differ.  Else the reason."""
    import numpy as np

    from deeplearning4j_tpu.models import gpt

    got, ref = np.asarray(got), np.asarray(ref)
    if got.shape != ref.shape:
        return f"shape {got.shape} != {ref.shape}"
    diff = np.flatnonzero(got != ref)
    if diff.size == 0:
        return None
    p = int(diff[0])
    ctx = np.concatenate([np.asarray(prompt, np.int32), ref[:p]])[None, :]
    logits = np.asarray(gpt.forward_logits(cfg, params, ctx))[0, -1]
    a, b = float(logits[ref[p]]), float(logits[got[p]])
    if abs(a - b) <= BF16_TIE * max(1.0, abs(a)):
        return None
    return (f"token {p}: {int(got[p])} (logit {b:.4f}) vs reference "
            f"{int(ref[p])} (logit {a:.4f})")


def serve_prompts(cfg: Any, sz: Sizes):
    """Prompts cut from the training stream's distribution.  The request
    after ``ref_request`` repeats it, so the repeat is admitted while the
    original's prompt pages are still resident and mounts them."""
    rows = token_stream(cfg.vocab_size, len(sz.prompt_lens), cfg.max_len,
                        seed=7)
    prompts = [rows[i, :n] for i, n in enumerate(sz.prompt_lens)]
    prompts.insert(sz.ref_request + 1, prompts[sz.ref_request])
    return prompts


def check_streams(cfg: Any, sz: Sizes, outs: List[Any]) -> None:
    import numpy as np

    for i, out in enumerate(outs):
        check(out.shape == (sz.max_tokens,),
              f"request {i}: {out.shape[0]} tokens, asked {sz.max_tokens}")
        check(np.all((out >= 0) & (out < cfg.vocab_size)),
              f"request {i}: token id out of range")
    check(np.array_equal(outs[sz.ref_request + 1], outs[sz.ref_request]),
          "the same request twice gave different tokens")


def phase_serve(cfg: Any, params: Any, sz: Sizes
                ) -> Tuple[List[Any], List[Any]]:
    """Returns (prompts, their token streams) for phase 4 to replay."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import gpt
    from deeplearning4j_tpu.runtime.metrics import decode_metrics
    from deeplearning4j_tpu.runtime.telemetry import registry
    from deeplearning4j_tpu.serving.decode import (ContinuousBatcher,
                                                   DecodeEngine)

    eng = DecodeEngine(cfg, params, n_slots=sz.n_slots)
    xla0 = xla_requests()
    warm = eng.warmup()
    say("serve", buckets=eng.buckets, page_tokens=eng.page_tokens,
        kv_pages=eng.n_kv_pages, pool_MB=round(eng.pool_bytes / 1e6, 1),
        warmup=warm, xla_compile_requests=xla_requests() - xla0)
    # what the longest rung's decode program asks for beside its
    # arguments (the pool is aliased): the bf16 weights, one layer's
    # pages; a pool copy or an all-layer view would show here
    mem = eng._lower_decode(eng.buckets[-1]).compile().memory_analysis()
    say("serve", rung=eng.buckets[-1], n_slots=sz.n_slots,
        decode_temp_bytes=int(mem.temp_size_in_bytes),
        decode_argument_bytes=int(mem.argument_size_in_bytes),
        decode_alias_bytes=int(mem.alias_size_in_bytes))

    prompts = serve_prompts(cfg, sz)
    registry.mark()
    xla0 = xla_requests()
    replayed0 = decode_metrics.snapshot()["requests_replayed"]
    t0 = time.perf_counter()
    batcher = ContinuousBatcher(eng, default_max_tokens=sz.max_tokens)
    try:
        reqs = [batcher.submit(p, max_tokens=sz.max_tokens, temperature=0.0,
                               seed=i) for i, p in enumerate(prompts)]
        outs = [r.result(timeout=600.0) for r in reqs]
    finally:
        batcher.close()
    wall = time.perf_counter() - t0
    traces = registry.compile_delta_since_mark()
    xla = xla_requests() - xla0
    snap = decode_metrics.snapshot()
    say("serve", requests=len(outs), prompt_lens=[len(p) for p in prompts],
        tokens_each=sz.max_tokens, wall_s=round(wall, 3),
        compile_delta_since_mark=traces, xla_compile_requests=xla,
        prefix_hits=snap["prefix_hits"], joins=snap["joins"],
        pages_in_use_hw=snap["pages_in_use_hw"],
        params_held_casts=snap["params_held_casts"],
        params_held_bytes=snap["params_held_bytes"])

    check_streams(cfg, sz, outs)
    check(traces == 0, f"{traces} trace(s) in the marked decode window")
    check(xla == 0, f"{xla} XLA compile(s) in the marked decode window")
    check(snap["requests_replayed"] == replayed0,
          "a dispatch failed and its requests were replayed")
    eng.drop_residents()
    check(eng._alloc.in_use() == 0 and eng.pages_unaccounted() == 0,
          f"{eng._alloc.in_use()} KV page(s) still allocated after close "
          f"({eng.pages_unaccounted()} unaccounted)")

    # the unbatched reference, at the cache length and the rows a
    # prefill dispatch the engine used for this request, so the two run
    # the same arithmetic
    i = sz.ref_request
    bucket = eng.pick_bucket(len(prompts[i]) + sz.max_tokens)
    ref_fn = jax.jit(lambda p, pr: gpt.generate(
        cfg, p, pr, sz.max_tokens, jax.random.key(0), temperature=0.0,
        max_len=bucket, prefill_chunk=eng.prefill_rows(bucket)))
    ref = ref_fn(params, jnp.asarray(prompts[i])[None, :])[0]
    why = near_tie_or_equal(cfg, params, prompts[i], outs[i], ref)
    say("serve", reference="gpt.generate", request=i, bucket=bucket,
        identical=bool((outs[i] == jax.device_get(ref)).all()),
        tie_tolerance=BF16_TIE)
    check(why is None, f"request {i} disagrees with gpt.generate: {why}")
    return prompts, outs


# ---------------------------------------------------------------------------
# phase 3: kernels
# ---------------------------------------------------------------------------

def close(got: Any, ref: Any, what: str, scale: Optional[float] = None
          ) -> float:
    """bf16 agreement of ``got`` with ``ref``; ``scale`` widens atol for
    values whose natural size is far from 1.  Returns the max error."""
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(np.all(np.isfinite(got)), f"{what}: non-finite values")
    atol = BF16_ATOL * (scale if scale is not None else 1.0)
    err = np.abs(got - ref)
    check(np.all(err <= atol + BF16_RTOL * np.abs(ref)),
          f"{what}: max |err| {err.max():.3e} (atol {atol:.1e}, "
          f"rtol {BF16_RTOL})")
    return float(err.max())


def check_updates(kernel: str, on_tpu: bool,
                  items: List[Tuple[str, Any, Any, Any]]) -> None:
    """A table kernel's product is the UPDATE: for each (name, got, ref,
    start) compare ``got - start`` with ``ref - start`` at the update's
    own size."""
    import numpy as np

    errs = {}
    for name, got, ref, start in items:
        upd = np.asarray(ref - start)
        errs[name] = f"{close(got - start, upd, f'{kernel} {name}', float(np.abs(upd).max())):.2e}"
    say("kernels", kernel=kernel, verdict=verdict(on_tpu), max_err=errs)


def verdict(on_tpu: bool) -> str:
    return "compiled+agrees" if on_tpu else "interpreted+agrees"


def kernel_flash(sz: Sizes, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import transformer as tfm
    from deeplearning4j_tpu.ops.pallas_attention import make_attn_fn

    B, T, NH, D = 1, sz.flash_T, 12, 64
    attn = make_attn_fn("pallas")
    d = attn.describe((B, T, NH, D), (B, T, NH, D), True)
    auto = make_attn_fn("auto").describe((8, 1024, NH, D), (8, 1024, NH, D),
                                         True)
    say("kernels", kernel="flash_attention", shape=(B, T, NH, D),
        kernel_name=d.kernel_name, interpret=d.interpret, source=d.source,
        blocks=(d.block_q, d.block_k),
        auto_at_T1024=f"{auto.kernel_name} ({auto.source})")
    check(d.interpret == (not on_tpu) and d.impl == "pallas",
          f"flash attention resolved to {d}")
    if on_tpu:
        check(d.kernel_name == "pallas", f"kernel_name {d.kernel_name}")

    kq, kk, kv, kw = jax.random.split(jax.random.key(3), 4)
    q, k, v, w = (jax.random.normal(kx, (B, T, NH, D), jnp.bfloat16)
                  for kx in (kq, kk, kv, kw))

    def run(fn):
        def loss(q, k, v):
            o = fn(q, k, v, None, True)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (o,) + grads

    got = run(attn)
    ref = run(tfm.attention)
    errs = {}
    for name, g, r in zip(("out", "dq", "dk", "dv"), got, ref):
        scale = float(jnp.max(jnp.abs(r.astype(jnp.float32))))
        errs[name] = round(close(g, r, f"flash {name}", max(scale, 1.0)), 5)
    say("kernels", kernel="flash_attention", verdict=verdict(on_tpu),
        max_err=errs)


def kernel_word2vec(sz: Sizes, on_tpu: bool) -> None:
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nlp.word2vec import _hs_update, _neg_update
    from deeplearning4j_tpu.ops.pallas_word2vec import (choose_block,
                                                        fused_chunk_update)

    V, D, L, K, B = sz.w2v
    interpret = not on_tpu
    block = choose_block(V, D, K, B, interpret=interpret)
    say("kernels", kernel="word2vec", vocab=V, dim=D, depth=L, negative=K,
        pairs=B, block=block, interpret=interpret)
    check(block > 0, "choose_block found no VMEM-resident block")
    rng = np.random.RandomState(0)
    f32 = jnp.float32
    syn0, syn1, sneg = (jnp.asarray(rng.randn(V, D), f32) * 0.1
                        for _ in range(3))
    inputs = jnp.asarray(rng.randint(0, V, B), jnp.int32)
    targets = jnp.asarray(rng.randint(0, V, B), jnp.int32)
    codes = jnp.asarray(rng.randint(0, 2, (B, L)), f32)
    points = jnp.asarray(rng.randint(0, V, (B, L)), jnp.int32)
    mask = jnp.asarray(rng.rand(B, L) < 0.7, f32)
    negs = jnp.asarray(rng.randint(0, V, (B, K)), jnp.int32)
    pmask = jnp.asarray(rng.rand(B) < 0.9, f32)
    alpha = f32(0.025)

    a0, a1, an = fused_chunk_update(
        syn0, syn1, sneg, inputs, targets, codes, points, mask, negs, pmask,
        alpha, use_hs=True, negative=K, block=block, interpret=interpret)
    h0, r1 = _hs_update(syn0, syn1, inputs, codes, points,
                        mask * pmask[:, None], alpha)
    n0, rn = _neg_update(syn0, sneg, inputs, targets, negs, pmask, alpha)
    r0 = syn0 + (h0 - syn0) + (n0 - syn0)
    check_updates("word2vec", on_tpu, [("syn0", a0, r0, syn0),
                                       ("syn1", a1, r1, syn1),
                                       ("syn1neg", an, rn, sneg)])


def kernel_glove(sz: Sizes, on_tpu: bool) -> None:
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nlp.glove import _glove_update
    from deeplearning4j_tpu.ops.pallas_glove import (apply_chunk,
                                                     choose_block,
                                                     fused_glove_chunk)

    V, D, B = sz.glove
    interpret = not on_tpu
    block = choose_block(V, D, B, interpret=interpret)
    say("kernels", kernel="glove", vocab=V, dim=D, triples=B, block=block,
        interpret=interpret)
    check(block > 0, "choose_block found no VMEM-resident block")
    rng = np.random.RandomState(0)
    f32 = jnp.float32
    w, wt = (jnp.asarray(rng.randn(V, D), f32) * 0.1 for _ in range(2))
    b, bt = (jnp.asarray(rng.randn(V), f32) * 0.1 for _ in range(2))
    gw = gwt = jnp.full((V, D), 1e-8, f32)
    gb = gbt = jnp.full((V,), 1e-8, f32)
    rows = jnp.asarray(rng.randint(0, V, B), jnp.int32)
    cols = jnp.asarray(rng.randint(0, V, B), jnp.int32)
    x = jnp.asarray(rng.rand(B) * 50 + 1, f32)
    mask = jnp.asarray(rng.rand(B) < 0.9, f32)
    alpha = f32(0.05)

    (rw, rwt, rb, rbt, *_), _ = _glove_update(
        (w, wt, b, bt, gw, gwt, gb, gbt), rows, cols, x, mask, alpha,
        100.0, 0.75)
    ones = jnp.ones((V, 1), f32)
    accw, accwt, _ = fused_glove_chunk(
        jnp.concatenate([w, b[:, None], ones], axis=1),
        jnp.concatenate([wt, ones, bt[:, None]], axis=1),
        rows, cols, x, mask, x_max=100.0, power=0.75, block=block,
        interpret=interpret)
    wb, _ = apply_chunk(jnp.concatenate([w, b[:, None]], axis=1),
                        jnp.concatenate([gw, gb[:, None]], axis=1),
                        accw, alpha)
    wtb, _ = apply_chunk(jnp.concatenate([wt, bt[:, None]], axis=1),
                         jnp.concatenate([gwt, gbt[:, None]], axis=1),
                         accwt, alpha)
    check_updates("glove", on_tpu, [("w", wb[:, :D], rw, w),
                                    ("wt", wtb[:, :D], rwt, wt),
                                    ("b", wb[:, D], rb, b),
                                    ("bt", wtb[:, D], rbt, bt)])


def phase_kernels(sz: Sizes, device: Dict[str, Any]) -> None:
    """Every kernel runs, so one refusal does not hide the next; the
    phase fails if any did."""
    on_tpu = device["platform"] == "tpu"
    failed = []
    for name, fn in (("flash_attention", kernel_flash),
                     ("word2vec", kernel_word2vec),
                     ("glove", kernel_glove)):
        try:
            fn(sz, on_tpu)
        except Exception as e:      # noqa: BLE001 — reported, then fatal
            traceback.print_exc()
            say("kernels", kernel=name, verdict="rejected",
                message=repr(str(e)[:2000]))
            failed.append(name)
    check(not failed, f"kernel(s) rejected or wrong: {failed}")


# ---------------------------------------------------------------------------
# phase 4: four chips
# ---------------------------------------------------------------------------

def phase_four_chip(cfg: Any, sz: Sizes, device: Dict[str, Any],
                    lm1: Any, one_chip: Dict[str, Any],
                    prompts: List[Any], one_chip_outs: List[Any]) -> None:
    import jax
    import numpy as np

    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    from deeplearning4j_tpu.serving.router import Router

    devs = jax.devices()[:4]
    mesh = make_mesh(MeshSpec(data=2, model=2), devices=devs)
    say("four_chip", mesh_shape=dict(mesh.shape),
        device_grid=[[d.id for d in row]
                     for row in mesh.devices.reshape(2, 2)],
        coords=[getattr(d, "coords", None) for d in devs])
    lm4, res = phase_train(cfg, sz, device, mesh=mesh, phase="four_chip")

    sharded = 0
    for leaf in jax.tree.leaves(lm4.params):
        shards = leaf.addressable_shards
        on = {s.device.id for s in shards}
        check(on == {d.id for d in devs},
              f"a parameter sits on devices {on}, not on all four")
        if shards[0].data.nbytes < leaf.nbytes:
            sharded += 1
    check(sharded > 0, "no parameter is split over the model axis")
    say("four_chip", model_sharded_leaves=sharded,
        per_device_param_MB=round(sum(
            leaf.addressable_shards[0].data.nbytes
            for leaf in jax.tree.leaves(lm4.params)) / 1e6, 1),
        total_param_MB=round(lm4.num_param_bytes() / 1e6, 1))
    mesh_scores = np.asarray(res["cold"]["scores"])
    chip_scores = np.asarray(one_chip["cold"]["scores"])
    say("four_chip", mesh_scores=mesh_scores.round(4).tolist(),
        one_chip_scores=chip_scores.round(4).tolist())
    check(np.allclose(mesh_scores, chip_scores, rtol=MESH_SCORE_RTOL),
          f"mesh scores {mesh_scores} vs one chip {chip_scores}")

    t0 = time.perf_counter()
    router = Router.replicate(cfg, lm1.params, 4, devices=devs,
                              n_slots=sz.n_slots,
                              buckets=sz.router_buckets,
                              default_max_tokens=sz.max_tokens)
    setup = time.perf_counter() - t0
    try:
        homes = [{d.id for leaf in jax.tree.leaves(b.engine.current_params())
                  for d in leaf.devices()} for b in router.batchers]
        say("four_chip", replicas=len(router.batchers),
            replica_devices=[sorted(h) for h in homes],
            router_setup_s=round(setup, 3))
        check(all(len(h) == 1 for h in homes)
              and len(set().union(*homes)) == 4,
              f"replica parameters sit on {homes}, not on four devices")
        reqs = [router.submit(p, max_tokens=sz.max_tokens, temperature=0.0,
                              seed=i) for i, p in enumerate(prompts)]
        outs = [r.result(timeout=600.0) for r in reqs]
    finally:
        router.close()
    for i, (out, one) in enumerate(zip(outs, one_chip_outs)):
        why = near_tie_or_equal(cfg, lm1.params, prompts[i], out, one)
        check(why is None,
              f"router request {i} disagrees with the one-chip engine: "
              f"{why}")
    say("four_chip", router_requests=len(outs),
        identical_to_one_chip=sum(
            bool(np.array_equal(o, s))
            for o, s in zip(outs, one_chip_outs)))


# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse the control flow at gpt_tiny "
                         "(the only size that runs without a TPU)")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    t_start = time.perf_counter()

    device = phase_device(args.tiny)
    from deeplearning4j_tpu.models import gpt

    sz = TINY if args.tiny else FULL
    cfg = gpt.gpt_tiny() if args.tiny else gpt.gpt_config()
    say("device", size="tiny" if args.tiny else "full", config=cfg)

    failed: List[str] = []

    def run(name: str, fn: Callable[[], Any]) -> Any:
        t0 = time.perf_counter()
        try:
            out = fn()
        except Exception:       # noqa: BLE001 — printed, counted, exit 1
            traceback.print_exc()
            failed.append(name)
            out = None
        say(name, phase="passed" if name not in failed else "FAILED",
            phase_wall_s=round(time.perf_counter() - t0, 1),
            **xla_compiles())
        return out

    run("device", lambda: phase_peaks(device))
    trained = run("train", lambda: phase_train(cfg, sz, device))
    served = None
    if trained is not None:
        served = run("serve", lambda: phase_serve(cfg, trained[0].params, sz))
    else:
        failed.append("serve")
        say("serve", phase="FAILED", why="no trained parameters")
    run("kernels", lambda: phase_kernels(sz, device))
    if device["count"] >= 4:
        if trained is not None and served is not None:
            run("four_chip", lambda: phase_four_chip(
                cfg, sz, device, *trained, *served))
        else:
            failed.append("four_chip")
            say("four_chip", phase="FAILED", why="phases 1-2 did not pass")
    else:
        say("four_chip", phase="not run",
            why=f"{device['count']} device(s)")

    say("summary", failed=failed or None,
        total_wall_s=round(time.perf_counter() - t_start, 1),
        **xla_compiles())
    faulthandler.cancel_dump_traceback_later()
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
