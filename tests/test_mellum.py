"""Mellum 2 (models/mellum.py) against the plain reference
(benchmark/reference/mellum2.py) at small widths on the CPU, and through
the serving spine: two kinds of page under one ``DecodeEngine`` (a full
table and a window ring), grouped-query rows, top-k routing with
renormalisation.

Tolerance.  With float32 weights and ``compute_dtype="float32"`` the
program and the reference do the same arithmetic in another order
(grouped heads against repeated ones, a scan over periods, experts in a
loop over the hit against sorted blocks), and the CPU backend's float32
products are exact to rounding: logits of magnitude ~5 agree to 2e-4 of
their largest, as in ``tests/test_deepseek_v2.py``.  A window mask off
by one key moves them by 1e-2 and more.
"""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import mellum2 as ref  # noqa: E402
from deeplearning4j_tpu.models import mellum as ml  # noqa: E402
from deeplearning4j_tpu.parallel import expert  # noqa: E402
from deeplearning4j_tpu.runtime.metrics import decode_metrics  # noqa: E402
from deeplearning4j_tpu.serving.decode import (ContinuousBatcher,  # noqa: E402
                                               DecodeEngine, model_family)

F32_TOL = 2e-4
WINDOW = 16          # tiny_config's
C = 8                # page and prefill chunk of the engines here
RING = WINDOW // C + ml.RING_PREFILL_PAGES     # page_kinds' cap


def published_keys(cfg):
    """The reference reads a dict with the published key names."""
    return {"hidden_size": cfg.hidden, "layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "sliding_window": cfg.sliding_window,
            "layer_types": [cfg.period[l % len(cfg.period)]
                            for l in range(cfg.n_layers)],
            "rms_norm_eps": cfg.rms_norm_eps,
            "rope_parameters": {
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                    "factor": cfg.rope_factor,
                    "original_max_position_embeddings":
                        cfg.rope_original_max_len,
                    "beta_fast": cfg.rope_beta_fast,
                    "beta_slow": cfg.rope_beta_slow,
                    "attention_factor": cfg.rope_attention_factor},
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": cfg.rope_theta}}}


def model(seed=0, **over):
    cfg = ml.tiny_config(compute_dtype="float32", **over)
    return cfg, ml.init_params(jax.random.key(seed), cfg, std=0.3)


def reference_logits(cfg, params, ids):
    """Rows padded to whole blocks of 16 (causal: never attended)."""
    ids = np.atleast_2d(ids)
    n = ids.shape[1]
    padded = np.pad(ids, ((0, 0), (0, -n % 16)))
    return np.asarray(ref.logits(params, jnp.asarray(padded),
                                 config=published_keys(cfg), q_block=16,
                                 expert_block=8))[:, :n]


def some_ids(cfg, shape, seed=1):
    return np.asarray(jax.random.randint(jax.random.key(seed), shape, 0,
                                         cfg.vocab_size), np.int32)


def close(got, want):
    return np.abs(got - want).max() <= F32_TOL * np.abs(want).max()


def test_full_forward_matches_reference():
    cfg, params = model()
    ids = some_ids(cfg, (2, 80))                # five windows long
    got = np.asarray(ml.forward_logits(cfg, params, jnp.asarray(ids)))
    want = reference_logits(cfg, params, ids)
    assert np.abs(want).max() > 1.0
    assert close(got, want)


def test_the_published_config_is_the_tiny_ones_shape():
    cfg = ml.MellumConfig()
    assert (cfg.n_periods, cfg.layers_of(ml.WINDOW), cfg.layers_of(ml.FULL),
            cfg.kv_width) == (7, 21, 7, 512)
    # the ring: the 8 pages the frontier's row reads back to and the 2
    # of a prefill dispatch; 10 x 128 - 128 - 1,023 = 129 rows ahead
    assert ml.page_kinds(cfg, 128) == (("full", None), ("window", 10, 129))
    # a window that is no whole number of pages touches one page more
    assert ml.page_kinds(dataclasses.replace(cfg, sliding_window=1025),
                         128)[1] == ("window", 10, 128)
    assert ml.page_kinds(dataclasses.replace(cfg, sliding_window=1026),
                         128)[1] == ("window", 11, 255)
    with pytest.raises(ValueError, match="whole periods"):
        ml.MellumConfig(n_layers=6)


def hf_yarn_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """``_compute_yarn_parameters`` of the published modelling code,
    transcribed (``truncate`` at its default)."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))
                ) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    return (interpolation * (1 - extrapolation_factor)
            + extrapolation * extrapolation_factor)


@pytest.mark.parametrize("cfg", [ml.MellumConfig(), ml.tiny_config()],
                         ids=["published", "tiny"])
def test_rope_tables_against_a_direct_transcription(cfg):
    n, d = 300, cfg.head_dim
    at = np.arange(n, dtype=np.float64)[:, None]
    plain = 1.0 / cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    yarn = hf_yarn_inv_freq(d, cfg.rope_theta, cfg.rope_factor,
                            cfg.rope_original_max_len, cfg.rope_beta_fast,
                            cfg.rope_beta_slow)
    assert cfg.rope_attention_factor == pytest.approx(
        0.1 * math.log(cfg.rope_factor) + 1.0)
    for kind, inv_freq, scale in ((ml.WINDOW, plain, 1.0),
                                  (ml.FULL, yarn,
                                   cfg.rope_attention_factor)):
        cos, sin = ml.rope_tables(cfg, kind, n)
        ang = np.concatenate([at * inv_freq, at * inv_freq], axis=-1)
        np.testing.assert_allclose(cos, np.cos(ang) * scale, atol=1e-6)
        np.testing.assert_allclose(sin, np.sin(ang) * scale, atol=1e-6)
        r_cos, r_sin = ref.rope_tables(published_keys(cfg), kind, n)
        np.testing.assert_array_equal(np.asarray(r_cos), cos)
        np.testing.assert_array_equal(np.asarray(r_sin), sin)
    # the two kinds differ: the slow lanes are interpolated
    assert not np.allclose(yarn, plain)


def test_router_takes_exactly_k_and_their_weights_sum_to_one():
    scores = jax.nn.softmax(jax.random.normal(jax.random.key(4), (37, 64)))
    w, chosen = expert.route_topk_renorm(scores, 8)
    w, chosen, scores = (np.asarray(a) for a in (w, chosen, scores))
    assert (chosen.sum(axis=1) == 8).all()
    np.testing.assert_allclose(w.sum(axis=1), 1.0, rtol=1e-6)
    assert ((w > 0) == chosen).all()
    for row in range(37):
        best = set(np.argsort(-scores[row])[:8].tolist())
        assert set(np.flatnonzero(chosen[row]).tolist()) == best
        np.testing.assert_allclose(
            w[row, chosen[row]],
            scores[row, chosen[row]] / scores[row, chosen[row]].sum(),
            rtol=1e-6)
    # the reference's routing, written another way, takes the same
    r_w, r_e = ref.route({"num_experts_per_tok": 8}, jnp.asarray(scores))
    r_w, r_e = np.asarray(r_w), np.asarray(r_e)
    for row in range(37):
        assert set(r_e[row].tolist()) == set(
            np.flatnonzero(chosen[row]).tolist())
        np.testing.assert_allclose(np.sort(r_w[row]),
                                   np.sort(w[row, chosen[row]]), rtol=1e-6)


# ---------------------------------------------------------------------------
# The two paged paths, tables made by hand
# ---------------------------------------------------------------------------

stack = jax.jit(ml._paged_stack, static_argnums=0)
readout = jax.jit(ml._readout, static_argnums=0)


def paged_logits(cfg, params, row, n_prompt, pages=1):
    """Logits at every position of ``row`` as the serving path computes
    them: the prompt in chunks of ``pages`` pages of C as
    ``paged_prefill`` calls the stack, then a token a step as
    ``paged_decode`` does, the sequence in slot 1
    of 3 with scattered pages: a full table of every page and a window
    RING of ``page_kinds``' size, column ``j % ring`` for page ``j``."""
    S, TBL = 3, -(-len(row) // C)
    ring = ml.page_kinds(cfg, C)[1][1]
    pool = ml.init_pages(cfg, (1 + S * TBL, 1 + S * ring), C)
    ptab_f = np.zeros((S, TBL), np.int32)
    ptab_w = np.zeros((S, ring), np.int32)
    rng = np.random.default_rng(0)
    ptab_f[1] = 1 + rng.permutation(S * TBL)[:TBL]
    ptab_w[1] = 1 + rng.permutation(S * ring)[:ring]
    out = []
    W = pages * C
    at = np.arange(W, dtype=np.int32)
    for lo in range(0, n_prompt, W):
        n_valid = min(W, n_prompt - lo)
        chunk = np.zeros((W,), np.int32)
        chunk[:n_valid] = row[lo:lo + n_valid]
        pool, x, _ = stack(cfg, params, pool, (ptab_f[1][None],
                                               ptab_w[1][None]),
                           chunk[None], (lo + at)[None],
                           (at < n_valid)[None])
        out.append(np.asarray(readout(cfg, params, x[0, :n_valid])))
    active = np.array([False, True, False])
    for t in range(n_prompt, len(row)):
        toks = np.array([0, row[t], 0], np.int32)[:, None]
        pos = np.array([0, t, 0], np.int32)[:, None]
        pool, x, counts = stack(cfg, params, pool, (ptab_f, ptab_w), toks,
                                pos, active[:, None])
        out.append(np.asarray(readout(cfg, params, x[1])))
    return np.concatenate(out), np.asarray(counts), pool


@pytest.mark.parametrize("length,n_prompt,pages", [
    (12, 5, 1),     # shorter than the window
    (16, 11, 1),    # the window itself
    (17, 16, 1),    # one past it
    (88, 53, 1),    # several windows: the ring goes round in the prompt
    (88, 3, 1),     # ... and in the decode steps
    # two pages a chunk, what the ring leaves room for: the prompt ends
    # in the chunk's first page twice round the ring (its second page is
    # padding, never written, and lies over a page of the lap before),
    (88, 69, 2),
    (88, 77, 2),    # ... in its second page,
    (88, 64, 2),    # ... and on a chunk's edge
])
def test_chunked_prefill_then_decode_matches_reference_logits(
        length, n_prompt, pages):
    cfg, params = model()
    assert pages <= 1 + ml.page_kinds(cfg, C)[1][2] // C
    row = some_ids(cfg, (length,), seed=3)
    got, counts, pool = paged_logits(cfg, params, row, n_prompt, pages)
    want = reference_logits(cfg, params, row)[0]
    assert close(got, want)
    # one active slot, eight expert layers, two experts a token: the idle
    # slots were routed nowhere
    assert counts.tolist()[:2] == [16, 16] and counts[3] == 8
    assert 8 <= counts[2] <= 16
    assert pool.window_k.shape == (6, 1 + 3 * RING, C, 16)
    assert pool.full_k.shape == (2, 1 + 3 * -(-length // C), C, 16)


@pytest.mark.parametrize("n_prompt", [77, 88])
def test_a_page_more_than_the_ring_leaves_room_for_fails_the_comparison(
        n_prompt):
    """The control of the ring rule on the stack itself: chunks of three
    pages where the ring's four columns leave room for two.  The third
    page lies over a page the chunk's first row still reads (written
    over at 88 tokens; at 77, where it is padding, labelled away)."""
    cfg, params = model()
    pages = 2 + ml.page_kinds(cfg, C)[1][2] // C
    row = some_ids(cfg, (96,), seed=3)
    want = reference_logits(cfg, params, row)[0]
    got, _, _ = paged_logits(cfg, params, row, n_prompt, pages)
    assert close(got[:pages * C], want[:pages * C])
    assert not close(got[:n_prompt], want[:n_prompt])


@pytest.mark.parametrize("window", [WINDOW - 1, WINDOW + 1])
def test_a_window_mask_off_by_one_fails_the_comparison(window):
    """15 or 17 keys where the model has 16: both paged paths, and the
    cache-less forward, against the reference of the model itself."""
    cfg, params = model()
    off = dataclasses.replace(cfg, sliding_window=window)
    # the same ring; what it leaves ahead moves with the window
    assert [k[:2] for k in ml.page_kinds(off, C)] == [
        k[:2] for k in ml.page_kinds(cfg, C)]
    assert ml.page_kinds(off, C)[1][2] == (RING - 1) * C - (window - 1)
    row = some_ids(cfg, (56,), seed=3)
    want = reference_logits(cfg, params, row)[0]
    got, _, _ = paged_logits(off, params, row, n_prompt=29)
    # within the window the two agree; past it, prefill and decode alike,
    # not by orders of magnitude
    assert close(got[:WINDOW - 1], want[:WINDOW - 1])
    assert not close(got[:29], want[:29])
    assert not close(got[29:], want[29:])
    assert np.abs(got - want).max() > 50 * F32_TOL * np.abs(want).max()
    dense = np.asarray(ml.forward_logits(off, params, jnp.asarray(row[None])))
    assert not close(dense[0], want)


def test_pages_written_are_the_pages_read():
    """The family's page read and write, the pool's sizes by kind; a
    K/V pool of this family has no int8 form."""
    cfg, _ = model()
    pool = ml.init_pages(cfg, (6, 4), page_tokens=4)
    assert pool.full_k.shape == (2, 6, 4, 16)
    assert pool.window_v.shape == (6, 4, 4, 16)
    assert ml.pages_bytes(cfg, (6, 4), 4) == sum(a.size * 4 for a in pool)
    # a slot of 40 positions: 40 rows on 2 full layers, 16 on 6 window ones
    assert ml.slots_bytes_per_slot(cfg, 40) == 2 * 16 * 4 * (2 * 40 + 6 * 16)
    pids = (jnp.asarray([4, 2, 0], jnp.int32), jnp.asarray([3, 1], jnp.int32))
    keys = jax.random.split(jax.random.key(2), 4)
    pages = [jax.random.normal(k, (a.shape[0], len(p), 4, 16), jnp.float32)
             for k, a, p in zip(keys, pool, (pids[0], pids[0], pids[1],
                                             pids[1]))]
    pool = ml.paged_write_pages(cfg, pool, pids, *pages)
    for back, page in zip(ml.paged_read_pages(cfg, pool, pids), pages):
        np.testing.assert_array_equal(np.asarray(back), np.asarray(page))
    assert not np.asarray(pool.full_k[:, [1, 3, 5]]).any()
    assert not np.asarray(pool.window_v[:, [2]]).any()
    with pytest.raises(ValueError, match="int8"):
        ml.init_pages(cfg, (6, 4), 4, kv_dtype="int8")


# ---------------------------------------------------------------------------
# Through DecodeEngine
# ---------------------------------------------------------------------------

def engine(cfg, params, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("buckets", (32, 64, 128))
    return DecodeEngine(cfg, params, prefill_chunk=C, **kw)


def test_engine_takes_the_family_and_its_kinds_from_the_config():
    cfg, params = model()
    assert model_family(cfg) is ml
    eng = engine(cfg, params)
    assert [(k.name, k.bounded, k.cap) for k in eng._kinds] == [
        ("full", False, 16), ("window", True, RING)]
    assert eng.n_kv_pages == (4 * 16 + 1, 4 * RING + 1)
    assert eng.pool_bytes == ml.pages_bytes(cfg, eng.n_kv_pages, C)
    assert eng.kv_bytes_per_slot == ml.slots_bytes_per_slot(cfg, 128)
    for option, value in [("kv_dtype", "int8"), ("quantize", "int8"),
                          ("prefix_cache", True)]:
        with pytest.raises(ValueError, match=option):
            engine(cfg, params, **{option: value})


def teacher_forced_logits(eng, cfg, params, rows, n_prompts):
    """Logits at every position from each prompt's end on, out of the
    ENGINE's own pool and tables: the prompts started through
    ``eng.start`` (chunked ``paged_prefill``), then every running slot
    decoded a step at a time with what ``eng._stage`` hands
    ``paged_decode`` (tables at the dispatch's width, positions, the
    runnable mask), the row's own next token fed instead of the sampled
    one.  A finished slot is released mid-flight."""
    b = eng._slots
    slots = {}
    for row, n in zip(rows, n_prompts):
        slot, _ = eng.start(row[:n], max_tokens=len(row) - n + 1)
        slots[slot] = row
    out = {s: [] for s in slots}
    most = {k.name: 0 for k in eng._kinds}
    while slots:
        for s, row in slots.items():
            b.tokens_h[s] = row[b.pos_h[s]]
        ptab, tokens, pos, run, w, _ = eng._stage(0)
        assert run[list(slots)].all()
        pool, x, _ = stack(cfg, params, eng._pool_state(), ptab,
                           tokens[:, None], pos[:, None], run[:, None])
        eng._pool = pool
        logits = np.asarray(readout(cfg, params, x[:, 0]))
        b.pos_h[run] += 1
        for k in eng._kinds:
            most[k.name] = max(most[k.name], int(k.n_pages.max()))
        for s in list(slots):
            out[s].append(logits[s])
            if b.pos_h[s] == len(slots[s]):
                eng.release(s)
                del slots[s]
    return [np.stack(v) for v in out.values()], most


@pytest.mark.parametrize("lengths,n_prompts", [
    ([100], [37]),                              # alone, six windows long
    ([12, 16, 50, 100], [5, 15, 21, 70]),       # 4 slots, one dispatch
], ids=["alone", "four-slots-mixed"])
def test_engine_prefill_then_decode_matches_reference(lengths, n_prompts):
    cfg, params = model()
    eng = engine(cfg, params)
    rows = [some_ids(cfg, (n,), seed=10 + i) for i, n in enumerate(lengths)]
    got, most = teacher_forced_logits(eng, cfg, params, rows, n_prompts)
    for row, n, g in zip(rows, n_prompts, got):
        want = reference_logits(cfg, params, row)[0][n:]
        assert g.shape == want.shape and close(g, want)
    # the longest row took a page of the full kind every C positions and
    # never more than the ring of the window kind
    assert most == {"full": -(-max(lengths) // C), "window": RING}
    assert eng._alloc.in_use() == 0 and eng.pages_unaccounted() == 0


def greedy_gaps(cfg, params, prompt, tokens):
    """By how much each served token's reference logit lies under the
    reference's best, the row teacher-forced with the served tokens."""
    row = np.concatenate([prompt, tokens[:-1]])
    logits = reference_logits(cfg, params, row)[0][len(prompt) - 1:]
    return logits.max(axis=-1) - logits[np.arange(len(tokens)), tokens]


def test_continuous_batcher_serves_it_and_returns_every_page():
    cfg, params = model()
    eng = engine(cfg, params, n_slots=3)
    eng.warmup()
    before = {**decode_metrics.snapshot(),
              "slot_steps": decode_metrics.slot_steps}
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 70, 9, 33, 16, 3, 90)]
    budgets = [6, 30, 4, 40, 20, 10, 25]
    with ContinuousBatcher(eng) as batcher:
        # seven requests on three slots: they join and leave mid-decode
        handles = [batcher.submit(p, max_tokens=m, temperature=0.0,
                                  eos_id=None)
                   for p, m in zip(prompts, budgets)]
        outs = [h.result(timeout=120.0) for h in handles]
    after = {**decode_metrics.snapshot(),
             "slot_steps": decode_metrics.slot_steps}
    for p, m, out in zip(prompts, budgets, outs):
        assert len(out) == m
        assert greedy_gaps(cfg, params, p, out).max() <= 1e-3
    assert after["compile_delta_since_mark"] == 0
    assert eng._alloc.in_use() == 0 and eng.pages_unaccounted() == 0
    d = {k: after[k] - before[k] for k in (
        "moe_assignments", "moe_assignments_held", "moe_layer_dispatches",
        "decode_dispatches", "kv_rows_held_full", "kv_rows_held_window",
        "window_pages_reused", "slot_steps")}
    assert d["moe_layer_dispatches"] == 8 * d["decode_dispatches"]
    assert d["moe_assignments"] == d["moe_assignments_held"] \
        == 8 * 2 * d["slot_steps"]
    # past the window a window layer holds less than a full one, and the
    # long prompts and their continuations went round the ring
    assert 0 < d["kv_rows_held_window"] < d["kv_rows_held_full"]
    assert d["window_pages_reused"] >= (-(-70 // C) - RING) + (
        -(-90 // C) - RING)
    assert after["pages_in_use_full"] == after["pages_in_use_window"] == 0


def test_two_requests_with_one_long_prefix_both_agree():
    """A family with a bounded kind mounts no prefix by reference (a
    later slot would be handed window pages since written over): the
    second request prefills its own pages, and both are the
    reference's."""
    cfg, params = model()
    eng = engine(cfg, params)
    shared = some_ids(cfg, (72,), seed=21)
    prompts = [np.concatenate([shared, some_ids(cfg, (5,), seed=22 + i)])
               for i in range(2)]
    before = decode_metrics.snapshot()
    outs = []
    for p in prompts:
        slot, first = eng.start(p, max_tokens=12)
        toks = [first]
        for _ in range(11):
            toks.append(int(eng.advance()[slot]))
        outs.append(np.asarray(toks))
        eng.release(slot)
        assert not eng._resident
    after = decode_metrics.snapshot()
    assert after["prefix_hits"] == before["prefix_hits"]
    for p, out in zip(prompts, outs):
        assert greedy_gaps(cfg, params, p, out).max() <= 1e-3
    assert eng._alloc.in_use() == 0 and eng.pages_unaccounted() == 0


def test_the_traced_programs_do_not_change_from_process_to_process():
    """Python randomises string hashes a process: a program traced in a
    set's order would change its text, and with it its persistent
    compile-cache key, from run to run (on the chip: 57 s of set-up in
    every other run)."""
    import subprocess

    script = (
        "import hashlib, jax\n"
        "from deeplearning4j_tpu.models import mellum as ml\n"
        "from deeplearning4j_tpu.serving.decode import DecodeEngine\n"
        "cfg = ml.tiny_config(compute_dtype='float32')\n"
        "eng = DecodeEngine(cfg, ml.init_params(jax.random.key(0), cfg),\n"
        "                   n_slots=2, buckets=(32, 64), prefill_chunk=8)\n"
        "print(hashlib.sha256(eng._lower_decode(64).as_text().encode())\n"
        "      .hexdigest())\n")
    seen = set()
    for hash_seed in ("1", "2", "3"):
        env = {**os.environ, "PYTHONHASHSEED": hash_seed,
               "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        seen.add(out.stdout.strip().splitlines()[-1])
    assert len(seen) == 1
