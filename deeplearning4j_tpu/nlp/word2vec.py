"""Word2Vec — skip-gram with hierarchical softmax + negative sampling.

Reference parity: ``models/word2vec/Word2Vec.java:57`` (fit:101,
buildVocab:257, trainSentence:298, skipGram:314) and the inner kernel
``InMemoryLookupTable.iterateSample:195-303`` (HS tree walk: dot -> sigmoid
-> g=(1-code-f)*alpha -> axpy into syn0/syn1; negative-sampling loop over a
unigram table; lr decay by words seen).

TPU-native redesign — the reference's kernel is per-word BLAS-1 axpy on
small vectors, the worst possible TPU shape (SURVEY.md "hard parts": sparse
embedding updates).  Here whole [B]-pair chunks train inside one jitted
scan:

- the padded Huffman tables (vocab.encode_hs_tables) are gathered per
  chunk: codes/points [B, L] + mask; negative sampling draws [B, K]
  negatives on device from the unigram table;
- on TPU with a VMEM-sized vocabulary, the chunk update runs through the
  fused Pallas kernel (ops/pallas_word2vec): tables stay resident in
  VMEM and every row gather/scatter is a one-hot matmul on the MXU;
- otherwise the XLA path batches the math as einsums + count-normalized
  scatter-adds into syn0/syn1/syn1neg;
- the LR schedule (linear decay by words seen, min 1e-4 floor —
  Word2Vec.java trainSentence) is an on-device per-chunk clock, and
  ``depth_buckets`` optionally partitions pairs by center Huffman depth
  so frequent (shallow) centers skip padded levels.

Pair generation stays on host but runs ONCE per corpus: full-window
candidate pairs are built in slabs that STREAM into epoch 0's async
device dispatches (cold-fit wall time = max(host, device)), then cached
for later epochs/fits; the dynamic window shrink (b = rand % window,
skipGram:314) is applied ON DEVICE as a per-epoch mask, and each slab
trains as one ``lax.scan`` dispatch over fixed-size [B] chunks
(see _scan_slab / run_pair_training).
"""

from __future__ import annotations

import dataclasses
import logging
from functools import partial
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.text import DefaultTokenizerFactory
from deeplearning4j_tpu.nlp.vocab import (VocabCache, build_huffman,
                                          build_vocab, encode_hs_tables,
                                          unigram_table)
from deeplearning4j_tpu.nlp.word_vectors import WordVectors

log = logging.getLogger(__name__)

Array = jax.Array


@dataclasses.dataclass
class Word2VecConfig:
    vector_size: int = 100
    window: int = 5
    min_word_frequency: int = 1
    alpha: float = 0.025
    min_alpha: float = 1e-4
    negative: int = 0           # 0 => hierarchical softmax only
    use_hs: bool = True
    epochs: int = 1
    batch_size: int = 2048
    seed: int = 42
    table_size: int = 100_000
    #: "auto" picks the VMEM-resident Pallas kernel on TPU when the
    #: tables fit (ops/pallas_word2vec), else the XLA gather/scatter
    #: path; "pallas"/"xla" force a path ("pallas" off-TPU runs the
    #: kernel through the interpreter — test harness only)
    kernel: str = "auto"
    #: >1 partitions pairs by center Huffman depth into that many
    #: buckets with per-bucket sliced HS tables — shallow (frequent)
    #: pairs skip the deep padded levels.  Exact semantics (masked
    #: levels contribute nothing); costs one jit variant per bucket.
    depth_buckets: int = 1
    #: "masked" (default): candidate pairs at the full window are built
    #: once and the per-epoch dynamic window shrink masks on device —
    #: zero host pair work after epoch 0, but ~45% of pair compute is
    #: masked waste at window 5.  "exact": the shrink is applied host-
    #: side per epoch (the reference's actual algorithm) so the device
    #: trains only real pairs — fresh streaming every epoch (overlapped
    #: with dispatch), no replay cache.  "device": NO host pair work at
    #: all — the int32 token stream uploads once (~4 bytes/word vs
    #: ~16 bytes/PAIR for host-built slabs) and each epoch is ONE
    #: dispatch that gathers contexts, applies sentence-boundary and
    #: window-shrink masks, and trains, all on device (see
    #: _scan_stream_epoch).
    pair_mode: str = "masked"


# -- jitted training steps --------------------------------------------------

def _hs_update(syn0: Array, syn1: Array, inputs: Array, codes: Array,
               points: Array, mask: Array, alpha: Array):
    """One batched HS update (the XLA gather/scatter path).

    inputs [B] — rows of syn0 to train (context words);
    codes/points/mask [B, L] — the center words' Huffman paths.
    Padded pairs carry mask == 0 everywhere, so they contribute nothing."""
    l1 = syn0[inputs]                                   # [B, D]
    s1 = syn1[points]                                   # [B, L, D]
    f = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", l1, s1))
    g = (1.0 - codes.astype(jnp.float32) - f) * alpha * mask
    neu1e = jnp.einsum("bl,bld->bd", g, s1)             # dL/dl1
    dsyn1 = g[:, :, None] * l1[:, None, :]              # [B, L, D]
    B, L, D = dsyn1.shape
    # Rows hit many times in one batch would receive a SUM of updates all
    # computed at stale values (the reference applies them sequentially);
    # normalize to the per-row MEAN so the batched step stays stable at any
    # batch-size/vocab ratio.
    flat_pts = points.reshape(B * L)
    cnt1 = jnp.zeros(syn1.shape[0]).at[flat_pts].add(
        mask.reshape(B * L), mode="drop")
    syn1 = syn1.at[flat_pts].add(
        dsyn1.reshape(B * L, D)
        / jnp.maximum(cnt1, 1.0)[flat_pts][:, None], mode="drop")
    row_mask = (jnp.sum(mask, axis=1) > 0).astype(jnp.float32)
    cnt0 = jnp.zeros(syn0.shape[0]).at[inputs].add(row_mask, mode="drop")
    syn0 = syn0.at[inputs].add(
        neu1e / jnp.maximum(cnt0, 1.0)[inputs][:, None], mode="drop")
    return syn0, syn1


def _neg_update(syn0: Array, syn1neg: Array, inputs: Array, targets: Array,
                negatives: Array, pair_mask: Array, alpha: Array):
    """Negative sampling: target center word label 1, K negatives label 0.
    ``pair_mask`` [B] zeroes padded pairs."""
    l1 = syn0[inputs]                                    # [B, D]
    rows = jnp.concatenate([targets[:, None], negatives], axis=1)  # [B,K+1]
    labels = jnp.concatenate(
        [jnp.ones_like(targets[:, None], jnp.float32),
         jnp.zeros(negatives.shape, jnp.float32)], axis=1)
    sn = syn1neg[rows]                                   # [B, K+1, D]
    f = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", l1, sn))
    # mask accidental collisions negative == target
    valid = jnp.concatenate(
        [jnp.ones_like(targets[:, None], jnp.float32),
         (negatives != targets[:, None]).astype(jnp.float32)], axis=1)
    g = (labels - f) * alpha * valid * pair_mask[:, None]
    neu1e = jnp.einsum("bk,bkd->bd", g, sn)
    dneg = g[:, :, None] * l1[:, None, :]
    B, K1, D = dneg.shape
    # per-row mean normalization (see _hs_update)
    flat_rows = rows.reshape(B * K1)
    hit = (valid * pair_mask[:, None]).reshape(B * K1)
    cntn = jnp.zeros(syn1neg.shape[0]).at[flat_rows].add(hit, mode="drop")
    syn1neg = syn1neg.at[flat_rows].add(
        dneg.reshape(B * K1, D)
        / jnp.maximum(cntn, 1.0)[flat_rows][:, None], mode="drop")
    cnt0 = jnp.zeros(syn0.shape[0]).at[inputs].add(pair_mask, mode="drop")
    syn0 = syn0.at[inputs].add(
        neu1e / jnp.maximum(cnt0, 1.0)[inputs][:, None], mode="drop")
    return syn0, syn1neg


@partial(jax.jit, donate_argnums=(0, 1, 2),
         static_argnames=("use_hs", "negative", "window", "window_mask",
                          "pallas_block", "pallas_interpret"))
def _scan_slab(syn0: Array, syn1: Array, syn1neg: Array,
               centers: Array, contexts: Array, cpos: Array, deltas: Array,
               offsets: Array, chunk_ids: Array, n_real: Array,
               codes_t: Array, points_t: Array, mask_t: Array,
               table: Array, key: Array, epoch: Array,
               epoch_frac: Array, alpha0: Array,
               min_alpha: Array,
               *, use_hs: bool, negative: int, window: int,
               window_mask: bool = True,
               pallas_block: int = 0, pallas_interpret: bool = False):
    """One dispatch per SLAB of chunks: ``lax.scan`` over [NC, B] pair
    chunks so the whole epoch costs one host->device round trip.

    The per-chunk fused step still paid one dispatch per 16k pairs,
    which made training dispatch-latency-bound at small chunk compute.
    Scanning the chunks inside one jitted program removes that entirely.

    The reference's dynamic window shrink (skipGram:314's
    ``b = rand % window``: position ``pos`` trains only context offsets
    ``|delta| <= window - b``) moves ON DEVICE: per epoch a fresh
    ``b[n_positions]`` is drawn and pairs are masked by
    ``|delta| <= window - b[cpos]``.  That lets the host build the
    candidate pair list (all offsets up to ``window``) exactly ONCE per
    corpus instead of re-running pair generation every epoch.

    ``offsets`` [NC] = each chunk's first-pair word offset as a FRACTION
    of the total decay span (formed in float64 on host from exact int64
    word counts), and ``epoch_frac`` = total_words/total, so the linear
    lr decay by words seen (trainSentence:298) stays exact:
    ``alpha = max(min_alpha, alpha0 * (1 - (epoch*epoch_frac +
    offsets[c])))``.  ``n_real`` [NC] = real
    (unpadded) pairs per chunk; ``chunk_ids`` stay globally unique across
    slabs so negative draws never repeat within an epoch.
    """
    ekey = jax.random.fold_in(key, epoch)
    seed32 = jax.random.randint(
        jax.random.fold_in(ekey, 0), (), 0, 2 ** 31 - 1, jnp.uint32)
    B = centers.shape[1]
    col = jnp.arange(B)

    def b_draw(pos):
        # the one shrink-draw implementation, shared with the "device"
        # stream path (_scan_stream_epoch) so the two modes can never
        # diverge on shrink semantics
        return _hash_shrink(pos, seed32, window)

    def body(carry, inp):
        syn0, syn1, syn1neg = carry
        cen, ctx, pos, dlt, off, cid, nr = inp
        pmask = (col < nr).astype(jnp.float32)
        if window_mask:
            shrink = window - b_draw(pos)                    # [B]
            m = (jnp.abs(dlt) <= shrink).astype(jnp.float32) * pmask
        else:
            # pairs arrive pre-shrunk from the host (pair_mode="exact"):
            # every real pair trains
            m = pmask
        frac = epoch.astype(jnp.float32) * epoch_frac + off
        alpha = jnp.maximum(min_alpha, alpha0 * (1.0 - frac))
        if negative > 0:
            draws = jax.random.randint(
                jax.random.fold_in(ekey, 1 + cid),
                (B, negative), 0, table.shape[0])
            negs = table[draws]
        else:
            negs = jnp.zeros((B, 1), jnp.int32)
        if pallas_block > 0:
            from deeplearning4j_tpu.ops.pallas_word2vec import \
                fused_chunk_update
            if use_hs:
                codes_b, points_b, mask_b = (codes_t[cen], points_t[cen],
                                             mask_t[cen])
            else:      # no Huffman tables exist; (B, 1) dummies keep the
                B_ = cen.shape[0]          # kernel's BlockSpecs non-empty
                codes_b = jnp.zeros((B_, 1), jnp.float32)
                points_b = jnp.zeros((B_, 1), jnp.int32)
                mask_b = jnp.zeros((B_, 1), jnp.float32)
            syn0, syn1, syn1neg = fused_chunk_update(
                syn0, syn1, syn1neg, ctx, cen, codes_b,
                points_b, mask_b, negs, m, alpha,
                use_hs=use_hs, negative=negative,
                block=pallas_block, interpret=pallas_interpret)
        else:
            # both objectives read CHUNK-START tables and their syn0
            # deltas are summed — the exact semantics of the fused
            # Pallas kernel, so kernel="xla" and kernel="pallas" agree
            # to bf16 precision (tests/test_nlp.py asserts this)
            syn0_in = syn0
            if use_hs:
                hs0, syn1 = _hs_update(
                    syn0_in, syn1, ctx, codes_t[cen], points_t[cen],
                    mask_t[cen] * m[:, None], alpha)
                syn0 = syn0 + (hs0 - syn0_in)
            if negative > 0:
                ng0, syn1neg = _neg_update(
                    syn0_in, syn1neg, ctx, cen, negs, m, alpha)
                syn0 = syn0 + (ng0 - syn0_in)
        return (syn0, syn1, syn1neg), None

    (syn0, syn1, syn1neg), _ = jax.lax.scan(
        body, (syn0, syn1, syn1neg),
        (centers, contexts, cpos, deltas, offsets, chunk_ids, n_real))
    return syn0, syn1, syn1neg


def _hash_shrink(pos: Array, seed32: Array, window: int) -> Array:
    """Stateless per-(epoch, position) window-shrink draw: a Wang-style
    integer hash of the position — every pair sharing a center position
    sees the same b, no O(corpus) array is materialized per dispatch,
    and epochs re-draw via ``seed32``.  (The reference's own randomness
    is an LCG stream, Word2Vec.java skipGram:314.)"""
    h = pos.astype(jnp.uint32) * jnp.uint32(2654435761) + seed32
    h = (h ^ (h >> 16)) * jnp.uint32(2246822519)
    h = (h ^ (h >> 13)) * jnp.uint32(3266489917)
    return ((h ^ (h >> 16)) % jnp.uint32(window)).astype(jnp.int32)


def _stream_epoch_scan(syn0: Array, syn1: Array, syn1neg: Array,
                       tok: Array, n_stream: Array, chunk0: Array,
                       codes_t: Array, points_t: Array, mask_t: Array,
                       table: Array, key: Array, epoch: Array,
                       n_epochs_f: Array, alpha0: Array, min_alpha: Array,
                       *, use_hs: bool, negative: int, window: int,
                       pos_chunk: int, n_chunks: int,
                       pallas_block: int = 0,
                       pallas_interpret: bool = False):
    """Core of the pair_mode="device" epoch: scan ``n_chunks`` position
    chunks starting at chunk index ``chunk0`` (traced — the dp path
    gives each mesh shard its own stripe).  See _scan_stream_epoch."""
    ekey = jax.random.fold_in(key, epoch)
    seed32 = jax.random.randint(
        jax.random.fold_in(ekey, 0), (), 0, 2 ** 31 - 1, jnp.uint32)
    deltas = jnp.concatenate([jnp.arange(-window, 0),
                              jnp.arange(1, window + 1)]).astype(jnp.int32)
    W2 = 2 * window
    B = pos_chunk * W2
    n_pad = tok.shape[0]
    sid = jnp.cumsum((tok < 0).astype(jnp.int32))
    nf = n_stream.astype(jnp.float32)

    def body(carry, i):
        syn0, syn1, syn1neg = carry
        p0 = i * pos_chunk
        pos = p0 + jnp.arange(pos_chunk, dtype=jnp.int32)
        cen = tok[pos]
        j = pos[:, None] + deltas[None, :]                  # [P, 2W]
        jc = jnp.clip(j, 0, n_pad - 1)
        ctx = tok[jc]
        valid = ((j >= 0) & (cen[:, None] >= 0) & (ctx >= 0)
                 & (sid[jc] == sid[pos][:, None]))
        shrink = window - _hash_shrink(pos, seed32, window)
        m = valid & (jnp.abs(deltas)[None, :] <= shrink[:, None])
        pm = m.reshape(B).astype(jnp.float32)
        inputs = jnp.maximum(ctx, 0).reshape(B)
        cen_s = jnp.maximum(cen, 0)
        targets = jnp.broadcast_to(cen_s[:, None],
                                   (pos_chunk, W2)).reshape(B)
        frac = (epoch.astype(jnp.float32) * nf + p0) \
            / jnp.maximum(nf * n_epochs_f, 1.0)
        alpha = jnp.maximum(min_alpha, alpha0 * (1.0 - frac))
        if negative > 0:
            draws = jax.random.randint(
                jax.random.fold_in(ekey, 1 + i), (B, negative), 0,
                table.shape[0])
            negs = table[draws]
        else:
            negs = jnp.zeros((B, 1), jnp.int32)
        if use_hs:
            codes_b = jnp.broadcast_to(
                codes_t[cen_s][:, None, :],
                (pos_chunk, W2, codes_t.shape[1])).reshape(B, -1)
            points_b = jnp.broadcast_to(
                points_t[cen_s][:, None, :],
                (pos_chunk, W2, points_t.shape[1])).reshape(B, -1)
            mask_b = jnp.broadcast_to(
                mask_t[cen_s][:, None, :],
                (pos_chunk, W2, mask_t.shape[1])).reshape(B, -1)
        else:
            codes_b = jnp.zeros((B, 1), jnp.float32)
            points_b = jnp.zeros((B, 1), jnp.int32)
            mask_b = jnp.zeros((B, 1), jnp.float32)
        if pallas_block > 0:
            from deeplearning4j_tpu.ops.pallas_word2vec import \
                fused_chunk_update
            syn0, syn1, syn1neg = fused_chunk_update(
                syn0, syn1, syn1neg, inputs, targets, codes_b,
                points_b, mask_b, negs, pm, alpha,
                use_hs=use_hs, negative=negative,
                block=pallas_block, interpret=pallas_interpret)
        else:
            syn0_in = syn0
            if use_hs:
                hs0, syn1 = _hs_update(
                    syn0_in, syn1, inputs, codes_b,
                    points_b, mask_b * pm[:, None], alpha)
                syn0 = syn0 + (hs0 - syn0_in)
            if negative > 0:
                ng0, syn1neg = _neg_update(
                    syn0_in, syn1neg, inputs, targets, negs, pm, alpha)
                syn0 = syn0 + (ng0 - syn0_in)
        return (syn0, syn1, syn1neg), None

    (syn0, syn1, syn1neg), _ = jax.lax.scan(
        body, (syn0, syn1, syn1neg),
        chunk0 + jnp.arange(n_chunks, dtype=jnp.int32))
    return syn0, syn1, syn1neg


@partial(jax.jit, donate_argnums=(0, 1, 2),
         static_argnames=("use_hs", "negative", "window", "pos_chunk",
                          "n_chunks", "pallas_block", "pallas_interpret"))
def _scan_stream_epoch(syn0: Array, syn1: Array, syn1neg: Array,
                       tok: Array, n_stream: Array,
                       codes_t: Array, points_t: Array, mask_t: Array,
                       table: Array, key: Array, epoch: Array,
                       n_epochs_f: Array, alpha0: Array, min_alpha: Array,
                       *, use_hs: bool, negative: int, window: int,
                       pos_chunk: int, n_chunks: int,
                       pallas_block: int = 0,
                       pallas_interpret: bool = False):
    """One dispatch per EPOCH with ZERO host pair work (pair_mode
    ="device"): ``tok`` is the int32 token stream with ``-1`` sentence
    separators, uploaded ONCE per corpus (~4 bytes/word, vs ~16 bytes
    per PAIR for host-built slabs uploaded every fit).  Each
    scan step takes a [pos_chunk] window of positions and builds its
    pairs on device: contexts are ``tok`` gathers at the 2W signed
    offsets, sentence boundaries mask via a separator-count (cumsum)
    sentence id, and the reference's dynamic window shrink
    (skipGram:314) is the usual stateless hash mask.  The lr clock is
    the stream position (= words seen, separators included — within
    ~n_sentences/n_words of the reference's per-sentence clock)."""
    return _stream_epoch_scan(
        syn0, syn1, syn1neg, tok, n_stream, jnp.int32(0), codes_t,
        points_t, mask_t, table, key, epoch, n_epochs_f, alpha0,
        min_alpha, use_hs=use_hs, negative=negative, window=window,
        pos_chunk=pos_chunk, n_chunks=n_chunks,
        pallas_block=pallas_block, pallas_interpret=pallas_interpret)


def make_dp_stream_epoch(mesh, axis: str, n_shards: int, per: int, *,
                         use_hs: bool, negative: int, window: int,
                         pos_chunk: int, pallas_block: int,
                         pallas_interpret: bool, average: bool = True):
    """Data-parallel device-mode epoch over a mesh ``axis``: each shard
    trains its contiguous stripe of ``per`` position chunks on its OWN
    table replica, then replicas are parameter-AVERAGED (pmean) — the
    reference's Spark each-iteration averaging mode
    (SparkDl4jMultiLayer fitDataSet / ParameterAveragingTrainer role),
    per EPOCH at chip scale.  Returns a jitted epoch function with the
    _scan_stream_epoch signature.

    ``average=False`` skips the pmean (shard-local updates; replicas
    DIVERGE) — only for measuring the collective's share of epoch
    time, never for training."""
    from deeplearning4j_tpu.compat import shard_map
    from jax.sharding import PartitionSpec as P

    rep = P()

    def shard_fn(syn0, syn1, syn1neg, tok, n_stream, codes_t, points_t,
                 mask_t, table, key, epoch, n_epochs_f, alpha0,
                 min_alpha):
        c0 = jax.lax.axis_index(axis) * per
        syn0, syn1, syn1neg = _stream_epoch_scan(
            syn0, syn1, syn1neg, tok, n_stream, c0, codes_t, points_t,
            mask_t, table, key, epoch, n_epochs_f, alpha0, min_alpha,
            use_hs=use_hs, negative=negative, window=window,
            pos_chunk=pos_chunk, n_chunks=per,
            pallas_block=pallas_block, pallas_interpret=pallas_interpret)
        if not average:
            return syn0, syn1, syn1neg
        pm = lambda x: jax.lax.pmean(x, axis)
        return pm(syn0), pm(syn1), pm(syn1neg)

    f = shard_map(shard_fn, mesh=mesh, in_specs=(rep,) * 14,
                  out_specs=(rep,) * 3, check_vma=False)
    return jax.jit(f, donate_argnums=(0, 1, 2))


def run_stream_training(syn0, syn1, syn1neg, indexed, *,
                        vocab_size, dim, epochs, codes_t, points_t,
                        mask_t, table, window, alpha, min_alpha, use_hs,
                        negative, batch_size, kernel, seed,
                        stream_cache=None, mesh=None, data_axis="data"):
    """pair_mode="device" engine: upload the separator-delimited token
    stream once, then one ``_scan_stream_epoch`` dispatch per epoch.
    With ``mesh`` (and >1 devices on ``data_axis``), each device trains
    a stripe of the stream on its own replica and replicas are
    parameter-averaged per epoch (``make_dp_stream_epoch``).
    Returns (syn0, syn1, syn1neg, stream_cache, kernel_used)."""
    import dataclasses

    from deeplearning4j_tpu.ops.kernel_select import choose_kernel
    from deeplearning4j_tpu.ops.pallas_word2vec import (choose_block,
                                                        probe_compile)
    W2 = 2 * window
    # pos_chunk: pairs-per-chunk ~= batch_size, with B = pos_chunk*2W a
    # multiple of every kernel block size (512 | lcm constraint below)
    import math
    step = 512 // math.gcd(W2, 512)
    pos_chunk = max(step, (batch_size // W2) // step * step)
    B = pos_chunk * W2

    interpret = jax.devices()[0].platform != "tpu"

    def probe(blk):
        return probe_compile(blk, use_hs, negative, vocab_size, dim,
                             int(codes_t.shape[1]) if use_hs else 1)

    choice = choose_kernel(
        kernel, choose_block(vocab_size, dim, negative, B,
                             interpret=interpret),
        f"word2vec vocab {vocab_size} x dim {dim} (batch {B})", probe)
    # Honor the configured batch_size at the finest granularity the
    # selected kernel supports.  The 512-lcm floor above is only the
    # fused kernel's largest-BlockSpec preference — applied
    # unconditionally it rounded every small batch_size up to 256
    # POSITIONS (~1536 pair slots) per sequential update, which
    # collapsed convergence on small corpora to a handful of
    # mean-normalized steps per epoch.  That granularity cliff (not a
    # numeric issue) was the root cause of the device-mode quality
    # failures ROADMAP item 3 tracked.
    fine = max(8, (batch_size // W2) // 8 * 8)

    def _block_ok(blk):
        # a re-picked block must clear the same compile-probe gate the
        # original one did (block size changes the kernel signature);
        # on probe failure we keep the already-validated coarse block
        return choice.interpret or kernel != "auto" or probe(blk) is None

    if choice.block == 0:
        pos_chunk = fine                    # XLA path: any chunk shape
    elif pos_chunk > fine:
        blk2 = choose_block(vocab_size, dim, negative, fine * W2,
                            interpret=interpret)
        if blk2 and fine * W2 % blk2 == 0 and _block_ok(blk2):
            pos_chunk, choice = fine, dataclasses.replace(
                choice, block=blk2,
                why=f"{choice.why}; block {blk2} for batch granularity")
        else:
            # compiled kernel grids need B % block == 0: fall back to
            # the finest 128-lane-aligned chunk covering batch_size
            step128 = 128 // math.gcd(W2, 128)
            cand = max(step128, (batch_size // W2) // step128 * step128)
            blk3 = choose_block(vocab_size, dim, negative, cand * W2,
                                interpret=interpret)
            if (blk3 and cand * W2 % blk3 == 0 and cand < pos_chunk
                    and _block_ok(blk3)):
                pos_chunk, choice = cand, dataclasses.replace(
                    choice, block=blk3,
                    why=f"{choice.why}; block {blk3} for batch granularity")
    B = pos_chunk * W2
    pallas_block, pallas_interpret = choice.block, choice.interpret

    n_shards = int(mesh.shape[data_axis]) if mesh is not None else 1
    if stream_cache is None:
        # separator-delimited stream: sentence ids come from a cumsum on
        # device, so only ONE int32 array rides the link.  NC is padded
        # only to a multiple of n_shards (1 when unsharded) — a previous
        # next-power-of-two pad made up to ~2x of every epoch's scan
        # steps process fully-masked -1 filler.
        n_stream = int(sum(a.size + 1 for a in indexed))
        NC = -(-n_stream // pos_chunk)
        NC = max(n_shards, -(-NC // n_shards) * n_shards)
        stream = np.full(NC * pos_chunk, -1, np.int32)
        off = 0
        for a in indexed:
            stream[off:off + a.size] = a
            off += a.size + 1
        stream_cache = {"tok": jnp.asarray(stream), "n_stream": n_stream,
                        "n_chunks": NC, "pos_chunk": pos_chunk}
    if stream_cache["pos_chunk"] != pos_chunk:
        raise ValueError("stream cache built for a different batch "
                         "size; refit with a fresh instance")
    nkey = jax.random.key(seed + 1)
    had_neg = syn1neg is not None
    if not had_neg:
        syn1neg = jnp.zeros((1, 1), jnp.float32)
    NC = stream_cache["n_chunks"]
    if n_shards > 1 and NC % n_shards != 0:
        # Silently ignoring the mesh would train single-device while the
        # caller believes it is data-parallel; surface the mismatch.
        raise ValueError(
            f"stream cache has {NC} chunks, not divisible by the mesh's "
            f"{n_shards} '{data_axis}' shards; rebuild the cache (fit a "
            f"fresh instance with mesh=) instead of reusing this one")
    if n_shards > 1:
        # dp epoch fns are keyed by mesh layout: reusing a jitted
        # shard_map closed over a dead/different mesh trains on the
        # wrong layout or crashes (ADVICE r4, medium)
        mesh_key = (tuple(d.id for d in mesh.devices.flat), data_axis,
                    n_shards, NC // n_shards)
        dp_fns = stream_cache.setdefault("dp_epoch_fns", {})
        epoch_fn = dp_fns.get(mesh_key)
        if epoch_fn is None:
            epoch_fn = make_dp_stream_epoch(
                mesh, data_axis, n_shards, NC // n_shards,
                use_hs=use_hs, negative=negative, window=window,
                pos_chunk=pos_chunk, pallas_block=pallas_block,
                pallas_interpret=pallas_interpret)
            dp_fns[mesh_key] = epoch_fn
        for epoch in range(epochs):
            syn0, syn1, syn1neg = epoch_fn(
                syn0, syn1, syn1neg, stream_cache["tok"],
                jnp.int32(stream_cache["n_stream"]), codes_t, points_t,
                mask_t, table, nkey, jnp.int32(epoch),
                jnp.float32(max(epochs, 1)), jnp.float32(alpha),
                jnp.float32(min_alpha))
    else:
        for epoch in range(epochs):
            syn0, syn1, syn1neg = _scan_stream_epoch(
                syn0, syn1, syn1neg, stream_cache["tok"],
                jnp.int32(stream_cache["n_stream"]), codes_t, points_t,
                mask_t, table, nkey, jnp.int32(epoch),
                jnp.float32(max(epochs, 1)), jnp.float32(alpha),
                jnp.float32(min_alpha), use_hs=use_hs, negative=negative,
                window=window, pos_chunk=pos_chunk, n_chunks=NC,
                pallas_block=pallas_block,
                pallas_interpret=pallas_interpret)
    return (syn0, syn1, syn1neg if had_neg else None, stream_cache,
            choice)


# -- host-side pair generation ---------------------------------------------

def sentence_pairs(idx: np.ndarray, window: int,
                   rng: np.random.RandomState
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """(center, context) pairs with per-position dynamic window shrink
    (skipGram:314's b = rand % window).  Fully vectorized: the previous
    python double loop topped out around 450k words/s on host, below the
    device kernel's rate — pair generation must not be the pipeline's
    bottleneck."""
    n = idx.shape[0]
    if n < 2:
        return (np.empty(0, np.int32),) * 2
    b = rng.randint(0, window, size=n)
    deltas = np.concatenate([np.arange(-window, 0),
                             np.arange(1, window + 1)])      # [2W]
    pos = np.arange(n)
    j = pos[:, None] + deltas[None, :]                        # [n, 2W]
    valid = ((np.abs(deltas)[None, :] <= (window - b)[:, None])
             & (j >= 0) & (j < n))
    ci, di = np.nonzero(valid)            # row-major: same order as the
    return (idx[ci].astype(np.int32),     # reference's per-pos j sweep
            idx[j[ci, di]].astype(np.int32))


def corpus_pairs(indexed: Sequence[np.ndarray], window: int,
                 slab: int = 1 << 20
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray,
                            np.ndarray]:
    """CANDIDATE (center, context) pairs for the whole corpus at the FULL
    window — built once; the per-epoch dynamic window shrink is applied
    on-device as a mask (see _scan_slab).

    Returns (centers, contexts, center_pos, delta, word_offset) where
    ``center_pos`` indexes the concatenated token stream (the key for the
    on-device ``b`` draw), ``delta`` is the signed context offset, and
    ``word_offset`` is the words-seen count at the pair's sentence — the
    lr-decay clock.  Vectorized over ``slab``-position blocks so the
    [n, 2W] candidate matrix never exceeds ~40 MB however large the
    corpus."""
    outs = list(_corpus_pair_blocks(indexed, window, slab))
    if not outs:
        return (np.empty(0, np.int32),) * 4 + (np.empty(0, np.int64),)
    return tuple(np.concatenate([o[k] for o in outs])        # type: ignore
                 for k in range(5))


def _corpus_pair_blocks(indexed: Sequence[np.ndarray], window: int,
                        slab: int = 1 << 20, shrink_rng=None):
    """Yield candidate-pair 5-tuples per position slab (corpus_pairs'
    loop body, exposed for the streaming trainer).

    ``shrink_rng`` applies the reference's dynamic window shrink HOST-side
    (skipGram:314's ``b = rand % window``: position trains offsets
    ``|delta| <= window - b``): only surviving pairs are emitted, so the
    device trains ~(window+1)/(2*window) as many pairs instead of masking
    them out on-chip (pair_mode="exact")."""
    if not indexed:
        return
    tok = np.concatenate(indexed).astype(np.int32)
    lens = np.asarray([a.size for a in indexed])
    sid = np.repeat(np.arange(len(indexed)), lens)
    # words seen AFTER each sentence is processed (trainSentence:298
    # increments per sentence) — broadcast to its positions.  Kept int64
    # through prep: float32 loses integer exactness past 2^24 (~16.7M)
    # corpus words, which would drift the linear lr decay; the offset only
    # becomes float when the alpha RATIO is formed (in float64, prep_slab)
    seen_after = np.cumsum(lens, dtype=np.int64)
    word_off = seen_after[sid] - lens[sid]
    n = tok.size
    deltas = np.concatenate([np.arange(-window, 0),
                             np.arange(1, window + 1)]).astype(np.int32)
    for s0 in range(0, n, slab):
        s1 = min(n, s0 + slab)
        pos = np.arange(s0, s1, dtype=np.int32)
        j = pos[:, None] + deltas[None, :]                   # [S, 2W] i32
        jc = np.clip(j, 0, n - 1)
        valid = (j >= 0) & (j < n) & (sid[jc] == sid[s0:s1, None])
        if shrink_rng is not None:
            b = shrink_rng.randint(0, window, size=s1 - s0)
            valid &= np.abs(deltas)[None, :] <= (window - b)[:, None]
        ci, di = np.nonzero(valid)
        p = pos[ci]
        yield (tok[p], tok[j[ci, di]], p.astype(np.int32),
               deltas[di], word_off[p])


def corpus_pairs_slabs(indexed: Sequence[np.ndarray], window: int,
                       pairs_per_slab: int, shrink_rng=None):
    """Yield ``corpus_pairs``-shaped blocks of ~``pairs_per_slab`` pairs.
    Streaming form: the scanned trainer dispatches each block (async)
    before the host builds the next, so cold-fit wall time is
    max(host pair generation, device training), not their sum."""
    bufs: List[Tuple[np.ndarray, ...]] = []
    n = 0
    # position-slab sized so each block stays well under the pair budget
    # (a position contributes up to 2*window candidate pairs)
    pos_slab = max(1024, pairs_per_slab // (8 * window))
    for arr_slab in _corpus_pair_blocks(indexed, window, pos_slab,
                                        shrink_rng):
        bufs.append(arr_slab)
        n += arr_slab[0].size
        while n >= pairs_per_slab:
            # emit EXACTLY pairs_per_slab (uniform [NC, B] shapes ->
            # one jit variant for all full slabs); remainder carries over
            cat = tuple(np.concatenate([b[k] for b in bufs])
                        for k in range(5))
            yield tuple(a[:pairs_per_slab] for a in cat)
            bufs = [tuple(a[pairs_per_slab:] for a in cat)]
            n -= pairs_per_slab
    if n:
        yield tuple(np.concatenate([b[k] for b in bufs]) for k in range(5))


#: pairs per dispatch — bounds device buffers and jit-cache variants
PAIRS_PER_SLAB = 1 << 22
#: total pairs kept device-resident across epochs (beyond: host numpy,
#: re-uploaded once per slab per epoch — bounded HBM for any corpus)
RESIDENT_PAIR_CAP = 32 * (1 << 20)


def run_pair_training(syn0, syn1, syn1neg,
                      pairs=None, *,
                      vocab_size, dim, epochs,
                      total_words, codes_t, points_t,
                      mask_t, table, window,
                      alpha, min_alpha, use_hs,
                      negative, batch_size, kernel,
                      seed, dev_cache=None, pairs_iter=None,
                      pairs_iter_factory=None, window_mask=True,
                      hs_lengths=None, hs_weights=None, depth_buckets=1):
    """The shared scanned-epoch training engine (Word2Vec AND
    ParagraphVectors fit through here).

    Input pairs (centers, contexts, center_pos, delta, word_offset — the
    ``corpus_pairs`` layout, plus any always-train pairs encoded with
    delta = 0) arrive either materialized (``pairs``) or as a STREAM of
    blocks (``pairs_iter``, e.g. ``corpus_pairs_slabs``).  In streaming
    form epoch 0 interleaves host pair generation with async device
    dispatch: cold-fit wall time is max(host, device), not their sum.

    ``pairs_iter_factory(epoch) -> blocks`` streams a FRESH pair set
    every epoch (pair_mode="exact": the host applies the window shrink,
    so pass ``window_mask=False`` — no on-device masking, ~45% fewer
    trained pairs at window 5); no replay cache is kept in this mode.

    Handles kernel validation/selection (VMEM-resident Pallas kernel on
    TPU when the tables fit; ``kernel='pallas'`` raises when they
    don't), per-slab chunking with the device-residency cap, and
    globally-unique chunk ids (negative-sample draws never repeat within
    an epoch).  Returns ``(syn0, syn1, syn1neg, dev_cache,
    kernel_used)`` — thread
    ``dev_cache`` back in to replay the prepared slabs on later fits."""
    B = batch_size
    neg_tab = (syn1neg if syn1neg is not None
               else jnp.zeros((1, 1), jnp.float32))

    # kernel selection: VMEM-resident Pallas kernel on TPU whenever the
    # tables fit (2.7x the XLA path on v5e at bench shapes);
    # kernel="pallas" forces it (via the interpreter off-TPU: tests)
    from deeplearning4j_tpu.ops.kernel_select import choose_kernel
    from deeplearning4j_tpu.ops.pallas_word2vec import (choose_block,
                                                        probe_compile)
    # the resolved dispatch is returned so the fit (and bench rows)
    # record what ran and why — Mosaic's own message on a refusal
    kernel_used = choose_kernel(
        kernel,
        choose_block(vocab_size, dim, negative, B,
                     interpret=jax.devices()[0].platform != "tpu"),
        f"word2vec vocab {vocab_size} x dim {dim} (batch {B})",
        lambda blk: probe_compile(
            blk, use_hs, negative, vocab_size, dim,
            int(codes_t.shape[1]) if use_hs else 1))
    pallas_block = kernel_used.block
    pallas_interpret = kernel_used.interpret

    if epochs <= 0:
        return syn0, syn1, syn1neg, dev_cache, kernel_used
    total = max(1, total_words * epochs)
    nkey = jax.random.key(seed + 1)

    # -- depth buckets (opt-in): the HS level loop is static in L, so
    # every pair pays the vocabulary's MAX Huffman depth even though
    # zipf makes most centers shallow.  Bucketing pairs by center depth
    # and slicing the HS tables per bucket trains shallow pairs with a
    # short loop — exactly (levels beyond a pair's depth are masked
    # zeros, so dropping them changes nothing but chunk grouping).
    n_buckets = max(1, depth_buckets) if (use_hs and hs_lengths is not None
                                          ) else 1
    if n_buckets > 1:
        hs_len = np.asarray(hs_lengths)
        full_l = int(codes_t.shape[1])
        # pair-weighted boundaries: word count is the center-frequency
        # proxy (pairs per center scale with its occurrences)
        w = (np.asarray(hs_weights, np.float64)
             if hs_weights is not None else np.ones_like(hs_len, float))
        order = np.argsort(hs_len)
        cw = np.cumsum(w[order])
        cw /= cw[-1]
        qs = [hs_len[order][np.searchsorted(cw, i / n_buckets)]
              for i in range(1, n_buckets)]
        bounds = sorted(set(int(q) for q in qs) | {full_l})
        bounds = [b for b in bounds if b > 0]
        bucket_l = bounds                       # max depth per bucket
        tables = [(codes_t, points_t, mask_t) if lb == full_l else
                  (codes_t[:, :lb], points_t[:, :lb], mask_t[:, :lb])
                  for lb in bucket_l]

        def bucket_of(cen):
            return np.searchsorted(np.asarray(bucket_l),
                                   hs_len[cen], side="left")
    else:
        bucket_l = [int(codes_t.shape[1])]
        tables = [(codes_t, points_t, mask_t)]
        bucket_of = None

    def prep_slab(blk, resident):
        cen, ctx, cpos, dlt, woff = blk
        P = cen.size
        NC = -(-P // B)
        pad = NC * B - P

        def ch(a, fill=0):
            if pad:
                a = np.concatenate([a, np.full(pad, fill, a.dtype)])
            a = a.reshape(NC, B)
            return jnp.asarray(a) if resident else a

        n_real = np.full(NC, B, np.int32)
        n_real[-1] = P - (NC - 1) * B
        # per-chunk lr clock = word offset at the chunk's first pair,
        # converted to a FRACTION of the total decay span in float64 on
        # host (int64 offsets stay exact however large the corpus)
        off_frac = (woff[::B].astype(np.float64) / float(total)
                    ).astype(np.float32)
        return (ch(cen), ch(ctx), ch(cpos), ch(dlt),
                jnp.asarray(off_frac), jnp.asarray(n_real))

    def dispatch(slab, cid0, bidx, epoch, state):
        syn0, syn1, neg_tab = state
        cen_d, ctx_d, cpos_d, dlt_d, woff_d, n_real = slab
        NC = n_real.shape[0]
        cids = jnp.arange(cid0, cid0 + NC, dtype=jnp.int32)
        c_t, p_t, m_t = tables[bidx]
        return _scan_slab(
            syn0, syn1, neg_tab, cen_d, ctx_d, cpos_d, dlt_d,
            woff_d, cids, n_real, c_t, p_t, m_t, table,
            nkey, jnp.int32(epoch), jnp.float32(total_words / total),
            jnp.float32(alpha), jnp.float32(min_alpha),
            use_hs=use_hs, negative=negative, window=window,
            window_mask=window_mask,
            pallas_block=pallas_block, pallas_interpret=pallas_interpret)

    state = (syn0, syn1, neg_tab)

    def stream(blocks, epoch, slabs):
        """Stream pair blocks through prep+dispatch for one epoch — host
        preps slab k+1 while the device (async dispatch) trains slab k.
        ``slabs`` (a list) caches the prepared slabs for replay; None
        streams without caching (fresh pairs every epoch)."""
        nonlocal state
        seen_pairs = 0
        cid0 = 0
        # per-bucket carry buffers so every bucket emits uniform
        # PAIRS_PER_SLAB slabs (one jit variant per bucket)
        bufs: List[List[Tuple[np.ndarray, ...]]] = \
            [[] for _ in range(len(bucket_l))]
        buf_n = [0] * len(bucket_l)

        def record(part, bidx):
            """Prep, dispatch and (optionally) cache one slab — the single
            accounting path for both the direct and bucketed branches."""
            nonlocal seen_pairs, cid0, state
            resident = (slabs is not None
                        and seen_pairs + part[0].size <= RESIDENT_PAIR_CAP)
            slab = prep_slab(part, resident)
            state = dispatch(slab, cid0, bidx, epoch, state)
            if slabs is not None:
                slabs.append((slab, cid0, bidx))
            seen_pairs += part[0].size
            cid0 += slab[5].shape[0]

        def emit(bidx, blk_b, final):
            # NOTE: bucketed mode re-buffers blocks that corpus_pairs_slabs
            # already sized — one extra host memcpy per slab, accepted for
            # the opt-in path (it overlaps the async device dispatches)
            bufs[bidx].append(blk_b)
            buf_n[bidx] += blk_b[0].size
            while buf_n[bidx] >= PAIRS_PER_SLAB or (final and buf_n[bidx]):
                cat = tuple(np.concatenate([b[k] for b in bufs[bidx]])
                            for k in range(5))
                take = min(PAIRS_PER_SLAB, cat[0].size)
                bufs[bidx] = [tuple(a[take:] for a in cat)]
                buf_n[bidx] -= take
                record(tuple(a[:take] for a in cat), bidx)
                if final and buf_n[bidx] == 0:
                    break

        empty = tuple(np.empty(0, np.int32) for _ in range(4)) + (
            np.empty(0, np.int64),)
        for blk in blocks:
            if blk[0].size == 0:
                continue
            if len(bucket_l) == 1:
                # already exact-size slabs: dispatch directly, no rebuffer
                record(blk, 0)
            else:
                which = bucket_of(blk[0])
                for bidx in range(len(bucket_l)):
                    sel = which == bidx
                    if sel.any():
                        emit(bidx, tuple(a[sel] for a in blk),
                             final=False)
        for bidx in range(len(bucket_l)):
            if buf_n[bidx]:
                emit(bidx, empty, final=True)

    if pairs_iter_factory is not None:
        # pair_mode="exact": the pair set changes per epoch (host-side
        # window shrink, like the reference's per-epoch b draws), so
        # every epoch streams fresh — no replay cache
        for epoch in range(epochs):
            stream(pairs_iter_factory(epoch), epoch, None)
        syn0, syn1, neg_tab = state
        return (syn0, syn1,
                neg_tab if syn1neg is not None else None, None,
                kernel_used)

    if dev_cache is not None and dev_cache["bucket_l"] != bucket_l:
        raise ValueError(
            f"cached pair slabs were built for depth buckets "
            f"{dev_cache['bucket_l']} but the config now implies "
            f"{bucket_l}; refit with a fresh instance (or keep "
            f"depth_buckets stable across fits)")
    if dev_cache is None:
        if pairs_iter is None:
            if pairs is None:
                raise ValueError("need pairs, pairs_iter or dev_cache")

            def _slices():
                P = pairs[0].size
                for lo in range(0, P, PAIRS_PER_SLAB):
                    yield tuple(a[lo:lo + PAIRS_PER_SLAB] for a in pairs)

            pairs_iter = _slices()
        # epoch 0 streams; prepared slabs are cached for replay
        dev_cache = {"bucket_l": bucket_l, "slabs": []}
        stream(pairs_iter, 0, dev_cache["slabs"])
        first_epoch = 1
    else:
        first_epoch = 0
    for epoch in range(first_epoch, epochs):
        for slab, cid0, bidx in dev_cache["slabs"]:
            state = dispatch(slab, cid0, bidx, epoch, state)
    syn0, syn1, neg_tab = state
    return (syn0, syn1,
            neg_tab if syn1neg is not None else None, dev_cache,
            kernel_used)


def prepare_train_tables(cache, table_size: int):
    """Device-ready training tables from a built vocab: (codes_t,
    points_t, mask_t, unigram table, hs code lengths) — the Huffman
    hierarchical-softmax encoding plus the negative-sampling
    distribution.  One function, so a measurement made outside
    ``Word2Vec.fit`` times the EXACT tables training uses
    (InMemoryLookupTable syn1/expTable/negative-table construction role,
    InMemoryLookupTable.java:98-180)."""
    codes_np, points_np, lengths_t = encode_hs_tables(cache)
    mask_t = hs_mask_table(codes_np, lengths_t)
    return (jnp.asarray(codes_np), jnp.asarray(points_np), mask_t,
            jnp.asarray(unigram_table(cache, table_size)), lengths_t)


def hs_mask_table(codes_t: np.ndarray, lengths_t: np.ndarray) -> Array:
    """[V, L] float mask from per-word Huffman path lengths."""
    return jnp.asarray(
        (np.arange(codes_t.shape[1])[None, :] <
         np.asarray(lengths_t)[:, None]).astype(np.float32))


class Word2Vec:
    """fit() -> WordVectors.  API parity with Word2Vec.java's builder usage:
    Word2Vec(sentences, Word2VecConfig(...), tokenizer)."""

    def __init__(self, sentences: Iterable[str],
                 config: Optional[Word2VecConfig] = None,
                 tokenizer=None,
                 cache: Optional[VocabCache] = None):
        self.config = config or Word2VecConfig()
        self.tokenizer = tokenizer or DefaultTokenizerFactory()
        self.sentences = sentences
        self.cache = cache
        self.syn0: Optional[Array] = None
        self.syn1: Optional[Array] = None
        self.syn1neg: Optional[Array] = None
        self._wv: Optional[WordVectors] = None
        self._n_positions = 0       # corpus words (the lr-decay clock)
        self._dev_cache = None      # prepared pair slabs (see engine)
        self._indexed = None        # indexed corpus (exact/device modes)
        self._stream_cache = None   # uploaded token stream ("device")

    # -- vocab (buildVocab:257 parity) -------------------------------------
    def build_vocab(self) -> VocabCache:
        if self.cache is None:
            self.cache = build_vocab(self.sentences, self.tokenizer,
                                     self.config.min_word_frequency)
        if self.config.use_hs:
            build_huffman(self.cache)
        return self.cache

    def _index_sentences(self) -> List[np.ndarray]:
        """Tokenize + vocab-index the corpus; sets the lr-decay clock.

        Hot path of a cold fit (the whole corpus flows through it): one
        local dict lookup per token via ``map`` instead of a bound-method
        call + VocabWord attribute chase per token (~35% faster at the
        1M-word bench scale, where indexing is the largest host cost
        left in pair_mode="device")."""
        d = {w: vw.index for w, vw in self.cache.vocab.items()}
        get = d.get
        tok = self.tokenizer
        indexed: List[np.ndarray] = []
        n = 0
        for sent in self.sentences:
            arr = np.fromiter(
                (i for i in map(get, tok(sent)) if i is not None),
                np.int32)
            if arr.size:
                indexed.append(arr)
                n += arr.size
        self._n_positions = n
        return indexed

    def _reset_weights(self) -> None:
        """syn0 ~ U(-0.5, 0.5)/dim (InMemoryLookupTable:98-104)."""
        cfg = self.config
        V, D = len(self.cache), cfg.vector_size
        key = jax.random.key(cfg.seed)
        self.syn0 = (jax.random.uniform(key, (V, D)) - 0.5) / D
        self.syn1 = jnp.zeros((V, D))
        if cfg.negative > 0:
            self.syn1neg = jnp.zeros((V, D))

    def fit(self, initial_weights=None, mesh=None) -> WordVectors:
        """Train; ``initial_weights=(syn0, syn1, syn1neg|None)`` resumes
        from given tables instead of re-initializing — the hook the
        distributed performers use to absorb the current global state
        (scaleout word2vec job parity).  ``mesh`` (pair_mode="device"
        only): data-parallel training over the mesh's ``data`` axis with
        per-epoch parameter averaging — the reference's parallel
        word2vec (Word2Vec.java's trainSentence actor fan-out / Spark
        averaging) at chip scale."""
        cfg = self.config
        if cfg.kernel not in ("auto", "pallas", "xla"):
            raise ValueError(
                f"Word2VecConfig.kernel must be 'auto', 'pallas' or "
                f"'xla', got {cfg.kernel!r}")
        if cfg.pair_mode not in ("masked", "exact", "device"):
            raise ValueError(
                f"Word2VecConfig.pair_mode must be 'masked', 'exact' or "
                f"'device', got {cfg.pair_mode!r}")
        if not cfg.use_hs and cfg.negative <= 0:
            raise ValueError(
                "no training objective: enable use_hs and/or negative > 0")
        if mesh is not None and cfg.pair_mode != "device":
            raise ValueError(
                "fit(mesh=...) data-parallel training requires "
                f"pair_mode='device' (got {cfg.pair_mode!r})")
        self.build_vocab()
        if len(self.cache) == 0:
            raise ValueError("empty vocabulary")
        if initial_weights is not None:
            # jnp.array (copy), NOT asarray: the jitted steps donate their
            # table arguments, so a no-copy view of the caller's arrays
            # would be deleted by donation on the first step, corrupting
            # the state the caller warm-started from
            self.syn0, self.syn1, self.syn1neg = (
                jnp.array(initial_weights[0]),
                jnp.array(initial_weights[1]),
                None if initial_weights[2] is None
                else jnp.array(initial_weights[2]))
        else:
            self._reset_weights()
        codes_t, points_t, mask_t, table, lengths_t = prepare_train_tables(
            self.cache, cfg.table_size)
        counts = np.asarray([self.cache.vocab[w].count
                             for w in self.cache.index], np.float64)

        if cfg.negative > 0 and self.syn1neg is None:
            raise ValueError(
                "negative sampling enabled but no syn1neg table: pass "
                "initial_weights with a syn1neg entry (or None weights to "
                "initialize fresh)")
        # COLD fit: index sentences, then STREAM candidate-pair slabs —
        # epoch 0 trains each slab (async dispatch) while the host builds
        # the next.  pair_mode="masked" caches the prepared slabs so later
        # fits (and epochs 1+) replay them with zero host pair work;
        # pair_mode="exact" re-streams host-shrunk pairs every epoch.
        if cfg.pair_mode == "device":
            if self._indexed is None:
                self._indexed = self._index_sentences()
            (self.syn0, self.syn1, self.syn1neg, self._stream_cache,
             self.kernel_used) = run_stream_training(
                self.syn0, self.syn1, self.syn1neg, self._indexed,
                vocab_size=len(self.cache), dim=cfg.vector_size,
                epochs=cfg.epochs, codes_t=codes_t, points_t=points_t,
                mask_t=mask_t, table=table, window=cfg.window,
                alpha=cfg.alpha, min_alpha=cfg.min_alpha,
                use_hs=cfg.use_hs, negative=cfg.negative,
                batch_size=cfg.batch_size, kernel=cfg.kernel,
                seed=cfg.seed,
                stream_cache=getattr(self, "_stream_cache", None),
                mesh=mesh)
            self._wv = WordVectors(self.cache, self.syn0)
            return self._wv
        pairs_iter = factory = None
        if cfg.pair_mode == "exact":
            if self._indexed is None:
                self._indexed = self._index_sentences()
            indexed, w = self._indexed, cfg.window

            def factory(epoch):
                rng = np.random.RandomState(
                    (cfg.seed + 7919 * (epoch + 1)) % (2 ** 31 - 1))
                return corpus_pairs_slabs(indexed, w, PAIRS_PER_SLAB, rng)
        elif self._dev_cache is None:
            if self._indexed is None:
                self._indexed = self._index_sentences()
            pairs_iter = corpus_pairs_slabs(self._indexed,
                                            cfg.window, PAIRS_PER_SLAB)
        (self.syn0, self.syn1, self.syn1neg, self._dev_cache,
         self.kernel_used) = run_pair_training(
                self.syn0, self.syn1, self.syn1neg,
                vocab_size=len(self.cache), dim=cfg.vector_size,
                epochs=cfg.epochs, total_words=self._n_positions,
                codes_t=codes_t, points_t=points_t, mask_t=mask_t,
                table=table, window=cfg.window, alpha=cfg.alpha,
                min_alpha=cfg.min_alpha, use_hs=cfg.use_hs,
                negative=cfg.negative, batch_size=cfg.batch_size,
                kernel=cfg.kernel, seed=cfg.seed,
                dev_cache=self._dev_cache, pairs_iter=pairs_iter,
                pairs_iter_factory=factory,
                window_mask=cfg.pair_mode != "exact",
                hs_lengths=np.asarray(lengths_t),
                hs_weights=counts,
                depth_buckets=cfg.depth_buckets)
        self._wv = WordVectors(self.cache, self.syn0)
        return self._wv

    # -- query passthrough --------------------------------------------------
    @property
    def word_vectors(self) -> WordVectors:
        if self._wv is None:
            raise RuntimeError("call fit() first")
        return self._wv

    def similarity(self, a: str, b: str) -> float:
        return self.word_vectors.similarity(a, b)

    def words_nearest(self, word: str, top_n: int = 10):
        return self.word_vectors.words_nearest(word, top_n)
