"""GloVe — co-occurrence counting + AdaGrad weighted-least-squares fit.

Reference parity: ``models/glove/Glove.java:57`` (fit:106, parallel
minibatch loop :172-212), ``GloveWeightLookupTable.iterateSample`` (the
f(X) = (X/xMax)^0.75-weighted WLS update with per-row AdaGrad), and
``CoOccurrences.java`` (actor-parallel, disk-buffered counting).

TPU-native redesign:
- co-occurrence counting is a host-side hash accumulation (string work),
  emitted as COO triples (i, j, X_ij);
- training runs ONE dispatch per epoch: an on-device shuffle of the
  triples + a ``lax.scan`` over fixed-size chunks, each doing gathers of
  w/w~/b/b~ rows, the weighted-squared-error gradient, AdaGrad accumulator
  updates, and count-normalized scatter-adds (same stability treatment —
  and the same dispatch-latency restructure — as word2vec).
- the final embedding is w + w~ (standard GloVe practice).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.text import DefaultTokenizerFactory
from deeplearning4j_tpu.nlp.vocab import VocabCache, build_vocab
from deeplearning4j_tpu.nlp.word_vectors import WordVectors

Array = jax.Array


@dataclasses.dataclass
class GloveConfig:
    vector_size: int = 100
    window: int = 5
    min_word_frequency: int = 1
    alpha: float = 0.05          # AdaGrad master step
    x_max: float = 100.0
    weight_power: float = 0.75
    epochs: int = 5
    batch_size: int = 4096
    symmetric: bool = True
    seed: int = 13
    #: "auto" uses the VMEM-resident Pallas kernel on TPU when the
    #: tables fit (ops/pallas_glove); "pallas"/"xla" force a path
    #: ("pallas" off-TPU runs through the interpreter — tests)
    kernel: str = "auto"


def count_cooccurrences(sentences: Iterable[str], tokenizer,
                        cache: VocabCache, window: int = 5,
                        symmetric: bool = True
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """COO triples (rows, cols, counts); weight 1/d by distance d
    (standard GloVe counting; CoOccurrences.java equivalent).

    Vectorized: the per-(position, offset) python loop topped out around
    300k tokens/s; here each sentence contributes [n, W] index matrices
    and the (i, j) pairs are merged with one np.unique pass over packed
    i*V+j keys — the same host-throughput treatment as
    ``word2vec.corpus_pairs``."""
    V = max(1, len(cache))
    deltas = np.arange(1, window + 1)
    weights_d = (1.0 / deltas).astype(np.float32)
    merged_k = np.empty(0, np.int64)
    merged_v = np.empty(0, np.float32)
    keys_parts: list = []
    w_parts: list = []
    buffered = 0

    def collapse():
        """Fold the raw pair buffer into the running unique set — peak
        memory stays O(unique pairs + buffer cap), not O(total pairs)."""
        nonlocal merged_k, merged_v, keys_parts, w_parts, buffered
        keys = np.concatenate([merged_k] + keys_parts)
        ws = np.concatenate([merged_v] + w_parts)
        merged_k, inv = np.unique(keys, return_inverse=True)
        merged_v = np.zeros(merged_k.size, np.float32)
        np.add.at(merged_v, inv, ws)
        keys_parts, w_parts, buffered = [], [], 0

    for sent in sentences:
        idx = [cache.index_of(t) for t in tokenizer(sent)]
        idx = np.asarray([i for i in idx if i >= 0], np.int64)
        n = idx.size
        if n < 2:
            continue
        j = np.arange(n)[:, None] + deltas[None, :]          # [n, W]
        valid = j < n
        pi, di = np.nonzero(valid)
        a, b = idx[pi], idx[j[pi, di]]
        keys_parts.append(a * V + b)
        w_parts.append(weights_d[di])
        if symmetric:
            keys_parts.append(b * V + a)
            w_parts.append(weights_d[di])
        buffered += a.size * (2 if symmetric else 1)
        if buffered >= 4_000_000:
            collapse()
    if buffered or keys_parts:
        collapse()
    if merged_k.size == 0:
        return (np.empty(0, np.int32),) * 2 + (np.empty(0, np.float32),)
    return ((merged_k // V).astype(np.int32),
            (merged_k % V).astype(np.int32), merged_v)


def _glove_update(state, rows: Array, cols: Array, x: Array, mask: Array,
                  alpha: Array, x_max: float, power: float):
    """One batched AdaGrad WLS step on COO triples (plain function)."""
    w, wt, b, bt, gw, gwt, gb, gbt = state
    wi, wj = w[rows], wt[cols]                        # [B, D]
    diff = (jnp.einsum("bd,bd->b", wi, wj) + b[rows] + bt[cols]
            - jnp.log(jnp.maximum(x, 1e-12)))
    fx = jnp.minimum((x / x_max) ** power, 1.0)
    g = fx * diff * mask                              # [B]

    dwi = g[:, None] * wj
    dwj = g[:, None] * wi
    db = g

    def adagrad_scatter(table, gsq, idx, grad, hit):
        # count-normalized scatter (stability under duplicate rows)
        cnt = jnp.zeros(table.shape[0]).at[idx].add(hit, mode="drop")
        norm = jnp.maximum(cnt, 1.0)[idx]
        if grad.ndim == 2:
            norm = norm[:, None]
        grad = grad / norm
        gsq = gsq.at[idx].add(grad * grad, mode="drop")
        step = alpha * grad / jnp.sqrt(gsq[idx] + 1e-8)
        table = table.at[idx].add(-step, mode="drop")
        return table, gsq

    w, gw = adagrad_scatter(w, gw, rows, dwi, mask)
    wt, gwt = adagrad_scatter(wt, gwt, cols, dwj, mask)
    b, gb = adagrad_scatter(b, gb, rows, db, mask)
    bt, gbt = adagrad_scatter(bt, gbt, cols, db, mask)
    loss = 0.5 * jnp.sum(fx * diff * diff * mask) / jnp.maximum(
        jnp.sum(mask), 1.0)
    return (w, wt, b, bt, gw, gwt, gb, gbt), loss


def _glove_epoch_body(state, rows: Array, cols: Array, x: Array,
                      mask: Array, key: Array, epoch: Array, alpha: Array,
                      chunk0, *, x_max: float, power: float,
                      n_chunks: int, batch: int, pallas_block: int = 0,
                      pallas_interpret: bool = False):
    """Epoch core shared by the single-device jit and the dp shard_map:
    on-device shuffle of the COO triples (Glove.java's per-epoch example
    shuffle) + ``lax.scan`` over ``n_chunks`` fixed [batch] chunks
    STARTING at chunk ``chunk0`` of the permuted order (a dp shard
    passes its stripe offset; single-device passes 0).  Returns
    (state, (weighted loss sum, count sum)) so callers — or a psum
    across shards — can form the global mean."""
    perm = jax.random.permutation(jax.random.fold_in(key, epoch),
                                  rows.shape[0])

    if pallas_block > 0:
        from deeplearning4j_tpu.ops.pallas_glove import (apply_chunk,
                                                         fused_glove_chunk)
        # carry the EXTENDED layout across the epoch: wext = (w|b|1),
        # wtext = (wt|1|bt), gsq packed (gw|gb)/(gwt|gbt) — built once
        # here and split back once after the scan, not per chunk
        w, wt, b, bt, gw, gwt, gb, gbt = state
        V, D = w.shape
        ones = jnp.ones((V, 1), jnp.float32)
        ext = (jnp.concatenate([w, b[:, None], ones], axis=1),
               jnp.concatenate([wt, ones, bt[:, None]], axis=1),
               jnp.concatenate([gw, gb[:, None]], axis=1),
               jnp.concatenate([gwt, gbt[:, None]], axis=1))

        def body(st, i):
            wext, wtext, gext, gtext = st
            idx = jax.lax.dynamic_slice(perm, ((chunk0 + i) * batch,),
                                        (batch,))
            m = mask[idx]
            accw, accwt, ls = fused_glove_chunk(
                wext, wtext, rows[idx], cols[idx], x[idx], m,
                x_max=x_max, power=power, block=pallas_block,
                interpret=pallas_interpret)
            wb, gext = apply_chunk(wext[:, :D + 1], gext, accw, alpha)
            wtb, gtext = apply_chunk(
                jnp.concatenate([wtext[:, :D], wtext[:, D + 1:]],
                                axis=1), gtext, accwt, alpha)
            wext = jnp.concatenate([wb, ones], axis=1)
            wtext = jnp.concatenate([wtb[:, :D], ones, wtb[:, D:]],
                                    axis=1)
            loss = ls[0, 0] / jnp.maximum(ls[0, 1], 1.0)
            return (wext, wtext, gext, gtext), (loss, ls[0, 1])

        ext, (losses, cnts) = jax.lax.scan(body, ext,
                                           jnp.arange(n_chunks))
        wext, wtext, gext, gtext = ext
        state = (wext[:, :D], wtext[:, :D], wext[:, D], wtext[:, D + 1],
                 gext[:, :D], gtext[:, :D], gext[:, D], gtext[:, D])
    else:
        def body(st, i):
            idx = jax.lax.dynamic_slice(perm, ((chunk0 + i) * batch,),
                                        (batch,))
            m = mask[idx]
            st, loss = _glove_update(st, rows[idx], cols[idx], x[idx],
                                     m, alpha, x_max, power)
            return st, (loss, jnp.sum(m))

        state, (losses, cnts) = jax.lax.scan(body, state,
                                             jnp.arange(n_chunks))
    # weighted sums: chunk counts vary under the shuffle (and whole
    # chunks can be padding when n_chunks is bucketed up)
    return state, (jnp.sum(losses * cnts), jnp.sum(cnts))


@partial(jax.jit, donate_argnums=(0,),
         static_argnames=("x_max", "power", "n_chunks", "batch",
                          "pallas_block", "pallas_interpret"))
def _glove_scan_epoch(state, rows: Array, cols: Array, x: Array,
                      mask: Array, key: Array, epoch: Array, alpha: Array,
                      *, x_max: float, power: float, n_chunks: int,
                      batch: int, pallas_block: int = 0,
                      pallas_interpret: bool = False):
    """One dispatch per EPOCH (single-device path).  The eager per-chunk
    loop paid one dispatch per 4k triples; the scan removes that
    entirely (same restructure as word2vec's _scan_slab).
    Returns (state, mean loss)."""
    state, (ls, cs) = _glove_epoch_body(
        state, rows, cols, x, mask, key, epoch, alpha, jnp.int32(0),
        x_max=x_max, power=power, n_chunks=n_chunks, batch=batch,
        pallas_block=pallas_block, pallas_interpret=pallas_interpret)
    return state, ls / jnp.maximum(cs, 1.0)


def make_dp_glove_epoch(mesh, axis: str, n_shards: int, per: int, *,
                        x_max: float, power: float, batch: int,
                        pallas_block: int = 0,
                        pallas_interpret: bool = False,
                        average: bool = True):
    """Data-parallel GloVe epoch over a mesh ``axis``: every shard
    shuffles the SAME replicated COO triples (identical key -> identical
    permutation), trains its contiguous stripe of ``per`` chunks on its
    own table replica, and replicas are parameter-AVERAGED per epoch —
    the same Spark each-iteration-averaging semantics as word2vec's
    ``make_dp_stream_epoch`` (reference role: the spark glove job,
    models/embeddings/glove/Glove.java distributed fit).  AdaGrad
    accumulators average too (they are part of the replicated state).
    Loss is the count-weighted GLOBAL mean via psum.

    ``average=False`` skips the pmean — timing diagnostics only."""
    from deeplearning4j_tpu.compat import shard_map
    from jax.sharding import PartitionSpec as P

    rep = P()

    def shard_fn(state, rows, cols, x, mask, key, epoch, alpha):
        c0 = jax.lax.axis_index(axis) * per
        state, (ls, cs) = _glove_epoch_body(
            state, rows, cols, x, mask, key, epoch, alpha, c0,
            x_max=x_max, power=power, n_chunks=per, batch=batch,
            pallas_block=pallas_block, pallas_interpret=pallas_interpret)
        ls = jax.lax.psum(ls, axis)
        cs = jax.lax.psum(cs, axis)
        if average:
            state = tuple(jax.lax.pmean(t, axis) for t in state)
        return state, ls / jnp.maximum(cs, 1.0)

    f = shard_map(shard_fn, mesh=mesh, in_specs=(rep,) * 8,
                  out_specs=(rep, rep), check_vma=False)
    return jax.jit(f, donate_argnums=(0,))


class Glove:
    def __init__(self, sentences: Iterable[str],
                 config: Optional[GloveConfig] = None,
                 tokenizer=None, cache: Optional[VocabCache] = None):
        self.config = config or GloveConfig()
        self.tokenizer = tokenizer or DefaultTokenizerFactory()
        self.sentences = sentences
        self.cache = cache
        self._wv: Optional[WordVectors] = None
        self.state: Optional[Tuple] = None
        self.losses: list = []

    def fit(self, initial_weights: Optional[Tuple] = None,
            cooccurrences: Optional[Tuple[np.ndarray, np.ndarray,
                                          np.ndarray]] = None,
            mesh=None, data_axis: str = "data") -> WordVectors:
        """Train; ``initial_weights`` (an 8-tuple of w/w~/b/b~ tables plus
        their AdaGrad accumulators, as produced in ``self.state``) warm-
        starts from a previous or globally-averaged state — the hook the
        distributed GloVe performer uses (GlovePerformer.java parity).
        ``cooccurrences`` = precomputed (rows, cols, counts) COO triples;
        when given, the counting pass is skipped.

        With ``mesh`` (and >1 devices on ``data_axis``), each device
        trains a stripe of the shuffled triples on its own table replica
        and replicas are parameter-averaged per epoch
        (``make_dp_glove_epoch`` — the spark glove job's role)."""
        cfg = self.config
        if self.cache is None:
            self.cache = build_vocab(self.sentences, self.tokenizer,
                                     cfg.min_word_frequency)
        V, D = len(self.cache), cfg.vector_size
        if V == 0:
            raise ValueError("empty vocabulary")
        if cooccurrences is None:
            cooccurrences = count_cooccurrences(
                self.sentences, self.tokenizer, self.cache, cfg.window,
                cfg.symmetric)
        rows, cols, x = cooccurrences
        if rows.size == 0:
            raise ValueError("no co-occurrences")

        if initial_weights is not None:
            # jnp.array (copy), NOT asarray: _glove_scan_epoch donates its
            # state argument, so a no-copy view of the caller's arrays
            # would be deleted by donation on the first epoch, corrupting
            # the state tuple the caller warm-started from
            state = tuple(jnp.array(t) for t in initial_weights)
            if state[0].shape != (V, D):
                raise ValueError(
                    f"initial weights shaped {state[0].shape}, "
                    f"vocab expects {(V, D)}")
        else:
            key = jax.random.key(cfg.seed)
            k1, k2 = jax.random.split(key)
            init = lambda k: (jax.random.uniform(k, (V, D)) - 0.5) / D
            state = (init(k1), init(k2), jnp.zeros(V), jnp.zeros(V),
                     jnp.full((V, D), 1e-8), jnp.full((V, D), 1e-8),
                     jnp.full(V, 1e-8), jnp.full(V, 1e-8))

        # FIXED batch width + power-of-two chunk counts: the scanned
        # epoch specializes on (n_chunks, batch), and the distributed
        # performers re-fit shards of many different sizes — bucketing
        # bounds the distinct compilations at log2(P) instead of one per
        # shard size.
        B = cfg.batch_size
        P = rows.size
        n_shards = int(mesh.shape[data_axis]) if mesh is not None else 1
        NC = max(1, 1 << (-(-P // B) - 1).bit_length())
        # a dp mesh needs a chunk count divisible by the shard count
        # (word2vec.py's run_stream_training does the same): extra
        # chunks are fully-masked padding the weighted loss ignores
        NC = max(n_shards, -(-NC // n_shards) * n_shards)
        pad = NC * B - P
        if pad:
            rows = np.concatenate([rows, np.zeros(pad, np.int32)])
            cols = np.concatenate([cols, np.zeros(pad, np.int32)])
            x = np.concatenate([x, np.ones(pad, np.float32)])
        rows_d, cols_d = jnp.asarray(rows), jnp.asarray(cols)
        x_d = jnp.asarray(x)
        mask_d = jnp.asarray(np.arange(NC * B) < P, jnp.float32)
        from deeplearning4j_tpu.ops.kernel_select import choose_kernel
        from deeplearning4j_tpu.ops.pallas_glove import (choose_block,
                                                         probe_compile)
        #: resolved dispatch for this fit, with the reason — a refusal
        #: under auto carries Mosaic's own message (an explicit
        #: kernel="pallas" surfaces the compile error instead)
        self.kernel_used = choose_kernel(
            cfg.kernel,
            choose_block(V, D, B,
                         interpret=jax.devices()[0].platform != "tpu"),
            f"glove vocab {V} x dim {D} (batch {B})",
            lambda blk: probe_compile(blk, V, D))
        pallas_block = self.kernel_used.block
        pallas_interpret = self.kernel_used.interpret
        key = jax.random.key(cfg.seed)
        alpha = jnp.float32(cfg.alpha)
        if n_shards > 1:
            mesh_key = (tuple(d.id for d in mesh.devices.flat),
                        data_axis, n_shards, NC // n_shards, B)
            self._dp_fns = getattr(self, "_dp_fns", {})
            epoch_fn = self._dp_fns.get(mesh_key)
            if epoch_fn is None:
                epoch_fn = make_dp_glove_epoch(
                    mesh, data_axis, n_shards, NC // n_shards,
                    x_max=cfg.x_max, power=cfg.weight_power, batch=B,
                    pallas_block=pallas_block,
                    pallas_interpret=pallas_interpret)
                self._dp_fns[mesh_key] = epoch_fn
            for epoch in range(cfg.epochs):
                state, loss = epoch_fn(state, rows_d, cols_d, x_d,
                                       mask_d, key, jnp.int32(epoch),
                                       alpha)
                self.losses.append(float(loss))
        else:
            for epoch in range(cfg.epochs):
                state, loss = _glove_scan_epoch(
                    state, rows_d, cols_d, x_d, mask_d, key,
                    jnp.int32(epoch), alpha, x_max=cfg.x_max,
                    power=cfg.weight_power, n_chunks=NC, batch=B,
                    pallas_block=pallas_block,
                    pallas_interpret=pallas_interpret)
                self.losses.append(float(loss))
        self.state = state
        w, wt = state[0], state[1]
        self._wv = WordVectors(self.cache, w + wt)
        return self._wv

    @property
    def word_vectors(self) -> WordVectors:
        if self._wv is None:
            raise RuntimeError("call fit() first")
        return self._wv

    def similarity(self, a: str, b: str) -> float:
        return self.word_vectors.similarity(a, b)

    def words_nearest(self, word: str, top_n: int = 10):
        return self.word_vectors.words_nearest(word, top_n)
