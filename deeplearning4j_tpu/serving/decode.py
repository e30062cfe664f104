"""Continuous-batching autoregressive decode serving.

The PR 3 stack (`engine.py`/`batcher.py`) serves ONE-SHOT forwards:
each request is a single jitted dispatch and the cohort dissolves.
Autoregressive GPT traffic is a different shape — a request is a
SEQUENCE of dependent dispatches (one per token), so per-request
`generate()` calls serialize: every user waits behind every other
user's whole continuation, and the MXU runs at batch size 1.  The
serving half of Gemma-on-TPU (arXiv:2605.25645) and TensorFlow's
persistent-dataflow lesson (arXiv:1605.08695) both land on the same
recipe, implemented here:

- ``DecodeEngine`` owns ONE persistent pool of KV pages (see KV PAGES
  below) and ONE table of S slots (S = ``n_slots``, the sequences it
  runs at once, whatever their lengths), each with a host-side page
  table row.  A RUNG of the cache-length ladder (``buckets``, a T_max
  ladder like PR 3's batch ladder) is a compiled page-table WIDTH and
  nothing else: no rung owns a slot, a page or a dispatch.  ONE
  jitted, donated decode-step executable per (conf, width) — compiled
  through ``runtime/compile_cache.cached_jit`` — advances ALL occupied
  slots by one token per dispatch, taken at the narrowest width that
  covers the longest running slot.
- New requests JOIN the running batch: the prompt is prefilled into a
  free slot with the chunked dense prefill executable (matmul-bound
  slabs of SEVERAL pages a dispatch, ``DecodeEngine.prefill_rows``,
  written into the live pool) between two decode steps — nobody waits
  for a cohort to finish.  Finished
  sequences (EOS or token budget) free their slot and pages mid-flight
  and the next pending request takes them.
- ``ContinuousBatcher`` is the front-end: a background worker owns the
  engine, streams tokens back per request (``DecodeRequest`` handles),
  books time-to-first-token and per-token latency into
  ``runtime.metrics.decode_metrics``, and drains on close.

A replicated front-end with load-shedding lives in
``serving/router.py``.  Steady state is compile-free: ``warmup()``
pre-traces both executables at every width of the ladder, after which
any mix of prompt lengths, joins, and slot recycling dispatches only
cached programs (asserted by tier-1 tests and the telemetry gate).  The
worker/lock contract (engine driven by ONE thread, shared request
state mutated only under its Condition, no blocking wait under a held
lock) is machine-checked by jaxlint's concurrency family.

MODEL-SHARDED serving (the data×model tentpole's serving half): pass
``mesh=`` (a mesh with a ``model`` axis — ``Router.replicate(...,
model_degree=N)`` builds one per device group) and the engine pins
GSPMD shardings on both executables: params laid out per
``gpt.shard_specs`` (heads/MLP over ``model``, tied embedding over
vocab) and the page pool sharded over its HEAD axis
(``gpt.paged_specs``), so each chip holds only its heads' weights and
cache — a model bigger than one chip's HBM serves from a group of
chips, with per-chip param bytes ~1/model_degree of the replicated
layout.  The engine key grows ``mesh_signature`` so two groups (or a
sharded and a replicated engine) never share an executable.

SERVING TIER 2 — the per-chip-economics knobs (the quantized-serving
half of arXiv:2605.25645 + the int8 characterization of
arXiv:2309.08918):

- ``quantize="int8"|"bf16"``: post-training weight quantization
  (runtime/quantize.py) computed once at construction/``warmup()`` —
  per-channel int8 leaves with dequant fused INTO the jitted prefill/
  decode programs, so steady state streams int8 weight bytes from HBM.
  Quantized executables are NEW compile-cache entries (the engine key
  includes the mode); accuracy deltas are asserted by the tier-1
  numerics tests.
- ``kv_dtype="int8"``: KV pages stored int8 with per-token-row
  scales riding ``gpt.PagedKV`` — ~4x (fp32) / ~2x (bf16) the slots
  per chip at equal cache-length bucket (``kv_bytes_per_slot`` gauge).
- ``prefix_cache=``: a content-hashed :class:`PrefixCache` — requests
  sharing a chunk-aligned prompt prefix skip its re-prefill by copying
  cached KV pages into their slot's (``gpt.paged_write_pages``), the
  chunked-prefill substrate picking up at the first uncached chunk.
  Hits are BIT-exact vs cold prefill (the pages are exact copies) and
  never trace: the page read/write executables are pre-traced by
  ``warmup()`` like everything else.  The store assumes frozen params
  (the serving contract) — call ``clear()`` after a weight swap.

KV PAGES (``gpt.PagedKV``; the one storage scheme — the slab a slot
owned per rung, ``paged=False``, was removed in PR 30): ONE pool of
``KV_PAGE_TOKENS``-token pages, [L, P, C, NH*D], sized by ``n_pages``
(default: ``n_slots`` x the largest rung, + the trash page), donated
to every dispatch, and ONE host-side page table of ``n_slots`` rows as
wide as the largest rung.  A decode (or verify, or draft) dispatch is
handed the table's first ``w // C`` columns, ``w`` the narrowest rung
that covers the longest running slot, and reads, layer by layer, the
S x TBL pages those columns name — one layer's rows at a time, never
the pool, never an all-layer view — and writes each active slot's
fresh rows of that layer at (layer, page, offset), in place; an
inactive or stalled slot's rows go to the trash page 0.  A prefill
dispatch reads one slot's pages, at the width of the request's OWN
rung (``pick_bucket(prompt + max_tokens)``), and writes the pages of
its ``prefill_rows(rung)`` rows (a whole number of pages the engine
derives; the page width is not the dispatch width).
HBM holds what live tokens occupy, so admission counts free pages as
well as free slots, and a slot whose next page cannot be had STALLS a
dispatch instead of failing (:class:`KVPagesExhausted` only when
nothing can move).

KINDS OF PAGE (PR 32).  A family whose layers do not all keep a row
equally long declares the kinds of page its pool has
(``page_kinds(cfg, page_tokens)``; ``models/mellum.py``: ``full``, a
rung's worth a slot over the full-attention layers' slab, and
``window``, a ring of some ``sliding_window / C + 2`` pages a slot at
most over the sliding-window layers' slab).  The engine keeps an
allocator and a page table a kind (:class:`_PageKind`), admits on all
of them, grows and releases all of them, audits all of them, and hands
the family's two dispatches the tables in the declared order (the bare
array where there is one kind, as ever).  A kind's table row is a ring of its
``cap`` columns, the page of positions ``j C ..`` in column ``j %
cap``: a bounded kind REUSES a slot's oldest page for its newest rows,
in decode steps and between prefill chunks alike, with no allocator
call (the page's rows all lie further back than the window reads; a
prefill dispatch of such a family therefore carries no more pages than
the ring leaves room for, ``1 + ahead // C``: THE RING RULE,
:class:`_PageKind`); an unbounded kind's ``cap`` is the longest rung's
pages and never wraps.
One algorithm with the kinds as data: ``gpt`` and ``deepseek_v2``
declare none and run it with one unbounded kind.  A family with a
bounded kind mounts no prefix by reference (a later slot would be
handed pages since written over).

SLOT STATE (PR 39).  A family whose layers keep a fixed state a
sequence, not a row a token (``models/ling_hybrid.py``'s KDA layers: a
[heads, 128, 128] float32 matrix and a convolution's last rows),
declares it (``slot_state(cfg)``: the shapes of ONE slot's state) and
the engine holds a slab of ``n_slots`` of them beside the page pool:
the family's dispatches take the pool as ``(pages, state)``, donated
and threaded through every dispatch like the pages, so a step in
flight, a successor's join into its slot and the next step are ordered
by data.  A prefill dispatch is handed its slot's index (the family
zeroes the slot's state at ``start`` 0, a join's first dispatch:
``linear_state_resets``); a decode dispatch leaves a slot it does not
run as it was.  Such a family mounts no prefix (a hit would need the
state at the page edge).

SPECULATIVE ROUNDS.  ``advance_spec()`` is a round that commits more
than one token a slot.  With a SECOND MODEL as the draft
(``draft=(cfg_d, params_d)``, ``models/gpt.py`` only) the draft proposes
``draft_k`` tokens in one dispatch over a second pool behind the same
tables and the target verifies them in another.  With the model's OWN
block as the draft (``draft="self"``: a family that has
``paged_self_draft_round``, ``models/exaone_moe.py``'s MTP block) the
round is ONE dispatch: every slot feeds its current token and its
pending draft, the family verifies, commits and drafts again, and the
next drafts come back with the tokens in the one fetch; the draft
block's cache rows live in the family's own pool behind the slot's own
tables, and the join's prefill dispatches fill them.  Rows written
ahead of a slot's committed frontier into a bounded kind's ring are
held to THE RING RULE (:class:`_PageKind`).  Either way the committed
stream is, token for token, the plain ``advance()``'s: sampling keys
are of (seed, position), not of a step.  A self-draft round, like a
plain step, runs ONE AHEAD of its fetch under a ``ContinuousBatcher``
(:meth:`DecodeEngine.dispatch_round`): the next round takes its tokens,
positions and drafts from the round in flight on the device.
"""

from __future__ import annotations

import hashlib
import importlib
import itertools
import queue
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models import gpt
from deeplearning4j_tpu.parallel.mesh import (MODEL_AXIS, mesh_signature,
                                              model_degree)
from deeplearning4j_tpu.runtime import compile_cache, quantize as qz, telemetry
from deeplearning4j_tpu.runtime.metrics import decode_metrics

#: the most rows ONE prefill dispatch carries (``DecodeEngine
#: .prefill_rows``): a dispatch reads every weight once whatever its
#: rows, and bfloat16 weights on a v5e want some 240 rows before the
#: products cost what the read does (197 TFLOP/s over 819 GB/s).  One
#: value for every family, fixed from chip runs of the GPT-2 and
#: DeepSeek-V2 prefill cells at 128 and 256 (PERF.md section 6, PR 33).
PREFILL_ROWS_MAX = 256


def model_family(cfg):
    """The module under ``models/`` whose paged functions serve ``cfg``
    (``init_pages``, ``pages_bytes``, ``slots_bytes_per_slot``,
    ``paged_specs``, ``paged_prefill``, ``paged_decode``,
    ``paged_read_pages``, ``paged_write_pages``): the one a config names
    in its ``family``, ``models/gpt.py`` for a config that names none.
    A family may also state ``UNSUPPORTED_ENGINE_OPTIONS`` (engine
    options it has no code for: the engine raises instead of running
    another family's), ``DECODE_COUNTERS`` (names of the counts its
    ``paged_decode`` appends to the step's tokens), ``page_kinds(cfg,
    page_tokens)`` (the KINDS OF PAGE its pool has, each ``(name, the
    most pages a slot may hold or None for a rung's worth)`` and, for a
    bounded kind that takes speculative rows or several pages a prefill
    dispatch, the rows a dispatch may write ahead of the committed
    frontier: see :class:`_PageKind`; a family that states none has one
    kind),
    ``slot_state(cfg)`` (a state a slot holds, not pages: the SLOT
    STATE, whose ``paged_prefill`` also takes the slot's index),
    ``paged_self_draft_prefill`` / ``paged_self_draft_round`` /
    ``self_draft_depth(cfg)`` (its own draft block:
    ``DecodeEngine(draft="self")``) and
    ``COMPUTE_DTYPE_LEAVES`` (the leaves its steps read only through
    ``.astype(cfg.compute_dtype)``, each by its keys from the root:
    :func:`hold_in_compute_dtype`)."""
    name = getattr(cfg, "family", "gpt")
    return importlib.import_module(f"deeplearning4j_tpu.models.{name}")


def _leaf_at(tree: Any, keys: Sequence[Any]) -> Any:
    for k in keys:
        tree = tree[k]
    return tree


def _with_leaf(tree: Any, keys: Sequence[Any], leaf: Any) -> Any:
    """``tree`` (nested dicts) with the leaf at ``keys`` replaced: the
    dicts on the way copied, everything else the same objects."""
    if not keys:
        return leaf
    return {**tree, keys[0]: _with_leaf(tree[keys[0]], keys[1:], leaf)}


def hold_in_compute_dtype(cfg, tree: Any, shardings: Any = None,
                          label: str = "decode") -> Any:
    """``tree`` as a serving engine holds it: every leaf the family of
    ``cfg`` names in ``COMPUTE_DTYPE_LEAVES`` cast to
    ``cfg.compute_dtype`` by ONE jitted call on the device, so the
    steps' own ``.astype`` of it is no operation and no dispatch
    converts a weight again.  The values are the ones the steps would
    have computed (the same rounding of the same number, made once and
    kept), so every token is the one the raw tree gives.

    What the engine can observe decides, not an option: a named leaf
    already of that type (or not a floating array: a ``QTensor``),
    every leaf the family does not name, and the whole tree of a family
    that names none or of a float32 compute type are returned as the
    SAME objects, never copied.  ``shardings`` (the engine's
    ``NamedSharding`` tree under a mesh) lays the cast leaves out as
    their float32 originals.  Counted in
    ``decode_metrics.params_held_casts`` / ``params_held_bytes``."""
    cdt = jnp.dtype(cfg.compute_dtype)
    todo = {}
    for keys in getattr(model_family(cfg), "COMPUTE_DTYPE_LEAVES", ()):
        leaf = _leaf_at(tree, keys)
        if (hasattr(leaf, "dtype") and leaf.dtype != cdt
                and jnp.issubdtype(leaf.dtype, jnp.floating)):
            todo[keys] = leaf
    if not todo:
        return tree
    names = tuple(todo)
    lay: Dict[str, Any] = {}
    if shardings is not None:
        sh = [_leaf_at(shardings, keys) for keys in names]
        lay = dict(in_shardings=(sh,), out_shardings=sh)

    def cast(leaves):
        return [x.astype(cdt) for x in leaves]

    fn = compile_cache.cached_jit(
        cast, key=("hold_params", str(cdt), names,
                   tuple((mesh_signature(s.mesh), str(s.spec))
                         for s in lay.get("out_shardings", ()))),
        label=f"{label}.hold_params", **lay)
    with telemetry.span("decode.hold_params", leaves=len(names)):
        held = fn(list(todo.values()))
    decode_metrics.note_params_held(qz.tree_bytes(held))
    for keys, leaf in zip(names, held):
        tree = _with_leaf(tree, keys, leaf)
    return tree


#: tokens per KV page — ONE constant shared by the page allocator and
#: the PrefixCache's chunk alignment (== gpt.PREFILL_CHUNK, drift-guarded
#: by tests/test_serving_tier3.py): harvested prefix pages mount into
#: slots without re-chunking (a prefill dispatch writes several of them:
#: ``DecodeEngine.prefill_rows``)
KV_PAGE_TOKENS = gpt.PREFILL_CHUNK


class KVPagesExhausted(RuntimeError):
    """Typed page-pool exhaustion: an admit/extend needed more KV pages
    than the engine's pool has free.  Admission gates on
    ``DecodeEngine.can_admit`` and in-flight slots STALL (retry next
    dispatch) before this is raised; it reaches a request only when the
    pool cannot make progress at all (deadlock breaker evicts the
    youngest stalled slot) or a prompt alone exceeds the whole pool."""

    def __init__(self, needed: int, free: int, total: int,
                 slot: Optional[int] = None):
        super().__init__(
            f"KV page pool exhausted: need {needed} page(s), "
            f"{free} free of {total}")
        self.needed = needed
        self.free = free
        self.total = total
        self.slot = slot


class DeadlineExceeded(RuntimeError):
    """Typed per-request deadline expiry: the request's ``deadline_ms``
    budget elapsed while it was queued or mid-decode.  The batcher
    frees its slot and reclaims its KV pages the moment it expires —
    an expired request never occupies capacity a live one could use.
    Carries the partial stream length so clients can distinguish
    'never started' from 'cut off mid-continuation'."""

    def __init__(self, deadline_ms: float, elapsed_ms: float,
                 tokens_emitted: int):
        super().__init__(
            f"decode request deadline exceeded: {elapsed_ms:.1f}ms "
            f"elapsed of a {deadline_ms:.1f}ms budget "
            f"({tokens_emitted} token(s) emitted)")
        self.deadline_ms = deadline_ms
        self.elapsed_ms = elapsed_ms
        self.tokens_emitted = tokens_emitted


class PageAllocator:
    """Host-side refcounted free-list allocator over the engine's
    pool ids.  Page 0 is RESERVED (the trash page inactive-slot writes
    are redirected into) and never handed out.  ``alloc`` is
    all-or-nothing (typed :class:`KVPagesExhausted` on shortfall),
    ``share`` bumps refcounts for by-reference prefix mounts, ``free``
    releases one reference and reclaims the page at zero — a shared
    prefix page outlives the slot that harvested it.  Not thread-safe
    on its own: exactly the engine's driver thread mutates it (the
    engine's single-thread contract)."""

    def __init__(self, n_pages: int, n_reserved: int = 1):
        if n_pages <= n_reserved:
            raise ValueError(
                f"n_pages must exceed the {n_reserved} reserved page(s): "
                f"{n_pages}")
        self.n_pages = int(n_pages)
        self.n_reserved = int(n_reserved)
        self._free: List[int] = list(range(n_pages - 1, n_reserved - 1, -1))
        self._refs: Dict[int, int] = {}

    def n_free(self) -> int:
        return len(self._free)

    def in_use(self) -> int:
        return self.n_pages - self.n_reserved - len(self._free)

    def alloc(self, n: int) -> List[int]:
        if n < 0:
            raise ValueError(f"alloc count must be >= 0: {n}")
        if n > len(self._free):
            raise KVPagesExhausted(n, len(self._free), self.n_pages)
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._refs[p] = 1
        return out

    def share(self, pids: Sequence[int]) -> None:
        for p in pids:
            if p not in self._refs:
                raise ValueError(f"page {p} is not allocated")
            self._refs[p] += 1

    def free(self, pids: Sequence[int]) -> None:
        for p in pids:
            refs = self._refs.get(p)
            if refs is None:
                raise ValueError(f"page {p} is not allocated")
            if refs == 1:
                del self._refs[p]
                self._free.append(p)
            else:
                self._refs[p] = refs - 1

    def refcount(self, pid: int) -> int:
        return self._refs.get(pid, 0)

    def total_refs(self) -> int:
        """Sum of all outstanding page references — the leak-audit
        numerator: every live slot's table entries plus every resident
        -registry registration should account for exactly this many."""
        return sum(self._refs.values())


class AllocatorSet:
    """The allocators of an engine whose pool has several kinds of page,
    read as one: what ``DecodeEngine._alloc`` is for such an engine, so
    that an audit that asks it for ``in_use()`` counts pages of EVERY
    kind.  (An engine with one kind keeps the bare
    :class:`PageAllocator` there.)"""

    def __init__(self, allocators: Sequence[PageAllocator]):
        self.allocators = tuple(allocators)

    def in_use(self) -> int:
        return sum(a.in_use() for a in self.allocators)

    def total_refs(self) -> int:
        return sum(a.total_refs() for a in self.allocators)


class _PageKind:
    """One KIND OF PAGE of an engine's pool: pages of one slab of the
    family's pool (``gpt`` and ``deepseek_v2`` have one slab and one
    kind; ``mellum`` a slab for its full-attention layers and one for
    its sliding-window layers), with an allocator and a host page table
    of their own.  ``cap`` is the most pages a slot may hold of the
    kind: a rung's worth where the family gives no bound, else the
    bound (a sliding-window layer never reads further back than its
    window).  A slot's table row is a RING of ``cap`` columns: the page
    that holds positions ``j * C .. j * C + C - 1`` sits in column
    ``j % cap``, so a sequence that outgrows ``cap`` pages writes its
    newest rows over its oldest page, and a kind without a bound
    (``cap`` columns cover the longest rung) never wraps: the one
    table of PR 31.

    THE RING RULE.  A speculative round writes ``k + 1`` rows a slot,
    ``k`` of them AHEAD of the slot's committed frontier ``p``, and the
    page a row at ``p + a`` opens lies over the page ``cap`` before it.
    That page must hold no row the frontier still reads, so a bounded
    kind states ``ahead``, the most rows past the frontier a dispatch
    may write into its ring (what its ``cap`` pages leave over the rows
    a layer of the kind reads back; 0 where the family states none), and
    the engine refuses ``k > ahead`` at construction.  Inside that bound
    a rejected draft's row harms nothing: the slot's next round writes
    the committed token's row over it, and no row attends it before,
    because every mask is by position.  A PREFILL dispatch is held to
    the same rule: it starts page-aligned at the frontier and its
    ``m``-th page opens ``(m - 1) C`` rows past it, so it carries at
    most ``1 + ahead // C`` pages (``DecodeEngine.prefill_rows``)."""

    __slots__ = ("name", "bounded", "cap", "ahead", "alloc", "ptab",
                 "n_pages")

    def __init__(self, name: str, bound: Optional[int], table_pages: int,
                 n_slots: int, n_pages: Optional[int], ahead: int = 0):
        self.name = name
        self.bounded = bound is not None
        self.cap = min(bound, table_pages) if self.bounded else table_pages
        self.ahead = int(ahead)
        size = n_slots * self.cap + 1               # + the trash page
        if n_pages and not (self.bounded and n_pages > size):
            size = int(n_pages)
        self.alloc = PageAllocator(size)
        # trash-id 0 in unused entries
        self.ptab = np.zeros((n_slots, self.cap), np.int32)
        self.n_pages = np.zeros((n_slots,), np.int32)


def _bare(per_kind: Sequence[Any]) -> Any:
    """What a family is handed of a thing the engine keeps per kind of
    page (a table, a page count): the thing itself where there is one
    kind, the tuple in the family's declared order where several."""
    return per_kind[0] if len(per_kind) == 1 else tuple(per_kind)


def default_length_buckets(max_len: int, min_bucket: int = 32
                           ) -> Tuple[int, ...]:
    """Powers-of-two cache-length ladder up to (and including)
    ``max_len`` — same compile-bounding idea as the batch-size ladder in
    serving/engine.py, but over sequence capacity."""
    if max_len < 1:
        raise ValueError(f"max_len must be >= 1: {max_len}")
    ladder = [min(min_bucket, max_len)]
    while ladder[-1] < max_len:
        ladder.append(min(ladder[-1] * 2, max_len))
    return tuple(ladder)


class _PrefixEntry:
    """One stored prefix: its exact tokens, the KV *space* that
    produced the pages (model conf + quantization modes — pages from
    one space must never serve another), the host KV pages
    ([L, m, NH, D] k/v — int8 plus [L, m] scales for a quantized
    cache), and the alias keys registered for its chunk boundaries."""

    __slots__ = ("tokens", "space", "pages", "nbytes", "alias_keys")

    def __init__(self, tokens: np.ndarray, space: Any,
                 pages: Tuple[np.ndarray, ...]):
        self.tokens = tokens
        self.space = space
        # own the page memory: callers hand in SLICES of full
        # bucket-length device fetches, and a stored view would retain
        # the whole base array while nbytes accounted only the slice —
        # max_bytes would bound a fiction
        self.pages = tuple(np.array(p, copy=True) for p in pages)
        self.nbytes = int(tokens.nbytes
                          + sum(p.nbytes for p in self.pages))
        self.alias_keys: List[bytes] = []


class PrefixCache:
    """Content-hashed store of chunk-aligned prompt-prefix KV pages.

    Requests sharing a prompt prefix (system prompts, few-shot headers,
    conversation history) re-run the same prefill matmuls today; this
    store keeps the resulting KV rows host-side so a later request
    copies them into its slot and prefills only its tail.  Design
    points:

    - keys are SHA-1 digests of the KV *space* (the engine's model
      conf + quantize/kv_dtype — an int8 engine's pages must never
      serve a full-precision engine sharing the store) plus the exact
      prefix token bytes at every prefill-chunk boundary; a digest
      match is verified against the stored tokens AND space before
      use, so a collision can cost a miss, never a wrong hit;
    - entries are stored once under their longest chunk-aligned prefix
      with alias keys for every shorter boundary — a request sharing
      only the first k chunks of a longer stored prompt still hits
      (the page arrays are sliced views, no copy until the hit);
    - LRU-evicted under ``max_bytes``; thread-safe, and shareable
      across engine replicas of the same model (the pages are
      placement-free host arrays — ``Router``/autoscaling replicas
      warm each other);
    - the pages are EXACT copies of what prefill wrote (int8 payload +
      scales copy bit-for-bit), so a hit's continuation is bit-exact vs
      the cold prefill — asserted tier-1.

    Invalidation is the caller's contract: pages are only valid for the
    params that produced them — ``clear()`` on any weight swap.
    """

    def __init__(self, max_bytes: int = 256 << 20):
        if max_bytes < 1:
            raise ValueError(f"max_bytes must be >= 1: {max_bytes}")
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[bytes, _PrefixEntry]" = OrderedDict()
        # boundary digest -> {entry key: covered length}: a MULTIMAP,
        # because several entries can cover the same boundary (same
        # first chunks, different continuations) — evicting one must
        # not lose the boundary for the survivors
        self._alias: Dict[bytes, "OrderedDict[bytes, int]"] = {}
        self._bytes = 0

    @staticmethod
    def _boundary_digests(tokens: np.ndarray, chunk: int, n: int,
                          space: Any) -> List[bytes]:
        """Digests of ``tokens[:k*chunk]`` for k=1..n, computed with ONE
        incremental hasher (sha1 ``digest()`` is non-destructive) — a
        long prompt hashes its bytes once, not once per boundary, and
        ``repr(space)`` renders once per call instead of per rung."""
        tokens = np.ascontiguousarray(tokens, np.int32)
        hasher = hashlib.sha1(repr(space).encode() + b"\x00")
        out = []
        for k in range(1, n + 1):
            hasher.update(tokens[(k - 1) * chunk:k * chunk].tobytes())
            out.append(hasher.digest())
        return out

    def lookup(self, prompt: np.ndarray, chunk: int, space: Any = None
               ) -> Optional[Tuple[int, Tuple[np.ndarray, ...]]]:
        """Longest stored chunk-aligned STRICT prefix of ``prompt`` in
        ``space`` (at least one chunk always remains to prefill — it
        produces the first-token logits).  Returns (length, pages) or
        None."""
        prompt = np.asarray(prompt, np.int32)
        digs = self._boundary_digests(prompt, chunk,
                                      (prompt.size - 1) // chunk, space)
        for k in range(len(digs), 0, -1):
            m = k * chunk
            h = digs[k - 1]
            with self._lock:
                refs = self._alias.get(h)
                if not refs:
                    continue
                for full_key in reversed(list(refs)):   # newest first
                    e = self._entries.get(full_key)
                    if (e is None or refs[full_key] != m
                            or e.space != space
                            or e.tokens.size < m
                            or not np.array_equal(e.tokens[:m],
                                                  prompt[:m])):
                        continue
                    self._entries.move_to_end(full_key)
                    return m, tuple(p[:, :m] for p in e.pages)
        return None

    def insert(self, prefix: np.ndarray, pages: Tuple[np.ndarray, ...],
               chunk: int, space: Any = None) -> bool:
        """Store ``pages`` for ``prefix`` (length a chunk multiple) in
        ``space`` and register alias keys at every chunk boundary.
        Returns False when the exact prefix is already stored or it
        alone exceeds ``max_bytes``."""
        prefix = np.ascontiguousarray(prefix, np.int32)
        m = prefix.size
        if m < chunk or m % chunk:
            raise ValueError(
                f"prefix length {m} is not a positive multiple of the "
                f"prefill chunk {chunk}")
        entry = _PrefixEntry(prefix, space, pages)
        if entry.nbytes > self.max_bytes:
            return False
        digs = self._boundary_digests(prefix, chunk, m // chunk, space)
        full_key = digs[-1]
        with self._lock:
            if full_key in self._entries:
                return False
            while self._bytes + entry.nbytes > self.max_bytes \
                    and self._entries:
                evicted_key, old = self._entries.popitem(last=False)
                for a in old.alias_keys:
                    refs = self._alias.get(a)
                    if refs is not None:
                        refs.pop(evicted_key, None)
                        if not refs:
                            del self._alias[a]
                self._bytes -= old.nbytes
            self._entries[full_key] = entry
            self._bytes += entry.nbytes
            for k in range(1, m // chunk + 1):
                h = digs[k - 1]
                refs = self._alias.setdefault(h, OrderedDict())
                refs[full_key] = k * chunk
                refs.move_to_end(full_key)      # newest registrant wins
                entry.alias_keys.append(h)
        return True

    def clear(self) -> None:
        """Drop every entry — REQUIRED after any weight update: pages
        are only valid for the params that produced them."""
        with self._lock:
            self._entries.clear()
            self._alias.clear()
            self._bytes = 0

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"entries": len(self._entries), "bytes": self._bytes}


class _SlotTable:
    """Host-side state of an engine's ``n_slots`` slots, ONE table for
    every length: the occupancy/sampling arrays the decode dispatch
    takes each step (the page tables are :class:`_PageKind`'s; the pool
    is the only DEVICE state, and the engine's).  A rung of the ladder
    owns nothing here; a slot remembers the rung of the request it
    holds (``rung``: the width its prefill ran at and the most its page
    rows may grow to)."""

    __slots__ = ("active", "temps", "seeds", "owners", "rung",
                 "tokens_h", "pos_h", "ran", "epoch", "released_at")

    def __init__(self, n_slots: int):
        self.active = np.zeros((n_slots,), np.bool_)
        self.temps = np.zeros((n_slots,), np.float32)
        self.seeds = np.zeros((n_slots,), np.uint32)
        self.owners: List[Any] = [None] * n_slots
        self.rung = np.zeros((n_slots,), np.int32)
        # host mirrors of tokens/pos.  ``pos_h`` is a COUNT: a plain
        # step moves every slot that ran by one, at dispatch, whatever
        # its token turns out to be, so the next dispatch's pages, width
        # and positions never wait for a fetch.  ``tokens_h`` is the
        # fetched stream: it lags a step that is still uncollected
        # (:class:`_Step`), whose tokens the next dispatch then takes ON
        # THE DEVICE, and feeds only the slots that step did not run (a
        # slot that joined since, a slot that stalled on pages).
        # ``ran`` is the last dispatch's progress mask (a slot stalls
        # when its next page cannot be allocated)
        self.ran = np.zeros((n_slots,), np.bool_)
        self.tokens_h = np.zeros((n_slots,), np.int32)
        self.pos_h = np.zeros((n_slots,), np.int32)
        # bumped when a slot is started or released: a step in flight
        # knows by it whether a slot still holds the sequence it ran
        self.epoch = np.zeros((n_slots,), np.int64)
        # ``time.perf_counter()`` of a slot's last release (0: never
        # released): where its vacancy starts
        self.released_at = np.zeros((n_slots,), np.float64)


class _Step:
    """One plain decode dispatch on its way: what
    :meth:`DecodeEngine.dispatch_step` hands back and
    :meth:`DecodeEngine.collect` takes.  ``out`` is the step's output
    ON THE DEVICE (the ``[S]`` tokens, a family's counters behind them;
    its copy to the host was asked for at dispatch), ``run`` the slots
    it moved, ``epoch`` each slot's occupancy stamp then, ``pos`` the
    positions it fed, ``w`` / ``rungs`` its table width and the rungs it
    carried, ``pages`` the pages in use and the rows live behind it.
    ``after`` is the uncollected step whose tokens it took on the device
    (None: all from the host mirror), kept until this step is collected:
    a step may run ONE ahead of the fetch, never two."""

    __slots__ = ("out", "run", "epoch", "pos", "w", "rungs", "pages",
                 "after", "collected")

    def __init__(self, out, run, epoch, pos, w, rungs, pages, after):
        self.out = out
        self.run = run
        self.epoch = epoch
        self.pos = pos
        self.w = w
        self.rungs = rungs
        self.pages = pages
        self.after = after
        self.collected = False


class _Round:
    """One self-draft speculative round on its way: what
    :meth:`DecodeEngine.dispatch_round` hands back and
    :meth:`DecodeEngine.collect_round` takes.  ``out`` is the round's
    flat output ON THE DEVICE, ``run`` the slots it ran, ``epoch`` each
    slot's occupancy stamp then, ``pos`` the host's positions at
    dispatch and ``fed`` the positions the round was handed (``pos``
    itself, or a device array where slots took theirs from ``after``:
    the mask ``taken``), ``w`` / ``rungs`` its table width and the rungs
    it carried.  ``after`` is the uncollected round it took its tokens,
    positions and drafts from, kept until this round is collected: a
    round may run ONE ahead of the fetch, never two.  Collecting sets
    ``at``, the positions the round wrote at, and ``n_c``, its commit
    counts: the round behind it reads both at its own collect."""

    __slots__ = ("out", "run", "epoch", "pos", "fed", "taken", "w", "rungs",
                 "after", "collected", "at", "n_c")

    def __init__(self, out, run, epoch, pos, fed, taken, w, rungs, after):
        self.out = out
        self.run = run
        self.epoch = epoch
        self.pos = pos
        self.fed = fed
        self.taken = taken
        self.w = w
        self.rungs = rungs
        self.after = after
        self.collected = False
        self.at = self.n_c = None


class DecodeEngine:
    """Slot-structured, page-pooled KV-cache decode engine for a causal
    LM: ONE table of ``n_slots`` slots, the sequences it runs at once
    over ALL lengths (``n_slots`` bounds the engine, not a rung), and
    ONE decode dispatch for all of them.  ``buckets`` is the ladder of
    page-table WIDTHS a program is compiled for, no more: ``start()``
    prefills a request at the width of its own rung
    (``pick_bucket(prompt + max_tokens)``), ``advance()`` dispatches
    every running slot at the narrowest rung that covers the longest of
    them, read off the live positions and no option.  The model family
    is an argument, not an import: the engine
    takes its pool and its two dispatches from the family of ``cfg``
    (:func:`model_family`: ``models/gpt.py``, ``models/deepseek_v2.py``,
    ``models/mellum.py``, ``models/exaone_moe.py``)
    and holds, for each ``params`` tree it is given, the tree its
    executables take (``current_params()``), made once per tree: the
    leaves the family names in ``COMPUTE_DTYPE_LEAVES`` (those its steps
    read only through ``.astype(cfg.compute_dtype)``: GPT's six block
    matrices, none of DeepSeek-V2's) cast to that type by one jitted
    call (:func:`hold_in_compute_dtype`), every other leaf the given
    array.  A leaf already of the compute type is never copied, so a
    tree that arrives in it, or a float32 compute type, is held as
    given; float32 masters under a bfloat16 compute type cost half
    their named leaves' bytes again on the device for as long as the
    caller keeps its own tree (the engine drops its reference to a
    static one).  The draft's tree is held the same way by its own
    config.  ``quantize`` takes this step's place where it is set.  The
    mesh, quantization, int8 pools, a second model as the draft and the
    prefix store are ``models/gpt.py``'s; a family
    that has none of them says so and the engine raises
    (``draft="self"`` is a family's own draft block:
    ``models/exaone_moe.py``; ``draft_k`` defaults to 4 proposals of a
    second model, to what the family's own blocks draft for "self").  NOT
    thread-safe: exactly one thread (normally the
    ``ContinuousBatcher`` worker) may drive ``start``/``advance``/
    ``release``; construction and ``warmup()`` happen before serving.

    ``params`` may be the pytree or a zero-arg callable returning it
    (live-params convention shared with ``InferenceEngine``).  Both the
    prefill and the decode executables are built through the module
    compile engine with the page pool DONATED, so the cache updates in
    place (no 2x HBM) and identically-configured replicas share one
    compile per width.  ``n_pages`` sizes the pool (default: room for
    ``n_slots`` sequences of the largest bucket, + the trash page; where
    the family declares several kinds of page, every kind, a bounded
    one never past ``n_slots`` rings);
    ``prefill_chunk`` is the PAGE WIDTH (``page_tokens``: shrunk to the
    largest width that divides every rung), and no longer the rows of a
    prefill dispatch, which the engine derives a rung
    (:meth:`prefill_rows`); the keyword keeps its name until the
    benchmark's callers can be edited;
    ``paged`` selects nothing — ``True`` is the only value, kept until
    the benchmark's callers stop passing it.

    Tier-2 knobs (see the module docstring): ``quantize`` post-training
    weight quantization (``"int8"``/``"bf16"``, computed once per
    distinct params tree and memoized), ``kv_dtype="int8"`` for the
    quantized KV cache, ``prefix_cache`` (True for a private store, or
    a shared :class:`PrefixCache` instance so replicas warm each
    other).  Each knob keys its own compile-cache entries; a quantized
    engine never shares an executable with a full-precision one.
    """

    def __init__(self, cfg, params: Any, *, n_slots: int = 8,
                 buckets: Optional[Sequence[int]] = None,
                 prefill_chunk: int = gpt.PREFILL_CHUNK,
                 label: str = "decode", mesh=None,
                 quantize: Optional[str] = None,
                 kv_dtype: Optional[str] = None,
                 prefix_cache: Any = None,
                 paged: bool = True, n_pages: Optional[int] = None,
                 draft: Any = None,
                 draft_k: Optional[int] = None):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1: {n_slots}")
        self.cfg = cfg
        self._params = params
        self.mesh = mesh
        self.n_slots = int(n_slots)
        if not paged:
            raise ValueError(
                "DecodeEngine(paged=False): the pinned slot engine was "
                "removed in PR 30; every engine keeps its KV in the page "
                "pool (n_pages sizes it)")
        fam = self._family = model_family(cfg)
        fam_name = fam.__name__.rsplit(".", 1)[-1]
        asked = {"mesh": mesh is not None,
                 "kv_dtype": kv_dtype is not None,
                 "quantize": quantize is not None,
                 "draft": draft is not None,
                 "prefix_cache": bool(prefix_cache)}
        for option in getattr(fam, "UNSUPPORTED_ENGINE_OPTIONS", ()):
            if asked[option]:
                raise ValueError(
                    f"DecodeEngine option {option!r} is not supported "
                    f"for the {fam_name} family "
                    f"({type(cfg).__name__})")
        #: names of the counts this family's decode step returns behind
        #: its tokens (``decode_metrics.note_family_counts``)
        self._decode_counters = tuple(getattr(fam, "DECODE_COUNTERS", ()))
        self.draft = draft
        #: the draft is the model's OWN block (``draft="self"``: a family
        #: with ``paged_self_draft_round``), not a second model: proposal
        #: and verify ride in the one dispatch a round makes
        self._self_draft = isinstance(draft, str)
        if self._self_draft:
            if draft != "self" or not hasattr(fam, "paged_self_draft_round"):
                raise ValueError(
                    f"DecodeEngine(draft={draft!r}): the {fam_name} family "
                    f"has no draft of its own; pass (draft_cfg, "
                    f"draft_params)")
            depth = int(fam.self_draft_depth(cfg))
            draft_k = depth if draft_k is None else draft_k
            if not 1 <= draft_k <= depth:
                raise ValueError(
                    f"draft_k must be 1..{depth}, the tokens "
                    f"{type(cfg).__name__}'s own blocks draft a round: "
                    f"{draft_k}")
        elif draft is not None and fam is not gpt:
            raise ValueError(
                f"the {fam_name} family takes no second model as its "
                f"draft: pass draft=\"self\"")
        self.draft_k = int(4 if draft_k is None else draft_k)
        if draft is not None and self.draft_k < 1:
            raise ValueError(f"draft_k must be >= 1: {draft_k}")
        #: graceful-brownout knobs (the AutoscalingRouter pressure
        #: ladder flips them): plain bools, written by the router
        #: thread and read by the batcher worker each pass — a torn
        #: read costs at most one pass at the old setting, and both
        #: settings are CORRECT (spec-off and harvest-off change cost,
        #: never tokens), so no lock is needed
        self.spec_enabled = True
        self.harvest_enabled = True
        self.quantize = qz.check_mode(quantize)
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"kv_dtype must be None or 'int8': {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        if prefix_cache is True:
            prefix_cache = PrefixCache()
        self._prefix: Optional[PrefixCache] = prefix_cache or None
        # the KV space the engine's pages live in: a store shared
        # across replicas only serves hits between engines whose pages
        # are interchangeable (same conf, same quantization modes, same
        # params GENERATION — rebind_params bumps the generation, so a
        # freshly-swapped replica can never hit pages an old-params
        # replica harvested into the shared store mid-swap)
        self._params_gen = 0
        self._prefix_space = (repr(cfg), quantize, kv_dtype, 0)
        self._held_memo = qz.QuantMemo()
        self._static_held = False
        self.prefill_chunk = int(prefill_chunk)
        self.buckets = tuple(sorted(set(
            buckets if buckets is not None
            else default_length_buckets(cfg.max_len))))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"bad bucket ladder: {self.buckets}")
        if self.buckets[-1] > cfg.max_len:
            raise ValueError(
                f"bucket {self.buckets[-1]} exceeds the model's "
                f"max_len {cfg.max_len}")
        # pages are written at page-aligned offsets, so every rung must
        # be a whole number of pages or the last page of a near-full
        # prompt would fall off the table's end.  The width is a perf
        # knob, not a semantic one: shrink it to the largest width
        # dividing every rung (>= 1 always works) rather than reject
        # ladders like (32, 48) that max_len and default_length_buckets
        # legitimately produce.
        import math
        chunk = min(self.prefill_chunk, self.buckets[0])
        for t in self.buckets:
            chunk = math.gcd(chunk, t)
        if chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1: {self.prefill_chunk}")
        self.prefill_chunk = chunk
        self.label = label
        # page geometry: the page width is the (gcd-shrunk) value of
        # the ``prefill_chunk`` keyword; pools, tables, allocators and
        # prefix alignment are per page.  How many pages ONE prefill
        # dispatch carries is derived below (``_prefill_rows``), not
        # given.  The pool defaults to room for n_slots sequences of
        # the largest bucket (+ the trash page); pass n_pages to shrink
        # it — bounding HBM by live tokens is the point of the knob.
        self.page_tokens = chunk
        # the kinds of page the family's pool has (one where it states
        # none), each with its allocator and its table: ``n_pages``
        # sizes every kind, a bounded one never past its default
        declared = getattr(fam, "page_kinds", None)
        self._kinds: Tuple[_PageKind, ...] = tuple(
            _PageKind(name, bound, self.buckets[-1] // chunk, self.n_slots,
                      n_pages, *ahead)
            for name, bound, *ahead in (declared(cfg, chunk) if declared
                                        else (("kv", None),)))
        if draft is not None:
            # the ring rule (:class:`_PageKind`)
            for k in self._kinds:
                if k.bounded and self.draft_k > k.ahead:
                    raise ValueError(
                        f"draft_k={self.draft_k} rows ahead of the "
                        f"committed frontier do not fit the {k.name!r} "
                        f"kind's ring of {k.cap} page(s) of {chunk}: a "
                        f"round may write {k.ahead} ahead")
        #: names of the kinds a family DECLARED (their counters and span
        #: attributes are noted; a one-kind family's round notes nothing
        #: it did not before)
        self._kind_names = tuple(k.name for k in self._kinds
                                 ) if declared else ()
        #: a state a slot holds beside the pages (the SLOT STATE), one
        #: slot's leaves; None where the family declares none
        declared_state = getattr(fam, "slot_state", None)
        self._state_spec = declared_state(cfg) if declared_state else None
        #: bytes of the slot-state slab, ``n_slots`` slots' worth
        self.state_bytes = self.n_slots * sum(
            int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
            for s in jax.tree.leaves(self._state_spec))
        #: the slots whose state a join has started and no release ended
        self._state_held = np.zeros((self.n_slots,), np.bool_)
        #: pages of the pool: an int, or a tuple in the declared order
        self.n_kv_pages = _bare([k.alloc.n_pages for k in self._kinds])
        allocs = [k.alloc for k in self._kinds]
        self._alloc = allocs[0] if len(allocs) == 1 else AllocatorSet(allocs)
        self._pool = None
        self._dpool = None
        # prefixes are mounted BY REFERENCE only where a page, once
        # written, keeps its rows for as long as it is referenced: a
        # bounded kind's ring writes a slot's newest rows over its
        # oldest page, so a family with one registers and looks up
        # nothing (and what a mount copies is a row of ONE table)
        self._mounts_prefixes = (len(self._kinds) == 1
                                 and not self._kinds[0].bounded
                                 and self._state_spec is None)
        self._resident: "OrderedDict[bytes, Tuple[np.ndarray, Tuple[int, ...]]]" = OrderedDict()
        self._resident_max = max(self._kinds[0].alloc.n_pages // 2, 1)
        # rows of a prefill dispatch, a rung: a whole number of pages,
        # at most the rung and at most PREFILL_ROWS_MAX — and no more
        # pages than a bounded kind's ring leaves room for (the ring
        # rule, :class:`_PageKind`: the ``m``-th page of a dispatch
        # opens ``(m - 1) C`` rows past the frontier)
        room = [1 + k.ahead // chunk for k in self._kinds if k.bounded]
        self._prefill_rows = {
            t: chunk * min([max(1, min(t, PREFILL_ROWS_MAX) // chunk),
                            *room])
            for t in self.buckets}
        cfg_d = None
        self._draft_cfg = self._draft_params = None
        #: a self-draft's pending proposals, one row a slot (host mirror,
        #: as ``tokens_h``: every round takes them and hands back the next)
        self._drafts_h = (np.zeros((self.n_slots, self.draft_k), np.int32)
                          if self._self_draft else None)
        if draft is not None and not self._self_draft:
            cfg_d, self._draft_params = draft
            self._draft_cfg = cfg_d
            if not getattr(cfg_d, "causal", False):
                raise ValueError("draft config must be causal")
            if cfg_d.max_len < self.buckets[-1]:
                raise ValueError(
                    f"draft max_len {cfg_d.max_len} < largest bucket "
                    f"{self.buckets[-1]}: the draft mirrors target "
                    f"positions")
        self._slots = _SlotTable(self.n_slots)
        verify_fn = None
        # the key captures everything that determines the traced
        # programs besides input shapes (``geo`` below extends it with
        # the slot/bucket geometry)
        key = (f"{fam_name}_slots", repr(cfg))

        def prefill_fn(params, pool, ptab_s, toks, start, n_valid,
                       temperature, seed):
            return fam.paged_prefill(cfg, params, pool, ptab_s, toks,
                                     start, n_valid, temperature, seed)

        if self._state_spec is not None:
            # the join's dispatches are told whose state they carry
            def prefill_fn(params, pool, ptab_s, toks, start, n_valid,  # noqa: F811 — takes the plain one's place AND its name: the benchmark finds the program by it
                           temperature, seed, slot):
                return fam.paged_prefill(cfg, params, pool, ptab_s, toks,
                                         start, n_valid, temperature, seed,
                                         slot)

        def decode_fn(params, pool, ptab, tokens, pos, active,
                      temperature, seeds):
            return fam.paged_decode(cfg, params, pool, ptab, tokens,
                                    pos, active, temperature, seeds)

        n_slots = self.n_slots  # the compile engine keeps what it jits,
        #                         closure and all: never close over self

        def tokens_ahead_fn(prev_out, tokens_h, from_host):
            # the tokens of a step dispatched while the step before it is
            # still uncollected: that step's output where it ran the
            # slot, the host mirror elsewhere (a family's counters ride
            # behind the S tokens of ``prev_out``)
            return jnp.where(from_host, tokens_h, prev_out[:n_slots])

        if self._self_draft:
            # the join fills the draft block's cache and brings the first
            # draft back with the first token; a round is ONE program
            def prefill_fn(params, pool, ptab_s, toks, nxt, start, n_valid,  # noqa: F811 — the self-draft's prefill takes the plain one's place AND its name: the benchmark finds the program by it
                           temperature, seed):
                return fam.paged_self_draft_prefill(
                    cfg, params, pool, ptab_s, toks, nxt, start, n_valid,
                    temperature, seed)

            def spec_fn(params, pool, ptab, tokens, pos, active,
                        temperature, seeds, drafts):
                return fam.paged_self_draft_round(
                    cfg, params, pool, ptab, tokens, pos, active,
                    temperature, seeds, drafts)

            k_round = self.draft_k
            cut = np.cumsum([n_slots * (k_round + 1), n_slots,
                             n_slots * k_round]).tolist()

            def round_ahead_fn(prev_out, prev_pos, tokens_h, pos_h, drafts_h,
                               from_host):
                # what a round dispatched while the round before it is
                # still uncollected feeds: where that round ran the slot,
                # its last committed token, its position plus its commit
                # count and its next draft; the host mirrors elsewhere
                g = prev_out[:cut[0]].reshape(n_slots, k_round + 1)
                n_c = prev_out[cut[0]:cut[1]]
                nxt = prev_out[cut[1]:cut[2]].reshape(n_slots, k_round)
                last = jnp.take_along_axis(
                    g, jnp.maximum(n_c - 1, 0)[:, None], axis=1)[:, 0]
                return (jnp.where(from_host, tokens_h, last),
                        jnp.where(from_host, pos_h, prev_pos + n_c),
                        jnp.where(from_host[:, None], drafts_h, nxt))
        elif draft is not None:
            def verify_fn(params, pool, ptab, tokens, pos, active,
                          temperature, seeds, drafts):
                return gpt.paged_verify(cfg, params, pool, ptab,
                                        tokens, pos, active,
                                        temperature, seeds, drafts)
        if self.quantize is not None:
            # dequant fused INTO the jitted programs: the executables
            # take the quantized tree and stream int8 bytes from HBM.
            # The DRAFT model stays full-precision (it is already tiny
            # — quantizing it buys nothing and would couple its
            # numerics to the target's quantization mode).
            base_prefill, base_decode = prefill_fn, decode_fn

            def prefill_fn(params, *a):
                return base_prefill(qz.dequantize_tree(params), *a)

            def decode_fn(params, *a):
                return base_decode(qz.dequantize_tree(params), *a)

            if verify_fn is not None:
                base_verify = verify_fn

                def verify_fn(params, *a):
                    return base_verify(qz.dequantize_tree(params), *a)
        # one executable pair per (conf, slot-geometry, mesh,
        # quantization mode, kv dtype): the shapes traced differ only in
        # T_max across buckets, so the compile count is bounded by 2 x
        # len(buckets) — 4 x with a prefix store, since the page
        # read/write pair also traces per bucket shape; the mesh signature
        # keeps a sharded engine (or a second device group) from
        # hitting a replicated engine's executable, and the quant modes
        # key their own entries — a dequant-fused program must never be
        # served to a full-precision engine or vice versa
        geo = (self.n_slots, self.prefill_chunk, mesh_signature(mesh),
               self.quantize, self.kv_dtype, ("paged", self.n_kv_pages),
               ((draft if self._self_draft else repr(cfg_d), self.draft_k)
                if draft is not None else None))
        shard_kw_prefill: Dict[str, Any] = {}
        shard_kw_decode: Dict[str, Any] = {}
        shard_kw_read: Dict[str, Any] = {}
        shard_kw_write: Dict[str, Any] = {}
        shard_kw_verify: Dict[str, Any] = {}
        shard_kw_draft: Dict[str, Any] = {}
        shard_kw_dprefill: Dict[str, Any] = {}
        shard_kw_ahead: Dict[str, Any] = {}
        self._param_shardings = None
        self._pool_shardings = None
        self._dpool_shardings = None
        self._draft_shardings = None
        if mesh is not None:
            from deeplearning4j_tpu.parallel.sharded_fit import \
                named_shardings

            m_deg = model_degree(mesh)
            if cfg.n_heads % m_deg:
                raise ValueError(
                    f"n_heads={cfg.n_heads} not divisible by model "
                    f"degree {m_deg}: the KV page pool shards over "
                    f"heads (gpt.paged_specs)")
            pspecs = gpt.shard_specs(cfg, model_degree=m_deg)
            if self.quantize is not None:
                # int8 leaves keep the fp32 layout; per-channel scales
                # take the spec entry of the axis they index
                pspecs = qz.quant_specs(pspecs, self._raw_params(),
                                        self.quantize)
            psh = named_shardings(mesh, pspecs)
            repl = NamedSharding(mesh, P())
            self._param_shardings = psh
            poolsh = named_shardings(
                mesh, fam.paged_specs(cfg, self.kv_dtype))
            self._pool_shardings = poolsh
            # paged_prefill(params, pool, ptab_s, toks, start,
            # n_valid, temp, seed) / paged_decode(params, pool,
            # ptab, tokens, pos, active, temps, seeds): only params
            # and the pool carry a layout
            shard_kw_prefill = dict(
                in_shardings=(psh, poolsh) + (repl,) * 6,
                out_shardings=(poolsh, repl))
            shard_kw_decode = dict(
                in_shardings=(psh, poolsh) + (repl,) * 6,
                out_shardings=(poolsh, repl))
            shard_kw_ahead = dict(in_shardings=(repl,) * 3,
                                  out_shardings=repl)
            # prefix pages [L, TBL, C, NH, D] shard over heads like
            # the pool rows they copy; int8 scale pages replicated
            page_sh = (NamedSharding(
                mesh, P(None, None, None, MODEL_AXIS, None)),) * 2
            if self.kv_dtype == "int8":
                page_sh = page_sh + (repl, repl)
            shard_kw_read = dict(in_shardings=(poolsh, repl),
                                 out_shardings=page_sh)
            shard_kw_write = dict(in_shardings=(poolsh, repl) + page_sh,
                                  out_shardings=poolsh)
            if cfg_d is not None:
                shard_kw_verify = dict(
                    in_shardings=(psh, poolsh) + (repl,) * 7,
                    out_shardings=(poolsh, repl, repl))
                if cfg_d.n_heads % m_deg:
                    raise ValueError(
                        f"draft n_heads={cfg_d.n_heads} not divisible "
                        f"by model degree {m_deg}: the draft KV shards "
                        f"over heads alongside the target's")
                dpsh = named_shardings(
                    mesh, gpt.shard_specs(cfg_d, model_degree=m_deg))
                self._draft_shardings = dpsh
                dpoolsh = named_shardings(
                    mesh, gpt.paged_specs(cfg_d, self.kv_dtype))
                self._dpool_shardings = dpoolsh
                shard_kw_draft = dict(
                    in_shardings=(dpsh, dpoolsh) + (repl,) * 4,
                    out_shardings=(dpoolsh, repl))
                shard_kw_dprefill = dict(
                    in_shardings=(dpsh, dpoolsh) + (repl,) * 4,
                    out_shardings=dpoolsh)
        if cfg_d is not None:
            self._draft_params = self._hold_draft(self._draft_params)
        self._prefill = compile_cache.cached_jit(
            prefill_fn, key=(key, geo, "prefill"),
            label=f"{label}.prefill", donate_argnums=(1,),
            **shard_kw_prefill)
        self._decode = compile_cache.cached_jit(
            decode_fn, key=(key, geo, "step"),
            label=f"{label}.step", donate_argnums=(1,),
            **shard_kw_decode)
        # its name matches none of the patterns a benchmark cell finds
        # the step programs by (jit_decode_fn, jit_prefill_fn, jit_spec_fn)
        self._tokens_ahead = compile_cache.cached_jit(
            tokens_ahead_fn, key=(key, geo, "tokens_ahead"),
            label=f"{label}.tokens_ahead", **shard_kw_ahead)
        self._verify = self._draft_fn = self._draft_prefill = None
        self._spec = self._round_ahead = None
        if self._self_draft:
            self._spec = compile_cache.cached_jit(
                spec_fn, key=(key, geo, "spec"),
                label=f"{label}.spec", donate_argnums=(1,))
            # like ``tokens_ahead_fn``, named apart from the round program
            self._round_ahead = compile_cache.cached_jit(
                round_ahead_fn, key=(key, geo, "round_ahead"),
                label=f"{label}.round_ahead")
        elif draft is not None:
            k_steps = self.draft_k

            def draft_fn(params_d, dpool, ptab, tokens, pos, active):
                return gpt.paged_draft_propose(
                    cfg_d, params_d, dpool, ptab, tokens, pos,
                    active, k_steps)

            def draft_prefill_fn(params_d, dpool, ptab_s, toks,
                                 start, n_valid):
                p, _ = gpt.paged_prefill(
                    cfg_d, params_d, dpool, ptab_s, toks, start,
                    n_valid, jnp.float32(0.0), jnp.uint32(0))
                return p
            self._verify = compile_cache.cached_jit(
                verify_fn, key=(key, geo, "verify"),
                label=f"{label}.verify", donate_argnums=(1,),
                **shard_kw_verify)
            self._draft_fn = compile_cache.cached_jit(
                draft_fn, key=(key, geo, "draft"),
                label=f"{label}.draft", donate_argnums=(1,),
                **shard_kw_draft)
            self._draft_prefill = compile_cache.cached_jit(
                draft_prefill_fn, key=(key, geo, "draft_prefill"),
                label=f"{label}.draft_prefill", donate_argnums=(1,),
                **shard_kw_dprefill)
        self._read = self._write = None
        if self._prefix is not None:
            def read_fn(pool, pids):
                return fam.paged_read_pages(cfg, pool, pids)

            def write_fn(pool, pids, *pages):
                return fam.paged_write_pages(cfg, pool, pids, *pages)

            self._read = compile_cache.cached_jit(
                read_fn, key=(key, geo, "prefix_read"),
                label=f"{label}.prefix_read", **shard_kw_read)
            self._write = compile_cache.cached_jit(
                write_fn, key=(key, geo, "prefix_write"),
                label=f"{label}.prefix_write", donate_argnums=(0,),
                **shard_kw_write)
        #: KV bytes one slot of the largest bucket costs — the 'slots
        #: per chip' capacity denominator (int8 KV is the ~4x/2x lever)
        self.kv_bytes_per_slot = int(fam.slots_bytes_per_slot(
            cfg, self.buckets[-1], self.kv_dtype))
        decode_metrics.note_kv_bytes_per_slot(self.kv_bytes_per_slot)
        #: total pool HBM (target + draft pools) — the capacity
        #: denominator: slots/chip at a given HBM budget is bounded by
        #: live tokens, not bucket length
        self.pool_bytes = int(fam.pages_bytes(
            cfg, self.n_kv_pages, self.page_tokens, self.kv_dtype)
        ) + self.state_bytes
        if self._state_spec is not None:
            decode_metrics.note_state_bytes_held(self.state_bytes)
        if cfg_d is not None:
            self.pool_bytes += int(gpt.pages_bytes(
                cfg_d, self.n_kv_pages, self.page_tokens, self.kv_dtype))
        # prefix harvesting is ASYNC: the page read dispatches on the
        # serving thread (cheap), but the device->host transfer +
        # store insert run on a harvest worker so they never stall the
        # in-flight requests' inter-token latency.  Bounded queue,
        # drop-on-full: harvesting is opportunistic.  The worker is
        # spawned lazily (and re-spawned after close()).
        self._harvest_q: Optional["queue.Queue"] = None
        self._harvest_thread: Optional[threading.Thread] = None
        if self._prefix is not None:
            self._harvest_q = queue.Queue(maxsize=4)

    # -- params ------------------------------------------------------------
    def _raw_params(self) -> Any:
        p = self._params
        return p() if callable(p) else p

    def _quantize_and_place(self, raw_tree):
        # one-time full-tree fetch PER PARAMS TREE (memoized by QuantMemo
        # / the static flag): quantization is already a full-tree host
        # pass, and a weight swap must re-quantize before the next
        # dispatch can run anyway — steady state returns the memo and
        # never reaches this line
        if self.mesh is not None:
            raw = jax.device_get(raw_tree)  # jaxlint: disable=host-sync-on-serving-worker — once per params tree, memoized; not a steady-state fetch
        else:
            raw = raw_tree
        q = qz.quantize_tree(raw, self.quantize)
        if self._param_shardings is not None:
            q = jax.device_put(q, self._param_shardings)
        return q

    def _hold_draft(self, tree):
        """The draft's tree as its executables take it: laid out under
        a mesh, its family's ``COMPUTE_DTYPE_LEAVES`` in the draft
        config's compute type.  The draft is never quantized."""
        if self._draft_shardings is not None:
            tree = jax.device_put(tree, self._draft_shardings)
        return hold_in_compute_dtype(self._draft_cfg, tree,
                                     self._draft_shardings, self.label)

    def _hold(self, raw_tree):
        """The tree the executables take for ``raw_tree``: quantized
        where ``quantize`` is set, else with the family's
        ``COMPUTE_DTYPE_LEAVES`` in the compute type."""
        if self.quantize is not None:
            return self._quantize_and_place(raw_tree)
        return hold_in_compute_dtype(self.cfg, raw_tree,
                                     self._param_shardings, self.label)

    def current_params(self) -> Any:
        """The params tree the executables take, made ONCE per raw tree
        (:meth:`_hold`): quantized (and, under a mesh, laid out) when
        ``quantize`` is set, else the given tree with the leaves its
        family names cast to the compute type — the given tree itself
        where there is nothing to cast.  STATIC params are transformed
        once and the engine's reference to the raw tree is DROPPED
        (device memory then holds only the held tree once the caller
        releases theirs).  Live-params callables are memoized per
        raw-tree IDENTITY and re-pay the transform only when they
        return a new tree object (the post-training contract: weights
        are frozen while serving; a swap should also ``clear()`` any
        prefix cache)."""
        if not callable(self._params):
            if not self._static_held:
                self._params = self._hold(self._params)
                self._static_held = True
            return self._params
        return self._held_memo.get(self._raw_params(), self._hold)

    # -- geometry ----------------------------------------------------------
    def pick_bucket(self, total_len: int) -> int:
        """Smallest cache-length bucket that fits prompt + budget."""
        for t in self.buckets:
            if t >= total_len:
                return t
        raise ValueError(
            f"request needs {total_len} positions; largest bucket is "
            f"{self.buckets[-1]} (model max_len {self.cfg.max_len})")

    def prefill_rows(self, bucket: int) -> int:
        """Rows ONE prefill dispatch of rung ``bucket`` carries: a whole
        number of pages, at most the rung, at most
        :data:`PREFILL_ROWS_MAX`, and at most ``1 + ahead // C`` pages
        where the family declares a bounded kind of page (what its ring
        leaves room for: :class:`_PageKind`).  Derived from what the
        engine observes (page width, rung, the kinds' declared
        ``ahead``), no option."""
        return self._prefill_rows[bucket]

    def free_slot(self) -> Optional[int]:
        for i, o in enumerate(self._slots.owners):
            if o is None:
                return i
        return None

    def n_active(self) -> int:
        return int(self._slots.active.sum())

    def _width(self, top: int) -> int:
        """The narrowest rung whose table covers positions below
        ``top`` — the width a dispatch that writes through ``top - 1``
        is handed; the widest when none does (a speculative round's
        rows past the model's last position are out of every table and
        land in the trash page)."""
        return next((t for t in self.buckets if t >= top), self.buckets[-1])

    def _table_widths(self, width: int) -> List[int]:
        """Columns of each kind's table a dispatch (or a prefill) of
        ``width`` positions is handed: the rung's pages, a kind's ring
        at most."""
        return [min(k.cap, width // self.page_tokens) for k in self._kinds]

    def _tables(self, width: int, rows: Any = slice(None)) -> Any:
        """A copy of every kind's table at ``width`` as a dispatch takes
        it (:func:`_bare`): all slots' rows, or one slot's."""
        return _bare([k.ptab[rows, :n].copy() for k, n in
                      zip(self._kinds, self._table_widths(width))])

    def _pool_state(self):
        """Lazily materialize the page pool(s) — ONE pool for every
        length (a page's shape does not depend on a rung; only the
        page-table width a dispatch is handed does)."""
        if self._pool is None:
            pool = self._family.init_pages(self.cfg, self.n_kv_pages,
                                           self.page_tokens, self.kv_dtype)
            if self._state_spec is not None:
                pool = (pool, jax.tree.map(
                    lambda s: jnp.zeros((self.n_slots,) + s.shape, s.dtype),
                    self._state_spec))
            if self._pool_shardings is not None:
                pool = jax.device_put(pool, self._pool_shardings)
            self._pool = pool
        if self._draft_cfg is not None and self._dpool is None:
            # the draft pool is indexed by the SAME page tables as the
            # target's (same positions, same allocator) — one allocator
            # covers both models
            dpool = gpt.init_pages(self._draft_cfg, self.n_kv_pages,
                                   self.page_tokens, self.kv_dtype)
            if self._dpool_shardings is not None:
                dpool = jax.device_put(dpool, self._dpool_shardings)
            self._dpool = dpool
        return self._pool

    def _live_rows(self) -> int:
        """Token rows currently live across all slots — the
        page_utilization numerator."""
        st = self._slots
        return int(st.pos_h[st.active].sum())

    # -- admission / page tables -------------------------------------------
    def can_admit(self, prompt_len: int) -> bool:
        """Room for a request RIGHT NOW?  A free slot (of the engine's
        ``n_slots``, whatever the request's rung) plus enough free
        pages for the prompt and its first decode page.  In-flight
        growth past that STALLS rather than deadlocks, so admission
        only gates on the prompt floor."""
        if self.free_slot() is None:
            return False
        needed = -(-prompt_len // self.page_tokens) + 1
        return all(k.alloc.n_free() >= min(needed, k.cap)
                   for k in self._kinds)

    def check_capacity(self, prompt_len: int) -> None:
        """Raise the typed error when a prompt alone can NEVER fit the
        pool — the sync-validate path for oversize admits."""
        needed = -(-prompt_len // self.page_tokens) + 1
        for k in self._kinds:
            total = k.alloc.n_pages - k.alloc.n_reserved
            if min(needed, k.cap) > total:
                raise KVPagesExhausted(min(needed, k.cap), total,
                                       k.alloc.n_pages)

    def last_ran(self) -> np.ndarray:
        """[S] mask of slots the last advance/advance_spec actually
        moved — a slot can STALL on page exhaustion (its token output
        is stale and must be ignored)."""
        return self._slots.ran.copy()

    def _ensure_pages(self, span: int) -> np.ndarray:
        """Grow each active slot's page table to cover writes through
        ``pos + span``, never past the slot's own rung (rows beyond it
        are beyond the request's budget: they go to the trash page).
        Slots whose pages cannot be allocated STALL —
        masked out of this dispatch, retried next — and when nothing
        active can run at all the deadlock breaker raises the typed
        error naming a victim (the slot pinning the most pages in the
        engine, so evicting it frees the most room).  Returns the
        runnable mask."""
        b = self._slots
        run = b.active.copy()
        C = self.page_tokens
        for s in np.flatnonzero(b.active):
            need = int(b.pos_h[s] + span) // C + 1
            need = min(need, int(b.rung[s]) // C)
            for k in self._kinds:
                # a kind's ring is full at ``cap`` pages: from there on
                # the slot's newest rows go over its oldest page
                held = int(k.n_pages[s])
                short = min(need, k.cap) - held
                if short <= 0:
                    continue
                try:
                    ids = k.alloc.alloc(short)
                except KVPagesExhausted:
                    run[s] = False
                    break
                k.ptab[s, held:held + short] = ids
                k.n_pages[s] = held + short
        if b.active.any() and not run.any():
            victim = int(max(np.flatnonzero(b.active), key=lambda s: sum(
                int(k.n_pages[s]) for k in self._kinds)))
            short_of = min(self._kinds, key=lambda k: k.alloc.n_free())
            raise KVPagesExhausted(1, short_of.alloc.n_free(),
                                   short_of.alloc.n_pages, slot=victim)
        return run

    def _release_pages(self, slot: int) -> None:
        b = self._slots
        for k in self._kinds:
            n = int(k.n_pages[slot])
            if n:
                k.alloc.free(int(p) for p in k.ptab[slot, :n])
            k.ptab[slot, :] = 0
            k.n_pages[slot] = 0
        b.tokens_h[slot] = 0
        b.pos_h[slot] = 0
        decode_metrics.note_pages(self._alloc.in_use(), 0, 0)
        if self._resident:      # its pages may be the registry's alone now
            self._note_resident_held()
        if self._kind_names:
            self._note_kinds()
        decode_metrics.note_pages_leaked(self.pages_unaccounted())

    def _note_kinds(self, written_at: np.ndarray = np.zeros((0,), np.int32),
                    held_at: Optional[np.ndarray] = None) -> None:
        """The per-kind counters of a family that declared its kinds of
        page, after rows were written at positions ``written_at`` (a
        decode dispatch's, one a slot that ran; a speculative round's
        COMMITTED rows, each position once; or the starts of a
        prefill's chunks): the gauges ``pages_in_use_<kind>``;
        ``<kind>_pages_reused`` of a bounded kind grows by the rows that
        opened a page past the ring, written over the slot's oldest;
        after a decode dispatch ``kv_rows_held_<kind>`` grows by the
        rows a layer of the kind then holds for the slots that ran,
        ``held_at`` the last row each committed (a ring of ``cap`` pages
        holds the newest)."""
        C = self.page_tokens
        gauges, counts = {}, {}
        for k in self._kinds:
            gauges[f"pages_in_use_{k.name}"] = k.alloc.in_use()
            if k.bounded:
                counts[f"{k.name}_pages_reused"] = int(
                    ((written_at % C == 0) & (written_at // C >= k.cap)
                     ).sum())
            if held_at is not None:
                behind = np.maximum(0, held_at // C + 1 - k.cap) * C
                counts[f"kv_rows_held_{k.name}"] = int(
                    (held_at + 1 - behind).sum())
        decode_metrics.note_page_kinds(gauges, counts)

    def _drop_pool(self) -> None:
        """Poison-reset after a failed dispatch: the pool was
        donated into the failure, so it re-initializes to ZEROS on the
        next ``_pool_state``.  The resident-prefix registry must flush
        WITH it — its entries reference page ids whose KV bytes no
        longer exist, and a later mount-by-reference hit would serve
        zeroed cache rows as silently wrong tokens."""
        self._pool = None
        self._dpool = None
        self.drop_residents()

    def pages_unaccounted(self) -> int:
        """Allocator page references, of every kind of page, not
        explained by any live slot's page tables or the resident-prefix
        registry — nonzero means a
        reclaim path leaked (exported as the ``pages_leaked`` gauge,
        asserted zero by the chaos drill after drain)."""
        accounted = sum(int(k.n_pages.sum()) for k in self._kinds)
        accounted += sum(len(ids) for _, ids in self._resident.values())
        leaked = self._alloc.total_refs() - accounted
        if self._state_spec is not None:
            # and the slot state: held by exactly the slots that are taken
            taken = np.array([o is not None for o in self._slots.owners])
            leaked += int((self._state_held != taken).sum())
        return leaked

    # -- pool-resident prefix pages ----------------------------------------
    def _resident_lookup(self, prompt: np.ndarray):
        """Longest pool-RESIDENT chunk-aligned strict prefix of
        ``prompt`` — the mount-by-reference hit path: the hitting
        slot's page table points at the shared pages directly (zero
        copy, zero dispatch).  Writes can never touch them: the slot's
        own rows start at the next page boundary and its release only
        DECREFS the shared ids."""
        C = self.page_tokens
        n = (prompt.size - 1) // C
        if n < 1 or not self._resident:     # never filled without mounts
            return 0, None
        digs = PrefixCache._boundary_digests(prompt, C, n,
                                             self._prefix_space)
        for k in range(n, 0, -1):
            ent = self._resident.get(digs[k - 1])
            if ent is not None and np.array_equal(ent[0],
                                                  prompt[:k * C]):
                self._resident.move_to_end(digs[k - 1])
                return k * C, ent[1]
        return 0, None

    def _resident_register(self, prompt: np.ndarray, slot: int) -> int:
        """Register the slot's chunk-aligned prompt prefix pages as
        pool-resident at every chunk boundary (so a partial prefix
        match still hits).  The registry holds its own reference on
        each page — the pages outlive the harvesting slot and return
        to the pool when the LRU bound (or a weight swap) evicts the
        entry and the last sharer releases.  Returns the entries the
        bound evicted."""
        C = self.page_tokens
        m = C * ((prompt.size - 1) // C)
        if m < C or not self._mounts_prefixes:
            return 0
        digs = PrefixCache._boundary_digests(prompt, C, m // C,
                                             self._prefix_space)
        ptab = self._kinds[0].ptab      # the one kind of a family that mounts
        for k in range(1, m // C + 1):
            ids = tuple(int(p) for p in ptab[slot, :k])
            old = self._resident.pop(digs[k - 1], None)
            if old is not None:
                self._alloc.free(old[1])
            self._alloc.share(ids)
            self._resident[digs[k - 1]] = (prompt[:k * C].copy(), ids)
        evicted = 0
        while (sum(len(v[1]) for v in self._resident.values())
               > self._resident_max and self._resident):
            _, (_, ids) = self._resident.popitem(last=False)
            self._alloc.free(ids)
            evicted += 1
        return evicted

    def _note_resident_held(self) -> int:
        """Set the gauge ``pages_held_resident``: the pages in use that
        no slot's table has, which the registry alone keeps from
        ``can_admit`` (a family that mounts has one kind of page).
        Called wherever that number moves: a join, a release, a drop."""
        k = self._kinds[0]
        in_slots = k.ptab[np.arange(k.cap) < k.n_pages[:, None]]
        held = self._alloc.in_use() - np.unique(in_slots).size
        decode_metrics.note_pages_held_resident(held)
        return held

    def drop_residents(self) -> None:
        """Evict every pool-resident prefix registration, releasing the
        registry's page references (pages shared with live slots
        survive until those slots release — refcounts).  An operational
        pressure valve, and the occupancy-zero audit hook for drills:
        after a full drain plus ``drop_residents`` the allocator's
        ``in_use()`` must be exactly zero.  Call from the driver thread
        — or when the engine's worker is dead or quiescent (the
        allocator's single-driver contract)."""
        while self._resident:
            _, (_, ids) = self._resident.popitem(last=False)
            self._alloc.free(ids)
        decode_metrics.note_pages_held_resident(0)
        decode_metrics.note_pages_leaked(self.pages_unaccounted())

    # -- hot checkpoint swap -----------------------------------------------
    def rebind_params(self, params: Any,
                      draft_params: Any = None) -> None:
        """Hot checkpoint swap, engine side: replace the params tree
        while IDLE (the router drains this replica first; a busy rebind
        raises).  Same shapes/dtypes → the executables and their
        compile-cache entries are reused untouched: ZERO new compiles.
        The held tree (quantized, or cast to the compute type) is made
        again lazily on the next ``current_params()`` — call that on
        the swap thread to keep its cost off the serving worker.  The
        engine-local resident page registry is invalidated (pages are
        only valid for the params that wrote them); clearing a SHARED
        host :class:`PrefixCache` is the router's job, once per store."""
        if self.n_active():
            raise RuntimeError(
                f"rebind_params on a busy engine ({self.n_active()} "
                f"active slot(s)): drain first")
        self._params = params
        self._static_held = False
        self._held_memo = qz.QuantMemo()
        self._params_gen += 1
        self._prefix_space = (repr(self.cfg), self.quantize,
                              self.kv_dtype, self._params_gen)
        if draft_params is not None:
            if self._draft_cfg is None:
                raise ValueError("engine built without draft=")
            self._draft_params = self._hold_draft(draft_params)
        self.drop_residents()

    # -- prefix harvesting -------------------------------------------------
    def _ensure_harvester(self) -> None:
        """(Re)spawn the harvest worker.  The loop closes over ONLY the
        queue and the store — never the engine — so a dropped engine's
        device state is collectable even if ``close()`` was skipped."""
        t = self._harvest_thread
        if t is not None and t.is_alive():
            return
        q, store, space = self._harvest_q, self._prefix, self._prefix_space

        def loop():
            while True:
                item = q.get()
                try:
                    if item is None:
                        return
                    pages, prefix, chunk = item
                    # the read executable's outputs are fresh buffers
                    # — independent of the pool later dispatches
                    # donate — so fetching them here cannot race the
                    # serving thread.  A read comes back
                    # [L, TBL, C, ...]; flatten to the store's row
                    # format [L, m, ...], which knows no page width.
                    host = []
                    for p in pages:
                        a = np.asarray(p)  # jaxlint: disable=host-sync-on-serving-worker — the harvest worker EXISTS to absorb this fetch off the decode thread
                        a = a.reshape(
                            (a.shape[0], a.shape[1] * a.shape[2])
                            + a.shape[3:])
                        host.append(a[:, :prefix.size])
                    store.insert(prefix, tuple(host), chunk, space)
                except Exception:   # noqa: BLE001 — opportunistic path
                    # a failed harvest must never kill the worker: the
                    # request it served already completed; the prefix
                    # is simply not cached
                    pass
                finally:
                    q.task_done()

        self._harvest_thread = threading.Thread(
            target=loop, name="dl4j-prefix-harvest", daemon=True)
        self._harvest_thread.start()

    def flush_harvests(self) -> None:
        """Block until every queued prefix harvest is stored.  Serving
        itself is eventually consistent (a prefix becomes hittable
        shortly after its cold request); this is for callers — and
        tests — that need read-your-writes on the store."""
        if self._harvest_q is not None:
            self._harvest_q.join()

    def close(self) -> None:
        """Stop the harvest worker (pending harvests complete first).
        Serving through the engine keeps working — new harvests simply
        respawn the worker — so retiring a replica
        (``ContinuousBatcher.close`` calls this) never leaks a thread
        pinning the engine's device state."""
        t = self._harvest_thread
        if t is not None and t.is_alive():
            self._harvest_q.put(None)
            t.join()
        self._harvest_thread = None

    def _pad_pool_pages(self, pages: Sequence[np.ndarray], tbl: int):
        """Re-chunk stored prefix rows [L, m, ...] into the write
        executable's fixed page format [L, TBL, C, ...], ``tbl`` the
        pages of the request's own rung (host-side; pad pages land in
        the trash page, so one shape per rung — a fresh hit length
        never costs a trace)."""
        C = self.page_tokens
        out = []
        for p in pages:
            buf = np.zeros((p.shape[0], tbl, C) + p.shape[2:], p.dtype)
            m = p.shape[1]
            buf[:, :m // C] = np.ascontiguousarray(
                p[:, :C * (m // C)]).reshape(
                    (p.shape[0], m // C, C) + p.shape[2:])
            out.append(buf)
        return out

    # -- AOT warmup --------------------------------------------------------
    def _idle_step_args(self, bucket: int) -> Tuple[np.ndarray, ...]:
        """What a decode (verify, draft) dispatch at ``bucket``'s width
        takes behind the pool, with nothing running: a ZERO table slice
        [S, bucket // C] (one a kind of page), zero tokens/pos,
        all-inactive, zero sampling
        state — the shapes and types ``advance()`` dispatches with."""
        S = self.n_slots
        return (_bare([np.zeros((S, n), np.int32)
                       for n in self._table_widths(bucket)]),
                np.zeros((S,), np.int32), np.zeros((S,), np.int32),
                np.zeros((S,), np.bool_), np.zeros((S,), np.float32),
                np.zeros((S,), np.uint32))

    def warmup(self) -> dict:
        """Pre-trace the prefill + decode executables at every width of
        the ladder (AOT; plus the prefix page read/write pair when a
        prefix store is attached — a HIT must never trace; plus the
        draft/verify pair of a speculative engine), then reset the pool
        — steady-state traffic after this is compile-free for any
        prompt length / join / prefix-reuse pattern and for every
        width the running slots can make a dispatch take.  Returns
        {"buckets": n, "compiles": traces, "warmup_ms": wall}."""
        from deeplearning4j_tpu.runtime.metrics import compile_metrics

        labels = [f"{self.label}.prefill", f"{self.label}.step"]
        if self._prefix is not None:
            labels += [f"{self.label}.prefix_read",
                       f"{self.label}.prefix_write"]
        if self._self_draft:
            labels += [f"{self.label}.spec"]
        elif self.draft is not None:
            labels += [f"{self.label}.draft",
                       f"{self.label}.draft_prefill",
                       f"{self.label}.verify"]
        before = sum(
            compile_metrics.snapshot()["traces"].get(k, 0) for k in labels)
        params = self.current_params()
        t0 = time.perf_counter()
        with telemetry.span("decode.warmup", buckets=len(self.buckets)):
            for t in self.buckets:
                toks = np.zeros((self.prefill_rows(t),), np.int32)
                # all warmup dispatches run with ZERO page tables
                # and all-inactive masks: every write lands in the
                # trash page, the allocator is untouched, and the
                # pool is dropped afterwards anyway
                pool = self._pool_state()
                ptab, tokens, pos, idle, temps, seeds = \
                    self._idle_step_args(t)
                ptab_s = jax.tree.map(lambda a: a[0], ptab)
                pool, _ = self._prefill(
                    params, pool, ptab_s, toks,
                    *((toks,) if self._self_draft else ()), np.int32(0),
                    np.int32(1), np.float32(0.0), np.uint32(0),
                    *self._slot_arg(0))
                self._pool = pool
                if self._prefix is not None:
                    pages = self._read(pool, ptab_s)
                    self._pool = pool = self._write(pool, ptab_s,
                                                    *pages)
                if self._self_draft:
                    pool, out = self._spec(
                        params, pool, ptab, tokens, pos, idle, temps, seeds,
                        self._drafts_h)
                    # and as a round ONE AHEAD of its fetch takes it:
                    # tokens, positions and drafts device arrays, merged
                    # from the round before
                    toks_a, pos_a, drafts_a = self._round_ahead(
                        out, pos, tokens, pos, self._drafts_h, ~idle)
                    self._pool, _ = self._spec(
                        params, pool, ptab, toks_a, pos_a, idle, temps,
                        seeds, drafts_a)
                elif self.draft is not None:
                    self._dpool = self._draft_prefill(
                        self._draft_params, self._dpool, ptab_s,
                        toks, np.int32(0), np.int32(1))
                    self._dpool, props = self._draft_fn(
                        self._draft_params, self._dpool, ptab, tokens,
                        pos, idle)
                    pool, _, _ = self._verify(
                        params, pool, ptab, tokens, pos, idle, temps,
                        seeds, props)
                    self._pool = pool
                pool, out = self._decode(
                    params, self._pool, ptab, tokens, pos, idle, temps,
                    seeds)
                # and as a step ONE AHEAD of its fetch takes it: the
                # tokens a device array, merged from the step before
                pool, out = self._decode(
                    params, pool, ptab,
                    self._tokens_ahead(out, tokens, ~idle), pos, idle,
                    temps, seeds)
                self._pool = pool
                jax.block_until_ready(out)
            # warmup scribbled on the shared pools; re-init lazily so
            # serving starts from zeros
            self._pool = None
            self._dpool = None
        wall_ms = (time.perf_counter() - t0) * 1e3
        compiles = sum(
            compile_metrics.snapshot()["traces"].get(k, 0) for k in labels
        ) - before
        decode_metrics.mark_compiles()
        return {"buckets": len(self.buckets), "compiles": compiles,
                "warmup_ms": round(wall_ms, 1)}

    def _slot_arg(self, slot: int) -> Tuple[np.ndarray, ...]:
        """What a prefill dispatch takes behind its seed: the slot's
        index where the family holds a SLOT STATE, nothing else."""
        return () if self._state_spec is None else (np.int32(slot),)

    def _lower_decode(self, bucket: int):
        """The program a round dispatches (the decode step; a
        self-draft's speculative round) lowered for this engine at
        ``bucket``'s table width (traced again; compiling it hits the
        cache): for set-up, never for the serving thread."""
        if self._self_draft:
            return self._spec.jitted.lower(
                self.current_params(), self._pool_state(),
                *self._idle_step_args(bucket), self._drafts_h)
        return self._decode.jitted.lower(
            self.current_params(), self._pool_state(),
            *self._idle_step_args(bucket))

    def decode_hlo(self, bucket: int) -> str:
        """Optimized HLO text of the program a round dispatches (the
        decode step; the speculative round of a self-draft) at
        ``bucket``'s table
        width as compiled for this engine, every instruction with XLA's
        ``op_name`` (the ``jax.named_scope`` path it was traced under).
        A device trace names its ops by instruction and carries no
        ``op_name``; a reader that wants device time by scope joins the
        two on the instruction."""
        return self._lower_decode(bucket).compile().as_text()

    # -- serving -----------------------------------------------------------
    def start(self, prompt: np.ndarray, *, max_tokens: int,
              temperature: float = 0.0, seed: int = 0,
              owner: Any = True) -> Tuple[int, int]:
        """Prefill ``prompt`` [T_p] int32 into a free slot and return
        (slot, first_token).  The prefill runs at the table width of
        the request's OWN rung, ``pick_bucket(T_p + max_tokens)`` (the
        slot's page row never grows past it), whatever the other slots
        hold; their decode state rides along untouched — this is the
        mid-flight JOIN.  Raises RuntimeError when the engine has no
        free slot (callers gate on ``free_slot``)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_tokens < 1:
            raise ValueError(f"max_tokens must be >= 1: {max_tokens}")
        bucket = self.pick_bucket(prompt.size + max_tokens)
        b = self._slots
        slot = self.free_slot()
        if slot is None:
            raise RuntimeError(
                f"no free slot: all {self.n_slots} are taken")
        rid = getattr(owner, "rid", None)
        self.check_capacity(prompt.size)
        params = self.current_params()
        pool = self._pool_state()
        C = self.page_tokens
        n_chunks = -(-prompt.size // C)
        tbl = bucket // C
        # prefix reuse, best first: (1) pool-RESIDENT pages mount into
        # the page table BY REFERENCE — no copy, no dispatch; (2) the
        # host PrefixCache (shared across replicas) copies pages into
        # freshly-allocated pool pages
        # (a family that mounts none and has no store opens neither of
        # the two ``decode.prefix.*`` spans: they would always be empty)
        reuses = self._mounts_prefixes or self._prefix is not None
        hit_len, hit_ids, host_pages = 0, None, None
        if reuses:
            with telemetry.span("decode.prefix.lookup",
                                counter=(decode_metrics, "prefix_s"),
                                rid=rid, pages=(prompt.size - 1) // C) as sp:
                hit_len, hit_ids = self._resident_lookup(prompt)
                if not hit_len and self._prefix is not None:
                    hit = self._prefix.lookup(prompt, C, self._prefix_space)
                    if hit is not None:
                        hit_len, host_pages = hit
                sp.set(hit_tokens=hit_len)
        resident_hit = hit_ids is not None
        h = hit_len // C
        # the join walks the prompt from page h (a prefix hit stays
        # page-aligned) in dispatches of ``rows`` rows, a whole number
        # of pages; the last one is padded (``n_valid``)
        rows = self.prefill_rows(bucket)
        n_rows = prompt.size - h * C        # what no prefix hit covers
        # the first dispatch at position 0 starts the slot's state from
        # zero (no prefix hit in a family that holds one)
        state_reset = self._state_spec is not None and h == 0
        with telemetry.span("decode.prefill",
                            counter=(decode_metrics, "prefill_s"),
                            rid=rid, bucket=bucket, slot=slot,
                            prompt_tokens=int(prompt.size),
                            chunks=n_chunks, rows=rows,
                            prefix_hit_tokens=hit_len,
                            state_reset=state_reset):
            # only a RESIDENT hit reuses pages by reference (a family
            # that mounts has one kind of page); a host-store hit copies
            # into fresh pool pages, so it needs the full n_chunks
            # allocated (the hit region included)
            mounted = h if resident_hit else 0
            try:
                for k in self._kinds:
                    # chunk c is written into column c % cap: a prompt
                    # past a bounded kind's ring goes round it
                    n_held = min(n_chunks, k.cap)
                    k.ptab[slot, mounted:n_held] = k.alloc.alloc(
                        n_held - mounted)
                    k.n_pages[slot] = n_held
            except KVPagesExhausted:
                self._release_pages(slot)
                raise
            if resident_hit:
                self._alloc.share(hit_ids)
                self._kinds[0].ptab[slot, :h] = hit_ids
            first = None
            try:
                if host_pages is not None:
                    pids = np.zeros((tbl,), np.int32)
                    pids[:h] = self._kinds[0].ptab[slot, :h]
                    self._pool = pool = self._write(
                        pool, pids, *self._pad_pool_pages(host_pages, tbl))
                ptab_s = self._tables(bucket, slot)
                for chunk, lo, n_valid in self._prompt_dispatches(
                        prompt, h, rows):
                    after = ()
                    if self._self_draft:
                        # the token behind each row; -1 behind the
                        # prompt's last, which this dispatch samples
                        nxt = np.zeros((rows,), np.int32)
                        rest = prompt[lo + 1:lo + n_valid + 1]
                        nxt[:rest.size] = rest
                        nxt[rest.size:n_valid] = -1
                        after = (nxt,)
                    pool, first = self._prefill(
                        params, pool, ptab_s, chunk, *after,
                        np.int32(lo), np.int32(n_valid),
                        np.float32(temperature), np.uint32(seed),
                        *self._slot_arg(slot))
                    self._pool = pool
                if self._draft_cfg is not None:
                    # draft prefills EVERY page: host-store hits carry
                    # no draft KV, and re-writing a resident page's
                    # draft rows recomputes identical values (same
                    # tokens, same draft params) — harmless either way
                    for chunk, lo, n_valid in self._prompt_dispatches(
                            prompt, 0, rows):
                        self._dpool = self._draft_prefill(
                            self._draft_params, self._dpool, ptab_s, chunk,
                            np.int32(lo), np.int32(n_valid))
            except Exception:
                # the pool was donated into the failed dispatch — every
                # slot's KV is gone; drop it so serving
                # re-initializes instead of touching deleted buffers.
                # FIRST return this slot's page-table references
                # (resident-hit shares AND fresh pages) to the
                # allocator: the failed dispatch destroyed the KV
                # bytes, but the allocator's bookkeeping is host-side —
                # skipping this leaked the pages until engine teardown
                self._release_pages(slot)
                self._drop_pool()
                raise
            with telemetry.span("decode.prefill.sync",
                                counter=(decode_metrics, "prefill_sync_s")):
                if self._self_draft:            # [first, its draft]
                    first_tok, self._drafts_h[slot, 0] = (
                        int(t) for t in np.asarray(first))  # jaxlint: disable=host-sync-on-serving-worker — the join-time sync, once
                else:
                    first_tok = int(first)      # join-time sync, once
        dispatched = -(-n_rows // rows)
        decode_metrics.note_prefill(dispatched, dispatched * rows, n_rows)
        if state_reset:
            decode_metrics.note_linear_state_reset()
        self._state_held[slot] = self._state_spec is not None
        if hit_len:
            decode_metrics.note_prefix_hit(hit_len)
            telemetry.event("decode.prefix_hit", bucket=bucket, slot=slot,
                            tokens_saved=hit_len,
                            resident=bool(resident_hit))
        else:
            decode_metrics.note_prefix_miss()
        m_store = C * ((prompt.size - 1) // C)
        if (reuses and m_store > hit_len and m_store >= C
                and self.harvest_enabled):
            # harvest: register the prefix pages pool-resident (no
            # dispatch — the registry just refs the page ids) and, with
            # a host store attached, enqueue the cross-replica fetch
            with telemetry.span("decode.prefix.register",
                                counter=(decode_metrics, "prefix_s"),
                                rid=rid, pages=m_store // C) as sp:
                evicted = self._resident_register(prompt, slot)
                if self._prefix is not None:
                    pids = np.zeros((tbl,), np.int32)
                    pids[:m_store // C] = self._kinds[0].ptab[
                        slot, :m_store // C]
                    full = self._read(pool, pids)
                    self._ensure_harvester()
                    try:
                        self._harvest_q.put_nowait(
                            (full, prompt[:m_store].copy(), C))
                    except queue.Full:
                        pass        # backpressure: drop, opportunistic
                sp.set(entries=len(self._resident), evicted=evicted,
                       pages_held=self._note_resident_held())
        elif self._mounts_prefixes:
            self._note_resident_held()
        decode_metrics.note_pages(self._alloc.in_use(), 0, 0)
        if self._kind_names:
            self._note_kinds(np.arange(h, n_chunks) * C)
        b.tokens_h[slot] = first_tok
        b.pos_h[slot] = prompt.size
        b.active[slot] = True
        b.temps[slot] = np.float32(temperature)
        b.seeds[slot] = np.uint32(seed)
        b.owners[slot] = owner
        b.rung[slot] = bucket
        b.epoch[slot] += 1
        return slot, first_tok

    def _prompt_dispatches(self, prompt: np.ndarray, first_page: int,
                           rows: int):
        """What the prefill dispatches of ``prompt`` from page
        ``first_page`` on take, ``rows`` rows (a whole number of pages)
        each: (tokens [rows] zero-padded, start position, valid rows)."""
        for lo in range(first_page * self.page_tokens, prompt.size, rows):
            n_valid = min(rows, prompt.size - lo)
            chunk = np.zeros((rows,), np.int32)
            chunk[:n_valid] = prompt[lo:lo + n_valid]
            yield chunk, lo, n_valid

    def _stage(self, span: int):
        """What one dispatch for every running slot takes: the pages
        through ``pos + span`` allocated, the runnable mask, and the
        table at the narrowest rung's width ``w`` that covers the
        longest RUNNING slot's writes — read off the live positions,
        so a dispatch never gathers more than the longest live context
        needs, and never more than the widest live rung's own dispatch
        did when each rung made one.  Returns (ptab [S, w // C] (a
        tuple of them where the pool has several kinds of page, a
        bounded kind's no wider than its ring), tokens, pos, run, w,
        rungs), ``rungs`` the distinct rungs of
        the requests it carries (the dispatches a table a rung would
        have made)."""
        b = self._slots
        run = self._ensure_pages(span)
        b.ran = run
        top = int(b.pos_h[run].max()) + 1 + span if run.any() else 0
        w = self._width(top)
        rungs = len(set(b.rung[run].tolist()))
        return (self._tables(w), b.tokens_h.copy(), b.pos_h.copy(), run, w,
                rungs)

    def _width_attrs(self, w: int, rungs: int) -> Dict[str, int]:
        """What a ``decode.advance`` span says of its dispatch's table:
        the width, the rungs carried, a declared kind's own width."""
        return {"width": w, "rungs": rungs,
                **{f"width_{name}": n * self.page_tokens
                   for name, n in zip(self._kind_names,
                                      self._table_widths(w))}}

    def advance(self) -> np.ndarray:
        """ONE decode step for the engine, dispatched and collected back
        to back: every active slot, of whatever rung, emits its next
        token, at the narrowest table width that covers the longest
        running one (:meth:`_stage`).  It is
        ``collect(dispatch_step())``, the two halves a
        ``ContinuousBatcher`` orders ONE STEP APART.  Returns the [S]
        token array (entries for inactive slots are stale and must be
        ignored via the caller's ownership map; stalled ones via
        :meth:`last_ran`)."""
        return self.collect(self.dispatch_step())

    def dispatch_step(self, after: Optional[_Step] = None) -> _Step:
        """The first half of a plain decode step: stage and dispatch,
        fetch nothing.  Nothing the host needs for the NEXT dispatch
        depends on a token's value: ``pos_h`` moves by one for every
        slot that ran, here, and pages and table width follow from it.
        So a caller may dispatch the next step before it collects this
        one, handing this one as ``after``: the next step then takes its
        ``tokens`` from this step's output ON THE DEVICE for every slot
        this step ran and that has not been released or restarted
        since, and from ``tokens_h`` for the rest, merged by one tiny
        program of its own (``jit_tokens_ahead_fn``; the step program,
        its arguments and its text are what they were).  ONE step ahead,
        no more: ``tokens_h`` must hold the tokens of every step before
        ``after``, so dispatching behind a step whose own ``after`` is
        still uncollected raises.  A failure of the device may now
        surface in :meth:`collect`."""
        if (after is not None and after.after is not None  # jaxlint: disable=host-sync-in-hot-path — a _Step is a host record: the test reads no device value
                and not after.after.collected):
            raise RuntimeError(
                "a decode step runs ONE step ahead of its fetch: collect "
                "the step before last first")
        b = self._slots
        with telemetry.span("decode.advance",
                            counter=(decode_metrics, "advance_s"),
                            active=self.n_active()) as sp:
            params = self.current_params()
            with telemetry.span("decode.stage",
                                counter=(decode_metrics, "stage_s")):
                ptab, tokens, pos, run, w, rungs = self._stage(0)
                sp.set(n_run=int(run.sum()), **self._width_attrs(w, rungs))
                pool = self._pool_state()
            with telemetry.span("decode.dispatch",
                                counter=(decode_metrics, "dispatch_s")):
                try:
                    if after is not None:  # jaxlint: disable=host-sync-in-hot-path — a host record, as above
                        tokens = self._tokens_ahead(
                            after.out, tokens,
                            ~(after.run & (after.epoch == b.epoch)))
                    pool, out = self._decode(params, pool, ptab, tokens,
                                             pos, run, b.temps, b.seeds)
                    # asked for now, so the copy is not queued behind
                    # the step a caller dispatches next
                    out.copy_to_host_async()
                except Exception:
                    self._drop_pool()           # donated into the failure
                    raise
                self._pool = pool
            b.pos_h[run] += 1
            return _Step(out, run, b.epoch.copy(), pos, w, rungs,
                         (self._alloc.in_use(), self._live_rows()), after)

    def collect(self, step: _Step) -> np.ndarray:
        """The second half of a plain decode step: fetch its [S] tokens
        and book it.  The tokens land in ``tokens_h`` for the slots that
        still hold the sequence the step ran (a slot released or
        restarted since keeps what its new occupant wrote)."""
        b = self._slots
        run = step.run
        with telemetry.span("decode.advance",
                            counter=(decode_metrics, "advance_s")):
            try:
                toks = self._fetch(step.out)
            except Exception:
                self._drop_pool()       # the step failed on the device
                raise
            # the step it ran behind is long collected: let it go
            ahead, step.after, step.collected = (step.after is not None,
                                                 None, True)
            if self._decode_counters:
                # the family's counts came back behind the S tokens, in
                # the one fetch a step makes
                toks, counts = toks[:self.n_slots], toks[self.n_slots:]
                decode_metrics.note_family_counts(self._decode_counters,
                                                  counts)
            keep = run & (step.epoch == b.epoch)
            b.tokens_h[keep] = toks[keep]
            decode_metrics.note_decode_dispatch(
                int(run.sum()), self.n_slots, step.rungs,
                self.n_slots * step.w, ahead=ahead)
            decode_metrics.note_pages(*step.pages, self.page_tokens)
            if self._kind_names:
                self._note_kinds(step.pos[run], held_at=step.pos[run])
            return toks

    @staticmethod
    def _fetch(out: Any) -> np.ndarray:
        """The per-step stream sync: each active request's next token
        must land on host to stream.  This ONE [S]-int fetch per
        dispatch is the product; since a plain step runs one ahead it is
        no longer a stall of the device either: the next step is already
        dispatched when a batcher waits here."""
        with telemetry.span("decode.fetch",
                            counter=(decode_metrics, "fetch_s")):
            return np.asarray(out)  # jaxlint: disable=host-sync-on-serving-worker — the per-step token fetch IS the stream

    def advance_spec(self) -> Tuple[np.ndarray, np.ndarray]:
        """One SPECULATIVE round for the engine, over the same slot
        table and at the width :meth:`_stage` reads off it (through
        ``pos + draft_k``): the draft proposes
        ``draft_k`` tokens per slot in one dispatch (proposals stay on
        device), the target verifies all k+1 positions in ONE batched
        dispatch, and the longest accepted prefix (+ the target's own
        next token) commits.  Returns ``(out [S, k+1], n_commit [S])``
        — slot s committed ``out[s, :n_commit[s]]`` this round (0 for
        inactive/stalled slots).  Greedy target ⇒ bit-identical stream
        to non-speculative decode; sampled targets stay identical too,
        because sampling keys are POSITION-keyed (gpt._slot_key), not
        step-keyed.  Where the draft is the model's own block it is
        ``collect_round(dispatch_round())``, the two halves a
        ``ContinuousBatcher`` orders ONE ROUND APART; a second model's
        round, two dispatches over two pools, stays in series."""
        if self._self_draft:
            return self.collect_round(self.dispatch_round())
        if self._draft_fn is None:
            raise RuntimeError("engine built without draft=")
        b = self._slots
        k = self.draft_k
        with telemetry.span("decode.advance",
                            counter=(decode_metrics, "advance_s"),
                            active=self.n_active(), k=k) as sp:
            params = self.current_params()
            with telemetry.span("decode.stage",
                                counter=(decode_metrics, "stage_s")):
                # the draft is handed the verified frontier: its rows
                # below it hold exactly the committed tokens' KV
                # (accepted proposals consumed them), so no re-sync
                # dispatch is ever needed
                ptab, tokens, pos, run, w, rungs = self._stage(k)
                n_run = int(run.sum())
                sp.set(n_run=n_run, width=w, rungs=rungs)
                pool = self._pool_state()
            with telemetry.span("decode.dispatch",
                                counter=(decode_metrics, "dispatch_s")):
                try:
                    self._dpool, props = self._draft_fn(
                        self._draft_params, self._dpool, ptab, tokens,
                        pos, run)
                    pool, out, n_commit = self._verify(
                        params, pool, ptab, tokens, pos, run,
                        b.temps, b.seeds, props)
                except Exception:
                    self._drop_pool()
                    raise
                self._pool = pool
            # the committed tokens, and their counts on the same
            # round-trip (the proposals never land)
            toks = self._fetch(out)
            n_c = self._fetch(n_commit).astype(np.int64)
            idx = np.flatnonzero(n_c)
            b.tokens_h[idx] = toks[idx, n_c[idx] - 1]
            b.pos_h += n_c.astype(np.int32)
            decode_metrics.note_decode_dispatch(
                n_run, self.n_slots, rungs, self.n_slots * w)
            decode_metrics.note_spec(k * n_run,
                                     int(np.maximum(n_c - 1, 0).sum()))
            sp.set(committed=int(n_c.sum()))
            decode_metrics.note_pages(self._alloc.in_use(),
                                      self._live_rows(), self.page_tokens)
            return toks, n_c

    def dispatch_round(self, after: Optional[_Round] = None) -> _Round:
        """The first half of :meth:`advance_spec` where the draft is the
        model's own block (``draft="self"``): stage and dispatch ONE
        round, fetch nothing.  Every running slot feeds its current
        token and its pending draft; the family's round verifies,
        commits and drafts again, and the tokens, the commit counts, the
        next drafts and the family's counters (of all ``k + 1`` rows a
        slot, the rejected draft's among them) come back in the one
        fetch.  The draft block's cache rows live behind the slot's own
        tables, so there is no second pool, and its prefill was the
        join's.

        A round commits between 1 and ``k + 1`` tokens a slot, so the
        host moves ``pos_h`` by the ONE that is certain here, for every
        slot that ran, and by the rest at :meth:`collect_round`.  A
        caller may dispatch the next round before it collects this one,
        handing this one as ``after``: every slot this round ran and
        that has not been released or restarted since then takes its
        token, position and draft from this round's output ON THE
        DEVICE, merged with the host mirrors by one small program of its
        own (``jit_round_ahead_fn``; the round program, its arguments and
        its text are what they were).  Such a slot may stand up to ``k``
        rows past ``pos_h``, so its pages and the table's width are
        staged for ``2 k`` rows past it, not ``k``; the ring rule is of
        rows ahead of the frontier on the device, where positions are
        exact, and holds as it did.  ONE round ahead, no more:
        dispatching behind a round whose own ``after`` is still
        uncollected raises."""
        if (after is not None and after.after is not None  # jaxlint: disable=host-sync-in-hot-path — a _Round is a host record: the test reads no device value
                and not after.after.collected):
            raise RuntimeError(
                "a speculative round runs ONE round ahead of its fetch: "
                "collect the round before last first")
        b = self._slots
        k = self.draft_k
        with telemetry.span("decode.advance",
                            counter=(decode_metrics, "advance_s"),
                            active=self.n_active(), k=k) as sp:
            params = self.current_params()
            with telemetry.span("decode.stage",
                                counter=(decode_metrics, "stage_s")):
                ptab, tokens, pos, run, w, rungs = self._stage(
                    k if after is None else 2 * k)
                sp.set(n_run=int(run.sum()), **self._width_attrs(w, rungs))
                pool = self._pool_state()
            with telemetry.span("decode.dispatch",
                                counter=(decode_metrics, "dispatch_s")):
                fed, drafts, taken = pos, self._drafts_h.copy(), None
                try:
                    if after is not None:  # jaxlint: disable=host-sync-in-hot-path — a host record, as above
                        taken = after.run & (after.epoch == b.epoch)
                        tokens, fed, drafts = self._round_ahead(
                            after.out, after.fed, tokens, pos, drafts,
                            ~taken)
                    pool, out = self._spec(params, pool, ptab, tokens, fed,
                                           run, b.temps, b.seeds, drafts)
                    # asked for now, so the copy is not queued behind
                    # the round a caller dispatches next
                    out.copy_to_host_async()
                except Exception:
                    self._drop_pool()           # donated into the failure
                    raise
                self._pool = pool
            b.pos_h[run] += 1
            return _Round(out, run, b.epoch.copy(), pos, fed, taken, w,
                          rungs, after)

    def collect_round(self, rnd: _Round) -> Tuple[np.ndarray, np.ndarray]:
        """The second half of a self-draft round: fetch it and book it.
        Returns ``(out [S, k+1], n_commit [S])``: slot s committed
        ``out[s, :n_commit[s]]`` (0 for a slot the round did not run).
        For the slots that still hold the sequence the round ran, ``pos_h``
        moves by the commits past the one :meth:`dispatch_round` counted,
        and the last committed token and the next draft land in the host
        mirrors.  The round's rows are booked at the positions it wrote
        them: where it took a slot's from ``after``, that round's
        positions plus its commits."""
        b = self._slots
        k, S = self.draft_k, self.n_slots
        prev = rnd.after
        if prev is not None and not prev.collected:
            raise RuntimeError(
                "a speculative round is collected after the round it ran "
                "behind")
        with telemetry.span("decode.advance",
                            counter=(decode_metrics, "advance_s"),
                            k=k) as sp:
            try:
                flat = self._fetch(rnd.out)
            except Exception:
                self._drop_pool()       # the round failed on the device
                raise
            toks, n_c, nxt, counts = np.split(
                flat, [S * (k + 1), S * (k + 2), S * (2 * k + 2)])
            toks, nxt = toks.reshape(S, k + 1), nxt.reshape(S, k)
            if self._decode_counters:
                decode_metrics.note_family_counts(self._decode_counters,
                                                  counts)
            at = rnd.pos if prev is None else np.where(
                rnd.taken, prev.at + prev.n_c, rnd.pos)
            # the round it ran behind is collected: let it go
            rnd.after, rnd.collected, rnd.at, rnd.n_c = None, True, at, n_c
            keep = np.flatnonzero(rnd.run & (rnd.epoch == b.epoch))
            b.pos_h[keep] += n_c[keep] - 1
            b.tokens_h[keep] = toks[keep, n_c[keep] - 1]
            self._drafts_h[keep] = nxt[keep]
            n_run = int(rnd.run.sum())
            decode_metrics.note_decode_dispatch(
                n_run, S, rnd.rungs, S * rnd.w, ahead=prev is not None)
            decode_metrics.note_spec(k * n_run,
                                     int(np.maximum(n_c - 1, 0).sum()))
            sp.set(committed=int(n_c.sum()))
            decode_metrics.note_pages(self._alloc.in_use(),
                                      self._live_rows(), self.page_tokens)
            if self._kind_names:
                # every committed position once: a rejected draft's is
                # the next round's own
                idx = np.flatnonzero(n_c)
                rows = at[:, None] + np.arange(k + 1)
                self._note_kinds(rows[np.arange(k + 1) < n_c[:, None]],
                                 held_at=at[idx] + n_c[idx] - 1)
            return toks, n_c

    def release(self, slot: int) -> None:
        """Free a finished slot and return its page-table references
        to the allocator (pool-resident prefix pages survive: the
        registry holds its own reference) — the cache rows need no
        scrubbing: a future occupant of a page prefills its prompt over
        them and decode never attends past its own position."""
        b = self._slots
        b.active[slot] = False
        b.owners[slot] = None
        b.rung[slot] = 0
        b.epoch[slot] += 1
        b.released_at[slot] = time.perf_counter()
        self._state_held[slot] = False
        self._release_pages(slot)

    def vacant_since(self, slot: int) -> Optional[float]:
        """When ``slot`` was last released (``time.perf_counter()``);
        None for a slot that never was."""
        return float(self._slots.released_at[slot]) or None


class DecodeRequest:
    """Handle for one in-flight decode request: tokens stream into an
    internal buffer as the engine emits them; ``result()`` blocks for
    the full continuation, ``stream()`` yields tokens as they land.

    ``deadline_ms`` bounds the WHOLE request (queue wait included):
    once it elapses the batcher frees the slot, reclaims its KV pages,
    and resolves the future with the typed :class:`DeadlineExceeded` —
    an expired request never occupies capacity.

    The handle doubles as the re-dispatch JOURNAL: (prompt, seed,
    temperature, tokens emitted so far) is everything needed to replay
    the request on another replica and continue BIT-identically —
    sampling keys fold (seed, position), not step count, so the token
    at each absolute position is the same no matter which replica (or
    how many prefill/decode boundaries) produced it.

    ``rid`` numbers the requests of a process in the order they were
    made; every journal record of one request carries it."""

    _DONE = object()
    _rids = itertools.count(1)

    def __init__(self, prompt: np.ndarray, max_tokens: int,
                 temperature: float, seed: int, eos_id: Optional[int],
                 deadline_ms: Optional[float] = None):
        self.rid = next(DecodeRequest._rids)
        self.prompt = prompt
        self.max_tokens = max_tokens
        self.temperature = temperature
        self.seed = seed
        self.eos_id = eos_id
        self.deadline_ms = deadline_ms
        self.ttft_ms: Optional[float] = None
        self._t_submit = time.perf_counter()
        self._deadline: Optional[float] = (
            self._t_submit + deadline_ms / 1e3
            if deadline_ms is not None else None)
        self._tokens: List[int] = []
        self._cond = threading.Condition()
        self._done = False
        self._error: Optional[BaseException] = None
        # re-dispatch state: a detached request drops producer calls
        # (its old worker may be wedged and wake up later — zombie
        # pushes must not corrupt the adopted stream); the replay
        # budget stops a deterministic dispatch failure from requeueing
        # forever
        self._migrated = False
        self._replays = 0

    # -- producer side (batcher worker) ------------------------------------
    def _push(self, tok: int) -> None:
        with self._cond:
            if self._migrated:
                return
            if self.ttft_ms is None:
                self.ttft_ms = (time.perf_counter()
                                - self._t_submit) * 1e3
                decode_metrics.note_ttft_ms(self.ttft_ms)
            self._tokens.append(int(tok))
            self._cond.notify_all()

    def _finish(self, error: Optional[BaseException] = None) -> None:
        with self._cond:
            if self._migrated:
                return
            self._error = error
            self._done = True
            self._cond.notify_all()

    # -- re-dispatch journal ------------------------------------------------
    def _snapshot_tokens(self) -> np.ndarray:
        """The emitted-so-far half of the replay journal."""
        with self._cond:
            return np.asarray(self._tokens, np.int32)

    def _expired(self, now: float) -> bool:
        return (self._deadline is not None and now > self._deadline
                and not self.done())

    def _detach(self) -> None:
        """Cut the old (dead/wedged) worker off: every later ``_push``/
        ``_finish`` through THIS handle is dropped; only the adopting
        replica's :class:`_ReplayRequest` forwards into it."""
        with self._cond:
            self._migrated = True

    def _force_push(self, tok: int) -> None:
        """Producer path for the adopting replica — bypasses the
        detached guard (the replay shadow is the only caller)."""
        with self._cond:
            if self._done:
                return
            if self.ttft_ms is None:
                self.ttft_ms = (time.perf_counter()
                                - self._t_submit) * 1e3
                decode_metrics.note_ttft_ms(self.ttft_ms)
            self._tokens.append(int(tok))
            self._cond.notify_all()

    def _force_finish(self, error: Optional[BaseException] = None) -> None:
        with self._cond:
            if self._done:
                return
            self._error = error
            self._done = True
            self._cond.notify_all()

    # -- consumer side -----------------------------------------------------
    def done(self) -> bool:
        with self._cond:
            return self._done

    def result(self, timeout: Optional[float] = 120.0) -> np.ndarray:
        """Block until the request finishes; returns the generated
        tokens [n] int32 (prompt excluded)."""
        with self._cond:
            if not self._cond.wait_for(lambda: self._done, timeout):
                raise TimeoutError(
                    f"decode request not finished within {timeout}s")
            if self._error is not None:
                raise self._error
            return np.asarray(self._tokens, np.int32)

    def stream(self, timeout: Optional[float] = 120.0):
        """Yield tokens as they are generated; raises the request's
        error (if any) after the buffered tokens.  Tokens are yielded
        OUTSIDE the request lock: a consumer doing slow work per token
        (or abandoning the generator mid-stream) must never block the
        batcher worker's ``_push`` — that would stall every other
        request on the engine."""
        i = 0
        while True:
            with self._cond:
                ok = self._cond.wait_for(
                    lambda: self._done or len(self._tokens) > i, timeout)
                if not ok:
                    raise TimeoutError(
                        f"no token within {timeout}s")
                pending = self._tokens[i:]
                # _push always precedes _finish, so once done is set the
                # token list cannot grow — this snapshot is final
                finished = self._done
                err = self._error
            for tok in pending:
                i += 1
                yield tok
            if finished:
                if err is not None:
                    raise err
                return


class BatcherClosed(RuntimeError):
    """Typed rejection for a submit racing ``close()``: the batcher's
    closed flag flipped before the request could be enqueued.  Raised
    synchronously — a request is either accepted (and then drains to
    completion) or rejected with this; it can never hang unresolved."""


class _ReplayRequest(DecodeRequest):
    """Shadow of an evacuated request, re-submitted on a healthy
    replica.  Carries the original's full journal — prompt, sampling
    identity, the tokens already streamed — so the adopting batcher
    prefills (prompt + emitted) and continues from the NEXT position
    with the same (seed, position)-folded keys: the continuation is
    bit-identical to an undisturbed run.  Every produced token/finish
    forwards into the original handle (the one the client holds); the
    original's own producer path stays detached, so a wedged old
    worker waking up later cannot interleave stale tokens."""

    def __init__(self, orig: DecodeRequest):
        super().__init__(orig.prompt, orig.max_tokens, orig.temperature,
                         orig.seed, orig.eos_id)
        self._orig = orig
        self.rid = orig.rid             # one request, one id
        # inherit the ABSOLUTE deadline: migration must not extend a
        # request's budget (clients sized it end-to-end)
        self.deadline_ms = orig.deadline_ms
        self._deadline = orig._deadline
        self._t_submit = orig._t_submit
        self.ttft_ms = orig.ttft_ms     # don't re-book a TTFT sample
        self._tokens = [int(t) for t in orig._snapshot_tokens()]
        self._replays = orig._replays + 1

    def _push(self, tok: int) -> None:
        super()._push(tok)
        self._orig._force_push(tok)

    def _finish(self, error: Optional[BaseException] = None) -> None:
        super()._finish(error)
        self._orig._force_finish(error)


class ContinuousBatcher:
    """Streaming front-end over a ``DecodeEngine``: one worker thread
    admits pending requests into free slots (prefill joins between
    decode steps), advances every occupied slot one token per
    iteration in ONE dispatch, recycles slots on EOS/budget, and
    resolves ``DecodeRequest`` handles.  ``close()`` drains: accepted requests
    run to completion, then the worker exits.

    ONE STEP AHEAD.  A plain decode step is dispatched BEFORE the step
    before it is collected (``DecodeEngine.dispatch_step(after=)``): a
    pass dispatches step n+1, then fetches step n and delivers its
    tokens while n+1 runs, and the next pass's expire and admit run
    under it too.  At most one step is ever uncollected.  A step's
    tokens go to the requests that held its slots WHEN IT WAS
    DISPATCHED, and a token whose request has ended meanwhile is
    dropped.  An end by count is known at dispatch, so the slot is
    released there and its successor is admitted while the last token
    is in flight (the device runs its programs in order, so the
    successor's prefill writes the released pages after the step that
    still reads them); an end by ``eos_id`` is known one step late, and
    that slot runs ONE step past it, whose token is dropped and whose
    row, at the slot's own next position, is never attended.

    A SELF-DRAFT ROUND runs one round ahead the same way
    (``DecodeEngine.dispatch_round(after=)``): its commit count is data,
    but the device merges it into the next round's tokens, positions and
    drafts, and the host stages the pages for the most it can be.  A
    round commits at least one token a slot, so the end by count that is
    CERTAIN at dispatch (delivered, + this round's one, + one for a
    round in flight) releases the slot there; any other end (a round
    that commits more, an ``eos_id``) is seen when the round lands, and
    the slot's round behind it is dropped.  A brownout flip of
    ``spec_enabled`` lands what is in flight before the other kind of
    dispatch goes out, and a second model's round (two dispatches) stays
    in series."""

    #: a request is requeued at most this many times after failed
    #: dispatches before its error resolves the future — an injected
    #: one-shot fault replays cleanly, a deterministic dispatch bug
    #: cannot requeue forever
    MAX_REPLAYS = 2

    def __init__(self, engine: DecodeEngine, *,
                 default_max_tokens: int = 64):
        self.engine = engine
        self.default_max_tokens = int(default_max_tokens)
        self._cv = threading.Condition()
        self._pending: List[DecodeRequest] = []
        #: requests the worker has popped from ``_pending`` but not yet
        #: placed (``engine.start`` runs OUTSIDE the lock — prefill is
        #: milliseconds): tracked so ``depth()`` never undercounts
        #: mid-admit requests, or the router's shed bound would admit
        #: over capacity through the pop-to-place window
        self._admitting: List[DecodeRequest] = []
        #: slot -> the request it holds
        self._placed: Dict[int, DecodeRequest] = {}
        #: requests whose LAST token is in flight: their end by count
        #: was known when that step was dispatched, so their slot is
        #: already released (and may hold a successor)
        self._landing: List[DecodeRequest] = []
        #: the step dispatched and not yet collected, with the slot ->
        #: request map it was dispatched for (the worker's own)
        self._flying: Optional[Tuple[_Step, Dict[int, DecodeRequest]]] = None
        self._open = True
        #: health surface the router's monitor polls (plain reads of
        #: worker-written fields — a torn read costs one poll):
        #: consecutive failed dispatches, and when the worker last
        #: admitted or advanced anything
        self.dispatch_error_streak = 0
        self._last_progress = time.perf_counter()
        self._thread = threading.Thread(
            target=self._loop, name="dl4j-decode-batcher", daemon=True)
        self._thread.start()

    # -- client side -------------------------------------------------------
    def submit(self, prompt, max_tokens: Optional[int] = None,
               temperature: float = 0.0, seed: int = 0,
               eos_id: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> DecodeRequest:
        """Enqueue one prompt [T_p] (ints); returns its streaming
        handle.  Prompt-too-long raises synchronously (typed ValueError
        from the bucket ladder).  ``deadline_ms`` bounds the request
        end-to-end (queue wait included): past it the slot frees, the
        pages reclaim, and the future resolves with the typed
        :class:`DeadlineExceeded`."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0: {deadline_ms}")
        max_tokens = int(max_tokens or self.default_max_tokens)
        self.engine.pick_bucket(prompt.size + max_tokens)  # sync validate
        self.engine.check_capacity(prompt.size)  # typed oversize admit
        req = DecodeRequest(prompt, max_tokens, float(temperature),
                            int(seed), eos_id, deadline_ms=deadline_ms)
        with self._cv:
            if not self._open:
                raise BatcherClosed("ContinuousBatcher is closed")
            if not self._pending and not self._placed:
                # restart the stall clock on an idle->busy edge: the
                # monitor's progress_age must measure "has work and
                # isn't moving", not the idle stretch before this
                # request arrived
                self._last_progress = time.perf_counter()
            self._pending.append(req)
            decode_metrics.note_request(prompt.size)
            decode_metrics.note_queue_depth(len(self._pending))
            self._cv.notify()
        return req

    def resubmit(self, req: DecodeRequest) -> None:
        """Adopt an already-journaled request (the router's replay
        path): no re-validation — the original submit validated the
        geometry against an identically-configured engine (the factory
        contract).  The request's emitted-so-far tokens fold into its
        re-prefill at admission."""
        with self._cv:
            if not self._open:
                raise BatcherClosed("ContinuousBatcher is closed")
            if not self._pending and not self._placed:
                self._last_progress = time.perf_counter()  # stall clock
            self._pending.append(req)
            decode_metrics.note_queue_depth(len(self._pending))
            self._cv.notify()

    def generate(self, prompt, timeout: Optional[float] = 120.0,
                 **kw) -> np.ndarray:
        """Blocking convenience: submit + wait for the full result."""
        return self.submit(prompt, **kw).result(timeout)

    def depth(self) -> int:
        """Pending + mid-admit + in-flight request count — the router's
        least-depth dispatch and load-shed signal.  Mid-admit requests
        (popped, prefilling, not yet placed) COUNT: they occupy a slot
        the moment ``engine.start`` returns, and omitting them let a
        racing submit slip past the shed bound."""
        with self._cv:
            return (len(self._pending) + len(self._admitting)
                    + len(self._placed) + len(self._landing))

    # -- health surface (router monitor) -----------------------------------
    def worker_alive(self) -> bool:
        """Is the decode worker thread running?  False means every
        accepted request is stranded — the replica must be replaced."""
        return self._thread.is_alive()

    def progress_age(self) -> float:
        """Seconds since the worker last admitted or advanced anything.
        Meaningful as a STALL signal only while ``depth() > 0`` (an
        idle worker legitimately parks on its condition)."""
        return time.perf_counter() - self._last_progress

    def evacuate(self) -> List[DecodeRequest]:
        """Stop intake and hand back every unfinished request — queued
        AND mid-decode — for deterministic re-dispatch on a healthy
        replica (the router's health-replacement path).  Each request
        is DETACHED first: a wedged worker waking up later pushes into
        a dead handle, never into the adopted stream.  The engine's
        device state is deliberately untouched — the worker may be dead
        or stalled mid-dispatch, and the replica is being discarded
        wholesale; releasing its slots from this (foreign) thread would
        race the engine's single-driver contract."""
        with self._cv:
            self._open = False
            reqs = (list(self._pending) + list(self._admitting)
                    + list(self._placed.values()) + list(self._landing))
            self._pending.clear()
            self._admitting.clear()
            self._placed.clear()
            self._landing.clear()
            self._cv.notify_all()
        out = []
        for r in reqs:
            if not r.done():
                r._detach()
                out.append(r)
        return out

    # -- worker side -------------------------------------------------------
    def _admit(self) -> int:
        """Place as many pending requests as free slots allow; returns
        how many were admitted.  Runs on the worker thread only."""
        admitted = 0
        while True:
            # the lock's wait is the pick's: the callers' threads submit
            # and read the depth under it
            with telemetry.span("decode.admit.pick") as pick:
                with self._cv:
                    pick.set(pending=len(self._pending))
                    req = None
                    for i, r in enumerate(self._pending):
                        # a REPLAYED request re-prefills prompt + emitted
                        # (len(r._tokens) is worker-written only — this
                        # IS the worker); its rung is unchanged because
                        # emitted tokens move from budget to prompt 1:1
                        if self.engine.can_admit(
                                r.prompt.size + len(r._tokens)):
                            req = self._pending.pop(i)
                            self._admitting.append(req)
                            pick.set(rid=req.rid)
                            break
                    if req is None:
                        decode_metrics.note_queue_depth(len(self._pending))
            if req is None:
                return admitted
            # the wait ends here, so it is counted here; a replayed
            # request's runs from its first submit, as its caller's does
            now = time.perf_counter()
            decode_metrics.note_admission(now - req._t_submit)
            telemetry.completed("decode.queue_wait", req._t_submit, now,
                                rid=req.rid)
            joined = self.engine.n_active() > 0
            emitted = req._snapshot_tokens()
            eff_prompt = (np.concatenate([req.prompt, emitted])
                          if emitted.size else req.prompt)
            try:
                # replay is bit-exact because sampling keys fold (seed,
                # POSITION): the token at position p is identical
                # whether p was reached by decode here or by prefilling
                # the journaled stream — prefix-cache hits make the
                # re-prefill cheap
                slot, first = self.engine.start(
                    eff_prompt,
                    max_tokens=req.max_tokens - emitted.size,
                    temperature=req.temperature, seed=req.seed,
                    owner=req)
            except Exception as e:      # resolve, never wedge the client
                with self._cv:
                    if req in self._admitting:
                        self._admitting.remove(req)
                req._finish(e)
                continue
            if joined:
                decode_metrics.note_join()
            telemetry.event("decode.join", rid=req.rid, slot=slot,
                            prompt_tokens=int(eff_prompt.size),
                            mid_flight=joined, replayed=bool(emitted.size))
            admitted += 1
            t_free = self.engine.vacant_since(slot)
            if t_free is not None:
                # the slot's vacancy ends where the request's wait did,
                # before the join (that is ``prefill_s``'s); ``queued``
                # is the part in which this request already existed
                queued = max(0.0, now - max(t_free, req._t_submit))
                decode_metrics.note_slot_turnover(max(0.0, now - t_free),
                                                  queued)
                telemetry.completed("decode.slot_vacant", t_free, now,
                                    slot=slot, rid=req.rid,
                                    queued_ms=queued * 1e3)
            with self._cv:
                self._last_progress = time.perf_counter()
                if req in self._admitting:   # evacuate() may have
                    self._admitting.remove(req)  # adopted it mid-start
                self._placed[slot] = req
            with telemetry.span("decode.first_token", rid=req.rid,
                                slot=slot):
                req._push(first)
                self._maybe_finish(slot, req, first,
                                   n_out=len(req._tokens))

    def _maybe_finish(self, slot: int, req: DecodeRequest, tok: int,
                      n_out: int) -> bool:
        if (req.eos_id is not None and tok == req.eos_id) \
                or n_out >= req.max_tokens:
            with self._cv:
                holds = self._placed.get(slot) is req
            if holds:       # else released when its last step went out
                self.engine.release(slot)
            with self._cv:
                if holds:
                    self._placed.pop(slot, None)
                elif req in self._landing:
                    self._landing.remove(req)
            decode_metrics.note_complete(n_out)
            req._finish()
            telemetry.event("decode.complete", rid=req.rid, slot=slot,
                            tokens=n_out,
                            ttft_ms=round(req.ttft_ms or 0.0, 3))
            return True
        return False

    def _dispatch_ahead(self, flying, rounds: bool) -> None:
        """Dispatch the next plain step (or, where ``rounds``, the next
        self-draft round) for every running slot, behind
        the step or round still in flight (``flying``, which the caller
        lands afterwards).  A slot whose request this dispatch completes
        BY COUNT for certain (each step and each round commits at least
        one token) is released here, its request left to land its last
        token, so the slot is never run again for it and the next pass
        may admit a successor under the dispatch."""
        after = flying and flying[0]
        step = (self.engine.dispatch_round(after=after) if rounds
                else self.engine.dispatch_step(after=after))
        with self._cv:
            owners = {slot: r for slot, r in self._placed.items()
                      if step.run[slot]}
            self._flying = (step, owners)
            # delivered, + this dispatch's least, + the one still in flight
            landing = flying[1] if flying else {}
            done = [(slot, r) for slot, r in owners.items()
                    if len(r._tokens) + 1 + (landing.get(slot) is r)
                    >= r.max_tokens]
            for slot, r in done:
                del self._placed[slot]
                self._landing.append(r)
        for slot, _ in done:
            self.engine.release(slot)

    def _land(self, step, owners: Dict[int, DecodeRequest]) -> None:
        """Collect a dispatched step or round and deliver its tokens (a
        round's commits, in order) to the requests its slots held when
        it went out."""
        if isinstance(step, _Round):
            toks, n_c = self.engine.collect_round(step)
        else:
            toks, n_c = self.engine.collect(step)[:, None], None
        self.dispatch_error_streak = 0
        with telemetry.span("decode.deliver",
                            counter=(decode_metrics, "deliver_s")):
            with self._cv:
                self._last_progress = time.perf_counter()
            dropped = 0
            for slot, r in owners.items():
                if r.done():    # ended (eos, deadline, eviction) since
                    dropped += 1
                    continue
                for tok in toks[slot, :1 if n_c is None else n_c[slot]]:
                    r._push(int(tok))
                    if self._maybe_finish(slot, r, int(tok),
                                          n_out=len(r._tokens)):
                        break
            if dropped:
                decode_metrics.note_overshoot(dropped)

    def _spec_round(self) -> None:
        """One speculative round of a second model as the draft:
        dispatched, fetched and delivered in series."""
        out, n_c = self.engine.advance_spec()
        self.dispatch_error_streak = 0
        with telemetry.span("decode.deliver",
                            counter=(decode_metrics, "deliver_s")):
            ran = self.engine.last_ran()
            with self._cv:
                self._last_progress = time.perf_counter()
                owned = list(self._placed.items())
            for slot, r in owned:
                if not ran[slot]:
                    continue        # stalled on pages; retried next pass
                for j in range(int(n_c[slot])):
                    tok = int(out[slot, j])
                    r._push(tok)
                    if self._maybe_finish(slot, r, tok,
                                          n_out=len(r._tokens)):
                        break

    def _replay_all(self, e: Exception) -> None:
        """A failed dispatch poisons in-flight device state (it was
        donated): the failure drops the pool, so EVERY slot's KV is
        gone.  Free every slot (the page reclaim is host-side
        bookkeeping and stays valid) and REPLAY the requests instead of
        dooming them: re-admitted as (prompt + emitted), each continues
        bit-identically; the tokens of a step that never landed were
        never delivered, so the replay makes them again.  Past the
        replay budget the error resolves the future — a deterministic
        dispatch bug must not requeue forever."""
        self.dispatch_error_streak += 1
        with self._cv:
            placed = list(self._placed.items())
            lost = [r for _, r in placed] + self._landing
            self._placed.clear()
            self._landing = []
            self._flying = None
        for slot, _ in placed:
            self.engine.release(slot)
        replay = []
        for r in lost:
            if r.done():
                continue
            if r._replays >= self.MAX_REPLAYS:
                r._finish(e)
            else:
                r._replays += 1
                replay.append(r)
                decode_metrics.note_request_replayed()
        if replay:
            with self._cv:
                self._pending[:0] = replay

    def _advance_all(self) -> Tuple[int, int]:
        """ONE dispatch for every running slot, whatever their rungs,
        and the tokens of the step (or round) before it delivered while
        it runs; returns (dispatches made, whether the pass touched the
        engine at all: a step landed, or a dispatch failed), each 0 or
        1."""
        eng = self.engine
        flying, self._flying = self._flying, None
        # what the engine observes decides, once a pass (the brownout
        # ladder flips ``spec_enabled`` from the router's thread): a
        # self-draft round, a second model's round in series, or a step
        spec = eng.draft is not None and eng.spec_enabled
        rounds = spec and eng.draft == "self"
        series = spec and not rounds
        touched = int(flying is not None)
        dispatched = 0
        try:
            if flying is not None and (
                    series or isinstance(flying[0], _Round) != rounds):
                # a second model's round is in series, and a brownout
                # flip lands what is in flight before the other kind of
                # dispatch goes out
                self._land(*flying)
                flying = None
            with self._cv:
                # what THIS batcher placed: an evacuated one's slots stay
                # active in an engine that is discarded with it
                running = bool(self._placed)
            if running:
                try:
                    if series:
                        self._spec_round()
                    else:
                        self._dispatch_ahead(flying, rounds)
                    dispatched = 1
                except KVPagesExhausted as e:
                    # page deadlock breaker: the pool cannot advance ANY
                    # slot — evict the named victim (typed error to its
                    # client; its pages free the others).  Raised while
                    # staging: nothing was dispatched
                    if e.slot is None:
                        raise
                    with self._cv:
                        r = self._placed.pop(e.slot, None)
                    eng.release(e.slot)
                    if r is not None:
                        r._finish(e)
            if flying is not None:
                self._land(*flying)
        except Exception as e:
            self._replay_all(e)
            return 0, 1     # its spans booked their seconds: no idle pass
        return dispatched, touched

    def _expire(self) -> None:
        """Free every deadline-expired request (worker thread): queued
        ones simply leave the queue; placed ones release their slot —
        reclaiming their KV pages — so an expired request never
        occupies capacity a live one could use.  Each resolves with
        the typed :class:`DeadlineExceeded`."""
        now = time.perf_counter()
        with self._cv:
            exp_q = [r for r in self._pending if r._expired(now)]
            for r in exp_q:
                self._pending.remove(r)
            exp_s = [(slot, r) for slot, r in self._placed.items()
                     if r._expired(now)]
            for slot, _ in exp_s:
                self._placed.pop(slot, None)
        for slot, _ in exp_s:
            self.engine.release(slot)
        for r in exp_q + [r for _, r in exp_s]:
            decode_metrics.note_deadline_expiration()
            r._finish(DeadlineExceeded(
                r.deadline_ms, (now - r._t_submit) * 1e3,
                len(r._tokens)))
            telemetry.event("decode.deadline_exceeded", rid=r.rid,
                            deadline_ms=r.deadline_ms,
                            tokens=len(r._tokens))

    def _loop(self) -> None:
        while True:
            with self._cv:
                while self._open and not self._pending \
                        and not self._placed and self._flying is None:
                    with telemetry.span("decode.wait"):
                        self._cv.wait()
                if not self._open and not self._pending \
                        and not self._placed and self._flying is None:
                    return
            with telemetry.span("decode.round",
                                counter=(decode_metrics, "round_s")) as sp:
                with telemetry.span(
                        "decode.expire",
                        counter=(decode_metrics, "expire_s")) as sp_e:
                    self._expire()
                with telemetry.span(
                        "decode.admit",
                        counter=(decode_metrics, "admit_s")) as sp_a:
                    admitted = self._admit()
                advanced, touched = self._advance_all()
                if admitted or advanced:
                    decode_metrics.note_round()
                elif not touched:
                    # a pass that found nothing to do is no round, and
                    # its children book nothing either, or their sum
                    # could pass ``round_s``
                    for idle in (sp_e, sp_a, sp):
                        idle.discard()
            with self._cv:
                if self._open and not admitted and not self._placed \
                        and self._pending and self._flying is None:
                    # capacity-stalled: nothing is placed to advance
                    # and nothing pending fits — a timed wait instead
                    # of a hot spin (submit/close notifies early; the
                    # timeout keeps deadline expiry ticking)
                    with telemetry.span("decode.wait"):
                        self._cv.wait(0.005)

    # -- lifecycle ---------------------------------------------------------
    def close(self, timeout: float = 120.0) -> None:
        """Stop accepting, drain accepted requests to completion, join
        the worker, and stop the engine's prefix-harvest worker (the
        engine itself stays usable — a new batcher over it respawns
        harvesting on demand)."""
        with self._cv:
            self._open = False
            self._cv.notify_all()
        self._thread.join(timeout)
        self.engine.close()

    def __enter__(self) -> "ContinuousBatcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
