"""The one general traffic generator.  A traffic mix is a data file of
parameters under ``traffic/``; everything a driver sends comes from
here, drawn from ``--seed``.

Every seed gets the same multiset of sizes (they come from the file's
own ``shape_seed``); the run's seed draws the token ids and the order of
the sizes, so that two seeds differ in content and order, not in the
amount of work.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, NamedTuple

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         int(stream)]))


def lengths(spec: Dict[str, Any], n: int, gen: np.random.Generator
            ) -> np.ndarray:
    """``n`` lengths that stand for all of ``{"dist": "lognormal",
    "median", "sigma", "min", "max"}`` or ``{"dist": "uniform", "min",
    "max", "step"}``: not ``n`` draws but the distribution's own
    quantiles at (i + 1/2) / n, in an order shuffled by ``gen``."""
    dist = spec["dist"]
    q = (np.arange(n) + 0.5) / n
    if dist == "uniform":
        step = int(spec.get("step", 1))
        lo, hi = int(spec["min"]), int(spec["max"])
        x = lo + np.floor(q * ((hi - lo) // step + 1)) * step
    elif dist == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(p) for p in q])
        x = np.rint(float(spec["median"]) * np.exp(float(spec["sigma"]) * z))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    x = np.clip(x, int(spec["min"]), int(spec["max"])).astype(np.int64)
    return gen.permutation(x)


def successor_rows(vocab: int, n_rows: int, T: int, gen: np.random.Generator,
                   distinct_ids: int = 64, noise: float = 0.1) -> np.ndarray:
    """A learnable token stream (after ``chip_smoke.token_stream``): a
    fixed successor map over ``distinct_ids`` token ids spread across
    the whole vocabulary, followed except with probability ``noise``.
    Rows start at random ids, so all rows differ."""
    ids = np.unique(np.linspace(1, vocab - 1, distinct_ids).astype(np.int64))
    succ = gen.permutation(len(ids))
    x = np.empty((n_rows, T), np.int64)
    x[:, 0] = gen.integers(0, len(ids), n_rows)
    jump = gen.random((n_rows, T)) < noise
    rand = gen.integers(0, len(ids), (n_rows, T))
    for t in range(1, T):
        x[:, t] = np.where(jump[:, t], rand[:, t], succ[x[:, t - 1]])
    return ids[x].astype(np.int32)


def train_batches(traffic: Dict[str, Any], vocab: int, seed: int
                  ) -> List[np.ndarray]:
    """``distinct_batches`` batches [rows, seq_len] int32; a window
    cycles through them."""
    s = traffic["stream"]
    if s["kind"] != "successor_map":
        raise ValueError(f"unknown stream kind {s['kind']!r}")
    B, T, n = traffic["rows"], traffic["seq_len"], traffic["distinct_batches"]
    rows = successor_rows(vocab, B * n, T, rng(seed, 1),
                          int(s["distinct_ids"]), float(s["noise"]))
    return [rows[i * B:(i + 1) * B] for i in range(n)]


class Request(NamedTuple):
    prompt: np.ndarray      # [P] int32, uniform over the vocabulary
    max_tokens: int


class Requests:
    """A run's requests by index.  Request ``i`` has the ``i % n_shapes``-th
    (prompt, output) length pair of the list and token ids of its own,
    drawn from (seed, i): a caller that runs past the end of the list
    meets the same sizes again, never the same prompt, which would hit
    the prefix cache.  No two requests share a prefix beyond chance."""

    def __init__(self, shapes: List[tuple], vocab: int, seed: int):
        self.shapes = shapes
        self._vocab = vocab
        self._seed = int(seed)

    def __len__(self) -> int:
        return len(self.shapes)

    def __getitem__(self, index: int) -> Request:
        p_len, o_len = self.shapes[index % len(self.shapes)]
        gen = np.random.default_rng(np.random.SeedSequence(
            [self._seed, 3, int(index)]))
        return Request(gen.integers(0, self._vocab, p_len).astype(np.int32),
                       o_len)


def requests(traffic: Dict[str, Any], vocab: int, seed: int) -> Requests:
    """The run's request list: ``n_shapes`` (prompt, output) length
    pairs, the two distributions' quantiles paired at random by the
    file's ``shape_seed``, in the seed's own order."""
    n = int(traffic["n_shapes"])
    shapes = rng(int(traffic["shape_seed"]), 2)
    p_len = lengths(traffic["prompt_len"], n, shapes)
    o_len = lengths(traffic["output_len"], n, shapes)
    return Requests([(int(p_len[i]), int(o_len[i]))
                     for i in rng(seed, 3).permutation(n)], vocab, seed)
