"""Everything the ``closed_loop_family`` driver needs to know of the
Mellum family, for a configuration file that names it (``"family":
"mellum"``): weights from the seed, the program's config object, the
engine's arguments, what the algorithm NEEDS in operations and bytes,
and the call of the plain reference.

The configuration file holds the published ``config.json`` keys; the cut
(``layers``, the longest context) is in the keys its ``reduced`` lists.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark.lib import spec
from benchmark.lib.weights import seed_key

EXPERT_MATRICES = 3          # gate, up, down
DTYPE_BYTES = 2              # bfloat16: weights and cached rows
WINDOW, FULL = "sliding_attention", "full_attention"
PERIOD = (WINDOW, WINDOW, WINDOW, FULL)


def sizes(c: Dict[str, Any]) -> Dict[str, int]:
    kinds = c["layer_types"][:c["layers"]]
    return {"H": c["hidden_size"], "L": c["layers"],
            "NH": c["num_attention_heads"], "KV": c["num_key_value_heads"],
            "D": c["head_dim"], "F": c["moe_intermediate_size"],
            "E": c["num_experts"],
            "V": c["vocab_size"], "W": c["sliding_window"],
            "L_full": kinds.count(FULL), "L_win": kinds.count(WINDOW)}


# -- weights ---------------------------------------------------------------

def param_shapes(c: Dict[str, Any]) -> Dict[str, Any]:
    """The tree the program and the reference read (the reference's
    docstring lists it)."""
    s = sizes(c)
    H, L, D, F, E = s["H"], s["L"], s["D"], s["F"], s["E"]
    return {"embed": (s["V"], H),
            "layers": {"attn_norm": (L, H), "ffn_norm": (L, H),
                       "w_q": (L, H, s["NH"] * D), "w_k": (L, H, s["KV"] * D),
                       "w_v": (L, H, s["KV"] * D), "w_o": (L, s["NH"] * D, H),
                       "q_norm": (L, D), "k_norm": (L, D),
                       "router": (L, H, E)},
            "experts": {"w_gate": (L * E, H, F), "w_up": (L * E, H, F),
                        "w_down": (L * E, F, H)},
            "final_norm": (H,), "head": (H, s["V"])}


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make_params(c: Dict[str, Any], seed: int) -> Any:
    """Every leaf from ``--seed`` on the device, a jitted call a leaf and
    inside it a layer at a time (so that no more than one layer's float32
    draw of one leaf is ever live beside the tree): N(0, ``init_std``),
    norm gains 1 + that, rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    std = float(c.get("init_std", 0.02))
    L = sizes(c)["L"]

    def draw(key, shape, gain):
        w = std * jax.random.normal(key, shape, jnp.float32)
        return ((1.0 + w) if gain else w).astype(jnp.bfloat16)

    def stacked(key, shape, gain):
        # [L, ...] (the experts' [L * E, ...] as [L, E, ...])
        per = (shape[1:] if shape[0] == L
               else (shape[0] // L,) + shape[1:])
        out = jax.lax.map(lambda k: draw(k, per, gain),
                          jax.random.split(key, L))
        return out.reshape(shape)

    draw_j = jax.jit(draw, static_argnums=(1, 2))
    stacked_j = jax.jit(stacked, static_argnums=(1, 2))
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(c), is_leaf=_is_shape)
    keys = jax.random.split(seed_key(seed, stream=0), len(paths))
    leaves = []
    for k, (path, shape) in zip(keys, paths):
        gain = str(path[-1].key).endswith("norm")
        fn = stacked_j if len(path) > 1 else draw_j
        leaves.append(fn(k, shape, gain))
    return jax.tree.unflatten(treedef, leaves)


def program_config(c: Dict[str, Any]):
    """The one place the benchmark names the program's model family."""
    from deeplearning4j_tpu.models import mellum as ml

    kinds = tuple(c["layer_types"][:c["layers"]])
    if kinds != PERIOD * (c["layers"] // len(PERIOD)):
        raise ValueError(f"the {c['layers']} layers kept are not whole "
                         f"periods of {PERIOD}: {kinds}")
    full = c["rope_parameters"]["full_attention"]
    plain = c["rope_parameters"]["sliding_attention"]
    if (full["rope_type"], plain["rope_type"]) != ("yarn", "default") \
            or full["rope_theta"] != plain["rope_theta"]:
        raise ValueError(f"rope_parameters the program has no code for: "
                         f"{c['rope_parameters']}")
    if not (c["norm_topk_prob"] and c["use_sliding_window"]):
        raise ValueError("the program renormalises the taken experts and "
                         "windows the sliding layers")
    return ml.MellumConfig(
        vocab_size=c["vocab_size"], max_len=c["max_position_embeddings"],
        hidden=c["hidden_size"], n_layers=c["layers"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        moe_intermediate_size=c["moe_intermediate_size"],
        num_experts=c["num_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        sliding_window=c["sliding_window"], period=PERIOD,
        rms_norm_eps=float(c["rms_norm_eps"]),
        rope_theta=float(full["rope_theta"]),
        rope_factor=float(full["factor"]),
        rope_original_max_len=full["original_max_position_embeddings"],
        rope_beta_fast=float(full["beta_fast"]),
        rope_beta_slow=float(full["beta_slow"]),
        rope_attention_factor=float(full["attention_factor"]),
        compute_dtype=c["compute_dtype"])


def engine_kwargs(c: Dict[str, Any], tr: Dict[str, Any]) -> Dict[str, Any]:
    """``DecodeEngine``'s arguments beside config, weights and slots."""
    out: Dict[str, Any] = {"prefill_chunk": int(tr["prefill_chunk"])}
    if tr.get("buckets"):
        out["buckets"] = [int(b) for b in tr["buckets"]]
    if tr.get("n_pages"):
        out["n_pages"] = int(tr["n_pages"])
    return out


def vocab(c: Dict[str, Any]) -> int:
    return int(c["vocab_size"])


# -- what the algorithm needs ----------------------------------------------

def attention_params(c: Dict[str, Any]) -> int:
    """W_q, W_k, W_v, W_o of one layer: 21,233,664."""
    s = sizes(c)
    return (2 * s["H"] * s["NH"] * s["D"] + 2 * s["H"] * s["KV"] * s["D"])


def expert_params(c: Dict[str, Any]) -> int:
    s = sizes(c)
    return EXPERT_MATRICES * s["H"] * s["F"]


def layer_params(c: Dict[str, Any]) -> int:
    """Every element of one layer: attention, router, the four gains and
    the experts: 417,747,712."""
    s = sizes(c)
    return (attention_params(c) + s["H"] * s["E"] + 2 * s["H"] + 2 * s["D"]
            + s["E"] * expert_params(c))


def total_params(c: Dict[str, Any]) -> int:
    """Every element of the tree."""
    s = sizes(c)
    return s["L"] * layer_params(c) + 2 * s["V"] * s["H"] + s["H"]


def nonrouted_params(c: Dict[str, Any]) -> int:
    """Parameters in a matrix product for EVERY token: attention and the
    router of every layer, and the head.  The embedding is a look-up;
    norms are not products."""
    s = sizes(c)
    return (s["L"] * (attention_params(c) + s["H"] * s["E"])
            + s["H"] * s["V"])


def attention_flops_per_position(c: Dict[str, Any]) -> float:
    """FLOPs one query token spends on ONE attended position of ONE
    layer: q . k and p . v over D lanes, a query head."""
    s = sizes(c)
    return 4.0 * s["NH"] * s["D"]


def attended(c: Dict[str, Any], full_rows: float, window_rows: float
             ) -> float:
    """Rows attended over all layers, given one full layer's and one
    window layer's."""
    s = sizes(c)
    return s["L_full"] * full_rows + s["L_win"] * window_rows


def forward_flops_token(c: Dict[str, Any], context: float,
                        held_per_token_layer: float, folded: bool) -> float:
    """Forward FLOPs of one token attending ``context`` positions on a
    full layer and ``min(context, sliding_window)`` on a window layer: 2
    a parameter in a product — the non-routed ones and, a layer,
    ``held_per_token_layer`` experts (all 8 of a token's are held here).
    ``folded`` is the driver's word for a decode step; this family's
    attention has one form."""
    s = sizes(c)
    return (2.0 * nonrouted_params(c)
            + 2.0 * s["L"] * held_per_token_layer * expert_params(c)
            + attention_flops_per_position(c)
            * attended(c, context, min(context, s["W"])))


def sequence_forward_flops(c: Dict[str, Any], n: int,
                           held_per_token_layer: float) -> float:
    """A prompt of ``n`` tokens, the token at position i attending i + 1
    rows (the window's at most)."""
    w = min(n, sizes(c)["W"])
    full = n * (n + 1) / 2.0
    window = w * (w + 1) / 2.0 + (n - w) * w
    return (n * forward_flops_token(c, 0.0, held_per_token_layer, False)
            + attention_flops_per_position(c) * attended(c, full, window))


def cache_bytes_row(c: Dict[str, Any]) -> int:
    """Bytes of one cached position of ONE layer, K and V: 2,048 B."""
    s = sizes(c)
    return 2 * s["KV"] * s["D"] * DTYPE_BYTES


def expert_bytes(c: Dict[str, Any]) -> int:
    """One expert's three matrices: 12.39 MB."""
    return expert_params(c) * DTYPE_BYTES


def pages_bytes(c: Dict[str, Any], n_pages: tuple, page_tokens: int) -> int:
    """The pool's two slabs: ``n_pages`` of the (full, window) kind."""
    s = sizes(c)
    return page_tokens * cache_bytes_row(c) * (
        s["L_full"] * n_pages[0] + s["L_win"] * n_pages[1])


def decode_needed(c: Dict[str, Any], contexts_sum: float, n_tokens: int,
                  dispatches: float, expert_hits: float,
                  assignments_held: float) -> Dict[str, float]:
    """What decoding ``n_tokens`` tokens in ``dispatches`` dispatches
    needs.  FLOPs: the tokens' own.  Bytes: one pass over the non-routed
    weights a dispatch, each distinct expert a dispatch touched once
    (``expert_hits``, the program's count summed over layers and
    dispatches), and each token's live cached rows: its whole context on
    a full layer, the window's at most on a window layer.  The driver
    hands over the SUM of the contexts only, so the window layers' rows
    are ``min(contexts_sum, window x n_tokens)``: over by what the
    tokens with less context than the window fall under it."""
    s = sizes(c)
    rows = attended(c, contexts_sum, min(contexts_sum, s["W"] * n_tokens))
    flops = (2.0 * nonrouted_params(c) * n_tokens
             + 2.0 * assignments_held * expert_params(c)
             + attention_flops_per_position(c) * rows)
    nbytes = (dispatches * nonrouted_params(c) * DTYPE_BYTES
              + expert_hits * expert_bytes(c)
              + rows * cache_bytes_row(c))
    return {"flops": flops, "bytes": nbytes,
            "expert_bytes": expert_hits * expert_bytes(c)
            + 2.0 * assignments_held * s["H"] * 4.0}


# -- the reference -----------------------------------------------------------

class Tail:
    """The reference's logits at the LAST positions of a row, addressed
    by the row's own positions as the driver slices them (``[a:b]``): a
    whole row's would be 3 GB at 7,800 positions of 98,304."""

    def __init__(self, first: int, values: np.ndarray):
        self.first, self.values = first, values

    def __getitem__(self, span: slice) -> np.ndarray:
        if span.start < max(self.first, 0) or span.step is not None:
            raise IndexError(f"{span} reaches before position {self.first}, "
                             f"the first the reference kept")
        return self.values[span.start - self.first:span.stop - self.first]


def reference_logits(c: Dict[str, Any], params: Any, rows: List[np.ndarray],
                     tr: Dict[str, Any], precision: str = "f32"
                     ) -> List[Tail]:
    """Reference logits at the last ``output_len.max`` positions of each
    row (a row is a prompt and its served tokens but the last, and the
    positions compared are those that predicted a served token), the
    rows padded to one length (a multiple of ``reference_pad``; causal:
    the padding is never attended) and taken
    ``reference_rows_per_block`` at a time."""
    import jax.numpy as jnp

    ref = spec.reference(c["reference"])
    pad = int(tr.get("reference_pad", 256))
    step = int(tr["reference_rows_per_block"])
    last = int(tr["output_len"]["max"])
    T = -(-max(len(r) for r in rows) // pad) * pad
    out: List[Tail] = []
    for lo in range(0, len(rows), step):
        block = rows[lo:lo + step]
        ids = np.zeros((len(block), T), np.int32)
        for i, r in enumerate(block):
            ids[i, :len(r)] = r
        at = np.stack([np.arange(len(r) - last, len(r)) for r in block])
        logits = np.asarray(ref.logits(
            params, jnp.asarray(ids), config=c, precision=precision,
            at=np.maximum(at, 0),
            q_block=int(tr.get("reference_q_block", 256)),
            expert_block=int(tr.get("reference_expert_block", 256))))
        out.extend(Tail(len(r) - last, logits[i])
                   for i, r in enumerate(block))
    return out
