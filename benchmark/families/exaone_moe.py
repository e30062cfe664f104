"""Everything the ``closed_loop_family`` driver needs to know of the
K-EXAONE family, for a configuration file that names it (``"family":
"exaone_moe"``): weights from the seed, the program's config object, the
engine's arguments (the model's own MTP block as the draft of a
speculative round), what the algorithm NEEDS in operations and bytes,
and the call of the plain reference.

The configuration file holds the published ``config.json`` keys; the
rank's cut (``layers``, ``num_experts`` held, the vocabulary's slice,
the longest context) is in the keys its ``reduced`` lists.

What is NEEDED is the main model's work for the tokens that were
COMMITTED, whatever implements it: the non-routed weights of the main
stack once a dispatch, each main-stack expert a dispatch touched once
(the program's count), the live cached rows of the committed tokens.
The MTP block's work and a rejected draft's rows are how this program
gets more than one token out of a pass over the weights; they are in
the measured time and not in the need, so a share of a roofline can only
read lower for them.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark.families.mellum import Tail
from benchmark.lib import spec
from benchmark.lib.weights import seed_key

EXPERT_MATRICES = 3          # gate, up, down
DTYPE_BYTES = 2              # bfloat16: weights and cached rows
WINDOW, FULL = "sliding_attention", "full_attention"
PERIOD = (WINDOW, WINDOW, WINDOW, FULL)


def sizes(c: Dict[str, Any]) -> Dict[str, int]:
    kinds = c["layer_types"][:c["layers"]]
    dense = c["mlp_layer_types"][:c["layers"]].count("dense")
    return {"H": c["hidden_size"], "L": c["layers"],
            "NH": c["num_attention_heads"], "KV": c["num_key_value_heads"],
            "D": c["head_dim"], "I": c["intermediate_size"],
            "F": c["moe_intermediate_size"], "E": c["num_experts"],
            "E_all": c["router_width"],
            "Fs": c["moe_intermediate_size"] * c["num_shared_experts"],
            "K": c["num_experts_per_tok"], "V": c["vocab_size"],
            "W": c["sliding_window"], "dense": dense,
            "sparse": c["layers"] - dense,
            "L_full": kinds.count(FULL), "L_win": kinds.count(WINDOW),
            "mtp": c["num_nextn_predict_layers"]}


def held(c: Dict[str, Any]) -> tuple:
    return (int(c["held_experts_first"]), int(c["num_experts"]))


# -- weights ---------------------------------------------------------------

def param_shapes(c: Dict[str, Any]) -> Dict[str, Any]:
    """The tree the program and the reference read (the reference's
    docstring lists it)."""
    s = sizes(c)
    H, D = s["H"], s["D"]

    def ffn(width):
        return {"w_gate": (H, width), "w_up": (H, width),
                "w_down": (width, H)}

    def block(dense):
        layer: Dict[str, Any] = {
            "attn": {"w_q": (H, s["NH"] * D), "w_k": (H, s["KV"] * D),
                     "w_v": (H, s["KV"] * D), "w_o": (s["NH"] * D, H),
                     "q_norm": (D,), "k_norm": (D,)},
            "attn_out_norm": (H,), "ffn_out_norm": (H,)}
        if dense:
            layer["mlp"] = ffn(s["I"])
        else:
            layer["moe"] = {
                "router": (H, s["E_all"]), "bias": (s["E_all"],),
                "experts": {"w_gate": (s["E"], H, s["F"]),
                            "w_up": (s["E"], H, s["F"]),
                            "w_down": (s["E"], s["F"], H)},
                "shared": ffn(s["Fs"])}
        return layer

    shapes = {"embed": (s["V"], H),
              "layers": [block(kind == "dense")
                         for kind in c["mlp_layer_types"][:s["L"]]],
              "final_norm": (H,), "head": (H, s["V"])}
    if s["mtp"]:
        shapes["mtp"] = {"h_norm": (H,), "e_norm": (H,), "w_eh": (2 * H, H),
                         "block": block(False), "out_norm": (H,)}
    return shapes


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make_params(c: Dict[str, Any], seed: int) -> Any:
    """Every leaf from ``--seed`` on the device, a jitted call a leaf
    (so that no more than one leaf's float32 draw is ever live beside
    the tree): N(0, ``init_std``), the router's selection bias too, norm
    gains 1 + that, rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    std = float(c.get("init_std", 0.02))

    def draw(key, shape, gain):
        w = std * jax.random.normal(key, shape, jnp.float32)
        return (1.0 + w if gain else w).astype(jnp.bfloat16)

    draw = jax.jit(draw, static_argnums=(1, 2))
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(c), is_leaf=_is_shape)
    keys = jax.random.split(seed_key(seed, stream=0), len(paths))
    return jax.tree.unflatten(treedef, [
        draw(k, shape, str(path[-1].key).endswith("norm"))
        for k, (path, shape) in zip(keys, paths)])


def program_config(c: Dict[str, Any]):
    """The one place the benchmark names the program's model family."""
    from deeplearning4j_tpu.models import exaone_moe as ex

    s = sizes(c)
    kinds = tuple(c["layer_types"][:s["L"]])
    if kinds != tuple(PERIOD[l % len(PERIOD)] for l in range(s["L"])):
        raise ValueError(f"the {s['L']} layers kept do not follow the "
                         f"period {PERIOD}: {kinds}")
    if c["mlp_layer_types"][:s["L"]] != (
            ["dense"] * c["first_k_dense_replace"]
            + ["sparse"] * (s["L"] - c["first_k_dense_replace"])):
        raise ValueError("the dense layers are not the leading "
                         "first_k_dense_replace")
    rope = c["rope_parameters"]
    if (rope.get("rope_type", "default") != "default"
            or c["scoring_func"] != "sigmoid" or not c["norm_topk_prob"]
            or (c["n_group"], c["topk_group"]) != (1, 1)
            or c["mtp_layer_types"] != [FULL] * s["mtp"]):
        raise ValueError("a rotary table, a router or an MTP block the "
                         "program has no code for")
    return ex.ExaoneMoeConfig(
        vocab_size=s["V"], max_len=c["max_position_embeddings"],
        hidden=s["H"], n_layers=s["L"], n_heads=s["NH"], n_kv_heads=s["KV"],
        head_dim=s["D"], intermediate_size=s["I"],
        moe_intermediate_size=s["F"],
        first_k_dense_replace=c["first_k_dense_replace"],
        num_experts=s["E_all"], num_experts_per_tok=s["K"],
        num_shared_experts=c["num_shared_experts"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        sliding_window=s["W"], period=PERIOD,
        rms_norm_eps=float(c["rms_norm_eps"]),
        rope_theta=float(rope["rope_theta"]), n_mtp=s["mtp"],
        held_experts=held(c), compute_dtype=c["compute_dtype"])


def engine_kwargs(c: Dict[str, Any], tr: Dict[str, Any]) -> Dict[str, Any]:
    """``DecodeEngine``'s arguments beside config, weights and slots;
    ``draft`` of the traffic file switches the engine's self-draft on."""
    out: Dict[str, Any] = {"prefill_chunk": int(tr["prefill_chunk"])}
    if tr.get("buckets"):
        out["buckets"] = [int(b) for b in tr["buckets"]]
    if tr.get("n_pages"):
        out["n_pages"] = int(tr["n_pages"])
    if tr.get("draft"):
        out["draft"] = tr["draft"]
        out["draft_k"] = int(tr["draft_k"])
    return out


def vocab(c: Dict[str, Any]) -> int:
    return int(c["vocab_size"])


# -- what the algorithm needs ----------------------------------------------

def attention_params(c: Dict[str, Any]) -> int:
    """W_q, W_k, W_v, W_o of one layer: 113,246,208."""
    s = sizes(c)
    return 2 * s["H"] * s["NH"] * s["D"] + 2 * s["H"] * s["KV"] * s["D"]


def expert_params(c: Dict[str, Any]) -> int:
    s = sizes(c)
    return EXPERT_MATRICES * s["H"] * s["F"]


def _gains(c: Dict[str, Any]) -> int:
    s = sizes(c)
    return 2 * s["H"] + 2 * s["D"]


def sparse_layer_params(c: Dict[str, Any]) -> int:
    """Every element of one expert layer as this rank holds it:
    attention, router and its bias, the four gains, the shared expert,
    the 16 held experts: 755,790,080."""
    s = sizes(c)
    return (attention_params(c) + s["H"] * s["E_all"] + s["E_all"]
            + _gains(c) + EXPERT_MATRICES * s["H"] * s["Fs"]
            + s["E"] * expert_params(c))


def dense_layer_params(c: Dict[str, Any]) -> int:
    """Every element of the leading dense layer: 453,009,664."""
    s = sizes(c)
    return attention_params(c) + _gains(c) + EXPERT_MATRICES * s["H"] * s["I"]


def mtp_params(c: Dict[str, Any]) -> int:
    """The MTP block: an expert layer, the joining matrix, three gains."""
    s = sizes(c)
    return s["mtp"] * (sparse_layer_params(c) + 2 * s["H"] * s["H"]
                       + 3 * s["H"])


def total_params(c: Dict[str, Any]) -> int:
    """Every element of the tree."""
    s = sizes(c)
    return (s["dense"] * dense_layer_params(c)
            + s["sparse"] * sparse_layer_params(c) + mtp_params(c)
            + 2 * s["V"] * s["H"] + s["H"])


def nonrouted_params(c: Dict[str, Any]) -> int:
    """Parameters of the MAIN stack in a matrix product for EVERY token:
    attention of every layer, the dense layer's feed-forward, router and
    shared expert of every expert layer, and the head.  The embedding is
    a look-up; norms are not products; the MTP block drafts and is no
    part of a token's own forward."""
    s = sizes(c)
    return (s["L"] * attention_params(c)
            + s["dense"] * EXPERT_MATRICES * s["H"] * s["I"]
            + s["sparse"] * (s["H"] * s["E_all"]
                             + EXPERT_MATRICES * s["H"] * s["Fs"])
            + s["H"] * s["V"])


def attention_flops_per_position(c: Dict[str, Any]) -> float:
    """FLOPs one query token spends on ONE attended position of ONE
    layer: q . k and p . v over D lanes, a query head."""
    s = sizes(c)
    return 4.0 * s["NH"] * s["D"]


def attended(c: Dict[str, Any], full_rows: float, window_rows: float
             ) -> float:
    """Rows attended over the main stack's layers, given one full
    layer's and one window layer's."""
    s = sizes(c)
    return s["L_full"] * full_rows + s["L_win"] * window_rows


def forward_flops_token(c: Dict[str, Any], context: float,
                        held_per_token_layer: float, folded: bool) -> float:
    """Forward FLOPs of one token through the MAIN stack, attending
    ``context`` positions on a full layer and ``min(context,
    sliding_window)`` on a window layer: 2 a parameter in a product —
    the non-routed ones and, an expert layer, ``held_per_token_layer``
    experts.  ``folded`` is the driver's word for a decode step; this
    family's attention has one form."""
    s = sizes(c)
    return (2.0 * nonrouted_params(c)
            + 2.0 * s["sparse"] * held_per_token_layer * expert_params(c)
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
    """Bytes of one cached position of ONE layer, K and V: 4,096 B."""
    s = sizes(c)
    return 2 * s["KV"] * s["D"] * DTYPE_BYTES


def expert_bytes(c: Dict[str, Any]) -> int:
    """One expert's three matrices: 75.50 MB."""
    return expert_params(c) * DTYPE_BYTES


def pages_bytes(c: Dict[str, Any], n_pages: tuple, page_tokens: int) -> int:
    """The pool's two slabs: ``n_pages`` of the (full, window) kind; the
    full slab holds the MTP block's layer behind the main stack's."""
    s = sizes(c)
    return page_tokens * cache_bytes_row(c) * (
        (s["L_full"] + s["mtp"]) * n_pages[0] + s["L_win"] * n_pages[1])


def decode_needed(c: Dict[str, Any], contexts_sum: float, n_tokens: int,
                  dispatches: float, expert_hits: float,
                  assignments_held: float) -> Dict[str, float]:
    """What decoding ``n_tokens`` COMMITTED tokens in ``dispatches``
    dispatches (rounds) needs of the main model.  FLOPs: the tokens'
    own, each expert layer at the rank's expected share of a token's
    experts (``assignments_held`` counts a rejected draft's rows too, so
    it is not what the committed tokens needed).  Bytes: one pass over
    the non-routed weights a dispatch, each distinct main-stack expert a
    dispatch touched once (``expert_hits``, the program's count summed
    over layers and dispatches; it includes experts only a rejected
    draft's row chose, an expert a round's committed rows would mostly
    have hit anyway at 64 rows a layer), and each token's live cached
    rows: its whole context on a full layer, the window's at most on a
    window layer (``min(contexts_sum, window x n_tokens)``, as
    ``families/mellum.py``)."""
    s = sizes(c)
    rows = attended(c, contexts_sum, min(contexts_sum, s["W"] * n_tokens))
    share = s["K"] * s["E"] / s["E_all"]
    flops = (2.0 * nonrouted_params(c) * n_tokens
             + 2.0 * s["sparse"] * share * n_tokens * expert_params(c)
             + attention_flops_per_position(c) * rows)
    nbytes = (dispatches * nonrouted_params(c) * DTYPE_BYTES
              + expert_hits * expert_bytes(c)
              + rows * cache_bytes_row(c))
    return {"flops": flops, "bytes": nbytes,
            "expert_bytes": expert_hits * expert_bytes(c)
            + 2.0 * assignments_held * s["H"] * 4.0}


# -- the reference -----------------------------------------------------------

def reference_logits(c: Dict[str, Any], params: Any, rows: List[np.ndarray],
                     tr: Dict[str, Any], precision: str = "f32"
                     ) -> List[Tail]:
    """Reference logits at the last ``output_len.max`` positions of each
    row (a row is a prompt and its served tokens but the last, and the
    positions compared are those that predicted a served token), the
    rows padded to one length (a multiple of ``reference_pad``; causal:
    the padding is never attended) and taken
    ``reference_rows_per_block`` at a time.  No draft: the served stream
    is the plain model's."""
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
            params, jnp.asarray(ids), config=c, held=held(c),
            precision=precision, at=np.maximum(at, 0),
            q_block=int(tr.get("reference_q_block", 256))))
        out.extend(Tail(len(r) - last, logits[i])
                   for i, r in enumerate(block))
    return out
