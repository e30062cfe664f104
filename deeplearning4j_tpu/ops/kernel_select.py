"""Shared Pallas-vs-XLA kernel selection policy.

Word2Vec and GloVe auto-select a VMEM-resident Pallas kernel on TPU when
their tables fit, fall back to the XLA gather/scatter path otherwise,
and honor a forced ``kernel=`` config value ("pallas" off-TPU runs
through the interpreter — the test harness).  ``resolve_attn_kernel``
generalizes the same contract to the flash-attention training path
(ops/pallas_attention.make_attn_fn): auto-selection may consult an
autotuned winner, an explicit ``kernel="pallas"`` request NEVER falls
back silently, and off-TPU a forced Pallas kernel runs interpreted so
tier-1 exercises the kernel code path.  This is the one copy of that
policy.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from typing import Callable, Optional, Tuple

import jax

KERNELS = ("auto", "pallas", "xla")

#: attention kernel modes add "ring" (sequence-parallel ring attention,
#: parallel/ring_attention.py) to the shared vocabulary — one policy,
#: one spelling
ATTN_KERNELS = KERNELS + ("ring",)


def resolve_attn_kernel(kernel: str, *, k_len: int, aligned: bool,
                        on_tpu: bool, blocked: Optional[str] = None,
                        autotuned_impl: Optional[str] = None,
                        min_seq: int, desc: str = "flash attention",
                        seq_degree: int = 1) -> Tuple[str, bool]:
    """(impl, interpret) for a requested attention ``kernel`` mode.

    ``aligned`` is the Mosaic-tileability verdict for the shape,
    ``blocked`` an optional reason the Pallas kernel cannot run in this
    context at all (seq-parallel mesh, indivisible sharding, ...).
    ``autotuned_impl`` is a persisted sweep winner ("pallas"/"xla") that
    overrides the ``min_seq`` heuristic for auto mode on TPU.
    ``seq_degree`` is the mesh's sequence-parallel degree: above 1, ring
    attention (parallel/ring_attention.py) owns the axis — auto selects
    impl "ring" (unless an autotuned winner says plain XLA is faster at
    this shape), an explicit ``kernel='ring'`` demands it, and an
    explicit ``kernel='pallas'`` raises (the flash kernel has no ring
    schedule).

    Contract (same as :func:`resolve_kernel` for word2vec/glove): auto
    degrades silently, an explicit ``kernel='pallas'``/``'ring'`` raises
    instead of falling back, and a forced Pallas kernel off-TPU runs
    through the interpreter (the CPU test harness)."""
    if kernel not in ATTN_KERNELS:
        raise ValueError(
            f"kernel must be one of {ATTN_KERNELS}, got {kernel!r}")
    if kernel == "ring":
        if seq_degree <= 1 or blocked is not None:
            raise ValueError(
                f"kernel='ring' but {desc} cannot run ring attention: "
                f"{blocked or f'no sharded sequence axis (seq degree {seq_degree})'}"
                f" — never a silent fallback on an explicit request")
        return "ring", False
    if kernel == "xla":
        return "xla", False
    if seq_degree > 1:
        if kernel == "pallas":
            raise ValueError(
                f"kernel='pallas' but {desc} runs under sequence "
                f"parallelism (seq degree {seq_degree}) — ring attention "
                f"owns a sharded sequence axis; request kernel='ring' or "
                f"'auto'")
        if autotuned_impl == "xla":
            return "xla", False
        return "ring", False
    if aligned and blocked is None:
        if kernel == "pallas":
            return "pallas", not on_tpu
        if not on_tpu:
            return "xla", False          # auto off-TPU: interpreter is
        if autotuned_impl in ("pallas", "xla"):   # no training kernel
            return autotuned_impl, False
        return ("pallas" if k_len >= min_seq else "xla"), False
    if kernel == "pallas":
        raise ValueError(
            f"kernel='pallas' but {desc} cannot run the Pallas kernel: "
            f"{blocked or 'shape is not Mosaic-tileable'} — never a "
            f"silent fallback on an explicit request")
    return "xla", False


def resolve_kernel(kernel: str, block: int, desc: str
                   ) -> Tuple[int, bool]:
    """(pallas_block, pallas_interpret) for a requested ``kernel`` mode
    and a precomputed VMEM ``block`` (0 = doesn't fit).  Raises for
    unknown modes and for ``kernel='pallas'`` when the budget excludes
    it — never a silent fallback on an explicit request."""
    if kernel not in KERNELS:
        raise ValueError(
            f"kernel must be one of {KERNELS}, got {kernel!r}")
    if kernel == "xla":
        return 0, False
    platform = jax.devices()[0].platform
    if block and (platform == "tpu" or kernel == "pallas"):
        return block, platform != "tpu"
    if kernel == "pallas":
        raise ValueError(
            f"kernel='pallas' but {desc} exceeds the VMEM-resident "
            f"budget (or the batch size is not divisible by a "
            f"supported block)")
    return 0, False


@dataclasses.dataclass(frozen=True)
class KernelChoice:
    """What a fit dispatched and WHY — kept on the fit object
    (``kernel_used``) so the choice is readable afterwards: ``name`` is
    "pallas" / "pallas-interpret" / "xla", ``block`` the Pallas grid
    block (0 on the XLA path), ``why`` the request, the budget verdict
    or the compiler's own message."""
    name: str
    block: int
    interpret: bool
    why: str


def choose_kernel(kernel: str, block: int, desc: str,
                  probe: Callable[[int], Optional[str]]) -> KernelChoice:
    """:func:`resolve_kernel` plus, for ``auto`` on hardware, one real
    compile: ``probe(block)`` returns None when Mosaic took the kernel
    and its message when it did not, in which case auto uses XLA and
    says so (an explicit ``kernel="pallas"`` is never probed: its
    compile error surfaces from the fit itself)."""
    blk, interpret = resolve_kernel(kernel, block, desc)
    if kernel != "auto":
        why = f"kernel={kernel!r} requested"
    elif blk:
        refusal = probe(blk)
        if refusal is None:
            why = f"auto: Mosaic compiled block {blk}"
        else:
            why, blk = f"auto: Mosaic refused block {blk}: {refusal}", 0
    elif block:
        why = f"auto: platform is {jax.devices()[0].platform}, not tpu"
    else:
        why = f"auto: no VMEM-resident block for {desc}"
    name = ("xla" if not blk else
            "pallas-interpret" if interpret else "pallas")
    return KernelChoice(name, blk, interpret, why)


def probe_mosaic(compile_once: Callable[[], None], what: str,
                 timeout_s: float) -> Optional[str]:
    """Run one real kernel compile (``compile_once`` must also fetch a
    result value): None when Mosaic took it, else its message.  The
    compile runs in a daemon thread joined with ``timeout_s``, so a
    Mosaic compile that HANGS reads as a refusal and the fit proceeds
    on XLA.  CAVEAT (ADVICE r4): a timeout verdict abandons the hung
    compile thread ALIVE — it may still hold jaxlib's compile lock, so
    the next in-process compile can block behind it until it finishes
    or the process exits; a compile cannot be cancelled from Python,
    and a killable-subprocess probe is impossible because by fit() time
    this process already holds the (single-holder) TPU chip."""
    failure = []

    def attempt():
        try:
            compile_once()
        except Exception as e:            # Mosaic/compile-specific
            failure.append(e)

    t = threading.Thread(target=attempt, daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        refusal = (f"compile timed out after {timeout_s:.0f}s — the hung "
                   f"Mosaic compile thread is abandoned alive and may "
                   f"delay this process's next compile")
    elif failure:
        refusal = str(failure[0])
    else:
        return None
    logging.getLogger(__name__).warning(
        "%s Pallas kernel unavailable on this backend (%s); using the "
        "XLA path", what, refusal)
    return refusal
