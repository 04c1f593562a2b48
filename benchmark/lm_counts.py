"""Operation counts of the language-model cells, from shapes alone.

Kept with the benchmark (not imported from the program): the per-layer
metrics that state a share of the MXU's peak divide THESE counts by a time
from the device trace.  Only work the mathematics needs is counted: the
visible (i, j) pairs of the attention, the slots really routed to a held
expert.  What a kernel computes beyond that (masked blocks of a band,
padding rows) and what recomputation repeats is time without operations,
and lowers the share.
"""

from __future__ import annotations


def visible_pairs(length: int, window=None) -> int:
    """(i, j) pairs with ``0 <= i - j`` (``< window``) in a document of
    ``length`` tokens."""
    if window is None or length <= window:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def attention_core_flops(pairs: int, heads: int, head_dim: int) -> int:
    """Scores and values, forward and backward, of ``pairs`` visible pairs
    under ``heads`` query heads: forward q.k and p.v (2 products of
    ``2 * head_dim``), backward dv, dp, dq, dk (4 such products).  The
    backward kernels' recomputation of q.k is not counted."""
    return pairs * heads * (2 + 4) * 2 * head_dim


def grouped_ffn_flops(slots: float, hidden: int, width: int) -> float:
    """The gated feed-forward's three grouped products over ``slots``
    token slots, forward (3 products of ``2 * hidden * width``) and
    backward (twice that: one product for the rows, one for the
    weights)."""
    return slots * 3 * 3 * 2 * hidden * width


def lm_facts(config: dict, doc_lengths, steps_per_epoch: int) -> dict:
    """What the trace readers need of one language-model cell: the visible
    pairs of a mean train step by layer kind, and the sizes."""
    window = int(config["sliding_window"])
    kinds = config["layer_types"][:int(config["num_hidden_layers"])]
    heads = config["num_attention_heads_per_layer"]
    per_kind = {}
    for kind in set(kinds):
        w = window if kind == "sliding_attention" else None
        pairs = sum(visible_pairs(int(n), w) for n in doc_lengths)
        per_kind[kind] = {
            "pairs_per_step": pairs / max(steps_per_epoch, 1),
            "heads_summed": sum(int(h) for h, k in zip(heads, kinds)
                                if k == kind)}
    return {"attention": per_kind, "head_dim": int(config["head_dim"]),
            "hidden_size": int(config["hidden_size"]),
            "moe_intermediate_size": int(config["moe_intermediate_size"])}
