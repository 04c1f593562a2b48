"""Operation counts of the latent-attention cell, from shapes alone.

Kept with the benchmark (not imported from the program), as ``lm_counts.py``
is for the grouped-query cell: ``mla_core_mxu_pct`` divides THESE counts by
a time from the device trace.  Only work the mathematics needs is counted:
the visible (i, j) pairs of causal attention inside each document.  What
the kernels compute beyond that (masked blocks of the static band, padding
nodes, the recomputed forward) is time without operations, and lowers the
share.
"""

from __future__ import annotations


def visible_pairs(length: int) -> int:
    """(i, j) pairs with ``0 <= i - j`` in a document of ``length``."""
    return length * (length + 1) // 2


def attention_core_flops(pairs: float, heads: int, qk_dim: int,
                         v_dim: int) -> float:
    """Scores and values, forward and backward, of ``pairs`` visible pairs
    under ``heads`` heads whose keys are ``qk_dim`` and values ``v_dim``
    wide: forward q.k (``2 qk_dim``) and p.v (``2 v_dim``); backward dq
    and dk (``2 qk_dim`` each), dp and dv (``2 v_dim`` each).  The backward
    kernels' recomputation of q.k is not counted."""
    return pairs * heads * 3 * 2 * (qk_dim + v_dim)


def lm_facts(config: dict, doc_lengths, steps_per_epoch: int) -> dict:
    """What ``mla_core_mxu_pct`` needs of the cell (the driver's
    ``facts["lm"]``): the visible pairs of a mean train step, the sizes,
    and how many layers attend (the main layers and the
    multi-token-prediction module's)."""
    pairs = sum(visible_pairs(int(n)) for n in doc_lengths)
    return {"mla": {
        "pairs_per_step": pairs / max(steps_per_epoch, 1),
        "heads": int(config["num_attention_heads"]),
        "qk_dim": int(config["qk_nope_head_dim"])
        + int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "layers": int(config["num_hidden_layers"])
        + int(config.get("num_nextn_predict_layers", 0))}}


def mla_core_flops_per_step(lm: dict) -> float:
    m = lm["mla"]
    return m["layers"] * attention_core_flops(
        m["pairs_per_step"], m["heads"], m["qk_dim"], m["v_dim"])
