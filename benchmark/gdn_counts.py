"""Operations and bytes of the gated delta rule, from shapes alone.

Kept with the benchmark (not imported from the program):
``gdn_scan_roofline_pct`` divides THESE counts by a time from the device
trace.  They are the published chunked form's (Yang, Kautz, Hatamizadeh,
"Gated Delta Networks", arXiv:2412.06464) at the chunk the configuration
states (``linear_chunk_size``), per chunk that holds a real node, whatever
computes it (XLA's products or a kernel):

* operations, forward, a chunk of ``C`` nodes: once a KEY head ``K K^T`` and
  ``Q K^T`` (``2 C C d_k`` each); once a VALUE head the unit
  lower-triangular solve ``(I + A)^-1`` by substitution (``C^3 / 3``
  multiply-adds), ``W = T K`` (``2 C C d_k``), ``U = T V`` (``2 C C d_v``),
  the entering state's two reads ``W S`` and ``Q S`` and the chunk's update
  ``K^T V'`` (``2 C d_k d_v`` each) and ``(Q K^T) V'`` (``2 C C d_v``); the
  backward pass is twice the forward (one product for each operand).  The
  decays, exponentials, the l2 norms, what a doubling inverse spends beyond
  a substitution and the recomputed forward are time without counted
  operations.
* bytes: the least any implementation must move, each operand read and
  each result written once a pass, per node.  Forward: ``q`` and ``k``
  [H_k, d_k] and ``v`` [H_v, d_v] in the products' dtype, ``g`` and
  ``beta`` [H_v] in float32 read; ``o`` [H_v, d_v] in float32 written.
  Backward: the same five and ``do`` read; ``dq``, ``dk``, ``dv`` (the
  operands' dtype), ``dg`` and ``dbeta`` written.  States carried between
  chunks and every [C, C] intermediate are on-chip in the least
  implementation.

The share is the LARGER of operations over the bf16 peak and bytes over the
HBM peak, over the measured time: it says which of the two bounds the rule
and can never pass 100.

``lm_facts`` also gives the keys ``trace_lm.py`` reads of a grouped-query
cell (the attention layer's visible pairs and the experts' widths), so that
a ``benchmark`` PR can widen the accepted ``attn_core_*`` / ``moe_*``
entries to this cell by data alone.
"""

from __future__ import annotations


def rule_flops_per_chunk(chunk: int, key_heads: int, value_heads: int,
                         key_dim: int, value_dim: int) -> float:
    """Forward and backward products of one chunk of one layer."""
    c = chunk
    forward = (key_heads * 2 * (2 * c * c * key_dim)
               + value_heads * (2 * c ** 3 / 3.0
                                + 2 * c * c * key_dim
                                + 2 * 2 * c * c * value_dim
                                + 3 * 2 * c * key_dim * value_dim))
    return 3.0 * forward


def rule_bytes_per_node(key_heads: int, value_heads: int, key_dim: int,
                        value_dim: int, operand_bytes: int) -> float:
    """Forward and backward traffic of one node of one layer."""
    operands = (2 * key_heads * key_dim
                + value_heads * value_dim) * operand_bytes
    gates, o = 2 * value_heads * 4, value_heads * value_dim * 4
    forward = operands + gates + o
    backward = operands + gates + o + operands + gates
    return float(forward + backward)


def visible_pairs(length: int) -> int:
    """(i, j) pairs with ``0 <= i - j`` in a document of ``length``."""
    return length * (length + 1) // 2


def lm_facts(config: dict, doc_lengths, steps_per_epoch: int) -> dict:
    """What the rule's readers need of the cell (the driver's
    ``facts["lm"]``): the held shapes and how many layers run the rule;
    and, for ``trace_lm.py``, the attention layers' visible pairs of a mean
    train step and the experts' widths."""
    dtype = config["NeuralNetwork"]["Architecture"].get(
        "compute_dtype", "float32")
    layers = int(config["num_hidden_layers"])
    every = int(config["full_attention_interval"])
    full = sum((i + 1) % every == 0 for i in range(layers))
    steps = max(steps_per_epoch, 1)
    return {
        "gdn": {
            "layers": layers - full,
            "chunk": int(config["linear_chunk_size"]),
            "key_heads": int(config["linear_num_key_heads"]),
            "value_heads": int(config["linear_num_value_heads"]),
            "key_dim": int(config["linear_key_head_dim"]),
            "value_dim": int(config["linear_value_head_dim"]),
            "operand_bytes": 2 if dtype == "bfloat16" else 4,
            "tokens_per_step": sum(int(n) for n in doc_lengths) / steps},
        "attention": {"full_attention": {
            "pairs_per_step": sum(visible_pairs(int(n))
                                  for n in doc_lengths) / steps,
            "heads_summed": full * int(config["num_attention_heads"])}},
        "head_dim": int(config["head_dim"]),
        "hidden_size": int(config["hidden_size"]),
        "moe_intermediate_size": int(config["moe_intermediate_size"])}


def rule_least_seconds(lm: dict, real_chunks_per_step: float,
                       peak_flops: float, peak_bytes_per_s: float):
    """(least seconds a step, "compute" or "memory"): the larger of the
    two bounds for ``real_chunks_per_step`` real chunks, summed over the
    DeltaNet layers (the step records' ``gdn.chunks`` is that sum
    already)."""
    s = lm["gdn"]
    flops = real_chunks_per_step * rule_flops_per_chunk(
        s["chunk"], s["key_heads"], s["value_heads"], s["key_dim"],
        s["value_dim"])
    moved = real_chunks_per_step * s["chunk"] * rule_bytes_per_node(
        s["key_heads"], s["value_heads"], s["key_dim"], s["value_dim"],
        s["operand_bytes"])
    by_flops, by_bytes = flops / peak_flops, moved / peak_bytes_per_s
    return max(by_flops, by_bytes), (
        "compute" if by_flops >= by_bytes else "memory")
