"""Operations and bytes of the state-space scan, from shapes alone.

Kept with the benchmark (not imported from the program):
``ssm_scan_roofline_pct`` divides THESE counts by a time from the device
trace.  They are the chunked (state-space-duality) algorithm's at the
configured chunk, per chunk that holds a real node, whatever computes it:

* operations: the four products of a chunk of ``Q`` nodes, forward: ``C
  B^T`` once a group (``2 Q Q N``), per head the weighted sum over the
  chunk's nodes (``2 Q Q P``), the chunk's own state (``2 Q P N``) and the
  entering state's read (``2 Q N P``); the backward pass is twice the
  forward (one product for each operand).  Decays, exponentials, the
  gating and the recomputed forward are time without counted operations.
* bytes: the least any implementation must move, each operand read and
  each result written once a pass.  Forward: ``x`` [H, P], ``B`` and ``C``
  [G, N] in the products' dtype, ``dt`` [H] in float32 read; ``y`` [H, P]
  in float32 written.  Backward: the same four operands and ``dy`` read;
  ``dx``, ``dB``, ``dC`` (the operands' dtype) and ``ddt`` written.  Per
  node; states carried between chunks and every intermediate are on-chip
  in the least implementation.

The share is the LARGER of operations over the bf16 peak and bytes over
the HBM peak, over the measured time: it says which of the two bounds the
scan and can never pass 100.
"""

from __future__ import annotations


def scan_flops_per_chunk(chunk: int, heads: int, head_dim: int, groups: int,
                         state: int) -> float:
    """Forward and backward products of one chunk of one layer."""
    forward = (groups * 2 * chunk * chunk * state
               + heads * (2 * chunk * chunk * head_dim
                          + 2 * 2 * chunk * head_dim * state))
    return 3.0 * forward


def scan_bytes_per_node(heads: int, head_dim: int, groups: int, state: int,
                        operand_bytes: int) -> float:
    """Forward and backward traffic of one node of one layer."""
    operands = (heads * head_dim + 2 * groups * state) * operand_bytes
    dt, y = heads * 4, heads * head_dim * 4
    forward = operands + dt + y
    backward = operands + dt + y + operands + dt
    return float(forward + backward)


def lm_facts(config: dict, doc_lengths, steps_per_epoch: int) -> dict:
    """What the scan's readers need of the cell (the driver's
    ``facts["lm"]``): the held shapes and how many layers scan."""
    dtype = config["NeuralNetwork"]["Architecture"].get(
        "compute_dtype", "float32")
    return {"ssm": {
        "layers": str(config["hybrid_override_pattern"]).count("M"),
        "chunk": int(config["chunk_size"]),
        "heads": int(config["mamba_num_heads"]),
        "head_dim": int(config["mamba_head_dim"]),
        "groups": int(config["n_groups"]),
        "state": int(config["ssm_state_size"]),
        "operand_bytes": 2 if dtype == "bfloat16" else 4,
        "tokens_per_step": sum(int(n) for n in doc_lengths)
        / max(steps_per_epoch, 1)}}


def scan_least_seconds(lm: dict, real_chunks_per_step: float,
                       peak_flops: float, peak_bytes_per_s: float):
    """(least seconds a step, "compute" or "memory"): the larger of the
    two bounds for ``real_chunks_per_step`` chunks in each scanning
    layer."""
    s = lm["ssm"]
    flops = s["layers"] * real_chunks_per_step * scan_flops_per_chunk(
        s["chunk"], s["heads"], s["head_dim"], s["groups"], s["state"])
    moved = (s["layers"] * real_chunks_per_step * s["chunk"]
             * scan_bytes_per_node(s["heads"], s["head_dim"], s["groups"],
                                   s["state"], s["operand_bytes"]))
    by_flops, by_bytes = flops / peak_flops, moved / peak_bytes_per_s
    return max(by_flops, by_bytes), (
        "compute" if by_flops >= by_bytes else "memory")
