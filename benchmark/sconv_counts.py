"""Operations and bytes of the double-gated short convolution's core, from
shapes alone.

Kept with the benchmark (not imported from the program):
``sconv_core_roofline_pct`` divides THESE counts by a time from the device
trace.  They are the mathematics of ``y = C * conv(B * X)`` per REAL row
and channel, whatever computes it (one fused pass, three, or a kernel):

* operations, forward: the gate ``B * X`` (1), ``K`` taps' products and
  ``K - 1`` sums (``2 K - 1``), the gate ``C * v`` (1): ``2 K + 1``; the
  backward pass is twice the forward (one pass for each operand of every
  product).  The recomputed forward and the boundary's compares are time
  without counted operations.
* bytes: the least any implementation must move, each operand read and
  each result written once a pass, in the dtypes the configuration states
  (``operand_bytes`` a row and channel: 2 in bfloat16).  Forward: ``B``,
  ``C``, ``X`` read, ``y`` written: 4.  Backward: those three and ``dy``
  read, three gradients written: 7.  The taps' weight and its gradient
  ([K, channels], float32) are ignored; ``B * X``, ``v`` and every
  shifted copy are on-chip in the least implementation.

The share is the LARGER of operations over the bf16 peak and bytes over the
HBM peak, over the measured time: it says which of the two bounds the core
(the bytes, by two orders of magnitude) and can never pass 100.
"""

from __future__ import annotations


def core_flops_per_row(channels: int, taps: int) -> float:
    """Forward and backward operations of one row of one layer."""
    return 3.0 * channels * (2 * taps + 1)


def core_bytes_per_row(channels: int, operand_bytes: int) -> float:
    """Forward and backward traffic of one row of one layer."""
    return float(channels * operand_bytes * (4 + 7))


def lm_facts(config: dict, doc_lengths, steps_per_epoch: int) -> dict:
    """What the short convolution's readers need of the cell (the driver's
    ``facts["lm"]``): the held shapes and how many layers convolve."""
    dtype = config["NeuralNetwork"]["Architecture"].get(
        "compute_dtype", "float32")
    kinds = config["layer_types"][:int(config["num_hidden_layers"])]
    return {"sconv": {
        "layers": sum(k == "conv" for k in kinds),
        "channels": int(config["hidden_size"]),
        "taps": int(config["conv_L_cache"]),
        "operand_bytes": 2 if dtype == "bfloat16" else 4,
        "tokens_per_step": sum(int(n) for n in doc_lengths)
        / max(steps_per_epoch, 1)}}


def core_least_seconds(lm: dict, rows_per_step: float, peak_flops: float,
                       peak_bytes_per_s: float):
    """(least seconds a step, "compute" or "memory"): the larger of the
    two bounds for ``rows_per_step`` real rows, summed over the conv layers
    (the step records' ``sconv.rows`` is that sum already)."""
    s = lm["sconv"]
    by_flops = rows_per_step * core_flops_per_row(
        s["channels"], s["taps"]) / peak_flops
    by_bytes = rows_per_step * core_bytes_per_row(
        s["channels"], s["operand_bytes"]) / peak_bytes_per_s
    return max(by_flops, by_bytes), (
        "compute" if by_flops >= by_bytes else "memory")
