"""What a model counts for the step records, written once.

A stack keeps a count as a float32 scalar ``<prefix><key>`` of its
``batch_stats`` (``keep``); the train step hands those scalars on as step
metrics (train/trainer.py ``model_counters``), a scanned dispatch merges
each over its K steps by the key's rule (``rule``), and the step record
shows them as one block a prefix (``record_blocks``; docs/TELEMETRY.md).
A new count is a row here and the ``keep`` call that fills it.
"""

from __future__ import annotations

from typing import Callable, Dict, Iterable, Mapping, NamedTuple, Optional
from typing import Sequence, Tuple

import jax.numpy as jnp

# how a key merges over the K steps of a dispatch
SUM = "sum"      # a count of the dispatch
MEAN = "mean"    # weighted by each step's real graphs, as the losses are
SAME = "same"    # a number of the dispatch's shape, the same on every step


class Block(NamedTuple):
    """A block of the step record; it is written when its first key is
    among the step's metrics."""
    prefix: str                     # of its ``batch_stats`` / metric names
    keys: Dict[str, str]            # key -> rule, in the record's order
    optional: Tuple[str, ...] = ()  # keys a stack may leave out


BLOCKS = {
    # the expert layers' routing, summed over the layers (the imbalances
    # averaged over them): ops/moe.py stats; the last two under a router
    # with a correction bias (models/sequence.py balance)
    "moe": Block("moe_", {"slots_held": SUM, "slots_all": SUM,
                          "load_max_over_mean": MEAN, "dense_steps": SUM,
                          "load_all_max_over_mean": MEAN,
                          "bias_abs_max": MEAN},
                 ("load_all_max_over_mean", "bias_abs_max")),
    # the attention kernels' block schedule, summed over the attending
    # layers' forward calls (ops/attention.py scheduled_blocks), and the MB
    # the attention halves' checkpoints keep in ONE step (kept_mb)
    "attention": Block("attn_", {"blocks_run": SUM, "blocks_band": SUM,
                                 "kept_mb": SAME}),
    # what ONE state-space layer's scan walked (ops/ssm.py scan_counts)
    "ssm": Block("ssm_", {"chunks": SUM, "chunks_padding": SUM,
                          "resets": SUM}),
    # what the short convolutions met, summed over the conv layers
    # (ops/sconv.py conv_counts), and the MB their checkpoints keep in ONE
    # step (models/lfm2_moe.py KEEP_SCONV)
    "sconv": Block("sconv_", {"rows": SUM, "starts": SUM, "taps_cut": SUM,
                              "kept_mb": SAME}),
    # what the gated delta rule walked, summed over the DeltaNet layers
    # (ops/ssm.py scan_counts at the rule's chunk; ``resets``: graph starts
    # a layer), and the MB their checkpoints keep in ONE step
    # (models/qwen3_next.py KEEP_GDN)
    "gdn": Block("gdn_", {"chunks": SUM, "chunks_padding": SUM,
                          "resets": SUM, "kept_mb": SAME}),
    # the MB the dense feed-forwards' checkpoints keep in ONE step, summed
    # over the dense layers (models/sequence.py KEEP_FFN)
    "ffn": Block("ffn_", {"kept_mb": SAME}),
}

_RULES = {b.prefix + key: how for b in BLOCKS.values()
          for key, how in b.keys.items()}


def rule(name: str) -> Optional[str]:
    """How the step metric ``name`` merges over a dispatch: SUM, MEAN or
    SAME; None for a name that is no model counter."""
    return _RULES.get(name)


def keep(stack, block: str, train: bool, keys: Sequence[str],
         values: Callable[[], Iterable]) -> None:
    """Declare ``block``'s ``keys`` as float32 scalars of ``stack``'s
    ``batch_stats`` and, in a train step, fill them with ``values()`` (one
    a key, in that order).  ``values`` is called in a train step only: an
    eval step and the initialisation trace nothing for a count."""
    table = BLOCKS[block]
    unknown = [k for k in keys if k not in table.keys]
    if unknown:
        raise KeyError(
            f"{block}: {unknown} are not keys of telemetry/counters.py "
            f"BLOCKS[{block!r}] ({list(table.keys)})")
    cells = [stack.variable("batch_stats", table.prefix + k,
                            lambda: jnp.zeros((), jnp.float32))
             for k in keys]
    if not train or stack.is_initializing():
        return
    for cell, v in zip(cells, values()):
        cell.value = jnp.asarray(v, jnp.float32)


def record_blocks(metrics: Mapping[str, float]) -> Dict[str, Dict[str, float]]:
    """The step record's model blocks of one dispatch's merged metrics."""
    out = {}
    for name, (prefix, keys, optional) in BLOCKS.items():
        if prefix + next(iter(keys)) in metrics:
            out[name] = {k: float(metrics[prefix + k]) for k in keys
                         if k not in optional or prefix + k in metrics}
    return out
