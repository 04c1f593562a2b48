"""One chip's share of a layer that a deployment divides over many chips.

A layer of a sparse language model is shared by ``R`` chips: its routed
experts over expert-parallel ranks, its attention heads over
tensor-parallel ranks (one group of query heads per key/value head; or
none cut, where attention is data-parallel), its vocabulary over
vocabulary-parallel ranks, and, where it has state-space mixers, their heads
over tensor-parallel ranks by GROUP (a group's ``B`` and ``C`` and the
grouped norm's statistics belong to that group's heads alone, so a rank that
holds whole groups computes its heads exactly).  ``LayerShare`` says what
THIS chip holds; the layer reads it (models/laguna.py,
models/glm_moe_lite.py, models/nemotron_h.py, ops/moe.py): the
router keeps its published width and routes over all the experts, and the
chip computes the part of the result its own experts give.  On one chip the
layer runs without its exchange: what the absent experts and heads would
add is left out, nothing stands in for the absent chips, and the partial
result goes on to the next layer (the plain reference is given the same
share, models/laguna_reference.py).  The parameters a share holds ARE the
slice (``wq`` has the held heads' columns only), so only the experts and
the vocabulary need an offset: router ids and token ids are global.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict


@dataclasses.dataclass(frozen=True)
class LayerShare:
    num_experts_total: int      # the router's width
    experts_held: int
    expert_offset: int          # first held expert's global id
    kv_heads_total: int
    kv_heads_held: int
    kv_head_offset: int
    vocab_total: int
    vocab_rows: int
    vocab_offset: int           # first held row's global token id
    # state-space mixers (models/nemotron_h.py); a model without them
    # holds "one head of one group"
    ssm_heads_total: int = 1
    ssm_heads_held: int = 1
    ssm_head_offset: int = 0
    ssm_groups_total: int = 1
    ssm_groups_held: int = 1
    ssm_group_offset: int = 0

    def __post_init__(self):
        for held, off, total, what in (
                (self.experts_held, self.expert_offset,
                 self.num_experts_total, "experts"),
                (self.kv_heads_held, self.kv_head_offset,
                 self.kv_heads_total, "key/value heads"),
                (self.vocab_rows, self.vocab_offset, self.vocab_total,
                 "vocabulary rows"),
                (self.ssm_heads_held, self.ssm_head_offset,
                 self.ssm_heads_total, "state-space heads"),
                (self.ssm_groups_held, self.ssm_group_offset,
                 self.ssm_groups_total, "state-space groups")):
            if not (held >= 1 and off >= 0 and off + held <= total):
                raise ValueError(
                    f"share holds {what} [{off}, {off + held}) of {total}")
        # whole groups: the held heads are the held groups' heads
        per_group = self.ssm_heads_total // self.ssm_groups_total
        if (self.ssm_heads_total % self.ssm_groups_total
                or self.ssm_heads_held != per_group * self.ssm_groups_held
                or self.ssm_head_offset != per_group * self.ssm_group_offset):
            raise ValueError(
                f"share holds state-space heads [{self.ssm_head_offset}, "
                f"{self.ssm_head_offset + self.ssm_heads_held}) and groups "
                f"[{self.ssm_group_offset}, "
                f"{self.ssm_group_offset + self.ssm_groups_held}): not the "
                f"whole groups of {per_group} heads")

    @staticmethod
    def from_arch(lm: Dict[str, Any], share: Dict[str, Any],
                  experts_key: str = "num_experts") -> "LayerShare":
        """From the model's own section of ``Architecture`` (the sizes held
        here; ``experts_key`` is its name for the routed experts' count)
        and ``Architecture.share`` (the published totals and this chip's
        offsets; absent = the uncut model).  A deployment that cuts nothing
        from the heads (attention data-parallel: models/glm_moe_lite.py)
        gives no ``kv_heads_total``, and every head is held.  A model with
        state-space mixers names their heads ``mamba_num_heads`` and their
        groups ``n_groups``."""
        ssm = {}
        if "mamba_num_heads" in lm:
            heads, groups = int(lm["mamba_num_heads"]), int(lm["n_groups"])
            ssm = dict(
                ssm_heads_total=int(share.get("ssm_heads_total", heads)),
                ssm_heads_held=heads,
                ssm_head_offset=int(share.get("ssm_head_offset", 0)),
                ssm_groups_total=int(share.get("ssm_groups_total", groups)),
                ssm_groups_held=groups,
                ssm_group_offset=int(share.get("ssm_group_offset", 0)))
        return LayerShare(
            num_experts_total=int(share.get("num_experts_total",
                                            lm[experts_key])),
            experts_held=int(lm[experts_key]),
            expert_offset=int(share.get("expert_offset", 0)),
            kv_heads_total=int(share.get("kv_heads_total",
                                         lm["num_key_value_heads"])),
            kv_heads_held=int(lm["num_key_value_heads"]),
            kv_head_offset=int(share.get("kv_head_offset", 0)),
            vocab_total=int(share.get("vocab_total", lm["vocab_size"])),
            vocab_rows=int(lm["vocab_size"]),
            vocab_offset=int(share.get("vocab_offset", 0)), **ssm)

    def local_expert(self, expert_ids):
        """(local id, held?) of global router ids (any array)."""
        local = expert_ids - self.expert_offset
        return local, (local >= 0) & (local < self.experts_held)
