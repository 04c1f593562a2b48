"""Model factory (parity: reference hydragnn/models/create.py:31-307).

Dispatches on ``model_type`` to the 9 conv stacks and initializes parameters
with a fixed seed (the reference seeds torch with 0; create.py:105).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from hydragnn_tpu.config.config import SEQUENCE_MODELS
from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.base import Base, ModelConfig, sequence_module
from hydragnn_tpu.models.sage import SAGEStack
from hydragnn_tpu.models.gin import GINStack
from hydragnn_tpu.models.gat import GATStack
from hydragnn_tpu.models.mfc import MFCStack
from hydragnn_tpu.models.pna import PNAStack
from hydragnn_tpu.models.cgcnn import CGCNNStack
from hydragnn_tpu.models.schnet import SCFStack
from hydragnn_tpu.models.egnn import EGCLStack
from hydragnn_tpu.models.dimenet import DIMEStack

_STACKS = {
    "SAGE": SAGEStack,
    "GIN": GINStack,
    "GAT": GATStack,
    "MFC": MFCStack,
    "PNA": PNAStack,
    "CGCNN": CGCNNStack,
    "SchNet": SCFStack,
    "DimeNet": DIMEStack,
    "EGNN": EGCLStack,
}

# THE canonical arch list: bench.py's per-arch sweep and the fused-vs-
# scatter parity tests (tests/test_fused_block.py) both derive from it, so
# a newly registered stack cannot miss bench or parity coverage.  The
# sequence stacks (config.SEQUENCE_MODELS: language models over each
# graph's nodes, not message passing over an edge list) take no part in
# those sweeps; each has its own reference and tests.
ALL_ARCHS = tuple(_STACKS)


def create_model_config(config: Dict[str, Any]) -> Base:
    """Build the (uninitialized) flax module from a finalized config dict."""
    cfg = ModelConfig.from_config(config["NeuralNetwork"])
    return create_model(cfg)


def create_model(cfg: ModelConfig) -> Base:
    if cfg.model_type in SEQUENCE_MODELS:
        if cfg.lm is None or cfg.share is None:
            raise ValueError(f"{cfg.model_type} requires Architecture."
                             f"{SEQUENCE_MODELS[cfg.model_type]}")
        return sequence_module(cfg.model_type).Stack(cfg=cfg)
    if cfg.model_type not in _STACKS:
        raise ValueError(f"Unknown model_type: {cfg.model_type}")
    if (cfg.model_type == "GAT" and cfg.dropout > 0
            and cfg.hidden_dim * cfg.gat_heads >= 256):
        import warnings

        # measured pathology (tools/gat_pathology.py, docs/PERF.md round
        # 5): at this width, attention dropout makes the BN running
        # statistics track a train-time distribution that mismatches
        # eval mode — train loss converges while EVAL error grows past
        # predict-the-mean, in BOTH this framework and the torch
        # reference (ACCURACY_r04/r05).  Dropout 0 measured test MAE
        # 0.40 vs 2.46 (flagship Morse-QM9 protocol, lr 1e-3).
        warnings.warn(
            f"GAT with attention dropout {cfg.dropout} at width "
            f"{cfg.hidden_dim}x{cfg.gat_heads} heads diverges in eval "
            "mode (BN running-stats mismatch; see docs/PERF.md round 5)."
            ' Set "Architecture": {"dropout": 0.0} — measured test MAE '
            "0.40 vs 2.46 on the flagship protocol.",
            stacklevel=2)
    if cfg.model_type == "PNA":
        assert cfg.pna_avg_deg_log is not None, "PNA requires degree input."
    if cfg.model_type == "MFC":
        assert cfg.max_degree is not None, "MFC requires max_neighbours input."
    if cfg.model_type == "SchNet":
        assert cfg.num_gaussians is not None, "SchNet requires num_gaussians input."
        assert cfg.num_filters is not None, "SchNet requires num_filters input."
        assert cfg.radius is not None, "SchNet requires radius input."
    if cfg.model_type == "DimeNet":
        for key in (
            "basis_emb_size",
            "envelope_exponent",
            "int_emb_size",
            "out_emb_size",
            "num_after_skip",
            "num_before_skip",
            "num_radial",
            "num_spherical",
            "radius",
        ):
            assert getattr(cfg, key) is not None, f"DimeNet requires {key} input."
    if cfg.model_type == "CGCNN" and cfg.node_head is not None:
        if cfg.node_head.type == "conv" and "node" in cfg.output_type:
            raise ValueError(
                '"conv" node decoder is not supported for CGCNN '
                "(reference CGCNNStack.py:66-89)."
            )
    return _STACKS[cfg.model_type](cfg=cfg)


def init_model(
    model: Base, example_batch: GraphBatch, seed: int = 0
) -> Dict[str, Any]:
    """Initialize variables ({'params', 'batch_stats'}) with a fixed seed."""
    rngs = {"params": jax.random.PRNGKey(seed), "dropout": jax.random.PRNGKey(seed + 1)}
    return model.init(rngs, example_batch, train=False)
