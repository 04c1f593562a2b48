"""CGCNN stack (parity: reference hydragnn/models/CGCNNStack.py).

CGConv with additive aggregation: for z_ij = [x_i, x_j, e_ij],
out_i = x_i + sum_{j->i} sigmoid(W_f z_ij) * softplus(W_s z_ij).
CGConv preserves feature dimension, so the stack forces
hidden_dim = input_dim (reference CGCNNStack.py:30-40), and conv-type node
heads are rejected (CGCNNStack.py:66-89 — enforced in ModelConfig.from_config
via the create-time validation in models/create.py).

The whole gated sum (both gathers -> gate MLP pair -> sigmoid*softplus ->
segment sum) dispatches to ONE Pallas pass (ops/cgcnn_mp.py) when the
batch carries the sender-sort marker and the widths fit the kernel's
tile limits; the composed XLA path below is the bit-tested fallback.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import flax.linen as nn

from hydragnn_tpu.graph import segment
from hydragnn_tpu.models.base import Base
from hydragnn_tpu.models.layers import DenseParams
from hydragnn_tpu.ops.aggregate import aggr_backend
from hydragnn_tpu.ops.fused_block import note_fallback


def _cgcnn_pipeline_enabled(dim: int, edge_dim: int) -> bool:
    """Fused gated-sum gate (ops/cgcnn_mp.py): structural tile limits
    only — like EGNN's interaction block there is NO width floor,
    because the win is eliminating the [E, 2F+A] concat and both [E, F]
    gate/core streams plus the scatter pass, which dominates at
    CGCNN's stream-bound widths.  Env override HYDRAGNN_CGCNN_FUSED=1/0
    forces it either way (subject to the structural limits)."""
    from hydragnn_tpu.ops.cgcnn_mp import CGCNN_F_LIMIT, CGCNN_GEO_LIMIT

    if dim > CGCNN_F_LIMIT or edge_dim > CGCNN_GEO_LIMIT:
        return False
    v = os.environ.get("HYDRAGNN_CGCNN_FUSED")
    if v is not None:
        return v.strip().lower() not in ("0", "false", "off", "no", "")
    return True


def _cgcnn_fused_wanted() -> bool:
    if aggr_backend() == "fused":
        return True
    v = os.environ.get("HYDRAGNN_CGCNN_FUSED")
    return v is not None and v.strip().lower() not in (
        "0", "false", "off", "no", "")


class CGConv(nn.Module):
    dim: int  # feature dim, preserved
    edge_dim: int = 0

    @nn.compact
    def __call__(self, x, pos, g, train):
        use_ea = bool(self.edge_dim) and g.edge_attr is not None
        a = g.edge_attr.shape[-1] if use_ea else 0

        # gate params are declared matmul-free so the fused block can
        # consume them raw; the composed path applies them exactly as
        # the nn.Dense layers they replace (identical names/inits —
        # checkpoints are path-independent).  Input width comes from the
        # ACTUAL x (nn.Dense sized lazily the same way; self.dim only
        # fixes the output width)
        zin = 2 * x.shape[-1] + a
        kf, bf = DenseParams(zin, self.dim, name="lin_f")()
        ks, bs = DenseParams(zin, self.dim, name="lin_s")()

        perm = g.extras.get("edge_perm_sender") if g.extras else None
        fused = (perm is not None
                 and _cgcnn_pipeline_enabled(self.dim, a))
        segment._count("cgcnn", fused)
        if not fused and _cgcnn_fused_wanted():
            note_fallback(
                "CGCNN",
                reason="no_sender_perm" if perm is None else "width_gate",
                dim=int(self.dim), edge_dim=int(a))

        if fused:
            from hydragnn_tpu.ops.cgcnn_mp import cgcnn_gated_block

            em = g.edge_mask.astype(jnp.int32)
            agg = cgcnn_gated_block(
                x, g.edge_attr if use_ea else None, em, kf, bf, ks, bs,
                g.senders, g.receivers, perm)
        else:
            # dense-backward gathers (marker-gated): 55.4k -> 68.1k
            # graphs/s vs same-session baseline on the v5e sweep (the
            # concat's scatter-add backward was the remaining XLA
            # scatter here)
            parts = [segment.gather_receiver_sorted(x, g),
                     segment.gather_sender(x, g)]
            if use_ea:
                parts.append(g.edge_attr)
            z = jnp.concatenate(parts, axis=-1)
            gate = jax.nn.sigmoid(z @ kf + bf)
            core = jax.nn.softplus(z @ ks + bs)
            # fused multi-moment scatter (sum moment only) when the
            # batch carries the collate marker
            # (HYDRAGNN_AGGR_BACKEND=fused), else masked segment_sum —
            # one dispatcher with the PNA-class archs
            agg = segment.poly_scatter_segment(
                gate * core, g, ("sum",))["sum"]
        return x + agg, pos


class CGCNNStack(Base):
    def make_conv(self, name, in_dim, out_dim, last_layer):
        return CGConv(dim=in_dim, edge_dim=self.cfg.edge_dim or 0, name=name)
