"""EGNN stack (parity: reference hydragnn/models/EGCLStack.py).

E(n)-equivariant graph convolution layer: edge MLP on
[h_src, h_dst, ||dx||^2, edge_attr]; equivariant coordinate update from a
scalar gate on the edge features (tanh-bounded, clamped, mean-aggregated);
node MLP on [h, sum of incident messages].  The coordinate branch runs on
all but the last layer (reference EGCLStack.py:36-46); aggregation happens
at the edge *source* as in the reference (EGCLStack.py:194,210).
No BatchNorm feature layers (reference uses Identity; EGCLStack.py:41).

The whole interaction block (gather -> edge MLP -> coord gate -> both
scatters) dispatches to ONE Pallas pass (ops/egcl_mp.py) when the batch
carries the sender-sort marker and the widths fit the kernel's tile
limits; the composed XLA path below is the bit-tested fallback.
"""

from __future__ import annotations

import os

import jax.numpy as jnp
import flax.linen as nn

from hydragnn_tpu.graph import segment
from hydragnn_tpu.models.base import Base
from hydragnn_tpu.models.layers import DenseParams, edge_geometry
from hydragnn_tpu.ops.aggregate import aggr_backend
from hydragnn_tpu.ops.fused_block import note_fallback


def _egcl_pipeline_enabled(features: int, hidden: int, geo_dim: int) -> bool:
    """Fused EGCL interaction-block gate (ops/egcl_mp.py): structural
    tile limits only — unlike SchNet's cfconv there is NO width floor,
    because the win here is eliminating the [E, *] streams (concat, two
    MLP activations, gate, translations) plus BOTH scatter passes, which
    dominates even at EGNN's mainline hidden width 64 where the step is
    gather/scatter-bound rather than matmul-bound.  Env override
    HYDRAGNN_EGCL_FUSED=1/0 forces it either way (subject to the
    structural limits — the kernel cannot run beyond them)."""
    from hydragnn_tpu.ops.egcl_mp import (
        EGCL_F_LIMIT, EGCL_GEO_LIMIT, EGCL_H_LIMIT)

    if features > EGCL_F_LIMIT or hidden > EGCL_H_LIMIT \
            or geo_dim > EGCL_GEO_LIMIT:
        return False
    v = os.environ.get("HYDRAGNN_EGCL_FUSED")
    if v is not None:
        return v.strip().lower() not in ("0", "false", "off", "no", "")
    return True


def _egcl_fused_wanted() -> bool:
    """Did the operator ask for the fused data layout?  Either knob
    counts: the global aggregation backend or the EGCL-specific force."""
    if aggr_backend() == "fused":
        return True
    v = os.environ.get("HYDRAGNN_EGCL_FUSED")
    return v is not None and v.strip().lower() not in (
        "0", "false", "off", "no", "")


class EGCL(nn.Module):
    out_dim: int
    hidden_dim: int
    edge_dim: int
    equivariant: bool

    @nn.compact
    def __call__(self, x, pos, g, train):
        n = x.shape[0]
        src, dst = g.senders, g.receivers

        # shared per-edge geometry, computed ONCE (the coord branch used
        # to recompute diff/radial on the fallback route)
        diff, radial = edge_geometry(pos, src, dst)
        use_ea = bool(self.edge_dim) and g.edge_attr is not None
        geo_dim = 4 + (g.edge_attr.shape[-1] if use_ea else 0)

        # edge/coord MLP params are declared matmul-free so the fused
        # block can consume them raw; the composed path applies them
        # exactly as the nn.Dense layers they replace (identical
        # names/inits — checkpoints are path-independent)
        in_dim = 2 * x.shape[-1] + geo_dim - 3
        k0, b0 = DenseParams(in_dim, self.hidden_dim,
                             name="edge_mlp_0")()
        k1, b1 = DenseParams(self.hidden_dim, self.hidden_dim,
                             name="edge_mlp_1")()
        kc0 = bc0 = kc1 = None
        if self.equivariant:
            kc0, bc0 = DenseParams(self.hidden_dim, self.hidden_dim,
                                   name="coord_mlp_0")()
            kc1, _ = DenseParams(
                self.hidden_dim, 1, use_bias=False,
                kernel_init=nn.initializers.variance_scaling(
                    0.001, "fan_avg", "uniform"),
                name="coord_mlp_1")()

        perm = g.extras.get("edge_perm_sender") if g.extras else None
        fused = (perm is not None
                 and _egcl_pipeline_enabled(x.shape[-1], self.hidden_dim,
                                            geo_dim))
        segment._count("egcl", fused)
        if not fused and _egcl_fused_wanted():
            # models hold no MetricsLogger — record the reason here (trace
            # time, deduped) for the trainer to surface as a unified
            # `fused_fallback` health event after the first epoch
            note_fallback(
                "EGNN",
                reason="no_sender_perm" if perm is None else "width_gate",
                features=int(x.shape[-1]), hidden=int(self.hidden_dim),
                geo_dim=int(geo_dim))

        if fused:
            from hydragnn_tpu.ops.egcl_mp import egcl_block

            geo = jnp.concatenate(
                [diff, radial] + ([g.edge_attr] if use_ea else []),
                axis=-1)
            em = g.edge_mask.astype(jnp.int32)
            agg, psum = egcl_block(
                self.equivariant, x, geo, em, k0, b0, k1, b1,
                kc0, bc0, kc1, src, dst, perm)
            if self.equivariant:
                cnt = segment.segment_count(src, n, g.edge_mask)
                pos = pos + segment._mean_divide(psum[:, :3], cnt)
        else:
            # gathers whose backward rides the dense sorted scatter
            # (marker-gated; measured +9% end-to-end on the v5e sweep)
            parts = [segment.gather_sender(x, g),
                     segment.gather_receiver_sorted(x, g), radial]
            if use_ea:
                parts.append(g.edge_attr)
            m = jnp.concatenate(parts, axis=-1)
            m = nn.relu(m @ k0 + b0)
            m = nn.relu(m @ k1 + b1)
            m = m * g.edge_mask[:, None]

            if self.equivariant:
                c = nn.relu(m @ kc0 + bc0)
                c = jnp.tanh(c @ kc1)  # tanh=True in reference E_GCL
                trans = jnp.clip(diff * c, -100.0, 100.0)
                # sender-side aggregation matching the reference; the
                # fused path scatters the same translation sum in-kernel
                pos = pos + segment.segment_mean(trans, src, n,
                                                 g.edge_mask)

            agg = segment.segment_sum(m, src, n, g.edge_mask)

        h = jnp.concatenate([x, agg], axis=-1)
        h = nn.Dense(self.hidden_dim, name="node_mlp_0")(h)
        h = nn.relu(h)
        h = nn.Dense(self.out_dim, name="node_mlp_1")(h)
        return h, pos


class EGCLStack(Base):
    has_batchnorm: bool = False

    def make_conv(self, name, in_dim, out_dim, last_layer):
        c = self.cfg
        return EGCL(
            out_dim,
            hidden_dim=c.hidden_dim,
            edge_dim=c.edge_dim or 0,
            equivariant=c.equivariance and not last_layer,
            name=name,
        )
