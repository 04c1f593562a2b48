"""SchNet stack (parity: reference hydragnn/models/SCFStack.py).

Continuous-filter convolution: Gaussian-smeared edge distances feed a filter
MLP (shifted-softplus) with a cosine cutoff envelope; messages are
filter-modulated linear node features, sum-aggregated.  An optional
E(3)-equivariant position-update branch (coord MLP on the filter values,
mean-aggregated displacement) runs on all but the last layer
(reference SCFStack.py:143-223).

Edge distances are recomputed from current positions each layer — the edge
*topology* is fixed host-side (static shapes), which matches the reference's
RadiusInteractionGraph behavior as long as positions move within the cutoff.
No BatchNorm feature layers (reference uses Identity; SCFStack.py:63).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import flax.linen as nn

from hydragnn_tpu.graph import segment
from hydragnn_tpu.models.base import Base
from hydragnn_tpu.models.layers import (
    DenseParams, edge_geometry, shifted_softplus)

# historical import location (DenseParams now lives in models/layers.py)
_DenseParams = DenseParams


def gaussian_smearing(dist, radius, num_gaussians):
    """PyG GaussianSmearing(0, radius, num_gaussians) parity."""
    offsets = jnp.linspace(0.0, radius, num_gaussians)
    coeff = -0.5 / (offsets[1] - offsets[0]) ** 2
    return jnp.exp(coeff * (dist[:, None] - offsets[None, :]) ** 2)


def _scf_pipeline_enabled(num_filters: int, num_gaussians: int) -> bool:
    """Fused CFConv edge pipeline gate (ops/scf_mp.py): structural limits
    (basis fits the padded lane count, width fits VMEM) plus a width
    floor — the in-kernel filter MLP re-evaluates E*F^2 in both backward
    passes, which only pays off where the composed path is stream-bound
    (measured BOTH sides of the crossover on the v5e: h64 f32 7.62 ->
    8.19 ms = pipeline loses; h512/h1024 bf16 +27% = pipeline wins —
    docs/PERF.md round 4).  Env override HYDRAGNN_SCF_FUSED=1/0 forces
    it either way.

    Numerics note (bf16 models): the pipeline evaluates the filter MLP
    and its backward matmuls — including the dW0/dW1 weight grads and
    drbf, which feed distance/position grads — with bf16 operands (f32
    accumulation), whereas the composed path's filter chain runs in f32
    (f32 params x f32 rbf).  Crossing the F >= 256 default therefore
    changes filter numerics beyond the stream dtype; drift is pinned to
    <4% of grad scale by tests/test_scf_fused.py::
    test_bf16_gradients_within_tolerance.  A/B against the composed path
    with HYDRAGNN_SCF_FUSED=0 if exact f32 filters are needed."""
    from hydragnn_tpu.ops.scf_mp import SCF_F_LIMIT

    if num_gaussians > 127 or num_filters > SCF_F_LIMIT:
        return False
    v = os.environ.get("HYDRAGNN_SCF_FUSED")
    if v is not None:
        return v.strip().lower() not in ("0", "false", "off", "no", "")
    return num_filters >= 256


class SCFConv(nn.Module):
    out_dim: int
    num_gaussians: int
    num_filters: int
    cutoff: float
    equivariant: bool
    use_edge_attr: bool

    @nn.compact
    def __call__(self, x, pos, g, train):
        n = x.shape[0]
        src, dst = g.senders, g.receivers

        if self.use_edge_attr and g.edge_attr is not None:
            w = jnp.linalg.norm(g.edge_attr, axis=-1)
        else:
            d = pos[src] - pos[dst]
            # eps inside the sqrt keeps the gradient finite on padding
            # self-edges (distance exactly 0) for jax.grad wrt positions
            w = jnp.sqrt(jnp.sum(d * d, axis=-1) + 1e-12)
        rbf = gaussian_smearing(w, self.cutoff, self.num_gaussians)

        # cosine envelope, hard-zeroed beyond the cutoff (edge topology is
        # static, so drifted positions must not re-enter with full weight)
        cut = 0.5 * (jnp.cos(w * jnp.pi / self.cutoff) + 1.0)
        cut = jnp.where(w <= self.cutoff, cut, 0.0)

        # filter params are declared matmul-free so the fused edge
        # pipeline below can consume them raw; the composed path applies
        # them exactly as the nn.Dense layers they replace (identical
        # names/inits — checkpoints are path-independent)
        k0, b0 = DenseParams(self.num_gaussians, self.num_filters,
                             name="filter_0")()
        k1, b1 = DenseParams(self.num_filters, self.num_filters,
                             name="filter_1")()
        perm = g.extras.get("edge_perm_sender") if g.extras else None
        fused_pipeline = (
            perm is not None and not self.equivariant
            and _scf_pipeline_enabled(self.num_filters, self.num_gaussians))

        filt = None
        if not fused_pipeline:
            filt = shifted_softplus(rbf @ k0 + b0) @ k1 + b1
            filt = filt * cut[:, None] * g.edge_mask[:, None]

        # xavier-uniform init on lin1/lin2, zero bias — parity with reference
        # CFConv.reset_parameters (SCFStack.py:185-188)
        h = nn.Dense(self.num_filters, use_bias=False,
                     kernel_init=nn.initializers.xavier_uniform(),
                     name="lin1")(x)

        if self.equivariant:
            diff, _ = edge_geometry(pos, src, dst)
            cmlp = nn.Dense(self.num_filters, name="coord_mlp_0")(filt)
            cmlp = nn.relu(cmlp)
            cmlp = nn.Dense(
                1,
                use_bias=False,
                # torch xavier_uniform_(gain=g) has std g*sqrt(2/fan_avg*... )
                # => variance_scaling needs scale = g^2 (reference
                # SCFStack.py:162-163, gain 0.001)
                kernel_init=nn.initializers.variance_scaling(
                    1e-6, "fan_avg", "uniform"
                ),
                name="coord_mlp_1",
            )(cmlp)
            trans = jnp.clip(diff * cmlp, -100.0, 100.0)
            # aggregated at the edge source, matching reference CFConv
            # coord_model (SCFStack.py:173-181)
            pos = pos + segment.segment_mean(trans, src, n, g.edge_mask)

        if fused_pipeline:
            # whole-edge-pipeline Pallas kernel (ops/scf_mp.py): filter MLP
            # + gather + multiply + segment-sum with no [E, F] HBM streams
            from hydragnn_tpu.ops.scf_mp import scf_edge_pipeline

            # tallied only when taken: below the width floor the CFConv
            # rides gather_mul (its own tally entry), by design
            segment._count("scf", True)
            cm = cut * g.edge_mask
            # em: schedule-skip validity (kernel never visits masked-edge
            # blocks — ~half the edge slots at flagship padding ratios)
            em = g.edge_mask.astype(jnp.int32)
            agg = scf_edge_pipeline(h, rbf, cm, em, k0, b0, k1, b1,
                                    g.senders, g.receivers, perm)
        else:
            # lowers to the fused gather-multiply-aggregate Pallas kernel
            # under HYDRAGNN_AGGR_BACKEND=fused (ops/fused_mp.py; measured
            # numbers in docs/PERF.md)
            agg = segment.gather_mul_segment(h, filt, g)
        out = nn.Dense(self.out_dim,
                       kernel_init=nn.initializers.xavier_uniform(),
                       name="lin2")(agg)
        return out, pos


class SCFStack(Base):
    has_batchnorm: bool = False

    def make_conv(self, name, in_dim, out_dim, last_layer):
        c = self.cfg
        assert c.num_gaussians is not None and c.num_filters is not None
        assert c.radius is not None, "SchNet requires radius input."
        return SCFConv(
            out_dim,
            num_gaussians=c.num_gaussians,
            num_filters=c.num_filters,
            cutoff=c.radius,
            equivariant=c.equivariance and not last_layer,
            use_edge_attr=c.use_edge_attr,
            name=name,
        )
