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

import jax.numpy as jnp
import flax.linen as nn

from hydragnn_tpu.graph import segment
from hydragnn_tpu.models.base import Base
from hydragnn_tpu.models.layers import DenseParams, edge_geometry

# historical import location (DenseParams now lives in models/layers.py)
_DenseParams = DenseParams


def gaussian_smearing(dist, radius, num_gaussians):
    """PyG GaussianSmearing(0, radius, num_gaussians) parity."""
    offsets = jnp.linspace(0.0, radius, num_gaussians)
    coeff = -0.5 / (offsets[1] - offsets[0]) ** 2
    return jnp.exp(coeff * (dist[:, None] - offsets[None, :]) ** 2)


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

        # filter params are declared matmul-free: the filter network is
        # handed on as its GENERATOR, so that under the fused backend the
        # two gather-multiply kernels make each block of it in VMEM,
        # forward and backward, and no [E, F] filter, pre-activation or
        # filter cotangent exists in HBM (ops/scf_mp.py; every width up to
        # SCF_F_LIMIT — the >= 256-filter gate and its env override went
        # with the three-pass kernel, PERF.md PR 27).  Everywhere else it
        # is evaluated exactly as the nn.Dense layers it replaces
        # (identical names/inits — checkpoints are path-independent).
        # Under a bf16 model the in-VMEM filter runs bf16 operands with
        # f32 accumulation where the composed one runs f32 (drift pinned
        # by tests/test_scf_fused.py::test_bf16_gradients_within_tolerance)
        k0, b0 = DenseParams(self.num_gaussians, self.num_filters,
                             name="filter_0")()
        k1, b1 = DenseParams(self.num_filters, self.num_filters,
                             name="filter_1")()
        filt = segment.CFFilter(rbf, cut, k0, b0, k1, b1)
        if self.equivariant:
            # the coordinate MLP below consumes the filter values
            filt = filt.dense(g.edge_mask)

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

        # under HYDRAGNN_AGGR_BACKEND=fused: the gather-multiply-aggregate
        # Pallas kernels (ops/fused_mp.py), the filter made inside them
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
