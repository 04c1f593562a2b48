"""Shared neural building blocks: activations, losses, MLPs, masked BatchNorm.

Parity targets:
  - activation selector        -> reference hydragnn/utils/model.py:30-44
  - loss selector              -> reference hydragnn/utils/model.py:47-55
  - PyG BatchNorm under padding-> :class:`MaskedBatchNorm` (masked statistics;
    with jit + sharding the batch statistics are computed over the *global*
    sharded batch, which natively gives SyncBatchNorm semantics, reference
    hydragnn/utils/distributed.py:238-239)
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import os

import jax
import jax.numpy as jnp
import flax.linen as nn


class PReLU(nn.Module):
    """Learnable leaky-ReLU (torch.nn.PReLU parity: single shared slope 0.25)."""

    @nn.compact
    def __call__(self, x):
        alpha = self.param("alpha", lambda key: jnp.asarray(0.25, jnp.float32))
        return jnp.where(x >= 0, x, alpha * x)


def activation_module(name: str):
    """Activation by config name (reference hydragnn/utils/model.py:30-44)."""
    fns = {
        "relu": nn.relu,
        "selu": nn.selu,
        "elu": nn.elu,
        "lrelu_01": lambda x: nn.leaky_relu(x, 0.1),
        "lrelu_025": lambda x: nn.leaky_relu(x, 0.25),
        "lrelu_05": lambda x: nn.leaky_relu(x, 0.5),
    }
    if name == "prelu":
        return PReLU()
    if name not in fns:
        raise ValueError(f"Unknown activation function: {name}")
    return fns[name]


def loss_function(name: str) -> Callable[[jax.Array, jax.Array, jax.Array], jax.Array]:
    """Masked, mean-reduced loss (reference hydragnn/utils/model.py:47-55).

    Signature: (pred, target, mask) -> scalar.  ``mask`` broadcasts along the
    leading axis; the mean runs over valid elements only, so padded rows
    reproduce the reference's unpadded loss exactly.
    """

    def _masked_mean(err, mask):
        # shard-aware (graph/partition.py): under a halo-sharding trace the
        # valid rows are split across shards — psum numerator and count so
        # every shard computes the exact GLOBAL masked mean (identity
        # outside a halo trace)
        from hydragnn_tpu.graph.partition import halo_psum

        m = mask.reshape(mask.shape + (1,) * (err.ndim - mask.ndim))
        denom = jnp.maximum(
            halo_psum(jnp.sum(m)) * err.shape[-1], 1.0)
        return halo_psum(jnp.sum(err * m)) / denom

    if name == "mse":
        return lambda p, t, m: _masked_mean((p - t) ** 2, m)
    if name == "mae":
        return lambda p, t, m: _masked_mean(jnp.abs(p - t), m)
    if name == "smooth_l1":

        def _sl1(p, t, m):
            d = jnp.abs(p - t)
            return _masked_mean(jnp.where(d < 1.0, 0.5 * d * d, d - 0.5), m)

        return _sl1
    if name == "rmse":
        return lambda p, t, m: jnp.sqrt(_masked_mean((p - t) ** 2, m) + 1e-16)
    if name == "softmax_xent":

        def _xent(p, t, m):
            # integer node labels in a float column (exact to 2^24); a
            # negative label marks a node with nothing to predict (the
            # last node of its graph), left out like padding
            from hydragnn_tpu.utils.scope import phase

            with phase("lm.xent"):
                ids = t[..., 0].astype(jnp.int32)
                p = p.astype(jnp.float32)
                picked = jnp.take_along_axis(
                    p, jnp.maximum(ids, 0)[..., None], axis=-1)
                nll = jax.nn.logsumexp(p, axis=-1, keepdims=True) - picked
                return _masked_mean(nll, m * (ids >= 0))

        return _xent
    raise ValueError(f"Unknown loss function: {name}")


def symmetric_uniform_init(bound: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


# torch.nn.Linear default init: kaiming_uniform(a=sqrt(5)) on the kernel
# (== uniform(+-sqrt(1/fan_in))) and uniform(+-1/sqrt(fan_in)) bias.  The
# reference relies on this spread-out init; zero-init biases make narrow ReLU
# heads collapse to constants on some seeds.
torch_kernel_init = nn.initializers.variance_scaling(
    1.0 / 3.0, "fan_in", "uniform")


class TDense(nn.Module):
    """Dense layer with torch.nn.Linear's default initialization."""

    features: int

    @nn.compact
    def __call__(self, x):
        import math

        fan_in = x.shape[-1]
        bound = 1.0 / math.sqrt(fan_in)
        kernel = self.param(
            "kernel", torch_kernel_init, (fan_in, self.features))
        bias = self.param(
            "bias", symmetric_uniform_init(bound), (self.features,))
        return x @ kernel + bias


class MLP(nn.Module):
    """Dense stack: hidden layers with activation, linear output layer."""

    features: Sequence[int]
    activation: str = "relu"
    final_activation: bool = False

    @nn.compact
    def __call__(self, x):
        act = activation_module(self.activation)
        for i, f in enumerate(self.features):
            x = TDense(f, name=f"dense_{i}")(x)
            if i < len(self.features) - 1 or self.final_activation:
                x = act(x)
        return x


class MaskedBatchNorm(nn.Module):
    """BatchNorm over valid (masked) rows with running statistics.

    Equivalent to PyG ``BatchNorm`` (torch momentum 0.1, eps 1e-5) but exact
    under padded static-shape batching: padded rows contribute nothing to the
    batch statistics.  Under jit with a data-sharded batch the reductions are
    global across devices — i.e. cross-replica (Sync) BatchNorm for free.
    """

    features: int
    momentum: float = 0.1  # torch convention: new = (1-m)*old + m*batch
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x, mask, use_running_average: bool = False):
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((self.features,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((self.features,), jnp.float32)
        )
        scale = self.param("scale", nn.initializers.ones, (self.features,))
        bias = self.param("bias", nn.initializers.zeros, (self.features,))

        # experimental recipe knob (wide-GAT eval-divergence studies,
        # docs/PERF.md): override the running-stats momentum without
        # touching the checkpointed module tree
        momentum = float(
            os.environ.get("HYDRAGNN_BN_MOMENTUM") or self.momentum)

        if use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            # shard-aware statistics: under a halo-sharding trace
            # (graph/partition.py:halo_context) the masked rows are split
            # across shards — psum the partial sums/counts so every shard
            # normalizes with the exact GLOBAL batch statistics (the same
            # SyncBatchNorm semantics the GSPMD path gets implicitly, and
            # the property that keeps a halo copy bit-consistent with its
            # owner row).  Identity outside a halo trace.
            from hydragnn_tpu.graph.partition import halo_psum

            m = mask.astype(x.dtype)[:, None]
            count = jnp.maximum(halo_psum(jnp.sum(m)), 1.0)
            mean = halo_psum(jnp.sum(x * m, axis=0)) / count
            var = halo_psum(jnp.sum(((x - mean) ** 2) * m, axis=0)) / count
            if not self.is_initializing():
                # torch tracks the *unbiased* variance in running stats
                unbiased = var * count / jnp.maximum(count - 1.0, 1.0)
                ra_mean.value = (
                    1.0 - momentum
                ) * ra_mean.value + momentum * mean
                ra_var.value = (
                    1.0 - momentum
                ) * ra_var.value + momentum * unbiased
        return scale * (x - mean) * jax.lax.rsqrt(var + self.eps) + bias


def shifted_softplus(x):
    """softplus(x) - log(2): SchNet's activation (PyG ShiftedSoftplus)."""
    return jax.nn.softplus(x) - jnp.log(2.0)


class DenseParams(nn.Module):
    """Parameters of an ``nn.Dense`` WITHOUT its matmul: same names
    (kernel/bias), same default inits, same param tree — so the fused
    edge-block paths (ops/fused_block.py specs: SchNet's cfconv,
    DimeNet's triplet interaction, EGNN's interaction block, CGCNN's
    gated sum) and the composed paths share checkpoints.
    ``kernel_init`` overrides for layers whose nn.Dense twin uses a
    non-default init (EGNN's coord gate)."""

    in_dim: int
    features: int
    use_bias: bool = True
    kernel_init: object = None

    @nn.compact
    def __call__(self):
        init = self.kernel_init or nn.linear.default_kernel_init
        k = self.param("kernel", init, (self.in_dim, self.features))
        if not self.use_bias:
            return k, None
        b = self.param("bias", nn.initializers.zeros_init(),
                       (self.features,))
        return k, b


def edge_geometry(pos, src, dst):
    """The ONE per-edge geometry definition shared by the composed paths
    and the fused kernels (EGNN's interaction block, SchNet's coord
    branch, the builder's geo-lane packing): normalized difference
    vector and squared distance.  eps inside the sqrt: padding
    self-edges have radial == 0 exactly, where sqrt's gradient is inf —
    this path must stay differentiable for the energy-gradient force
    loss (jax.grad wrt pos)."""
    diff = pos[src] - pos[dst]
    radial = jnp.sum(diff * diff, axis=-1, keepdims=True)
    diff = diff / (jnp.sqrt(radial + 1e-12) + 1.0)  # norm_diff=True
    return diff, radial
