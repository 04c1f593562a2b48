"""Shared multi-headed GNN skeleton (flax.linen).

TPU-native re-design of the reference's ``Base`` (reference
hydragnn/models/Base.py:24-426): a stack of interchangeable message-passing
convolutions + masked BatchNorm feature layers, masked global mean pooling,
and N decoder heads (graph-level MLP heads behind a shared MLP trunk;
node-level MLP / per-node-MLP / conv-stack heads).

Differences by design (TPU-first):
  - operates on padded static-shape :class:`GraphBatch` with masks, so one
    compiled XLA program serves every batch;
  - batch statistics in :class:`MaskedBatchNorm` are computed over the global
    (sharded) batch under jit — cross-replica SyncBatchNorm for free;
  - the multi-head label layout is static (see graph/batch.py), so the loss
    is a plain masked mean per head, with task weights normalized to sum 1
    (parity with reference Base.loss_hpweighted, Base.py:343-360).
"""

from __future__ import annotations

import dataclasses
from importlib import import_module
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from hydragnn_tpu.config.config import SEQUENCE_MODELS
from hydragnn_tpu.graph import segment
from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.layers import (
    MLP,
    MaskedBatchNorm,
    activation_module,
    loss_function,
)


def sequence_module(model_type: str):
    """The module of a sequence stack (a row of ``SEQUENCE_MODELS``): its
    ``Config`` and ``Stack``.  Imported when asked for, so that building a
    message-passing model imports no language model."""
    return import_module(
        f"hydragnn_tpu.models.{SEQUENCE_MODELS[model_type]}")


def _validated_compute_dtype(arch) -> str:
    """"bfloat16" via ``mixed_precision: true`` or an explicit
    ``compute_dtype``; anything unrecognized raises instead of silently
    training in f32 while the user believes bf16 is on."""
    dt = ("bfloat16" if arch.get("mixed_precision")
          else arch.get("compute_dtype", "float32"))
    if dt not in ("float32", "bfloat16"):
        raise ValueError(
            f"compute_dtype must be 'float32' or 'bfloat16', got {dt!r}")
    return dt


@dataclasses.dataclass(frozen=True)
class GraphHeadCfg:
    num_sharedlayers: int
    dim_sharedlayers: int
    num_headlayers: int
    dim_headlayers: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class NodeHeadCfg:
    num_headlayers: int
    dim_headlayers: Tuple[int, ...]
    type: str = "mlp"  # "mlp" | "mlp_per_node" | "conv"


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Static (hashable) model hyper-parameters.

    Mirrors the argument list of the reference factory
    (hydragnn/models/create.py:71-102) as one frozen dataclass.
    """

    model_type: str
    input_dim: int
    hidden_dim: int
    output_dim: Tuple[int, ...]
    output_type: Tuple[str, ...]
    graph_head: Optional[GraphHeadCfg]
    node_head: Optional[NodeHeadCfg]
    activation: str = "relu"
    loss_fn: str = "mse"
    task_weights: Tuple[float, ...] = ()
    equivariance: bool = False
    num_conv_layers: int = 2
    num_nodes: Optional[int] = None
    edge_dim: Optional[int] = None
    dropout: float = 0.25
    freeze_conv: bool = False
    initial_bias: Optional[float] = None
    # "bfloat16" = mixed precision: f32 params/grads/loss, bf16 compute
    # (cast at the train-step boundary, hydragnn_tpu/train/trainer.py)
    compute_dtype: str = "float32"
    # --- architecture-specific knobs ---
    pna_avg_deg_log: Optional[float] = None
    pna_avg_deg_lin: Optional[float] = None
    gat_heads: int = 6
    gat_negative_slope: float = 0.05
    max_degree: Optional[int] = None
    max_neighbours: Optional[int] = None
    num_gaussians: Optional[int] = None
    num_filters: Optional[int] = None
    radius: Optional[float] = None
    envelope_exponent: Optional[int] = None
    num_before_skip: Optional[int] = None
    num_after_skip: Optional[int] = None
    num_radial: Optional[int] = None
    num_spherical: Optional[int] = None
    basis_emb_size: Optional[int] = None
    int_emb_size: Optional[int] = None
    out_emb_size: Optional[int] = None
    # the language-model stack (models/laguna.py): the sizes held on this
    # chip and the chip's share of the layer (parallel/share.py)
    lm: Optional[Any] = None
    share: Optional[Any] = None

    def __post_init__(self):
        # validate HERE so every construction path (from_config, direct
        # dataclass use, dataclasses.replace, env knobs) is covered — the
        # trainer maps anything != "bfloat16" to f32 without error, so an
        # unvalidated typo like "bf16" would silently train in f32
        if self.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                "compute_dtype must be 'float32' or 'bfloat16', "
                f"got {self.compute_dtype!r}")

    @property
    def use_edge_attr(self) -> bool:
        return self.edge_dim is not None and self.edge_dim > 0

    @property
    def num_heads(self) -> int:
        return len(self.output_dim)

    @property
    def norm_task_weights(self) -> Tuple[float, ...]:
        s = sum(abs(w) for w in self.task_weights)
        return tuple(w / s for w in self.task_weights)

    @staticmethod
    def from_config(config: Dict[str, Any]) -> "ModelConfig":
        """Build from a finalized reference-schema JSON config dict
        (accepts the full config or its NeuralNetwork section)."""
        if "NeuralNetwork" in config:
            config = config["NeuralNetwork"]
        arch = config["Architecture"]
        training = config["Training"]
        heads_cfg = arch.get("output_heads", {})
        graph_head = None
        if "graph" in heads_cfg:
            g = heads_cfg["graph"]
            graph_head = GraphHeadCfg(
                num_sharedlayers=g["num_sharedlayers"],
                dim_sharedlayers=g["dim_sharedlayers"],
                num_headlayers=g["num_headlayers"],
                dim_headlayers=tuple(g["dim_headlayers"]),
            )
        node_head = None
        if "node" in heads_cfg:
            n = heads_cfg["node"]
            node_head = NodeHeadCfg(
                num_headlayers=n["num_headlayers"],
                dim_headlayers=tuple(n["dim_headlayers"]),
                type=n.get("type", "mlp"),
            )
        pna_deg = arch.get("pna_deg")
        avg_log = avg_lin = None
        if pna_deg is not None:
            hist = np.asarray(pna_deg, dtype=np.float64)
            bins = np.arange(len(hist), dtype=np.float64)
            total = max(hist.sum(), 1.0)
            avg_log = float((np.log(bins + 1) * hist).sum() / total)
            avg_lin = float((bins * hist).sum() / total)
        hidden_dim = arch["hidden_dim"]
        lm = share = None
        if arch["model_type"] in SEQUENCE_MODELS:
            from hydragnn_tpu.parallel.share import LayerShare

            config = sequence_module(arch["model_type"]).Config
            lm = config.from_arch(arch)
            share = LayerShare.from_arch(
                arch[SEQUENCE_MODELS[arch["model_type"]]],
                arch.get("share") or {}, experts_key=config.experts_key)
        if arch["model_type"] == "CGCNN":
            # CGConv preserves feature dims (reference CGCNNStack.py:30-40)
            hidden_dim = arch["input_dim"]
        return ModelConfig(
            model_type=arch["model_type"],
            input_dim=arch["input_dim"],
            hidden_dim=hidden_dim,
            output_dim=tuple(arch["output_dim"]),
            output_type=tuple(arch["output_type"]),
            graph_head=graph_head,
            node_head=node_head,
            activation=arch.get("activation_function", "relu"),
            loss_fn=training.get("loss_function_type", "mse"),
            task_weights=tuple(float(w) for w in arch["task_weights"]),
            equivariance=bool(arch.get("equivariance", False)),
            num_conv_layers=arch["num_conv_layers"],
            num_nodes=arch.get("num_nodes"),
            edge_dim=arch.get("edge_dim"),
            freeze_conv=bool(arch.get("freeze_conv_layers", False)),
            initial_bias=arch.get("initial_bias"),
            compute_dtype=_validated_compute_dtype(arch),
            pna_avg_deg_log=avg_log,
            pna_avg_deg_lin=avg_lin,
            max_degree=arch.get("max_neighbours"),
            max_neighbours=arch.get("max_neighbours"),
            num_gaussians=arch.get("num_gaussians"),
            num_filters=arch.get("num_filters"),
            radius=arch.get("radius"),
            envelope_exponent=arch.get("envelope_exponent"),
            num_before_skip=arch.get("num_before_skip"),
            num_after_skip=arch.get("num_after_skip"),
            num_radial=arch.get("num_radial"),
            num_spherical=arch.get("num_spherical"),
            basis_emb_size=arch.get("basis_emb_size"),
            int_emb_size=arch.get("int_emb_size"),
            out_emb_size=arch.get("out_emb_size"),
            lm=lm,
            share=share,
            # extension over the reference schema (its Base hardcodes
            # dropout=0.25 with a FIXME about config exposure,
            # reference Base.py:40): Architecture.dropout overrides the
            # GAT attention-dropout rate.  Setting 0.0 is the measured
            # recipe for the wide-GAT eval divergence — docs/PERF.md
            # round 5, test MAE 0.40 vs 2.46 at the flagship protocol.
            dropout=float(arch.get("dropout", 0.25)),
        )


class MLPNode(nn.Module):
    """Node-level MLP head: one shared MLP, or one MLP per node index
    (reference hydragnn/models/Base.py:366-426)."""

    hidden_dims: Tuple[int, ...]
    output_dim: int
    activation: str
    per_node: bool = False
    num_nodes: Optional[int] = None

    @nn.compact
    def __call__(self, x, node_gid):
        if not self.per_node:
            return MLP(
                tuple(self.hidden_dims) + (self.output_dim,),
                activation=self.activation,
            )(x)
        assert self.num_nodes is not None, "num_nodes required for mlp_per_node"
        act = activation_module(self.activation)
        # Per-node parameter banks: [num_nodes, in, out] selected by the
        # node's index within its (fixed-size) graph.
        n = x.shape[0]
        local_idx = jnp.arange(n, dtype=jnp.int32) - node_gid * self.num_nodes
        local_idx = jnp.clip(local_idx, 0, self.num_nodes - 1)
        dims = (x.shape[-1],) + tuple(self.hidden_dims) + (self.output_dim,)
        h = x
        for i in range(len(dims) - 1):
            w = self.param(
                f"w_{i}",
                nn.initializers.lecun_normal(),
                (self.num_nodes, dims[i], dims[i + 1]),
            )
            b = self.param(
                f"b_{i}", nn.initializers.zeros, (self.num_nodes, dims[i + 1])
            )
            h = jnp.einsum("ni,nio->no", h, jnp.take(w, local_idx, axis=0))
            h = h + jnp.take(b, local_idx, axis=0)
            if i < len(dims) - 2:
                h = act(h)
        return h


class Base(nn.Module):
    """Shared skeleton; subclasses provide ``make_conv`` (+ dim overrides)."""

    cfg: ModelConfig

    # Subclasses flip this off when the reference uses Identity feature
    # layers instead of BatchNorm (SchNet, EGNN; SCFStack.py:63, EGCLStack.py:41).
    has_batchnorm: bool = True

    def make_conv(self, name: str, in_dim: int, out_dim: int, last_layer: bool):
        raise NotImplementedError

    def encoder_dims(self) -> List[Tuple[int, int, int]]:
        """Per-encoder-layer (in_dim, out_dim, bn_features)."""
        c = self.cfg
        dims = [(c.input_dim, c.hidden_dim, c.hidden_dim)]
        for _ in range(c.num_conv_layers - 1):
            dims.append((c.hidden_dim, c.hidden_dim, c.hidden_dim))
        return dims

    def node_conv_dims(self, head_dim: int) -> Tuple[List[Tuple[int, int, int]], Tuple[int, int, int]]:
        """Hidden conv dims + output conv dims for conv-type node heads
        (reference Base._init_node_conv, Base.py:141-199)."""
        c = self.cfg
        hdn = list(c.node_head.dim_headlayers)
        hidden = [(c.hidden_dim, hdn[0], hdn[0])]
        for i in range(c.node_head.num_headlayers - 1):
            hidden.append((hdn[i], hdn[i + 1], hdn[i + 1]))
        out = (hdn[-1], head_dim, head_dim)
        return hidden, out

    def encoder_out_dim(self) -> int:
        return self.cfg.hidden_dim

    @nn.compact
    def __call__(self, g: GraphBatch, train: bool = True):
        c = self.cfg
        act = activation_module(c.activation)
        num_graphs = g.num_graphs

        # --- encoder: conv stack + feature layers ---
        x, pos = g.x, g.pos
        enc_dims = self.encoder_dims()
        n_layers = len(enc_dims)
        for i, (din, dout, bnf) in enumerate(enc_dims):
            last = i == n_layers - 1
            conv = self.make_conv(f"encoder_conv_{i}", din, dout, last)
            x, pos = conv(x, pos, g, train)
            if self.has_batchnorm:
                x = MaskedBatchNorm(bnf, name=f"encoder_bn_{i}")(
                    x, g.node_mask, use_running_average=not train
                )
            x = act(x)

        # --- decoder: masked mean pool + heads ---
        x_graph = segment.masked_mean_pool(
            x, g.node_gid, num_graphs, g.node_mask,
            sorted_hint=bool(g.extras and "edge_perm_sender" in g.extras))

        graph_shared = None
        if c.graph_head is not None:
            gh = c.graph_head
            graph_shared = MLP(
                (gh.dim_sharedlayers,) * gh.num_sharedlayers,
                activation=c.activation,
                final_activation=True,
                name="graph_shared",
            )

        # Conv-type node heads share their hidden conv stack across heads
        # (reference appends the same modules to every head; Base.py:258-266).
        node_conv_hidden = None
        if (
            c.node_head is not None
            and c.node_head.type == "conv"
            and "node" in c.output_type
        ):
            hidden_dims, _ = self.node_conv_dims(0)
            node_conv_hidden = [
                (
                    self.make_conv(f"node_conv_hidden_{j}", din, dout, False),
                    MaskedBatchNorm(bnf, name=f"node_conv_hidden_bn_{j}"),
                )
                for j, (din, dout, bnf) in enumerate(hidden_dims)
            ]

        outputs = []
        for ihead, (head_dim, head_type) in enumerate(zip(c.output_dim, c.output_type)):
            if head_type == "graph":
                gh = c.graph_head
                z = graph_shared(x_graph)
                z = MLP(
                    tuple(gh.dim_headlayers) + (head_dim,),
                    activation=c.activation,
                    name=f"head_{ihead}",
                )(z)
                outputs.append(z)
            elif head_type == "node":
                nh = c.node_head
                if nh.type in ("mlp", "mlp_per_node"):
                    z = MLPNode(
                        hidden_dims=nh.dim_headlayers,
                        output_dim=head_dim,
                        activation=c.activation,
                        per_node=nh.type == "mlp_per_node",
                        num_nodes=c.num_nodes,
                        name=f"head_{ihead}",
                    )(x, g.node_gid)
                elif nh.type == "conv":
                    _, (odin, odout, obnf) = self.node_conv_dims(head_dim)
                    z, zpos = x, pos
                    for conv, bn in node_conv_hidden:
                        z, zpos = conv(z, zpos, g, train)
                        z = act(bn(z, g.node_mask, use_running_average=not train))
                    out_conv = self.make_conv(f"head_{ihead}_out_conv", odin, odout, True)
                    z, zpos = out_conv(z, zpos, g, train)
                    z = act(
                        MaskedBatchNorm(obnf, name=f"head_{ihead}_out_bn")(
                            z, g.node_mask, use_running_average=not train
                        )
                    )
                else:
                    raise ValueError(f"Unknown node head type: {nh.type}")
                outputs.append(z)
            else:
                raise ValueError(f"Unknown head type: {head_type}")
        return tuple(outputs)


def multihead_loss_nll(
    cfg: ModelConfig,
    outputs: Sequence[jax.Array],
    g: GraphBatch,
) -> Tuple[jax.Array, List[jax.Array]]:
    """Gaussian NLL multi-task loss for UQ heads (parity with the reference's
    disabled stub Base.loss_nll, Base.py:322-341: each head emits [mean,
    log_sigma] pairs; loss = 0.5*log(2*pi*sigma^2) + (x-mu)^2/(2*sigma^2))."""
    weights = cfg.norm_task_weights
    total = 0.0
    per_head = []
    for ihead, (out, head_type) in enumerate(zip(outputs, cfg.output_type)):
        label = g.labels[ihead]
        mask = g.graph_mask if head_type == "graph" else g.node_mask
        dim = label.shape[-1]
        mean, log_sigma = out[..., :dim], out[..., dim : 2 * dim]
        # clamp log_sigma so padded rows cannot produce inf/NaN through exp
        log_sigma = jnp.clip(log_sigma, -15.0, 15.0)
        var = jnp.exp(2.0 * log_sigma)
        nll = 0.5 * jnp.log(2.0 * jnp.pi * var) + (label - mean) ** 2 / (
            2.0 * var)
        m = mask.reshape(mask.shape + (1,) * (nll.ndim - mask.ndim))
        nll = jnp.where(m > 0, nll, 0.0)
        # shard-aware like loss_function's masked mean (graph/partition.py)
        from hydragnn_tpu.graph.partition import halo_psum

        head_loss = halo_psum(jnp.sum(nll)) / jnp.maximum(
            halo_psum(jnp.sum(m)) * dim, 1.0)
        per_head.append(head_loss)
        total = total + weights[ihead] * head_loss
    return total, per_head


def set_initial_bias(params, cfg: ModelConfig):
    """Set the output-layer bias of every graph head to ``cfg.initial_bias``
    (parity: reference Base.initial_bias for UQ, Base.py:134-139)."""
    import flax

    if cfg.initial_bias is None:
        return params
    flat = flax.traverse_util.flatten_dict(params)
    # last dense index per head module
    last_dense: Dict[str, int] = {}
    for path in flat:
        if len(path) >= 2 and str(path[0]).startswith("head_") and str(
                path[1]).startswith("dense_"):
            idx = int(str(path[1]).split("_")[1])
            last_dense[path[0]] = max(last_dense.get(path[0], -1), idx)
    for path in list(flat):
        if (len(path) >= 3 and str(path[0]).startswith("head_")
                and str(path[1]) == f"dense_{last_dense.get(path[0], -1)}"
                and path[2] == "bias"):
            flat[path] = jnp.full_like(flat[path], cfg.initial_bias)
    return flax.traverse_util.unflatten_dict(flat)


def encoder_freeze_mask(updates, frozen: bool):
    """Zero updates for encoder conv/bn params (parity: reference
    Base.freeze_conv, Base.py:128-132 — frozen conv layers receive no
    gradient updates and no weight decay)."""
    if not frozen:
        return updates
    import jax.tree_util as jtu

    def zero_enc(path, u):
        top = str(getattr(path[0], "key", path[0]))
        if top.startswith("encoder_"):
            return jnp.zeros_like(u)
        return u

    return jtu.tree_map_with_path(zero_enc, updates)


def print_model(model: "Base", params, verbosity: int = 0) -> int:
    """Parameter-count summary (reference utils/model.py:157-165)."""
    import numpy as np

    from hydragnn_tpu.utils.print_utils import print_distributed

    leaves = jax.tree.leaves(params)
    total = int(sum(np.prod(l.shape) for l in leaves))
    print_distributed(
        verbosity,
        f"{type(model).__name__}: {len(leaves)} parameter arrays, "
        f"{total} parameters")
    return total


def multihead_loss(
    cfg: ModelConfig,
    outputs: Sequence[jax.Array],
    g: GraphBatch,
) -> Tuple[jax.Array, List[jax.Array]]:
    """Weighted multi-task loss over padded batches.

    Parity with reference Base.loss_hpweighted (Base.py:343-360): per-head
    loss via the configured loss function, total = sum of per-head losses
    times normalized task weights.  ``loss_function_type: "gaussian_nll"``
    selects the UQ loss (heads emit [mean, log_sigma] at 2x the label dim;
    pair with ``Architecture.initial_bias`` — parity-plus over the
    reference's disabled stub, Base.py:322-341).
    """
    if cfg.loss_fn == "gaussian_nll":
        return multihead_loss_nll(cfg, outputs, g)
    loss_fn = loss_function(cfg.loss_fn)
    weights = cfg.norm_task_weights
    total = 0.0
    per_head = []
    for ihead, (out, head_type) in enumerate(zip(outputs, cfg.output_type)):
        label = g.labels[ihead]
        mask = g.graph_mask if head_type == "graph" else g.node_mask
        head_loss = loss_fn(out, label, mask)
        per_head.append(head_loss)
        total = total + weights[ihead] * head_loss
    return total, per_head
