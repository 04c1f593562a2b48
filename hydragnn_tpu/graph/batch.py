"""Padded, static-shape graph batch — the core data structure of the framework.

TPU-first redesign of the reference's dynamic PyG ``Batch`` (HydraGNN collates
variable-size graphs with ``Batch.from_data_list``; see reference
hydragnn/preprocess/load_data.py:226-297).  XLA requires static shapes, so we
batch graphs jraph-style: concatenate nodes/edges of all graphs in the batch,
then pad nodes, edges and graphs up to a fixed ``PadSpec``.  Padding nodes are
assigned to a trailing *padding graph* (the last graph slot), padding edges
connect the last (padding) node to itself, and boolean masks record validity.

The multi-head label layout is *static*: instead of the reference's per-batch
``data.y``/``y_loc`` offset bookkeeping computed on CPU every step
(reference hydragnn/train/train_validate_test.py:287-350), the batcher emits
one label array per head — graph-level heads get ``[num_graphs, dim]``,
node-level heads get ``[num_nodes, dim]`` — so the loss is a masked mean with
no runtime index computation.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from flax import struct


@dataclasses.dataclass(frozen=True)
class HeadSpec:
    """Static description of one prediction head (one task).

    Mirrors the information the reference spreads across
    ``Variables_of_interest.type``/``output_index``/``output_dim``
    (reference hydragnn/utils/config_utils.py:153-189).
    """

    name: str
    type: str  # "graph" | "node"
    dim: int   # feature dimension of this head's output (per graph or per node)


@dataclasses.dataclass(frozen=True)
class PadSpec:
    """Static padded sizes of a batch: everything XLA needs to know."""

    num_nodes: int
    num_edges: int
    num_graphs: int  # includes the trailing padding graph

    def __post_init__(self):
        assert self.num_nodes >= 1 and self.num_graphs >= 1

    @staticmethod
    def for_batch(
        batch_size: int,
        max_nodes_per_graph: int,
        max_edges_per_graph: int,
        round_to: int = 8,
    ) -> "PadSpec":
        """Pad spec for batches of up to ``batch_size`` graphs.

        One extra node/graph slot is reserved for padding; sizes are rounded
        up so the per-batch shapes hit TPU-friendly multiples.
        """

        def _round(x: int) -> int:
            return int(-(-x // round_to) * round_to)

        return PadSpec(
            num_nodes=_round(batch_size * max_nodes_per_graph + 1),
            num_edges=_round(batch_size * max_edges_per_graph + 1),
            num_graphs=batch_size + 1,
        )


@struct.dataclass
class GraphBatch:
    """A padded batch of graphs as a JAX pytree.

    Shapes (all static):
      x:          [N, F]   node input features
      pos:        [N, 3]   node positions
      senders:    [E]      edge source node index (message source)
      receivers:  [E]      edge destination node index (aggregation site)
      edge_attr:  [E, Fe]  or None
      node_gid:   [N]      graph id per node (padding nodes -> last graph)
      node_mask:  [N]      1.0 for real nodes
      edge_mask:  [E]      1.0 for real edges
      graph_mask: [G]      1.0 for real graphs
      labels:     tuple of per-head label arrays; graph heads [G, dim],
                  node heads [N, dim] (ordering matches the HeadSpec list)
      cell:       [G, 3, 3] periodic cell per graph, or None
      extras:     dict of auxiliary per-batch arrays (e.g. energy scaling)
    """

    x: jax.Array
    pos: jax.Array
    senders: jax.Array
    receivers: jax.Array
    edge_attr: Optional[jax.Array]
    node_gid: jax.Array
    node_mask: jax.Array
    edge_mask: jax.Array
    graph_mask: jax.Array
    labels: Tuple[jax.Array, ...]
    cell: Optional[jax.Array] = None
    extras: Dict[str, jax.Array] = struct.field(default_factory=dict)

    @property
    def num_nodes(self) -> int:
        return self.x.shape[0]

    @property
    def num_edges(self) -> int:
        return self.senders.shape[0]

    @property
    def num_graphs(self) -> int:
        return self.graph_mask.shape[0]

    @property
    def n_real_graphs(self) -> jax.Array:
        return jnp.sum(self.graph_mask)


class GraphSample:
    """One host-side graph sample (numpy).

    The host-side analog of a PyG ``Data`` object: node features ``x``,
    positions ``pos``, optional precomputed edges, and packed label arrays
    (``graph_y``/``node_y``) that :func:`collate` slices into per-head
    labels via :func:`default_label_slices` or
    ``config.label_slices_from_config``.
    """

    __slots__ = (
        "x",
        "pos",
        "edge_index",
        "edge_attr",
        "graph_y",
        "node_y",
        "cell",
        "extras",
    )

    def __init__(
        self,
        x: np.ndarray,
        pos: np.ndarray,
        edge_index: Optional[np.ndarray] = None,
        edge_attr: Optional[np.ndarray] = None,
        graph_y: Optional[np.ndarray] = None,
        node_y: Optional[np.ndarray] = None,
        cell: Optional[np.ndarray] = None,
        extras: Optional[Dict[str, np.ndarray]] = None,
    ):
        self.x = np.asarray(x, dtype=np.float32)
        self.pos = np.asarray(pos, dtype=np.float32)
        self.edge_index = (
            None if edge_index is None else np.asarray(edge_index, dtype=np.int32)
        )
        self.edge_attr = (
            None if edge_attr is None else np.asarray(edge_attr, dtype=np.float32)
        )
        self.graph_y = (
            None if graph_y is None else np.asarray(graph_y, dtype=np.float32)
        )
        self.node_y = None if node_y is None else np.asarray(node_y, dtype=np.float32)
        self.cell = None if cell is None else np.asarray(cell, dtype=np.float32)
        self.extras = extras or {}

    @property
    def num_nodes(self) -> int:
        return self.x.shape[0]

    @property
    def num_edges(self) -> int:
        return 0 if self.edge_index is None else self.edge_index.shape[1]


def collate(
    samples: Sequence[GraphSample],
    pad: PadSpec,
    head_specs: Sequence[HeadSpec],
    graph_feature_slices: Optional[Sequence[Tuple[int, int]]] = None,
    node_feature_slices: Optional[Sequence[Tuple[int, int]]] = None,
) -> GraphBatch:
    """Collate + pad host-side samples into a static-shape ``GraphBatch``.

    ``graph_feature_slices`` / ``node_feature_slices`` give, per head, the
    ``(start, end)`` column slice into ``sample.graph_y`` / ``sample.node_y``
    from which that head's labels are taken.  When omitted, heads consume
    consecutive slices by their declared dim.
    """

    n_samp = len(samples)
    if n_samp > pad.num_graphs - 1:
        raise ValueError(
            f"batch of {n_samp} graphs exceeds pad spec {pad.num_graphs - 1}"
        )
    tot_nodes = sum(s.num_nodes for s in samples)
    tot_edges = sum(s.num_edges for s in samples)
    if tot_nodes > pad.num_nodes - 1 or tot_edges > pad.num_edges:
        raise ValueError(
            f"batch ({tot_nodes} nodes, {tot_edges} edges) exceeds pad spec "
            f"({pad.num_nodes - 1}, {pad.num_edges})"
        )

    fdim = samples[0].x.shape[1] if samples[0].x.ndim > 1 else 1
    N, E, G = pad.num_nodes, pad.num_edges, pad.num_graphs

    # Vectorized packing: one np.concatenate per field instead of a 512-way
    # Python assignment loop (the loop was the input-pipeline bottleneck —
    # slower than the chip's step rate at flagship batch sizes).
    node_counts = np.fromiter(
        (s.num_nodes for s in samples), np.int64, count=n_samp)
    edge_counts = np.fromiter(
        (s.num_edges for s in samples), np.int64, count=n_samp)
    node_offs = np.zeros(n_samp, np.int64)
    np.cumsum(node_counts[:-1], out=node_offs[1:])

    x = np.zeros((N, fdim), np.float32)
    xs_list = [s.x if s.x.ndim > 1 else s.x[:, None] for s in samples]
    np.concatenate(xs_list, axis=0, out=x[:tot_nodes])
    pos = np.zeros((N, 3), np.float32)
    np.concatenate([s.pos for s in samples], axis=0, out=pos[:tot_nodes])

    senders = np.full((E,), N - 1, np.int32)
    receivers = np.full((E,), N - 1, np.int32)
    has_edge_attr = samples[0].edge_attr is not None
    edge_attr = None
    if has_edge_attr:
        ea_dim = samples[0].edge_attr.shape[1]
        edge_attr = np.zeros((E, ea_dim), np.float32)
    if tot_edges:
        ei = np.concatenate(
            [s.edge_index for s in samples if s.num_edges], axis=1)
        edge_shift = np.repeat(node_offs, edge_counts).astype(np.int32)
        senders[:tot_edges] = ei[0] + edge_shift
        receivers[:tot_edges] = ei[1] + edge_shift
        if has_edge_attr:
            np.concatenate(
                [s.edge_attr for s in samples if s.num_edges],
                axis=0, out=edge_attr[:tot_edges])

    node_gid = np.full((N,), G - 1, np.int32)
    node_gid[:tot_nodes] = np.repeat(
        np.arange(n_samp, dtype=np.int32), node_counts)
    node_mask = np.zeros((N,), np.float32)
    node_mask[:tot_nodes] = 1.0
    edge_mask = np.zeros((E,), np.float32)
    edge_mask[:tot_edges] = 1.0
    graph_mask = np.zeros((G,), np.float32)
    graph_mask[:n_samp] = 1.0

    has_cell = samples[0].cell is not None
    cell = None
    if has_cell:
        cell = np.zeros((G, 3, 3), np.float32)
        np.stack([s.cell for s in samples], axis=0, out=cell[:n_samp])

    # Per-head labels with a static layout.
    if graph_feature_slices is None and node_feature_slices is None:
        graph_feature_slices, node_feature_slices = default_label_slices(head_specs)
    elif graph_feature_slices is None or node_feature_slices is None:
        raise ValueError(
            "graph_feature_slices and node_feature_slices must be given together"
        )
    labels: List[np.ndarray] = []
    # One flat [n_samp, gy_dim] view of the packed graph labels, sliced per
    # head — avoids a per-sample loop per head.  Only pack a label type some
    # head consumes, and only when every sample carries it with a uniform
    # width; otherwise fall back to the per-sample loop (which tolerates
    # ragged/missing label arrays as long as each head's slice is valid).
    gy = ny = None
    if any(h.type == "graph" for h in head_specs):
        if all(s.graph_y is not None for s in samples):
            gys = [np.asarray(s.graph_y).reshape(-1) for s in samples]
            if all(a.shape == gys[0].shape for a in gys):
                gy = np.stack(gys)
    if any(h.type == "node" for h in head_specs):
        if all(s.node_y is not None for s in samples):
            nys = [s.node_y for s in samples]
            if all(a.ndim == 2 and a.shape[1] == nys[0].shape[1] for a in nys):
                ny = np.concatenate(nys, axis=0)
    for i, h in enumerate(head_specs):
        if h.type == "graph":
            lab = np.zeros((G, h.dim), np.float32)
            lo, hi = graph_feature_slices[i]
            if gy is not None:
                lab[:n_samp] = gy[:, lo:hi]
            else:
                for gid, s in enumerate(samples):
                    if s.graph_y is not None:
                        lab[gid] = np.asarray(s.graph_y).reshape(-1)[lo:hi]
        else:
            lab = np.zeros((N, h.dim), np.float32)
            lo, hi = node_feature_slices[i]
            if ny is not None:
                lab[:tot_nodes] = ny[:, lo:hi]
            else:
                node_off = 0
                for s in samples:
                    n = s.num_nodes
                    if s.node_y is not None:
                        lab[node_off : node_off + n] = s.node_y[:, lo:hi]
                    node_off += n
        labels.append(lab)

    extras: Dict[str, np.ndarray] = {}
    # HYDRAGNN_AGGR_BACKEND=fused: attach the sender-sorted edge permutation
    # — the fused kernels' gate (its presence is collate's word that their
    # invariants hold) and the sender order the backward passes of
    # ops/fused_block.py, gat_mp.py and poly_mp.py run on (ops/fused_mp.py
    # reads only the receiver order) — only when the block-locality
    # invariant holds (every graph fits one node block).  All other
    # invariants (nondecreasing receivers, contiguous graphs, intra-graph
    # edges) hold by construction of this function; the models fall back to
    # the XLA path whenever the permutation is absent.
    from hydragnn_tpu.ops.aggregate import aggr_backend

    if aggr_backend() == "fused":
        from hydragnn_tpu.ops.fused_mp import _NODE_BLOCK

        max_nodes = int(max((s.num_nodes for s in samples), default=0))
        # receivers must ACTUALLY be nondecreasing — true for edges built by
        # graph/neighborlist, but stored edge lists (gpack/pickle written by
        # external pipelines) carry arbitrary order and would make the
        # kernel's steered ranges silently wrong
        recv_sorted = bool(np.all(np.diff(receivers[:tot_edges]) >= 0))
        if max_nodes <= _NODE_BLOCK and recv_sorted:
            extras["edge_perm_sender"] = np.argsort(
                senders, kind="stable").astype(np.int32)
    if samples[0].extras:
        for k in samples[0].extras:
            v0 = np.asarray(samples[0].extras[k])
            if v0.shape and v0.shape[0] == samples[0].num_nodes:
                # per-node extra: concatenate + pad like node features
                arr = np.zeros((N,) + v0.shape[1:], np.float32)
                np.concatenate(
                    [np.asarray(s.extras[k], np.float32)
                     for s in samples], axis=0, out=arr[:tot_nodes])
            else:
                # per-graph extra (scalar or fixed-shape array per graph)
                arr = np.zeros((G,) + v0.shape, np.float32)
                arr[:n_samp] = np.stack(
                    [np.asarray(s.extras[k], np.float32) for s in samples])
            extras[k] = arr

    return GraphBatch(
        x=x,
        pos=pos,
        senders=senders,
        receivers=receivers,
        edge_attr=edge_attr,
        node_gid=node_gid,
        node_mask=node_mask,
        edge_mask=edge_mask,
        graph_mask=graph_mask,
        labels=tuple(labels),
        cell=cell,
        extras=extras,
    )


def default_label_slices(
    head_specs: Sequence[HeadSpec],
) -> Tuple[List[Tuple[int, int]], List[Tuple[int, int]]]:
    """Consecutive column slices for heads into packed graph_y / node_y."""
    gslices: List[Tuple[int, int]] = []
    nslices: List[Tuple[int, int]] = []
    goff = noff = 0
    for h in head_specs:
        if h.type == "graph":
            gslices.append((goff, goff + h.dim))
            nslices.append((0, 0))
            goff += h.dim
        else:
            nslices.append((noff, noff + h.dim))
            gslices.append((0, 0))
            noff += h.dim
    return gslices, nslices
