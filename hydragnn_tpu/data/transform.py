"""Config-driven transformation: normalized RawSamples -> GraphSamples.

The analog of the reference's SerializedDataLoader
(reference hydragnn/preprocess/serialized_dataset_loader.py:103-241): apply
optional rotation normalization, build the radius graph (PBC or open), compute
edge lengths and normalize them by the *global* max over the dataset, then lay
out per-sample label tables (``graph_y`` = all graph features, ``node_y`` =
all node features) and select the input features into ``x``.  The per-head
slices into those tables come from ``config.label_slices_from_config`` — the
static replacement of the reference's runtime ``update_predicted_values`` /
``y_loc`` bookkeeping (hydragnn/preprocess/utils.py:237-279).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from hydragnn_tpu.config.config import SEQUENCE_MODELS
from hydragnn_tpu.data.raw import RawSample
from hydragnn_tpu.graph.batch import GraphSample
from hydragnn_tpu.graph.neighborlist import (
    edge_lengths,
    normalize_rotation,
    radius_graph,
    radius_graph_pbc,
)


def select_feature_columns(
    dims: Sequence[int], selected: Sequence[int]
) -> List[int]:
    """Column indices of the selected feature blocks (parity with reference
    update_atom_features, hydragnn/preprocess/utils.py:282-293)."""
    cols: List[int] = []
    offsets = np.concatenate([[0], np.cumsum(dims)]).astype(int)
    for i in selected:
        cols.extend(range(offsets[i], offsets[i + 1]))
    return cols


EDGE_FREE_MODELS = SEQUENCE_MODELS


def transform_raw_samples(
    records: Sequence[RawSample],
    config: Dict[str, Any],
    world_max_edge_length: Optional[float] = None,
    stats: Optional[Dict[str, Any]] = None,
) -> List[GraphSample]:
    """Build GraphSamples per the config's Architecture + Variables sections.

    ``world_max_edge_length`` lets multi-host callers pass the cross-host
    max (parity with the reference's all_reduce(MAX) edge normalization,
    serialized_dataset_loader.py:148-164); single-host callers leave it None
    and the local max is used.

    ``stats``, when given, receives ``edge_length_norm`` — the
    normalization constant actually applied to length edge features.
    The data pipeline persists it into the config's ``Serving`` section
    so the online server can normalize request edges identically
    (hydragnn_tpu/serve/server.py:sample_from_json).
    """
    nn_sec = config["NeuralNetwork"]
    arch = nn_sec["Architecture"]
    var = nn_sec["Variables_of_interest"]
    ds = config.get("Dataset", {})

    radius = float(arch.get("radius") or 5.0)
    max_neigh = int(arch.get("max_neighbours") or 100)
    pbc = bool(arch.get("periodic_boundary_conditions", False))
    rot = bool(ds.get("rotational_invariance", False))
    edge_feature_names = arch.get("edge_features") or []

    node_dims = [int(d) for d in ds.get("node_features", {}).get("dim", [])]
    input_cols = (
        select_feature_columns(node_dims, var["input_node_features"])
        if node_dims
        else list(var["input_node_features"])
    )

    # a stack whose edge set is implicit (attention over each graph's
    # nodes, models/laguna.py) gets no edge list: a document of 8192
    # tokens would be 33 M of them
    edge_free = arch.get("model_type") in EDGE_FREE_MODELS

    built = []
    max_len = 0.0
    for rec in records:
        pos = np.asarray(rec.pos, dtype=np.float64)
        if rot:
            pos = normalize_rotation(pos).astype(np.float64)
        if edge_free:
            edge_index = np.zeros((2, 0), np.int32)
            lengths = np.zeros((0, 1))
        elif pbc:
            assert rec.cell is not None, "PBC requires a cell per sample"
            edge_index, lengths = radius_graph_pbc(
                pos, rec.cell, radius, max_neighbours=max_neigh)
            lengths = lengths.reshape(-1, 1)
        else:
            edge_index = radius_graph(pos, radius, max_neighbours=max_neigh)
            lengths = edge_lengths(pos, edge_index)
        if lengths.size:
            max_len = max(max_len, float(lengths.max()))
        built.append((rec, pos, edge_index, lengths))

    norm = world_max_edge_length if world_max_edge_length else max_len
    norm = norm or 1.0
    if stats is not None:
        if edge_feature_names:
            stats["edge_length_norm"] = float(norm)
        # the neighbor cap ACTUALLY used for graph building — finalize
        # later overwrites arch.max_neighbours for PNA (degree-histogram
        # length), so the saved config alone can't reproduce this build
        stats["edge_build_max_neighbours"] = int(max_neigh)

    out: List[GraphSample] = []
    for rec, pos, edge_index, lengths in built:
        x_full = np.asarray(rec.x, dtype=np.float32)
        edge_attr = (lengths / norm).astype(np.float32) if edge_feature_names else None
        out.append(
            GraphSample(
                x=x_full[:, input_cols],
                pos=pos.astype(np.float32),
                edge_index=edge_index,
                edge_attr=edge_attr,
                graph_y=None if rec.y is None else np.asarray(rec.y, np.float32),
                node_y=x_full,
                cell=rec.cell,
            )
        )
    return out


def local_max_edge_length(
    records: Sequence[RawSample], config: Dict[str, Any]
) -> float:
    """Max edge length over local records (input to a cross-host max)."""
    arch = config["NeuralNetwork"]["Architecture"]
    radius = float(arch.get("radius") or 5.0)
    max_neigh = int(arch.get("max_neighbours") or 100)
    m = 0.0
    for rec in records:
        ei = radius_graph(np.asarray(rec.pos, np.float64), radius, max_neigh)
        if ei.shape[1]:
            m = max(m, float(edge_lengths(np.asarray(rec.pos), ei).max()))
    return m


def check_data_samples_equivalence(s1: GraphSample, s2: GraphSample,
                                  tol: float = 1e-6) -> bool:
    """Whether two GraphSamples describe the same graph up to edge ORDER
    (parity: reference check_data_samples_equivalence,
    hydragnn/preprocess/utils.py:83-99 — used to assert that
    rotation-normalized copies keep an equivalent edge set).

    Shape-equality on x/pos/labels plus an order-independent edge-set
    match; when both samples carry ``edge_attr``, matched edges must agree
    within ``tol``.  Vectorized (lexicographic sort of the edge lists)
    instead of the reference's O(E^2) scan.
    """
    if (np.shape(s1.x) != np.shape(s2.x)
            or np.shape(s1.pos) != np.shape(s2.pos)
            or np.shape(s1.graph_y) != np.shape(s2.graph_y)
            or np.shape(s1.node_y) != np.shape(s2.node_y)):
        return False
    e1, e2 = np.asarray(s1.edge_index), np.asarray(s2.edge_index)
    if e1.shape != e2.shape:
        return False
    o1 = np.lexsort((e1[1], e1[0]))
    o2 = np.lexsort((e2[1], e2[0]))
    if not np.array_equal(e1[:, o1], e2[:, o2]):
        return False
    a1, a2 = getattr(s1, "edge_attr", None), getattr(s2, "edge_attr", None)
    if (a1 is None) != (a2 is None):
        return False  # schema mismatch: only one sample carries edge_attr
    if a1 is not None and a2 is not None:
        a1 = np.asarray(a1)
        a2 = np.asarray(a2)
        if a1.shape != a2.shape:
            return False
        # duplicate parallel edges (multigraphs): lexsort on (src, dst)
        # alone pairs duplicates by original position, which can mismatch
        # attrs that agree as a multiset — include the attr columns as
        # secondary sort keys so equal multisets align (round-3 advisor)
        a1f = a1.reshape(a1.shape[0], -1)
        a2f = a2.reshape(a2.shape[0], -1)
        k1 = tuple(a1f[:, c] for c in range(a1f.shape[1] - 1, -1, -1))
        k2 = tuple(a2f[:, c] for c in range(a2f.shape[1] - 1, -1, -1))
        # (the attr keys only permute rows WITHIN equal-(src,dst) groups,
        # so the edge-set equality established above still holds)
        o1 = np.lexsort(k1 + (e1[1], e1[0]))
        o2 = np.lexsort(k2 + (e2[1], e2[0]))
        bad = np.linalg.norm(a1f[o1] - a2f[o2], axis=-1) >= tol
        if bad.any():
            # sorted pairing can misalign multi-column attrs when parallel
            # duplicate edges near-tie (< tol) in a leading column — fall
            # back to an exact per-duplicate-group multiset match for the
            # groups that failed
            return _duplicate_group_match(
                e1[:, o1], a1f[o1], a2f[o2], np.nonzero(bad)[0], tol)
    return True


def _duplicate_group_match(e_sorted, a1s, a2s, bad_rows, tol) -> bool:
    """Exact within-tol bipartite match for the duplicate-(src,dst) groups
    whose sorted attr pairing failed.  Groups are tiny (parallel edges of
    one node pair), so an optimal assignment on the binary violation
    matrix (scipy Hungarian) decides exactly whether a within-tol perfect
    matching exists."""
    from scipy.optimize import linear_sum_assignment

    done = set()
    for r in np.unique(bad_rows):
        key = (e_sorted[0, r], e_sorted[1, r])
        if key in done:
            continue
        done.add(key)
        grp = np.nonzero((e_sorted[0] == key[0]) & (e_sorted[1] == key[1]))[0]
        dists = np.linalg.norm(
            a1s[grp][:, None, :] - a2s[grp][None, :, :], axis=-1)
        viol = (dists >= tol).astype(np.int64)
        ri, ci = linear_sum_assignment(viol)
        if viol[ri, ci].sum():
            return False
    return True
