"""Raw atomistic dataset loaders: text files -> normalized numpy records.

Host-side re-design of the reference raw-data path
(reference hydragnn/preprocess/raw_dataset_loader.py:90-279,
lsms_raw_dataset_loader.py:34-106, cfg_raw_dataset_loader.py): parse per-file
structures into :class:`RawSample` records (full node-feature table, positions,
graph features), scale ``*_scaled_num_nodes`` features, then min-max normalize
every feature over the whole dataset (optionally reduced across hosts).

Everything here is plain numpy — graph construction and feature selection
happen later in :mod:`hydragnn_tpu.data.transform`; nothing touches the TPU.
"""

from __future__ import annotations

import dataclasses
import os
import pickle
import random
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from hydragnn_tpu.resilience.ckpt_io import atomic_write_pickles


@dataclasses.dataclass
class RawSample:
    """One parsed structure: full feature tables, before config selection."""

    x: np.ndarray                      # [n, F_node] full node-feature table
    pos: np.ndarray                    # [n, 3]
    y: np.ndarray                      # [F_graph_total] packed graph features
    cell: Optional[np.ndarray] = None  # [3, 3] or None
    supercell_size: Optional[np.ndarray] = None

    @property
    def num_nodes(self) -> int:
        return self.x.shape[0]


def nsplit(seq: Sequence, n: int) -> List[List]:
    """Split ``seq`` into ``n`` contiguous chunks, sizes differing by <=1
    (parity with reference nsplit, hydragnn/utils/distributed.py:257-259)."""
    k, m = divmod(len(seq), n)
    return [
        list(seq[i * k + min(i, m) : (i + 1) * k + min(i + 1, m)]) for i in range(n)
    ]


def tensor_divide(num: np.ndarray, den) -> np.ndarray:
    """0-safe division (parity: reference utils/model.py tensor_divide)."""
    den = np.asarray(den, dtype=np.float64)
    out = np.zeros_like(np.asarray(num, dtype=np.float64))
    np.divide(num, den, out=out, where=den != 0)
    return out


class AbstractRawDataset:
    """Base raw loader: file walking, rank sharding, scaling, normalization.

    Config keys consumed (Dataset section of the reference JSON schema):
    ``path`` (dict of split -> dir), ``node_features``/``graph_features``
    (name/dim/column_index), ``name``, ``format``.
    """

    def __init__(self, config: Dict[str, Any], dist: bool = False,
                 rank: int = 0, world_size: int = 1):
        ds = config["Dataset"]
        self.name = ds["name"]
        self.path_dictionary = ds["path"]
        self.node_feature_name = list(ds["node_features"]["name"])
        self.node_feature_dim = [int(d) for d in ds["node_features"]["dim"]]
        self.node_feature_col = [int(c) for c in ds["node_features"]["column_index"]]
        gf = ds.get("graph_features", {})
        self.graph_feature_name = list(gf.get("name", []))
        self.graph_feature_dim = [int(d) for d in gf.get("dim", [])]
        self.graph_feature_col = [int(c) for c in gf.get("column_index", [])]
        self.dist = dist
        self.rank = rank
        self.world_size = world_size
        self.minmax_node_feature: Optional[np.ndarray] = None
        self.minmax_graph_feature: Optional[np.ndarray] = None
        # one list of RawSample per split, in path_dictionary order
        self.dataset_list: List[List[RawSample]] = []
        self.serial_data_name_list: List[str] = []

    # -- per-format hook ---------------------------------------------------
    def transform_file(self, filepath: str) -> Optional[RawSample]:
        raise NotImplementedError

    # -- pipeline ----------------------------------------------------------
    def load_raw_data(self) -> None:
        """Walk each split dir, parse, scale and normalize (parity with
        reference AbstractRawDataLoader.load_raw_data,
        raw_dataset_loader.py:90-160)."""
        for dataset_type, raw_path in self.path_dictionary.items():
            if not os.path.isabs(raw_path):
                raw_path = os.path.join(os.getcwd(), raw_path)
            if not os.path.exists(raw_path):
                raise ValueError(f"Folder not found: {raw_path}")
            filelist = sorted(os.listdir(raw_path))
            assert len(filelist) > 0, f"No data files provided in {raw_path}!"
            if self.dist:
                # deterministic shuffle then contiguous shard per rank
                # (reference raw_dataset_loader.py:111-122, seed 43)
                random.Random(43).shuffle(filelist)
                filelist = nsplit(filelist, self.world_size)[self.rank]

            dataset: List[RawSample] = []
            for fname in filelist:
                if fname == ".DS_Store":
                    continue
                full = os.path.join(raw_path, fname)
                if os.path.isfile(full):
                    rec = self.transform_file(full)
                    if rec is not None:
                        dataset.append(rec)
                elif os.path.isdir(full):
                    for sub in sorted(os.listdir(full)):
                        subfull = os.path.join(full, sub)
                        if os.path.isfile(subfull):
                            rec = self.transform_file(subfull)
                            if rec is not None:
                                dataset.append(rec)
            dataset = self.scale_features_by_num_nodes(dataset)
            suffix = "" if dataset_type == "total" else f"_{dataset_type}"
            self.serial_data_name_list.append(f"{self.name}{suffix}.pkl")
            self.dataset_list.append(dataset)

        self.normalize_dataset()

    def scale_features_by_num_nodes(
        self, dataset: List[RawSample]
    ) -> List[RawSample]:
        """Divide features named ``*_scaled_num_nodes`` by the node count
        (parity: raw_dataset_loader.py:166-189)."""
        g_idx = [i for i, n in enumerate(self.graph_feature_name)
                 if "_scaled_num_nodes" in n]
        n_idx = [i for i, n in enumerate(self.node_feature_name)
                 if "_scaled_num_nodes" in n]
        g_cols = _feature_columns(self.graph_feature_dim, g_idx)
        n_cols = _feature_columns(self.node_feature_dim, n_idx)
        for rec in dataset:
            if g_cols and rec.y is not None:
                rec.y[g_cols] = rec.y[g_cols] / rec.num_nodes
            if n_cols:
                rec.x[:, n_cols] = rec.x[:, n_cols] / rec.num_nodes
        return dataset

    def normalize_dataset(self) -> None:
        """Min-max normalize per feature (each feature may span several
        columns); records extrema in ``minmax_*_feature`` (parity:
        raw_dataset_loader.py:196-279)."""
        n_nf = len(self.node_feature_dim)
        n_gf = len(self.graph_feature_dim)
        self.minmax_graph_feature = np.full((2, n_gf), np.inf)
        self.minmax_node_feature = np.full((2, n_nf), np.inf)
        self.minmax_graph_feature[1, :] *= -1
        self.minmax_node_feature[1, :] *= -1

        for dataset in self.dataset_list:
            for rec in dataset:
                go = 0
                for i, d in enumerate(self.graph_feature_dim):
                    seg = rec.y[go : go + d]
                    self.minmax_graph_feature[0, i] = min(
                        seg.min(), self.minmax_graph_feature[0, i])
                    self.minmax_graph_feature[1, i] = max(
                        seg.max(), self.minmax_graph_feature[1, i])
                    go += d
                no = 0
                for i, d in enumerate(self.node_feature_dim):
                    seg = rec.x[:, no : no + d]
                    self.minmax_node_feature[0, i] = min(
                        seg.min(), self.minmax_node_feature[0, i])
                    self.minmax_node_feature[1, i] = max(
                        seg.max(), self.minmax_node_feature[1, i])
                    no += d

        if self.dist and self.world_size > 1:
            from hydragnn_tpu.parallel.comm import host_allreduce
            self.minmax_graph_feature[0] = host_allreduce(
                self.minmax_graph_feature[0], op="min")
            self.minmax_graph_feature[1] = host_allreduce(
                self.minmax_graph_feature[1], op="max")
            self.minmax_node_feature[0] = host_allreduce(
                self.minmax_node_feature[0], op="min")
            self.minmax_node_feature[1] = host_allreduce(
                self.minmax_node_feature[1], op="max")

        for dataset in self.dataset_list:
            for rec in dataset:
                go = 0
                for i, d in enumerate(self.graph_feature_dim):
                    lo, hi = self.minmax_graph_feature[:, i]
                    rec.y[go : go + d] = tensor_divide(
                        rec.y[go : go + d] - lo, hi - lo)
                    go += d
                no = 0
                for i, d in enumerate(self.node_feature_dim):
                    lo, hi = self.minmax_node_feature[:, i]
                    rec.x[:, no : no + d] = tensor_divide(
                        rec.x[:, no : no + d] - lo, hi - lo)
                    no += d

    def save_serialized(self, serialized_dir: str) -> None:
        """Pickle each split with minmax headers (parity with the reference's
        serialized pickle layout, raw_dataset_loader.py:146-160)."""
        os.makedirs(serialized_dir, exist_ok=True)
        for name, dataset in zip(self.serial_data_name_list, self.dataset_list):
            atomic_write_pickles(
                os.path.join(serialized_dir, name),
                self.minmax_node_feature, self.minmax_graph_feature,
                dataset)


def _feature_columns(dims: List[int], feat_indices: List[int]) -> List[int]:
    cols: List[int] = []
    off = 0
    for i, d in enumerate(dims):
        if i in feat_indices:
            cols.extend(range(off, off + d))
        off += d
    return cols


class LSMSDataset(AbstractRawDataset):
    """LSMS text format (parity: lsms_raw_dataset_loader.py:39-106).

    Line 0: graph features (whitespace separated).  Lines 1+: per-node rows
    ``feature index x y z out...`` — node features picked by column_index,
    then the LSMS charge-density fixup: selected column 1 -= selected column 0.
    """

    def transform_file(self, filepath: str) -> Optional[RawSample]:
        with open(filepath, "r", encoding="utf-8") as f:
            lines = f.readlines()
        graph_feat = lines[0].split()
        g = []
        for item in range(len(self.graph_feature_dim)):
            for icomp in range(self.graph_feature_dim[item]):
                g.append(float(graph_feat[self.graph_feature_col[item] + icomp]))
        pos_rows, feat_rows = [], []
        for line in lines[1:]:
            toks = line.split()
            if not toks:
                continue
            pos_rows.append([float(toks[2]), float(toks[3]), float(toks[4])])
            row = []
            for item in range(len(self.node_feature_dim)):
                for icomp in range(self.node_feature_dim[item]):
                    row.append(float(toks[self.node_feature_col[item] + icomp]))
            feat_rows.append(row)
        x = np.asarray(feat_rows, dtype=np.float64)
        if x.shape[1] >= 2:
            # charge density = raw charge - num protons
            x[:, 1] = x[:, 1] - x[:, 0]
        return RawSample(
            x=x,
            pos=np.asarray(pos_rows, dtype=np.float64),
            y=np.asarray(g, dtype=np.float64),
        )


class XYZDataset(AbstractRawDataset):
    """Extended-XYZ files: line 0 = atom count, line 1 = comment holding the
    graph features (whitespace separated, picked by column_index), then
    ``symbol/number x y z f...`` rows.  Native parser (the reference reads
    CFG/XYZ through ASE, cfg_raw_dataset_loader.py; ASE is gated here)."""

    def transform_file(self, filepath: str) -> Optional[RawSample]:
        with open(filepath, "r", encoding="utf-8") as f:
            lines = f.readlines()
        n = int(lines[0].split()[0])
        comment = lines[1].split()
        g = []
        for item in range(len(self.graph_feature_dim)):
            for icomp in range(self.graph_feature_dim[item]):
                g.append(float(comment[self.graph_feature_col[item] + icomp]))
        pos_rows, feat_rows = [], []
        for line in lines[2 : 2 + n]:
            toks = line.split()
            first = toks[0]
            z = float(first) if first[0].isdigit() else float(
                ATOMIC_NUMBERS.get(first, 0))
            pos_rows.append([float(toks[1]), float(toks[2]), float(toks[3])])
            row = [z]
            for item in range(len(self.node_feature_dim)):
                for icomp in range(self.node_feature_dim[item]):
                    col = self.node_feature_col[item] + icomp
                    if col > 0:
                        row.append(float(toks[3 + col]))
            feat_rows.append(row[: sum(self.node_feature_dim)])
        return RawSample(
            x=np.asarray(feat_rows, dtype=np.float64),
            pos=np.asarray(pos_rows, dtype=np.float64),
            y=np.asarray(g, dtype=np.float64),
        )


class CFGDataset(AbstractRawDataset):
    """AtomEye extended-CFG parser (parity with the reference's ASE-based
    cfg_raw_dataset_loader.py, without the ASE dependency).

    Supports the standard keys ``Number of particles``, ``H0(i,j)`` cell
    entries, ``.NO_VELOCITY.``, ``entry_count`` and per-atom blocks of
    ``mass / symbol / s1 s2 s3 aux...`` with fractional coordinates."""

    def transform_file(self, filepath: str) -> Optional[RawSample]:
        n_atoms = None
        H = np.zeros((3, 3), dtype=np.float64)
        rows: List[List[float]] = []
        with open(filepath, "r", encoding="utf-8") as f:
            lines = [ln.strip() for ln in f if ln.strip()]
        i = 0
        mass_pending = None
        symbol_pending = None
        while i < len(lines):
            ln = lines[i]
            if ln.startswith("Number of particles"):
                n_atoms = int(ln.split("=")[1])
            elif ln.startswith("H0("):
                idx = ln[3:ln.index(")")].split(",")
                r, c = int(idx[0]) - 1, int(idx[1]) - 1
                H[r, c] = float(ln.split("=")[1].split()[0])
            elif ln.startswith((".NO_VELOCITY.", "entry_count", "auxiliary", "A =")):
                pass
            else:
                toks = ln.split()
                if len(toks) == 1 and _is_float(toks[0]):
                    mass_pending = float(toks[0])
                elif len(toks) == 1:
                    symbol_pending = toks[0]
                elif len(toks) >= 3 and all(_is_float(t) for t in toks):
                    z = float(ATOMIC_NUMBERS.get(symbol_pending, 0))
                    frac = np.asarray([float(toks[0]), float(toks[1]),
                                       float(toks[2])], dtype=np.float64)
                    cart = frac @ H
                    aux = [float(t) for t in toks[3:]]
                    rows.append([z, *cart, *aux])
            i += 1
        if not rows:
            return None
        arr = np.asarray(rows, dtype=np.float64)
        pos = arr[:, 1:4]
        feats = np.concatenate([arr[:, :1], arr[:, 4:]], axis=1)
        # select configured columns from [z, aux...]
        sel = []
        for item in range(len(self.node_feature_dim)):
            for icomp in range(self.node_feature_dim[item]):
                sel.append(self.node_feature_col[item] + icomp)
        sel = [c for c in sel if c < feats.shape[1]]
        x = feats[:, sel] if sel else feats
        y = np.zeros((sum(self.graph_feature_dim),), dtype=np.float64)
        return RawSample(x=x, pos=pos, y=y, cell=H)


def _is_float(s: str) -> bool:
    try:
        float(s)
        return True
    except ValueError:
        return False


# Minimal symbol -> atomic number table for native XYZ/CFG parsing.
ATOMIC_NUMBERS: Dict[str, int] = {
    s: i + 1
    for i, s in enumerate(
        "H He Li Be B C N O F Ne Na Mg Al Si P S Cl Ar K Ca Sc Ti V Cr Mn Fe "
        "Co Ni Cu Zn Ga Ge As Se Br Kr Rb Sr Y Zr Nb Mo Tc Ru Rh Pd Ag Cd In "
        "Sn Sb Te I Xe Cs Ba La Ce Pr Nd Pm Sm Eu Gd Tb Dy Ho Er Tm Yb Lu Hf "
        "Ta W Re Os Ir Pt Au Hg Tl Pb Bi Po At Rn".split()
    )
}

class TokenDataset(AbstractRawDataset):
    """Token documents: one file a document, its token ids as
    whitespace-separated integers.  Node features are ``[id, next id]``
    (-1 where a token has no successor in its document) and, where the
    config names a third (a fourth ...) node feature, the id two (three
    ...) tokens on; positions are zeros, and nothing is scaled: an id is
    a category, and min-max normalisation would make it a fraction.  The
    language-model stacks read column 0 and are trained against column 1
    (models/laguna.py), the multi-token-prediction head against column 2
    (models/glm_moe_lite.py)."""

    def transform_file(self, filepath: str) -> Optional[RawSample]:
        with open(filepath, "r", encoding="utf-8") as f:
            ids = np.asarray(f.read().split(), dtype=np.float64)
        if not len(ids):
            return None
        ahead = max(len(self.node_feature_dim), 2)
        cols = [np.concatenate([ids[j:], np.full(min(j, len(ids)), -1.0)])
                for j in range(ahead)]
        return RawSample(x=np.stack(cols, axis=1),
                         pos=np.zeros((len(ids), 3)),
                         y=np.zeros((sum(self.graph_feature_dim),)))

    def normalize_dataset(self) -> None:
        # the headers of the serialized layout, as an identity map
        n_nf, n_gf = len(self.node_feature_dim), len(self.graph_feature_dim)
        self.minmax_node_feature = np.stack([np.zeros(n_nf), np.ones(n_nf)])
        self.minmax_graph_feature = np.stack([np.zeros(n_gf), np.ones(n_gf)])


RAW_FORMATS = {
    "tokens": TokenDataset,
    "LSMS": LSMSDataset,
    "unit_test": LSMSDataset,
    "XYZ": XYZDataset,
    "CFG": CFGDataset,
}
