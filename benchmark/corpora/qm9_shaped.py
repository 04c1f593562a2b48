"""qm9_shaped — seeded QM9-shaped molecules, made in bulk.

The geometry and Morse pair energy of chip_smoke.py's generator
(``synthesize_molecules``: H/C/N/O/F drawn 0.5/0.3/0.08/0.1/0.02, atoms
uniform in a cube of side 1.2 * n^(1/3) A, energy per atom = half the sum
over ordered pairs closer than 2 A of 0.1 (z_i + z_j) ((1 - exp(-(d - 1)))^2
- 1)), vectorised: one numpy pass per chunk of molecules instead of one
Python iteration per molecule, and no neighbour cap on the energy pairs.

Two streams of randomness, on purpose.  The SIZE of every molecule comes
from ``layout_seed`` (a parameter of the corpus, fixed in the configuration
file); everything else — species, positions, energies — comes from the
run's ``--seed``.  The loader pads each dispatch group of batches to the
smallest bucket that fits its largest batch, so which groups land in the
worst-case bucket is decided by the sizes and the shuffle alone; with sizes
redrawn per seed that share swings the work of a run by +-7 % and no bound
under 10 % could be held (PERF.md, Cells).  A fixed layout makes the amount
of work the same for every seed while the numbers the chip computes on
still change.

``to_samples`` is the benchmark's stand-in for XYZ parse -> min-max
normalisation -> ``transform_raw_samples``: the same GraphSamples the raw
path builds from the same molecules (tests/benchmark/
test_driver_matches_run_training.py holds it to that), with the radius graph
built for a whole chunk at once.
"""

from __future__ import annotations

import numpy as np

SPECIES = np.asarray([1, 6, 7, 8, 9], np.int8)
SPECIES_P = np.asarray([0.5, 0.3, 0.08, 0.1, 0.02])
_CHUNK = 8192


def _pair_distances(pos: np.ndarray) -> np.ndarray:
    """[m, A, A] distances of a padded chunk [m, A, 3], in float64 from the
    Gram matrix (one batched matmul, not an [m, A, A, 3] difference)."""
    p = pos.astype(np.float64)
    sq = (p * p).sum(-1)
    d2 = sq[:, :, None] + sq[:, None, :] - 2.0 * (p @ p.transpose(0, 2, 1))
    return np.sqrt(np.maximum(d2, 0.0))


def generate(n: int, seed: int, params: dict) -> dict:
    """``n`` molecules as flat arrays: ``n_atoms`` [n], ``z`` [sum],
    ``pos`` [sum, 3] float32, ``energy`` [n] float64 (per atom)."""
    lo, hi = int(params["atoms_lo"]), int(params["atoms_hi"])
    sizes = np.random.default_rng(
        [int(params.get("layout_seed", 0)), 0x51]).integers(
            lo, hi + 1, size=n).astype(np.int32)
    rng = np.random.default_rng([int(seed), 0xC0])
    z_out, pos_out, e_out = [], [], []
    for c0 in range(0, n, _CHUNK):
        sz = sizes[c0:c0 + _CHUNK]
        m = len(sz)
        z = SPECIES[rng.choice(len(SPECIES), size=(m, hi), p=SPECIES_P)]
        pos = (rng.random((m, hi, 3))
               * (1.2 * np.cbrt(sz.astype(np.float64)))[:, None, None]
               ).astype(np.float32)
        real = np.arange(hi)[None, :] < sz[:, None]
        d = _pair_distances(pos)
        pair = (real[:, :, None] & real[:, None, :] & (d < 2.0)
                & ~np.eye(hi, dtype=bool)[None])
        w = 0.1 * (z[:, :, None].astype(np.float64) + z[:, None, :])
        morse = np.zeros_like(d)
        morse[pair] = w[pair] * ((1.0 - np.exp(-(d[pair] - 1.0))) ** 2 - 1.0)
        e_out.append(0.5 * morse.sum((1, 2)) / sz)
        z_out.append(z[real])
        pos_out.append(pos[real])
    return {"n_atoms": sizes, "z": np.concatenate(z_out),
            "pos": np.concatenate(pos_out),
            "energy": np.concatenate(e_out)}


def _minmax(a: np.ndarray) -> np.ndarray:
    """data/raw.py:normalize_dataset on one feature (0-safe divide)."""
    a = a.astype(np.float64)
    lo, hi = a.min(), a.max()
    return (a - lo) / (hi - lo) if hi > lo else np.zeros_like(a)


def radius_edges(pos: np.ndarray, n_atoms: np.ndarray, radius: float,
                 max_neighbours: int):
    """graph/neighborlist.py:radius_graph for every molecule of a padded
    chunk ``pos`` [m, A, 3]: per target up to ``max_neighbours`` sources
    within ``radius``, targets in order — nearest first where the cap can
    bind; where it cannot (fewer atoms than the cap) every neighbour is
    kept and the sort is skipped, sources then in index order (an edge
    order the model's sums do not depend on beyond rounding).  Returns
    (src, dst) as molecule-local int32 indices and the edge count per
    molecule."""
    m, a, _ = pos.shape
    d = _pair_distances(pos)                       # [m, target, source]
    real = np.arange(a)[None, :] < n_atoms[:, None]
    ok = (real[:, :, None] & real[:, None, :] & (d <= radius)
          & ~np.eye(a, dtype=bool)[None])
    idx = np.arange(a, dtype=np.int32)
    if int(max_neighbours) >= a - 1:
        keep = ok
        src = np.broadcast_to(idx[None, None, :], ok.shape)
    else:
        d = np.where(ok, d, np.inf)
        src = np.argsort(d, axis=2, kind="stable")[
            :, :, :int(max_neighbours)].astype(np.int32)
        keep = np.isfinite(np.take_along_axis(d, src, axis=2))
    dst = np.broadcast_to(idx[None, :, None], keep.shape)
    return src[keep], dst[keep], keep.sum((1, 2)).astype(np.int64)


def to_samples(corpus: dict, config: dict) -> list:
    """GraphSamples as ``dataset_loading_and_splitting`` would hand them to
    the loaders: x = min-max-normalised atomic number (also ``node_y``),
    graph_y = min-max-normalised energy per atom, float32 positions, the
    radius graph of the configuration's cutoff and neighbour cap."""
    from hydragnn_tpu.graph.batch import GraphSample

    arch = config["NeuralNetwork"]["Architecture"]
    radius = float(arch.get("radius") or 5.0)
    max_nb = int(arch.get("max_neighbours") or 100)
    sizes = corpus["n_atoms"].astype(np.int64)
    n, a = len(sizes), int(sizes.max())
    off = np.concatenate([[0], np.cumsum(sizes)])
    x = _minmax(corpus["z"]).astype(np.float32).reshape(-1, 1)
    y = _minmax(corpus["energy"]).astype(np.float32).reshape(-1, 1)
    pos = corpus["pos"]
    samples = []
    for c0 in range(0, n, _CHUNK):
        sz = sizes[c0:c0 + _CHUNK]
        m = len(sz)
        padded = np.zeros((m, a, 3), np.float32)
        real = np.arange(a)[None, :] < sz[:, None]
        padded[real] = pos[off[c0]:off[c0 + m]]
        src, dst, n_edges = radius_edges(padded, sz, radius, max_nb)
        edges = np.stack([src, dst])
        eoff = np.concatenate([[0], np.cumsum(n_edges)]).tolist()
        aoff = off[c0:c0 + m + 1].tolist()
        for i in range(m):
            xi = x[aoff[i]:aoff[i + 1]]
            samples.append(GraphSample(
                x=xi, pos=pos[aoff[i]:aoff[i + 1]],
                edge_index=edges[:, eoff[i]:eoff[i + 1]],
                graph_y=y[c0 + i], node_y=xi))
    return samples
