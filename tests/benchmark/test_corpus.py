"""qm9_shaped: seeded, reproducible, and its cache round-trips."""

import os

import numpy as np

from benchload import load, run_module

PARAMS = {"atoms_lo": 5, "atoms_hi": 9, "layout_seed": 0}
CONFIG = {"NeuralNetwork": {"Architecture": {"radius": 3.0,
                                             "max_neighbours": 4}}}


def test_same_seed_same_corpus_other_seed_other_numbers():
    gen = load("corpora", "qm9_shaped")
    a, b, c = (gen.generate(50, s, PARAMS) for s in (3, 3, 4))
    for k in a:
        assert np.array_equal(a[k], b[k]), k
    # the layout (sizes) is a parameter of the corpus; the numbers change
    assert np.array_equal(a["n_atoms"], c["n_atoms"])
    assert not np.array_equal(a["pos"], c["pos"])
    assert not np.array_equal(a["energy"], c["energy"])
    other = gen.generate(50, 3, {**PARAMS, "layout_seed": 1})
    assert not np.array_equal(a["n_atoms"], other["n_atoms"])
    assert a["n_atoms"].min() >= 5 and a["n_atoms"].max() <= 9
    assert a["pos"].shape == (a["n_atoms"].sum(), 3)
    assert np.all(np.isfinite(a["energy"]))


def test_edges_are_the_programs_radius_graph():
    from hydragnn_tpu.graph.neighborlist import radius_graph

    gen = load("corpora", "qm9_shaped")
    samples = gen.to_samples(gen.generate(40, 1, PARAMS), CONFIG)
    capped = 0
    for s in samples:
        want = radius_graph(s.pos.astype(np.float64), 3.0, max_neighbours=4)
        assert np.array_equal(s.edge_index, want)
        capped += int(np.bincount(want[1]).max() == 4)
        assert 0.0 <= s.x.min() and s.x.max() <= 1.0
        assert s.graph_y.shape == (1,) and s.node_y.shape == s.x.shape
    assert capped > 0            # the neighbour cap did bind somewhere


def test_cache_round_trip(tmp_path, monkeypatch):
    run = run_module()
    monkeypatch.setattr(run, "HERE", str(tmp_path))
    os.makedirs(tmp_path / "corpora")
    os.symlink(os.path.join(os.path.dirname(run.__file__), "corpora",
                            "qm9_shaped.py"),
               tmp_path / "corpora" / "qm9_shaped.py")
    said = []
    cfg = {"generator": "qm9_shaped", "n": 30, "params": PARAMS}
    first = run.corpus_samples(cfg, 7, CONFIG, said.append)
    files = os.listdir(tmp_path / ".cache" / "corpus")
    assert len(files) == 1 and files[0].endswith(".npz") and not said
    again = run.corpus_samples(cfg, 7, CONFIG, said.append)
    assert len(said) == 1 and "read" in said[0]
    for a, b in zip(first, again):
        assert np.array_equal(a.pos, b.pos)
        assert np.array_equal(a.edge_index, b.edge_index)
        assert np.array_equal(a.graph_y, b.graph_y)
    # bounded: only the newest CORPUS_CACHE_KEEP corpora stay on disk
    for seed in range(8, 8 + run.CORPUS_CACHE_KEEP + 1):
        run.corpus_samples(cfg, seed, CONFIG, said.append)
    assert len(os.listdir(tmp_path / ".cache" / "corpus")) \
        == run.CORPUS_CACHE_KEEP
