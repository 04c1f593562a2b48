"""The yardstick cannot drift from the entry point unnoticed: on one tiny
seeded corpus, written as XYZ files for ``hydragnn_tpu.run_training`` and
handed as arrays to the benchmark's ``train_epochs`` driver, both choose
the same pipeline and reach the same first-epoch train loss."""

import copy
import json
import os

import pytest

from benchload import BENCH, load

# the XYZ text rounds positions to 1e-6 A and the energy to 1e-10; CPU
# float32, same programs, same batches
REL_TOL = 1e-4
SEED = 3
N_EPOCH = 3

ARCHS = {
    "SchNet": {"model_type": "SchNet", "radius": 3.0, "max_neighbours": 6,
               "num_gaussians": 8, "num_filters": 8, "hidden_dim": 8,
               "num_conv_layers": 2},
    # the post_collate path: the triplet table sized from the corpus
    "DimeNet": {"model_type": "DimeNet", "radius": 3.0, "max_neighbours": 6,
                "hidden_dim": 8, "num_conv_layers": 2, "int_emb_size": 4,
                "basis_emb_size": 2, "out_emb_size": 8, "num_radial": 3,
                "num_spherical": 2, "envelope_exponent": 5,
                "num_before_skip": 1, "num_after_skip": 1},
}


def _config(arch_name):
    with open(os.path.join(BENCH, "configs", "schnet_qm9.json")) as f:
        config = json.load(f)
    for key in ("dry_cpu", "Provenance", "expect"):
        config.pop(key)
    config["corpus"] = {"generator": "qm9_shaped", "n": 120,
                        "params": {"atoms_lo": 5, "atoms_hi": 9,
                                   "layout_seed": 0}}
    arch = config["NeuralNetwork"]["Architecture"]
    arch.update(ARCHS[arch_name], aggregation_backend="scatter")
    arch["output_heads"]["graph"].update(dim_sharedlayers=8,
                                         dim_headlayers=[8, 8])
    config["NeuralNetwork"]["Training"].update(batch_size=16,
                                               num_epoch=N_EPOCH)
    return config


def _write_xyz(corpus, dirpath):
    os.makedirs(dirpath)
    off = 0
    for i, n in enumerate(corpus["n_atoms"]):
        rows = "\n".join(
            f"{int(z)} {p[0]:.6f} {p[1]:.6f} {p[2]:.6f}"
            for z, p in zip(corpus["z"][off:off + n],
                            corpus["pos"][off:off + n]))
        with open(os.path.join(dirpath, f"mol_{i:06d}.xyz"), "w") as f:
            f.write(f"{n}\n{corpus['energy'][i]:.10f}\n{rows}\n")
        off += n


@pytest.mark.parametrize("arch_name", sorted(ARCHS))
def test_driver_and_run_training_agree(arch_name, tmp_path, monkeypatch):
    import hydragnn_tpu

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("SERIALIZED_DATA_PATH", str(tmp_path))
    gen = load("corpora", "qm9_shaped")
    driver = load("drivers", "train_epochs")
    config = _config(arch_name)
    corpus = gen.generate(config["corpus"]["n"], SEED,
                          config["corpus"]["params"])

    # the entry point, from raw files
    _write_xyz(corpus, str(tmp_path / "raw"))
    entry = copy.deepcopy(config)
    entry["Dataset"].update(format="XYZ", path={"total": "./raw"})
    _state, want, _cfg = hydragnn_tpu.run_training(
        entry, logs_dir=str(tmp_path / "logs_entry"), seed=SEED)

    # the benchmark's driver, from the same molecules as arrays; the job
    # ends by itself after N_EPOCH epochs, long before the window closes
    said = []
    result = driver.run({
        "t_start": 0.0, "config": config, "seed": SEED, "seconds": 3600.0,
        "trace": False, "dry": True, "say": said.append,
        "traffic": {},
        "workdir": str(tmp_path / "bench_run"),
        "corpus": lambda c, seed, cfg: gen.to_samples(corpus, cfg),
    })
    facts = result["facts"]
    assert facts["pipeline"] == want["pipeline"]
    got, ref = facts["history"]["train"], [float(v) for v in want["train"]]
    assert len(got) == len(ref) == N_EPOCH
    assert abs(got[0] - ref[0]) <= REL_TOL * abs(ref[0]), (got, ref)
    # epoch 0 is set-up and the last epoch has no next start to close it:
    # epoch 1 is the one whole epoch counted, with every train graph in it
    assert [e["epoch"] for e in facts["epochs"]] == [1]
    assert facts["epochs"][0]["graphs"] == 96
