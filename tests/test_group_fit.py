"""A resident dispatch group is padded to what it holds (PR 36).

The train loader of a run that stages its corpus on the device fits each
dispatch group's PadSpec to the groups of the ONE plan that gets staged
(``GraphDataLoader.fit_to_groups``, told by the trainer's
``_align_bucket_group``); every other loader keeps the quantile ladder, bit
for bit.  All of it is host metadata: these tests run on sizes, or on tiny
CPU jobs.
"""

import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from hydragnn_tpu.data.dataloader import (
    GraphDataLoader,
    bucket_pad_specs,
    bucket_pad_specs_from_sizes,
    fit_group_specs,
)
from hydragnn_tpu.graph.batch import GraphSample, HeadSpec, PadSpec
from hydragnn_tpu.graph.neighborlist import radius_graph

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "benchmark")


def _bench_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"group_fit_{kind}_{name}", os.path.join(_BENCH, kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_run_module():
    spec = importlib.util.spec_from_file_location(
        "group_fit_benchmark_run", os.path.join(_BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_config(name):
    with open(os.path.join(_BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


class _Sized:
    """What the planner reads of a sample: its sizes."""

    __slots__ = ("num_nodes", "num_edges")

    def __init__(self, num_nodes, num_edges):
        self.num_nodes, self.num_edges = int(num_nodes), int(num_edges)


def _cell_sizes(config_name):
    """(nodes, edges) of every sample of a benchmark corpus, from its
    ``layout_seed`` alone, as the corpus plug-ins draw them."""
    corpus = _bench_config(config_name)["corpus"]
    params = corpus["params"]
    if corpus["generator"] == "qm9_shaped":
        # qm9_shaped.generate's first line; radius 10 A in a cube of side
        # <= 3.7 A and 32 neighbours allowed: every ordered pair is an edge
        nodes = np.random.default_rng(
            [int(params["layout_seed"]), 0x51]).integers(
                params["atoms_lo"], params["atoms_hi"] + 1,
                size=corpus["n"]).astype(np.int64)
        return nodes, nodes * (nodes - 1)
    nodes = _bench_module("corpora", "packed_docs").lengths(
        corpus["n"], params).astype(np.int64)
    return nodes, np.zeros_like(nodes)


def _cell_loader(config_name, batch_size, group, fit):
    """The train loader of a cell as ``create_dataloaders`` builds it and
    the trainer aligns it: ladder from all samples, 80 % train split,
    ``loader_seed`` 0."""
    nodes, edges = _cell_sizes(config_name)
    ladder = bucket_pad_specs_from_sizes(nodes, edges, batch_size, 3)
    n_train = int(0.8 * len(nodes))
    samples = [_Sized(n, e) for n, e in zip(nodes[:n_train], edges[:n_train])]
    loader = GraphDataLoader(samples, [], batch_size, shuffle=True, seed=0,
                             pad_specs=ladder, bucket_group=group)
    if fit:
        assert loader.fit_to_groups() is True
    return loader, ladder


def _dispatched(loader, plan):
    """The batches a run dispatches: whole groups only."""
    return plan[:len(plan) // loader.bucket_group * loader.bucket_group]


def _waste(loader, plan, what):
    real = sum(getattr(loader.samples[i], what) for ix, _ in plan for i in ix)
    padded = sum(getattr(spec, what) for _, spec in plan)
    return 100.0 * (1.0 - real / padded)


# cell -> config, micro-batch, batches a dispatch group (K, or devices x K,
# as _auto_pipeline and the traffic files choose them), the padding
# counter the ledger reads (PR 35), the fitted shapes, the waste after
CELLS = {
    "schnet_qm9-resident": dict(
        config="schnet_qm9", batch=512, group=32, counter="num_edges",
        before=29.781, shapes=[[10176, 209088, 3]], after=(6.0, 8.0)),
    "schnet_qm9-dp4": dict(
        config="schnet_qm9", batch=512, group=100, counter="num_edges",
        before=53.239, shapes=[[10176, 209088, 1]], after=(7.0, 8.0)),
    "laguna_s_2_1-packed8k": dict(
        config="laguna_s_2_1", batch=24, group=8, counter="num_nodes",
        before=46.278, shapes=[[15168, 8, 1]], after=(28.0, 31.0)),
    "glm_4_7_flash-packed4k": dict(
        config="glm_4_7_flash", batch=8, group=4, counter="num_nodes",
        before=33.72, shapes=[[17512, 8, 1]], after=(23.0, 25.0)),
}


def test_qm9_sizes_are_the_generators():
    gen = _bench_module("corpora", "qm9_shaped")
    params = _bench_config("schnet_qm9")["corpus"]["params"]
    corpus = gen.generate(64, 3, params)
    sizes = np.random.default_rng(
        [int(params["layout_seed"]), 0x51]).integers(
            params["atoms_lo"], params["atoms_hi"] + 1, size=64)
    assert np.array_equal(corpus["n_atoms"], sizes)
    padded = np.zeros((64, int(sizes.max()), 3), np.float32)
    off = 0
    for i, n in enumerate(sizes):
        padded[i, :n] = corpus["pos"][off:off + n]
        off += n
    _src, _dst, n_edges = gen.radius_edges(padded, sizes, 10.0, 32)
    assert np.array_equal(n_edges, sizes * (sizes - 1))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_plan_recount_of_the_four_cells(cell):
    """(a) the plan of epoch 0 from sizes alone: today's counters to the
    digit, the fitted shapes, the waste after, no more shapes than today."""
    c = CELLS[cell]
    ladder_loader, ladder = _cell_loader(c["config"], c["batch"], c["group"],
                                         fit=False)
    today = _dispatched(ladder_loader, ladder_loader._index_plan())
    assert ladder_loader.group_shapes == []
    assert abs(_waste(ladder_loader, today, c["counter"]) - c["before"]) < 6e-3
    fitted_loader, _ = _cell_loader(c["config"], c["batch"], c["group"],
                                    fit=True)
    plan = fitted_loader._index_plan()
    fitted = _dispatched(fitted_loader, plan)
    assert fitted_loader.group_shapes == c["shapes"]
    lo, hi = c["after"]
    assert lo < _waste(fitted_loader, fitted, c["counter"]) < hi
    shapes_today = {spec for _, spec in today}
    shapes_fitted = {spec for _, spec in fitted}
    assert len(shapes_fitted) <= len(shapes_today) <= 3
    # the same batches in the same order: only the padding changed
    assert len(plan) == len(ladder_loader._index_plan())
    for (ix_a, _), (ix_b, _) in zip(plan, ladder_loader._index_plan()):
        assert np.array_equal(ix_a, ix_b)
    # the trailing partial group is never dispatched and costs no shape
    for _, spec in plan[len(fitted):]:
        assert spec in set(ladder) | shapes_fitted


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_spec_is_a_member_and_pick_spec_still_fits(cell):
    """(b) what the benchmark's comparisons reach into: every spec a batch
    is padded to is in ``pad_specs`` beside the ladder's rungs (worst case
    last), a spec is found by its node count, ``_pick_spec([batch])`` fits,
    and planning again gives the same shapes."""
    c = CELLS[cell]
    loader, ladder = _cell_loader(c["config"], c["batch"], c["group"],
                                  fit=True)
    plan = loader._index_plan()
    assert all(spec in loader.pad_specs for _, spec in plan)
    assert all(rung in loader.pad_specs for rung in ladder)
    assert loader.pad_specs[-1] == ladder[-1] == loader.pad_spec
    assert loader.pad_specs == sorted(
        loader.pad_specs, key=lambda p: (p.num_nodes, p.num_edges))
    assert len(loader.pad_specs) == len(set(loader.pad_specs))
    # train_epochs_mla.py: the largest staged shape, looked up by nodes
    nodes = max(spec.num_nodes for _, spec in _dispatched(loader, plan))
    found = next(p for p in loader.pad_specs if p.num_nodes == nodes)
    assert [found.num_nodes, found.num_edges] == c["shapes"][-1][:2]
    # train_epochs_lm.py: the first micro-batch of train samples
    for batch in ([loader.samples[i] for i in plan[0][0]],
                  loader.samples[:c["batch"]]):
        spec = loader._pick_spec([batch])
        assert spec in loader.pad_specs
        assert spec.num_nodes - 1 >= sum(s.num_nodes for s in batch)
        assert spec.num_edges >= sum(s.num_edges for s in batch)
    before = list(loader.pad_specs)
    again = loader._index_plan()
    assert [spec for _, spec in again] == [spec for _, spec in plan]
    assert loader.pad_specs == before


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_eval_loaders_get_the_fitted_shapes_as_rungs(cell):
    """Eval batches are still looked up one by one, never fitted; the
    fitted train shapes are further rungs to them (one PadSpec set for the
    three loaders), so no eval batch pads to more than before, none wants a
    shape of its own, and Laguna's test batch (13,661 tokens) leaves the
    20,000-node rung for the train step's 15,168."""
    c = CELLS[cell]
    train, ladder = _cell_loader(c["config"], c["batch"], c["group"],
                                 fit=True)
    train._index_plan()
    nodes, edges = _cell_sizes(c["config"])
    n_train, n_val = int(0.8 * len(nodes)), int(0.1 * len(nodes))
    picked = {}
    for name, sl in (("val", slice(n_train, n_train + n_val)),
                     ("test", slice(n_train + n_val, None))):
        samples = [_Sized(n, e) for n, e in zip(nodes[sl], edges[sl])]
        loader = GraphDataLoader(samples, [], c["batch"], pad_specs=ladder)
        before = [spec for _, spec in loader._index_plan()]
        loader.add_specs(train.pad_specs)
        after = [spec for _, spec in loader._index_plan()]
        assert loader.pad_specs == train.pad_specs
        assert loader.fit_groups is False and loader.group_shapes == []
        for a, b in zip(after, before):
            assert a.num_nodes <= b.num_nodes and a.num_edges <= b.num_edges
        assert len(set(after)) <= max(len(set(before)), 1) + len(c["shapes"])
        picked[name] = sorted({p.num_nodes for p in after})
    want = {"laguna_s_2_1-packed8k": {"val": [10536], "test": [15168]},
            "glm_4_7_flash-packed4k": {"val": [12768], "test": [12768]}}
    if cell in want:
        assert picked == want[cell]
    else:   # SchNet's 13 + 13 batches: the q50 and q99 rungs, as before
        assert set(picked["val"]) | set(picked["test"]) <= {9728, 10072}


def test_fit_group_specs_merges_and_caps():
    fit = lambda needs, cap: fit_group_specs(needs, 9, cap)  # noqa: E731
    # within 3 %: one shape, the elementwise maximum, rounded up to 8s
    specs = fit([(1000, 5000), (1010, 4990), (990, 5100)], 3)
    assert set(specs) == {PadSpec(1016, 5104, 9)}
    # far apart: a shape each while the cap allows it
    specs = fit([(1000, 5000), (2000, 9000), (4000, 20000)], 3)
    assert [p.num_nodes for p in specs] == [1008, 2008, 4008]
    # the cap binds: the nearest pair merges, every group still fits
    needs = [(1000, 5000), (1200, 6000), (2000, 9000), (4000, 20000)]
    specs = fit(needs, 3)
    assert len(set(specs)) == 3
    assert specs[0] == specs[1] == PadSpec(1208, 6000, 9)
    for (n, e), p in zip(needs, fit(needs, 1)):
        assert p == PadSpec(4008, 20000, 9)
    for cap in (1, 2, 3, 4):
        for (n, e), p in zip(needs, fit(needs, cap)):
            assert p.num_nodes - 1 >= n and p.num_edges >= e
    # nodes and edges need not rise together: a merged shape holds both
    specs = fit([(1000, 9000), (1020, 5000)], 1)
    assert set(specs) == {PadSpec(1024, 9000, 9)}
    # no edges at all (a language-model corpus): one slot of 8
    assert fit([(100, 0)], 3) == [PadSpec(104, 8, 9)]
    assert fit([], 3) == []


def _old_index_plan(loader):
    """The parent commit's ``_index_plan`` (758d067), kept here as the
    reference a loader that is NOT staged resident must match item for
    item."""
    order = loader._local_indices()
    nb = len(loader)
    plan = []
    for g0 in range(0, nb, loader.bucket_group):
        idxs = [order[b * loader.batch_size:(b + 1) * loader.batch_size]
                for b in range(g0, min(g0 + loader.bucket_group, nb))]
        if len(loader.pad_specs) == 1:
            spec = loader.pad_spec
        else:
            need_nodes = max(sum(loader.samples[i].num_nodes for i in ix)
                             for ix in idxs)
            need_edges = max(sum(loader.samples[i].num_edges for i in ix)
                             for ix in idxs)
            spec = next(
                (p for p in loader.pad_specs if p.num_nodes - 1 >= need_nodes
                 and p.num_edges >= need_edges), loader.pad_specs[-1])
        plan += [(np.asarray(ix), spec) for ix in idxs]
    return plan


@pytest.mark.parametrize("group", [1, 4, 32])
@pytest.mark.parametrize("n_buckets", [1, 3])
def test_unstaged_loader_plans_as_the_parent_did(group, n_buckets):
    """(c) seeded, several epochs: identical item for item, also after the
    trainer aligned the loader for a run that is not resident."""
    from hydragnn_tpu.train.trainer import _align_bucket_group

    rng = np.random.default_rng(11)
    nodes = rng.integers(3, 30, size=3000)
    samples = [_Sized(n, n * (n - 1)) for n in nodes]
    ladder = bucket_pad_specs_from_sizes(
        nodes, nodes * (nodes - 1), 16, n_buckets)
    for aligned in (False, True):
        loader = GraphDataLoader(samples, [], 16, shuffle=True, seed=5,
                                 pad_specs=ladder, bucket_group=group)
        if aligned:
            class Wrap:
                def __init__(self, inner):
                    self.loader = inner

            assert _align_bucket_group(Wrap(Wrap(loader)), group) is None
            assert loader.bucket_group == group
        assert loader.fit_groups is False
        for epoch in range(3):
            loader.set_epoch(epoch)
            got, want = loader._index_plan(), _old_index_plan(loader)
            assert len(got) == len(want) == len(loader)
            for (ix_g, spec_g), (ix_w, spec_w) in zip(got, want):
                assert np.array_equal(ix_g, ix_w) and spec_g == spec_w
        assert loader.pad_specs == sorted(ladder, key=lambda p: p.num_nodes)
        assert loader.group_shapes == []


def test_align_bucket_group_fits_only_what_can_be_fitted():
    from hydragnn_tpu.train.trainer import _align_bucket_group

    nodes = np.random.default_rng(2).integers(3, 30, size=600)
    samples = [_Sized(n, n * (n - 1)) for n in nodes]
    mk = lambda n_buckets: GraphDataLoader(  # noqa: E731
        samples, [], 16, shuffle=True, pad_specs=bucket_pad_specs_from_sizes(
            nodes, nodes * (nodes - 1), 16, n_buckets))
    loader = mk(3)
    assert _align_bucket_group(loader, 4, fit=True) is loader
    assert loader.fit_groups and loader.bucket_group == 4
    # a single spec (HYDRAGNN_NUM_BUCKETS=1, multi-process) stays single
    single = mk(1)
    assert _align_bucket_group(single, 4, fit=True) is None
    assert not single.fit_groups and single.bucket_group == 4
    assert {spec for _, spec in single._index_plan()} == {single.pad_spec}
    # K = 1 on one device: nothing is stacked, nothing is told
    alone = mk(3)
    assert _align_bucket_group(alone, 1, fit=True) is None
    assert not alone.fit_groups and alone.bucket_group == 1


# ---------------------------------------------------------------------------
# tiny CPU jobs through the stock trainer
# ---------------------------------------------------------------------------


def _graphs(n, seed=0):
    """Graphs of 4-14 atoms: batch sums spread enough for three rungs."""
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        a = int(rng.randint(4, 15))
        pos = rng.rand(a, 3).astype(np.float32) * 2.0
        x = rng.rand(a, 1).astype(np.float32)
        out.append(GraphSample(
            x=x, pos=pos, edge_index=radius_graph(pos, 1.2, 10),
            graph_y=x.sum(keepdims=True)[0], node_y=x))
    return out


def _shape(batch):
    return tuple(batch.x.shape[:-1]) + tuple(batch.senders.shape[-1:])


def test_base_loader_iterated_after_a_resident_run_gives_the_staged_shapes():
    """(b) ``train_epochs_mla.py`` iterates the base loader AFTER the run:
    the resident wrapper stops forwarding ``set_epoch`` once staging is
    complete, so the base loader plans the staged epoch again."""
    from hydragnn_tpu.data.prefetch import ResidentDeviceLoader
    from hydragnn_tpu.parallel.mesh import DeviceStackLoader
    from hydragnn_tpu.train.trainer import _align_bucket_group

    samples = _graphs(150)
    heads = [HeadSpec("e", "graph", 1)]
    base = GraphDataLoader(samples, heads, 8, shuffle=True, seed=3,
                           pad_specs=bucket_pad_specs(samples, 8, 3))
    assert _align_bucket_group(base, 4, fit=True) is base
    resident = ResidentDeviceLoader(DeviceStackLoader(base, 4, drop_last=True))
    staged = []
    for epoch in range(3):
        resident.set_epoch(epoch)
        shapes = sorted(_shape(b) for b in resident)
        staged.append(shapes)
    assert staged[0] == staged[1] == staged[2] and len(staged[0]) == 4
    assert base.epoch == 0
    again = [b for b in base]
    assert len(again) == 19 and len(base.group_shapes) <= 3
    for g, group_shape in enumerate(sorted(
            (4,) + _shape(again[4 * g]) for g in range(4))):
        assert group_shape == staged[0][g]
    for b in again:
        assert any(b.x.shape[0] == p.num_nodes
                   and b.senders.shape[0] == p.num_edges
                   for p in base.pad_specs)
    assert sum(n for _, _, n in base.group_shapes) == 4


def _sage():
    from hydragnn_tpu.models.base import GraphHeadCfg, ModelConfig
    from hydragnn_tpu.models.create import create_model

    cfg = ModelConfig(
        model_type="SAGE", input_dim=1, hidden_dim=8, output_dim=(1,),
        output_type=("graph",), graph_head=GraphHeadCfg(1, 8, 1, (8,)),
        node_head=None, task_weights=(1.0,), num_conv_layers=2)
    return cfg, create_model(cfg)


def _sage_loaders(batch_size=8, seed=7):
    samples = _graphs(176, seed=5)
    heads = [HeadSpec("e", "graph", 1)]
    ladder = bucket_pad_specs(samples, batch_size, 3)
    mk = lambda split, shuffle: GraphDataLoader(  # noqa: E731
        split, heads, batch_size, pad_specs=ladder, shuffle=shuffle,
        seed=seed)
    return mk(samples[:144], True), mk(samples[144:160], False), mk(
        samples[160:], False)


def _train_sage(tmp_path, name, num_epoch=3, resume_meta=None, state=None,
                **kw):
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.trainer import (
        create_train_state,
        train_validate_test,
    )

    cfg, model = _sage()
    opt = select_optimizer({"type": "AdamW", "learning_rate": 0.01})
    train_l, val_l, test_l = _sage_loaders()
    if state is None:
        state = create_train_state(model, next(iter(train_l)), opt)
    return train_validate_test(
        model, cfg, state, opt, train_l, val_l, test_l,
        {"Training": {"num_epoch": num_epoch},
         "Variables_of_interest": {"output_names": ["e"]}},
        log_name=name, logs_dir=str(tmp_path), resume_meta=resume_meta,
        use_mesh_dp=False, **kw)


@pytest.fixture
def resident_k4(monkeypatch):
    monkeypatch.setenv("HYDRAGNN_RESIDENT_DATASET", "1")
    monkeypatch.setenv("HYDRAGNN_STEPS_PER_DISPATCH", "4")
    monkeypatch.delenv("HYDRAGNN_CHAOS_PREEMPT_STEP", raising=False)


def test_pipeline_block_says_who_shaped_the_groups(tmp_path, resident_k4,
                                                   monkeypatch):
    """(e) ``group_fit`` / ``group_shapes`` are written (history, manifest,
    resume bundle), a resumed run writes them again, and a bundle from
    before the keys existed resumes."""
    from hydragnn_tpu.resilience import load_resume_bundle, resume_dir
    from hydragnn_tpu.telemetry import MetricsLogger, TelemetryConfig
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.trainer import create_train_state

    tele = MetricsLogger(
        TelemetryConfig(enable=True, sinks=("jsonl",)), run_name="whole",
        out_dir=str(tmp_path / "whole" / "telemetry"), rank=0, world_size=1)
    state_a, hist = _train_sage(tmp_path, "whole", telemetry=tele)
    pipe = hist["pipeline"]
    assert pipe["resident"] is True and pipe["steps_per_dispatch"] == 4
    assert pipe["group_fit"] is True
    shapes = pipe["group_shapes"]
    assert 1 <= len(shapes) <= 3 and sum(n for _, _, n in shapes) == 4
    assert all(isinstance(v, int) for shape in shapes for v in shape)
    with open(tmp_path / "whole" / "telemetry" / "events.jsonl") as f:
        events = [json.loads(ln) for ln in f if ln.strip()]
    manifest = next(e for e in events if e["event"] == "manifest")
    assert manifest["history"]["pipeline"] == pipe
    # every train step ran at a fitted shape
    padded = {(e["padding"]["padded_nodes"], e["padding"]["padded_edges"])
              for e in events if e["event"] == "step"}
    assert padded <= {(4 * n, 4 * e) for n, e, _ in shapes}
    from tools.teleview import pipeline_line

    line = pipeline_line(manifest["history"]["pipeline"])
    assert "fitted" in line and f"{shapes[0][0]}x{shapes[0][1]}" in line
    assert "from the ladder" in pipeline_line({**pipe, "group_fit": False})

    # not resident: the ladder, and the block says so
    monkeypatch.setenv("HYDRAGNN_RESIDENT_DATASET", "0")
    _, hist_host = _train_sage(tmp_path, "hostfed", num_epoch=1)
    assert hist_host["pipeline"]["group_fit"] is False
    assert hist_host["pipeline"]["group_shapes"] == []
    monkeypatch.setenv("HYDRAGNN_RESIDENT_DATASET", "1")

    # preempted in epoch 1, resumed: the bundle carries the keys, the
    # resumed run plans anew and writes them again
    monkeypatch.setenv("HYDRAGNN_CHAOS_PREEMPT_STEP", "6")
    _, hist_b = _train_sage(tmp_path, "cut")
    assert hist_b.get("preempted") is True
    monkeypatch.delenv("HYDRAGNN_CHAOS_PREEMPT_STEP")
    cfg, model = _sage()
    opt = select_optimizer({"type": "AdamW", "learning_rate": 0.01})
    skeleton = create_train_state(
        model, next(iter(_sage_loaders()[0])), opt)
    state_r, meta = load_resume_bundle(
        skeleton, resume_dir(str(tmp_path), "cut"))
    assert meta["pipeline"]["group_fit"] is True
    assert meta["pipeline"]["group_shapes"] == shapes
    _, hist_c = _train_sage(tmp_path, "cut", resume_meta=meta, state=state_r)
    assert hist_c["pipeline"]["group_fit"] is True
    assert sum(n for _, _, n in hist_c["pipeline"]["group_shapes"]) == 4
    assert len(hist_c["train"]) == 3

    # a checkpoint written before the keys existed
    old = {**meta, "pipeline": {k: v for k, v in meta["pipeline"].items()
                                if k not in ("group_fit", "group_shapes")}}
    state_r, _ = load_resume_bundle(
        skeleton, resume_dir(str(tmp_path), "cut"))
    _, hist_d = _train_sage(tmp_path, "cut", resume_meta=old, state=state_r)
    assert hist_d["pipeline"]["group_fit"] is True
    assert len(hist_d["train"]) == 3


# ---------------------------------------------------------------------------
# (d) padding is inert
# ---------------------------------------------------------------------------


def _schnet_job(driver, n, batch_size):
    config = _bench_config("schnet_qm9")
    for key in ("dry_cpu", "Provenance", "expect"):
        config.pop(key)
    config["corpus"] = {"generator": "qm9_shaped", "n": n,
                        "params": {"atoms_lo": 5, "atoms_hi": 14,
                                   "layout_seed": 0}}
    arch = config["NeuralNetwork"]["Architecture"]
    arch.update(radius=3.0, max_neighbours=6, num_gaussians=8, num_filters=8,
                hidden_dim=8, num_conv_layers=2,
                aggregation_backend="scatter")
    arch["output_heads"]["graph"].update(dim_sharedlayers=8,
                                         dim_headlayers=[8, 8])
    config["NeuralNetwork"]["Training"].update(batch_size=batch_size)
    gen = _bench_module("corpora", "qm9_shaped")
    corpus = gen.generate(n, 3, config["corpus"]["params"])
    return driver.assemble(config, gen.to_samples(corpus, config), 3, 0)


def _laguna_job(driver):
    config = _bench_config("laguna_s_2_1")
    config = _bench_run_module().deep_merge(config, config["dry_cpu"])
    config["corpus"]["n"] = 160
    for key in ("Provenance", "expect", "dry_cpu"):
        config.pop(key, None)
    skip = ("share", "corpus", "Verbosity", "Dataset", "NeuralNetwork",
            "Telemetry", "Visualization")
    arch = config["NeuralNetwork"]["Architecture"]
    arch["laguna"] = {k: v for k, v in config.items() if k not in skip}
    arch["share"] = config["share"]
    config["corpus"]["params"]["vocab_size"] = config["vocab_size"]
    gen = _bench_module("corpora", "packed_docs")
    corpus = gen.generate(config["corpus"]["n"], 3,
                          config["corpus"]["params"])
    return driver.assemble(config, gen.to_samples(corpus, config), 3, 0)


def _run_job(job, tmp_path, name, num_epoch, mesh=None):
    """The job through ``train_validate_test`` as the benchmark's driver
    calls it; returns the per-dispatch step records and the history."""
    import copy

    from hydragnn_tpu.telemetry import MetricsLogger, TelemetryConfig
    from hydragnn_tpu.train.trainer import train_validate_test

    nn = copy.deepcopy(job["config"]["NeuralNetwork"])
    nn["Training"]["num_epoch"] = num_epoch
    tele = MetricsLogger(
        TelemetryConfig(enable=True, sinks=("jsonl",)), run_name=name,
        out_dir=str(tmp_path / name / "telemetry"), rank=0, world_size=1)
    state = jax.tree.map(lambda a: a.copy(), job["state"])
    train_l, val_l, test_l = job["loaders"]
    _state, hist = train_validate_test(
        job["model"], job["cfg"], state, job["opt_spec"], train_l, val_l,
        test_l, nn, name, 0, rank=0, world_size=1, logs_dir=str(tmp_path),
        telemetry=tele, mesh=mesh, use_mesh_dp=mesh is not None)
    with open(tmp_path / name / "telemetry" / "events.jsonl") as f:
        events = [json.loads(ln) for ln in f if ln.strip()]
    return [e for e in events if e["event"] == "step"], hist


@pytest.mark.parametrize("case", ["schnet", "laguna_dry_cpu", "schnet_dp4"])
def test_padding_is_inert(case, tmp_path, resident_k4, monkeypatch):
    """(d) the same batches in the same steps, over fewer empty rows: a
    short resident run gives the same losses fitted and unfitted, dispatch
    by dispatch and epoch by epoch (float32, <= 1e-6 relative) — on the
    local scan path, on the language-model stack, on the DP mesh path over
    4 host devices."""
    driver = _bench_module("drivers", "train_epochs")
    mesh = None
    if case == "schnet_dp4":
        from hydragnn_tpu.parallel.mesh import make_mesh

        monkeypatch.setenv("HYDRAGNN_STEPS_PER_DISPATCH", "2")
        mesh = make_mesh(jax.devices()[:4])
    if case == "laguna_dry_cpu":
        build = lambda: _laguna_job(driver)  # noqa: E731
    else:
        # assemble() splits the batch over this process's 8 host devices
        build = lambda: _schnet_job(  # noqa: E731
            driver, 400, 128 if mesh is not None else 64)

    job = build()
    steps, hist = _run_job(job, tmp_path, "fitted", 2, mesh)
    train_l, val_l, test_l = job["loaders"]
    assert len(train_l.pad_specs) > len(train_l._ladder)
    assert val_l.pad_specs == test_l.pad_specs == train_l.pad_specs
    monkeypatch.setattr(GraphDataLoader, "fit_to_groups", lambda self: False)
    job = build()
    steps_0, hist_0 = _run_job(job, tmp_path, "ladder", 2, mesh)
    assert all(l.pad_specs == l._ladder for l in job["loaders"])

    assert hist["pipeline"]["group_fit"] is True
    assert hist_0["pipeline"]["group_fit"] is False
    assert hist["pipeline"]["resident"] and hist_0["pipeline"]["resident"]
    assert hist["pipeline"]["use_mesh_dp"] is (mesh is not None)
    assert len(steps) == len(steps_0) >= 4
    # fewer padded slots, the same real ones
    slots = lambda recs, key: sum(r["padding"][key] for r in recs)  # noqa: E731
    assert slots(steps, "padded_nodes") < slots(steps_0, "padded_nodes")
    assert slots(steps, "nodes_real") == slots(steps_0, "nodes_real")
    assert slots(steps, "edges_real") == slots(steps_0, "edges_real")
    for a, b in zip(steps, steps_0):
        assert a["num_graphs"] == b["num_graphs"]
        assert abs(a["loss"] - b["loss"]) <= 1e-6 * abs(b["loss"]), (a, b)
    for key in ("train", "val", "test"):
        for a, b in zip(hist[key], hist_0[key]):
            assert abs(a - b) <= 1e-6 * abs(b), (key, hist[key], hist_0[key])

