"""Telemetry subsystem: sinks, ring buffer, in-jit norms, padding math,
in-run MFU basis sharing with bench.py, cross-rank reduction, and the
prefetch shm-drain regression.

Tier-1 (not slow-marked): the observability spine every perf PR reports
through has to stay green at the same cadence as the trainer itself.
"""

import json
import os
import re
import socket
import subprocess
import sys
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hydragnn_tpu.graph.batch import (
    GraphSample,
    HeadSpec,
    PadSpec,
    collate,
)
from hydragnn_tpu.graph.neighborlist import radius_graph
from hydragnn_tpu.models.base import GraphHeadCfg, ModelConfig
from hydragnn_tpu.models.create import create_model
from hydragnn_tpu.train.optimizer import select_optimizer
from hydragnn_tpu.train.trainer import (
    _loss_and_metrics,
    create_train_state,
    make_train_step,
    merge_scanned_metrics,
    tree_l2_norm,
)
from hydragnn_tpu.telemetry import (
    JsonlSink,
    MetricsLogger,
    RingBuffer,
    TelemetryConfig,
    batch_pad_meta,
    waste_pct,
)
from hydragnn_tpu.telemetry.flops import step_cost_flops


def _samples(n_graphs=6, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n_graphs):
        n = rng.randint(4, 8)
        pos = rng.rand(n, 3).astype(np.float32) * 2.0
        x = rng.randint(0, 4, (n, 1)).astype(np.float32)
        ei = radius_graph(pos, radius=1.2, max_neighbours=8)
        out.append(GraphSample(
            x=x, pos=pos, edge_index=ei,
            graph_y=rng.rand(1).astype(np.float32)))
    return out


def _cfg():
    return ModelConfig(
        model_type="SAGE", input_dim=1, hidden_dim=8, output_dim=(1,),
        output_type=("graph",), graph_head=GraphHeadCfg(2, 8, 1, (8,)),
        node_head=None, task_weights=(1.0,), num_conv_layers=2)


def _batch(samples=None, batch_size=6):
    samples = samples or _samples(batch_size)
    heads = [HeadSpec("energy", "graph", 1)]
    pad = PadSpec.for_batch(batch_size, max(s.num_nodes for s in samples),
                            max(s.num_edges for s in samples))
    return collate(samples, pad, heads), pad, samples


# ---------------------------------------------------------------------------
# sinks + ring buffer
# ---------------------------------------------------------------------------


def test_jsonl_sink_schema_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    sink = JsonlSink(path)
    records = [
        {"event": "run_start", "run_id": "r1", "rank": 0, "t": 1.0},
        {"event": "step", "run_id": "r1", "rank": 0, "epoch": 0, "step": 1,
         "loss": 0.5, "tasks": [0.5], "grad_norm": 1.25,
         "step_time_s": 0.01,
         "padding": {"nodes_waste_pct": 12.5, "edges_waste_pct": 25.0}},
        {"event": "epoch", "run_id": "r1", "rank": 0, "epoch": 0,
         "train_loss": 0.5, "val_loss": 0.4, "test_loss": 0.3, "lr": 1e-3,
         "epoch_time_s": 2.0, "train_tasks": [0.5]},
        {"event": "manifest", "run_id": "r1", "total_steps": 1,
         "timers": {"train": {"total_s": 2.0, "count": 1}}},
    ]
    for r in records:
        sink.emit(r)
    sink.close()
    back = [json.loads(line) for line in open(path)]
    assert back == records  # full schema round-trip, key for key
    # numpy scalars must serialize as plain JSON numbers
    sink2 = JsonlSink(path)
    sink2.emit({"event": "step", "loss": np.float32(0.25),
                "num_graphs": np.int64(4)})
    sink2.close()
    last = json.loads(open(path).readlines()[-1])
    assert last["loss"] == 0.25 and last["num_graphs"] == 4


def test_ring_buffer_aggregation():
    ring = RingBuffer(capacity=4)
    for i in range(10):
        ring.push({"loss": float(i), "const": 2.0})
    agg = ring.aggregate()
    # capacity 4: only steps 6..9 remain
    assert agg["loss"]["min"] == 6.0
    assert agg["loss"]["max"] == 9.0
    assert agg["loss"]["avg"] == pytest.approx(7.5)
    assert agg["loss"]["last"] == 9.0
    assert agg["loss"]["count"] == 4
    assert agg["const"]["avg"] == 2.0


# ---------------------------------------------------------------------------
# in-jit metrics
# ---------------------------------------------------------------------------


def test_grad_norm_matches_eager_recompute():
    cfg = _cfg()
    model = create_model(cfg)
    opt = select_optimizer({"type": "AdamW", "learning_rate": 1e-3})
    g, _, _ = _batch()
    state = create_train_state(model, g, opt)
    step = make_train_step(model, cfg, opt, ["energy"],
                           telemetry_metrics=True)
    _, metrics = step(state, g)

    # eager recompute with the SAME dropout fold the step uses
    dropout_rng = jax.random.fold_in(jax.random.PRNGKey(0xD0), state.step)

    def loss_fn(params):
        return _loss_and_metrics(
            model, cfg, params, state.batch_stats, g, True, -1, -1,
            dropout_rng)

    _, grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
    want = np.sqrt(sum(
        float(np.sum(np.square(np.asarray(l, np.float64))))
        for l in jax.tree_util.tree_leaves(grads)))
    assert float(metrics["grad_norm"]) == pytest.approx(want, rel=1e-4)
    # param/update norms present and positive
    assert float(metrics["param_norm"]) > 0
    assert float(metrics["update_norm"]) > 0
    # the real-slot counters match the masks
    assert float(metrics["nodes_real"]) == float(np.sum(g.node_mask))
    assert float(metrics["edges_real"]) == float(np.sum(g.edge_mask))


def test_tree_l2_norm_skips_non_float():
    tree = {"a": jnp.asarray([3.0, 4.0]), "n": jnp.asarray([7], jnp.int32)}
    assert float(tree_l2_norm(tree)) == pytest.approx(5.0)


def test_merge_scanned_metrics_counts_vs_means():
    ms = {
        "loss": jnp.asarray([1.0, 3.0]),
        "num_graphs": jnp.asarray([2.0, 6.0]),
        "nodes_real": jnp.asarray([10.0, 20.0]),
        "edges_real": jnp.asarray([4.0, 8.0]),
        "grad_norm": jnp.asarray([1.0, 2.0]),
        "task_0": jnp.asarray([1.0, 3.0]),
        "attn_blocks_run": jnp.asarray([109.0, 98.0]),
        "attn_blocks_band": jnp.asarray([324.0, 324.0]),
    }
    merged = merge_scanned_metrics(ms)
    # counts SUM across the scanned steps
    assert float(merged["attn_blocks_run"]) == 207.0
    assert float(merged["attn_blocks_band"]) == 648.0
    assert float(merged["num_graphs"]) == 8.0
    assert float(merged["nodes_real"]) == 30.0
    assert float(merged["edges_real"]) == 12.0
    # scalars merge graph-weighted: (1*2 + 3*6) / 8
    assert float(merged["loss"]) == pytest.approx(2.5)
    assert float(merged["task_0"]) == pytest.approx(2.5)
    assert float(merged["grad_norm"]) == pytest.approx((2.0 + 12.0) / 8.0)


# ---------------------------------------------------------------------------
# padding-waste math
# ---------------------------------------------------------------------------


def test_padding_waste_against_hand_built_padspec():
    samples = _samples(4, seed=3)
    heads = [HeadSpec("energy", "graph", 1)]
    pad = PadSpec(num_nodes=64, num_edges=96, num_graphs=5)
    g = collate(samples, pad, heads)
    meta = batch_pad_meta(g)
    assert meta == {"padded_nodes": 64, "padded_edges": 96,
                    "padded_graphs": 5}
    real_nodes = sum(s.num_nodes for s in samples)
    real_edges = sum(s.num_edges for s in samples)
    assert float(np.sum(g.node_mask)) == real_nodes
    assert waste_pct(real_nodes, meta["padded_nodes"]) == pytest.approx(
        (1 - real_nodes / 64) * 100)
    assert waste_pct(real_edges, meta["padded_edges"]) == pytest.approx(
        (1 - real_edges / 96) * 100)
    # stacked batches: leading axes multiply padded slots
    stacked = jax.tree_util.tree_map(
        lambda x: np.stack([np.asarray(x)] * 3), g)
    meta3 = batch_pad_meta(stacked)
    assert meta3 == {"padded_nodes": 3 * 64, "padded_edges": 3 * 96,
                     "padded_graphs": 3 * 5}


# ---------------------------------------------------------------------------
# shared flops basis (bench <-> telemetry)
# ---------------------------------------------------------------------------


def test_bench_uses_shared_flops_helper():
    """bench.py's _cost_flops must be a thin delegate of the telemetry
    helper: same function, same numbers, no drift."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    def f(a, b):
        return a @ b

    a = jnp.ones((16, 16))
    want = step_cost_flops(f, a, a)
    got = bench._cost_flops(f, a, a)
    assert got == want and want > 0
    # and bench's MFU peak comes from the telemetry table, keyed by the
    # device JAX reports: the v5e has its published row, a device outside
    # the table (this CPU) has no peak — telemetry then emits no
    # mfu_est_pct and bench refuses to compute one
    from hydragnn_tpu.telemetry.flops import DEVICE_PEAKS, peak_flops

    assert peak_flops("TPU v5 lite") == 197e12
    assert DEVICE_PEAKS["TPU v5 lite"]["source"]
    assert peak_flops(jax.devices()[0].device_kind) is None
    with pytest.raises(RuntimeError, match="no published peak"):
        bench._mxu_peak()


def test_step_cost_flops_accepts_avals():
    """Lowering from ShapeDtypeStructs (post-donation avals) must work."""
    def f(a, b):
        return a @ b

    aval = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    assert step_cost_flops(f, aval, aval) > 0


# ---------------------------------------------------------------------------
# end-to-end smoke (the ISSUE acceptance criterion) + teleview
# ---------------------------------------------------------------------------


def test_training_smoke_emits_full_jsonl(tmp_path, capsys):
    from hydragnn_tpu.data.dataloader import create_dataloaders
    from hydragnn_tpu.train.trainer import train_validate_test

    samples = _samples(48, seed=1)
    heads = [HeadSpec("energy", "graph", 1)]
    tl, vl, sl = create_dataloaders(
        samples[:32], samples[32:40], samples[40:], 8, heads)
    cfg = _cfg()
    model = create_model(cfg)
    opt = select_optimizer({"type": "AdamW", "learning_rate": 1e-3})
    state = create_train_state(model, next(iter(tl)), opt)
    out_dir = str(tmp_path / "telemetry")
    tele = MetricsLogger(
        TelemetryConfig(enable=True, sinks=("jsonl",)),
        run_name="tele_smoke", out_dir=out_dir)
    state, hist = train_validate_test(
        model, cfg, state, opt, tl, vl, sl,
        {"Training": {"num_epoch": 2},
         "Variables_of_interest": {"output_names": ["energy"]}},
        "tele_smoke", verbosity=0, rank=0, world_size=1,
        use_mesh_dp=False, logs_dir=str(tmp_path), telemetry=tele)

    recs = [json.loads(line)
            for line in open(os.path.join(out_dir, "events.jsonl"))]
    steps = [r for r in recs if r["event"] == "step"]
    epochs = [r for r in recs if r["event"] == "epoch"]
    manifests = [r for r in recs if r["event"] == "manifest"]
    assert len(epochs) == 2 and len(manifests) == 1 and steps
    for r in steps:
        # the acceptance-criterion field set, per step
        assert {"loss", "tasks", "grad_norm", "step_time_s", "padding",
                "run_id", "rank", "epoch", "step"} <= set(r)
        assert "nodes_waste_pct" in r["padding"]
        # the CPU cost model supplies flops, but a CPU has no row in
        # DEVICE_PEAKS: a count is recorded, a utilization is not
        assert r["flops_per_dispatch"] > 0
        assert "mfu_est_pct" not in r
        assert r["tasks"], "per-head losses missing"
    # manifest folds the TimerTracer summaries in
    assert "train" in manifests[-1]["timers"]
    assert manifests[-1]["total_steps"] == steps[-1]["step"]
    # ... and the fused-vs-fallback dispatch tally (trace-time counts of
    # this run's aggregation dispatch decisions; scatter backend here, so
    # every entry is a :scatter fallback)
    disp = manifests[-1]["aggr_dispatch"]
    assert disp and all(k.endswith(":scatter") for k in disp)
    assert manifests[-1]["aggr_dispatch_summary"] == "scatter"
    run_starts = [r for r in recs if r["event"] == "run_start"]
    assert run_starts[-1]["aggr_backend"] == "scatter"
    # both bracket records name the device the process got, the three
    # versions, and its peak (none here)
    dev = jax.devices()[0]
    for r in (run_starts[-1], manifests[-1]):
        assert r["platform"] == dev.platform == "cpu"
        assert r["device_kind"] == dev.device_kind
        assert r["device_count"] == len(jax.devices())
        assert r["jax"] == jax.__version__ and r["jaxlib"]
        assert "libtpu" in r
        assert r["peak_flops_basis"] is None
    # epoch record carries loader padding + pipeline accounting
    assert "padding_waste_pct" in epochs[0]

    # tools/teleview.py renders it
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import teleview

    assert teleview.main([out_dir, "--tail", "4"]) == 0
    rendered = capsys.readouterr().out
    assert "mfu%" in rendered and "epochs:" in rendered
    assert "aggr dispatch:" in rendered


@pytest.mark.parametrize("stack", ["laguna", "glm_moe_lite", "nemotron_h",
                                   "lfm2_moe", "sage"])
def test_step_records_carry_the_attention_schedule_of_a_language_model(
        stack, tmp_path):
    """A language-model stack's step record has an ``attention`` block
    (ops/attention.py scheduled_blocks, summed over the attending layers'
    forward calls); a message-passing stack's has none.  A stack with
    state-space layers also has an ``ssm`` block (ops/ssm.py scan_counts:
    what one layer's scan walked), one with short convolutions an ``sconv``
    block (ops/sconv.py conv_counts, summed over the conv layers, and the
    MB those layers' checkpoints keep); no other stack has either.  A stack
    whose dense feed-forward keeps its up-products says how many MB in an
    ``ffn`` block (models/sequence.py KEEP_FFN)."""
    if stack == "sage":
        cfg, (batch, _pad, _s), layers = _cfg(), _batch(), 0
    else:
        import test_glm_moe_lite
        import test_laguna
        import test_lfm2_moe
        import test_nemotron_h

        T = {"laguna": test_laguna, "glm_moe_lite": test_glm_moe_lite,
             "nemotron_h": test_nemotron_h, "lfm2_moe": test_lfm2_moe}[stack]
        cfg = ModelConfig.from_config(T.nn_section())
        rng = np.random.default_rng(0)
        docs = [T.sample(rng.integers(0, 64, size=n)) for n in (5, 20, 3, 12)]
        heads = [HeadSpec(f"next{i}", "node", 1)
                 for i in range(len(cfg.output_dim))]
        batch = collate(docs, PadSpec(48, 8, 5), heads)
        # three layers, and the multi-token-prediction module's own; the
        # state-space stack's pattern has ONE attention layer, as has the
        # short-convolution stack's
        layers = {"laguna": 3, "glm_moe_lite": 4, "nemotron_h": 1,
                  "lfm2_moe": 1}[stack]
    model = create_model(cfg)
    opt = select_optimizer({"type": "AdamW", "learning_rate": 1e-3})
    state = create_train_state(model, batch, opt)
    step = jax.jit(make_train_step(model, cfg, opt, telemetry_metrics=True))
    out_dir = str(tmp_path / "telemetry")
    tele = MetricsLogger(TelemetryConfig(enable=True, sinks=("jsonl",)),
                         run_name=f"attn_{stack}", out_dir=out_dir)
    tele.begin_epoch(0)
    for _ in range(2):
        state, metrics = step(state, batch)
        tele.on_step(metrics, batch)
    tele.flush_steps()
    tele.finalize()
    steps = [r for r in map(json.loads, open(
        os.path.join(out_dir, "events.jsonl"))) if r["event"] == "step"]
    assert len(steps) == 2
    for r in steps:
        if not layers:
            assert "attention" not in r and "moe" not in r
            continue
        # 48 nodes are one block of the kernels' 512: every layer's band
        # is its diagonal block, and that always runs
        # the dense backend (the CPU's) names nothing for a checkpoint to keep
        assert r["attention"] == {"blocks_run": float(layers),
                                  "blocks_band": float(layers),
                                  "kept_mb": 0.0}
        assert "moe" in r
        # 48 node slots in chunks of 16, 40 real nodes, four graphs
        assert r.get("ssm") == ({"chunks": 3.0, "chunks_padding": 0.0,
                                 "resets": 4.0}
                                if stack == "nemotron_h" else None)
        # two conv layers over 40 real rows in four graphs of three nodes
        # and more: 3 taps cut a graph and a layer; in float32 a layer's
        # checkpoint keeps nothing of its input product
        # (tests/test_product_residuals.py has the bfloat16 numbers)
        assert r.get("sconv") == ({"rows": 80.0, "starts": 8.0,
                                   "taps_cut": 24.0, "kept_mb": 0.0}
                                  if stack == "lfm2_moe" else None)
        # a stack whose dense layer may keep its up-products says so;
        # latent attention's never does, the state-space stack has no
        # such layer
        assert r.get("ffn") == ({"kept_mb": 0.0}
                                if stack in ("laguna", "lfm2_moe") else None)


def test_disabled_logger_writes_nothing(tmp_path):
    out_dir = str(tmp_path / "telemetry")
    tele = MetricsLogger(TelemetryConfig(enable=False), out_dir=out_dir)
    g, _, _ = _batch()
    tele.begin_epoch(0)
    tele.on_step({"loss": jnp.float32(1.0), "num_graphs": jnp.float32(1.0)},
                 g)
    tele.flush_steps()
    tele.log_epoch(0, {"train_loss": 1.0, "val_loss": 1.0, "test_loss": 1.0,
                       "lr": 1e-3, "epoch_time_s": 1.0, "train_tasks": []})
    tele.finalize()
    assert not os.path.exists(out_dir)


# ---------------------------------------------------------------------------
# cross-rank reduction (2-process harness)
# ---------------------------------------------------------------------------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_multi_rank_epoch_reduction(tmp_path):
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "mp_telemetry_worker.py")
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one device per process

    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(r), "2", str(port), str(tmp_path)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for r in range(2)
    ]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-3000:]}"
    m = re.search(r"TELEMRESULT rank=0 min=([\d.]+) max=([\d.]+) "
                  r"avg=([\d.]+)", outs[0] + outs[1])
    assert m, outs[0][-2000:]
    mn, mx, avg = (float(m.group(i)) for i in (1, 2, 3))
    assert (mn, mx, avg) == (pytest.approx(1.0), pytest.approx(3.0),
                             pytest.approx(2.0))


# ---------------------------------------------------------------------------
# prefetch shm drain regression
# ---------------------------------------------------------------------------


def _shm_entries():
    try:
        return {n for n in os.listdir("/dev/shm") if n.startswith("psm_")}
    except FileNotFoundError:  # non-Linux
        return set()


@pytest.mark.skipif(not os.path.isdir("/dev/shm"),
                    reason="needs /dev/shm to observe segment leaks")
def test_prefetch_shm_drained_on_abandoned_epoch():
    """Abandoning a ProcessPrefetchLoader epoch mid-flight and closing the
    loader must leave ZERO new /dev/shm segments: futures whose cancel()
    fails are blocked on and their segments released (the ADVICE shm-leak
    fix)."""
    from hydragnn_tpu.data.dataloader import GraphDataLoader
    from hydragnn_tpu.data.prefetch import ProcessPrefetchLoader

    samples = _samples(64, seed=5)
    heads = [HeadSpec("energy", "graph", 1)]

    def slow_collate(b):
        time.sleep(0.05)  # keep collations in flight at abandon time
        return b

    loader = GraphDataLoader(samples, heads, 4, shuffle=False,
                             post_collate=slow_collate)
    proc = ProcessPrefetchLoader(loader, num_workers=2, prefetch=4)
    before = _shm_entries()
    try:
        it = iter(proc)
        next(it)
        next(it)
        it.close()  # abandon mid-epoch -> GeneratorExit drain
    finally:
        proc.close()  # settles anything still in flight
    # segments are unlinked synchronously by the drain; allow a short
    # grace for the kernel to reflect it in the directory listing
    for _ in range(50):
        leaked = _shm_entries() - before
        if not leaked:
            break
        time.sleep(0.1)
    assert not leaked, f"leaked shm segments: {sorted(leaked)}"


def test_shm_import_releases_on_failure():
    """_shm_import must unlink the segment even when reconstruction fails
    mid-loop (try/finally regression)."""
    from hydragnn_tpu.data.prefetch import _shm_export, _shm_import

    batch = {"a": np.arange(8, dtype=np.float32)}
    desc = _shm_export(batch)
    tag, name, specs, treedef = desc
    bad = (tag, name, [("a", (8,), "<f4", 0), ("boom",)], treedef)
    with pytest.raises(Exception):
        _shm_import(bad)
    # the segment must be gone despite the failure
    from multiprocessing import shared_memory

    with pytest.raises(FileNotFoundError):
        shared_memory.SharedMemory(name=name)
