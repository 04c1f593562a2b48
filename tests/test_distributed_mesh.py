"""Data-parallel mesh tests on the virtual 8-device CPU mesh (the analog of
the reference's mpirun -n 2 CI leg; see SURVEY.md §4): the sharded train step
must agree with the single-device step, and the full DP loop must train."""

import numpy as np
import jax
import jax.numpy as jnp

from hydragnn_tpu.graph.batch import GraphSample, HeadSpec, PadSpec, collate
from hydragnn_tpu.graph.neighborlist import radius_graph
from hydragnn_tpu.models.base import GraphHeadCfg, ModelConfig, NodeHeadCfg
from hydragnn_tpu.models.create import create_model
from hydragnn_tpu.parallel.mesh import (
    DeviceStackLoader,
    make_dp_eval_step,
    make_dp_train_step,
    make_mesh,
    replicate_state,
    stack_batches,
)
from hydragnn_tpu.train.optimizer import select_optimizer
from hydragnn_tpu.train.trainer import (
    create_train_state,
    make_eval_step,
    make_train_step,
)


def _make_batches(n_batches, batch_size=4, nodes=8, seed=0):
    rng = np.random.RandomState(seed)
    heads = [HeadSpec("energy", "graph", 1), HeadSpec("f", "node", 1)]
    out = []
    for _ in range(n_batches):
        samples = []
        for _ in range(batch_size):
            pos = rng.rand(nodes, 3).astype(np.float32) * 2.0
            x = rng.rand(nodes, 1).astype(np.float32)
            ei = radius_graph(pos, 1.2, 10)
            samples.append(GraphSample(
                x=x, pos=pos, edge_index=ei,
                graph_y=x.sum(keepdims=True)[0],
                node_y=np.concatenate([x.sum() * np.ones_like(x), x], 1)))
        pad = PadSpec.for_batch(batch_size, nodes, 80)
        out.append(collate(samples, pad, heads,
                           [(0, 1), (0, 0)], [(0, 0), (1, 2)]))
    return out, heads


def _cfg():
    return ModelConfig(
        model_type="SAGE", input_dim=1, hidden_dim=8,
        output_dim=(1, 1), output_type=("graph", "node"),
        graph_head=GraphHeadCfg(1, 8, 1, (8,)),
        node_head=NodeHeadCfg(1, (8,), "mlp"),
        task_weights=(1.0, 1.0), num_conv_layers=2)


def test_dp_matches_single_device():
    """One DP step over 8 devices with the SAME per-device batch must equal
    the single-device step on that batch (gradient pmean of identical grads)."""
    n_dev = len(jax.devices())
    assert n_dev == 8, "conftest must provide 8 virtual devices"
    mesh = make_mesh()
    cfg = _cfg()
    model = create_model(cfg)
    opt = select_optimizer({"type": "SGD", "learning_rate": 0.05})
    (batch,), _ = (lambda t: (t[0], t[1]))(_make_batches(1))

    state_single = create_train_state(model, batch, opt, seed=0)
    state_dp = replicate_state(
        create_train_state(model, batch, opt, seed=0), mesh)

    single_step = jax.jit(make_train_step(model, cfg, opt))
    dp_step = make_dp_train_step(model, cfg, opt, mesh)

    state_single, m1 = single_step(state_single, batch)
    state_dp, m2 = dp_step(state_dp, stack_batches([batch] * n_dev))

    assert np.isclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(state_single.params),
                    jax.tree.leaves(state_dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_lr_change_keeps_the_dp_step_compiled():
    """The plateau scheduler rewrites the learning rate between epochs:
    the new leaf must keep the replicated placement of the one it
    replaces, or the mesh step compiles a second time in mid-run (it did,
    11 s on four chips, the first time the scheduler fired inside a
    benchmark window)."""
    from hydragnn_tpu.train.optimizer import (
        get_learning_rate, set_learning_rate)

    n_dev = len(jax.devices())
    mesh = make_mesh()
    cfg = _cfg()
    model = create_model(cfg)
    opt = select_optimizer({"type": "AdamW", "learning_rate": 0.01})
    (batch,), _ = (lambda t: (t[0], t[1]))(_make_batches(1))
    stacked = stack_batches([batch] * n_dev)
    state = replicate_state(
        create_train_state(model, batch, opt, seed=0), mesh)
    dp_step = make_dp_train_step(model, cfg, opt, mesh)
    state, _ = dp_step(state, stacked)
    state, _ = dp_step(state, stacked)
    old = state.opt_state.hyperparams["learning_rate"]

    state = state.replace(opt_state=set_learning_rate(state.opt_state, 5e-3))
    new = state.opt_state.hyperparams["learning_rate"]
    assert new.sharding == old.sharding and new.dtype == old.dtype
    assert get_learning_rate(state.opt_state) == np.float32(5e-3)

    compiles = []

    def on_duration(name, *_a, **_k):
        if name.endswith("backend_compile_duration"):
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        state, m = dp_step(state, stacked)
    finally:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(on_duration)
    assert np.isfinite(float(m["loss"]))
    assert compiles == [], compiles


def test_dp_training_loop_converges():
    """Run ~40 DP steps over distinct per-device batches; loss must drop."""
    n_dev = len(jax.devices())
    mesh = make_mesh()
    cfg = _cfg()
    model = create_model(cfg)
    opt = select_optimizer({"type": "AdamW", "learning_rate": 0.01})
    batches, _ = _make_batches(n_dev * 5, seed=3)

    state = replicate_state(
        create_train_state(model, batches[0], opt, seed=0), mesh)
    dp_step = make_dp_train_step(model, cfg, opt, mesh)

    losses = []
    for epoch in range(8):
        for i in range(5):
            stacked = stack_batches(batches[i * n_dev:(i + 1) * n_dev])
            state, m = dp_step(state, stacked)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_dp_eval_matches_single():
    n_dev = len(jax.devices())
    mesh = make_mesh()
    cfg = _cfg()
    model = create_model(cfg)
    opt = select_optimizer({"type": "AdamW", "learning_rate": 0.01})
    batches, _ = _make_batches(n_dev, seed=5)
    state = create_train_state(model, batches[0], opt, seed=0)

    eval_single = jax.jit(make_eval_step(model, cfg))
    eval_dp = make_dp_eval_step(model, cfg, mesh)

    # per-batch average of single-device losses weighted by graphs
    tot, n = 0.0, 0.0
    for b in batches:
        m = eval_single(state, b)
        tot += float(m["loss"]) * float(m["num_graphs"])
        n += float(m["num_graphs"])
    expected = tot / n

    m = eval_dp(replicate_state(state, mesh), stack_batches(batches))
    got = float(m["loss"])  # pmean over devices (equal num_graphs per device)
    assert np.isclose(expected, got, rtol=1e-5)
    # stacked outputs cover every device's batch
    assert np.asarray(m["outputs"][0]).shape[0] == n_dev


def test_device_stack_loader():
    from hydragnn_tpu.data.dataloader import GraphDataLoader

    batches, heads = _make_batches(1)
    rng = np.random.RandomState(0)
    samples = []
    for _ in range(50):
        pos = rng.rand(8, 3).astype(np.float32) * 2.0
        x = rng.rand(8, 1).astype(np.float32)
        samples.append(GraphSample(
            x=x, pos=pos, edge_index=radius_graph(pos, 1.2, 10),
            graph_y=x.sum(keepdims=True)[0],
            node_y=np.concatenate([x.sum() * np.ones_like(x), x], 1)))
    loader = GraphDataLoader(
        samples, heads, batch_size=4, shuffle=True,
        graph_feature_slices=[(0, 1), (0, 0)],
        node_feature_slices=[(0, 0), (1, 2)])
    stacked_loader = DeviceStackLoader(loader, 8, drop_last=False)
    seen = 0
    for g in stacked_loader:
        assert g.x.shape[0] == 8  # leading device axis
        seen += float(np.asarray(g.graph_mask).sum())
    assert seen == 50  # wrap-padding keeps every sample exactly once
