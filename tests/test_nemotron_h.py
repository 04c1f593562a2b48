"""The Nemotron-H stack against its plain float32 reference, at a small
size on the CPU: loss and every parameter group's gradient on seeded
weights and a seeded correction bias, with the pattern's repeated pairs
scanned, composed (sequential scan, dense attention, ``ragged_dot``) and as
the chip runs it (chunked scan, kernels interpreted); the bias steps by its
speed and carries no gradient, an eval step leaves it; **the shares add
up**: all the group shares of an ``M`` layer, both head shares of the
attention layer and all the expert shares of an ``E`` layer, the shared
expert counted once, equal the uncut reference's layer; the pattern's
segments; the step's ``ssm`` counters; the reference's two copies; and the
JSON entry point."""

import filecmp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.graph.batch import GraphSample, HeadSpec, PadSpec, collate
from hydragnn_tpu.models import nemotron_h_reference as R
from hydragnn_tpu.models.base import ModelConfig
from hydragnn_tpu.models.create import create_model
from hydragnn_tpu.models.sequence import BIAS_UPDATE_SPEED
from hydragnn_tpu.models.nemotron_h import (
    NemotronHConfig,
    layer_trees,
    segment_name,
    segments,
)
from hydragnn_tpu.parallel.share import LayerShare
from hydragnn_tpu.train.trainer import _loss_and_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PATTERN = "EMEM*"
LM = {
    "model_type": "nemotron_h", "vocab_size": 64, "hidden_size": 32,
    "hybrid_override_pattern": PATTERN, "num_hidden_layers": 5,
    "layer_norm_epsilon": 1e-5, "mamba_num_heads": 2, "mamba_head_dim": 8,
    "n_groups": 1, "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 8,
    "n_routed_experts": 4, "num_experts_per_tok": 5,
    "moe_intermediate_size": 24, "moe_latent_size": 16,
    "moe_shared_expert_intermediate_size": 40, "norm_topk_prob": True,
    "routed_scaling_factor": 5.0, "n_group": 1, "topk_group": 1,
    "mlp_hidden_act": "relu2", "num_nextn_predict_layers": 0}
SHARE = {"num_experts_total": 16, "expert_offset": 4, "vocab_total": 512,
         "vocab_offset": 0, "kv_heads_total": 2, "kv_head_offset": 0,
         "ssm_heads_total": 8, "ssm_groups_total": 4}
DOC_LENGTHS = (5, 20, 1, 12, 9)     # boundaries inside chunks; one node
EXPERT_LAYERS = ("layer_0", "layer_2")
HEADS = [HeadSpec("next", "node", 1)]


def nn_section(dtype="float32", lm=LM, share=SHARE):
    return {
        "Architecture": {
            "model_type": "NemotronH", "hidden_dim": lm["hidden_size"],
            "num_conv_layers": lm["num_hidden_layers"], "input_dim": 1,
            "output_dim": [1], "output_type": ["node"],
            "task_weights": [1.0], "compute_dtype": dtype,
            "nemotron_h": lm, "share": share, "max_graph_nodes": 24,
            "output_heads": {}},
        "Training": {"loss_function_type": "softmax_xent"}}


def sample(ids):
    ids = np.asarray(ids)
    nxt = np.concatenate([ids[1:], [-1]])
    return GraphSample(x=ids.astype(np.float32)[:, None],
                       pos=np.zeros((len(ids), 3)),
                       node_y=nxt.astype(np.float32)[:, None])


@pytest.fixture(scope="module")
def docs():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 64, size=n) for n in DOC_LENGTHS]


@pytest.fixture(scope="module")
def batch(docs):
    b = collate([sample(d) for d in docs], PadSpec(56, 8, 6), HEADS)
    return jax.tree.map(jnp.asarray, b)


def seeded(model, batch):
    """Initial variables with a seeded, non-zero bias on every expert layer
    and the small parameters moved off their constant starts."""
    variables = model.init({"params": jax.random.PRNGKey(1)}, batch,
                           train=False)
    stats = dict(variables["batch_stats"])
    keys = jax.random.split(jax.random.PRNGKey(9), len(EXPERT_LAYERS))
    for name, key in zip(EXPERT_LAYERS, keys):
        assert stats[f"bias_{name}"].shape == (16,)
        assert not np.any(np.asarray(stats[f"bias_{name}"]))
        stats[f"bias_{name}"] = 0.3 * jax.random.normal(key, (16,))
    leaves, tree = jax.tree_util.tree_flatten_with_path(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    moved = [leaf + 0.1 * jax.random.normal(k, leaf.shape)
             if str(path[-1].key) in ("norm", "gate_norm", "conv_b", "D",
                                      "final_norm") else leaf
             for (path, leaf), k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, moved), stats


def loss_and_grads(model, cfg, params, stats, batch, train=True):
    def loss_fn(p):
        return _loss_and_metrics(model, cfg, p, stats, batch, train)

    (loss, (_heads, new_stats, _out)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params)
    return float(loss), grads, new_stats


def biases_of(stats):
    return {name: stats[f"bias_{name}"] for name in EXPERT_LAYERS}


def test_the_pattern_is_cut_into_scanned_runs_and_single_layers():
    assert segments("EMEMEMEMEM*") == ((0, "EM", 5), (10, "*", 1))
    assert segments(PATTERN) == ((0, "EM", 2), (4, "*", 1))
    assert segments("MEM*") == ((0, "M", 1), (1, "E", 1), (2, "M", 1),
                                (3, "*", 1))
    assert segments("MM**") == ((0, "M", 2), (2, "*", 1), (3, "*", 1))
    assert segment_name(0, "EM", 5) == "layers_0_9"
    assert segment_name(10, "*", 1) == "layer_10"
    for bad in ("", "EMX"):
        with pytest.raises(ValueError, match="hybrid_override_pattern"):
            NemotronHConfig.from_arch(
                {"nemotron_h": dict(LM, hybrid_override_pattern=bad)})
    with pytest.raises(ValueError, match="num_hidden_layers"):
        NemotronHConfig.from_arch(
            {"nemotron_h": dict(LM, num_hidden_layers=4)})
    with pytest.raises(ValueError, match="not implemented"):
        NemotronHConfig.from_arch(
            {"nemotron_h": dict(LM, num_nextn_predict_layers=1)})


@pytest.mark.parametrize("backends", [
    ("dense", "ragged_dot", "sequential", False),
    ("splash", "gmm", "chunked", True)],
    ids=["composed", "as_on_the_chip_interpreted"])
def test_loss_and_every_gradient_leaf_match_the_reference(
        docs, batch, backends):
    cfg = ModelConfig.from_config(nn_section())
    ab, mb, sb, interpret = backends
    model = create_model(cfg).clone(
        attention_backend=ab, moe_backend=mb, ssm_backend=sb,
        interpret=interpret)
    params, stats = seeded(model, batch)
    # the two EM pairs are ONE scanned module over stacked leaves
    assert set(params) == {"embed", "layers_0_3", "layer_4", "final_norm",
                           "head"}
    assert params["layers_0_3"]["unit_0"]["experts_w1"].shape == (
        2, 4, 16, 24)
    assert params["layers_0_3"]["unit_1"]["in_proj"].shape == (
        2, 32, 2 * 16 + 2 * 16 + 2)
    loss, grads, new_stats = loss_and_grads(model, cfg, params, stats, batch)
    ref_loss, ref_grads = R.loss_and_grads(
        layer_trees(params, PATTERN), LM, SHARE, biases_of(stats), docs)
    assert abs(loss - ref_loss) <= 1e-5 * ref_loss
    got = dict(jax.tree_util.tree_leaves_with_path(
        layer_trees(grads, PATTERN)))
    ref = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert got.keys() == ref.keys() and len(got) == 2 * 8 + 2 * 9 + 5 + 3
    for path, r in ref.items():
        dev = float(jnp.linalg.norm(got[path] - r)
                    / (jnp.linalg.norm(r) + 1e-12))
        assert dev < 3e-5, (jax.tree_util.keystr(path), dev)
    # the step's counters: every real node's k slots on both expert layers
    nodes = sum(DOC_LENGTHS)
    assert float(new_stats["moe_slots_all"]) == 2 * nodes * 5
    assert 0 < float(new_stats["moe_slots_held"]) < float(
        new_stats["moe_slots_all"])
    assert float(new_stats["moe_dense_steps"]) == 0.0
    assert float(new_stats["moe_load_all_max_over_mean"]) >= 1.0
    assert float(new_stats["ssm_chunks"]) == 4.0          # 56 -> 64 / 16
    assert float(new_stats["ssm_chunks_padding"]) == 1.0  # 47 real nodes
    assert float(new_stats["ssm_resets"]) == len(DOC_LENGTHS)
    assert float(new_stats["attn_blocks_band"]) > 0
    # the bias stepped by exactly its speed, up or down, on every layer
    for name in EXPERT_LAYERS:
        step = np.abs(np.asarray(
            new_stats[f"bias_{name}"] - stats[f"bias_{name}"]))
        assert np.allclose(step[step > 0], BIAS_UPDATE_SPEED, atol=1e-7)
        assert (step > 0).sum() >= 12, name


def test_no_gradient_reaches_the_bias_and_an_eval_step_leaves_it(batch):
    cfg = ModelConfig.from_config(nn_section())
    model = create_model(cfg)
    params, stats = seeded(model, batch)

    def loss_of_bias(b):
        return _loss_and_metrics(model, cfg, params,
                                 dict(stats, bias_layer_0=b), batch, True)[0]

    assert not np.any(np.asarray(jax.grad(loss_of_bias)(
        stats["bias_layer_0"])))
    _l, _g, after_eval = loss_and_grads(model, cfg, params, stats, batch,
                                        train=False)
    for key, value in stats.items():
        assert np.array_equal(np.asarray(after_eval[key]),
                              np.asarray(value)), key
    # ... and it READ it: another bias, another loss
    zero = {k: jnp.zeros_like(v) for k, v in stats.items()}
    a = loss_and_grads(model, cfg, params, stats, batch, train=False)[0]
    b = loss_and_grads(model, cfg, params, zero, batch, train=False)[0]
    assert a != b


def test_bfloat16_products_stay_near_the_reference(docs, batch):
    cfg = ModelConfig.from_config(nn_section("bfloat16"))
    model = create_model(cfg).clone(ssm_backend="chunked")
    params, stats = seeded(model, batch)
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(params))
    loss, grads, _ = loss_and_grads(model, cfg, params, stats, batch)
    ref_loss, ref_grads = R.loss_and_grads(
        layer_trees(params, PATTERN), LM, SHARE, biases_of(stats), docs)
    assert abs(loss - ref_loss) < 0.02 * ref_loss
    g = jnp.concatenate([a.ravel() for a in jax.tree.leaves(
        layer_trees(grads, PATTERN))])
    r = jnp.concatenate([a.ravel() for a in jax.tree.leaves(ref_grads)])
    assert g.dtype == jnp.float32
    dev = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
    assert 1e-4 < dev < 0.08      # rounded, and no more than rounded


# -- the shares add up --------------------------------------------------------

WHOLE = dict(LM, mamba_num_heads=8, n_groups=4, num_attention_heads=4,
             num_key_value_heads=2, n_routed_experts=16)


def _whole_layer(kind, key):
    """Seeded parameters of one UNCUT layer, as the reference names them."""
    d, k = 32, iter(jax.random.split(key, 12))

    def normal(*shape, fan):
        return jax.random.normal(next(k), shape) * fan ** -0.5

    norm = 1.0 + 0.1 * jax.random.normal(next(k), (d,))
    if kind == "M":
        heads, hd, groups, state = 8, 8, 4, 16
        inner, conv = heads * hd, heads * hd + 2 * groups * state
        return {"norm": norm, "in_proj": normal(d, inner + conv + heads,
                                                fan=d),
                "conv_w": normal(4, conv, fan=4), "conv_b": normal(conv,
                                                                   fan=4),
                "A_log": jnp.log(jax.random.uniform(next(k), (heads,),
                                                    minval=1.0, maxval=16.0)),
                "D": 1.0 + normal(heads, fan=4),
                "dt_bias": normal(heads, fan=1) - 2.0,
                "gate_norm": 1.0 + normal(inner, fan=100),
                "out_proj": normal(inner, d, fan=inner)}
    if kind == "*":
        return {"norm": norm, "wq": normal(d, 4 * 8, fan=d),
                "wk": normal(d, 2 * 8, fan=d), "wv": normal(d, 2 * 8, fan=d),
                "wo": normal(4 * 8, d, fan=32)}
    return {"norm": norm, "router": normal(d, 16, fan=d),
            "down": normal(d, 16, fan=d),
            "experts_w1": normal(16, 16, 24, fan=16),
            "experts_w2": normal(16, 24, 16, fan=24),
            "up": normal(16, d, fan=16), "shared_w1": normal(d, 40, fan=d),
            "shared_w2": normal(40, d, fan=40)}


def _mamba_share(p, rank, ranks=4):
    """Group ``rank``'s columns of an uncut Mamba-2 layer: its heads' z, x
    and dt, its own B and C, its channels of the conv and of the gated
    norm, its rows of the output product."""
    heads, hd, groups, state = 8, 8, 4, 16
    inner, per = heads * hd, heads // ranks
    z = np.arange(rank * per * hd, (rank + 1) * per * hd)
    gs = groups // ranks * state
    conv = np.concatenate([
        z, inner + np.arange(rank * gs, (rank + 1) * gs),
        inner + groups * state + np.arange(rank * gs, (rank + 1) * gs)])
    dt = np.arange(rank * per, (rank + 1) * per)
    cols = np.concatenate([z, inner + conv,
                           2 * inner + 2 * groups * state + dt])
    return {"norm": p["norm"], "in_proj": p["in_proj"][:, cols],
            "conv_w": p["conv_w"][:, conv], "conv_b": p["conv_b"][conv],
            "A_log": p["A_log"][dt], "D": p["D"][dt],
            "dt_bias": p["dt_bias"][dt], "gate_norm": p["gate_norm"][z],
            "out_proj": p["out_proj"][z]}


def _run_layer(kind, p, x, gid, mask, share, bias=None):
    """One layer of the PROGRAM on the packed batch, from reference-named
    parameters: the mixer's part alone (the residual taken off)."""
    from hydragnn_tpu.models.nemotron_h import LAYERS, Backends

    held = dict(LM, mamba_num_heads=p["A_log"].shape[0] if kind == "M"
                else LM["mamba_num_heads"],
                n_groups=(p["conv_w"].shape[1] - p["A_log"].shape[0] * 8)
                // 32 if kind == "M" else LM["n_groups"],
                num_attention_heads=p["wq"].shape[1] // 8 if kind == "*"
                else LM["num_attention_heads"],
                num_key_value_heads=p["wk"].shape[1] // 8 if kind == "*"
                else LM["num_key_value_heads"],
                n_routed_experts=p["experts_w1"].shape[0] if kind == "E"
                else LM["n_routed_experts"])
    lm = NemotronHConfig.from_arch({"nemotron_h": held,
                                    "max_graph_nodes": 24})
    layer = LAYERS[kind](lm, share, jnp.float32,
                         Backends("dense", "ragged_dot", "chunked"))
    y, _stats, _blocks = layer.apply({"params": p}, x, gid, mask, bias)
    return y - x


def _reference_layer(p, x_docs, bias=None):
    """The uncut reference's mixer over each document."""
    cfg = dict(WHOLE)
    return jnp.concatenate([
        R.layer_forward(p, cfg, R.whole_share(cfg), x, bias) - x
        for x in x_docs])


@pytest.fixture(scope="module")
def packed(batch):
    x = jax.random.normal(jax.random.PRNGKey(4), (56, 32))
    real = sum(DOC_LENGTHS)
    offs = np.concatenate([[0], np.cumsum(DOC_LENGTHS)])
    return x, [x[a:b] for a, b in zip(offs[:-1], offs[1:])], real, batch


def test_all_group_shares_of_a_state_space_layer_add_up(packed):
    x, x_docs, real, batch = packed
    p = _whole_layer("M", jax.random.PRNGKey(11))
    with jax.default_matmul_precision("highest"):
        want = _reference_layer(p, x_docs)
        got = sum(_run_layer(
            "M", _mamba_share(p, rank), x, batch.node_gid, batch.node_mask,
            LayerShare(16, 4, 0, 2, 1, 0, 512, 64, 0, ssm_heads_total=8,
                       ssm_heads_held=2, ssm_head_offset=2 * rank,
                       ssm_groups_total=4, ssm_groups_held=1,
                       ssm_group_offset=rank))
            for rank in range(4))
    np.testing.assert_allclose(got[:real], want, rtol=0, atol=2e-5)
    # one share alone is NOT the layer
    assert float(jnp.max(jnp.abs(got[:real] - want))) < 0.01 * float(
        jnp.max(jnp.abs(want)))


def test_both_head_shares_of_the_attention_layer_add_up(packed):
    x, x_docs, real, batch = packed
    p = _whole_layer("*", jax.random.PRNGKey(12))
    with jax.default_matmul_precision("highest"):
        want = _reference_layer(p, x_docs)
        got = 0.0
        for rank in range(2):
            q = np.arange(rank * 16, (rank + 1) * 16)
            kv = np.arange(rank * 8, (rank + 1) * 8)
            part = {"norm": p["norm"], "wq": p["wq"][:, q],
                    "wk": p["wk"][:, kv], "wv": p["wv"][:, kv],
                    "wo": p["wo"][q]}
            got = got + _run_layer(
                "*", part, x, batch.node_gid, batch.node_mask,
                LayerShare(16, 4, 0, 2, 1, rank, 512, 64, 0))
    np.testing.assert_allclose(got[:real], want, rtol=0, atol=2e-5)


def test_all_expert_shares_of_a_latent_expert_layer_add_up(packed):
    """Four ranks of four experts each: their routed parts, each brought
    up from the latent space, and the shared expert ONCE, are the uncut
    reference's layer."""
    x, x_docs, real, batch = packed
    p = _whole_layer("E", jax.random.PRNGKey(13))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(14), (16,))
    with jax.default_matmul_precision("highest"):
        want = _reference_layer(p, x_docs, bias)
        # what every rank computes alike, once
        u = jnp.concatenate([R.rms_norm(d, p["norm"], 1e-5) for d in x_docs])
        shared = R.relu2(u @ p["shared_w1"]) @ p["shared_w2"]
        got = shared
        for rank in range(4):
            part = dict(p, experts_w1=p["experts_w1"][4 * rank:4 * rank + 4],
                        experts_w2=p["experts_w2"][4 * rank:4 * rank + 4])
            y = _run_layer("E", part, x, batch.node_gid, batch.node_mask,
                           LayerShare(16, 4, 4 * rank, 2, 1, 0, 512, 64, 0),
                           bias)
            got = got + (y[:real] - shared)
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-5)
    assert float(jnp.max(jnp.abs(shared - want))) > 0.05


def test_reference_copy_under_benchmark_is_byte_identical():
    assert filecmp.cmp(
        os.path.join(REPO, "hydragnn_tpu/models/nemotron_h_reference.py"),
        os.path.join(REPO, "benchmark/reference/nemotron_h_reference.py"),
        shallow=False)
    assert len(R.ASSUMED) >= 6


def test_json_config_trains_through_run_training(tmp_path, monkeypatch):
    """``model_type: "NemotronH"`` through ``run_training`` on the normal
    path: token files, the loader, buckets, the resident scan-K trainer
    (on the test session's eight host devices: the DP mesh; the step
    records' ``ssm`` block is held in tests/test_telemetry.py)."""
    import hydragnn_tpu

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("SERIALIZED_DATA_PATH", str(tmp_path))
    monkeypatch.setenv("HYDRAGNN_RESIDENT_DATASET", "1")
    rng = np.random.default_rng(1)
    table = rng.integers(0, 64, size=64)
    os.makedirs(tmp_path / "dataset" / "docs_tiny")
    for d, n in enumerate(rng.integers(4, 30, size=80)):
        ids = [int(rng.integers(64))]
        for _ in range(n - 1):       # a fixed successor: learnable
            ids.append(int(table[ids[-1]]))
        (tmp_path / "dataset" / "docs_tiny" / f"doc{d:03d}.txt").write_text(
            " ".join(map(str, ids)))
    config = {
        "Verbosity": {"level": 0},
        "Dataset": {
            "name": "docs_tiny", "format": "tokens",
            "path": {"total": "dataset/docs_tiny"},
            "node_features": {"name": ["token_id", "next_token_id"],
                              "dim": [1, 1], "column_index": [0, 1]}},
        "NeuralNetwork": {
            "Architecture": {
                "model_type": "NemotronH", "hidden_dim": 32,
                "num_conv_layers": 5, "nemotron_h": LM, "share": SHARE,
                "output_heads": {}, "task_weights": [1.0]},
            "Variables_of_interest": {
                "input_node_features": [0], "output_index": [1],
                "type": ["node"], "output_names": ["next_token_id"],
                "denormalize_output": False},
            "Training": {
                "num_epoch": 8, "batch_size": 4, "perc_train": 0.8,
                "loss_function_type": "softmax_xent",
                "Optimizer": {"type": "AdamW", "learning_rate": 3e-3}}},
        "Visualization": {"create_plots": False},
    }
    _state, history, final = hydragnn_tpu.run_training(
        config, logs_dir=str(tmp_path / "logs"))
    train = [float(v) for v in history["train"]]
    assert train[-1] < 0.8 * train[0]
    assert history["pipeline"]["resident"] is True
    assert history["pipeline"]["steps_per_dispatch"] >= 2
    arch = final["NeuralNetwork"]["Architecture"]
    assert arch["max_graph_nodes"] == 29 and arch["output_dim"] == [1]
