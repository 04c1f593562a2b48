"""The LFM2-MoE stack against its plain float32 reference, at a small size
on the CPU: loss and every parameter's gradient on seeded weights and a
seeded expert bias, composed (dense attention, ``ragged_dot``) and as the
chip runs it (kernels interpreted); the tied table's gradient is the sum of
both uses; the bias steps by its speed and carries no gradient, an eval
step leaves it; **the shares add up**: all the expert shares of an expert
layer, operator and router counted once, equal the uncut reference's
layer; a node none of whose experts is held gets the residual alone; the
step's ``sconv`` counters; what the config refuses; the reference's two
copies; and the JSON entry point."""

import filecmp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.graph.batch import GraphSample, HeadSpec, PadSpec, collate
from hydragnn_tpu.models import lfm2_moe_reference as R
from hydragnn_tpu.models.base import ModelConfig
from hydragnn_tpu.models.create import create_model
from hydragnn_tpu.models.sequence import BIAS_UPDATE_SPEED
from hydragnn_tpu.models.lfm2_moe import Experts, Lfm2MoeConfig
from hydragnn_tpu.parallel.share import LayerShare
from hydragnn_tpu.train.trainer import _loss_and_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one dense conv layer, one attention layer and one conv layer with experts
LM = {
    "model_type": "lfm2_moe", "vocab_size": 64, "hidden_size": 32,
    "intermediate_size": 48, "moe_intermediate_size": 24,
    "num_hidden_layers": 3,
    "layer_types": ["conv", "full_attention", "conv"],
    "num_dense_layers": 1, "conv_L_cache": 3, "conv_bias": False,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_experts": 4, "num_experts_per_tok": 3, "use_expert_bias": True,
    "norm_topk_prob": True, "routed_scaling_factor": 1.0, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
SHARE = {"num_experts_total": 16, "expert_offset": 4, "vocab_total": 512,
         "vocab_offset": 0}
DOC_LENGTHS = (5, 20, 1, 2, 12, 9)      # a one-node and a two-node graph
LONGEST = max(DOC_LENGTHS)
EXPERT_LAYERS = ("layer_1", "layer_2")
HEADS = [HeadSpec("next", "node", 1)]


def nn_section(dtype="float32", lm=LM, share=SHARE):
    return {
        "Architecture": {
            "model_type": "Lfm2Moe", "hidden_dim": lm["hidden_size"],
            "num_conv_layers": lm["num_hidden_layers"], "input_dim": 1,
            "output_dim": [1], "output_type": ["node"],
            "task_weights": [1.0], "compute_dtype": dtype,
            "lfm2_moe": lm, "share": share, "max_graph_nodes": 24,
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
    b = collate([sample(d) for d in docs], PadSpec(56, 8, 7), HEADS)
    return jax.tree.map(jnp.asarray, b)


def seeded(model, batch):
    """Initial variables with a seeded, non-zero bias on every expert layer
    and the norms' scales moved off one."""
    variables = model.init({"params": jax.random.PRNGKey(1)}, batch,
                           train=False)
    stats = dict(variables["batch_stats"])
    keys = jax.random.split(jax.random.PRNGKey(9), len(EXPERT_LAYERS))
    for name, key in zip(EXPERT_LAYERS, keys):
        assert stats[f"bias_{name}"].shape == (16,)
        assert not np.any(np.asarray(stats[f"bias_{name}"]))
        stats[f"bias_{name}"] = 0.3 * jax.random.normal(key, (16,))
    assert "bias_layer_0" not in stats          # the dense layer has none
    leaves, tree = jax.tree_util.tree_flatten_with_path(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    moved = [leaf + 0.1 * jax.random.normal(k, leaf.shape)
             if "norm" in str(path[-1].key) else leaf
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


def test_config_reads_the_catalogs_keys_and_refuses_other_forms():
    lm = Lfm2MoeConfig.from_arch({"lfm2_moe": LM, "max_graph_nodes": 24})
    assert lm.head_dim == 8 and lm.rope_theta == 1e6
    assert lm.layer_types == ("conv", "full_attention", "conv")
    assert lm.expert_layers == EXPERT_LAYERS
    assert Lfm2MoeConfig.from_arch(
        {"lfm2_moe": dict(LM, head_dim=16)}).head_dim == 16
    for key, bad in (("conv_bias", True), ("use_expert_bias", False),
                     ("tie_word_embeddings", False)):
        with pytest.raises(ValueError, match="not implemented"):
            Lfm2MoeConfig.from_arch({"lfm2_moe": dict(LM, **{key: bad})})
    with pytest.raises(ValueError, match="rope_type"):
        Lfm2MoeConfig.from_arch({"lfm2_moe": dict(LM, rope_parameters={
            "rope_theta": 1e6, "rope_type": "yarn"})})
    for bad in (["conv", "conv"], ["conv", "mamba", "conv"]):
        with pytest.raises(ValueError, match="layer_types"):
            Lfm2MoeConfig.from_arch({"lfm2_moe": dict(LM, layer_types=bad)})


@pytest.mark.parametrize("backends", [
    ("dense", "ragged_dot", False), ("splash", "gmm", True)],
    ids=["composed", "as_on_the_chip_interpreted"])
def test_loss_and_every_gradient_leaf_match_the_reference(
        docs, batch, backends):
    cfg = ModelConfig.from_config(nn_section())
    ab, mb, interpret = backends
    model = create_model(cfg).clone(
        attention_backend=ab, moe_backend=mb, interpret=interpret)
    params, stats = seeded(model, batch)
    # ONE table: there is no head matrix
    assert set(params) == {"embed", "layer_0", "layer_1", "layer_2",
                           "final_norm"}
    assert set(params["layer_0"]) == {"op", "ffn"}
    assert set(params["layer_0"]["op"]) == {"norm", "w_in", "conv_w",
                                            "w_out"}
    assert params["layer_0"]["op"]["w_in"].shape == (32, 96)
    assert params["layer_0"]["op"]["conv_w"].shape == (3, 32)
    assert set(params["layer_1"]["op"]) == {"norm", "wq", "wk", "wv",
                                            "q_norm", "k_norm", "wo"}
    assert params["layer_1"]["op"]["q_norm"].shape == (8,)
    assert set(params["layer_2"]["moe"]) == {
        "norm", "router", "experts_w1", "experts_w3", "experts_w2"}
    loss, grads, new_stats = loss_and_grads(model, cfg, params, stats, batch)
    ref_loss, ref_grads = R.loss_and_grads(
        params, LM, SHARE, biases_of(stats), docs, pad_to=lambda n: LONGEST)
    assert abs(loss - ref_loss) <= 1e-5 * ref_loss
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    ref = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert got.keys() == ref.keys() and len(got) == 2 + 8 + 12 + 9
    for path, r in ref.items():
        dev = float(jnp.linalg.norm(got[path] - r)
                    / (jnp.linalg.norm(r) + 1e-12))
        assert dev < 3e-5, (jax.tree_util.keystr(path), dev)
    # the step's counters: every real node's k slots on both expert layers
    nodes = sum(DOC_LENGTHS)
    assert float(new_stats["moe_slots_all"]) == 2 * nodes * 3
    assert 0 < float(new_stats["moe_slots_held"]) < float(
        new_stats["moe_slots_all"])
    assert float(new_stats["moe_dense_steps"]) == 0.0
    assert float(new_stats["moe_load_all_max_over_mean"]) >= 1.0
    assert float(new_stats["attn_blocks_band"]) > 0
    # two conv layers: the real rows, a start a graph, 2 taps cut by the
    # one-node graph and 3 by every longer one
    assert float(new_stats["sconv_rows"]) == 2 * nodes
    assert float(new_stats["sconv_starts"]) == 2 * len(DOC_LENGTHS)
    assert float(new_stats["sconv_taps_cut"]) == 2 * (2 + 5 * 3)
    # the bias stepped by exactly its speed, up or down, on every layer
    for name in EXPERT_LAYERS:
        step = np.abs(np.asarray(
            new_stats[f"bias_{name}"] - stats[f"bias_{name}"]))
        assert np.allclose(step[step > 0], BIAS_UPDATE_SPEED, atol=1e-7)
        assert (step > 0).sum() >= 12, name


def test_the_tied_tables_gradient_is_the_sum_of_both_uses(docs, batch):
    """The table as the embedding alone and as the head alone, each through
    the reference with the other use cut off by ``stop_gradient``: the
    program's one gradient is their sum."""
    cfg = ModelConfig.from_config(nn_section())
    model = create_model(cfg)
    params, stats = seeded(model, batch)
    _loss, grads, _ = loss_and_grads(model, cfg, params, stats, batch)
    count = sum(len(d) - 1 for d in docs)
    pieces = R.document_pieces(LM, SHARE)

    def nll(table_in, table_out):
        total = 0.0
        for d in docs:
            if len(d) < 2:
                continue
            # every document padded (masked) to one length: one shape
            ids = jnp.asarray(np.concatenate(
                [d, np.full(LONGEST - len(d), d[-1])]), jnp.int32)
            x = table_in[ids]
            for i in range(3):
                x = pieces["layer"](params[f"layer_{i}"], x,
                                    biases_of(stats).get(f"layer_{i}"))
            total = total + pieces["next"](x, params["final_norm"],
                                           table_out, ids, len(d))
        return total / count

    with jax.default_matmul_precision("highest"):
        as_embedding, as_head = jax.grad(nll, argnums=(0, 1))(
            params["embed"], params["embed"])
    assert float(jnp.linalg.norm(as_embedding)) > 1e-3
    assert float(jnp.linalg.norm(as_head)) > 1e-3
    np.testing.assert_allclose(grads["embed"], as_embedding + as_head,
                               atol=2e-6)


def test_no_gradient_reaches_the_bias_and_an_eval_step_leaves_it(batch):
    cfg = ModelConfig.from_config(nn_section())
    model = create_model(cfg)
    params, stats = seeded(model, batch)

    def loss_of_bias(b):
        return _loss_and_metrics(model, cfg, params,
                                 dict(stats, bias_layer_1=b), batch, True)[0]

    assert not np.any(np.asarray(jax.grad(loss_of_bias)(
        stats["bias_layer_1"])))
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
    model = create_model(cfg)
    params, stats = seeded(model, batch)
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(params))
    loss, grads, _ = loss_and_grads(model, cfg, params, stats, batch)
    ref_loss, ref_grads = R.loss_and_grads(
        params, LM, SHARE, biases_of(stats), docs, pad_to=lambda n: LONGEST)
    assert abs(loss - ref_loss) < 0.02 * ref_loss
    g = jnp.concatenate([a.ravel() for a in jax.tree.leaves(grads)])
    r = jnp.concatenate([a.ravel() for a in jax.tree.leaves(ref_grads)])
    assert g.dtype == jnp.float32
    dev = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
    assert 1e-4 < dev < 0.08      # rounded, and no more than rounded


# -- the shares add up --------------------------------------------------------

def _expert_layer(key):
    """Seeded parameters of one UNCUT expert half, as the reference names
    them: 16 experts."""
    d, f, k = 32, 24, iter(jax.random.split(key, 6))

    def normal(*shape, fan):
        return jax.random.normal(next(k), shape) * fan ** -0.5

    return {"norm": 1.0 + 0.1 * jax.random.normal(next(k), (d,)),
            "router": normal(d, 16, fan=d),
            "experts_w1": normal(16, d, f, fan=d),
            "experts_w3": normal(16, d, f, fan=d),
            "experts_w2": normal(16, f, d, fan=f)}


def _run_experts(p, h, mask, share, bias):
    """The expert half of the PROGRAM on the packed batch, from
    reference-named parameters."""
    held = dict(LM, num_experts=p["experts_w1"].shape[0])
    lm = Lfm2MoeConfig.from_arch({"lfm2_moe": held, "max_graph_nodes": 24})
    y, stats = Experts(lm, share, jnp.float32, "ragged_dot", False).apply(
        {"params": p}, h, mask, bias)
    return y, stats


@pytest.fixture(scope="module")
def packed(batch):
    h = jax.random.normal(jax.random.PRNGKey(4), (56, 32))
    real = sum(DOC_LENGTHS)
    offs = np.concatenate([[0], np.cumsum(DOC_LENGTHS)])
    return h, [h[a:b] for a, b in zip(offs[:-1], offs[1:])], real, batch


def test_all_expert_shares_of_an_expert_layer_add_up(packed):
    """Four ranks of four experts each, the router (whole on every rank)
    deciding once: their parts are the uncut reference's layer.  There is
    no shared expert to count once."""
    h, h_docs, real, batch = packed
    p = _expert_layer(jax.random.PRNGKey(13))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(14), (16,))
    cfg = dict(LM, num_experts=16)
    with jax.default_matmul_precision("highest"):
        # the expert half reads one row at a time: the documents at once
        want = R.moe(p, cfg, R.whole_share(cfg),
                     R.rms_norm(jnp.concatenate(h_docs), p["norm"], 1e-5),
                     bias)
        got, held = 0.0, 0.0
        for rank in range(4):
            part = {k: (v[4 * rank:4 * rank + 4]
                        if k.startswith("experts_") else v)
                    for k, v in p.items()}
            y, stats = _run_experts(
                part, h, batch.node_mask,
                LayerShare(16, 4, 4 * rank, 2, 2, 0, 512, 64, 0), bias)
            got = got + y[:real]
            held += float(stats["slots_held"])
            # one share alone is NOT the layer
            assert float(jnp.max(jnp.abs(y[:real] - want))) > 0.05
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    assert held == real * 3          # every slot fell on exactly one rank


def test_a_node_with_no_held_expert_gets_the_residual_alone(batch):
    """A bias that sends every node to experts 0-2 of 16, a share that
    holds 4-7: the expert half adds exact zeros, in the program and in the
    reference, and the layer's second half is the residual."""
    cfg = ModelConfig.from_config(nn_section())
    model = create_model(cfg)
    params, stats = seeded(model, batch)
    away = jnp.where(jnp.arange(16) < 3, 100.0, 0.0)
    h = jax.random.normal(jax.random.PRNGKey(5), (56, 32))
    y, s = _run_experts(params["layer_2"]["moe"], h, batch.node_mask,
                        cfg.share, away)
    assert float(s["slots_held"]) == 0.0
    assert not np.any(np.asarray(y))
    u = R.rms_norm(h[:5], params["layer_2"]["moe"]["norm"], 1e-5)
    assert not np.any(np.asarray(
        R.moe(params["layer_2"]["moe"], LM, SHARE, u, away)))
    # ... and a bias towards the held ones gives every node something
    here = jnp.where((jnp.arange(16) >= 4) & (jnp.arange(16) < 7), 100.0, 0.0)
    y, s = _run_experts(params["layer_2"]["moe"], h, batch.node_mask,
                        cfg.share, here)
    real = sum(DOC_LENGTHS)
    assert float(s["slots_held"]) == 3 * real
    assert np.all(np.any(np.asarray(y)[:real] != 0, axis=1))
    assert not np.any(np.asarray(y)[real:])       # padding rows: nothing


def test_reference_copy_under_benchmark_is_byte_identical():
    assert filecmp.cmp(
        os.path.join(REPO, "hydragnn_tpu/models/lfm2_moe_reference.py"),
        os.path.join(REPO, "benchmark/reference/lfm2_moe_reference.py"),
        shallow=False)
    assert len(R.ASSUMED) >= 6


def test_json_config_trains_through_run_training(tmp_path, monkeypatch):
    """``model_type: "Lfm2Moe"`` through ``run_training`` on the normal
    path: token files, the loader, buckets, the resident scan-K trainer
    (on the test session's eight host devices: the DP mesh; the step
    records' ``sconv`` block is held in tests/test_telemetry.py)."""
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
                "model_type": "Lfm2Moe", "hidden_dim": 32,
                "num_conv_layers": 3, "lfm2_moe": LM, "share": SHARE,
                "output_heads": {}, "task_weights": [1.0]},
            "Variables_of_interest": {
                "input_node_features": [0], "output_index": [1],
                "type": ["node"], "output_names": ["next_token_id"],
                "denormalize_output": False},
            "Training": {
                "num_epoch": 6, "batch_size": 4, "perc_train": 0.8,
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
