"""The Qwen3-Next stack against its plain float32 reference, at a small size
on the CPU: loss and every parameter's gradient on seeded weights, under a
share and uncut, composed (dense attention, ``ragged_dot``, the sequential
rule) and as the chip runs it (kernels interpreted, the chunked rule);
bfloat16 products stay near; **the shares add up**: the four shares of a
4-rank layout, the shared expert under its gate and everything else every
rank computes alike counted once, equal the uncut reference's expert
layer; the parameter count is the formula's; the step's ``gdn`` counters;
what a DeltaNet half keeps; what the config refuses; the reference's two
copies; and the JSON entry point."""

import filecmp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.graph.batch import GraphSample, HeadSpec, PadSpec, collate
from hydragnn_tpu.models import qwen3_next_reference as R
from hydragnn_tpu.models.base import ModelConfig
from hydragnn_tpu.models.create import create_model
from hydragnn_tpu.models.qwen3_next import Qwen3NextConfig
from hydragnn_tpu.models.sequence import MoE
from hydragnn_tpu.parallel.share import LayerShare
from hydragnn_tpu.train.trainer import _loss_and_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one whole period: three DeltaNet layers and one attention layer
# (ONE_LAYER: its first DeltaNet layer alone, where a test asks nothing of
# the others)
LM = {
    "model_type": "qwen3_next", "vocab_size": 64, "hidden_size": 32,
    "intermediate_size": 80, "moe_intermediate_size": 24,
    "shared_expert_intermediate_size": 24, "num_hidden_layers": 4,
    "full_attention_interval": 4, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "rope_scaling": None,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4, "num_experts": 4, "num_experts_per_tok": 3,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "tie_word_embeddings": False, "use_sliding_window": False,
    "linear_chunk_size": 8}
ONE_LAYER = dict(LM, num_hidden_layers=1)
SHARE = {"num_experts_total": 16, "expert_offset": 4, "vocab_total": 512,
         "vocab_offset": 0}
DOC_LENGTHS = (5, 20, 1, 2, 12, 9)      # a one-node and a two-node graph
LONGEST = max(DOC_LENGTHS)
HEADS = [HeadSpec("next", "node", 1)]
CHIP = dict(attention_backend="splash", moe_backend="gmm",
            gdn_backend="chunked", interpret=True)


def nn_section(dtype="float32", lm=LM, share=SHARE):
    return {
        "Architecture": {
            "model_type": "Qwen3Next", "hidden_dim": lm["hidden_size"],
            "num_conv_layers": lm["num_hidden_layers"], "input_dim": 1,
            "output_dim": [1], "output_type": ["node"],
            "task_weights": [1.0], "compute_dtype": dtype,
            "qwen3_next": lm, "share": share, "max_graph_nodes": 24,
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
    """Initial variables with every norm's parameter moved off its start
    (the zero-centred ones off 0, the gated norm off 1)."""
    variables = model.init({"params": jax.random.PRNGKey(1)}, batch,
                           train=False)
    leaves, tree = jax.tree_util.tree_flatten_with_path(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    moved = [leaf + 0.1 * jax.random.normal(k, leaf.shape)
             if "norm" in str(path[-1].key) else leaf
             for (path, leaf), k in zip(leaves, keys)]
    return (jax.tree_util.tree_unflatten(tree, moved),
            variables["batch_stats"])


def loss_and_grads(model, cfg, params, stats, batch, train=True):
    def loss_fn(p):
        return _loss_and_metrics(model, cfg, p, stats, batch, train)

    (loss, (_heads, new_stats, _out)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params)
    return float(loss), grads, new_stats


def worst_leaf(grads, ref_grads):
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    ref = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert got.keys() == ref.keys()
    return max((float(jnp.linalg.norm(got[p] - r)
                      / (jnp.linalg.norm(r) + 1e-12)),
                jax.tree_util.keystr(p)) for p, r in ref.items()), len(got)


def test_config_reads_the_catalogs_keys_and_refuses_other_forms():
    lm = Qwen3NextConfig.from_arch({"qwen3_next": LM, "max_graph_nodes": 24})
    assert lm.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert lm.linear_chunk_size == 8
    assert (lm.head_dim, lm.partial_rotary_factor, lm.rope_theta) == (
        16, 0.25, 1e7)
    assert lm.router_scoring == "softmax"
    assert lm.moe_routed_scaling_factor == 1.0
    # the keys of the program's own may be left out: their defaults hold
    bare = {k: v for k, v in LM.items() if k != "linear_chunk_size"}
    assert Qwen3NextConfig.from_arch(
        {"qwen3_next": bare}).linear_chunk_size == 64
    # a layer_types list, where a config has one, is read as it stands
    listed = dict(LM, layer_types=["full_attention", "linear_attention",
                                   "linear_attention", "full_attention"])
    assert Qwen3NextConfig.from_arch({"qwen3_next": listed}).layer_types[
        0] == "full_attention"
    for key, bad in (("mlp_only_layers", [1]), ("decoder_sparse_step", 2),
                     ("rope_scaling", {"type": "yarn"}),
                     ("tie_word_embeddings", True),
                     ("use_sliding_window", True), ("hidden_act", "gelu"),
                     ("attention_bias", True)):
        with pytest.raises(ValueError, match="not implemented"):
            Qwen3NextConfig.from_arch({"qwen3_next": dict(LM, **{key: bad})})
    with pytest.raises(ValueError, match="layer_types"):
        Qwen3NextConfig.from_arch({"qwen3_next": dict(
            LM, layer_types=["linear_attention", "mamba", "x", "y"])})
    with pytest.raises(ValueError, match="whole groups"):
        Qwen3NextConfig.from_arch({"qwen3_next": dict(
            LM, linear_num_value_heads=3)})


@pytest.mark.parametrize("backends,share", [
    ({}, SHARE), (CHIP, SHARE), ({}, None)],
    ids=["composed", "as_on_the_chip_interpreted", "composed_uncut"])
def test_loss_and_every_gradient_leaf_match_the_reference(
        docs, batch, backends, share):
    lm = LM if share else dict(LM, num_experts=16)
    share = share or R.whole_share(lm)
    cfg = ModelConfig.from_config(nn_section(lm=lm, share=share))
    model = create_model(cfg).clone(**backends)
    params, stats = seeded(model, batch)
    assert set(params) == {"embed", "layer_0", "layer_1", "layer_2",
                           "layer_3", "final_norm", "head"}
    for layer in ("layer_0", "layer_1", "layer_2"):
        assert set(params[layer]) == {"mixer", "moe"}
        assert set(params[layer]["mixer"]) == {
            "norm", "w_qkvz", "w_ba", "conv_w", "A_log", "dt_bias",
            "gate_norm", "w_out"}
    # [q | k | v | z]: 2 x 16 + 2 x 32; the taps over [q | k | v] alone
    assert params["layer_0"]["mixer"]["w_qkvz"].shape == (32, 96)
    assert params["layer_0"]["mixer"]["w_ba"].shape == (32, 8)
    assert params["layer_0"]["mixer"]["conv_w"].shape == (4, 64)
    assert params["layer_0"]["mixer"]["gate_norm"].shape == (8,)
    assert set(params["layer_3"]["mixer"]) == {
        "norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo"}
    # the query product is twice as wide: per head q | gate
    assert params["layer_3"]["mixer"]["wq"].shape == (32, 4 * 2 * 16)
    assert set(params["layer_3"]["moe"]) == {
        "norm", "router", "experts_w1", "experts_w3", "experts_w2",
        "shared_w1", "shared_w3", "shared_w2", "shared_gate"}
    assert params["layer_3"]["moe"]["shared_gate"].shape == (32, 1)
    loss, grads, new_stats = loss_and_grads(model, cfg, params, stats, batch)
    ref_loss, ref_grads = R.loss_and_grads(
        params, lm, share, docs, pad_to=lambda n: LONGEST)
    assert abs(loss - ref_loss) <= 1e-5 * ref_loss
    (dev, where), leaves = worst_leaf(grads, ref_grads)
    assert leaves == 3 + 3 * (8 + 9) + 7 + 9
    assert dev < 5e-5, (where, dev)
    # the step's counters: every real node's k slots on four expert layers
    nodes = sum(DOC_LENGTHS)
    assert float(new_stats["moe_slots_all"]) == 4 * nodes * 3
    if share is not SHARE:
        assert float(new_stats["moe_slots_held"]) == 4 * nodes * 3
    else:
        assert 0 < float(new_stats["moe_slots_held"]) < 4 * nodes * 3
    assert float(new_stats["moe_dense_steps"]) == 0.0
    assert float(new_stats["attn_blocks_band"]) > 0
    # three DeltaNet layers walk the same 7 chunks of 8 (none all padding)
    # and start a state a real graph
    assert float(new_stats["gdn_chunks"]) == 3 * 7
    assert float(new_stats["gdn_chunks_padding"]) == 0.0
    assert float(new_stats["gdn_resets"]) == 3 * len(DOC_LENGTHS)
    assert float(new_stats["gdn_kept_mb"]) == 0.0


def test_an_eval_step_counts_nothing(batch):
    cfg = ModelConfig.from_config(nn_section(lm=ONE_LAYER))
    model = create_model(cfg)
    params, stats = seeded(model, batch)
    _l, _g, after = loss_and_grads(model, cfg, params, stats, batch,
                                   train=False)
    assert all(float(v) == 0.0 for v in after.values())
    assert {k for k in after if k.startswith("gdn_")} == {
        "gdn_chunks", "gdn_chunks_padding", "gdn_resets", "gdn_kept_mb"}


def test_bfloat16_products_stay_near_the_reference(docs, batch):
    cfg = ModelConfig.from_config(nn_section("bfloat16"))
    model = create_model(cfg).clone(gdn_backend="chunked")
    params, stats = seeded(model, batch)
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(params))
    loss, grads, _ = loss_and_grads(model, cfg, params, stats, batch)
    ref_loss, ref_grads = R.loss_and_grads(
        params, LM, SHARE, docs, pad_to=lambda n: LONGEST)
    assert abs(loss - ref_loss) < 0.02 * ref_loss
    g = jnp.concatenate([a.ravel() for a in jax.tree.leaves(grads)])
    r = jnp.concatenate([a.ravel() for a in jax.tree.leaves(ref_grads)])
    assert g.dtype == jnp.float32
    dev = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
    assert 1e-4 < dev < 0.08      # rounded, and no more than rounded


@pytest.mark.parametrize("backend", ["sequential", "chunked"])
def test_a_deltanet_half_keeps_the_rules_inverse_where_narrow(
        batch, monkeypatch, backend):
    """On the chunked backend the half's checkpoint keeps the rule's float32
    inverse in bfloat16 and nothing in float32; on the sequential backend,
    which computes no inverse, nothing.  The gradients are the bare
    checkpoint's, the step record says what is held, and the chunked
    gradient program holds the inverse's rounds once where the bare
    checkpoint's holds them twice."""
    import hydragnn_tpu.models.qwen3_next as Q
    from test_gdn import square_products

    # a chunk of 16 where the heads are 8 wide: the [C, C] products are the
    # inverse's alone
    lm = dict(ONE_LAYER, linear_chunk_size=16)
    chunked = backend == "chunked"

    def run(dtype):
        cfg = ModelConfig.from_config(nn_section(dtype, lm=lm))
        model = create_model(cfg).clone(gdn_backend=backend)
        params, stats = seeded(model, batch)
        loss, grads, new_stats = loss_and_grads(model, cfg, params, stats,
                                                batch)
        products = square_products(jax.make_jaxpr(jax.grad(
            lambda p: _loss_and_metrics(model, cfg, p, stats, batch, True)[0]
        ))(params).jaxpr, 16)
        return loss, grads, float(new_stats["gdn_kept_mb"]), products

    loss, grads, kept, products = run("bfloat16")
    # one layer's 4 chunks x 4 heads of float32 [16, 16]
    assert kept == pytest.approx(4 * 4 * 16 * 16 * 4 / 1e6 if chunked else 0)
    # three rounds of two products and the backward rule's two: once
    assert products == (8 if chunked else 0)
    assert run("float32")[2:] == (0.0, 14 if chunked else 0)
    monkeypatch.setattr(Q, "where_narrow", lambda policy, dtype: None)
    bare_loss, bare_grads, bare_kept, bare_products = run("bfloat16")
    assert bare_kept == 0.0
    assert bare_products == (14 if chunked else 0)
    assert loss == pytest.approx(bare_loss, rel=1e-6)
    (dev, where), _ = worst_leaf(grads, bare_grads)
    assert dev < 1e-5, where


def test_parameter_count_is_the_formulas(batch):
    cfg = ModelConfig.from_config(nn_section())
    shapes = jax.eval_shape(lambda b: create_model(cfg).init(
        {"params": jax.random.PRNGKey(0)}, b, train=False), batch)
    d, hk, hv, dk, hd, heads, kv = 32, 2, 4, 8, 16, 4, 2
    key, value, f, e, router, vocab = hk * dk, hv * dk, 24, 4, 16, 64
    deltanet = (d * (2 * key + 2 * value) + d * 2 * hv
                + 4 * (2 * key + value) + 2 * hv + dk + value * d + d)
    attention = (d * heads * 2 * hd + 2 * d * kv * hd + heads * hd * d
                 + 2 * hd + d)
    experts = d * router + e * 3 * d * f + 3 * d * f + d + d
    want = (3 * (deltanet + experts) + attention + experts
            + 2 * vocab * d + d)
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(shapes["params"])) == want


# -- the shares add up --------------------------------------------------------

def _expert_layer(key):
    """Seeded parameters of one UNCUT expert half, as the reference names
    them: 16 experts and the shared expert under its gate."""
    d, f, k = 32, 24, iter(jax.random.split(key, 10))

    def normal(*shape, fan):
        return jax.random.normal(next(k), shape) * fan ** -0.5

    return {"norm": 0.1 * jax.random.normal(next(k), (d,)),
            "router": normal(d, 16, fan=d),
            "experts_w1": normal(16, d, f, fan=d),
            "experts_w3": normal(16, d, f, fan=d),
            "experts_w2": normal(16, f, d, fan=f),
            "shared_w1": normal(d, f, fan=d),
            "shared_w3": normal(d, f, fan=d),
            "shared_w2": normal(f, d, fan=f),
            "shared_gate": normal(d, 1, fan=d)}


def _run_experts(p, h, mask, share):
    """The expert half of the PROGRAM on the packed batch, from
    reference-named parameters."""
    held = dict(LM, num_experts=p["experts_w1"].shape[0])
    lm = Qwen3NextConfig.from_arch({"qwen3_next": held})
    return MoE(lm, share, jnp.float32, "ragged_dot", False,
               zero_centred=True, shared_gate=True).apply(
                   {"params": p}, h, mask)


def test_all_four_shares_of_an_expert_layer_add_up(batch):
    """Four ranks of four experts each; the router, the norm and the
    shared expert under its sigmoid gate are whole on every rank, so every
    rank computes them alike and they count ONCE: the four routed parts
    plus one shared part are the uncut reference's layer."""
    h = jax.random.normal(jax.random.PRNGKey(4), (56, 32))
    real = sum(DOC_LENGTHS)
    p = _expert_layer(jax.random.PRNGKey(13))
    cfg = dict(LM, num_experts=16)
    with jax.default_matmul_precision("highest"):
        u = R.zrms(h[:real], p["norm"], 1e-6)
        want = R.moe(p, cfg, R.whole_share(cfg), u)
        alike = R.shared(p, u)       # what every rank computes alike
        assert float(jnp.max(jnp.abs(alike))) > 0.05
        routed, held = 0.0, 0.0
        for rank in range(4):
            part = {k: (v[4 * rank:4 * rank + 4]
                        if k.startswith("experts_") else v)
                    for k, v in p.items()}
            y, stats = _run_experts(
                part, h, batch.node_mask,
                LayerShare(16, 4, 4 * rank, 2, 2, 0, 512, 64, 0))
            # a rank's result is its routed part and the shared expert
            routed = routed + (y[:real] - alike)
            held += float(stats["slots_held"])
            # one share alone is NOT the layer
            assert float(jnp.max(jnp.abs(y[:real] - want))) > 0.05
    np.testing.assert_allclose(routed + alike, want, rtol=0, atol=2e-5)
    assert held == real * 3          # every slot fell on exactly one rank


def test_reference_copy_under_benchmark_is_byte_identical():
    assert filecmp.cmp(
        os.path.join(REPO, "hydragnn_tpu/models/qwen3_next_reference.py"),
        os.path.join(REPO, "benchmark/reference/qwen3_next_reference.py"),
        shallow=False)
    assert len(R.ASSUMED) >= 5 and len(R.DEPARTURES) >= 3
    with open(os.path.join(
            REPO, "hydragnn_tpu/models/qwen3_next_reference.py")) as f:
        text = f.read()
    assert "import hydragnn" not in text and "from hydragnn" not in text
    assert 'default_matmul_precision("highest")' in text


def test_json_config_trains_through_run_training(tmp_path, monkeypatch):
    """``model_type: "Qwen3Next"`` through ``run_training`` on the normal
    path: token files, the loader, buckets, the resident scan-K trainer
    (on the test session's eight host devices: the DP mesh)."""
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
                "model_type": "Qwen3Next", "hidden_dim": 32,
                "num_conv_layers": 4, "qwen3_next": LM, "share": SHARE,
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
