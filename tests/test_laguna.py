"""The Laguna stack against its plain float32 reference, at a small size on
the CPU: loss and every gradient leaf on seeded weights for a batch that
mixes documents shorter and longer than the window, on both layer kinds,
dense and expert feed-forward; the share test (all expert shares and all
head shares of one layer add up to the uncut layer, the shared expert
counted once); dropless routing under a skewed router; the new loss; and
the JSON entry points (run_training, run_prediction)."""

import dataclasses
import filecmp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.graph.batch import GraphSample, HeadSpec, PadSpec, collate
from hydragnn_tpu.models import laguna_reference as R
from hydragnn_tpu.models.base import ModelConfig
from hydragnn_tpu.models.create import create_model
from hydragnn_tpu.ops import attention, moe
from hydragnn_tpu.parallel.share import LayerShare
from hydragnn_tpu.train.trainer import _loss_and_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LM = {
    "model_type": "laguna", "vocab_size": 64, "hidden_size": 32,
    "intermediate_size": 64, "num_hidden_layers": 3,
    "num_key_value_heads": 1, "head_dim": 16, "rms_norm_eps": 1e-6,
    "num_experts": 4, "num_experts_per_tok": 3, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "norm_topk_prob": True,
    "gating": "per-head", "sliding_window": 8,
    "moe_routed_scaling_factor": 2.5,
    "layer_types": ["full_attention", "sliding_attention", "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse"],
    "num_attention_heads_per_layer": [2, 3, 2],
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}}
SHARE = {"num_experts_total": 16, "expert_offset": 4, "kv_heads_total": 2,
         "kv_head_offset": 1, "vocab_total": 512, "vocab_offset": 0}
DOC_LENGTHS = (5, 20, 3, 12)        # window 8: shorter and longer


def nn_section(dtype="float32", lm=LM, share=SHARE):
    return {
        "Architecture": {
            "model_type": "Laguna", "hidden_dim": lm["hidden_size"],
            "num_conv_layers": lm["num_hidden_layers"], "input_dim": 1,
            "output_dim": [1], "output_type": ["node"],
            "task_weights": [1.0], "compute_dtype": dtype, "laguna": lm,
            "share": share, "max_graph_nodes": 24, "output_heads": {}},
        "Training": {"loss_function_type": "softmax_xent"}}


def sample(ids):
    nxt = np.concatenate([ids[1:], [-1]]).astype(np.float32)[:, None]
    return GraphSample(x=ids.astype(np.float32)[:, None],
                       pos=np.zeros((len(ids), 3)), node_y=nxt)


@pytest.fixture(scope="module")
def docs():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 64, size=n) for n in DOC_LENGTHS]


@pytest.fixture(scope="module")
def batch(docs):
    b = collate([sample(d) for d in docs], PadSpec(48, 8, 5),
                [HeadSpec("next", "node", 1)])
    return jax.tree.map(jnp.asarray, b)


def loss_and_grads(model, cfg, variables, batch):
    def loss_fn(p):
        return _loss_and_metrics(model, cfg, p, variables["batch_stats"],
                                 batch, True)

    (loss, (_ph, stats, _out)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(variables["params"])
    return float(loss), grads, stats


@pytest.mark.parametrize("backends", [
    ("dense", "ragged_dot", False), ("splash", "gmm", True)],
    ids=["composed", "kernels_interpreted"])
def test_loss_and_every_gradient_leaf_match_the_reference(
        docs, batch, backends):
    cfg = ModelConfig.from_config(nn_section())
    ab, mb, interpret = backends
    model = create_model(cfg).clone(attention_backend=ab, moe_backend=mb,
                                    interpret=interpret)
    variables = model.init({"params": jax.random.PRNGKey(1)}, batch,
                           train=False)
    loss, grads, stats = loss_and_grads(model, cfg, variables, batch)
    ref_loss, ref_grads = R.loss_and_grads(
        variables["params"], LM, SHARE, docs)
    assert abs(loss - ref_loss) <= 1e-5 * ref_loss
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    ref = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert got.keys() == ref.keys() and len(got) == 41
    for path, r in ref.items():
        dev = float(jnp.linalg.norm(got[path] - r)
                    / (jnp.linalg.norm(r) + 1e-12))
        assert dev < 2e-5, (jax.tree_util.keystr(path), dev)
    # the step's routing counters: every real node's k slots, some held
    assert float(stats["moe_slots_all"]) == 2 * sum(DOC_LENGTHS) * 3
    assert 0 < float(stats["moe_slots_held"]) < float(stats["moe_slots_all"])
    assert float(stats["moe_dense_steps"]) == 0.0


def test_bfloat16_products_stay_near_the_reference(docs, batch):
    cfg = ModelConfig.from_config(nn_section("bfloat16"))
    model = create_model(cfg)
    variables = model.init({"params": jax.random.PRNGKey(1)}, batch,
                           train=False)
    assert all(p.dtype == jnp.float32
               for p in jax.tree.leaves(variables["params"]))
    loss, grads, _ = loss_and_grads(model, cfg, variables, batch)
    ref_loss, ref_grads = R.loss_and_grads(
        variables["params"], LM, SHARE, docs)
    assert abs(loss - ref_loss) < 0.02 * ref_loss
    g = jnp.concatenate([a.ravel() for a in jax.tree.leaves(grads)])
    r = jnp.concatenate([a.ravel() for a in jax.tree.leaves(ref_grads)])
    assert g.dtype == jnp.float32
    dev = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
    assert 1e-4 < dev < 0.05      # rounded, and no more than rounded


@pytest.mark.parametrize("window", [None, 8])
def test_attention_kernels_match_the_dense_twin(window):
    k = jax.random.split(jax.random.PRNGKey(2), 4)
    n, gid = 40, jnp.asarray([0] * 5 + [1] * 20 + [2] * 3 + [3] * 12)
    q = jax.random.normal(k[0], (n, 4, 16))
    kk = jax.random.normal(k[1], (n, 2, 16))
    v = jax.random.normal(k[2], (n, 2, 16))

    def run(backend):
        def f(q, kk, v):
            o = attention.graph_attention(
                q, kk, v, gid, window=window, max_span=20, backend=backend,
                interpret=True)
            return jnp.sum(o * jnp.sin(o)), o
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
            q, kk, v)

    (a, oa), ga = run("dense")
    (b, ob), gb = run("splash")
    np.testing.assert_allclose(oa, ob, atol=2e-5)
    for x, y in zip(ga, gb):
        np.testing.assert_allclose(x, y, atol=5e-5)
    # the contract itself: a node sees its own graph, back, inside the band
    seen = np.asarray(attention.visible(gid, window))
    assert seen[24, 5] == (window is None) and seen[24, 20] and \
        not seen[5, 4] and not seen[5, 6]


def _layer_params(key, lm, share_heads, experts):
    d, hd = lm["hidden_size"], lm["head_dim"]
    f = lm["moe_intermediate_size"]
    k = jax.random.split(key, 16)
    n = lambda i, *s: jax.random.normal(k[i], s) * s[0] ** -0.5  # noqa: E731
    h, kv = share_heads
    return {
        "attn": {"norm": jnp.ones(d), "wq": n(0, d, h * hd),
                 "wk": n(1, d, kv * hd), "wv": n(2, d, kv * hd),
                 "wg": n(3, d, h), "wo": n(4, h * hd, d)},
        "moe": {"norm": jnp.ones(d), "router": n(5, d, experts),
                "experts_w1": n(6, d, experts * f).reshape(
                    d, experts, f).swapaxes(0, 1),
                "experts_w3": n(7, d, experts * f).reshape(
                    d, experts, f).swapaxes(0, 1),
                "experts_w2": n(8, f, experts * d).reshape(
                    f, experts, d).swapaxes(0, 1),
                "shared_w1": n(9, d, f), "shared_w3": n(10, d, f),
                "shared_w2": n(11, f, d)}}


def test_all_shares_of_a_layer_add_up_to_the_uncut_layer():
    """4 expert shares x 4 experts and 2 head shares x (1 key/value head,
    3 query heads) of one sliding layer with experts: the program's parts
    (shared expert counted once) sum to the uncut reference's output."""
    lm = dict(LM, num_hidden_layers=1, layer_types=["sliding_attention"],
              mlp_layer_types=["sparse"], num_attention_heads_per_layer=[6],
              num_key_value_heads=2, num_experts=16)
    whole = _layer_params(jax.random.PRNGKey(3), lm, (6, 2), 16)
    x = jax.random.normal(jax.random.PRNGKey(4), (20, lm["hidden_size"]))
    want = R.layer_forward(whole, lm, R.whole_share(lm), 0, x)

    hd, eps = lm["head_dim"], lm["rms_norm_eps"]
    u = R.rms_norm(x, whole["attn"]["norm"], eps)
    attn = 0.0
    for r in range(2):                       # head shares
        held = dict(lm, num_key_value_heads=1,
                    num_attention_heads_per_layer=[3])
        a = whole["attn"]
        cols = slice(r * 3 * hd, (r + 1) * 3 * hd)
        part = {"wq": a["wq"][:, cols], "wg": a["wg"][:, r * 3:(r + 1) * 3],
                "wk": a["wk"][:, r * hd:(r + 1) * hd],
                "wv": a["wv"][:, r * hd:(r + 1) * hd],
                "wo": a["wo"][cols]}
        attn = attn + R.attention(part, held, 0, u)
    h = x + attn
    m = whole["moe"]
    um = R.rms_norm(h, m["norm"], eps)
    routed = 0.0
    for r in range(4):                       # expert shares, the program's
        share = LayerShare(16, 4, 4 * r, 2, 1, 0, 64, 64, 0)
        y, stats = moe.routed_experts(
            um, m["router"], m["experts_w1"][4 * r:4 * r + 4],
            m["experts_w3"][4 * r:4 * r + 4],
            m["experts_w2"][4 * r:4 * r + 4], share, top_k=3, scale=2.5)
        routed = routed + y
        assert float(stats["dense_steps"]) == 0.0
    shared = R.gated_mlp(um, m["shared_w1"], m["shared_w3"], m["shared_w2"])
    np.testing.assert_allclose(h + routed + shared, want, atol=2e-5)


def test_no_held_slot_is_dropped_under_a_skewed_router():
    """A router that sends nearly every node to the held experts: the load
    passes the grouped path's capacity, the dense path takes the step, and
    the result is the reference's (every slot computed)."""
    share = LayerShare(16, 4, 4, 2, 1, 0, 64, 64, 0)
    k = jax.random.split(jax.random.PRNGKey(5), 5)
    n, d, f = 400, 32, 16
    u = jax.random.normal(k[0], (n, d)).at[:, 0].set(5.0)
    router = jax.random.normal(k[1], (d, 16)).at[0, 4:8].set(3.0)
    w1, w3 = (jax.random.normal(k[i], (4, d, f)) * 0.2 for i in (2, 3))
    w2 = jax.random.normal(k[4], (4, f, d)) * 0.2
    cfg = {"num_experts_per_tok": 3, "moe_routed_scaling_factor": 2.5}
    p = {"router": router, "experts_w1": w1, "experts_w3": w3,
         "experts_w2": w2}
    want = R.moe(p, cfg, {"expert_offset": 4}, u, shared=False)
    for capacity, dense in ((512, 1.0), (1536, 0.0)):
        y, stats = moe.routed_experts(u, router, w1, w3, w2, share, top_k=3,
                                      scale=2.5, capacity=capacity)
        assert float(stats["slots_held"]) > 512        # over the small one
        assert float(stats["dense_steps"]) == dense
        np.testing.assert_allclose(y, want, atol=2e-4)
    # padding nodes are routed nowhere
    mask = jnp.arange(n) < 100
    _y, stats = moe.routed_experts(u, router, w1, w3, w2, share, top_k=3,
                                   node_mask=mask.astype(jnp.float32))
    assert float(stats["slots_all"]) == 300.0
    assert float(stats["slots_held"]) <= 300.0


def _held_case(name, key):
    """(local, held, capacity) [n, k] for one shape of held slots."""
    k1, k2 = jax.random.split(key)
    if name == "several_slots_a_node":      # up to 3 held slots on a node
        n, k, cap = 150, 3, 512
        local = jax.random.randint(k1, (n, k), 0, 4)
        held = jax.random.bernoulli(k2, 0.5, (n, k))
    elif name == "one_node_in_two_row_tiles":
        # every node holds experts 0 and 3: rows r and 200 + r, which lie
        # in different tiles of the nodes -> rows kernel
        n, k, cap = 200, 3, 512
        local = jnp.tile(jnp.asarray([0, 1, 3]), (n, 1))
        held = jnp.tile(jnp.asarray([True, False, True]), (n, 1))
        assert 200 // moe.GATHER_TILE != 0
    elif name == "load_zero":
        n, k, cap = 150, 3, 512
        local = jax.random.randint(k1, (n, k), 0, 4)
        held = jnp.zeros((n, k), bool)
    elif name == "load_is_the_capacity":
        n, k, cap = 256, 2, 512
        local = jax.random.randint(k1, (n, k), 0, 4)
        held = jnp.ones((n, k), bool)
    else:
        assert name == "padding_nodes"      # three node tiles, the last
        n, k, cap = 300, 3, 512             # ragged and all padding
        local = jax.random.randint(k1, (n, k), 0, 4)
        held = jax.random.bernoulli(k2, 0.4, (n, k)) & (
            jnp.arange(n) < 250)[:, None]
        assert n % moe.NODE_TILE and n > 2 * moe.NODE_TILE
    return local, held, cap


def _grouped_value_and_grads(backend, interpret, dtype, local, held, cap,
                             key):
    n, k = held.shape
    d, f = 32, 16
    ks = jax.random.split(key, 6)
    u = jax.random.normal(ks[0], (n, d)).astype(dtype)
    weights = jax.random.uniform(ks[1], (n, k), minval=0.1)
    w1, w3 = (jax.random.normal(ks[i], (4, d, f)).astype(dtype) * 0.3
              for i in (2, 3))
    w2 = jax.random.normal(ks[4], (4, f, d)).astype(dtype) * 0.3
    target = jax.random.normal(ks[5], (n, d))
    loads = jnp.sum((local[..., None] == jnp.arange(4)) & held[..., None],
                    axis=(0, 1), dtype=jnp.int32)

    def f_(u, weights):
        y = moe._grouped_path(u, weights, local, held, loads, w1, w3, w2,
                              cap, backend, interpret)
        return jnp.sum(y * target), y

    return jax.value_and_grad(f_, argnums=(0, 1), has_aux=True), (u, weights)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", [
    "several_slots_a_node", "one_node_in_two_row_tiles", "load_zero",
    "load_is_the_capacity", "padding_nodes"])
def test_row_kernels_interpreted_match_the_twin(case, dtype):
    """The two row-walk kernels (interpreted) against the XLA twin through
    the grouped path itself: the sum, and the gradients with respect to
    the nodes' rows and to the routing weights."""
    dtype = jnp.dtype(dtype)
    local, held, cap = _held_case(case, jax.random.PRNGKey(7))
    got, want = (
        fn(*args) for fn, args in (
            _grouped_value_and_grads(backend, interpret, dtype, local, held,
                                     cap, jax.random.PRNGKey(8))
            for backend, interpret in (("gmm", True), ("ragged_dot", False))))
    ((_, ya), (dua, dwa)), ((_, yb), (dub, dwb)) = got, want
    assert ya.dtype == jnp.float32 and dua.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for a, b in ((ya, yb), (dua, dub), (dwa, dwb)):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.max(jnp.abs(a - b))) <= tol * max(
            float(jnp.max(jnp.abs(b))), 1e-6)
    # a slot that is not held moves nothing and learns nothing
    assert not np.any(np.asarray(dwa)[~np.asarray(held)])
    if case == "load_zero":
        assert not np.any(np.asarray(ya)) and not np.any(np.asarray(dua))
    else:
        assert np.any(np.asarray(dwa)) and np.any(np.asarray(dua))


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_grouped_path_moves_no_index_array_of_all_the_slots():
    """After the top-k nothing gathers, sorts or scatters over N x k: the
    index (or key) arrays of the grouped path are ``capacity`` long."""
    n, k, cap = 400, 3, 512
    local, held = (jnp.zeros((n, k), jnp.int32), jnp.ones((n, k), bool))
    fn, args = _grouped_value_and_grads(
        "ragged_dot", False, jnp.float32, local, held, cap,
        jax.random.PRNGKey(0))
    moved = {"gather": [], "sort": [], "scatter": []}
    for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            idx = eqn.invars[1].aval.shape
            moved[name[:7]].append(int(np.prod(idx[:-1])))
        elif name == "sort":
            moved["sort"].append(
                eqn.invars[0].aval.shape[eqn.params["dimension"]])
    assert moved["gather"] and moved["sort"] and moved["scatter"]
    assert all(m <= cap for ms in moved.values() for m in ms), moved
    assert moved["sort"] == [cap]


def test_softmax_xent_skips_padding_and_last_nodes():
    from hydragnn_tpu.models.layers import loss_function

    logits = jnp.asarray([[2.0, 0.0, 0.0], [0.0, 3.0, 0.0],
                          [0.0, 0.0, 9.0], [5.0, 0.0, 0.0]])
    labels = jnp.asarray([[0.0], [2.0], [-1.0], [1.0]])
    mask = jnp.asarray([1.0, 1.0, 1.0, 0.0])
    got = loss_function("softmax_xent")(logits, labels, mask)
    logp = jax.nn.log_softmax(logits, axis=-1)
    np.testing.assert_allclose(got, -(logp[0, 0] + logp[1, 2]) / 2,
                               rtol=1e-6)


def test_share_rejects_what_it_cannot_hold():
    with pytest.raises(ValueError, match="experts"):
        LayerShare(16, 4, 14, 2, 1, 0, 64, 64, 0)
    cfg = ModelConfig.from_config(nn_section())
    assert cfg.share.expert_offset == 4 and cfg.lm.num_layers == 3
    with pytest.raises(ValueError, match="Architecture.laguna"):
        create_model(dataclasses.replace(cfg, lm=None))


def test_the_two_copies_of_the_reference_are_one_file():
    assert filecmp.cmp(
        os.path.join(REPO, "hydragnn_tpu", "models", "laguna_reference.py"),
        os.path.join(REPO, "benchmark", "reference", "laguna_reference.py"),
        shallow=False)


def test_yarn_frequencies_blend_as_published():
    rope = LM["rope_parameters"]["full_attention"]
    inv, scale, rot = R.rotary_inv_freq(rope, 128)
    assert rot == 64 and scale == pytest.approx(1.4852030263919618)
    base = 500000.0 ** (np.arange(0, 64, 2) / 64)
    # fast dims keep their frequency, slow dims are divided by the factor
    np.testing.assert_allclose(inv[:3], 1.0 / base[:3])
    np.testing.assert_allclose(inv[-3:], 1.0 / (128 * base[-3:]))
    assert np.all(np.diff(inv) < 0)
    inv, scale, rot = R.rotary_inv_freq(
        LM["rope_parameters"]["sliding_attention"], 128)
    assert rot == 128 and scale == 1.0 and inv[1] == pytest.approx(
        10000.0 ** (-2 / 128))


def _json_config(num_epoch):
    return {
        "Verbosity": {"level": 0},
        "Dataset": {
            "name": "docs_tiny", "format": "tokens",
            "path": {"total": "dataset/docs_tiny"},
            "node_features": {"name": ["token_id", "next_token_id"],
                              "dim": [1, 1], "column_index": [0, 1]}},
        "NeuralNetwork": {
            "Architecture": {
                "model_type": "Laguna", "hidden_dim": 32,
                "num_conv_layers": 3, "laguna": LM, "share": SHARE,
                "output_heads": {}, "task_weights": [1.0]},
            "Variables_of_interest": {
                "input_node_features": [0], "output_index": [1],
                "type": ["node"], "output_names": ["next_token_id"],
                "denormalize_output": False},
            "Training": {
                "num_epoch": num_epoch, "batch_size": 4, "perc_train": 0.8,
                "loss_function_type": "softmax_xent",
                "Optimizer": {"type": "AdamW", "learning_rate": 3e-3}}},
        "Visualization": {"create_plots": False},
    }


def test_json_config_trains_and_predicts_through_the_entry_points(
        tmp_path, monkeypatch):
    """Token files -> run_training on the stock loop's resident scan-K
    path -> run_prediction on the saved model: no side script."""
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
    config = _json_config(num_epoch=8)
    _state, history, final = hydragnn_tpu.run_training(
        config, logs_dir=str(tmp_path / "logs"))
    train = [float(v) for v in history["train"]]
    assert train[-1] < 0.8 * train[0]
    assert history["pipeline"]["resident"] is True
    assert history["pipeline"]["steps_per_dispatch"] >= 2
    arch = final["NeuralNetwork"]["Architecture"]
    assert arch["max_graph_nodes"] == 29 and arch["output_dim"] == [1]
    error, _tasks, true_values, predicted = hydragnn_tpu.run_prediction(
        config, logs_dir=str(tmp_path / "logs"))
    assert np.isfinite(error)
    # per real test node the most likely next id beside the true one; the
    # fixed successor table was learned for most of them
    assert predicted[0].shape == true_values[0].shape
    has_next = true_values[0][:, 0] >= 0
    hit = (predicted[0] == true_values[0])[has_next, 0]
    assert hit.mean() > 0.5


def test_reference_rows_in_blocks_are_the_rows_at_once():
    """The benchmark runs the reference's attention ``q_block`` rows at a
    time so that a long document fits: the same numbers, the same
    gradient."""
    lm = dict(LM, num_hidden_layers=1, layer_types=["sliding_attention"],
              mlp_layer_types=["sparse"], num_attention_heads_per_layer=[3])
    p = _layer_params(jax.random.PRNGKey(0), lm, (3, 1), 4)["attn"]
    u = jax.random.normal(jax.random.PRNGKey(1), (32, 32))

    def f(q_block):
        return jax.value_and_grad(lambda u: jnp.sum(jnp.sin(
            R.attention(p, lm, 0, u, q_block=q_block))))(u)

    (a, ga), (b, gb) = f(None), f(8)
    np.testing.assert_allclose(a, b, rtol=1e-6)
    np.testing.assert_allclose(ga, gb, atol=2e-6)
