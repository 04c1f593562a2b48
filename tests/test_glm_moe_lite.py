"""The GLM-4.7-Flash stack against its plain float32 reference, at a small
size on the CPU: loss, both heads' losses and every gradient leaf on seeded
weights and a seeded correction bias, composed and with the kernels
interpreted; the attention kernels where every query head has a key/value
head of its own; the bias rule (selection under ``score + b``, weights
from the unbiased scores, one step of the update speed after a train step,
eval leaves it); the share test (8 shares of a 64-expert layer add up to
the uncut reference's layer, the shared expert counted once); the
multi-token-prediction head's mask at graph ends and across graph
boundaries of a packed batch; two heads under ``task_weights``; a
checkpoint round trip that carries ``b``; and the JSON entry point."""

import filecmp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.graph.batch import GraphSample, HeadSpec, PadSpec, collate
from hydragnn_tpu.models import glm_moe_lite_reference as R
from hydragnn_tpu.models.base import ModelConfig
from hydragnn_tpu.models.create import create_model
from hydragnn_tpu.models.glm_moe_lite import GlmMoeLiteConfig
from hydragnn_tpu.models.sequence import BIAS_UPDATE_SPEED
from hydragnn_tpu.ops import attention, moe
from hydragnn_tpu.parallel.share import LayerShare
from hydragnn_tpu.train.trainer import _loss_and_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LM = {
    "model_type": "glm4_moe_lite", "vocab_size": 64, "hidden_size": 32,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "n_shared_experts": 1, "n_routed_experts": 4, "num_experts_per_tok": 3,
    "num_attention_heads": 3, "num_key_value_heads": 3, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
    "v_head_dim": 16, "rms_norm_eps": 1e-5, "rope_theta": 1000000,
    "rope_scaling": None, "norm_topk_prob": True,
    "routed_scaling_factor": 1.8, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1}
SHARE = {"num_experts_total": 16, "expert_offset": 4, "vocab_total": 512,
         "vocab_offset": 0}
DOC_LENGTHS = (5, 20, 3, 12)
EXPERT_LAYERS = ("layer_1", "layer_2", "mtp")
WEIGHT = 0.3


def nn_section(dtype="float32", lm=LM, share=SHARE):
    return {
        "Architecture": {
            "model_type": "GlmMoeLite", "hidden_dim": lm["hidden_size"],
            "num_conv_layers": lm["num_hidden_layers"], "input_dim": 1,
            "output_dim": [1, 1], "output_type": ["node", "node"],
            "task_weights": [1.0, WEIGHT], "compute_dtype": dtype,
            "glm_moe_lite": lm, "share": share, "max_graph_nodes": 24,
            "output_heads": {}},
        "Training": {"loss_function_type": "softmax_xent"}}


def sample(ids):
    ids = np.asarray(ids)
    nxt = np.concatenate([ids[1:], [-1]])
    after = np.concatenate([ids[2:], [-1, -1]])[:len(ids)]
    return GraphSample(x=ids.astype(np.float32)[:, None],
                       pos=np.zeros((len(ids), 3)),
                       node_y=np.stack([nxt, after], 1).astype(np.float32))


HEADS = [HeadSpec("next", "node", 1), HeadSpec("next_next", "node", 1)]


@pytest.fixture(scope="module")
def docs():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 64, size=n) for n in DOC_LENGTHS]


@pytest.fixture(scope="module")
def batch(docs):
    b = collate([sample(d) for d in docs], PadSpec(48, 8, 5), HEADS)
    return jax.tree.map(jnp.asarray, b)


def seeded(model, batch):
    """Initial variables with a seeded, non-zero bias on every expert
    layer (a start at zero would not tell ``score + b`` from ``score``)."""
    variables = model.init({"params": jax.random.PRNGKey(1)}, batch,
                           train=False)
    stats = dict(variables["batch_stats"])
    keys = jax.random.split(jax.random.PRNGKey(9), len(EXPERT_LAYERS))
    for name, key in zip(EXPERT_LAYERS, keys):
        assert stats[f"bias_{name}"].shape == (16,)
        assert not np.any(np.asarray(stats[f"bias_{name}"]))
        stats[f"bias_{name}"] = 0.3 * jax.random.normal(key, (16,))
    return variables["params"], stats


def loss_and_grads(model, cfg, params, stats, batch, train=True):
    def loss_fn(p):
        return _loss_and_metrics(model, cfg, p, stats, batch, train)

    (loss, (heads, new_stats, _out)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params)
    return float(loss), [float(h) for h in heads], grads, new_stats


def biases_of(stats):
    return {name: stats[f"bias_{name}"] for name in EXPERT_LAYERS}


@pytest.mark.parametrize("backends", [
    ("dense", "ragged_dot", False), ("splash", "gmm", True)],
    ids=["composed", "kernels_interpreted"])
def test_losses_and_every_gradient_leaf_match_the_reference(
        docs, batch, backends):
    cfg = ModelConfig.from_config(nn_section())
    ab, mb, interpret = backends
    model = create_model(cfg).clone(attention_backend=ab, moe_backend=mb,
                                    interpret=interpret)
    params, stats = seeded(model, batch)
    loss, heads, grads, new_stats = loss_and_grads(
        model, cfg, params, stats, batch)
    ref_loss, ref_heads, ref_grads = R.loss_and_grads(
        params, LM, SHARE, biases_of(stats), docs, WEIGHT)
    assert abs(loss - ref_loss) <= 1e-5 * ref_loss
    for got, want in zip(heads, ref_heads):
        assert abs(got - want) <= 1e-5 * want
    # the trainer's weighted multi-head loss, weights normalised
    assert loss == pytest.approx(
        (heads[0] + WEIGHT * heads[1]) / (1 + WEIGHT), rel=1e-6)
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    ref = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert got.keys() == ref.keys() and len(got) == 67
    for path, r in ref.items():
        dev = float(jnp.linalg.norm(got[path] - r)
                    / (jnp.linalg.norm(r) + 1e-12))
        assert dev < 2e-5, (jax.tree_util.keystr(path), dev)
    # the step's routing counters: every real node's k slots on the two
    # main expert layers, every node WITH a successor's on the module's
    nodes, with_next = sum(DOC_LENGTHS), sum(n - 1 for n in DOC_LENGTHS)
    assert float(new_stats["moe_slots_all"]) == (2 * nodes + with_next) * 3
    assert 0 < float(new_stats["moe_slots_held"]) < float(
        new_stats["moe_slots_all"])
    assert float(new_stats["moe_dense_steps"]) == 0.0
    assert float(new_stats["moe_load_all_max_over_mean"]) > 1.0
    # the bias stepped by exactly its speed, up or down, on every layer
    for name in EXPERT_LAYERS:
        step = np.abs(np.asarray(
            new_stats[f"bias_{name}"] - stats[f"bias_{name}"]))
        assert np.allclose(step[step > 0], BIAS_UPDATE_SPEED, atol=1e-7)
        assert (step > 0).sum() >= 12, name
    assert float(new_stats["moe_bias_abs_max"]) == pytest.approx(max(
        float(jnp.max(jnp.abs(new_stats[f"bias_{n}"])))
        for n in EXPERT_LAYERS))


def test_an_eval_step_reads_the_bias_and_leaves_it(batch):
    cfg = ModelConfig.from_config(nn_section())
    model = create_model(cfg)
    params, stats = seeded(model, batch)
    _l, _h, _g, after_eval = loss_and_grads(
        model, cfg, params, stats, batch, train=False)
    for key, value in stats.items():
        assert np.array_equal(np.asarray(after_eval[key]),
                              np.asarray(value)), key
    # ... and it READ it: another bias, another loss
    zero = {k: jnp.zeros_like(v) for k, v in stats.items()}
    a = loss_and_grads(model, cfg, params, stats, batch, train=False)[0]
    b = loss_and_grads(model, cfg, params, zero, batch, train=False)[0]
    assert a != b


def test_the_epoch_loops_eval_step_returns_losses_and_no_logits(batch):
    from hydragnn_tpu.train.trainer import TrainState, make_eval_step

    cfg = ModelConfig.from_config(nn_section())
    model = create_model(cfg)
    params, stats = seeded(model, batch)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       batch_stats=stats, opt_state=None)
    full = jax.jit(make_eval_step(model, cfg))(state, batch)
    lean = jax.jit(make_eval_step(model, cfg, outputs=False))(state, batch)
    assert [o.shape for o in full["outputs"]] == [(48, 64), (48, 64)]
    assert set(lean) == {"loss", "num_graphs", "per_head"}
    assert float(lean["loss"]) == float(full["loss"])
    assert [float(h) for h in lean["per_head"]] == [
        float(h) for h in full["per_head"]]


def test_bfloat16_products_stay_near_the_reference(docs, batch):
    cfg = ModelConfig.from_config(nn_section("bfloat16"))
    model = create_model(cfg)
    params, stats = seeded(model, batch)
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(params))
    loss, _heads, grads, _ = loss_and_grads(model, cfg, params, stats, batch)
    ref_loss, _rh, ref_grads = R.loss_and_grads(
        params, LM, SHARE, biases_of(stats), docs, WEIGHT)
    assert abs(loss - ref_loss) < 0.02 * ref_loss
    g = jnp.concatenate([a.ravel() for a in jax.tree.leaves(grads)])
    r = jnp.concatenate([a.ravel() for a in jax.tree.leaves(ref_grads)])
    assert g.dtype == jnp.float32
    dev = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
    assert 1e-4 < dev < 0.08      # rounded, and no more than rounded


@pytest.mark.parametrize("heads,kv,d", [(4, 4, 16), (20, 20, 8), (4, 2, 16)],
                         ids=["own_kv_head_each", "twenty_heads", "grouped"])
def test_attention_kernels_match_the_dense_twin_per_head_layout(heads, kv, d):
    """Every query head over a key/value head of its own is ONE multi-head
    kernel call; grouped queries stay one multi-query call per key/value
    head.  Both against the dense twin, value and gradients."""
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    n, gid = 40, jnp.asarray([0] * 5 + [1] * 20 + [2] * 3 + [3] * 12)
    q = jax.random.normal(k[0], (n, heads, d))
    kk = jax.random.normal(k[1], (n, kv, d))
    v = jax.random.normal(k[2], (n, kv, d))

    def run(backend):
        def f(q, kk, v):
            o = attention.graph_attention(
                q, kk, v, gid, max_span=20, backend=backend, interpret=True)
            return jnp.sum(o * jnp.sin(o)), o
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
            q, kk, v)

    (_a, oa), ga = run("dense")
    (_b, ob), gb = run("splash")
    np.testing.assert_allclose(oa, ob, atol=2e-5)
    for x, y in zip(ga, gb):
        np.testing.assert_allclose(x, y, atol=5e-5)


def test_one_multi_head_kernel_call_where_every_head_has_its_own_kv():
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    n, gid = 40, jnp.zeros((40,), jnp.int32)
    q = jnp.ones((n, 20, 8))
    calls = {}
    real = sk.SplashAttentionKernel.__call__

    def counting(self, q, *a):
        key = (q.shape[0], self.kwargs["is_mqa"])
        calls[key] = calls.get(key, 0) + 1
        return real(self, q, *a)

    sk.SplashAttentionKernel.__call__ = counting
    try:
        attention.graph_attention(q, q, q, gid, backend="splash",
                                  interpret=True)
        attention.graph_attention(q, q[:, :1], q[:, :1], gid,
                                  backend="splash", interpret=True)
    finally:
        sk.SplashAttentionKernel.__call__ = real
    # (query heads of the call, multi-query) -> calls
    assert calls == {(20, False): 1, (20, True): 1}


def test_route_selects_under_the_bias_and_weighs_without_it():
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    u = jax.random.normal(k[0], (50, 32))
    w = jax.random.normal(k[1], (32, 16)) * 32 ** -0.5
    bias = jnp.zeros((16,)).at[5].set(10.0).at[2].set(-10.0)
    ids0, w0 = moe.route(u, w, 4, True, 1.8, "sigmoid", jnp.zeros((16,)))
    ids, wts = moe.route(u, w, 4, True, 1.8, "sigmoid", bias)
    ids, ids0 = np.asarray(ids), np.asarray(ids0)
    # expert 5 is now every node's first choice, expert 2 no node's
    assert (ids[:, 0] == 5).all() and not (ids == 2).any()
    assert (ids0 == 2).any() and not (ids0 == 5).all()
    # ... yet the weights are the unbiased sigmoid scores of the selected,
    # renormalised, times the scaling factor
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        u, w, precision=jax.lax.Precision.HIGHEST)))
    picked = np.take_along_axis(scores, ids, axis=1)
    np.testing.assert_allclose(
        wts, 1.8 * picked / picked.sum(1, keepdims=True), rtol=1e-6)
    assert float(jnp.max(wts)) <= 1.8
    # the reference's routing is the same function
    rid, rw = R.routing({"router": w}, {"num_experts_per_tok": 4,
                                       "routed_scaling_factor": 1.8},
                        u, bias)
    assert np.array_equal(np.asarray(rid), ids)
    np.testing.assert_allclose(rw, wts, rtol=1e-6)
    # no gradient reaches the bias
    g = jax.grad(lambda b: jnp.sum(
        moe.route(u, w, 4, True, 1.8, "sigmoid", b)[1]))(bias)
    assert not np.any(np.asarray(g))
    # softmax scoring without a bias is what it was: the k largest softmax
    # scores renormalised (models/laguna.py)
    ids_s, w_s = moe.route(u, w, 4, True, 2.5)
    top, want = jax.lax.top_k(jax.nn.softmax(jnp.dot(
        u, w, precision=jax.lax.Precision.HIGHEST), axis=-1), 4)
    assert np.array_equal(np.asarray(ids_s), np.asarray(want))
    np.testing.assert_allclose(
        w_s, 2.5 * top / jnp.sum(top, -1, keepdims=True), rtol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        moe.route(u, w, 4, scoring="tanh")


def test_a_skewed_router_moves_the_bias_by_exactly_its_speed():
    """A router that sends every node to experts 4..7: after one train
    step their bias is ``-gamma``, every other expert's ``+gamma``; the
    counts are over the router's FULL width."""
    share = LayerShare(16, 4, 4, 1, 1, 0, 64, 64, 0)
    k = jax.random.split(jax.random.PRNGKey(5), 5)
    n, d, f = 200, 32, 16
    u = jax.random.normal(k[0], (n, d)).at[:, 0].set(5.0)
    router = (jax.random.normal(k[1], (d, 16)) * 0.01).at[0, 4:8].set(3.0)
    w1, w3 = (jax.random.normal(k[i], (4, d, f)) * 0.2 for i in (2, 3))
    w2 = jax.random.normal(k[4], (4, f, d)) * 0.2
    mask = (jnp.arange(n) < 150).astype(jnp.float32)
    _y, stats = moe.routed_experts(
        u, router, w1, w3, w2, share, top_k=4, scale=1.8, scoring="sigmoid",
        bias=jnp.zeros((16,)), node_mask=mask, capacity=1024)
    c = np.asarray(stats["counts_all"])
    assert c.sum() == 150 * 4 and (c[4:8] == 150).all()
    step = BIAS_UPDATE_SPEED * np.sign(c.mean() - c)
    assert (step[4:8] == -np.float32(BIAS_UPDATE_SPEED)).all()
    assert (np.delete(step, range(4, 8)) == np.float32(
        BIAS_UPDATE_SPEED)).all()
    # without a bias the layer counts nothing over the full width
    _y, plain = moe.routed_experts(u, router, w1, w3, w2, share, top_k=4)
    assert "counts_all" not in plain


def _layer_params(key, lm, experts):
    d, f = lm["hidden_size"], lm["moe_intermediate_size"]
    heads, rq, rkv = (lm["num_attention_heads"], lm["q_lora_rank"],
                      lm["kv_lora_rank"])
    nope, rope, dv = (lm["qk_nope_head_dim"], lm["qk_rope_head_dim"],
                      lm["v_head_dim"])
    k = jax.random.split(key, 16)
    n = lambda i, *s: jax.random.normal(k[i], s) * s[0] ** -0.5  # noqa: E731
    return {
        "attn": {"norm": jnp.ones(d), "wdq": n(0, d, rq),
                 "q_norm": jnp.ones(rq), "wuq": n(1, rq, heads * (nope + rope)),
                 "wdkv": n(2, d, rkv + rope), "kv_norm": jnp.ones(rkv),
                 "wukv": n(3, rkv, heads * (nope + dv)),
                 "wo": n(4, heads * dv, d)},
        "moe": {"norm": jnp.ones(d), "router": n(5, d, experts),
                "experts_w1": n(6, d, experts * f).reshape(
                    d, experts, f).swapaxes(0, 1),
                "experts_w3": n(7, d, experts * f).reshape(
                    d, experts, f).swapaxes(0, 1),
                "experts_w2": n(8, f, experts * d).reshape(
                    f, experts, d).swapaxes(0, 1),
                "shared_w1": n(9, d, f), "shared_w3": n(10, d, f),
                "shared_w2": n(11, f, d)}}


def test_all_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """8 expert shares x 8 experts of one 64-expert layer under a seeded
    bias: attention (whole on every rank) and the shared expert counted
    once, the program's routed parts summed, give the uncut reference's
    layer."""
    lm = dict(LM, n_routed_experts=64, num_experts_per_tok=4)
    whole = _layer_params(jax.random.PRNGKey(3), lm, 64)
    x = jax.random.normal(jax.random.PRNGKey(4), (40, lm["hidden_size"]))
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(6), (64,))
    want = R.layer_forward(whole, lm, R.whole_share(lm), x, bias)

    eps = lm["rms_norm_eps"]
    h = x + R.attention(whole["attn"], lm,
                        R.rms_norm(x, whole["attn"]["norm"], eps))
    m = whole["moe"]
    um = R.rms_norm(h, m["norm"], eps)
    routed, counts = 0.0, []
    for r in range(8):
        share = LayerShare(64, 8, 8 * r, 1, 1, 0, 64, 64, 0)
        held = slice(8 * r, 8 * r + 8)
        y, stats = moe.routed_experts(
            um, m["router"], m["experts_w1"][held], m["experts_w3"][held],
            m["experts_w2"][held], share, top_k=4, scale=1.8,
            scoring="sigmoid", bias=bias)
        routed = routed + y
        counts.append(np.asarray(stats["counts_all"]))
        assert float(stats["dense_steps"]) == 0.0
        # the reference given the same share computes the same part
        part = R.moe(m | {k: m[k][held] for k in (
            "experts_w1", "experts_w3", "experts_w2")}, lm,
            {"expert_offset": 8 * r}, um, bias, shared=False)
        np.testing.assert_allclose(y, part, atol=2e-5)
    shared = R.gated_mlp(um, m["shared_w1"], m["shared_w3"], m["shared_w2"])
    np.testing.assert_allclose(h + routed + shared, want, atol=2e-5)
    # every rank counts the same slots over the router's full width
    assert all(np.array_equal(c, counts[0]) for c in counts)
    assert counts[0].sum() == 40 * 4


def test_second_head_mask_at_graph_ends_and_across_boundaries(batch, docs):
    """In the packed batch node i+1 may be another graph's first node: the
    module reads a next id only inside the graph, its label is -1 on a
    graph's last TWO nodes, and changing a graph's ids moves no other
    graph's logits."""
    labels = np.asarray(batch.labels[1])[:, 0]
    ends = np.cumsum(DOC_LENGTHS)
    assert (labels[ends - 1] == -1).all() and (labels[ends - 2] == -1).all()
    assert (labels[:ends[0] - 2] == docs[0][2:]).all()
    assert (labels[sum(DOC_LENGTHS):] == 0).all()      # padding: masked
    cfg = ModelConfig.from_config(nn_section())
    model = create_model(cfg)
    params, stats = seeded(model, batch)

    def logits(b):
        return model.apply({"params": params, "batch_stats": stats}, b,
                           train=False)

    base = logits(batch)
    # another second graph (nodes 5..24): graphs 0, 2 and 3 do not move
    other = np.asarray(batch.x).copy()
    other[5:25, 0] = (other[5:25, 0] + 7) % 64
    moved = logits(batch.replace(x=jnp.asarray(other)))
    keep = np.r_[0:5, 25:40]
    for a, b in zip(base, moved):
        np.testing.assert_allclose(a[keep], b[keep], atol=1e-6)
        assert float(jnp.max(jnp.abs(a[5:25] - b[5:25]))) > 1e-3
    # the loss of head 2 counts exactly the nodes with a second successor
    from hydragnn_tpu.models.layers import loss_function

    mask = batch.node_mask * (labels >= 0)
    assert float(jnp.sum(mask)) == sum(n - 2 for n in DOC_LENGTHS)
    got = loss_function("softmax_xent")(base[1], batch.labels[1],
                                        batch.node_mask)
    logp = jax.nn.log_softmax(base[1], axis=-1)
    want = -jnp.sum(jnp.take_along_axis(
        logp, jnp.maximum(labels, 0).astype(jnp.int32)[:, None],
        axis=-1)[:, 0] * mask) / jnp.sum(mask)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_config_rejects_the_forms_it_does_not_compute():
    cfg = ModelConfig.from_config(nn_section())
    assert isinstance(cfg.lm, GlmMoeLiteConfig)
    assert cfg.lm.expert_layers == EXPERT_LAYERS
    assert cfg.share.experts_held == 4 and cfg.share.kv_heads_held == 3
    assert cfg.share.kv_heads_total == 3         # nothing cut from the heads
    for key, bad in (("topk_method", "greedy"), ("n_group", 2),
                     ("num_key_value_heads", 1),
                     ("rope_scaling", {"type": "yarn"}),
                     ("num_nextn_predict_layers", 2)):
        with pytest.raises(ValueError, match="GlmMoeLite"):
            ModelConfig.from_config(nn_section(lm=dict(LM, **{key: bad})))
    import dataclasses

    with pytest.raises(ValueError, match="Architecture.glm_moe_lite"):
        create_model(dataclasses.replace(cfg, lm=None))


def test_the_two_copies_of_the_reference_are_one_file():
    assert filecmp.cmp(
        os.path.join(REPO, "hydragnn_tpu", "models",
                     "glm_moe_lite_reference.py"),
        os.path.join(REPO, "benchmark", "reference",
                     "glm_moe_lite_reference.py"), shallow=False)
    assert len(R.ASSUMED) >= 6


def test_reference_rows_in_blocks_are_the_rows_at_once():
    p = _layer_params(jax.random.PRNGKey(0), LM, 4)["attn"]
    u = jax.random.normal(jax.random.PRNGKey(1), (32, 32))

    def f(q_block):
        return jax.value_and_grad(lambda u: jnp.sum(jnp.sin(
            R.attention(p, LM, u, q_block=q_block))))(u)

    (a, ga), (b, gb) = f(None), f(8)
    np.testing.assert_allclose(a, b, rtol=1e-6)
    np.testing.assert_allclose(ga, gb, atol=2e-6)


def test_reference_padding_moves_neither_loss_nor_gradient(docs, batch):
    """The benchmark pads every document to one length: the same losses."""
    cfg = ModelConfig.from_config(nn_section())
    params, stats = seeded(create_model(cfg), batch)
    a = R.loss_and_grads(params, LM, SHARE, biases_of(stats), docs, WEIGHT)
    b = R.loss_and_grads(params, LM, SHARE, biases_of(stats), docs, WEIGHT,
                         q_block=8, pad_to=lambda n: 24)
    assert a[0] == pytest.approx(b[0], rel=1e-6)
    assert a[1] == pytest.approx(b[1], rel=1e-6)
    for x, y in zip(jax.tree.leaves(a[2]), jax.tree.leaves(b[2])):
        np.testing.assert_allclose(x, y, atol=2e-6)


def _json_config(num_epoch):
    return {
        "Verbosity": {"level": 0},
        "Dataset": {
            "name": "docs_tiny_mtp", "format": "tokens",
            "path": {"total": "dataset/docs_tiny_mtp"},
            "node_features": {
                "name": ["token_id", "next_token_id", "next_next_token_id"],
                "dim": [1, 1, 1], "column_index": [0, 1, 2]}},
        "NeuralNetwork": {
            "Architecture": {
                "model_type": "GlmMoeLite", "hidden_dim": 32,
                "num_conv_layers": 3, "glm_moe_lite": LM, "share": SHARE,
                "output_heads": {}, "task_weights": [1.0, WEIGHT]},
            "Variables_of_interest": {
                "input_node_features": [0], "output_index": [1, 2],
                "type": ["node", "node"],
                "output_names": ["next_token_id", "next_next_token_id"],
                "denormalize_output": False},
            "Training": {
                "num_epoch": num_epoch, "batch_size": 4, "perc_train": 0.8,
                "loss_function_type": "softmax_xent",
                "Optimizer": {"type": "AdamW", "learning_rate": 3e-3}}},
        "Telemetry": {"enable": 1, "sinks": "jsonl"},
        "Visualization": {"create_plots": False},
    }


def test_json_config_trains_both_heads_and_the_checkpoint_carries_the_bias(
        tmp_path, monkeypatch):
    """Token files -> run_training on the stock loop's resident scan-K
    path: the loss falls, both heads are reported, the bias has moved, and
    it comes back from the pickle and from an orbax checkpoint."""
    import hydragnn_tpu
    from hydragnn_tpu.train.trainer import load_state
    from hydragnn_tpu.utils.checkpoint import (
        close_managers, restore_checkpoint, save_checkpoint)

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("SERIALIZED_DATA_PATH", str(tmp_path))
    monkeypatch.setenv("HYDRAGNN_RESIDENT_DATASET", "1")
    rng = np.random.default_rng(1)
    table = rng.integers(0, 64, size=64)
    os.makedirs(tmp_path / "dataset" / "docs_tiny_mtp")
    for d, n in enumerate(rng.integers(4, 30, size=80)):
        ids = [int(rng.integers(64))]
        for _ in range(n - 1):       # a fixed successor: learnable
            ids.append(int(table[ids[-1]]))
        (tmp_path / "dataset" / "docs_tiny_mtp" / f"doc{d:03d}.txt"
         ).write_text(" ".join(map(str, ids)))
    config = _json_config(num_epoch=8)
    state, history, final = hydragnn_tpu.run_training(
        config, logs_dir=str(tmp_path / "logs"))
    train = [float(v) for v in history["train"]]
    assert train[-1] < 0.8 * train[0]
    import glob
    import json

    events = []
    for path in glob.glob(str(tmp_path / "logs" / "**" / "events.jsonl"),
                          recursive=True):
        with open(path) as f:
            events += [json.loads(ln) for ln in f if ln.strip()]
    tasks = np.asarray([e["train_tasks"] for e in events
                        if e.get("event") == "epoch"], np.float64)
    assert tasks.shape == (8, 2)            # both heads, every epoch
    assert (tasks[-1] < tasks[0]).all()
    assert history["pipeline"]["resident"] is True
    assert history["pipeline"]["steps_per_dispatch"] >= 2
    arch = final["NeuralNetwork"]["Architecture"]
    assert arch["max_graph_nodes"] == 29 and arch["output_dim"] == [1, 1]
    bias = {k: np.asarray(v) for k, v in state.batch_stats.items()
            if k.startswith("bias_")}
    assert sorted(bias) == [f"bias_{n}" for n in sorted(EXPERT_LAYERS)]
    steps = int(state.step)
    for b in bias.values():     # moved, by whole steps of the speed
        assert 0 < np.abs(b).max() <= steps * BIAS_UPDATE_SPEED * 1.001
    skeleton = jax.tree.map(jnp.zeros_like, state)
    log_name = os.path.basename(os.path.dirname(glob.glob(
        str(tmp_path / "logs" / "*" / "*.pk"))[0]))
    from_pickle = load_state(skeleton, log_name, str(tmp_path / "logs"))
    save_checkpoint(state, str(tmp_path / "orbax"))
    from_orbax = restore_checkpoint(skeleton, str(tmp_path / "orbax"))
    close_managers()
    for restored in (from_pickle, from_orbax):
        for k, b in bias.items():
            if restored is from_orbax:
                assert np.array_equal(np.asarray(restored.batch_stats[k]), b)
            else:       # the pickle is the best epoch's state
                assert np.abs(np.asarray(restored.batch_stats[k])).max() > 0


def test_token_files_give_as_many_successor_columns_as_the_config_names(
        tmp_path):
    from hydragnn_tpu.data.raw import TokenDataset

    ds = TokenDataset.__new__(TokenDataset)
    ds.graph_feature_dim = []
    path = tmp_path / "doc.txt"
    path.write_text("7 8 9 10")
    ds.node_feature_dim = [1, 1]
    two = ds.transform_file(str(path)).x
    assert two.tolist() == [[7, 8], [8, 9], [9, 10], [10, -1]]
    ds.node_feature_dim = [1, 1, 1]
    three = ds.transform_file(str(path)).x
    assert three.tolist() == [[7, 8, 9], [8, 9, 10], [9, 10, -1],
                              [10, -1, -1]]
    path.write_text("5")
    assert ds.transform_file(str(path)).x.tolist() == [[5, -1, -1]]
