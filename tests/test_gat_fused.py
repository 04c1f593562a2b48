"""One-pass fused GATv2 attention (ops/gat_mp.py) vs the composed segment-op
path: forward parity, gradient parity, dropout-bit parity, and model-level
equivalence — interpret mode on CPU, same collate invariants as production.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hydragnn_tpu.graph import segment
from hydragnn_tpu.graph.batch import GraphSample, HeadSpec, PadSpec, collate
from hydragnn_tpu.graph.neighborlist import radius_graph
from hydragnn_tpu.ops.aggregate import backend_scope
from hydragnn_tpu.ops.gat_mp import gat_edge_attention


H, F = 4, 8
SLOPE = 0.05


def _batch(n_graphs=6, nodes=9, seed=0):
    rng = np.random.RandomState(seed)
    samples = []
    for _ in range(n_graphs):
        pos = rng.rand(nodes, 3).astype(np.float32) * 2.2
        samples.append(GraphSample(
            x=rng.rand(nodes, 2).astype(np.float32), pos=pos,
            edge_index=radius_graph(pos, 1.4, 8),
            graph_y=rng.rand(1).astype(np.float32)))
    pad = PadSpec.for_batch(n_graphs, nodes,
                            max(s.num_edges for s in samples))
    # collate attaches edge_perm_sender only under the fused backend
    with backend_scope("fused"):
        return collate(samples, pad, [HeadSpec("e", "graph", 1)])


def _inputs(g, seed=1):
    rng = np.random.RandomState(seed)
    n = g.x.shape[0]
    xl = jnp.asarray(rng.randn(n, H * F), jnp.float32)
    xr = jnp.asarray(rng.randn(n, H * F), jnp.float32)
    att = jnp.asarray(rng.randn(H, F), jnp.float32) * 0.5
    rows = jnp.arange(H * F)
    att_mat = jnp.zeros((H * F, H), jnp.float32).at[rows, rows // F].set(
        att.reshape(-1))
    return xl, xr, att, att_mat


def _reference_partials(xl, xr, att, g, b_edge):
    """Composed-op computation of (acc, m, d) as defined by the kernel:
    real incident edges only, numerator carries the dropout bits."""
    n = xl.shape[0]
    src, dst = g.senders, g.receivers
    z = jax.nn.leaky_relu(xl[src] + xr[dst], SLOPE)
    e = jnp.sum(z.reshape(-1, H, F) * att[None], axis=-1)      # [E, H]
    e = jnp.where(g.edge_mask[:, None] > 0, e, -1e30)
    m = segment.segment_max(e, dst, n)                          # 0 if empty
    deg = segment.degree(dst, n, g.edge_mask)
    m = jnp.where(deg[:, None] > 0, m, -1e30)
    # production's composed path stop-gradients the max shift too
    # (models/gat.py) — shift invariance makes this exact
    m = jax.lax.stop_gradient(m)
    p = jnp.exp(e - m[dst]) * g.edge_mask[:, None]
    d = jax.ops.segment_sum(p, dst, n)
    pb = p * b_edge
    w = jnp.repeat(pb, F, axis=1)
    acc = jax.ops.segment_sum(xl[src] * w, dst, n)
    return acc, m, d


def test_fused_forward_matches_composed():
    g = _batch()
    xl, xr, att, att_mat = _inputs(g)
    b = jnp.ones((g.senders.shape[0], H), jnp.float32)
    acc, m, d = gat_edge_attention(
        xl, xr, att_mat, g.senders, g.receivers,
        g.extras["edge_perm_sender"], g.edge_mask, b, (SLOPE, F))
    acc_r, m_r, d_r = _reference_partials(xl, xr, att, g, b)
    deg = np.asarray(segment.degree(g.receivers, xl.shape[0], g.edge_mask))
    has = deg > 0
    np.testing.assert_allclose(np.asarray(m)[has], np.asarray(m_r)[has],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(d)[has], np.asarray(d_r)[has],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(acc_r),
                               rtol=1e-4, atol=1e-4)


def test_fused_forward_dropout_bits():
    g = _batch(seed=3)
    xl, xr, att, att_mat = _inputs(g, seed=4)
    rng = np.random.RandomState(7)
    b = jnp.asarray(
        (rng.rand(g.senders.shape[0], H) > 0.3).astype(np.float32) / 0.7)
    acc, m, d = gat_edge_attention(
        xl, xr, att_mat, g.senders, g.receivers,
        g.extras["edge_perm_sender"], g.edge_mask, b, (SLOPE, F))
    acc_r, _, d_r = _reference_partials(xl, xr, att, g, b)
    # d ignores dropout (softmax-then-dropout); acc carries the bits
    np.testing.assert_allclose(np.asarray(acc), np.asarray(acc_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(d)[np.asarray(d_r) > 0],
        np.asarray(d_r)[np.asarray(d_r) > 0], rtol=1e-5, atol=1e-5)


def _merge_loss(acc, m, d, xl):
    """The production-style self-loop merge (models/gat.py): SHIFT-INVARIANT
    in m, which is what makes stop_gradient(m) exact — a non-invariant
    normalization (e.g. acc / max(d, 1)) would make the frozen-m gradient
    genuinely differ from autodiff-through-segment_max."""
    m = jax.lax.stop_gradient(m)
    m_t = jax.lax.stop_gradient(jnp.maximum(m, 0.0))  # e_self = 0
    r_e = jnp.exp(m - m_t)
    r_s = jnp.exp(-m_t)
    d_t = d * r_e + r_s
    out = (acc * jnp.repeat(r_e, F, axis=1)
           + jnp.repeat(r_s, F, axis=1) * xl) / jnp.repeat(d_t, F, axis=1)
    w = jnp.arange(out.size, dtype=jnp.float32).reshape(out.shape) * 1e-3
    return jnp.sum(out * w)


def _loss_fused(xl, xr, att_mat, g, b):
    acc, m, d = gat_edge_attention(
        xl, xr, att_mat, g.senders, g.receivers,
        g.extras["edge_perm_sender"], g.edge_mask, b, (SLOPE, F))
    return _merge_loss(acc, m, d, xl)


def _loss_composed(xl, xr, att_mat, g, b):
    att = att_mat[jnp.arange(H * F), jnp.arange(H * F) // F].reshape(H, F)
    acc, m, d = _reference_partials(xl, xr, att, g, b)
    return _merge_loss(acc, m, d, xl)


def test_fused_gradients_match_composed():
    g = _batch(seed=5)
    xl, xr, att, att_mat = _inputs(g, seed=6)
    rng = np.random.RandomState(11)
    b = jnp.asarray(
        (rng.rand(g.senders.shape[0], H) > 0.2).astype(np.float32) / 0.8)
    gf = jax.grad(_loss_fused, argnums=(0, 1, 2))(xl, xr, att_mat, g, b)
    gc = jax.grad(_loss_composed, argnums=(0, 1, 2))(xl, xr, att_mat, g, b)
    # tolerance sized for the CPU backend's reduced-precision (oneDNN)
    # matmuls that both implementations ride in interpret mode
    for a, bb, name in zip(gf[:2], gc[:2], ("dxl", "dxr")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(bb), rtol=2e-3, atol=2e-3,
            err_msg=name)
    # att_mat grad: only the block-diagonal entries reach the att
    # parameter (the model builds att_mat by scattering att onto the
    # diagonal); the kernel's dense cotangent legitimately carries
    # off-diagonal sensitivities the composed extraction zeroes
    rows = np.arange(H * F)
    np.testing.assert_allclose(
        np.asarray(gf[2])[rows, rows // F],
        np.asarray(gc[2])[rows, rows // F],
        rtol=2e-3, atol=2e-3, err_msg="datt diagonal")


def test_model_level_gradients_match(monkeypatch):
    """Full GATStack param gradients: fused vs composed (dropout off)."""
    from hydragnn_tpu.models.base import GraphHeadCfg, ModelConfig
    from hydragnn_tpu.models.create import create_model

    g = _batch(seed=9)
    cfg = ModelConfig(
        model_type="GAT", input_dim=2, hidden_dim=8, output_dim=(1,),
        output_type=("graph",), graph_head=GraphHeadCfg(1, 8, 1, (8,)),
        node_head=None, task_weights=(1.0,), num_conv_layers=2,
        dropout=0.0)
    model = create_model(cfg)
    monkeypatch.setenv("HYDRAGNN_GAT_FUSED", "1")
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        g, train=False)

    def loss(params, train):
        out = model.apply(
            {"params": params, "batch_stats": variables.get("batch_stats", {})},
            g, train=train,
            rngs={"dropout": jax.random.PRNGKey(2)} if train else None,
            mutable=["batch_stats"] if train else False)
        out = out[0] if train else out
        return sum(jnp.sum(o * o) for o in out)

    gf = jax.grad(lambda p: loss(p, True))(variables["params"])
    monkeypatch.setenv("HYDRAGNN_GAT_FUSED", "0")
    gp = jax.grad(lambda p: loss(p, True))(variables["params"])
    flat_f = jax.tree_util.tree_leaves_with_path(gf)
    flat_p = dict(jax.tree_util.tree_leaves_with_path(gp))
    for path, leaf in flat_f:
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat_p[path]), rtol=3e-3, atol=3e-3,
            err_msg=str(path))


def test_model_level_fused_equals_composed(monkeypatch):
    """Full GATStack forward: fused path (env-forced on) vs composed path
    (env-forced off) on the same params/batch must agree in eval mode."""
    from hydragnn_tpu.models.base import GraphHeadCfg, ModelConfig
    from hydragnn_tpu.models.create import create_model

    g = _batch(seed=8)
    cfg = ModelConfig(
        model_type="GAT", input_dim=2, hidden_dim=8, output_dim=(1,),
        output_type=("graph",), graph_head=GraphHeadCfg(1, 8, 1, (8,)),
        node_head=None, task_weights=(1.0,), num_conv_layers=2,
        dropout=0.0)
    model = create_model(cfg)
    monkeypatch.setenv("HYDRAGNN_GAT_FUSED", "1")
    params = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        g, train=False)
    out_fused = model.apply(params, g, train=False)
    monkeypatch.setenv("HYDRAGNN_GAT_FUSED", "0")
    out_plain = model.apply(params, g, train=False)
    for a, b in zip(out_fused, out_plain):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_tiled_matches_untiled(monkeypatch):
    """gat_edge_attention_tiled with a forced-small FUSED_HF_LIMIT (heads
    split into groups) must reproduce the one-call kernel: attention is
    independent per head, so the group slicing changes launches, not
    math — forward partials AND gradients."""
    import hydragnn_tpu.ops.gat_mp as gat_mp
    from hydragnn_tpu.ops.gat_mp import gat_edge_attention_tiled

    g = _batch(seed=13)
    xl, xr, att, att_mat = _inputs(g, seed=14)
    b = jnp.ones((g.senders.shape[0], H), jnp.float32)
    perm = g.extras["edge_perm_sender"]

    ref = gat_edge_attention(xl, xr, att_mat, g.senders, g.receivers,
                             perm, g.edge_mask, b, (SLOPE, F))
    assert H * F > 2 * F  # the forced limit below actually splits
    monkeypatch.setattr(gat_mp, "FUSED_HF_LIMIT", 2 * F)
    assert gat_mp._head_groups(H, F) == [2, 2]
    tiled = gat_edge_attention_tiled(
        xl, xr, att_mat, g.senders, g.receivers, perm, g.edge_mask, b,
        (SLOPE, F))
    for a, r, name in zip(tiled, ref, ("acc", "m", "d")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=1e-4, err_msg=name)

    def loss_tiled(xl_, xr_, am_):
        acc, m, d = gat_edge_attention_tiled(
            xl_, xr_, am_, g.senders, g.receivers, perm, g.edge_mask, b,
            (SLOPE, F))
        return _merge_loss(acc, m, d, xl_)

    gt = jax.grad(loss_tiled, argnums=(0, 1, 2))(xl, xr, att_mat)
    monkeypatch.setattr(gat_mp, "FUSED_HF_LIMIT", 1024)
    gu = jax.grad(loss_tiled, argnums=(0, 1, 2))(xl, xr, att_mat)
    for a, r, name in zip(gt[:2], gu[:2], ("dxl", "dxr")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-3, atol=2e-3, err_msg=name)
    # datt: compare the block-diagonal entries only — the one-call kernel
    # returns dense cotangents for att_mat's structurally-zero cross-group
    # entries that the tiled slicing (correctly) never touches, and the
    # model consumes only the diagonal (see test_fused_gradients_match_
    # composed)
    rows = np.arange(H * F)
    np.testing.assert_allclose(
        np.asarray(gt[2])[rows, rows // F],
        np.asarray(gu[2])[rows, rows // F],
        rtol=2e-3, atol=2e-3, err_msg="datt diagonal")


def test_wide_heads_stay_fused_via_head_tiling(monkeypatch):
    """hf = heads*hidden above FUSED_HF_LIMIT now STAYS on the fused path
    by tiling over balanced head groups (the pre-tiling behavior was a
    silent composed-path fallback at h256 x 6 heads — the GAT item of
    round-5 VERDICT weak-2) and must match the composed path numerically.
    The limit is monkeypatched small so the tier-1 test exercises the
    tiled path at toy width."""
    import hydragnn_tpu.ops.gat_mp as gat_mp
    from hydragnn_tpu.models.base import GraphHeadCfg, ModelConfig
    from hydragnn_tpu.models.create import create_model
    from hydragnn_tpu.models.gat import GATv2Conv

    calls = []
    orig = GATv2Conv._fused_attention

    def spy(self, *a, **k):
        calls.append(self.out_dim)
        return orig(self, *a, **k)

    monkeypatch.setattr(GATv2Conv, "_fused_attention", spy)
    monkeypatch.setenv("HYDRAGNN_GAT_FUSED", "1")
    # hidden=8 x 6 heads = hf 48 > 16 = limit -> 3 groups of 2 heads;
    # f=8 <= 16 keeps the per-head gate satisfied.  ONE patch point:
    # the dispatcher queries gat_mp's live limit (fused_head_width_ok)
    monkeypatch.setattr(gat_mp, "FUSED_HF_LIMIT", 16)

    g = _batch(seed=11)
    cfg = ModelConfig(
        model_type="GAT", input_dim=2, hidden_dim=8, output_dim=(1,),
        output_type=("graph",), graph_head=GraphHeadCfg(1, 8, 1, (8,)),
        node_head=None, task_weights=(1.0,), num_conv_layers=2,
        dropout=0.0)
    model = create_model(cfg)
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        g, train=False)
    out_fused = model.apply(
        {"params": variables["params"],
         "batch_stats": variables.get("batch_stats", {})}, g, train=False)
    assert calls, "wide config must stay on the fused (tiled) path"
    monkeypatch.setenv("HYDRAGNN_GAT_FUSED", "0")
    out_plain = model.apply(
        {"params": variables["params"],
         "batch_stats": variables.get("batch_stats", {})}, g, train=False)
    for a, bb in zip(out_fused, out_plain):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=5e-4, atol=5e-4)


def test_single_over_wide_head_falls_back(monkeypatch):
    """Only a SINGLE head wider than FUSED_HF_LIMIT still forces the
    composed path (no group can shrink below one head)."""
    import hydragnn_tpu.ops.gat_mp as gat_mp
    from hydragnn_tpu.models.base import GraphHeadCfg, ModelConfig
    from hydragnn_tpu.models.create import create_model
    from hydragnn_tpu.models.gat import GATv2Conv

    calls = []
    orig = GATv2Conv._fused_attention

    def spy(self, *a, **k):
        calls.append(self.out_dim)
        return orig(self, *a, **k)

    monkeypatch.setattr(GATv2Conv, "_fused_attention", spy)
    monkeypatch.setenv("HYDRAGNN_GAT_FUSED", "1")
    monkeypatch.setattr(gat_mp, "FUSED_HF_LIMIT", 4)  # < f = 8

    g = _batch(seed=12)
    cfg = ModelConfig(
        model_type="GAT", input_dim=2, hidden_dim=8, output_dim=(1,),
        output_type=("graph",), graph_head=GraphHeadCfg(1, 8, 1, (8,)),
        node_head=None, task_weights=(1.0,), num_conv_layers=2,
        dropout=0.0)
    model = create_model(cfg)
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        g, train=False)
    out = model.apply(
        {"params": variables["params"],
         "batch_stats": variables.get("batch_stats", {})}, g, train=False)
    assert np.all(np.isfinite(np.asarray(out[0])))
    assert calls == []  # every layer stayed on the composed path
