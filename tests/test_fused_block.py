"""One parity harness for the fused message-passing stack — every arch in
models/create.py:ALL_ARCHS against the composed XLA twin, plus kernel-level
parity for each spec on the fused-block builder (ops/fused_block.py) and
the shared schedule kernels it grew out of.

Collapses the former per-kernel suites (test_poly_mp.py, test_egcl_mp.py,
test_fused_mp.py) onto one file: a newly registered arch lands in the
model-level parametrization automatically, and a new builder spec adds a
kernel-level section here rather than a new test file.

Sections:
  1. model-level fused-vs-scatter parity, parametrized over ALL_ARCHS
  2. poly multi-moment kernels (ops/poly_mp.py): PNA/MFC/SAGE moments
  3. EGCL interaction-block spec (ops/egcl_mp.py on the builder)
  4. CGCNN gated-sum spec (ops/cgcnn_mp.py on the builder)
  5. DimeNet triplet paths: legacy W-window and the builder-backed
     wide-dim route
  6. gather-mul / dense segment-sum schedule kernels (ops/fused_mp.py)
  7. collate invariants + trace-time dispatch tally

Interpret mode on CPU, production collate invariants throughout.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hydragnn_tpu.graph import segment
from hydragnn_tpu.graph.batch import GraphSample, HeadSpec, PadSpec, collate
from hydragnn_tpu.graph.neighborlist import radius_graph
from hydragnn_tpu.models.base import GraphHeadCfg, ModelConfig
from hydragnn_tpu.models.create import ALL_ARCHS, create_model
from hydragnn_tpu.ops.aggregate import backend_scope
from hydragnn_tpu.ops.egcl_mp import egcl_block
from hydragnn_tpu.ops.fused_mp import gather_mul_segment_sum
from hydragnn_tpu.ops.poly_mp import gather_poly_segment, segment_poly_dense

_BIG = 1e9
ALL_MOMENTS = ("sum", "sq", "mxmn", "cnt")


# ---------------------------------------------------------------------------
# shared batch builders
# ---------------------------------------------------------------------------


def _batch(n_graphs=24, max_nodes=16, seed=0, max_neigh=10):
    rng = np.random.RandomState(seed)
    samples = []
    for _ in range(n_graphs):
        n = int(rng.randint(3, max_nodes + 1))
        pos = rng.rand(n, 3).astype(np.float32) * 2.5
        x = rng.rand(n, 2).astype(np.float32)
        ei = radius_graph(pos, 1.4, max_neigh)
        samples.append(GraphSample(x=x, pos=pos, edge_index=ei,
                                   graph_y=np.ones(1, np.float32), node_y=x))
    pad = PadSpec.for_batch(n_graphs, max_nodes, max_nodes * max_neigh)
    return collate(samples, pad, [HeadSpec("e", "graph", 1)])


def _edge_data(b, f=48, seed=1, quantize=False):
    rng = np.random.RandomState(seed)
    e = b.senders.shape[0]
    data = rng.randn(e, f).astype(np.float32)
    if quantize:
        # coarse grid -> deliberate within-segment ties, exercising the
        # even tie-split of the max/min gradient
        data = np.round(data * 2.0) / 2.0
    return jnp.asarray(data)


def _sender_perm(b):
    return jnp.asarray(np.argsort(np.asarray(b.senders), kind="stable"),
                       jnp.int32)


# ---------------------------------------------------------------------------
# 1. model-level parity: every arch, fused backend vs composed scatter
# ---------------------------------------------------------------------------

# one seed per arch, kept from the per-arch suites this file collapsed so
# the graphs (and any historically tuned tolerances) are unchanged
_ARCH_SEED = {"SchNet": 5, "DimeNet": 13}


def _model_cfg(model_type):
    kw = dict(
        model_type=model_type, input_dim=1,
        # CGCNN's conv is dim-preserving: hidden_dim forced = input_dim
        hidden_dim=1 if model_type == "CGCNN" else 16,
        output_dim=(1,), output_type=("graph",),
        graph_head=GraphHeadCfg(1, 16, 1, (16,)), node_head=None,
        task_weights=(1.0,), num_conv_layers=2,
        max_degree=16, max_neighbours=16,
        pna_avg_deg_log=1.1, pna_avg_deg_lin=3.0)
    if model_type == "SchNet":
        kw.update(num_gaussians=8, num_filters=16, radius=1.4,
                  max_neighbours=10)
    elif model_type == "DimeNet":
        kw.update(hidden_dim=8, graph_head=GraphHeadCfg(1, 8, 1, (8,)),
                  basis_emb_size=4, envelope_exponent=5, int_emb_size=4,
                  out_emb_size=4, num_after_skip=1, num_before_skip=1,
                  num_radial=4, num_spherical=3, radius=1.4,
                  max_neighbours=10)
    elif model_type == "EGNN":
        kw.update(equivariance=True, radius=1.4, max_neighbours=10)
    return ModelConfig(**kw)


def _model_batch(model_type, seed):
    b = _batch(seed=seed)
    if model_type == "DimeNet":
        from hydragnn_tpu.models.dimenet import add_dimenet_extras

        b = add_dimenet_extras(b, max_triplets=4096)
    return b


@pytest.mark.parametrize("model_type", ALL_ARCHS)
def test_model_fused_matches_scatter(model_type, monkeypatch):
    """Full forward + param grads under HYDRAGNN_AGGR_BACKEND=fused must
    match the composed scatter path for EVERY registered arch — the
    kernels are exact, not approximate.  (bench.py's sweep derives from
    the same ALL_ARCHS list, so a new arch lands in both at once.)"""
    cfg = _model_cfg(model_type)
    model = create_model(cfg)
    seed = _ARCH_SEED.get(model_type, 9)

    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "fused")
    b_fused = _model_batch(model_type, seed)
    assert "edge_perm_sender" in b_fused.extras
    v = model.init({"params": jax.random.PRNGKey(0),
                    "dropout": jax.random.PRNGKey(1)}, b_fused, train=False)

    def loss(params, b):
        out = model.apply({"params": params,
                           "batch_stats": v.get("batch_stats", {})},
                          b, train=False)
        return jnp.sum(out[0] ** 2)

    lf = float(loss(v["params"], b_fused))
    gf = jax.grad(loss)(v["params"], b_fused)

    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "scatter")
    b_plain = _model_batch(model_type, seed)
    lp = float(loss(v["params"], b_plain))
    gp = jax.grad(loss)(v["params"], b_plain)

    assert abs(lf - lp) < 1e-4 * max(1.0, abs(lp))
    for a, c in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-5)


def _eqns_with_stacks(jaxpr, prefix="", into_kernels=True):
    """Every equation of ``jaxpr`` and of the jaxprs inside it, each with
    its whole name stack (an inner equation's own is relative to the
    equation that holds it); ``into_kernels=False`` yields a
    ``pallas_call`` but not its body."""
    for eqn in jaxpr.eqns:
        own = str(eqn.source_info.name_stack)
        stack = "/".join(p for p in (prefix, own) if p)
        yield eqn, stack
        if eqn.primitive.name == "pallas_call" and not into_kernels:
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns_with_stacks(sub, stack, into_kernels)


@pytest.mark.parametrize("model_type", ALL_ARCHS)
def test_train_step_kernels_and_phases_are_named(model_type, monkeypatch):
    """What a device trace will call things: every ``pallas_call`` of a
    fused train step carries a declared kernel name, and the step's ops
    sit under the declared phases."""
    from hydragnn_tpu.analysis.registry import KERNEL_NAMES, SCOPE_NAMES
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.trainer import (
        create_train_state, make_train_step)

    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "fused")
    cfg = _model_cfg(model_type)
    model = create_model(cfg)
    batch = _model_batch(model_type, _ARCH_SEED.get(model_type, 9))
    opt = select_optimizer({"type": "AdamW", "learning_rate": 1e-3})
    state = jax.eval_shape(
        lambda b: create_train_state(model, b, opt), batch)
    step = make_train_step(model, cfg, opt, telemetry_metrics=True,
                           nonfinite_guard=True)
    eqns = list(_eqns_with_stacks(jax.make_jaxpr(step)(state, batch).jaxpr))
    kernels = [(e.params["name"], stack) for e, stack in eqns
               if e.primitive.name == "pallas_call"]
    assert kernels, "the fused backend traced no kernel"
    for name, stack in kernels:
        assert name in KERNEL_NAMES, (name, stack)
        assert "step.loss" in stack, (name, stack)
    # both passes are there, and the backward's kernels say so
    assert any("transpose(" in s for _n, s in kernels)
    assert any("transpose(" not in s for _n, s in kernels)
    phases = {m for _e, stack in eqns for m in stack.split("/")
              if m in SCOPE_NAMES}
    assert phases == {"step.loss", "step.optimizer", "step.metrics",
                      "step.guard"}


# ---------------------------------------------------------------------------
# 2. poly multi-moment kernels (ops/poly_mp.py)
# ---------------------------------------------------------------------------


def _refs(data, ids, mask, n):
    """Composed-path moments with the production masking conventions."""
    dm = data * mask[:, None]
    cat = jnp.concatenate([data, -data], axis=1)
    cat = jnp.where(mask[:, None] > 0, cat, -_BIG)
    mxmn = jax.ops.segment_max(cat, ids, num_segments=n)
    return {
        "sum": jax.ops.segment_sum(dm, ids, num_segments=n),
        "sq": jax.ops.segment_sum(dm * dm, ids, num_segments=n),
        "mxmn": mxmn,
        "cnt": jax.ops.segment_sum(mask, ids, num_segments=n),
    }


def test_scatter_forward_all_moments():
    b = _batch()
    data = _edge_data(b)
    ids, mask = jnp.asarray(b.receivers), jnp.asarray(b.edge_mask)
    n = b.x.shape[0]
    outs = segment_poly_dense(data, ids, n, ALL_MOMENTS, valid=mask)
    ref = _refs(data, ids, mask, n)
    np.testing.assert_allclose(outs[0], ref["sum"], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(outs[1], ref["sq"], rtol=1e-5, atol=1e-5)
    # empty segments: kernel yields -1e9, XLA's masked max too (both
    # pre-clean) — compare after the common clamp
    np.testing.assert_allclose(
        jnp.where(outs[2] <= -_BIG * 0.5, -_BIG, outs[2]),
        jnp.where(ref["mxmn"] <= -_BIG * 0.5, -_BIG, ref["mxmn"]),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(outs[3], ref["cnt"], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("quantize", [False, True],
                         ids=["distinct", "with-ties"])
def test_scatter_gradients_match_composed(quantize):
    """d(sum)/d(sq)/d(max)/d(min) vs the composed twin, including the
    even tie split jax.ops.segment_max's VJP applies."""
    b = _batch(seed=2)
    data = _edge_data(b, seed=3, quantize=quantize)
    ids, mask = jnp.asarray(b.receivers), jnp.asarray(b.edge_mask)
    n = b.x.shape[0]
    f = data.shape[1]

    def loss_fused(d):
        s, q, mxmn, cnt = segment_poly_dense(d, ids, n, ALL_MOMENTS,
                                             valid=mask)
        mx = jnp.where(mxmn[:, :f] <= -_BIG * 0.5, 0.0, mxmn[:, :f])
        mn = jnp.where(mxmn[:, f:] <= -_BIG * 0.5, 0.0, -mxmn[:, f:])
        return (jnp.sum(s ** 2) + 0.5 * jnp.sum(q ** 2)
                + jnp.sum(mx ** 2) + jnp.sum(mn ** 3) + jnp.sum(cnt))

    def loss_ref(d):
        r = _refs(d, ids, mask, n)
        mm = jnp.where(r["mxmn"] <= -_BIG * 0.5, 0.0, r["mxmn"])
        return (jnp.sum(r["sum"] ** 2) + 0.5 * jnp.sum(r["sq"] ** 2)
                + jnp.sum(mm[:, :f] ** 2) + jnp.sum((-mm[:, f:]) ** 3)
                + jnp.sum(r["cnt"]))

    g1 = jax.grad(loss_fused)(data)
    g2 = jax.grad(loss_ref)(data)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-4)
    # masked edges must carry EXACTLY zero gradient
    m = np.asarray(b.edge_mask)
    assert np.all(np.asarray(g1)[m == 0] == 0.0)


def test_gather_forward_and_gradients():
    """Gather mode (messages formed in-VMEM): all moments of x[senders]
    over real edges, fwd + dx vs the materialized composed twin."""
    b = _batch(seed=7)
    rng = np.random.RandomState(8)
    n = b.x.shape[0]
    f = 40
    x = jnp.asarray(rng.rand(n, f), jnp.float32)
    s, r = jnp.asarray(b.senders), jnp.asarray(b.receivers)
    mask = jnp.asarray(b.edge_mask)
    perm = _sender_perm(b)

    outs = gather_poly_segment(x, s, r, perm, ALL_MOMENTS, mask=mask)
    ref = _refs(x[s], r, mask, n)
    np.testing.assert_allclose(outs[0], ref["sum"], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(outs[1], ref["sq"], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        jnp.where(outs[2] <= -_BIG * 0.5, -_BIG, outs[2]),
        jnp.where(ref["mxmn"] <= -_BIG * 0.5, -_BIG, ref["mxmn"]),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(outs[3], ref["cnt"], rtol=1e-6, atol=1e-6)

    def loss_fused(x_):
        su, q, mxmn, cnt = gather_poly_segment(x_, s, r, perm, ALL_MOMENTS,
                                               mask=mask)
        mx = jnp.where(mxmn[:, :f] <= -_BIG * 0.5, 0.0, mxmn[:, :f])
        mn = jnp.where(mxmn[:, f:] <= -_BIG * 0.5, 0.0, -mxmn[:, f:])
        return (jnp.sum(su ** 2) + 0.5 * jnp.sum(q ** 2)
                + jnp.sum(mx ** 2) + jnp.sum(mn ** 3))

    def loss_ref(x_):
        rr = _refs(x_[s], r, mask, n)
        mm = jnp.where(rr["mxmn"] <= -_BIG * 0.5, 0.0, rr["mxmn"])
        return (jnp.sum(rr["sum"] ** 2) + 0.5 * jnp.sum(rr["sq"] ** 2)
                + jnp.sum(mm[:, :f] ** 2) + jnp.sum((-mm[:, f:]) ** 3))

    g1 = jax.grad(loss_fused)(x)
    g2 = jax.grad(loss_ref)(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-4)


def test_gather_sum_cnt_only():
    """The SAGE/MFC moment set (sum + cnt): forward and the one-pass
    fused backward (no [E, F] intermediate) vs the composed twin."""
    b = _batch(seed=9)
    rng = np.random.RandomState(10)
    n = b.x.shape[0]
    x = jnp.asarray(rng.rand(n, 32), jnp.float32)
    s, r = jnp.asarray(b.senders), jnp.asarray(b.receivers)
    mask = jnp.asarray(b.edge_mask)
    perm = _sender_perm(b)

    su, cnt = gather_poly_segment(x, s, r, perm, ("sum", "cnt"), mask=mask)
    np.testing.assert_allclose(
        su, jax.ops.segment_sum(x[s] * mask[:, None], r, num_segments=n),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        cnt, jax.ops.segment_sum(mask, r, num_segments=n),
        rtol=1e-6, atol=1e-6)
    # the neighbor-MEAN composition SAGE uses (max(cnt,1) divide)
    mean = su / jnp.maximum(cnt, 1.0)[:, None]
    np.testing.assert_allclose(
        mean, np.asarray(segment.gather_segment_mean(x, b)),
        rtol=1e-5, atol=1e-5)

    g1 = jax.grad(lambda x_: jnp.sum(gather_poly_segment(
        x_, s, r, perm, ("sum", "cnt"), mask=mask)[0] ** 2))(x)
    g2 = jax.grad(lambda x_: jnp.sum(jax.ops.segment_sum(
        x_[s] * mask[:, None], r, num_segments=n) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-4)


def test_all_masked_segment_yields_zero_moments():
    """A node with NO real in-edges (every slot masked) must read 0 for
    every cleaned moment — the segment_mean/max/min empty conventions."""
    b = _batch(seed=11)
    e = b.senders.shape[0]
    data = _edge_data(b, seed=12) + 5.0   # strictly positive: a leaked
    ids = jnp.asarray(b.receivers)        # masked max would be visibly > 0
    n = b.x.shape[0]
    mask = jnp.zeros((e,), jnp.float32)   # EVERYTHING masked
    s, q, mxmn, cnt = segment_poly_dense(data, ids, n, ALL_MOMENTS,
                                         valid=mask)
    assert np.all(np.asarray(s) == 0.0)
    assert np.all(np.asarray(q) == 0.0)
    assert np.all(np.asarray(cnt) == 0.0)
    f = data.shape[1]
    mx = jnp.where(mxmn[:, :f] <= -_BIG * 0.5, 0.0, mxmn[:, :f])
    mn = jnp.where(mxmn[:, f:] <= -_BIG * 0.5, 0.0, -mxmn[:, f:])
    assert np.all(np.asarray(mx) == 0.0)
    assert np.all(np.asarray(mn) == 0.0)


# ---------------------------------------------------------------------------
# 3. EGCL interaction-block spec (ops/egcl_mp.py on the builder)
# ---------------------------------------------------------------------------

F, H = 16, 24  # distinct feature/hidden widths catch f/h transpositions


def _egcl_batch(n_graphs=6, nodes=9, seed=0, isolate=False):
    rng = np.random.RandomState(seed)
    samples = []
    for i in range(n_graphs):
        pos = rng.rand(nodes, 3).astype(np.float32) * 2.2
        if isolate and i == 0:
            # empty segments: park two nodes far outside every cutoff so
            # they have NO incident edges (their agg/psum rows must read 0)
            pos[-2:] += 50.0
        samples.append(GraphSample(
            x=rng.rand(nodes, 2).astype(np.float32), pos=pos,
            edge_index=radius_graph(pos, 1.4, 8),
            graph_y=rng.rand(1).astype(np.float32)))
    pad = PadSpec.for_batch(n_graphs, nodes,
                            max(s.num_edges for s in samples))
    with backend_scope("fused"):
        return collate(samples, pad, [HeadSpec("e", "graph", 1)])


def _egcl_inputs(g, seed=1, edge_attr_dim=0):
    """Random op inputs; geo is [diff(3), radial(1), edge_attr(A)] with
    |diff| < 1 like the real normalized difference."""
    rng = np.random.RandomState(seed)
    n = g.x.shape[0]
    e = g.senders.shape[0]
    x = jnp.asarray(rng.randn(n, F), jnp.float32)
    gd = 4 + edge_attr_dim
    geo = jnp.asarray(rng.rand(e, gd) * 0.8, jnp.float32)
    w0 = jnp.asarray(rng.randn(2 * F + 1 + edge_attr_dim, H) * 0.3,
                     jnp.float32)
    b0 = jnp.asarray(rng.randn(H) * 0.1, jnp.float32)
    w1 = jnp.asarray(rng.randn(H, H) * 0.3, jnp.float32)
    b1 = jnp.asarray(rng.randn(H) * 0.1, jnp.float32)
    wc0 = jnp.asarray(rng.randn(H, H) * 0.3, jnp.float32)
    bc0 = jnp.asarray(rng.randn(H) * 0.1, jnp.float32)
    wc1 = jnp.asarray(rng.randn(H, 1) * 0.5, jnp.float32)
    return x, geo, w0, b0, w1, b1, wc0, bc0, wc1


def _egcl_composed(x, geo, mask, w0, b0, w1, b1, wc0, bc0, wc1,
                   senders, receivers, n, equivariant):
    """The composed-path math (models/egnn.py fallback route), on raw
    weights."""
    diff, feat = geo[:, :3], geo[:, 3:]
    m = jnp.concatenate([x[senders], x[receivers], feat], axis=-1)
    m = jax.nn.relu(m @ w0 + b0)
    m = jax.nn.relu(m @ w1 + b1)
    m = m * mask[:, None]
    agg = jax.ops.segment_sum(m, senders, num_segments=n)
    if not equivariant:
        return agg, None
    c = jax.nn.relu(m @ wc0 + bc0)
    c = jnp.tanh(c @ wc1)
    trans = jnp.clip(diff * c, -100.0, 100.0) * mask[:, None]
    psum = jax.ops.segment_sum(trans, senders, num_segments=n)
    return agg, psum


def _run_egcl_fused(g, args, equivariant):
    x, geo = args[0], args[1]
    em = jnp.asarray(g.edge_mask).astype(jnp.int32)
    perm = jnp.asarray(g.extras["edge_perm_sender"])
    if equivariant:
        return egcl_block(True, x, geo, em, *args[2:],
                          g.senders, g.receivers, perm)
    return egcl_block(False, x, geo, em, *args[2:6], None, None, None,
                      g.senders, g.receivers, perm)


def test_egcl_forward_matches_composed():
    g = _egcl_batch()
    args = _egcl_inputs(g)
    mask = jnp.asarray(g.edge_mask)
    agg, psum = _run_egcl_fused(g, args, True)
    ref_agg, ref_psum = _egcl_composed(args[0], args[1], mask, *args[2:],
                                       g.senders, g.receivers,
                                       args[0].shape[0], True)
    np.testing.assert_allclose(np.asarray(agg), np.asarray(ref_agg),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(psum[:, :3]),
                               np.asarray(ref_psum), rtol=1e-5, atol=1e-5)


def test_egcl_forward_non_equivariant():
    """Last-layer EGCL: no coordinate branch, message sum only."""
    g = _egcl_batch(seed=2)
    args = _egcl_inputs(g, seed=3)
    mask = jnp.asarray(g.edge_mask)
    agg, psum = _run_egcl_fused(g, args, False)
    assert psum is None
    ref_agg, _ = _egcl_composed(args[0], args[1], mask, *args[2:],
                                g.senders, g.receivers, args[0].shape[0],
                                False)
    np.testing.assert_allclose(np.asarray(agg), np.asarray(ref_agg),
                               rtol=1e-5, atol=1e-5)


def test_egcl_forward_empty_segments():
    """Nodes with no incident edges (isolated + padding slots) read
    exactly zero in both outputs."""
    g = _egcl_batch(seed=4, isolate=True)
    args = _egcl_inputs(g, seed=5)
    mask = jnp.asarray(g.edge_mask)
    agg, psum = _run_egcl_fused(g, args, True)
    ref_agg, ref_psum = _egcl_composed(args[0], args[1], mask, *args[2:],
                                       g.senders, g.receivers,
                                       args[0].shape[0], True)
    np.testing.assert_allclose(np.asarray(agg), np.asarray(ref_agg),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(psum[:, :3]),
                               np.asarray(ref_psum), rtol=1e-5, atol=1e-5)
    # the isolated nodes really have no edges (the scenario is live)
    deg = np.zeros(args[0].shape[0])
    np.add.at(deg, np.asarray(g.senders)[np.asarray(mask) > 0], 1.0)
    assert (deg == 0).any()
    assert np.all(np.asarray(agg)[deg == 0] == 0.0)


def _egcl_grad_parity(g, seed, equivariant, edge_attr_dim=0,
                      rtol=3e-4, atol=3e-4):
    args = _egcl_inputs(g, seed=seed, edge_attr_dim=edge_attr_dim)
    mask = jnp.asarray(g.edge_mask)
    n = args[0].shape[0]
    rng = np.random.RandomState(seed + 70)
    wa = jnp.asarray(rng.randn(n, H), jnp.float32)
    wp = jnp.asarray(rng.randn(n, 3), jnp.float32)
    nargs = len(args) if equivariant else 7

    def loss_fused(a):
        agg, psum = _run_egcl_fused(g, a, equivariant)
        out = jnp.sum(agg * wa)
        if equivariant:
            out = out + jnp.sum(psum[:, :3] * wp)
        return out

    def loss_ref(a):
        full = tuple(a) + tuple(args[len(a):])
        agg, psum = _egcl_composed(full[0], full[1], mask, *full[2:],
                                   g.senders, g.receivers, n, equivariant)
        out = jnp.sum(agg * wa)
        if equivariant:
            out = out + jnp.sum(psum * wp)
        return out

    gf = jax.grad(loss_fused)(args[:nargs])
    gr = jax.grad(loss_ref)(args[:nargs])
    emask = np.asarray(g.edge_mask)
    names = ("x", "geo", "w0", "b0", "w1", "b1", "wc0", "bc0", "wc1")
    for name, a, b in zip(names, gf, gr):
        a, b = np.asarray(a), np.asarray(b)
        if name == "geo":
            # contract: masked edges get EXACTLY zero dgeo (their blocks
            # are schedule-skipped; uninitialized rows are where-selected)
            assert np.all(a[emask == 0] == 0.0)
            a, b = a[emask == 1], b[emask == 1]
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol,
                                   err_msg=name)


def test_egcl_gradients_match_composed():
    _egcl_grad_parity(_egcl_batch(seed=3), seed=6, equivariant=True)


def test_egcl_gradients_non_equivariant():
    _egcl_grad_parity(_egcl_batch(seed=7), seed=8, equivariant=False)


def test_egcl_gradients_with_edge_attr():
    """edge_attr lanes ride the geo stream; their grads must chain too."""
    _egcl_grad_parity(_egcl_batch(seed=9), seed=10, equivariant=True,
                      edge_attr_dim=5)


def test_egcl_model_level_fused_equals_composed(monkeypatch):
    """EGNN with the fused block forced on vs off: same params (the
    DenseParams tree matches the composed path's), same forward, same
    param grads — through BOTH the message and coordinate branches (two
    conv layers: the first is equivariant, so updated positions feed the
    second layer's geometry)."""
    g = _egcl_batch(n_graphs=4, seed=5)  # fewer edge blocks: interpret mode
    cfg = ModelConfig(
        model_type="EGNN", input_dim=2, hidden_dim=F, output_dim=(1,),
        output_type=("graph",), graph_head=GraphHeadCfg(1, 8, 1, (8,)),
        node_head=None, task_weights=(1.0,), num_conv_layers=2,
        equivariance=True, radius=1.4, max_neighbours=8)
    model = create_model(cfg)
    monkeypatch.setenv("HYDRAGNN_EGCL_FUSED", "1")
    variables = model.init({"params": jax.random.PRNGKey(0)}, g,
                           train=False)

    def loss(params, fused):
        monkeypatch.setenv("HYDRAGNN_EGCL_FUSED", "1" if fused else "0")
        out = model.apply({"params": params}, g, train=False)
        return sum(jnp.sum(o * o) for o in out)

    lf = loss(variables["params"], True)
    lg = loss(variables["params"], False)
    np.testing.assert_allclose(float(lf), float(lg), rtol=2e-5)

    gf = jax.grad(lambda p: loss(p, True))(variables["params"])
    gp = jax.grad(lambda p: loss(p, False))(variables["params"])
    flat_f = jax.tree_util.tree_leaves_with_path(gf)
    flat_p = dict(jax.tree_util.tree_leaves_with_path(gp))
    assert flat_f  # same tree structure both ways
    for path, leaf in flat_f:
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat_p[path]), rtol=5e-4,
            atol=5e-4, err_msg=str(path))


def test_egcl_pipeline_gate_defaults(monkeypatch):
    from hydragnn_tpu.models.egnn import _egcl_pipeline_enabled

    # judge the defaults with the env override ABSENT — a developer's
    # ambient HYDRAGNN_EGCL_FUSED would flip the first assert
    monkeypatch.delenv("HYDRAGNN_EGCL_FUSED", raising=False)
    assert _egcl_pipeline_enabled(64, 64, 4)     # mainline: default ON
    assert not _egcl_pipeline_enabled(256, 64, 4)   # features > tile
    assert not _egcl_pipeline_enabled(64, 256, 4)   # hidden > tile
    assert not _egcl_pipeline_enabled(64, 64, 200)  # geo payload > lanes
    monkeypatch.setenv("HYDRAGNN_EGCL_FUSED", "0")
    assert not _egcl_pipeline_enabled(64, 64, 4)    # forced off
    monkeypatch.setenv("HYDRAGNN_EGCL_FUSED", "1")
    assert _egcl_pipeline_enabled(128, 128, 4)      # forced on


def test_egcl_bf16_forward_within_tolerance():
    """bf16 node features ride bf16 windows in VMEM; result must stay
    within bf16 tolerance of the f32 composed path."""
    g = _egcl_batch(seed=6)
    args = _egcl_inputs(g, seed=12)
    mask = jnp.asarray(g.edge_mask)
    bf_args = (args[0].astype(jnp.bfloat16),) + args[1:]
    agg, psum = _run_egcl_fused(g, bf_args, True)
    assert agg.dtype == jnp.bfloat16
    ref_agg, ref_psum = _egcl_composed(args[0], args[1], mask, *args[2:],
                                       g.senders, g.receivers,
                                       args[0].shape[0], True)
    for out, ref in ((agg, ref_agg), (psum[:, :3], ref_psum)):
        ref = np.asarray(ref, np.float32)
        scale = np.abs(ref).max() + 1e-6
        err = np.abs(np.asarray(out, np.float32) - ref).max() / scale
        assert err < 0.03, err


def test_egcl_bf16_gradients_within_tolerance():
    """bf16 operands through the fused backward (weight grads included)
    stay within bf16 drift of the f32 composed reference."""
    g = _egcl_batch(seed=13)
    args = _egcl_inputs(g, seed=14)
    mask = jnp.asarray(g.edge_mask)
    n = args[0].shape[0]
    rng = np.random.RandomState(15)
    wa = jnp.asarray(rng.randn(n, H), jnp.float32)

    def loss_fused(a):
        bf = (a[0].astype(jnp.bfloat16),) + tuple(a[1:])
        agg, psum = _run_egcl_fused(g, bf, True)
        return (jnp.sum(agg.astype(jnp.float32) * wa)
                + jnp.sum(psum[:, :3]))

    def loss_ref(a):
        agg, psum = _egcl_composed(a[0], a[1], mask, *a[2:],
                                   g.senders, g.receivers, n, True)
        return jnp.sum(agg * wa) + jnp.sum(psum)

    gf = jax.grad(loss_fused)(args)
    gr = jax.grad(loss_ref)(args)
    emask = np.asarray(g.edge_mask).astype(bool)
    names = ("x", "geo", "w0", "b0", "w1", "b1", "wc0", "bc0", "wc1")
    for name, a, b in zip(names, gf, gr):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        if name == "geo":
            a, b = a[emask], b[emask]
        scale = np.abs(b).max() + 1e-6
        err = np.abs(a - b).max() / scale
        # deeper chain than scf's two matmuls (edge MLP + coord gate +
        # tanh, 4 bf16 matmul layers each way) — drift bound scales with
        # depth; observed ~0.067 max on x grads.  geo's diff lanes carry
        # the gate value c itself (ddiff = c * dpsum), whose relative
        # error is the whole chain's accumulated drift: widest bound.
        assert err < (0.20 if name == "geo" else 0.10), (name, err)


# ---------------------------------------------------------------------------
# 4. CGCNN gated-sum spec (ops/cgcnn_mp.py on the builder)
# ---------------------------------------------------------------------------


def _cgcnn_ref(x, ea, mask, kf, bf, ks, bs, senders, receivers, n):
    """The composed-path gated sum (models/cgcnn.py fallback route)."""
    parts = [x[receivers], x[senders]]
    if ea is not None:
        parts.append(ea)
    z = jnp.concatenate(parts, axis=-1)
    gate = jax.nn.sigmoid(z @ kf + bf)
    core = jax.nn.softplus(z @ ks + bs)
    return jax.ops.segment_sum(gate * core * mask[:, None], receivers,
                               num_segments=n)


def test_cgcnn_gated_block_parity_with_edge_attr(monkeypatch):
    """Forward + grads (x, edge_attr, both kernel/bias pairs) vs the
    composed concat path, incl. the exactly-zero-grad contract on
    masked edges."""
    from hydragnn_tpu.ops.cgcnn_mp import cgcnn_gated_block

    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "fused")
    b = _batch(seed=21)
    rng = np.random.RandomState(22)
    n = b.x.shape[0]
    e = b.senders.shape[0]
    f, a, d = 24, 5, 16  # distinct in/attr/out widths catch transpositions
    x = jnp.asarray(rng.randn(n, f) * 0.5, jnp.float32)
    ea = jnp.asarray(rng.randn(e, a) * 0.5, jnp.float32)
    kf = jnp.asarray(rng.randn(2 * f + a, d) * 0.3, jnp.float32)
    bf = jnp.asarray(rng.randn(d) * 0.1, jnp.float32)
    ks = jnp.asarray(rng.randn(2 * f + a, d) * 0.3, jnp.float32)
    bs = jnp.asarray(rng.randn(d) * 0.1, jnp.float32)
    em = jnp.asarray(b.edge_mask).astype(jnp.int32)
    mask = jnp.asarray(b.edge_mask)
    perm = jnp.asarray(b.extras["edge_perm_sender"])
    s, r = jnp.asarray(b.senders), jnp.asarray(b.receivers)
    wa = jnp.asarray(rng.randn(n, d), jnp.float32)

    out = cgcnn_gated_block(x, ea, em, kf, bf, ks, bs, s, r, perm)
    ref = _cgcnn_ref(x, ea, mask, kf, bf, ks, bs, s, r, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)

    gf = jax.grad(lambda x_, ea_, kf_, bf_, ks_, bs_: jnp.sum(
        cgcnn_gated_block(x_, ea_, em, kf_, bf_, ks_, bs_, s, r, perm)
        * wa), argnums=(0, 1, 2, 3, 4, 5))(x, ea, kf, bf, ks, bs)
    gr = jax.grad(lambda x_, ea_, kf_, bf_, ks_, bs_: jnp.sum(
        _cgcnn_ref(x_, ea_, mask, kf_, bf_, ks_, bs_, s, r, n) * wa),
        argnums=(0, 1, 2, 3, 4, 5))(x, ea, kf, bf, ks, bs)
    names = ("x", "edge_attr", "kf", "bf", "ks", "bs")
    emask = np.asarray(b.edge_mask)
    for name, gfa, gra in zip(names, gf, gr):
        gfa, gra = np.asarray(gfa), np.asarray(gra)
        if name == "edge_attr":
            assert np.all(gfa[emask == 0] == 0.0)
            gfa, gra = gfa[emask == 1], gra[emask == 1]
        np.testing.assert_allclose(gfa, gra, rtol=3e-4, atol=3e-4,
                                   err_msg=name)


def test_cgcnn_gated_block_no_edge_attr_bf16(monkeypatch):
    """edge_attr=None (zero-width geo payload, bias lane only) and bf16
    inputs: output dtype follows x, drift within bf16 tolerance."""
    from hydragnn_tpu.ops.cgcnn_mp import cgcnn_gated_block

    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "fused")
    b = _batch(seed=23)
    rng = np.random.RandomState(24)
    n = b.x.shape[0]
    f = 16
    x = jnp.asarray(rng.randn(n, f) * 0.5, jnp.float32)
    kf = jnp.asarray(rng.randn(2 * f, f) * 0.3, jnp.float32)
    bf = jnp.asarray(rng.randn(f) * 0.1, jnp.float32)
    ks = jnp.asarray(rng.randn(2 * f, f) * 0.3, jnp.float32)
    bs = jnp.asarray(rng.randn(f) * 0.1, jnp.float32)
    em = jnp.asarray(b.edge_mask).astype(jnp.int32)
    mask = jnp.asarray(b.edge_mask)
    perm = jnp.asarray(b.extras["edge_perm_sender"])
    s, r = jnp.asarray(b.senders), jnp.asarray(b.receivers)

    out = cgcnn_gated_block(x, None, em, kf, bf, ks, bs, s, r, perm)
    ref = _cgcnn_ref(x, None, mask, kf, bf, ks, bs, s, r, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)

    out_bf = cgcnn_gated_block(x.astype(jnp.bfloat16), None, em,
                               kf, bf, ks, bs, s, r, perm)
    assert out_bf.dtype == jnp.bfloat16
    refn = np.asarray(ref, np.float32)
    scale = np.abs(refn).max() + 1e-6
    err = np.abs(np.asarray(out_bf, np.float32) - refn).max() / scale
    assert err < 0.03, err


# ---------------------------------------------------------------------------
# 5. DimeNet triplet paths
# ---------------------------------------------------------------------------


def test_dimenet_fused_triplet_parity(monkeypatch):
    """The edge-space fused triplet interaction (tri_window > 0, W-window
    gather_mul_segment_sum) must match the composed gather+scatter path in
    forward AND param gradients on a real collated DimeNet batch."""
    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "fused")
    monkeypatch.setenv("HYDRAGNN_DIMENET_FUSED_TRI", "1")
    from hydragnn_tpu.models.dimenet import add_dimenet_extras, count_triplets

    rng = np.random.RandomState(0)
    samples = []
    for _ in range(5):
        pos = rng.rand(8, 3).astype(np.float32) * 2.0
        samples.append(GraphSample(
            x=rng.randint(0, 4, (8, 1)).astype(np.float32), pos=pos,
            edge_index=radius_graph(pos, 1.5, 8),
            graph_y=rng.rand(1).astype(np.float32)))
    pad = PadSpec.for_batch(5, 8, max(s.num_edges for s in samples))
    batch = collate(samples, pad, [HeadSpec("e", "graph", 1)])
    real = np.asarray(batch.edge_mask) > 0
    ei_real = np.stack([np.asarray(batch.senders)[real],
                        np.asarray(batch.receivers)[real]])
    t = count_triplets(ei_real, batch.x.shape[0])
    batch = add_dimenet_extras(batch, max_triplets=t + 8)
    assert "dn_tri_window" in batch.extras, "span must fit the window here"

    cfg = ModelConfig(
        model_type="DimeNet", input_dim=1, hidden_dim=8, output_dim=(1,),
        output_type=("graph",), graph_head=GraphHeadCfg(1, 8, 1, (8,)),
        node_head=None, task_weights=(1.0,), num_conv_layers=2,
        num_radial=3, num_spherical=4, basis_emb_size=4, int_emb_size=8,
        out_emb_size=8, envelope_exponent=5, num_before_skip=1,
        num_after_skip=1, radius=1.5)
    model = create_model(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)}, batch,
                        train=False)["params"]

    ex_plain = dict(batch.extras)
    del ex_plain["dn_tri_window"]
    batch_plain = batch.replace(extras=ex_plain)

    def loss(p, b):
        out = model.apply({"params": p}, b, train=False)
        return sum(jnp.sum(o ** 2) for o in out)

    lf, gf = jax.value_and_grad(loss)(params, batch)
    lp, gp = jax.value_and_grad(loss)(params, batch_plain)
    assert abs(float(lf) - float(lp)) < 1e-4 * max(1.0, abs(float(lp)))
    for a, c in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-3, atol=2e-3)


def test_dimenet_tri_builder_wide_dims_parity(monkeypatch):
    """int_emb_size > the factored kernel's cap routes the triplet
    interaction onto the builder-backed fused path (ops/dn_tri.py
    dimenet_tri_builder) instead of falling back to the composed
    gather+scatter — forward and param grads must match the composed
    route, and the branch selection itself is asserted."""
    import hydragnn_tpu.models.dimenet as D
    from test_dn_tri import _tables

    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "fused")
    batch_on = _tables(seed=8)
    assert "dn_tri_ok" in batch_on.extras
    monkeypatch.setenv("HYDRAGNN_DN_TRI_OFF", "1")
    batch_off = _tables(seed=8)
    assert "dn_tri_ok" not in batch_off.extras
    monkeypatch.delenv("HYDRAGNN_DN_TRI_OFF")

    # int_emb_size=96 > 64: the factored-basis kernel rejects, the
    # builder path (caps at 128) activates
    cfg = ModelConfig(
        model_type="DimeNet", input_dim=1, hidden_dim=16, output_dim=(1,),
        output_type=("graph",), graph_head=GraphHeadCfg(1, 8, 1, (8,)),
        node_head=None, task_weights=(1.0,), num_conv_layers=2,
        radius=1.3, max_neighbours=6, envelope_exponent=5,
        num_before_skip=1, num_after_skip=1, num_radial=3,
        num_spherical=7, basis_emb_size=8, int_emb_size=96,
        out_emb_size=16)

    seen = {}
    orig = D.InteractionPPBlock.__call__

    def patched(self, *a, **k):
        seen["kernel"] = self.tri_kernel
        seen["builder"] = self.tri_builder
        return orig(self, *a, **k)

    monkeypatch.setattr(D.InteractionPPBlock, "__call__", patched)

    model = create_model(cfg)
    variables = model.init({"params": jax.random.PRNGKey(0)}, batch_on,
                           train=False)
    assert seen == {"kernel": False, "builder": True}, seen

    def loss(params, batch):
        out = model.apply({"params": params}, batch, train=False)
        return sum(jnp.sum(o * o) for o in out)

    l_on = float(loss(variables["params"], batch_on))
    l_off = float(loss(variables["params"], batch_off))
    np.testing.assert_allclose(l_on, l_off, rtol=2e-5)

    g_on = jax.grad(lambda p: loss(p, batch_on))(variables["params"])
    g_off = jax.grad(lambda p: loss(p, batch_off))(variables["params"])
    flat_on = jax.tree_util.tree_leaves_with_path(g_on)
    flat_off = dict(jax.tree_util.tree_leaves_with_path(g_off))
    assert flat_on
    for path, leaf in flat_on:
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat_off[path]),
            rtol=5e-4, atol=5e-4, err_msg=str(path))


# ---------------------------------------------------------------------------
# 6. gather-mul / dense segment-sum schedule kernels (ops/fused_mp.py)
# ---------------------------------------------------------------------------


def _arrays(b, f=64, seed=1):
    rng = np.random.RandomState(seed)
    n, e = b.x.shape[0], b.senders.shape[0]
    x = jnp.asarray(rng.rand(n, f), jnp.float32)
    w = jnp.asarray(rng.rand(e, f), jnp.float32) * jnp.asarray(
        b.edge_mask)[:, None]
    return x, w


def _gms_ref(b, x, w):
    return jax.ops.segment_sum(
        x[jnp.asarray(b.senders)] * w, jnp.asarray(b.receivers),
        num_segments=x.shape[0])


def test_fused_forward_exact():
    b = _batch()
    x, w = _arrays(b)
    out = gather_mul_segment_sum(
        x, w, jnp.asarray(b.senders), jnp.asarray(b.receivers))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_gms_ref(b, x, w)),
                               rtol=1e-5, atol=1e-5)


def test_fused_gradients_exact():
    b = _batch(seed=2)
    x, w = _arrays(b, seed=3)
    s, r = jnp.asarray(b.senders), jnp.asarray(b.receivers)

    gx1, gw1 = jax.grad(
        lambda x_, w_: jnp.sum(
            gather_mul_segment_sum(x_, w_, s, r) ** 2),
        argnums=(0, 1))(x, w)
    gx2, gw2 = jax.grad(
        lambda x_, w_: jnp.sum(_gms_ref(b, x_, w_) ** 2),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                               rtol=1e-5, atol=1e-5)
    m = np.asarray(b.edge_mask)[:, None]
    np.testing.assert_allclose(np.asarray(gw1) * m, np.asarray(gw2) * m,
                               rtol=1e-5, atol=1e-5)


def _dense_collated():
    """Dense all-to-all graphs: degree 15 in a 16-node graph."""
    rng = np.random.RandomState(0)
    samples = []
    for _ in range(24):
        pos = rng.rand(16, 3).astype(np.float32)
        x = rng.rand(16, 2).astype(np.float32)
        samples.append(GraphSample(
            x=x, pos=pos, edge_index=radius_graph(pos, 10.0, 15),
            graph_y=np.ones(1, np.float32), node_y=x))
    return collate(samples, PadSpec.for_batch(24, 16, 16 * 15),
                   [HeadSpec("e", "graph", 1)])


def test_extreme_degrees_exact():
    """The dense schedule has no degree bound: dense all-to-all graphs
    (degree 15 in a 16-node graph) are processed exactly, fwd and bwd."""
    b = _dense_collated()
    x, w = _arrays(b)
    s, r = jnp.asarray(b.senders), jnp.asarray(b.receivers)
    out = gather_mul_segment_sum(x, w, s, r)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_gms_ref(b, x, w)),
                               rtol=1e-5, atol=1e-5)
    gx1 = jax.grad(lambda x_: jnp.sum(
        gather_mul_segment_sum(x_, w, s, r) ** 2))(x)
    gx2 = jax.grad(lambda x_: jnp.sum(_gms_ref(b, x_, w) ** 2))(x)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                               rtol=1e-5, atol=1e-5)


def _gss_ref(x, s, r, mask):
    return jax.ops.segment_sum(x[s] * mask[:, None], r,
                               num_segments=x.shape[0])


def test_gather_segment_sum_wless_exact():
    """The w-less variant (GIN/MFC neighbor sum) and its gradient."""
    from hydragnn_tpu.ops.fused_mp import gather_segment_sum

    b = _batch(seed=7)
    x, _ = _arrays(b, seed=8)
    s, r = jnp.asarray(b.senders), jnp.asarray(b.receivers)
    mask = jnp.asarray(b.edge_mask)

    out = gather_segment_sum(x, s, r, mask)
    want = _gss_ref(x, s, r, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    g1 = jax.grad(lambda x_: jnp.sum(
        gather_segment_sum(x_, s, r, mask) ** 2))(x)
    g2 = jax.grad(lambda x_: jnp.sum(_gss_ref(x_, s, r, mask) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-5)


# --- the receiver-order backward (one pass: dw and dx from the edge list as
# shipped).  Synthetic edge lists, built straight to the kernel's contract,
# pin down the schedule corners collate's molecules only graze. ---

_NB, _EB = 128, 512      # the kernel's node block and edge block

# edges per node block, then the length of the masked tail
_LAYOUTS = {
    # (a) E is not a multiple of the edge block
    "ragged": ([300, 700, 411], 0),
    # (b) edge block 0 holds the edges of four node blocks, block 1 of two
    "straddle": ([300, 100, 90, 900, 250], 0),
    # (c) a node block without edges in the middle and at the end
    "empty_blocks": ([400, 0, 600, 200, 0], 0),
    # (d) a masked tail longer than one edge block (plus a ragged end)
    "masked_tail": ([350, 500, 220], 2 * _EB + 77),
    # (e) node ids above 8,192 (66 node blocks, edges in the last three):
    # the kernels compare the lane-major ids as INTEGERS, so no id is
    # rounded on its way to a one-hot (bf16, one MXU pass, holds 8 bits)
    "high_ids": ([0] * 63 + [300, 700, 411], _EB + 5),
}


def _synthetic_edges(layout, window, seed, f=40):
    """A receiver-sorted edge list whose senders honour the ``window``
    invariant (an edge of node block i sends from blocks i-hw..i+hw), with
    a masked tail parked on node N-1 the way collate parks padding."""
    counts, tail = _LAYOUTS[layout]
    rng = np.random.RandomState(seed)
    nb, hw = len(counts), window // 2
    n = nb * _NB - 37                   # N is not a whole number of blocks
    recv, send = [], []
    for i, c in enumerate(counts):
        hi = min((i + 1) * _NB, n)
        recv.append(np.sort(rng.randint(i * _NB, hi, c)))
        send.append(rng.randint(max(0, i - hw) * _NB,
                                min(n, (i + hw + 1) * _NB), c))
    recv = np.concatenate(recv + [np.full(tail, n - 1)]).astype(np.int32)
    send = np.concatenate(send + [np.full(tail, n - 1)]).astype(np.int32)
    valid = np.concatenate(
        [np.ones(sum(counts)), np.zeros(tail)]).astype(np.float32)
    x = jnp.asarray(rng.randn(n, f), jnp.float32)
    w = jnp.asarray(rng.randn(recv.size, f) * valid[:, None], jnp.float32)
    return x, w, jnp.asarray(send), jnp.asarray(recv), jnp.asarray(valid)


def _check_bwd_against_composed(x, w, s, r, valid, window):
    """dx and dw of the fused op (``edge_valid`` passed) against the
    composed gather + segment_sum; masked dw exactly zero, nothing NaN."""
    n = x.shape[0]
    ct = jnp.asarray(
        np.random.RandomState(5).randn(n, x.shape[1]), jnp.float32)

    def fused(x_, w_):
        return jnp.sum(ct * gather_mul_segment_sum(
            x_, w_, s, r, window, edge_valid=valid))

    def composed(x_, w_):
        return jnp.sum(ct * jax.ops.segment_sum(
            x_[s] * w_, r, num_segments=n))

    gx1, gw1 = jax.grad(fused, argnums=(0, 1))(x, w)
    gx2, gw2 = jax.grad(composed, argnums=(0, 1))(x, w)
    gw1, m = np.asarray(gw1), np.asarray(valid) != 0
    # poison check: unvisited dw blocks are uninitialised memory (NaN in
    # interpret mode) — the rule must select them away, not multiply
    assert np.isfinite(gw1).all() and np.isfinite(np.asarray(gx1)).all()
    assert (gw1[~m] == 0.0).all()
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gw1[m], np.asarray(gw2)[m],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layout", list(_LAYOUTS))
@pytest.mark.parametrize("window", [3, 5])
def test_receiver_order_backward_exact(window, layout):
    x, w, s, r, valid = _synthetic_edges(layout, window, seed=21)
    np.testing.assert_allclose(
        np.asarray(gather_mul_segment_sum(x, w, s, r, window,
                                          edge_valid=valid)),
        np.asarray(jax.ops.segment_sum(x[s] * w, r,
                                       num_segments=x.shape[0])),
        rtol=1e-5, atol=1e-5)
    _check_bwd_against_composed(x, w, s, r, valid, window)


@pytest.mark.parametrize("f,window", [(520, 5), (1000, 3)],
                         ids=["f640w5", "f1024w3"])
def test_receiver_order_backward_wide_f_halves_edge_block(f, window):
    """The one thing that adapts: at wide F the backward halves its edge
    block (VMEM), on the forward's 512-padded operands — a second schedule
    over the same edge list AND the same packed id operand (two granules
    of it a step where the forward took four), same gradients."""
    from hydragnn_tpu.ops.fused_mp import _EDGE_BLOCK, _bwd_edge_block

    assert _bwd_edge_block(128, 3) == _EDGE_BLOCK == _bwd_edge_block(512, 5)
    assert _bwd_edge_block(640, 5) == _EDGE_BLOCK // 2
    assert _bwd_edge_block(1024, 3) == _EDGE_BLOCK // 2
    x, w, s, r, valid = _synthetic_edges("straddle", window, seed=28, f=f)
    _check_bwd_against_composed(x, w, s, r, valid, window)


@pytest.mark.parametrize("layout", list(_LAYOUTS))
def test_receiver_order_backward_wless_exact(layout):
    from hydragnn_tpu.ops.fused_mp import gather_segment_sum

    x, _, s, r, valid = _synthetic_edges(layout, 3, seed=22)
    n = x.shape[0]
    ct = jnp.asarray(
        np.random.RandomState(6).randn(n, x.shape[1]), jnp.float32)
    g1 = jax.grad(lambda x_: jnp.sum(
        ct * gather_segment_sum(x_, s, r, valid)))(x)
    g2 = jax.grad(lambda x_: jnp.sum(ct * _gss_ref(x_, s, r, valid)))(x)
    assert np.isfinite(np.asarray(g1)).all()
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("has_w", [True, False], ids=["w", "wless"])
def test_receiver_order_backward_dense_graphs(has_w):
    from hydragnn_tpu.ops.fused_mp import gather_segment_sum

    b = _dense_collated()
    x, w = _arrays(b, seed=23)
    s, r = jnp.asarray(b.senders), jnp.asarray(b.receivers)
    valid = jnp.asarray(b.edge_mask)
    if has_w:
        _check_bwd_against_composed(x, w, s, r, valid, 3)
        return
    g1 = jax.grad(lambda x_: jnp.sum(
        gather_segment_sum(x_, s, r, valid) ** 2))(x)
    g2 = jax.grad(lambda x_: jnp.sum(_gss_ref(x_, s, r, valid) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("has_w", [True, False], ids=["w", "wless"])
def test_receiver_order_backward_under_jit_scan(has_w):
    """Two steps of a ``lax.scan`` under ``jax.jit`` (the scan-K trainer's
    shape): each step's gradients equal the composed path's."""
    from hydragnn_tpu.ops.fused_mp import gather_segment_sum

    x, w, s, r, valid = _synthetic_edges("masked_tail", 3, seed=24)
    n = x.shape[0]
    scales = jnp.asarray([1.0, -0.5], jnp.float32)

    def grads(op):
        def body(carry, scale):
            g = jax.grad(lambda x_, w_: jnp.sum(op(x_ * scale, w_) ** 2),
                         argnums=(0, 1))(x, w)
            return carry, g
        return jax.jit(lambda: jax.lax.scan(body, 0.0, scales)[1])()

    if has_w:
        got = grads(lambda x_, w_: gather_mul_segment_sum(
            x_, w_, s, r, edge_valid=valid))
        want = grads(lambda x_, w_: jax.ops.segment_sum(
            x_[s] * w_, r, num_segments=n))
    else:
        got = grads(lambda x_, w_: gather_segment_sum(x_, s, r, valid))
        want = grads(lambda x_, w_: _gss_ref(x_, s, r, valid))
    m = np.asarray(valid) != 0
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(got[1])).all()
    np.testing.assert_allclose(np.asarray(got[1])[:, m],
                               np.asarray(want[1])[:, m],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layout", list(_LAYOUTS))
def test_dense_schedule_revisits_only_consecutively(layout):
    """Host-side, on the tables themselves: every node block and every
    edge block occupies ONE consecutive run of grid steps — Pallas keeps
    an accumulated output block resident only across consecutive steps,
    and the backward accumulates into both (P by node block, dw by edge
    block)."""
    from hydragnn_tpu.ops.fused_block import _dense_schedule
    from hydragnn_tpu.ops.fused_mp import _pack

    x, w, s, r, valid = _synthetic_edges(layout, 3, seed=25)
    _, _, ids_p, recv_f = _pack(x, w, s, r, None, valid)
    n_blocks = -(-x.shape[0] // _NB)
    n_eblocks = recv_f.shape[0] // _EB
    # the receivers the kernels read (sublane 1 of every 128-edge granule
    # of the packed operand) are the flat ones the schedule is made from
    np.testing.assert_array_equal(
        np.asarray(ids_p).reshape(-1, 8, 128)[:, 1].ravel(),
        np.asarray(recv_f))
    si, se, av, fi, s_max = _dense_schedule(
        recv_f, n_blocks, _NB, _EB, n_eblocks)
    si, se, av, fi = (np.asarray(t) for t in (si, se, av, fi))
    assert si.shape == (s_max,) == se.shape
    for table in (si, se):
        starts = np.flatnonzero(np.diff(table, prepend=table[0] - 1))
        assert len(set(table[starts])) == len(starts), table
    # each node block is entered once, every real edge is scheduled with
    # its own node block exactly once, and no masked edge ever is
    assert (si[fi == 1] == np.arange(n_blocks)).all()
    recv = np.asarray(recv_f).reshape(n_eblocks, _EB)
    hits = np.zeros(recv.shape, np.int64)
    for i, eb in zip(si[av == 1], se[av == 1]):
        hits[eb] += recv[eb] // _NB == i
    assert (hits.ravel() == (recv.ravel() < n_blocks * _NB)).all()


def test_backward_has_no_sort_and_no_edge_sized_gather(monkeypatch):
    """Structure, no chip needed: through the dispatcher on the fused
    path, the gradient's jaxpr holds no sort, no gather with E rows, and
    ONE kernel named ``gather_mul_seg_bwd`` per call of the op — the
    sender-order tax cannot creep back unseen."""
    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "fused")
    b = _batch(seed=26)
    assert "edge_perm_sender" in b.extras
    x, w = _arrays(b, seed=27)
    e = w.shape[0]

    def loss(x_, w_):
        h = segment.gather_mul_segment(x_, w_, b)
        return jnp.sum(segment.gather_mul_segment(h, w_, b) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w).jaxpr
    eqns = [eq for eq, _stack in _eqns_with_stacks(jaxpr)]
    names = [eq.primitive.name for eq in eqns]
    assert not [n for n in names if "sort" in n], names
    # nothing E-sized is gathered: no gather RESULT has E rows, and the only
    # gathers that READ an E-row operand are the schedule's binary-search
    # probes (searchsorted over the receivers: one row per node block)
    probes = -(-x.shape[0] // _NB) + 1
    for eq in eqns:
        if eq.primitive.name != "gather":
            continue
        operand, out = eq.invars[0].aval.shape, eq.outvars[0].aval.shape
        assert not out or out[0] < e, (operand, out)
        if operand[0] >= e:
            assert int(np.prod(out)) <= probes, (operand, out)
    kernels = [eq.params["name"] for eq in eqns
               if eq.primitive.name == "pallas_call"]
    assert kernels.count("gather_mul_seg_bwd") == 2, kernels
    assert kernels.count("gather_mul_seg_fwd") == 2, kernels


# --- the multiplier's third source: a chain evaluated in VMEM (SchNet's
# filter network, packed by ops/scf_mp.py) on the same two kernels ---

_CG = 7      # basis lanes of the synthetic chain problems


def _chain_problem(layout, seed, f=40):
    """The synthetic edge list of ``layout`` with a CFConv generator on
    it: rbf, the masked cutoff column and the filter network's weights."""
    x, _, s, r, valid = _synthetic_edges(layout, 3, seed, f=f)
    rng = np.random.RandomState(seed + 100)
    e = int(s.shape[0])
    rbf = jnp.asarray(rng.rand(e, _CG), jnp.float32)
    cm = jnp.asarray(rng.rand(e).astype(np.float32)) * valid
    k0 = jnp.asarray(rng.randn(_CG, f) * 0.4, jnp.float32)
    b0 = jnp.asarray(rng.randn(f) * 0.1, jnp.float32)
    k1 = jnp.asarray(rng.randn(f, f) * 0.2, jnp.float32)
    b1 = jnp.asarray(rng.randn(f) * 0.1, jnp.float32)
    return (x, rbf, cm, k0, b0, k1, b1), s, r, valid


def _chain_fused(args, s, r, valid):
    from hydragnn_tpu.ops.scf_mp import scf_edge_pipeline

    x, rbf, cm, k0, b0, k1, b1 = args
    return scf_edge_pipeline(x, rbf, cm, valid.astype(jnp.int32),
                             k0, b0, k1, b1, s, r)


def _chain_composed(args, s, r):
    x, rbf, cm, k0, b0, k1, b1 = args
    filt = (jax.nn.softplus(rbf @ k0 + b0) - jnp.log(2.0)) @ k1 + b1
    return jax.ops.segment_sum(x[s] * filt * cm[:, None], r,
                               num_segments=x.shape[0])


@pytest.mark.parametrize("layout", list(_LAYOUTS))
def test_chain_form_matches_composed(layout):
    """The filter made inside the kernels against the composed expression
    over every schedule corner: the output and dh, drbf, dcut, dk0, db0,
    dk1, db1 from the ONE backward pass; masked edges exact zeros."""
    args, s, r, valid = _chain_problem(layout, seed=31)
    n = args[0].shape[0]
    np.testing.assert_allclose(
        np.asarray(_chain_fused(args, s, r, valid)),
        np.asarray(_chain_composed(args, s, r)), rtol=2e-5, atol=2e-5)
    ct = jnp.asarray(
        np.random.RandomState(32).randn(n, args[0].shape[1]), jnp.float32)
    got = jax.grad(lambda a: jnp.sum(ct * _chain_fused(a, s, r, valid)))(args)
    want = jax.grad(lambda a: jnp.sum(ct * _chain_composed(a, s, r)))(args)
    m = np.asarray(valid) != 0
    for name, a, b in zip(("h", "rbf", "cut", "k0", "b0", "k1", "b1"),
                          got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all(), name
        if name in ("rbf", "cut"):
            # never-visited blocks are uninitialised memory, selected away
            assert (a[~m] == 0.0).all(), name
            a, b = a[m], b[m]
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4, err_msg=name)


@pytest.mark.parametrize("f", [520, 1000], ids=["f640", "f1024"])
def test_chain_form_wide_f_shrinks_edge_blocks(f):
    """What adapts in the chain form: past F_pad 512 the ``[F, F]`` weight
    block and its gradient accumulator crowd VMEM, so the forward runs
    256-edge and the backward 128-edge blocks on the same 512-padded
    operands — other schedules over one edge list and ONE packed id
    operand (two granules a forward step, one a backward step), the same
    numbers."""
    from hydragnn_tpu.ops.fused_mp import _chain_edge_block

    assert _chain_edge_block(128, False) == _EB == _chain_edge_block(512, True)
    assert (_chain_edge_block(640, False), _chain_edge_block(1024, True)) \
        == (_EB // 2, _EB // 4)
    args, s, r, valid = _chain_problem("straddle", seed=37, f=f)
    ct = jnp.asarray(np.random.RandomState(38).randn(
        *args[0].shape), jnp.float32)
    got = jax.grad(lambda a: jnp.sum(ct * _chain_fused(a, s, r, valid)))(args)
    want = jax.grad(lambda a: jnp.sum(ct * _chain_composed(a, s, r)))(args)
    m = np.asarray(valid) != 0
    for name, a, b in zip(("h", "rbf", "cut", "k0", "b0", "k1", "b1"),
                          got, want):
        a, b = np.asarray(a), np.asarray(b)
        if name in ("rbf", "cut"):
            a, b = a[m], b[m]
        scale = float(np.abs(b).max()) + 1e-6
        np.testing.assert_allclose(a / scale, b / scale, atol=2e-5,
                                   err_msg=name)


def test_chain_form_under_jit_scan_with_weights_only():
    """The shape of both benchmark cells: ``lax.scan`` under ``jax.jit``,
    gradients wrt the features and the filter weights, the geometry batch
    data (not differentiated)."""
    args, s, r, valid = _chain_problem("masked_tail", seed=33)
    geo, rest = args[1:3], args[3:]
    scales = jnp.asarray([1.0, -0.5], jnp.float32)

    def grads(op):
        def body(carry, scale):
            g = jax.grad(lambda x_, w_: jnp.sum(
                op((x_ * scale, *geo, *w_)) ** 2), argnums=(0, 1))(
                    args[0], rest)
            return carry, g
        return jax.jit(lambda: jax.lax.scan(body, 0.0, scales)[1])()

    got = grads(lambda a: _chain_fused(a, s, r, valid))
    want = grads(lambda a: _chain_composed(a, s, r))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(np.abs(np.asarray(b)).max()) + 1e-6
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=3e-5)


def _kernel_eqns(fn, *args):
    """``{kernel name: [pallas_call equations]}`` of ``fn``'s jaxpr."""
    out = {}
    for eq, _stack in _eqns_with_stacks(jax.make_jaxpr(fn)(*args).jaxpr):
        if eq.primitive.name == "pallas_call":
            out.setdefault(eq.params["name"], []).append(eq)
    return out


def _count_in_kernel(eqn, *prims):
    return sum(e.primitive.name in prims
               for e, _s in _eqns_with_stacks(eqn.params["jaxpr"]))


def test_chain_backward_writes_dgeo_only_when_geometry_is_differentiated():
    """``symbolic_zeros``: with rbf and the cutoff from batch data (both
    cells) the backward kernel has NO edge-sized output; differentiating
    them (positions: force training) adds exactly the ``dgeo`` stream."""
    args, s, r, valid = _chain_problem("ragged", seed=34)
    e_pad = -(-int(s.shape[0]) // _EB) * _EB

    def edge_outputs(argnums):
        def loss(*a):
            return jnp.sum(_chain_fused(a, s, r, valid) ** 2)
        (bwd,) = _kernel_eqns(jax.grad(loss, argnums=argnums),
                              *args)["gather_mul_seg_bwd"]
        return [v.aval.shape for v in bwd.outvars
                if v.aval.shape[0] == e_pad]

    assert edge_outputs((0, 3, 4, 5, 6)) == []
    assert edge_outputs((0, 1, 2, 3, 4, 5, 6)) == [(e_pad, 128)]
    assert edge_outputs((0, 2)) == [(e_pad, 128)]       # the cutoff alone


@pytest.mark.parametrize("form", ["array", "wless"])
def test_array_and_wless_kernels_keep_their_shape(form):
    """The chain is a STATIC third source: the other callers' kernels
    (GAT, DimeNet, GIN / SAGE / MFC, poly_mp's sum-only backward) trace as
    before it existed — the same operands and streams, the same
    contractions, no filter network."""
    from hydragnn_tpu.ops.fused_mp import gather_segment_sum

    x, w, s, r, valid = _synthetic_edges("straddle", 3, seed=35)
    if form == "array":
        def loss(x_, w_):
            return jnp.sum(gather_mul_segment_sum(
                x_, w_, s, r, edge_valid=valid) ** 2)
        # tables + packed ids + multiplier (+ g) + windows -> outputs; dots
        want = {"gather_mul_seg_fwd": (4 + 2 + 3, 1, 2),
                "gather_mul_seg_bwd": (5 + 3 + 3, 2, 3)}
    else:
        def loss(x_, w_):
            return jnp.sum(gather_segment_sum(x_, s, r, valid) ** 2)
        # no multiplier operand: the mask rides with the ids
        want = {"gather_mul_seg_fwd": (4 + 1 + 3, 1, 2),
                "gather_mul_seg_bwd": (5 + 2, 1, 2)}
    kernels = _kernel_eqns(jax.grad(loss), x, w)
    assert set(kernels) == set(want)
    for name, (n_in, n_out, n_dot) in want.items():
        (eq,) = kernels[name]
        assert (len(eq.invars), len(eq.outvars)) == (n_in, n_out), name
        assert _count_in_kernel(eq, "dot_general") == n_dot, name
        assert _count_in_kernel(eq, "logistic", "log1p", "exp",
                                "custom_jvp_call") == 0, name


def _tiled_bytes(block, dtype):
    """HBM bytes of one block of a Mosaic operand: the two minor
    dimensions round up to (8, 128) tiles."""
    *lead, r, c = block
    return (int(np.prod(lead, dtype=np.int64)) * -(-r // 8) * 8
            * -(-c // 128) * 128 * np.dtype(dtype).itemsize)


@pytest.mark.parametrize("kernel", ["gather_mul_seg_fwd",
                                    "gather_mul_seg_bwd"])
@pytest.mark.parametrize("form", ["chain", "array", "wless"])
def test_edge_ids_cost_at_most_32_bytes_an_edge(form, kernel):
    """The id operands' HBM layout, counted from the gradient's jaxpr (no
    chip needed): per edge block, every INPUT stream indexed by the edge
    block is reckoned in tiled bytes.  The ids are ONE int32 operand of
    (8, 128) granules, <= 32 B an edge (two ``[E_pad, 1]`` columns, 128
    lanes wide each, were 1,024 B), and at F_pad 128 a step fetches <= 272
    KiB of edge streams (768 KiB then): the multiplier's 256 + 16."""
    from hydragnn_tpu.ops.fused_mp import gather_segment_sum

    f = 100                                           # F_pad 128
    if form == "chain":
        args, s, r, valid = _chain_problem("masked_tail", seed=41, f=f)

        def loss(*a):
            return jnp.sum(_chain_fused(a, s, r, valid) ** 2)
        grad, targs = jax.grad(loss, argnums=(0, 3, 4, 5, 6)), args
    else:
        x, w, s, r, valid = _synthetic_edges("masked_tail", 3, seed=41, f=f)
        if form == "array":
            def loss(x_, w_):
                return jnp.sum(gather_mul_segment_sum(
                    x_, w_, s, r, edge_valid=valid) ** 2)
        else:
            def loss(x_, w_):
                return jnp.sum(gather_segment_sum(x_, s, r, valid) ** 2)
        grad, targs = jax.grad(loss, argnums=(0, 1)), (x, w)
    e_pad = -(-int(s.shape[0]) // _EB) * _EB
    granule_rows = e_pad // 128 * 8
    (eq,) = _kernel_eqns(grad, *targs)[kernel]
    gm = eq.params["grid_mapping"]
    id_bytes = stream_bytes = 0
    edges = set()
    for bm in gm.block_mappings[:gm.num_inputs]:
        aval = bm.array_aval
        block = tuple(b.block_size for b in bm.block_shape)
        if aval.shape[0] == granule_rows and aval.dtype == jnp.int32:
            assert aval.shape[1] == 128 and block[1] == 128, aval
            edges.add(block[0] // 8 * 128)
            id_bytes += _tiled_bytes(block, aval.dtype)
        elif aval.shape[0] == e_pad:
            edges.add(block[0])
        else:
            continue
        stream_bytes += _tiled_bytes(block, aval.dtype)
    # every edge stream of the kernel moves the same edges a step
    (be,) = edges
    assert be == _EB
    assert 0 < id_bytes <= 32 * be, (id_bytes, be)
    assert stream_bytes <= 272 * 1024, stream_bytes
    # no id (or mask) column is left: nothing one lane wide is an operand
    assert not [bm for bm in gm.block_mappings
                if bm.array_aval.shape[-1] == 1]


def test_schnet128_train_step_keeps_the_filter_inside_the_kernels(
        monkeypatch):
    """Structure of the benchmark's step, no chip needed: a fused SchNet
    train step at 128 filters holds ONE ``gather_mul_seg_fwd`` and ONE
    ``gather_mul_seg_bwd`` per conv layer, and outside the kernels no
    contraction or reduction reads an ``[E, F]`` operand — the filter
    network, its pre-activation and its cotangents exist only in VMEM."""
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.trainer import (
        create_train_state, make_train_step)

    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "fused")
    layers, f = 3, 128
    cfg = ModelConfig(
        model_type="SchNet", input_dim=1, hidden_dim=32, output_dim=(1,),
        output_type=("graph",), graph_head=GraphHeadCfg(1, 16, 1, (16,)),
        node_head=None, task_weights=(1.0,), num_conv_layers=layers,
        num_gaussians=50, num_filters=f, radius=1.4, max_neighbours=10)
    model = create_model(cfg)
    batch = _batch(seed=36)
    e = batch.senders.shape[0]
    e_sizes = {e, -(-e // _EB) * _EB}
    assert batch.x.shape[0] not in e_sizes
    opt = select_optimizer({"type": "AdamW", "learning_rate": 1e-3})
    state = jax.eval_shape(
        lambda b: create_train_state(model, b, opt), batch)
    step = make_train_step(model, cfg, opt, telemetry_metrics=True,
                           nonfinite_guard=True)

    outside = [eq for eq, _s in _eqns_with_stacks(
        jax.make_jaxpr(step)(state, batch).jaxpr, into_kernels=False)]
    kernels = [eq.params["name"] for eq in outside
               if eq.primitive.name == "pallas_call"]
    assert kernels.count("gather_mul_seg_fwd") == layers, kernels
    assert kernels.count("gather_mul_seg_bwd") == layers, kernels
    for eqn in outside:
        name = eqn.primitive.name
        if name != "dot_general" and not name.startswith("reduce_"):
            continue
        for v in eqn.invars:
            shape = getattr(v.aval, "shape", ())
            assert not (len(shape) == 2 and shape[0] in e_sizes
                        and shape[1] == f), (name, shape)


def test_segment_sum_dense_exact():
    """Scatter-only dense-schedule kernel vs jax.ops.segment_sum, fwd+bwd,
    over both sorted id streams the models use (receivers, node_gid)."""
    from hydragnn_tpu.ops.fused_mp import segment_sum_dense

    b = _batch(seed=11)
    rng = np.random.RandomState(12)
    e = b.senders.shape[0]
    data = jnp.asarray(rng.rand(e, 48), jnp.float32) * jnp.asarray(
        b.edge_mask)[:, None]
    r = jnp.asarray(b.receivers)
    n = b.x.shape[0]
    np.testing.assert_allclose(
        np.asarray(segment_sum_dense(data, r, n)),
        np.asarray(jax.ops.segment_sum(data, r, num_segments=n)),
        rtol=1e-5, atol=1e-5)
    g1 = jax.grad(lambda d: jnp.sum(segment_sum_dense(d, r, n) ** 2))(data)
    g2 = jax.grad(lambda d: jnp.sum(
        jax.ops.segment_sum(d, r, num_segments=n) ** 2))(data)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-5)

    nd = jnp.asarray(rng.rand(n, 32), jnp.float32)
    gid = jnp.asarray(b.node_gid)
    ng = b.graph_mask.shape[0]
    np.testing.assert_allclose(
        np.asarray(segment_sum_dense(nd, gid, ng)),
        np.asarray(jax.ops.segment_sum(nd, gid, num_segments=ng)),
        rtol=1e-5, atol=1e-5)


def test_dense_bwd_gathers_exact(monkeypatch):
    """gather_sender / gather_receiver_sorted: forward identical to plain
    gathers, backward (dense-scatter path) identical to XLA's."""
    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "fused")
    b = _batch(seed=13)
    rng = np.random.RandomState(14)
    x = jnp.asarray(rng.rand(b.x.shape[0], 32), jnp.float32)

    for fn, idx in ((segment.gather_sender, b.senders),
                    (segment.gather_receiver_sorted, b.receivers)):
        np.testing.assert_array_equal(
            np.asarray(fn(x, b)), np.asarray(x[jnp.asarray(idx)]))
        g1 = jax.grad(lambda x_: jnp.sum(fn(x_, b) ** 2))(x)
        g2 = jax.grad(lambda x_: jnp.sum(x_[jnp.asarray(idx)] ** 2))(x)
        # f32 accumulation order differs between the onehot-matmul scatter
        # and XLA's scatter-add; values here reach ~1e4
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# 7. collate invariants + trace-time dispatch tally
# ---------------------------------------------------------------------------


def test_collate_attaches_perm_under_fused_backend(monkeypatch):
    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "fused")
    b = _batch()
    assert "edge_perm_sender" in b.extras
    perm = np.asarray(b.extras["edge_perm_sender"])
    s = np.asarray(b.senders)
    assert (np.diff(s[perm]) >= 0).all()
    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "scatter")
    b2 = _batch()
    assert "edge_perm_sender" not in (b2.extras or {})


def test_collate_skips_perm_when_invariants_broken(monkeypatch):
    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "fused")
    rng = np.random.RandomState(0)

    # graph larger than the kernel's node block -> no perm
    n = 200
    pos = rng.rand(n, 3).astype(np.float32) * 6.0
    x = rng.rand(n, 2).astype(np.float32)
    ei = radius_graph(pos, 1.4, 10)
    big = GraphSample(x=x, pos=pos, edge_index=ei,
                      graph_y=np.ones(1, np.float32), node_y=x)
    pad = PadSpec.for_batch(1, n, n * 10)
    b = collate([big], pad, [HeadSpec("e", "graph", 1)])
    assert "edge_perm_sender" not in (b.extras or {})

    # receiver-unsorted stored edge list (external pipeline) -> no perm
    n2 = 8
    pos2 = rng.rand(n2, 3).astype(np.float32)
    x2 = rng.rand(n2, 2).astype(np.float32)
    ei2 = np.asarray([[1, 0, 3], [5, 2, 0]], np.int32)  # recv not sorted
    small = GraphSample(x=x2, pos=pos2, edge_index=ei2,
                        graph_y=np.ones(1, np.float32), node_y=x2)
    pad2 = PadSpec.for_batch(1, n2, 8)
    b2 = collate([small], pad2, [HeadSpec("e", "graph", 1)])
    assert "edge_perm_sender" not in (b2.extras or {})


def test_dispatcher_fused_matches_fallback(monkeypatch):
    """poly_scatter_segment / poly_gather_segment: the fused dict (marker
    present) must equal the composed dict (marker stripped), including
    the mx/mn empty-segment zero-clean and cnt == degree."""
    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "fused")
    b = _batch(seed=13)
    assert "edge_perm_sender" in b.extras
    ex = dict(b.extras)
    del ex["edge_perm_sender"]
    b_plain = b.replace(extras=ex)

    data = _edge_data(b, seed=14)
    moments = ("sum", "sq", "mx", "mn", "cnt")
    rf = segment.poly_scatter_segment(data, b, moments)
    rp = segment.poly_scatter_segment(data, b_plain, moments)
    for k in moments:
        np.testing.assert_allclose(np.asarray(rf[k]), np.asarray(rp[k]),
                                   rtol=1e-5, atol=1e-5, err_msg=k)

    rng = np.random.RandomState(15)
    x = jnp.asarray(rng.rand(b.x.shape[0], 24), jnp.float32)
    gf = segment.poly_gather_segment(x, b, moments)
    gp = segment.poly_gather_segment(x, b_plain, moments)
    for k in moments:
        np.testing.assert_allclose(np.asarray(gf[k]), np.asarray(gp[k]),
                                   rtol=1e-5, atol=1e-5, err_msg=k)


def test_dispatch_tally_counts_fused_and_fallback(monkeypatch):
    """The trace-time dispatch tally: a marker-carrying batch counts
    :fused, a marker-less one :scatter, and the width gate falls back
    (the silent-fast-path-loss signal the telemetry manifest surfaces)."""
    from hydragnn_tpu.ops.poly_mp import POLY_MAX_F_MXMN
    from hydragnn_tpu.telemetry import pipeline

    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "fused")
    b = _batch(seed=16)
    data = _edge_data(b, seed=17, f=16)

    base = pipeline.dispatch_snapshot()
    segment.poly_scatter_segment(data, b, ("sum", "mx"))
    d1 = pipeline.dispatch_snapshot()
    assert d1.get("poly_scatter:fused", 0) \
        == base.get("poly_scatter:fused", 0) + 1

    ex = dict(b.extras)
    del ex["edge_perm_sender"]
    segment.poly_scatter_segment(data, b.replace(extras=ex), ("sum", "mx"))
    d2 = pipeline.dispatch_snapshot()
    assert d2.get("poly_scatter:scatter", 0) \
        == d1.get("poly_scatter:scatter", 0) + 1

    # width gate: F above the mxmn cap must take the composed path even
    # with the marker present — and still be numerically right
    wide = jnp.asarray(
        np.random.RandomState(18).rand(b.senders.shape[0],
                                       POLY_MAX_F_MXMN + 1), jnp.float32)
    out = segment.poly_scatter_segment(wide, b, ("sum", "mx"))
    d3 = pipeline.dispatch_snapshot()
    assert d3.get("poly_scatter:scatter", 0) \
        == d2.get("poly_scatter:scatter", 0) + 1
    np.testing.assert_allclose(
        np.asarray(out["sum"]),
        np.asarray(jax.ops.segment_sum(
            wide * jnp.asarray(b.edge_mask)[:, None],
            jnp.asarray(b.receivers), num_segments=b.x.shape[0])),
        rtol=1e-5, atol=1e-5)

    assert pipeline.dispatch_summary(
        {"poly_scatter:fused": 2}) == "fused"
    assert pipeline.dispatch_summary(
        {"a:fused": 1, "b:scatter": 2}) == "mixed(fused=1,scatter=2)"


def test_dispatch_tally_counts_egcl(monkeypatch):
    """The egcl dispatch site tallies fused vs scatter — that tally is
    what makes EGNN visible to bench's per-arch aggr_backend column —
    and a requested-but-denied fused path records a unified
    fused_fallback event carrying {arch, reason}."""
    from hydragnn_tpu.telemetry import pipeline as tp

    g = _egcl_batch(seed=11)
    cfg = ModelConfig(
        model_type="EGNN", input_dim=2, hidden_dim=F, output_dim=(1,),
        output_type=("graph",), graph_head=GraphHeadCfg(1, 8, 1, (8,)),
        node_head=None, task_weights=(1.0,), num_conv_layers=2,
        equivariance=True, radius=1.4, max_neighbours=8)
    model = create_model(cfg)
    monkeypatch.setenv("HYDRAGNN_EGCL_FUSED", "1")
    before = tp.dispatch_snapshot()
    variables = model.init({"params": jax.random.PRNGKey(0)}, g,
                           train=False)
    model.apply({"params": variables["params"]}, g, train=False)
    delta = tp.dispatch_delta(before, tp.dispatch_snapshot())
    assert delta.get("egcl:fused", 0) > 0
    monkeypatch.setenv("HYDRAGNN_EGCL_FUSED", "0")
    before = tp.dispatch_snapshot()
    model.apply({"params": variables["params"]}, g, train=False)
    delta = tp.dispatch_delta(before, tp.dispatch_snapshot())
    assert delta.get("egcl:scatter", 0) > 0
    # forcing fused requested-but-denied records the fallback reason on
    # the unified "fused" channel, tagged with the arch
    tp.pop_fallbacks("fused")
    monkeypatch.setenv("HYDRAGNN_EGCL_FUSED", "1")
    monkeypatch.setattr("hydragnn_tpu.ops.egcl_mp.EGCL_H_LIMIT", 1)
    model.apply({"params": variables["params"]}, g, train=False)
    fbs = tp.pop_fallbacks("fused")
    assert fbs and fbs[0]["reason"] == "width_gate"
    assert fbs[0]["arch"] == "EGNN"


def test_dispatch_tally_counts_cgcnn(monkeypatch):
    """The cgcnn dispatch site: marker-carrying batch tallies :fused,
    marker-less :scatter, and a requested-but-denied width emits the
    unified fused_fallback with arch=CGCNN."""
    from hydragnn_tpu.telemetry import pipeline as tp

    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "fused")
    b = _batch(seed=25)
    cfg = _model_cfg("CGCNN")
    model = create_model(cfg)
    before = tp.dispatch_snapshot()
    variables = model.init({"params": jax.random.PRNGKey(0),
                            "dropout": jax.random.PRNGKey(1)}, b,
                           train=False)
    model.apply({"params": variables["params"],
                 "batch_stats": variables.get("batch_stats", {})},
                b, train=False)
    delta = tp.dispatch_delta(before, tp.dispatch_snapshot())
    assert delta.get("cgcnn:fused", 0) > 0

    ex = dict(b.extras)
    del ex["edge_perm_sender"]
    b_plain = b.replace(extras=ex)
    tp.pop_fallbacks("fused")
    before = tp.dispatch_snapshot()
    model.apply({"params": variables["params"],
                 "batch_stats": variables.get("batch_stats", {})},
                b_plain, train=False)
    delta = tp.dispatch_delta(before, tp.dispatch_snapshot())
    assert delta.get("cgcnn:scatter", 0) > 0
    fbs = tp.pop_fallbacks("fused")
    assert fbs and fbs[0]["arch"] == "CGCNN"
    assert fbs[0]["reason"] == "no_sender_perm"
