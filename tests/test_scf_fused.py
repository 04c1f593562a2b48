"""Parity tests for the fused CFConv edge pipeline (ops/scf_mp.py, the
packing front of fused_mp's chain form): forward, all gradients, and the
model-level SCFConv wiring vs the composed path — interpret mode on CPU."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hydragnn_tpu.graph.batch import GraphSample, HeadSpec, PadSpec, collate
from hydragnn_tpu.graph.neighborlist import radius_graph
from hydragnn_tpu.ops.aggregate import backend_scope
from hydragnn_tpu.ops.scf_mp import scf_edge_pipeline
from hydragnn_tpu.models.layers import shifted_softplus

F, G = 16, 7


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
    with backend_scope("fused"):
        return collate(samples, pad, [HeadSpec("e", "graph", 1)])


def _inputs(g, seed=1):
    rng = np.random.RandomState(seed)
    n = g.x.shape[0]
    e = g.senders.shape[0]
    h = jnp.asarray(rng.randn(n, F), jnp.float32)
    rbf = jnp.asarray(rng.rand(e, G), jnp.float32)
    cm = jnp.asarray(rng.rand(e).astype(np.float32)
                     * np.asarray(g.edge_mask))
    w0 = jnp.asarray(rng.randn(G, F) * 0.4, jnp.float32)
    b0 = jnp.asarray(rng.randn(F) * 0.1, jnp.float32)
    w1 = jnp.asarray(rng.randn(F, F) * 0.3, jnp.float32)
    b1 = jnp.asarray(rng.randn(F) * 0.1, jnp.float32)
    return h, rbf, cm, w0, b0, w1, b1


def _composed(h, rbf, cm, w0, b0, w1, b1, senders, receivers, num_nodes):
    filt = (shifted_softplus(rbf @ w0 + b0) @ w1 + b1) * cm[:, None]
    msgs = h[senders] * filt
    return jax.ops.segment_sum(msgs, receivers, num_segments=num_nodes)


# one node block (54 nodes), and collated molecules across four of them
# with ids above 256 and an edge list of several 128-edge id granules
_SIZES = pytest.mark.parametrize("n_graphs", [6, 48], ids=["1blk", "4blk"])


@_SIZES
def test_forward_matches_composed(n_graphs):
    g = _batch(n_graphs)
    h, rbf, cm, w0, b0, w1, b1 = _inputs(g)
    perm = jnp.asarray(g.extras["edge_perm_sender"])
    em = jnp.asarray(g.edge_mask).astype(jnp.int32)
    out = scf_edge_pipeline(h, rbf, cm, em, w0, b0, w1, b1,
                            g.senders, g.receivers, perm)
    ref = _composed(h, rbf, cm, w0, b0, w1, b1, g.senders, g.receivers,
                    h.shape[0])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@_SIZES
def test_gradients_match_composed(n_graphs):
    g = _batch(n_graphs, seed=3)
    inputs = _inputs(g, seed=4)
    perm = jnp.asarray(g.extras["edge_perm_sender"])
    n = inputs[0].shape[0]
    # non-uniform weighting catches transposition errors a plain sum hides
    rng = np.random.RandomState(7)
    wmat = jnp.asarray(rng.randn(n, F), jnp.float32)

    em = jnp.asarray(g.edge_mask).astype(jnp.int32)

    def loss_fused(args):
        h_, rbf_, cm_ = args[:3]
        out = scf_edge_pipeline(h_, rbf_, cm_, em, *args[3:],
                                g.senders, g.receivers, perm)
        return jnp.sum(out * wmat)

    def loss_ref(args):
        out = _composed(*args, g.senders, g.receivers, n)
        return jnp.sum(out * wmat)

    gf = jax.grad(loss_fused)(inputs)
    gr = jax.grad(loss_ref)(inputs)
    emask = np.asarray(g.edge_mask)
    names = ("h", "rbf", "cm", "w0", "b0", "w1", "b1")
    for name, a, b in zip(names, gf, gr):
        a, b = np.asarray(a), np.asarray(b)
        if name == "cm":
            # contract: masked edges get EXACTLY zero dcm from the fused
            # path (their blocks are schedule-skipped); the composed dcm
            # is nonzero there but unconsumed by any caller
            assert np.all(a[emask == 0] == 0.0)
            a, b = a[emask == 1], b[emask == 1]
        elif name == "rbf":
            assert np.all(a[emask == 0] == 0.0)
            a, b = a[emask == 1], b[emask == 1]
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4,
                                   err_msg=name)


def _schnet(num_filters=F, num_gaussians=G, layers=2):
    from hydragnn_tpu.models.base import GraphHeadCfg, ModelConfig
    from hydragnn_tpu.models.create import create_model

    return create_model(ModelConfig(
        model_type="SchNet", input_dim=2, hidden_dim=F, output_dim=(1,),
        output_type=("graph",), graph_head=GraphHeadCfg(1, 8, 1, (8,)),
        node_head=None, task_weights=(1.0,), num_conv_layers=layers,
        num_gaussians=num_gaussians, num_filters=num_filters, radius=1.4,
        max_neighbours=8))


def _unmarked(g):
    """The same batch as the ``scatter`` backend collates it: without the
    verified-invariants marker every op takes its composed path."""
    return g.replace(extras={k: v for k, v in g.extras.items()
                              if k != "edge_perm_sender"})


def test_model_level_fused_equals_composed():
    """SCFConv on the fused path (the filter made inside the kernels) vs
    the composed path, chosen by the batch's marker as the backends do:
    same params (the DenseParams tree matches), same forward, same param
    grads."""
    g = _batch(seed=5)
    model = _schnet()
    variables = model.init({"params": jax.random.PRNGKey(0)}, g, train=False)

    def loss(params, batch):
        out = model.apply({"params": params}, batch, train=False)
        return sum(jnp.sum(o * o) for o in out)

    lf, lg = loss(variables["params"], g), loss(variables["params"],
                                                _unmarked(g))
    np.testing.assert_allclose(float(lf), float(lg), rtol=2e-5)

    gf = jax.grad(lambda p: loss(p, g))(variables["params"])
    gp = jax.grad(lambda p: loss(p, _unmarked(g)))(variables["params"])
    flat_f = jax.tree_util.tree_leaves_with_path(gf)
    flat_p = dict(jax.tree_util.tree_leaves_with_path(gp))
    assert flat_f  # same tree structure both ways
    for path, leaf in flat_f:
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat_p[path]), rtol=5e-4, atol=5e-4,
            err_msg=str(path))


def test_filter_dispatch_follows_shape_and_backend():
    """Where the filter is made is chosen by what the code can observe —
    the batch's marker and the structural limits — with no width floor and
    no knob: narrow filters run in the kernels too; a basis wider than the
    geometry tile or filters beyond the VMEM limit keep the composed
    filter (and still ride the array kernel); the scatter backend
    composes everything.  Each choice is tallied."""
    from hydragnn_tpu.graph.segment import CFFilter
    from hydragnn_tpu.telemetry import pipeline

    def fits(f, gauss):
        z = jnp.zeros
        return CFFilter(z((4, gauss)), z((4,)), z((gauss, f)), z((f,)),
                        z((f, f)), z((f,))).fits_kernel()

    assert fits(64, 50) and fits(128, 50) and fits(1024, 127)
    assert not fits(2048, 50)       # [F, F] blocks beyond VMEM
    assert not fits(512, 200)       # basis exceeds the geometry lanes

    g = _batch(seed=12)

    def tally(batch, **kw):
        model = _schnet(**kw)
        before = pipeline.dispatch_snapshot()
        jax.eval_shape(lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, batch, train=False))
        return pipeline.dispatch_delta(before, pipeline.dispatch_snapshot())

    fused = tally(g)
    assert fused["gather_mul:fused"] == 2 == fused["gather_mul_filter:fused"]
    assert "gather_mul_filter:scatter" not in fused
    wide_basis = tally(g, num_gaussians=200)
    assert wide_basis["gather_mul:fused"] == 2
    assert wide_basis["gather_mul_filter:scatter"] == 2
    composed = tally(_unmarked(g))
    assert composed["gather_mul:scatter"] == 2
    assert composed["gather_mul_filter:scatter"] == 2


def test_bf16_gradients_within_tolerance():
    """bf16 models run the fused filter MLP and ALL backward matmuls
    (incl. dW0/dW1 weight grads and drbf) with bf16 operands, while the
    composed path they replace evaluates the filter chain in f32 — so
    switching backends changes filter numerics beyond the stream dtype.
    This pins the bf16 gradient drift against the f32 composed reference
    (round-4 advisor finding 1)."""
    g = _batch(seed=9)
    h, rbf, cm, w0, b0, w1, b1 = _inputs(g, seed=10)
    perm = jnp.asarray(g.extras["edge_perm_sender"])
    em = jnp.asarray(g.edge_mask).astype(jnp.int32)
    n = h.shape[0]
    rng = np.random.RandomState(11)
    wmat = jnp.asarray(rng.randn(n, F), jnp.float32)

    def loss_fused(args):
        h_, rbf_, cm_ = args[:3]
        out = scf_edge_pipeline(h_.astype(jnp.bfloat16), rbf_, cm_, em,
                                *args[3:], g.senders, g.receivers, perm)
        return jnp.sum(out.astype(jnp.float32) * wmat)

    def loss_ref(args):
        out = _composed(*args, g.senders, g.receivers, n)
        return jnp.sum(out * wmat)

    inputs = (h, rbf, cm, w0, b0, w1, b1)
    gf = jax.grad(loss_fused)(inputs)
    gr = jax.grad(loss_ref)(inputs)
    emask = np.asarray(g.edge_mask).astype(bool)
    for name, a, b in zip(("h", "rbf", "cm", "w0", "b0", "w1", "b1"),
                          gf, gr):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        if name in ("rbf", "cm"):
            a, b = a[emask], b[emask]
        # bf16 operands: ~8 mantissa bits through two matmul layers
        scale = np.abs(b).max() + 1e-6
        err = np.abs(a - b).max() / scale
        assert err < 0.04, (name, err)


def test_bf16_forward_within_tolerance():
    """bf16 inputs ride bf16 windows/W1 in VMEM (halved stream bytes);
    result must stay within bf16 tolerance of the f32 composed path."""
    g = _batch(seed=6)
    h, rbf, cm, w0, b0, w1, b1 = _inputs(g, seed=8)
    perm = jnp.asarray(g.extras["edge_perm_sender"])
    em = jnp.asarray(g.edge_mask).astype(jnp.int32)
    out = scf_edge_pipeline(h.astype(jnp.bfloat16), rbf, cm, em,
                            w0, b0, w1, b1, g.senders, g.receivers, perm)
    ref = _composed(h, rbf, cm, w0, b0, w1, b1, g.senders, g.receivers,
                    h.shape[0])
    assert out.dtype == jnp.bfloat16
    scale = float(jnp.max(jnp.abs(ref))) + 1e-6
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) / scale
    assert err < 0.03, err
