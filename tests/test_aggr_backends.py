"""The aggregation-path decision: two backends, one reader.

``scatter`` is ``jax.ops.segment_sum``; under ``fused`` the sums that know
their ids are sorted ride ``ops/fused_mp.segment_sum_dense``.  That kernel is
held here to the drop-in contract of a segment sum — same forward values, same
gradients, same silent dropping of out-of-range ids (how padded edges and
triplets are discarded), with and without its ``valid`` mask — and the
dispatchers in ``graph/segment.py`` to agreeing across the two backends.  A
value that is neither takes the typo path.  Pallas runs in interpret mode
off-TPU, so this exercises the real kernel logic on the CPU.
"""

import os
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hydragnn_tpu.graph import segment
from hydragnn_tpu.graph.batch import GraphSample, HeadSpec, PadSpec, collate
from hydragnn_tpu.graph.neighborlist import radius_graph
from hydragnn_tpu.ops import aggregate
from hydragnn_tpu.ops.aggregate import KNOWN_BACKENDS, aggr_backend
from hydragnn_tpu.ops.fused_mp import segment_sum_dense
from hydragnn_tpu.telemetry import pipeline

ENV = "HYDRAGNN_AGGR_BACKEND"


def _dense(data, ids, n):
    return segment_sum_dense(data, ids, n)


def _dense_valid(data, ids, n):
    """As the models call it: rows the mask drops carry zeros, and the mask
    parks them out of range whatever slot their id names."""
    valid = ((ids >= 0) & (ids < n)).astype(jnp.int32)
    parked = jnp.where(valid != 0, ids, n - 1)
    m = valid.astype(data.dtype)
    return segment_sum_dense(data * (m[:, None] if data.ndim > 1 else m),
                             parked, n, valid=valid)


IMPLS = {"dense": _dense, "dense_valid": _dense_valid}


def _case(e=70, n=13, f=5, seed=0):
    """Sorted ids; the last seven rows are out of range and must vanish."""
    rng = np.random.RandomState(seed)
    data = rng.randn(e, f).astype(np.float32)
    ids = np.sort(rng.randint(0, n, size=e))
    ids[-7:] = n + np.sort(rng.randint(0, 3, size=7))
    return jnp.asarray(data), jnp.asarray(ids), n


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_forward_matches_scatter(impl):
    data, ids, n = _case()
    want = jax.ops.segment_sum(data, ids, n)
    got = IMPLS[impl](data, ids, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_gradient_matches_scatter(impl):
    data, ids, n = _case(seed=1)
    w = jnp.asarray(np.random.RandomState(2).randn(n, data.shape[1]),
                    jnp.float32)

    def loss(fn):
        return lambda d: jnp.sum(fn(d, ids, n) * w)

    g_want = jax.grad(loss(jax.ops.segment_sum))(data)
    g_got = jax.grad(loss(IMPLS[impl]))(data)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(g_got)[-7:].any()   # dropped rows: zero gradient


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_jit_and_1d(impl):
    data, ids, n = _case(e=40, f=1, seed=3)
    data1d = data[:, 0]
    want = jax.ops.segment_sum(data1d, ids, n)
    got = jax.jit(IMPLS[impl], static_argnums=2)(data1d, ids, n)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_bf16_inputs(impl):
    """bf16 messages accumulate in f32 and come back as bf16."""
    data, ids, n = _case(seed=6)
    got = IMPLS[impl](data.astype(jnp.bfloat16), ids, n)
    assert got.dtype == jnp.bfloat16
    want = jax.ops.segment_sum(data.astype(jnp.bfloat16), ids, n)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=0.05, atol=0.05)


@pytest.mark.parametrize("n", [600, 2500])  # 2500 spans >2 node blocks
def test_dense_forward_and_grad_across_node_blocks(n):
    from hydragnn_tpu.ops.fused_mp import _NODE_BLOCK

    assert 2500 > 2 * _NODE_BLOCK
    rng = np.random.RandomState(0)
    ids = jnp.asarray(np.repeat(np.arange(n), rng.randint(0, 13, size=n)))
    data = jnp.asarray(rng.randn(len(ids), 7).astype(np.float32))
    want = jax.ops.segment_sum(data, ids, n)
    got = segment_sum_dense(data, ids, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    w = jnp.asarray(np.random.RandomState(1).randn(n, data.shape[1]),
                    jnp.float32)
    g_want = jax.grad(
        lambda d: jnp.sum(jax.ops.segment_sum(d, ids, n) * w))(data)
    g_got = jax.grad(
        lambda d: jnp.sum(segment_sum_dense(d, ids, n) * w))(data)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                               rtol=1e-5, atol=1e-5)


def _collated():
    rng = np.random.RandomState(2)
    samples = []
    for _ in range(6):
        pos = rng.rand(10, 3).astype(np.float32) * 2.5
        samples.append(GraphSample(
            x=rng.rand(10, 1).astype(np.float32), pos=pos,
            edge_index=radius_graph(pos, 1.3, 8),
            graph_y=rng.rand(1).astype(np.float32)))
    return collate(samples, PadSpec.for_batch(6, 12, 90),
                   [HeadSpec("e", "graph", 1)])


def test_dense_on_collated_receivers():
    """The real invariant source: collate's receivers with a padded tail,
    whose edges all name one real node slot and must not reach it."""
    b = _collated()
    assert int(b.edge_mask.sum()) < b.num_edges
    rng = np.random.RandomState(3)
    data = jnp.asarray(rng.randn(b.num_edges, 5).astype(np.float32))
    masked = data * b.edge_mask[:, None]
    want = jax.ops.segment_sum(masked, b.receivers, b.num_nodes)
    got = segment_sum_dense(masked, b.receivers, b.num_nodes,
                            valid=b.edge_mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", KNOWN_BACKENDS)
def test_dispatchers_agree_with_default(backend, monkeypatch):
    """``gather_mul_segment`` / ``gather_segment`` / ``scatter_segment`` on
    one collated batch give the unset default's answer under either
    backend, and the ``aggr_dispatch`` tally names the path that ran.

    The baseline is computed with the knob removed so a pre-set shell env
    can't make this vacuous."""
    monkeypatch.delenv(ENV, raising=False)
    plain = _collated()
    assert "edge_perm_sender" not in (plain.extras or {})
    monkeypatch.setenv(ENV, backend)
    b = _collated()
    assert ("edge_perm_sender" in (b.extras or {})) == (backend == "fused")

    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(b.num_nodes, 16).astype(np.float32))
    w = jnp.asarray(rng.randn(b.num_edges, 16).astype(np.float32))
    ops = {
        "gather_mul": lambda g: segment.gather_mul_segment(x, w, g),
        "gather_sum": lambda g: segment.gather_segment(x, g),
        "scatter_sum": lambda g: segment.scatter_segment(w, g),
    }
    for op, fn in ops.items():
        want = fn(plain)
        before = pipeline.dispatch_snapshot()
        got = fn(b)
        delta = pipeline.dispatch_delta(before, pipeline.dispatch_snapshot())
        assert delta == {f"{op}:{backend}": 1}, (op, delta)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("value", ["onehot", "pallas"])
def test_retired_value_takes_the_typo_path(value, monkeypatch):
    """A backend that no longer exists is an unknown value: the reader warns
    once and every check misses (collate attaches no marker), and a config
    that names it is refused with the two that exist."""
    import hydragnn_tpu

    monkeypatch.setattr(aggregate, "_warned_unknown", set())
    monkeypatch.setenv(ENV, f" {value.upper()} ")   # one normalisation
    with pytest.warns(UserWarning, match=r"'scatter', 'fused'"):
        assert aggr_backend() == value
    with warnings.catch_warnings():
        warnings.simplefilter("error")                # once, not per read
        assert aggr_backend() == value
        assert "edge_perm_sender" not in (_collated().extras or {})

    monkeypatch.delenv(ENV)
    config = {"NeuralNetwork": {"Architecture": {
        "aggregation_backend": value}}}
    with pytest.raises(ValueError, match=r"\('scatter', 'fused'\)"):
        hydragnn_tpu.run_training(config)
    assert ENV not in os.environ


def test_backend_scope_sets_and_restores(monkeypatch):
    """The one scoped writer: sets for the block, restores a value or its
    absence on every exit path, and leaves a user-set value alone when
    asked not to override."""
    from hydragnn_tpu.ops.aggregate import backend_scope

    monkeypatch.delenv(ENV, raising=False)
    with backend_scope("fused"):
        assert aggr_backend() == "fused"
    assert ENV not in os.environ
    with backend_scope(None, override=False):    # a config without the key
        assert ENV not in os.environ
    monkeypatch.setenv(ENV, "scatter")
    with backend_scope("fused", override=False):
        assert aggr_backend() == "scatter"
    with pytest.raises(RuntimeError):
        with backend_scope("fused"):
            assert aggr_backend() == "fused"
            raise RuntimeError
    assert os.environ[ENV] == "scatter"
