"""ops/gdn.py: the gated delta rule over each graph's nodes.  The chunked
form (the TPU path) against the sequential recurrence, values and all five
gradients, at chunk sizes that put a graph boundary inside a chunk, on a
chunk's edge, and a graph longer than three chunks; the recurrence against
a numpy loop over documents; padding nodes inert; packed graphs equal each
graph alone; ``beta = 0`` leaves the state decayed only and ``g = 0, beta =
1`` is the plain delta rule; the unit lower-triangular inverse and its
written-down backward pass; and what a checkpoint round a call keeps by
name: the inverse, so that the gradient program holds the doubling rounds
once."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.ops.gdn import (
    GDN_INV,
    graph_gated_delta,
    inverse_bytes,
    unit_lower_inverse,
)

HK, HV, DK, DV = 2, 4, 8, 8


def case(lengths, pad, seed=0):
    """Packed documents of ``lengths`` and ``pad`` padding nodes behind
    them: (q, k, v, g, beta, node_gid, node_mask) as the layer hands them
    over (q, k l2-normed, q scaled)."""
    rng = np.random.default_rng(seed)
    n = sum(lengths) + pad
    gid = np.concatenate([np.full(m, i) for i, m in enumerate(lengths)]
                         + [np.full(pad, len(lengths))]).astype(np.int32)
    mask = np.concatenate([np.ones(sum(lengths)),
                           np.zeros(pad)]).astype(np.float32)
    q = rng.normal(size=(n, HK, DK)).astype(np.float32)
    k = rng.normal(size=(n, HK, DK)).astype(np.float32)
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(n, HV, DV)).astype(np.float32)
    g = (-0.5 * np.abs(rng.normal(size=(n, HV)))).astype(np.float32)
    beta = (1 / (1 + np.exp(-rng.normal(size=(n, HV))))).astype(np.float32)
    return tuple(map(jnp.asarray, (q, k, v, g, beta, gid, mask)))


def loop(q, k, v, g, beta, lengths):
    """The recurrence as written, one document at a time, in numpy."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    out, at = np.zeros(v.shape), 0
    for m in lengths:
        s = np.zeros((HV, DK, DV))
        for t in range(at, at + m):
            for h in range(HV):
                kh, qh = k[t, h // (HV // HK)], q[t, h // (HV // HK)]
                decayed = np.exp(g[t, h]) * s[h]
                r = v[t, h] - decayed.T @ kh
                s[h] = decayed + beta[t, h] * np.outer(kh, r)
                out[t, h] = s[h].T @ qh
        at += m
    return out


def value_and_grads(args, backend, chunk):
    q, k, v, g, beta, gid, mask = args

    def loss(q, k, v, g, beta):
        o = graph_gated_delta(q, k, v, g, beta, gid, mask, chunk=chunk,
                              backend=backend)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape))), o

    with jax.default_matmul_precision("highest"):
        (_, o), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(q, k, v, g, beta)
    return o, grads


# (lengths, padding nodes, chunk): boundaries inside a chunk; on a chunk's
# edge; one graph longer than three chunks; a one-node and a two-node graph
# beside a long one, with padding that fills whole chunks
CASES = {
    "boundaries_inside_chunks": ((5, 20, 1, 2, 12, 9), 7, 8),
    "boundaries_on_chunk_edges": ((16, 16, 32), 0, 16),
    "one_graph_over_three_chunks": ((40,), 0, 8),
    "long_graph_between_short_ones": ((3, 70, 5), 34, 16),
}


@pytest.mark.parametrize("name", list(CASES))
def test_chunked_is_the_recurrence_values_and_gradients(name):
    lengths, pad, chunk = CASES[name]
    args = case(lengths, pad)
    o_seq, g_seq = value_and_grads(args, "sequential", chunk)
    o_chk, g_chk = value_and_grads(args, "chunked", chunk)
    np.testing.assert_allclose(o_chk, o_seq, atol=2e-6)
    for a, b, what in zip(g_chk, g_seq, ("q", "k", "v", "g", "beta")):
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        assert float(jnp.max(jnp.abs(a - b))) < 5e-6 * scale, what


@pytest.mark.parametrize("backend", ["sequential", "chunked"])
def test_the_rule_is_the_written_recurrence(backend):
    lengths, pad = (5, 20, 1, 2, 12, 9), 7
    args = case(lengths, pad, seed=3)
    with jax.default_matmul_precision("highest"):
        o = graph_gated_delta(*args, chunk=8, backend=backend)
    want = loop(*args[:5], lengths)
    np.testing.assert_allclose(o, want, atol=3e-6)
    # padding nodes give exact zeros
    assert not np.any(np.asarray(o)[sum(lengths):])


@pytest.mark.parametrize("backend", ["sequential", "chunked"])
def test_padding_nodes_are_inert(backend):
    """What the padding nodes carry changes nothing, in values and in the
    real nodes' gradients, and no gradient reaches them."""
    lengths, pad = (6, 11), 15
    args = case(lengths, pad, seed=1)
    real = sum(lengths)
    junk = [a.at[real:].set(7.0) for a in args[:3]] + [
        args[3].at[real:].set(-3.0), args[4].at[real:].set(0.9)]
    o_a, g_a = value_and_grads(args, backend, 8)
    o_b, g_b = value_and_grads(tuple(junk) + args[5:], backend, 8)
    np.testing.assert_array_equal(o_a, o_b)
    for a, b in zip(g_a, g_b):
        np.testing.assert_array_equal(a[:real], b[:real])
        assert not np.any(np.asarray(b[real:]))


@pytest.mark.parametrize("backend", ["sequential", "chunked"])
def test_packed_graphs_equal_each_graph_alone(backend):
    lengths = (5, 20, 1, 12)
    q, k, v, g, beta, gid, mask = case(lengths, 2, seed=2)
    with jax.default_matmul_precision("highest"):
        packed = graph_gated_delta(q, k, v, g, beta, gid, mask, chunk=8,
                                   backend=backend)
        at = 0
        for m in lengths:
            rows = slice(at, at + m)
            alone = graph_gated_delta(
                q[rows], k[rows], v[rows], g[rows], beta[rows],
                jnp.zeros((m,), jnp.int32), None, chunk=8, backend=backend)
            np.testing.assert_allclose(packed[rows], alone, atol=2e-6)
            at += m


@pytest.mark.parametrize("backend", ["sequential", "chunked"])
def test_beta_zero_writes_nothing_and_no_decay_is_the_plain_delta_rule(
        backend):
    lengths = (9, 14)
    q, k, v, g, beta, gid, mask = case(lengths, 1, seed=4)
    with jax.default_matmul_precision("highest"):
        # beta = 0: the state is decayed only, so from zero it stays zero
        o = graph_gated_delta(q, k, v, g, jnp.zeros_like(beta), gid, mask,
                              chunk=8, backend=backend)
        assert not np.any(np.asarray(o))
        # g = 0, beta = 1: S_t = S_{t-1} + k_t (v_t - S_{t-1}^T k_t)^T, the
        # plain delta rule: with unit keys the state then returns v_t for
        # k_t exactly
        one, zero = jnp.ones_like(beta), jnp.zeros_like(g)
        o = graph_gated_delta(k, k, v, zero, one, gid, mask, chunk=8,
                              backend=backend)
    np.testing.assert_allclose(
        np.asarray(o)[:sum(lengths)], np.asarray(v)[:sum(lengths)],
        atol=5e-6)


def test_unit_lower_inverse_and_its_backward_pass():
    rng = np.random.default_rng(5)
    a = jnp.asarray(np.tril(0.3 * rng.normal(size=(3, 2, 16, 16)), -1),
                    jnp.float32)
    eye = jnp.eye(16)
    with jax.default_matmul_precision("highest"):
        x = unit_lower_inverse(a)
        np.testing.assert_allclose(x @ (eye + a), jnp.broadcast_to(
            eye, a.shape), atol=2e-5)
        # the worst case of a power series: ones below the diagonal, whose
        # inverse is the bidiagonal [1, -1]; exact here
        ones = jnp.tril(jnp.ones((64, 64)), -1)
        np.testing.assert_array_equal(
            unit_lower_inverse(ones),
            jnp.eye(64) - jnp.eye(64, k=-1))
        w = jnp.asarray(rng.normal(size=a.shape), jnp.float32)
        got = jax.grad(lambda a: jnp.sum(unit_lower_inverse(a) * w))(a)
        want = jax.grad(lambda a: jnp.sum(
            jnp.linalg.inv(eye + a) * w))(a)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def square_products(jaxpr, c):
    """The ``dot_general``s of ``jaxpr`` and of every jaxpr inside it whose
    two operands are both [..., c, c]: the inverse's rounds and its backward
    rule's pair, and nothing else of the rule while no head size is c."""
    own = sum(e.primitive.name == "dot_general"
              and all(v.aval.shape[-2:] == (c, c) for v in e.invars)
              for e in jaxpr.eqns)
    return own + sum(square_products(sub, c) for e in jaxpr.eqns
                     for sub in jax.core.jaxprs_in_params(e.params))


def checkpointed(args, chunk, policy=None, checkpoint=True):
    """(loss and the five gradients) of one chunked call, under
    ``jax.checkpoint`` with ``policy`` unless ``checkpoint`` is off, as a
    function of q, k, v, g, beta."""
    gid, mask = args[5:]

    def rule(q, k, v, g, beta):
        return graph_gated_delta(q, k, v, g, beta, gid, mask, chunk=chunk,
                                 backend="chunked")

    if checkpoint:
        rule = jax.checkpoint(rule, policy=policy)

    def loss(*operands):
        o = rule(*operands)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape)))

    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))


names = jax.checkpoint_policies.save_only_these_names


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_checkpoint_that_keeps_the_inverse_runs_the_rounds_once(chunk):
    """One run of the rounds is ``2 (log2 C - 1)`` products of [C, C] by
    [C, C] and the backward rule two more.  A checkpoint recomputes the
    rounds (two runs) unless its policy keeps ``GDN_INV``, which is named on
    the array the backward rule reads: a name on another variable would
    leave the count where a policy without it has it."""
    assert chunk not in (DK, DV)
    args = case((24, 40), 0, seed=6)
    rounds = 2 * (chunk.bit_length() - 2)

    def count(*policy, **how):
        jaxpr = jax.make_jaxpr(checkpointed(args, chunk, *policy, **how))(
            *args[:5])
        return square_products(jaxpr.jaxpr, chunk)

    assert count(checkpoint=False) == rounds + 2
    assert count() == 2 * rounds + 2
    assert count(names("some.other.name")) == 2 * rounds + 2
    assert count(names("some.other.name", GDN_INV)) == rounds + 2


def test_keeping_the_inverse_changes_no_value_and_no_gradient():
    args = case((5, 20, 1, 2, 12, 9), 7, seed=8)
    with jax.default_matmul_precision("highest"):
        kept = checkpointed(args, 8, names(GDN_INV))(*args[:5])
        bare = checkpointed(args, 8)(*args[:5])
        free = checkpointed(args, 8, checkpoint=False)(*args[:5])
    for other in (bare, free):
        for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(other)):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_the_inverses_bytes_are_of_the_chunked_shape_alone():
    # 203 chunks of 64 x 32 heads x [64, 64] float32: the cell's 106.4 MB
    assert inverse_bytes(12968, 32, 64, "chunked") == 203 * 32 * 64 * 64 * 4
    assert inverse_bytes(56, 4, 8, "chunked") == 7 * 4 * 8 * 8 * 4
    assert inverse_bytes(12968, 32, 64, "sequential") == 0


def test_bfloat16_operands_stay_near_float32():
    args = case((24, 40), 0, seed=6)
    q, k, v = (a.astype(jnp.bfloat16) for a in args[:3])
    o32 = graph_gated_delta(*args, chunk=16, backend="chunked")
    o16 = graph_gated_delta(q, k, v, *args[3:], chunk=16, backend="chunked")
    assert o16.dtype == jnp.float32
    dev = float(jnp.linalg.norm(o16 - o32) / jnp.linalg.norm(o32))
    assert 1e-4 < dev < 3e-2


def test_what_it_refuses():
    args = case((8,), 0)
    with pytest.raises(ValueError, match="power of two"):
        graph_gated_delta(*args, chunk=12, backend="chunked")
    with pytest.raises(ValueError, match="unknown gated-delta backend"):
        graph_gated_delta(*args, backend="fused")
