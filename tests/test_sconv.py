"""The double-gated short convolution (ops/sconv.py) against a loop over
each document in numpy: values and every gradient, of the custom-VJP form
and of its plain twin; a one-node and a two-node graph, a boundary at every
offset of the three taps, padding rows between and after graphs; under
``jit`` + ``scan``; a change of one graph's rows moves no other graph's
output; ``reach`` and the counters for the step records; bfloat16 in,
bfloat16 out."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.ops.sconv import (
    conv_counts,
    graph_short_conv,
    graph_short_conv_plain,
    tap_reach,
)

C, K = 8, 3
# graph id and mask of 24 rows: graphs of 1, 2, 3 and 5 nodes, two padding
# rows BETWEEN graphs (a loader never makes those; the op must not care),
# a graph of 4, then padding to the end
LAYOUT = [(0, 1), (1, 2), (2, 3), (None, 2), (3, 5), (4, 4), (None, 7)]
FORMS = {"custom_vjp": graph_short_conv, "plain": graph_short_conv_plain}


def layout():
    gid, mask, spans, at = [], [], [], 0
    for g, n in LAYOUT:
        if g is not None:
            spans.append((at, at + n))
        gid += [g if g is not None else 5] * n
        mask += [g is not None] * n
        at += n
    return (np.asarray(gid, np.int32), np.asarray(mask, np.float32), spans)


def by_document(b, c, x, w, spans):
    """The definition: each document alone, left-padded with K - 1 zeros."""
    y = np.zeros_like(b)
    for lo, hi in spans:
        z = np.concatenate([np.zeros((K - 1, b.shape[1])),
                            b[lo:hi] * x[lo:hi]])
        for t in range(hi - lo):
            v = sum(w[K - 1 - j] * z[K - 1 + t - j] for j in range(K))
            y[lo + t] = c[lo + t] * v
    return y


@pytest.fixture(scope="module")
def arrays():
    rng = np.random.default_rng(0)
    n = sum(m for _, m in LAYOUT)
    b, c, x, dy = (rng.standard_normal((n, C)) for _ in range(4))
    return b, c, x, rng.standard_normal((K, C)), dy


def test_reach_is_the_index_inside_the_graph_capped_and_minus_one_on_padding():
    gid, mask, _ = layout()
    reach = np.asarray(tap_reach(jnp.asarray(gid), jnp.asarray(mask), K))
    assert reach.tolist() == (
        [0] + [0, 1] + [0, 1, 2] + [-1, -1] + [0, 1, 2, 2, 2]
        + [0, 1, 2, 2] + [-1] * 7)
    # no mask: every row is real, ids alone cut the graphs
    assert np.asarray(tap_reach(jnp.asarray(gid[:6]), None, K)).tolist() == [
        0, 0, 1, 0, 1, 2]
    rows, starts, cut = (float(v) for v in conv_counts(
        jnp.asarray(gid), jnp.asarray(mask), K))
    # a one-node graph cuts 2 taps, every longer one 3
    assert (rows, starts, cut) == (15.0, 5.0, 2.0 + 4 * 3.0)


@pytest.mark.parametrize("form", list(FORMS))
def test_values_and_gradients_match_the_loop_over_documents(arrays, form):
    b, c, x, w, dy = arrays
    gid, mask, spans = layout()
    fn = FORMS[form]
    args = tuple(jnp.asarray(a, jnp.float32) for a in (b, c, x, w))
    y, vjp = jax.vjp(lambda *a: fn(*a, jnp.asarray(gid), jnp.asarray(mask)),
                     *args)
    np.testing.assert_allclose(y, by_document(b, c, x, w, spans), atol=1e-5)
    # the gradients of sum(y * dy), by finite differences of the definition
    # (it is bilinear in (b, x), linear in c and in w: central differences
    # of step 1 are exact up to rounding)
    got = vjp(jnp.asarray(dy, jnp.float32))
    for i, name in enumerate("bcxw"):
        want = np.zeros_like(arrays[i])
        for idx in np.ndindex(*want.shape):
            hi, lo = [a.copy() for a in (b, c, x, w)], [
                a.copy() for a in (b, c, x, w)]
            hi[i][idx] += 0.5
            lo[i][idx] -= 0.5
            want[idx] = np.sum(dy * (by_document(*hi, spans)
                                     - by_document(*lo, spans)))
        np.testing.assert_allclose(got[i], want, atol=2e-4, err_msg=name)
    # padding rows: nothing out, nothing back
    pad = mask == 0
    assert not np.any(np.asarray(y)[pad])
    for g in got[:3]:
        assert not np.any(np.asarray(g)[pad])


def test_one_graphs_rows_move_no_other_graphs_output(arrays):
    b, c, x, w, _ = arrays
    gid, mask, spans = layout()
    lo, hi = spans[2]                       # the three-node graph
    args = [jnp.asarray(a, jnp.float32) for a in (b, c, x)]
    base = graph_short_conv(*args, jnp.asarray(w, jnp.float32),
                            jnp.asarray(gid), jnp.asarray(mask))
    moved = [a.at[lo:hi].add(3.0) for a in args]
    # ... and the padding rows next to it carry anything at all
    moved = [a.at[hi:hi + 2].set(1e6) for a in moved]
    out = graph_short_conv(*moved, jnp.asarray(w, jnp.float32),
                           jnp.asarray(gid), jnp.asarray(mask))
    other = np.ones(len(gid), bool)
    other[lo:hi] = False
    np.testing.assert_array_equal(np.asarray(out)[other],
                                  np.asarray(base)[other])
    assert np.all(np.asarray(out)[lo:hi] != np.asarray(base)[lo:hi])


def test_under_jit_and_scan_with_traced_ids(arrays):
    """As the trainer runs it: the step scanned, ids and mask traced."""
    b, c, x, w, dy = arrays
    gid, mask, spans = layout()

    def loss(params, batch):
        b_, c_, x_, w_ = params
        g, m, d = batch
        return jnp.sum(graph_short_conv(b_, c_, x_, w_, g, m) * d)

    @jax.jit
    def steps(params, batches):
        def body(carry, batch):
            v, g = jax.value_and_grad(loss)(params, batch)
            return carry + v, g
        return jax.lax.scan(body, 0.0, batches)

    params = tuple(jnp.asarray(a, jnp.float32) for a in (b, c, x, w))
    # step 0: the layout; step 1: ONE graph over every row
    batches = (jnp.stack([jnp.asarray(gid), jnp.zeros_like(gid)]),
               jnp.stack([jnp.asarray(mask), jnp.ones_like(mask)]),
               jnp.stack([jnp.asarray(dy, jnp.float32)] * 2))
    total, grads = steps(params, batches)
    want0 = np.sum(by_document(b, c, x, w, spans) * dy)
    want1 = np.sum(by_document(b, c, x, w, [(0, len(gid))]) * dy)
    assert float(total) == pytest.approx(want0 + want1, rel=1e-5)
    eager = jax.grad(loss)(params, tuple(a[0] for a in batches))
    for g, e in zip(grads, eager):
        np.testing.assert_allclose(g[0], e, atol=1e-5)


def test_bfloat16_rows_in_bfloat16_out_float32_inside(arrays):
    b, c, x, w, dy = arrays
    gid, mask, spans = layout()
    rows = tuple(jnp.asarray(a, jnp.bfloat16) for a in (b, c, x))
    w32 = jnp.asarray(w, jnp.float32)
    y, vjp = jax.vjp(lambda *a: graph_short_conv(
        *a, jnp.asarray(gid), jnp.asarray(mask)), *rows, w32)
    assert y.dtype == jnp.bfloat16
    grads = vjp(jnp.asarray(dy, jnp.bfloat16))
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3 + [jnp.float32]
    # the rounded INPUTS through the float32 definition: one rounding of
    # the result apart
    exact = by_document(*(np.asarray(a, np.float64) for a in rows), w, spans)
    np.testing.assert_allclose(np.asarray(y, np.float64), exact,
                               rtol=2 ** -8, atol=1e-6)
