"""ops/ssm.py: the chunked state-space scan against the sequential
recurrence (values and gradients) with graph boundaries inside a chunk, a
one-node graph, padding rows and a length that is no multiple of the chunk;
both against a per-graph numpy loop; the convolution never reads across a
boundary; and the whole under ``jit`` + ``lax.scan``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.ops.ssm import (
    graph_causal_conv,
    graph_ssm,
    graph_starts,
    scan_counts,
)

H, P, G, S = 4, 8, 2, 16


def _batch(lengths, n, seed=0):
    """Nodes of graphs of ``lengths`` then padding up to ``n`` (padding
    nodes carry the last graph slot's id, as collate gives them)."""
    rng = np.random.default_rng(seed)
    real = int(sum(lengths))
    gid = np.full((n,), len(lengths), np.int32)
    gid[:real] = np.repeat(np.arange(len(lengths)), lengths)
    mask = (np.arange(n) < real).astype(np.float32)
    arrays = {
        "x": rng.normal(size=(n, H, P)),
        "dt": np.log1p(np.exp(rng.normal(size=(n, H)) - 1.0)),
        "A": -rng.uniform(0.5, 4.0, size=(H,)),
        "B": rng.normal(size=(n, G, S)) / np.sqrt(S),
        "C": rng.normal(size=(n, G, S)) / np.sqrt(S),
        "D": rng.normal(size=(H,)),
    }
    return ({k: jnp.asarray(v, jnp.float32) for k, v in arrays.items()},
            jnp.asarray(gid), jnp.asarray(mask))


def _by_hand(a, gid, mask):
    """The recurrence, one graph at a time, in float64 numpy."""
    a = {k: np.asarray(v, np.float64) for k, v in a.items()}
    gid, mask = np.asarray(gid), np.asarray(mask)
    y = a["D"][None, :, None] * a["x"]
    for g in np.unique(gid[mask > 0]):
        s = np.zeros((H, P, S))
        for t in np.flatnonzero((gid == g) & (mask > 0)):
            for h in range(H):
                grp = h // (H // G)
                s[h] = (np.exp(a["dt"][t, h] * a["A"][h]) * s[h]
                        + a["dt"][t, h] * np.outer(a["x"][t, h],
                                                   a["B"][t, grp]))
                y[t, h] += s[h] @ a["C"][t, grp]
    return y


CASES = {
    # a boundary inside a chunk, a one-node graph, padding, 37 = 2 x 16 + 5
    "boundaries_in_chunk": ([5, 1, 9, 14], 37, 16),
    # a graph over several chunks, ending on a chunk's last node
    "graph_over_chunks": ([32, 7], 48, 8),
    # one chunk holds everything; no padding at all
    "one_chunk": ([3, 4], 7, 16),
    # a graph's first node is a chunk's first node, whole chunks of padding
    "starts_on_chunk": ([16, 16, 3], 80, 16),
}


@pytest.mark.parametrize("case", list(CASES))
def test_chunked_and_sequential_give_the_recurrence(case):
    lengths, n, chunk = CASES[case]
    a, gid, mask = _batch(lengths, n)
    want = _by_hand(a, gid, mask)
    with jax.default_matmul_precision("highest"):
        for backend in ("sequential", "chunked"):
            got = graph_ssm(a["x"], a["dt"], a["A"], a["B"], a["C"], a["D"],
                            gid, mask, chunk=chunk, backend=backend)
            real = np.asarray(mask) > 0
            np.testing.assert_allclose(np.asarray(got)[real], want[real],
                                       rtol=2e-5, atol=2e-5, err_msg=backend)
            # a padding node reads no state
            np.testing.assert_allclose(np.asarray(got)[~real], want[~real],
                                       rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("case", list(CASES))
def test_gradients_of_the_two_forms_agree(case):
    lengths, n, chunk = CASES[case]
    a, gid, mask = _batch(lengths, n, seed=1)
    probe = jnp.asarray(np.random.default_rng(2).normal(size=(n, H, P)),
                        jnp.float32) * mask[:, None, None]

    def loss(a, backend):
        y = graph_ssm(a["x"], a["dt"], a["A"], a["B"], a["C"], a["D"], gid,
                      mask, chunk=chunk, backend=backend)
        return jnp.sum(y * probe)

    with jax.default_matmul_precision("highest"):
        seq = jax.grad(loss)(a, "sequential")
        chk = jax.grad(loss)(a, "chunked")
    for k in a:
        scale = float(jnp.max(jnp.abs(seq[k]))) + 1e-6
        np.testing.assert_allclose(np.asarray(chk[k]) / scale,
                                   np.asarray(seq[k]) / scale,
                                   rtol=0, atol=3e-5, err_msg=k)
    # nothing flows into a padding node's inputs
    pad = np.asarray(mask) == 0
    assert not np.any(np.asarray(chk["dt"])[pad])
    assert not np.any(np.asarray(chk["B"])[pad])


def test_a_graph_never_sees_its_neighbour():
    """Changing one graph's inputs moves no other graph's rows."""
    lengths, n, chunk = [5, 1, 9, 14], 37, 16
    a, gid, mask = _batch(lengths, n)
    b = {k: v for k, v in a.items()}
    rows = slice(6, 15)             # the third graph
    for k in ("x", "dt", "B", "C"):
        b[k] = a[k].at[rows].multiply(1.7)
    for backend in ("sequential", "chunked"):
        ya, yb = (graph_ssm(v["x"], v["dt"], v["A"], v["B"], v["C"], v["D"],
                            gid, mask, chunk=chunk, backend=backend)
                  for v in (a, b))
        other = np.ones(n, bool)
        other[rows] = False
        assert np.array_equal(np.asarray(ya)[other], np.asarray(yb)[other])
        assert not np.allclose(np.asarray(ya)[rows], np.asarray(yb)[rows])


def test_conv_reads_no_other_graph():
    lengths, n = [5, 1, 9, 2], 20
    _, gid, mask = _batch(lengths, n)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(n, 6)).astype(np.float32)
    w = rng.normal(size=(4, 6)).astype(np.float32)
    b = rng.normal(size=(6,)).astype(np.float32)
    got = np.asarray(graph_causal_conv(jnp.asarray(x), jnp.asarray(w),
                                       jnp.asarray(b), gid, mask))
    off = 0
    for length in lengths:
        doc = x[off:off + length]
        padded = np.concatenate([np.zeros((3, 6), np.float32), doc])
        want = b + sum(w[k] * padded[k:k + length] for k in range(4))
        np.testing.assert_allclose(got[off:off + length], want, rtol=1e-5,
                                   atol=1e-5)
        off += length
    # a padding node reads nothing: the bias alone
    np.testing.assert_allclose(got[off:], np.broadcast_to(b, (n - off, 6)),
                               rtol=1e-6)


def test_counts_and_starts():
    _, gid, mask = _batch([16, 16, 3], 80)
    assert np.flatnonzero(np.asarray(graph_starts(gid, mask))).tolist() == [
        0, 16, 32]
    chunks, padding, resets = scan_counts(gid, mask, chunk=16)
    assert (float(chunks), float(padding), float(resets)) == (5.0, 2.0, 3.0)


def test_under_jit_and_scan_in_bfloat16():
    """The cell's form: operands in bfloat16, steps scanned; it stays
    within bfloat16 rounding of the float32 recurrence."""
    lengths, n, chunk = [5, 1, 9, 14], 37, 16
    a, gid, mask = _batch(lengths, n)

    @jax.jit
    def steps(a):
        def one(carry, scale):
            y = graph_ssm((a["x"] * scale).astype(jnp.bfloat16), a["dt"],
                          a["A"], a["B"].astype(jnp.bfloat16),
                          a["C"].astype(jnp.bfloat16), a["D"], gid, mask,
                          chunk=chunk, backend="chunked")
            return carry + jnp.sum(y), y
        return jax.lax.scan(one, 0.0, jnp.asarray([1.0, 2.0]))

    _, ys = steps(a)
    want = _by_hand(a, gid, mask)
    assert ys.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(ys[0]), want, rtol=0, atol=0.08)
    np.testing.assert_allclose(np.asarray(ys[1]), 2 * want, rtol=0, atol=0.16)
