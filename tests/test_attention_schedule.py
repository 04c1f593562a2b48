"""The splash backend's block schedule (ops/attention.py): which blocks of
the static band run follows the batch's ``node_gid``, and padding nodes
are graphs of their own.  CPU, the kernels interpreted at a block of 128."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.ops import attention


@pytest.fixture
def block(monkeypatch, request):
    """The module's tile edge for this test (the kernels are cached by
    shape, not by tile: start and end with an empty cache)."""
    attention._splash_kernel.cache_clear()
    monkeypatch.setattr(attention, "_BLOCK", request.param)
    yield request.param
    attention._splash_kernel.cache_clear()


def packing(seed, block, graphs=None, tail=None):
    """A batch as graph/batch.py collate lays it out: 1-24 contiguous
    graphs of 1 to 3 blocks' length, then ONE padding graph of 0 to
    several blocks; the node axis itself is not a whole number of blocks."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(1, 3 * block + 1, graphs or rng.integers(1, 25))
    tail = (rng.integers(0, 5 * block) if tail is None else tail)
    gid = np.repeat(np.arange(len(lengths) + 1), [*lengths, tail])
    mask = (gid < len(lengths)).astype(np.float32)
    return jnp.asarray(gid, jnp.int32), jnp.asarray(mask), int(max(lengths))


def by_block(seen, block):
    nb = seen.shape[0] // block
    return seen.reshape(nb, block, nb, block).any(axis=(1, 3))


@pytest.mark.parametrize("block", [16], indirect=True)
@pytest.mark.parametrize("seed", range(10))
@pytest.mark.parametrize("band", ["full", "window", "ragged_window"])
def test_the_rule_schedules_exactly_the_blocks_with_a_visible_pair(
        block, seed, band):
    gid, mask, longest = packing(seed, block)
    window = {"full": None, "window": 2 * block,
              "ragged_window": block + 5}[band]
    n_pad, width = attention._padded(gid.shape[0], window, longest)
    own = attention._own_ids(gid, mask, n_pad)
    needed, in_band = attention._needed(own, width)
    needed = np.asarray(needed)
    seen = by_block(np.asarray(attention.visible(own, window)), block)
    diagonal = np.eye(len(seen), dtype=bool)
    # every block visible() marks anywhere is scheduled, every scheduled
    # off-diagonal block has a visible pair, the diagonal always runs
    assert (needed | diagonal == seen | diagonal).all()
    assert needed[diagonal].all() and seen[diagonal].all()
    assert not (needed & ~in_band).any()
    run, all_ = attention.scheduled_blocks(
        gid, mask, window=window, max_span=longest)
    assert int(run) == needed.sum() <= all_ == in_band.sum()


@pytest.mark.parametrize("block", [16], indirect=True)
def test_padding_nodes_see_themselves_and_nothing_else(block):
    gid, mask, _ = packing(3, block, graphs=4, tail=3 * block)
    n = gid.shape[0]
    own = np.asarray(attention._own_ids(gid, mask, n + 7))
    seen = np.asarray(attention.visible(jnp.asarray(own), None))
    pad = np.r_[np.asarray(mask) == 0, np.ones(7, bool)]
    assert (seen[pad] == np.eye(n + 7, dtype=bool)[pad]).all()
    assert not seen[:, pad][~pad].any()
    # real nodes keep their ids, and no padding id collides with one
    assert (own[~pad] == np.asarray(gid)[~pad[:n]]).all()
    assert len(set(own[pad])) == pad.sum() and own[pad].min() > own[~pad].max()


@pytest.mark.parametrize("block", [16], indirect=True)
def test_one_graph_that_fills_the_axis_runs_the_whole_band(block):
    gid = jnp.zeros((8 * block,), jnp.int32)
    for window in (None, 3 * block):
        run, band = attention.scheduled_blocks(gid, window=window)
        assert int(run) == band == (36 if window is None else 26)
    # ... and the same axis as eight graphs runs the diagonal alone
    run, band = attention.scheduled_blocks(
        jnp.repeat(jnp.arange(8), block), max_span=block)
    assert (int(run), band) == (8, 15)
    run, band = attention.scheduled_blocks(
        jnp.repeat(jnp.arange(8), block), max_span=4 * block)
    assert (int(run), band) == (8, 30)


def tables(gid, mask, window, longest, heads, multi_head):
    n_pad, band = attention._padded(gid.shape[0], window, longest)
    static = attention._splash_kernel(n_pad, heads, band, True, multi_head)
    needed, in_band = attention._needed(
        attention._own_ids(gid, mask, n_pad), band)
    return static, np.asarray(needed), in_band


@pytest.mark.parametrize("block", [128], indirect=True)
@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("window", [None, 256])
def test_the_tables_switch_blocks_off_and_move_no_data_for_them(
        block, seed, window):
    gid, mask, longest = packing(seed, block, graphs=6)
    static, needed, in_band = tables(gid, mask, window, longest, 2, False)
    for name, dkv in (("fwd_mask_info", False), ("dq_mask_info", False),
                      ("dkv_mask_info", True)):
        info = getattr(static, name)
        new = attention._follow(info, jnp.asarray(needed), dkv)
        assert new.data_next.dtype == info.data_next.dtype
        assert new.block_mask.dtype == info.block_mask.dtype
        assert new.data_next.shape == info.data_next.shape
        old_mask, old_data = np.asarray(info.block_mask[0]), np.asarray(
            info.data_next[0])
        new_mask, new_data = np.asarray(new.block_mask[0]), np.asarray(
            new.data_next[0])
        if dkv:     # positions run down the columns: walk them as rows
            old_mask, old_data, new_mask, new_data = (
                a.T for a in (old_mask, old_data, new_mask, new_data))
        ran = np.zeros_like(needed)
        for row in range(len(new_mask)):
            on = np.flatnonzero(new_mask[row])
            assert len(on)                       # the diagonal, at least
            for p in range(new_mask.shape[1]):
                if new_mask[row, p]:
                    assert new_mask[row, p] == old_mask[row, p]
                    assert new_data[row, p] == old_data[row, p]
                    at = (new_data[row, p], row) if dkv else (
                        row, new_data[row, p])
                    ran[at] = True
                else:   # the next running position's block, else the last
                    later = on[on > p]
                    q = later[0] if len(later) else on[-1]
                    assert new_data[row, p] == old_data[row, q]
        assert (ran == needed).all()
        assert (old_mask != 0).sum() == in_band.sum()
    assert needed.sum() < in_band.sum()


@pytest.mark.parametrize("block", [128], indirect=True)
@pytest.mark.parametrize("heads,kv", [(4, 1), (2, 2), (4, 2)],
                         ids=["multi_query", "multi_head", "grouped"])
@pytest.mark.parametrize("window", [None, 256], ids=["full", "window"])
def test_scheduled_kernels_match_the_dense_twin_on_all_rows(
        block, heads, kv, window):
    """Outputs and the gradients of q, k and v, padding rows included; the
    padding tail (520 nodes) is longer than the band (384 / 256)."""
    lengths = [100, 384, 30, 260, 1, 129]
    gid = jnp.asarray(np.repeat(np.arange(7), [*lengths, 520]), jnp.int32)
    mask = (gid < 6).astype(jnp.float32)
    n = gid.shape[0]
    key = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(key[0], (n, heads, 16))
    k = jax.random.normal(key[1], (n, kv, 16))
    v = jax.random.normal(key[2], (n, kv, 16))
    weigh = jax.random.normal(key[3], (n, heads, 16))

    def run(backend):
        def f(q, k, v):
            o = attention.graph_attention(
                q, k, v, gid, mask, window=window, max_span=384,
                backend=backend, interpret=True)
            return jnp.sum(o * weigh), o
        return jax.jit(jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True))(q, k, v)

    (_, dense), dense_grads = run("dense")
    (_, splash), splash_grads = run("splash")
    assert np.isfinite(np.asarray(dense)).all()
    assert np.isfinite(np.asarray(splash)).all()
    np.testing.assert_allclose(dense, splash, atol=2e-5)
    for a, b in zip(dense_grads, splash_grads):
        assert np.isfinite(np.asarray(b)).all()
        np.testing.assert_allclose(a, b, atol=5e-5)
    # a padding row is its own value row
    pad = np.asarray(mask) == 0
    np.testing.assert_allclose(
        np.asarray(splash)[pad],
        np.repeat(np.asarray(v), heads // kv, axis=1)[pad], atol=1e-6)
    run_, band = attention.scheduled_blocks(
        gid, mask, window=window, max_span=384)
    assert int(run_) < band


@pytest.mark.parametrize("block", [128], indirect=True)
def test_real_rows_are_what_the_static_band_gave(block):
    """Against the kernel as it was (its static tables, the padding nodes
    one graph): every real row's output and the gradients it feeds are
    the same sums with exact zeros left out."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    gid = jnp.asarray(np.repeat(np.arange(4), [200, 300, 150, 374]),
                      jnp.int32)
    mask = (gid < 3).astype(jnp.float32)
    n = gid.shape[0]
    key = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v, weigh = (jax.random.normal(key[i], (n, 2, 16))
                      for i in range(4))
    weigh = weigh * mask[:, None, None]      # nothing reads a padding row

    def static(q, k, v):
        kernel = attention._splash_kernel(n, 2, 384, True, True)
        o = kernel((q / 4.0).swapaxes(0, 1), k.swapaxes(0, 1),
                   v.swapaxes(0, 1), sk.SegmentIds(q=gid, kv=gid))
        return jnp.sum(o.swapaxes(0, 1) * weigh), o.swapaxes(0, 1)

    def scheduled(q, k, v):
        o = attention.graph_attention(q, k, v, gid, mask, max_span=384,
                                      backend="splash", interpret=True)
        return jnp.sum(o * weigh), o

    (_, a), ga = jax.value_and_grad(static, (0, 1, 2), has_aux=True)(q, k, v)
    (_, b), gb = jax.value_and_grad(scheduled, (0, 1, 2), has_aux=True)(
        q, k, v)
    real = np.asarray(mask) > 0
    np.testing.assert_array_equal(np.asarray(a)[real], np.asarray(b)[real])
    for x, y in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(x)[real], np.asarray(y)[real],
                                   rtol=0, atol=1e-6)
