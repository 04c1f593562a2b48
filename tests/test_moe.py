"""ops/moe.py ``routed_experts``: the rows to dispatch apart from the rows
routed on, and the expert's form as an argument (models/nemotron_h.py's
latent expert space: ungated ``relu^2`` experts over rows that are not what
the router reads), on the grouped path (composed and with the kernels
interpreted), on the dense path and against plain einsums, values and
gradients; 22 slots a node over a wide router; ``route`` reads its selected
scores by mask to the bits the gather it replaced gave (PR 38: the parent's
form is kept here as ``parent_route``); and with that form put back the
defaults trace to the program the two older callers always had."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from hydragnn_tpu.ops import moe
from hydragnn_tpu.parallel.share import LayerShare

N, D, LAT, F, E, HELD, OFF, K = 96, 32, 16, 24, 32, 4, 8, 6
SHARE = LayerShare(E, HELD, OFF, 1, 1, 0, 64, 64, 0)


def _inputs(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    return {
        "u": jax.random.normal(k[0], (N, D)),
        "rows": jax.random.normal(k[1], (N, LAT)),
        "router": jax.random.normal(k[2], (D, E)) * D ** -0.5,
        "w1": jax.random.normal(k[3], (HELD, LAT, F)) * LAT ** -0.5,
        "w2": jax.random.normal(k[4], (HELD, F, LAT)) * F ** -0.5,
        "bias": 0.2 * jax.random.normal(k[5], (E,)),
        "mask": (jnp.arange(N) < N - 7).astype(jnp.float32),
    }


def _plain(a):
    """The same sum from plain einsums: every held expert on every node."""
    ids, weights = moe.route(a["u"], a["router"], K, True, 5.0, "sigmoid",
                             a["bias"])
    held = OFF + jnp.arange(HELD)
    w = jnp.sum(jnp.where(ids[:, :, None] == held, weights[:, :, None], 0.0),
                axis=1) * a["mask"][:, None]
    hidden = jnp.square(jax.nn.relu(
        jnp.einsum("nl,elf->enf", a["rows"], a["w1"])))
    return jnp.einsum("ne,enl->nl", w,
                      jnp.einsum("enf,efl->enl", hidden, a["w2"]))


def _run(a, **kw):
    y, stats = moe.routed_experts(
        a["u"], a["router"], a["w1"], None, a["w2"], SHARE, top_k=K,
        node_mask=a["mask"], scale=5.0, scoring="sigmoid", bias=a["bias"],
        rows=a["rows"], expert="relu2", **kw)
    return y, stats


PATHS = {
    "grouped_composed": dict(backend="ragged_dot"),
    "grouped_kernels_interpreted": dict(backend="gmm", interpret=True),
    # a capacity no step fits: every step takes the dense path
    "dense": dict(backend="ragged_dot", capacity=0),
}


@pytest.mark.parametrize("path", list(PATHS))
def test_latent_rows_and_relu2_experts_match_plain_einsums(path):
    a = _inputs()
    with jax.default_matmul_precision("highest"):
        want = _plain(a)
        y, stats = _run(a, **PATHS[path])
    assert y.shape == (N, LAT) and y.dtype == jnp.float32
    np.testing.assert_allclose(y, want, rtol=0, atol=2e-5)
    assert float(stats["dense_steps"]) == float(path == "dense")
    assert float(stats["slots_all"]) == (N - 7) * K
    assert stats["counts_all"].shape == (E,)
    assert float(jnp.sum(stats["counts_all"])) == (N - 7) * K
    # padding nodes are routed nowhere
    assert not np.any(np.asarray(y)[N - 7:])


@pytest.mark.parametrize("path", list(PATHS))
def test_gradients_match_plain_einsums(path):
    a = _inputs(1)
    probe = jax.random.normal(jax.random.PRNGKey(5), (N, LAT))
    keys = ("u", "rows", "router", "w1", "w2")

    def loss(fn, *vals):
        return jnp.sum(fn(dict(a, **dict(zip(keys, vals)))) * probe)

    vals = [a[k] for k in keys]
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda *v: loss(_plain, *v),
                        argnums=range(5))(*vals)
        got = jax.grad(lambda *v: loss(
            lambda b: _run(b, **PATHS[path])[0], *v),
            argnums=range(5))(*vals)
    for k, g, w in zip(keys, got, want):
        np.testing.assert_allclose(g, w, rtol=0, atol=5e-5, err_msg=k)
    # the router reads ``u``, the experts read ``rows``: each gets its own
    assert np.any(np.asarray(got[0])) and np.any(np.asarray(got[1]))


def test_twenty_two_slots_a_node_over_a_wide_router():
    """The benchmark cell's routing shape at a small node count: 22 of 512
    experts a node, 8 held; the grouped path holds every held slot."""
    n, e, k = 200, 512, 22
    share = LayerShare(e, 8, 0, 1, 1, 0, 64, 64, 0)
    key = jax.random.split(jax.random.PRNGKey(2), 5)
    u = jax.random.normal(key[0], (n, D))
    rows = jax.random.normal(key[1], (n, LAT))
    router = jax.random.normal(key[2], (D, e)) * D ** -0.5
    w1 = jax.random.normal(key[3], (8, LAT, F)) * LAT ** -0.5
    w2 = jax.random.normal(key[4], (8, F, LAT)) * F ** -0.5
    args = (u, router, w1, None, w2, share)
    kw = dict(top_k=k, scale=5.0, scoring="sigmoid", bias=jnp.zeros((e,)),
              rows=rows, expert="relu2")
    with jax.default_matmul_precision("highest"):
        y, stats = moe.routed_experts(*args, **kw)
        dense, _ = moe.routed_experts(*args, capacity=0, **kw)
    np.testing.assert_allclose(y, dense, rtol=0, atol=2e-5)
    assert float(stats["dense_steps"]) == 0.0
    assert float(stats["slots_all"]) == n * k
    c = np.asarray(stats["counts_all"])
    assert c.shape == (e,) and c.sum() == n * k
    assert float(stats["slots_held"]) == c[:8].sum()
    # a node sends one expert at most one slot: no more than n a expert
    assert c.max() <= n
    assert moe.default_capacity(n, k, 8, e) >= float(stats["slots_held"])


def test_a_wide_router_is_counted_slot_by_slot_to_the_same_counts(
        monkeypatch):
    ids = jax.random.randint(jax.random.PRNGKey(3), (300, 22), 0, 512)
    real = jnp.arange(300) < 280
    at_once = moe._counts_all(ids, real, 512)
    monkeypatch.setattr(moe, "COUNT_AT_ONCE", 0)
    by_slot = jax.jit(moe._counts_all, static_argnums=2)(ids, real, 512)
    assert np.array_equal(np.asarray(at_once), np.asarray(by_slot))
    assert float(jnp.sum(by_slot)) == 280 * 22
    # the benchmark cell's shape takes the loop, GLM's the one compare
    assert 12496 * 22 * 512 > 1 << 24 >= 17512 * 4 * 64


def test_the_expert_form_must_fit_the_matrices_given():
    a = _inputs()
    w3 = jnp.zeros_like(a["w1"])
    for w3_given, form in ((w3, "relu2"), (None, "gated_silu"),
                           (None, "swish")):
        with pytest.raises(ValueError, match="expert form"):
            moe.routed_experts(a["rows"], a["router"][:LAT], a["w1"],
                               w3_given, a["w2"], SHARE, top_k=K,
                               expert=form)


def parent_route(u, router_w, top_k, norm_topk=True, scale=1.0,
                 scoring="softmax", bias=None, norm_eps=None):
    """``route`` as 4cb4377 (PR 37) had it: the selected scores are
    ``top_k``'s values, or XLA's gather where a bias selects
    (``norm_eps``: what ``routed_experts`` passes on since PR 40; its
    callers of that time give none)."""
    assert norm_eps is None
    logits = jnp.dot(u.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores, eps = ((jax.nn.softmax(logits, axis=-1), None)
                   if scoring == "softmax" else
                   (jax.nn.sigmoid(logits), 1e-20))
    if bias is None:
        top, ids = lax.top_k(scores, top_k)
    else:
        _, ids = lax.top_k(scores + lax.stop_gradient(bias), top_k)
        top = jnp.take_along_axis(scores, ids, axis=-1)
    if norm_topk:
        total = jnp.sum(top, axis=-1, keepdims=True)
        top = top / (total if eps is None else total + eps)
    return ids, top * scale


@pytest.mark.parametrize("k,e", [(10, 256), (4, 64), (22, 512)])
@pytest.mark.parametrize("norm_topk", [True, False])
@pytest.mark.parametrize("biased", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_route_reads_by_mask_what_the_gather_read(scoring, biased,
                                                  norm_topk, k, e):
    """Ids equal; weights and the gradients with respect to the nodes and
    the router equal to the last bit, eagerly and jitted; the last rows are
    padding nodes, which all carry one input."""
    n, d = 37, 24
    key = jax.random.split(jax.random.PRNGKey(k + e), 4)
    u = jax.random.normal(key[0], (n, d)).at[-6:].set(0.25)
    router = jax.random.normal(key[1], (d, e)) * d ** -0.5
    bias = 0.3 * jax.random.normal(key[2], (e,)) if biased else None
    mix = jax.random.normal(key[3], (n, k))

    def run(fn):
        def loss(u, router):
            ids, weights = fn(u, router, k, norm_topk, 2.5, scoring, bias)
            return jnp.sum(weights * mix), (ids, weights)
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)

    for wrap in (lambda f: f, jax.jit):
        (_, (ids, weights)), (du, dw) = wrap(run(moe.route))(u, router)
        (_, (ids0, weights0)), (du0, dw0) = wrap(run(parent_route))(
            u, router)
        assert np.array_equal(ids, ids0)
        for got, want in ((weights, weights0), (du, du0), (dw, dw0)):
            assert np.array_equal(np.asarray(got), np.asarray(want))
        assert np.any(np.asarray(du)) and np.any(np.asarray(dw))
    assert len(set(np.asarray(ids[-1]).tolist())) == k


# sha256[:16] of the gradient jaxpr below as the commit BEFORE the keyword
# arguments (7bd1849, PR 36) printed it, on this container's jax 0.9.0: the
# defaults must leave the two older callers' traced program as it was, once
# ``route`` (the one function PR 38 rewrote) is the parent's again.  A jax
# upgrade changes how jaxprs print: regenerate from that commit then.
OLD_PROGRAM = {"ragged_dot": "40d9d2c9e908e55e", "gmm": "f7222e22480f0372"}


def _old_callers_digest(backend):
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    u = jax.random.normal(k[0], (N, D))
    router = jax.random.normal(k[1], (D, E)) * D ** -0.5
    w1 = jax.random.normal(k[2], (HELD, D, F))
    w3 = jax.random.normal(k[3], (HELD, D, F))
    w2 = jax.random.normal(k[4], (HELD, F, D))
    mask = (jnp.arange(N) < N - 7).astype(jnp.float32)

    def loss(u, router, w1, w3, w2, bias):
        y, stats = moe.routed_experts(
            u, router, w1, w3, w2, SHARE, top_k=K, node_mask=mask,
            scale=1.8, scoring="sigmoid", bias=bias,
            compute_dtype=jnp.bfloat16, backend=backend,
            interpret=backend == "gmm")
        y2, _ = moe.routed_experts(
            u, router, w1, w3, w2, SHARE, top_k=K, node_mask=mask,
            scale=2.5, backend=backend, interpret=backend == "gmm")
        return jnp.sum(y) + jnp.sum(y2) + jnp.sum(stats["counts_all"])

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
        u, router, w1, w3, w2, jnp.zeros((E,))))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("backend", list(OLD_PROGRAM))
def test_the_defaults_trace_to_the_old_program(backend, monkeypatch):
    monkeypatch.setattr(moe, "route", parent_route)
    assert _old_callers_digest(backend) == OLD_PROGRAM[backend]


# the same jaxpr with ``route`` as it is, as 12b8c6c (PR 38) printed it:
# ``norm_eps`` (PR 40, models/lfm2_moe.py's 1e-6) left at its default
# changes nothing in the three older callers' traced program
PR38_PROGRAM = {"ragged_dot": "54e032fbef03a757", "gmm": "dcbc4c9afd9c4586"}


@pytest.mark.parametrize("backend", list(PR38_PROGRAM))
def test_the_default_epsilon_traces_to_the_program_before_it(backend):
    assert _old_callers_digest(backend) == PR38_PROGRAM[backend]


def test_the_epsilon_of_the_renormalisation_is_an_argument():
    """None is 1e-20, to the jaxpr; 1e-6 selects the same experts and moves
    a weight by 1e-6 over the sum of the selected scores and no more;
    softmax scores take no epsilon whatever is given."""
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    u = jax.random.normal(k[0], (37, D))
    router = jax.random.normal(k[1], (D, E)) * D ** -0.5
    bias = 0.3 * jax.random.normal(k[2], (E,))

    def text(**kw):
        return str(jax.make_jaxpr(lambda u, r, b: moe.route(
            u, r, K, True, 1.0, "sigmoid", b, **kw))(u, router, bias))

    assert text() == text(norm_eps=1e-20) != text(norm_eps=1e-6)
    ids0, w0 = moe.route(u, router, K, True, 1.0, "sigmoid", bias)
    ids1, w1 = moe.route(u, router, K, True, 1.0, "sigmoid", bias, 1e-6)
    assert np.array_equal(ids0, ids1)
    total = jnp.sum(jnp.take_along_axis(
        jax.nn.sigmoid(jnp.dot(u, router, precision=lax.Precision.HIGHEST)),
        ids0, axis=-1), axis=-1, keepdims=True)
    # 1e-6 over a sum of K scores in (0, 1), and float32's own rounding
    rel = np.asarray((w0 - w1) / w0)
    assert np.all(np.abs(rel - 1e-6 / np.asarray(total)) <= 2.5e-7)
    assert 1e-7 < float(rel.mean()) < 1e-6
    soft = [moe.route(u, router, K, True, 1.0, "softmax", None, eps)[1]
            for eps in (None, 1e-6)]
    assert np.array_equal(*soft)
