"""The router decides once a step (PR 38): every checkpoint that wraps an
expert layer keeps ``route``'s logits and ids (ops/moe.py ``KEEP_ROUTE``),
so the gradient of each language-model stack's train loss holds ONE
``top_k`` and ONE router product forward per expert layer body (a scanned
pair counts once) where the parent's form recomputed them once (a
half-layer's checkpoint) or twice (a checkpointed layer inside a scanned,
checkpointed pair); nothing under ``moe.route`` gathers or scatters; and
the loss and every gradient leaf are the parent form's to the last bit."""

import jax
import numpy as np
import pytest
from jax import lax

import test_glm_moe_lite as glm
import test_laguna as laguna
import test_nemotron_h as nemotron
from test_laguna import _eqns
from test_moe import parent_route

from hydragnn_tpu.graph.batch import HeadSpec, PadSpec, collate
from hydragnn_tpu.models import glm_moe_lite, nemotron_h
from hydragnn_tpu.models import laguna as laguna_model
from hydragnn_tpu.models.base import ModelConfig
from hydragnn_tpu.models.create import create_model
from hydragnn_tpu.ops import attention, moe
from hydragnn_tpu.train.trainer import _loss_and_metrics

WRAPPERS = (laguna_model, glm_moe_lite, nemotron_h)
# stack -> (its tests' module, heads, expert layer BODIES in the program,
# forward runs of a body in the parent's gradient)
STACKS = {
    "laguna": (laguna, [HeadSpec("next", "node", 1)], 2, 2),
    "glm_moe_lite": (glm, glm.HEADS, 3, 2),
    # EMEM*: the two (E, M) pairs are one scanned body
    "nemotron_h": (nemotron, nemotron.HEADS, 1, 3),
}


def _grad_fn(name):
    tests, heads, _, _ = STACKS[name]
    rng = np.random.default_rng(0)
    docs = [rng.integers(0, 64, size=n) for n in tests.DOC_LENGTHS]
    batch = jax.tree.map(
        jax.numpy.asarray,
        collate([tests.sample(d) for d in docs], PadSpec(56, 8, 6), heads))
    cfg = ModelConfig.from_config(tests.nn_section())
    model = create_model(cfg)
    variables = model.init({"params": jax.random.PRNGKey(1)}, batch,
                           train=False)

    def loss(p):
        return _loss_and_metrics(model, cfg, p, variables["batch_stats"],
                                 batch, True)[0]

    return jax.value_and_grad(loss), variables["params"]


def _router_products(jaxpr):
    return sum(1 for e in _eqns(jaxpr)
               if e.primitive.name == "dot_general"
               and e.params["precision"] is not None
               and lax.Precision.HIGHEST in tuple(e.params["precision"]))


def _prims_under_route(jaxpr, inside=False):
    """The primitives whose name stack, their callers' included, holds the
    scope ``moe.route``: what the compiled program's ``op_name``s say."""
    for eqn in jaxpr.eqns:
        here = inside or "moe.route" in str(eqn.source_info.name_stack)
        if here:
            yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _prims_under_route(sub, here)


@pytest.mark.parametrize("name", list(STACKS))
def test_one_top_k_a_layer_no_gather_and_the_parents_bits(name, monkeypatch):
    _, _, bodies, runs = STACKS[name]
    fn, params = _grad_fn(name)
    jaxpr = jax.make_jaxpr(fn)(params).jaxpr
    prims = [e.primitive.name for e in _eqns(jaxpr)]
    assert prims.count("top_k") == bodies
    # forward once, and the two products of its transpose
    assert _router_products(jaxpr) == 3 * bodies
    routed = set(_prims_under_route(jaxpr))
    assert {"top_k", "dot_general", "select_n"} <= routed
    assert not {"gather", "scatter-add", "scatter_add"} & routed, routed
    loss, grads = jax.jit(fn)(params)

    # the parent's form: no policy at any wrap, top_k's values or a gather
    for module in WRAPPERS:
        monkeypatch.setattr(module, "KEEP_ROUTE", None)
    monkeypatch.setattr(nemotron_h, "KEEP", None)
    monkeypatch.setattr(moe, "route", parent_route)
    fn0, params0 = _grad_fn(name)
    jaxpr0 = jax.make_jaxpr(fn0)(params0).jaxpr
    prims0 = [e.primitive.name for e in _eqns(jaxpr0)]
    assert prims0.count("top_k") == runs * bodies
    assert _router_products(jaxpr0) == (runs + 2) * bodies
    assert {"gather", "scatter-add"} & set(_prims_under_route(jaxpr0))
    loss0, grads0 = jax.jit(fn0)(params0)
    assert float(loss) == float(loss0)
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(grads),
                                 jax.tree.leaves(grads0)):
        assert np.array_equal(np.asarray(got), np.asarray(want)), (
            jax.tree_util.keystr(path))
        assert np.any(np.asarray(got)), jax.tree_util.keystr(path)


def test_every_wrap_of_an_expert_layer_has_the_one_policy():
    assert all(module.KEEP_ROUTE is moe.KEEP_ROUTE for module in WRAPPERS)
    # the state-space stack's checkpoints keep an attention layer's names
    # too (tests/test_attention_residuals.py): the router's among them
    name = attention._name_primitive()
    assert all(nemotron_h.KEEP(name, name=n)
               for n in (moe.ROUTE_LOGITS, moe.ROUTE_IDS))
