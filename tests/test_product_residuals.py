"""Wide products run once a step (PR 42): the checkpoint round a short
convolution keeps its input product (models/lfm2_moe.py ``KEEP_SCONV``) and
the checkpoint of each slice of a dense feed-forward its two up-products
(models/sequence.py ``KEEP_FFN``, where the layer hands it over: Laguna's and
LFM2's do, latent attention's stack does not), so the half's gradient holds
ONE such product where the bare checkpoint held two, the residuals grow by
exactly the named arrays, the loss and every gradient are the bare
checkpoint's to the last bit, and the step records' ``sconv.kept_mb`` /
``ffn.kept_mb`` are the bytes of exactly those arrays.  All of it where
the products leave the MXU in 2 bytes a value: in float32 the layers keep
nothing of them (models/sequence.py ``where_narrow``).  CPU, float32 and
bfloat16: 48 node slots (four slices of 12), three graphs of 20, 17 and 5
nodes and 6 padding nodes."""

import collections
import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

import test_glm_moe_lite as glm
import test_laguna as laguna
import test_lfm2_moe as lfm2
from test_laguna import _eqns

from hydragnn_tpu.graph.batch import HeadSpec, PadSpec, collate
from hydragnn_tpu.models import glm_moe_lite, lfm2_moe, sequence
from hydragnn_tpu.models import laguna as laguna_model
from hydragnn_tpu.models.base import ModelConfig
from hydragnn_tpu.models.create import create_model
from hydragnn_tpu.ops import attention
from hydragnn_tpu.ops.moe import KEEP_ROUTE, ROUTE_IDS, ROUTE_LOGITS
from hydragnn_tpu.telemetry import MetricsLogger, TelemetryConfig
from hydragnn_tpu.train.trainer import (
    _loss_and_metrics,
    merge_scanned_metrics,
    model_counters,
)

GRAPHS, SLOTS, HIDDEN = (20, 17, 5), 48, 32
CHUNKS = sequence.DENSE_CHUNKS
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _config(tests, **changes):
    section = tests.nn_section(lm={**copy.deepcopy(tests.LM), **changes})
    return ModelConfig.from_config(section)


def _lfm2_conv(dtype):
    """A conv layer with experts behind it: the short convolution alone."""
    cfg = _config(lfm2, layer_types=["conv"], num_hidden_layers=1,
                  num_dense_layers=0)
    return lfm2_moe.Lfm2Layer(cfg.lm, cfg.share, 0, dtype, "dense", None,
                              True), "pb", cfg.lm


def _lfm2_layer0(dtype):
    cfg = _config(lfm2)             # layer 0: conv, dense
    return lfm2_moe.Lfm2Layer(cfg.lm, cfg.share, 0, dtype, "dense", None,
                              True), "pb", cfg.lm


def _laguna_layer0(dtype):
    cfg = _config(laguna)           # layer 0: full attention, dense
    return laguna_model.LagunaLayer(cfg.lm, cfg.share, 0, dtype, "dense",
                                    None, True), "p", cfg.lm


def _glm_layer0(dtype):
    cfg = _config(glm)
    return glm_moe_lite.GlmLayer(cfg.lm, cfg.share, True, dtype, "dense",
                                 None, True), "pb", cfg.lm


# what names a product: (the shapes of its operands and of its result), the
# arrays a checkpoint keeps of it (shape: how many), the module that draws
# the checkpoint and its policy's name there
def _proj(lm):
    return (((SLOTS, HIDDEN), (HIDDEN, 3 * HIDDEN), (SLOTS, 3 * HIDDEN)),
            {(SLOTS, 3 * HIDDEN): 1}, lfm2_moe, "KEEP_SCONV", "sconv")


def _up(module):
    def site(lm):
        rows, f = SLOTS // CHUNKS, lm.intermediate_size
        # h1 and h3: two products of one shape a pass; kept stacked
        return (((rows, HIDDEN), (HIDDEN, f), (rows, f)),
                {(CHUNKS, rows, f): 2}, module, "KEEP_FFN", "ffn")
    return site


# layer -> (its builder, the sites whose products its checkpoints keep)
LAYERS = {
    "lfm2_conv": (_lfm2_conv, (_proj,)),
    "lfm2_layer0": (_lfm2_layer0, (_proj, _up(lfm2_moe))),
    "laguna_layer0": (_laguna_layer0, (_up(laguna_model),)),
}
SITES = [(layer, i) for layer, (_, sites) in LAYERS.items()
         for i in range(len(sites))]


def _half(build, dtype):
    """(a loss of the layer by its parameters and input, with the layer's
    other outputs beside it; the arguments; the language-model block)."""
    layer, takes, lm = build(DTYPES[dtype])
    gid = jnp.asarray(np.repeat(np.arange(4), [*GRAPHS, SLOTS - sum(GRAPHS)]),
                      jnp.int32)
    mask = (gid < 3).astype(jnp.float32)
    first = np.concatenate([[0], np.cumsum(GRAPHS)])
    positions = jnp.arange(SLOTS, dtype=jnp.int32) - jnp.asarray(
        first, jnp.int32)[gid]
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(keys[0], (SLOTS, HIDDEN))
    weigh = jax.random.normal(keys[1], (SLOTS, HIDDEN)) * mask[:, None]
    args = (gid, mask, *((positions,) if "p" in takes else ()),
            *((None,) if "b" in takes else ()))
    params = layer.init({"params": keys[2]}, x, *args)["params"]

    def loss(params, x):
        out, *more = layer.apply({"params": params}, x, *args)
        return jnp.sum(out * weigh), more[-1]

    return loss, (params, x), lm


def _grad(loss):
    return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)


def _run(fn, args):
    """Every rounding the program states is made: the CPU's compiler runs a
    bfloat16 product in float32 and may then skip the rounding between it
    and a fused reader (``xla_allow_excess_precision``), and which readers
    are fused to a product is what keeping its result changes.  (The TPU
    takes the product's result from the MXU in bfloat16 either way.)"""
    return jax.jit(_grad(fn)).lower(*args).compile(
        compiler_options={"xla_allow_excess_precision": False})(*args)


def _products(fn, args):
    """The matrix products of ``fn``'s gradient, forward, recomputed forward
    and backward, by the shapes of their operands and result."""
    return collections.Counter(
        (*(tuple(v.aval.shape) for v in e.invars),
         tuple(e.outvars[0].aval.shape))
        for e in _eqns(jax.make_jaxpr(_grad(fn))(*args).jaxpr)
        if e.primitive.name == "dot_general")


def _residuals(fn, args):
    """The float arrays kept from forward to backward, by shape and dtype."""
    return collections.Counter(
        (tuple(aval.shape), aval.dtype.name) for aval, _ in saved_residuals(
            lambda *a: fn(*a)[0], *args)
        if jnp.issubdtype(aval.dtype, jnp.floating))


def _same_bits(got, want):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            jax.tree_util.keystr(path))
        assert np.any(np.asarray(a)), jax.tree_util.keystr(path)


def _mb(arrays, dtype):
    return sum(int(np.prod(s)) * c for s, c in arrays.items()) * (
        jnp.dtype(dtype).itemsize) / 1e6


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("layer,site", SITES)
def test_the_half_keeps_its_product_and_runs_it_once(layer, site, dtype,
                                                     monkeypatch):
    """One site at a time: its policy taken away, the layer's other
    checkpoints as they are.  In float32 the layer hands the policy to no
    checkpoint (models/sequence.py where_narrow: 4 bytes a value), and the
    half is the bare checkpoint's."""
    build, sites = LAYERS[layer]
    fn, args, lm = _half(build, dtype)
    product, arrays, module, policy, block = sites[site](lm)
    named = collections.Counter(
        {(shape, dtype): count for shape, count in arrays.items()})
    per_pass = sum(arrays.values())     # products of this shape a pass
    products = _products(fn, args)
    kept = _residuals(fn, args)
    (loss, mb), grads = _run(fn, args)

    # the bare checkpoint: the half recomputed from its input alone
    monkeypatch.setattr(module, policy, None)
    fn0, args0, _ = _half(build, dtype)
    products0 = _products(fn0, args0)
    assert products0[product] == 2 * per_pass
    kept0 = _residuals(fn0, args0)
    (loss0, mb0), grads0 = _run(fn0, args0)
    assert float(mb0[block]) == 0.0
    assert all(float(v) == float(mb[k]) for k, v in mb0.items() if k != block)
    assert float(loss) == float(loss0)
    _same_bits(grads, grads0)
    if dtype == "float32":
        assert products == products0 and kept == kept0
        assert float(mb[block]) == 0.0
        return
    assert products[product] == per_pass        # the forward's, and no other
    # no other product of the layer comes or goes
    assert products0 - products == {product: per_pass}
    assert not products - products0
    # what the policy adds to the residuals is the named arrays, in the
    # compute dtype, and nothing else, and it takes nothing away
    assert kept - kept0 == named and not kept0 - kept
    assert float(mb[block]) == pytest.approx(_mb(arrays, dtype), rel=1e-6)


def test_where_narrow_hands_the_policy_on_at_two_bytes_a_value():
    keep = sequence.KEEP_FFN
    assert sequence.where_narrow(keep, jnp.bfloat16) is keep
    assert sequence.where_narrow(keep, jnp.float16) is keep
    assert sequence.where_narrow(keep, jnp.float32) is None
    assert sequence.where_narrow(None, jnp.bfloat16) is None


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_latent_attentions_dense_layer_keeps_nothing(dtype):
    """models/glm_moe_lite.py hands ``DenseFFN`` no policy: its slices are
    recomputed from their input alone, both up-products twice a step, and
    its layer counts nothing."""
    fn, args, lm = _half(_glm_layer0, dtype)
    product, arrays, *_ = _up(None)(lm)
    assert _products(fn, args)[product] == 4
    kept = _residuals(fn, args)
    assert not any(shape in arrays for shape, _ in kept), kept
    out = jax.eval_shape(fn, *args)
    assert len(out[1]) == 3         # attention's blocks: no dict of MB
    assert sequence.DenseFFN(lm, DTYPES[dtype]).policy is None


def test_the_policies_keep_the_names_they_say():
    ask = attention._name_primitive()
    names = (lfm2_moe.SCONV_PROJ, sequence.FFN_H1, sequence.FFN_H3,
             ROUTE_LOGITS, ROUTE_IDS, attention.ATTN_Q, attention.ATTN_K,
             attention.ATTN_V, attention.ATTN_OUT)
    kept = {key: {n for n in names if p(ask, name=n)}
            for key, p in (("sconv", lfm2_moe.KEEP_SCONV),
                           ("ffn", sequence.KEEP_FFN),
                           ("route", KEEP_ROUTE),
                           ("attn", attention.KEEP_ATTN))}
    assert kept["sconv"] == {"sconv.in.proj"}
    assert kept["ffn"] == {"ffn.dense.h1", "ffn.dense.h3"}
    assert kept["route"] == {ROUTE_LOGITS, ROUTE_IDS}
    assert kept["attn"] == set(names[5:])
    assert lfm2_moe.KEEP_FFN is laguna_model.KEEP_FFN is sequence.KEEP_FFN
    assert not hasattr(glm_moe_lite, "KEEP_FFN")


def test_named_mb_asks_the_policy_for_each_name():
    a = jax.ShapeDtypeStruct((100, 30), jnp.bfloat16)
    named = {lfm2_moe.SCONV_PROJ: a, sequence.FFN_H1: a,
             attention.ATTN_OUT: 1000}
    assert attention.named_mb(None, named) == 0.0
    assert attention.named_mb(lfm2_moe.KEEP_SCONV, named) == 6000 / 1e6
    assert attention.named_mb(sequence.KEEP_FFN, named) == 6000 / 1e6
    assert attention.named_mb(attention.KEEP_ATTN_OUT, named) == 1000 / 1e6
    assert attention.named_mb(KEEP_ROUTE, named) == 0.0


# stack -> (its tests, conv layers, the dense layers' width where their
# checkpoints keep the up-products)
STACKS = {"laguna": (laguna, 0, 64), "lfm2_moe": (lfm2, 2, 48),
          "glm_moe_lite": (glm, 0, None)}


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("name", list(STACKS))
def test_step_records_carry_the_kept_mb_of_the_shapes(name, dtype, tmp_path):
    """Through the train step's counters and the logger, summed over the
    layers: 48 node slots, one dense layer a stack."""
    tests, convs, width = STACKS[name]
    cfg = ModelConfig.from_config(tests.nn_section(dtype))
    rng = np.random.default_rng(0)
    docs = [tests.sample(rng.integers(0, 64, size=n)) for n in (5, 20, 3, 12)]
    heads = [HeadSpec(f"next{i}", "node", 1)
             for i in range(len(cfg.output_dim))]
    batch = jax.tree.map(jnp.asarray, collate(docs, PadSpec(48, 8, 5), heads))
    model = create_model(cfg)
    variables = jax.jit(lambda k: model.init({"params": k}, batch,
                                             train=False))(
        jax.random.PRNGKey(1))
    loss, (per_head, stats, _) = jax.jit(lambda p: _loss_and_metrics(
        model, cfg, p, variables["batch_stats"], batch, True))(
            variables["params"])
    # as train/trainer.py make_train_step fills its metrics
    metrics = {"loss": loss, "num_graphs": batch.n_real_graphs,
               **{f"task_{i}": t for i, t in enumerate(per_head)},
               **model_counters(stats)}
    out_dir = str(tmp_path / "telemetry")
    tele = MetricsLogger(TelemetryConfig(enable=True, sinks=("jsonl",)),
                         run_name=f"kept_{name}", out_dir=out_dir)
    tele.begin_epoch(0)
    tele.on_step(metrics, batch)
    tele.flush_steps()
    tele.finalize()
    (record,) = [r for r in map(json.loads, open(
        os.path.join(out_dir, "events.jsonl"))) if r["event"] == "step"]
    # in float32 the layers hand their policies to no checkpoint
    narrow = dtype == "bfloat16"
    if width is None:
        assert "ffn" not in record and "ffn_kept_mb" not in stats
    else:
        assert record["ffn"] == {"kept_mb": pytest.approx(
            narrow * _mb({(48, width): 2}, dtype), rel=1e-6)}
    if convs:
        assert record["sconv"]["kept_mb"] == pytest.approx(
            narrow * _mb({(48, 3 * HIDDEN): convs}, dtype), rel=1e-6)
        assert narrow == (record["sconv"]["kept_mb"] > 0)
    else:
        assert "sconv" not in record


def test_the_scan_merge_leaves_kept_mb_as_it_is():
    """Numbers of the dispatch's shape: over K scanned steps neither summed
    nor averaged (an average over graphs would be 0 for a dispatch of empty
    steps)."""
    ms = {"num_graphs": jnp.asarray([0.0, 0.0, 0.0]),
          "loss": jnp.asarray([1.0, 2.0, 3.0]),
          "sconv_rows": jnp.asarray([5.0, 6.0, 7.0]),
          "sconv_kept_mb": jnp.asarray([1155.7] * 3),
          "ffn_kept_mb": jnp.asarray([1107.5] * 3)}
    for graphs in ([0.0, 0.0, 0.0], [12.0, 7.0, 12.0]):
        ms["num_graphs"] = jnp.asarray(graphs)
        merged = merge_scanned_metrics(ms)
        assert float(merged["sconv_kept_mb"]) == float(np.float32(1155.7))
        assert float(merged["ffn_kept_mb"]) == float(np.float32(1107.5))
        assert float(merged["sconv_rows"]) == 18.0
