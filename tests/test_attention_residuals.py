"""Attention runs once a step (PR 41): the checkpoint round each stack's
attention half keeps what ops/attention.py names of the splash backend (the
forward kernel's result and log-sum-exp; q, k, v too where the half is
grouped-query attention: ``KEEP_ATTN``; latent attention rebuilds them:
``KEEP_ATTN_OUT``), so the half's gradient holds ONE forward kernel a call
where the bare checkpoint held two, the loss and every gradient are the
bare checkpoint's to the last bit, the dense backend names nothing, and the
step records' ``attention.kept_mb`` is the bytes of exactly those arrays.
CPU, the kernels interpreted at their real tile of 512: 1,100 node slots,
three graphs of 400, 500 and 124 nodes and 76 padding nodes."""

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
import test_nemotron_h as nemotron
from test_laguna import _eqns

from hydragnn_tpu.graph.batch import HeadSpec, PadSpec, collate
from hydragnn_tpu.models import glm_moe_lite, lfm2_moe, nemotron_h
from hydragnn_tpu.models import laguna as laguna_model
from hydragnn_tpu.models.base import ModelConfig
from hydragnn_tpu.models.create import create_model
from hydragnn_tpu.ops import attention
from hydragnn_tpu.ops.moe import KEEP_ROUTE
from hydragnn_tpu.telemetry import MetricsLogger, TelemetryConfig
from hydragnn_tpu.train.trainer import (
    _loss_and_metrics,
    merge_scanned_metrics,
    model_counters,
)

GRAPHS, SLOTS = (400, 500, 124), 1100
N_PAD = 1536                        # 1,100 slots in tiles of 512


def _with(tests, **changes):
    """``tests``' model section, its language-model block changed."""
    lm = {**copy.deepcopy(tests.LM), **changes}
    section = tests.nn_section(lm=lm)
    section["Architecture"]["max_graph_nodes"] = 512
    return ModelConfig.from_config(section)


def _laguna(backend):
    cfg = _with(laguna)             # layer 0: full attention, dense
    return laguna_model.LagunaLayer(
        cfg.lm, cfg.share, 0, jnp.float32, backend, None, True), "p", 2, 1, 16


def _glm(backend):
    cfg = _with(glm)
    return glm_moe_lite.GlmLayer(
        cfg.lm, cfg.share, True, jnp.float32, backend, None, True), "pb", 3, 3, 16


def _nemotron(backend):
    cfg = _with(nemotron)
    return nemotron_h._layer(
        "*", cfg.lm, cfg.share, jnp.float32,
        nemotron_h.Backends(backend, None, None, True), "attn"), "b", 2, 1, 8


def _lfm2(backend):
    cfg = _with(lfm2, layer_types=["full_attention"], num_hidden_layers=1)
    return lfm2_moe.Lfm2Layer(
        cfg.lm, cfg.share, 0, jnp.float32, backend, None, True), "pb", 4, 2, 8


# stack -> (the layer whose attention half is checkpointed, whether it takes
# positions and a bias, query heads, key/value heads, head size; the module that
# draws the checkpoint, its policy's name there, that policy's names, and
# what the half falls back to without it)
STACKS = {
    "laguna": (_laguna, laguna_model, "KEEP_ATTN", "qkvo", None),
    "glm_moe_lite": (_glm, glm_moe_lite, "KEEP_ATTN_OUT", "o", None),
    "nemotron_h": (_nemotron, nemotron_h, "KEEP", "qkvo", KEEP_ROUTE),
    "lfm2_moe": (_lfm2, lfm2_moe, "KEEP_ATTN", "qkvo", None),
}
TESTS = {"laguna": laguna, "glm_moe_lite": glm, "nemotron_h": nemotron,
         "lfm2_moe": lfm2}


def _calls(heads, kv):
    """(kernel calls of one ``graph_attention``, query heads a call): one
    over all heads where every head has its own key/value head, else one a
    key/value head."""
    return (1, heads) if kv == heads and heads > 1 else (kv, heads // kv)


def _named(names, n, n_pad, heads, kv, size):
    """The float32 arrays a checkpoint keeps of one ``graph_attention``
    call, as a multiset of shapes: each kernel call's result and
    log-sum-exp, and q, k, v as they arrive, named flat."""
    calls, per = _calls(heads, kv)
    kept = collections.Counter()
    if "o" in names:
        kept[(per, n_pad, size)] += calls
        kept[(per, n_pad)] += calls
    if "q" in names:            # flat, [N, heads x size]
        kept[(n, heads * size)] += 1
        kept[(n, kv * size)] += 2
    return kept


def _mb(kept):
    return sum(4 * int(np.prod(s)) * c for s, c in kept.items()) / 1e6


def _half(name, backend):
    """(a loss of the layer by its parameters and input, with the half's
    blocks beside it; the arguments; the expected named shapes and the
    kernel calls of one pass)."""
    build, _, _, names, _ = STACKS[name]
    layer, takes, heads, kv, size = build(backend)
    gid = jnp.asarray(np.repeat(np.arange(4), [*GRAPHS, SLOTS - sum(GRAPHS)]),
                      jnp.int32)
    mask = (gid < 3).astype(jnp.float32)
    first = np.concatenate([[0], np.cumsum(GRAPHS)])
    positions = jnp.arange(SLOTS, dtype=jnp.int32) - jnp.asarray(
        first, jnp.int32)[gid]
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (SLOTS, 32))
    weigh = jax.random.normal(keys[1], (SLOTS, 32)) * mask[:, None]
    args = (gid, mask, *((positions,) if "p" in takes else ()),
            *((None,) if "b" in takes else ()))
    params = layer.init({"params": keys[2]}, x, *args)["params"]

    def loss(params, x):
        out, _, blocks, *_ = layer.apply({"params": params}, x, *args)
        return jnp.sum(out * weigh), blocks

    return loss, (params, x), (_named(names, SLOTS, N_PAD, heads, kv, size),
                               _calls(heads, kv)[0])


def _grad(loss):
    return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)


def _kernels(jaxpr):
    return collections.Counter(
        e.params["name"]
        for e in _eqns(jaxpr) if e.primitive.name == "pallas_call")


def _residuals(fn, args):
    """The float arrays kept from forward to backward, by shape (the ids and
    the kernels' block tables, integers of a few KB, are left out)."""
    return collections.Counter(
        tuple(aval.shape) for aval, _ in saved_residuals(
            lambda *a: fn(*a)[0], *args)
        if jnp.issubdtype(aval.dtype, jnp.floating))


def _same_bits(got, want):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            jax.tree_util.keystr(path))
        assert np.any(np.asarray(a)), jax.tree_util.keystr(path)


@pytest.mark.parametrize("name", list(STACKS))
def test_the_half_keeps_its_names_and_runs_one_forward_kernel(
        name, monkeypatch):
    _, module, policy, _, bare = STACKS[name]
    fn, args, (named, calls) = _half(name, "splash")
    kernels = _kernels(jax.make_jaxpr(_grad(fn))(*args).jaxpr)
    forward = [k for k in kernels if "_fwd_" in k]
    assert len(forward) == 1 and kernels[forward[0]] == calls, kernels
    assert sum(kernels.values()) == 3 * calls       # and one dq, one dkv
    kept = _residuals(fn, args)
    (loss, blocks), grads = jax.jit(_grad(fn))(*args)
    assert float(blocks[2]) == pytest.approx(_mb(named), rel=1e-6)

    # the bare checkpoint: the half recomputed from its input alone
    monkeypatch.setattr(module, policy, bare)
    fn0, args0, _ = _half(name, "splash")
    kernels0 = _kernels(jax.make_jaxpr(_grad(fn0))(*args0).jaxpr)
    assert kernels0[forward[0]] == 2 * calls, kernels0
    assert sum(kernels0.values()) == 4 * calls
    kept0 = _residuals(fn0, args0)
    # what the policy adds to the residuals is the named arrays and nothing
    # else (for latent attention no q, k, v), and it takes nothing away
    assert kept - kept0 == named and not kept0 - kept
    (loss0, blocks0), grads0 = jax.jit(_grad(fn0))(*args0)
    assert float(blocks0[2]) == 0.0
    assert float(loss) == float(loss0)
    _same_bits(grads, grads0)


@pytest.mark.parametrize("name", list(STACKS))
def test_the_dense_backend_names_nothing(name, monkeypatch):
    _, module, policy, _, bare = STACKS[name]
    fn, args, _ = _half(name, "dense")
    assert not _kernels(jax.make_jaxpr(_grad(fn))(*args).jaxpr)
    kept = _residuals(fn, args)
    (loss, blocks), grads = jax.jit(_grad(fn))(*args)
    assert float(blocks[2]) == 0.0
    monkeypatch.setattr(module, policy, bare)
    fn0, args0, _ = _half(name, "dense")
    assert _residuals(fn0, args0) == kept
    (loss0, _), grads0 = jax.jit(_grad(fn0))(*args0)
    assert float(loss) == float(loss0)
    _same_bits(grads, grads0)


# stack -> (attending layers as (query heads, key/value heads, head size),
# the multi-token-prediction module's layer included)
LAYERS = {"laguna": [(2, 1, 16), (3, 1, 16), (2, 1, 16)],
          "glm_moe_lite": [(3, 3, 16)] * 4,
          "nemotron_h": [(2, 1, 8)], "lfm2_moe": [(4, 2, 8)]}


@pytest.mark.parametrize("name", list(STACKS))
def test_step_records_carry_the_kept_mb_of_the_shapes(name, tmp_path):
    """Through the train step's counters and the logger, summed over the
    attending layers: 48 node slots are one tile of the kernels.  (0 where
    the checkpoint has no policy or the backend is ``dense``: above.)"""
    names = STACKS[name][3]
    tests = TESTS[name]
    cfg = ModelConfig.from_config(tests.nn_section())
    rng = np.random.default_rng(0)
    docs = [tests.sample(rng.integers(0, 64, size=n)) for n in (5, 20, 3, 12)]
    heads = [HeadSpec(f"next{i}", "node", 1)
             for i in range(len(cfg.output_dim))]
    batch = collate(docs, PadSpec(48, 8, 5), heads)
    batch = jax.tree.map(jnp.asarray, batch)
    model = create_model(cfg)
    variables = jax.jit(lambda k: model.init({"params": k}, batch,
                                             train=False))(
        jax.random.PRNGKey(1))
    model = model.clone(attention_backend="splash", interpret=True)

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
    want = sum(_mb(_named(names, 48, 512, *layer)) for layer in LAYERS[name])
    assert want > 0
    assert record["attention"]["kept_mb"] == pytest.approx(want, rel=1e-6)
    assert record["attention"]["blocks_run"] == len(LAYERS[name])


def test_the_scan_merge_leaves_kept_mb_as_it_is():
    """``attn_kept_mb`` is a number of the dispatch's shape: over K scanned
    steps it is neither summed nor averaged (an average over graphs would
    be 0 for a dispatch of empty steps)."""
    ms = {"num_graphs": jnp.asarray([0.0, 0.0, 0.0]),
          "loss": jnp.asarray([1.0, 2.0, 3.0]),
          "attn_blocks_run": jnp.asarray([5.0, 6.0, 7.0]),
          "attn_kept_mb": jnp.asarray([248.3, 248.3, 248.3])}
    merged = merge_scanned_metrics(ms)
    assert float(merged["attn_kept_mb"]) == float(np.float32(248.3))
    assert float(merged["attn_blocks_run"]) == 18.0
    ms["num_graphs"] = jnp.asarray([12.0, 7.0, 12.0])
    assert float(merge_scanned_metrics(ms)["attn_kept_mb"]) == float(
        np.float32(248.3))


def test_the_policies_keep_the_names_they_say():
    name = attention._name_primitive()
    kept = {p: {n for n in (attention.ATTN_Q, attention.ATTN_K,
                            attention.ATTN_V, attention.ATTN_OUT,
                            "moe.route.ids")
                if p(name, name=n)}
            for p in (attention.KEEP_ATTN, attention.KEEP_ATTN_OUT,
                      nemotron_h.KEEP)}
    assert kept[attention.KEEP_ATTN_OUT] == {attention.ATTN_OUT}
    assert kept[attention.KEEP_ATTN] == {
        attention.ATTN_Q, attention.ATTN_K, attention.ATTN_V,
        attention.ATTN_OUT}
    assert kept[nemotron_h.KEEP] == kept[attention.KEEP_ATTN] | {
        "moe.route.ids"}
    assert laguna_model.KEEP_ATTN is lfm2_moe.KEEP_ATTN is attention.KEEP_ATTN
    assert glm_moe_lite.KEEP_ATTN_OUT is attention.KEEP_ATTN_OUT
