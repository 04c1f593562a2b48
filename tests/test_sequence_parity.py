"""The four older sequence stacks' programs are the ones of commit 532a60a
(PR 42, the parent of the PR that moved their shared layers to
models/sequence.py and their counters to telemetry/counters.py), and the
fifth's (models/qwen3_next.py) the ones of the PR that brought it (44): the
traced gradient program with its scope names, the seeded initial values and
the step record's model blocks are pinned by value, so that a move of
shared code that changes any of them fails here and not as a moved rate in
a benchmark cell.

The digests were printed by this file (``python tests/test_sequence_parity.py``)
on 532a60a (``qwen3_next``'s on PR 44's tree), on this container's jax 0.9.0.  A jax upgrade changes how jaxprs
print and may change what the initialisers draw: regenerate from a commit
whose programs are known to be good, never from the tree under test.

PR 46 moved ``qwen3_next``'s two gradient digests and no other: the
DeltaNet half's input product lost its name (models/qwen3_next.py: no
policy asks for it any more) and the delta rule's inverse gained one
(ops/gdn.py ``GDN_INV``), which the digests above do not see, because on
the CPU the rule's default backend is the sequential twin and computes no
inverse.  ``CHUNKED`` pins the fifth stack's program with the chunked rule,
as the chip runs it.  All three pairs were taken on PR 46's tree, after
this check, made once by hand: with each tree's own name stubbed out (the
product's on PR 44's tree, the inverse's on PR 46's) the two trees' float32
programs print alike, sequential ``895962a7dc36a225`` (so that digest IS
PR 44's program less a name) and chunked ``b6bf4ec28a35b813``.
"""

import functools
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_glm_moe_lite
import test_laguna
import test_lfm2_moe
import test_nemotron_h
import test_qwen3_next
from hydragnn_tpu.graph.batch import HeadSpec, PadSpec, collate
from hydragnn_tpu.models.base import ModelConfig
from hydragnn_tpu.models.create import create_model
from hydragnn_tpu.telemetry.logger import MetricsLogger, TelemetryConfig
from hydragnn_tpu.train.optimizer import select_optimizer
from hydragnn_tpu.train.trainer import (
    _loss_and_metrics,
    create_train_state,
    make_scan_train_step,
)

STACKS = {"laguna": test_laguna, "glm_moe_lite": test_glm_moe_lite,
          "nemotron_h": test_nemotron_h, "lfm2_moe": test_lfm2_moe,
          "qwen3_next": test_qwen3_next}
DTYPES = ("float32", "bfloat16")
BLOCKS = ("moe", "attention", "ssm", "sconv", "ffn", "gdn")


@functools.lru_cache(maxsize=None)
def _setup(stack, dtype="float32", **backends):
    T = STACKS[stack]
    cfg = ModelConfig.from_config(T.nn_section(dtype))
    rng = np.random.default_rng(0)
    docs = [T.sample(rng.integers(0, 64, size=n)) for n in (5, 20, 3, 12)]
    heads = [HeadSpec(f"next{i}", "node", 1)
             for i in range(len(cfg.output_dim))]
    batch = jax.tree.map(jnp.asarray, collate(docs, PadSpec(48, 8, 5), heads))
    model = create_model(cfg).clone(**backends)
    opt = select_optimizer({"type": "AdamW", "learning_rate": 1e-3})
    return cfg, model, opt, batch, create_train_state(model, batch, opt)


def _sha(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def grad_digest(stack, dtype, **backends):
    """sha256[:16] of the gradient jaxpr of one train step's loss, printed
    with every equation's scope (``name_stack``: the names the per-layer
    metrics find their operations by); a checkpoint policy prints as a
    function at an address, which is taken out.  ``backends``: fields of
    the stack set by name where the CPU's default is not the chip's."""
    cfg, model, _opt, batch, state = _setup(stack, dtype, **backends)

    def loss(params):
        return _loss_and_metrics(model, cfg, params, state.batch_stats,
                                 batch, True)

    jaxpr = jax.make_jaxpr(jax.grad(loss, has_aux=True))(state.params)
    text = jaxpr.pretty_print(source_info=False, name_stack=True)
    return _sha(re.sub(r" at 0x[0-9a-f]+", "", text))


def init_digest(stack):
    """sha256[:16] over every leaf of ``create_train_state``'s parameters
    and ``batch_stats`` at seed 0: path, dtype, shape and bytes."""
    *_, state = _setup(stack)
    h = hashlib.sha256()
    for tree in (state.params, state.batch_stats):
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            a = np.asarray(leaf)
            h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}"
                     .encode())
            h.update(a.tobytes())
    return h.hexdigest()[:16]


def record_blocks(stack, out_dir):
    """(the metric keys of one scanned K=2 dispatch, the model blocks of
    its step record)."""
    cfg, model, opt, batch, state = _setup(stack)
    step = jax.jit(make_scan_train_step(model, cfg, opt, steps=2,
                                        telemetry_metrics=True))
    stacked = jax.tree.map(lambda a: jnp.stack([a, a]), batch)
    tele = MetricsLogger(TelemetryConfig(enable=True, sinks=("jsonl",)),
                         run_name=f"parity_{stack}", out_dir=out_dir)
    tele.begin_epoch(0)
    _state, metrics = step(state, stacked)
    tele.on_step(metrics, stacked)
    tele.flush_steps()
    tele.finalize()
    (rec,) = [r for r in map(json.loads, open(
        os.path.join(out_dir, "events.jsonl"))) if r["event"] == "step"]
    return sorted(metrics), {b: rec[b] for b in BLOCKS if b in rec}


GRAD = {("laguna", "float32"): "a87bb8242efc87c5",
        ("laguna", "bfloat16"): "af9c59b29f0009d4",
        ("glm_moe_lite", "float32"): "755eaa65acbe5902",
        ("glm_moe_lite", "bfloat16"): "c75b17700f1f33a8",
        ("nemotron_h", "float32"): "0e3c957341cf5476",
        ("nemotron_h", "bfloat16"): "dee757781689d06a",
        ("lfm2_moe", "float32"): "fff73300fb999c36",
        ("lfm2_moe", "bfloat16"): "eb51e43b95eea108",
        ("qwen3_next", "float32"): "895962a7dc36a225",
        ("qwen3_next", "bfloat16"): "b35b5cd66bec1f68"}
# The fifth stack's program as the chip runs its rule: the ``chunked``
# backend asked for by name (the CPU's default, the sequential twin, computes
# no inverse, so the digests above do not see what ops/gdn.py names).
CHUNKED = {"float32": "87180c25d2c7cd0d", "bfloat16": "46bcba3261355253"}
INIT = {"laguna": "1a2ad2312401d5e2", "glm_moe_lite": "6af477e66acdf500",
        "nemotron_h": "76d5a17d33ed9ea1", "lfm2_moe": "bc5b069b5b854333",
        "qwen3_next": "26ca93c2dc20d91a"}

# the trainer's own metrics of a telemetry step, then what each stack counts
_STEP = ["edges_real", "grad_norm", "loss", "nodes_real", "num_graphs",
         "param_norm", "task_0", "update_norm"]
_ATTN = ["attn_blocks_band", "attn_blocks_run", "attn_kept_mb"]
_MOE = ["moe_dense_steps", "moe_load_max_over_mean", "moe_slots_all",
        "moe_slots_held"]
_BIAS = ["moe_bias_abs_max", "moe_load_all_max_over_mean"]
_NO_KERNEL = {"blocks_band": 1.0, "blocks_run": 1.0, "kept_mb": 0.0}


def _attention(calls):
    # 48 nodes are one block of the kernels' 512, and the dense backend
    # (the CPU's) names nothing for a checkpoint to keep
    return {k: v * calls for k, v in _NO_KERNEL.items()}


RECORD = {
    "laguna": (_STEP + _ATTN + _MOE + ["ffn_kept_mb"], {
        "attention": _attention(6), "ffn": {"kept_mb": 0.0},
        "moe": {"dense_steps": 0.0, "load_max_over_mean": 1.4266667366027832,
                "slots_all": 480.0, "slots_held": 90.0}}),
    "glm_moe_lite": (_STEP + ["task_1"] + _ATTN + _MOE + _BIAS, {
        "attention": _attention(8),
        "moe": {"bias_abs_max": 0.001500000013038516, "dense_steps": 0.0,
                "load_all_max_over_mean": 2.224691390991211,
                "load_max_over_mean": 1.8554677963256836,
                "slots_all": 696.0, "slots_held": 177.0}}),
    "nemotron_h": (_STEP + _ATTN + _MOE + _BIAS + [
        "ssm_chunks", "ssm_chunks_padding", "ssm_resets"], {
        "attention": _attention(2),
        "moe": {"bias_abs_max": 0.001500000013038516, "dense_steps": 0.0,
                "load_all_max_over_mean": 1.7000000476837158,
                "load_max_over_mean": 1.4741954803466797,
                "slots_all": 800.0, "slots_held": 160.0},
        "ssm": {"chunks": 6.0, "chunks_padding": 0.0, "resets": 8.0}}),
    "lfm2_moe": (_STEP + _ATTN + _MOE + _BIAS + [
        "ffn_kept_mb", "sconv_kept_mb", "sconv_rows", "sconv_starts",
        "sconv_taps_cut"], {
        "attention": _attention(2), "ffn": {"kept_mb": 0.0},
        "moe": {"bias_abs_max": 0.001500000013038516, "dense_steps": 0.0,
                "load_all_max_over_mean": 2.1666667461395264,
                "load_max_over_mean": 1.5797533988952637,
                "slots_all": 480.0, "slots_held": 131.0},
        "sconv": {"kept_mb": 0.0, "rows": 160.0, "starts": 16.0,
                  "taps_cut": 48.0}}),
    # three DeltaNet layers x (6 chunks of 8, one of them padding, 4
    # graphs) x the dispatch's 2 steps
    "qwen3_next": (_STEP + _ATTN + _MOE + [
        "gdn_chunks", "gdn_chunks_padding", "gdn_kept_mb", "gdn_resets"], {
        "attention": _attention(2),
        "gdn": {"chunks": 36.0, "chunks_padding": 6.0, "kept_mb": 0.0,
                "resets": 24.0},
        "moe": {"dense_steps": 0.0, "load_max_over_mean": 1.4006855487823486,
                "slots_all": 960.0, "slots_held": 243.0}})}


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("stack", list(STACKS))
def test_the_gradient_program_is_the_parents(stack, dtype):
    assert grad_digest(stack, dtype) == GRAD[stack, dtype]


@pytest.mark.parametrize("dtype", DTYPES)
def test_the_chunked_rules_gradient_program_is_pr_46s(dtype):
    assert grad_digest("qwen3_next", dtype,
                       gdn_backend="chunked") == CHUNKED[dtype]


@pytest.mark.parametrize("stack", list(STACKS))
def test_the_seeded_initial_values_are_the_parents(stack):
    assert init_digest(stack) == INIT[stack]


@pytest.mark.parametrize("stack", list(STACKS))
def test_the_step_records_model_blocks_are_the_parents(stack, tmp_path):
    keys, blocks = record_blocks(stack, str(tmp_path / "telemetry"))
    want_keys, want_blocks = RECORD[stack]
    assert keys == sorted(want_keys)
    assert blocks.keys() == want_blocks.keys()
    for name, want in want_blocks.items():
        assert blocks[name] == pytest.approx(want, rel=1e-6), name


if __name__ == "__main__":
    import pprint
    import tempfile

    pprint.pprint({"GRAD": {(s, d): grad_digest(s, d)
                            for s in STACKS for d in DTYPES},
                   "CHUNKED": {d: grad_digest("qwen3_next", d,
                                              gdn_backend="chunked")
                               for d in DTYPES},
                   "INIT": {s: init_digest(s) for s in STACKS},
                   "RECORD": {s: record_blocks(s, tempfile.mkdtemp())
                              for s in STACKS}}, width=78)
