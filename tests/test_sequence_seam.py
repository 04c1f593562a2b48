"""The seam a sequence stack comes in by: its own module, ONE row of
``config.SEQUENCE_MODELS``, and rows of telemetry/counters.py for what it
counts.  A toy fifth stack is registered by that row alone and trained for
a dispatch with models/base.py, models/create.py, train/trainer.py and
telemetry/logger.py as they are; the five stacks that exist meet the same
contract, and none reads another."""

import dataclasses
import importlib
import json
import os
import re
import sys
import types
from typing import ClassVar

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_laguna
from hydragnn_tpu.config.config import ALL_MODEL_TYPES, SEQUENCE_MODELS
from hydragnn_tpu.data import transform
from hydragnn_tpu.graph.batch import HeadSpec, PadSpec, collate
from hydragnn_tpu.models import sequence
from hydragnn_tpu.models.base import ModelConfig
from hydragnn_tpu.models.create import create_model
from hydragnn_tpu.telemetry import counters
from hydragnn_tpu.telemetry.logger import MetricsLogger, TelemetryConfig
from hydragnn_tpu.train.optimizer import select_optimizer
from hydragnn_tpu.train.trainer import (
    create_train_state,
    make_scan_train_step,
    merge_scanned_metrics,
    model_counters,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = os.path.join(REPO, "hydragnn_tpu", "models")


# ---------------------------------------------------------------------------
# (i) a fifth stack by one row
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ToyConfig:
    hidden_size: int
    vocab_size: int
    intermediate_size: int
    rms_norm_eps: float
    experts_key: ClassVar[str] = "num_experts"

    @staticmethod
    def from_arch(arch):
        lm = arch["toy_lm"]
        return ToyConfig(int(lm["hidden_size"]), int(lm["vocab_size"]),
                         int(lm["intermediate_size"]),
                         float(lm["rms_norm_eps"]))


class ToyStack(sequence.SequenceStack):
    """An embedding, one dense feed-forward that keeps its up-products
    where they are narrow, a head; it counts the ``ffn`` block."""

    @nn.compact
    def __call__(self, g, train=True):
        lm, share, dtype = self.cfg.lm, self.cfg.share, self.compute_dtype
        embed = self.param("embed", nn.initializers.normal(stddev=1.0),
                           (share.vocab_rows, lm.hidden_size))
        ids, _ = sequence.ids_and_positions(g, share)
        x = jnp.take(embed, ids, axis=0)
        ffn = sequence.DenseFFN(
            lm, dtype, sequence.where_narrow(sequence.KEEP_FFN, dtype),
            name="ffn")
        kept = {"ffn": ffn.kept_mb(x)}
        x = x + ffn(x)
        head = self.param("head", sequence.fan_in(lm.hidden_size),
                          (lm.hidden_size, share.vocab_rows))
        sequence.count_kept(self, [kept], train, "ffn")
        return (sequence.dot(x, head, dtype),)


TOY = {"hidden_size": 16, "vocab_size": 64, "intermediate_size": 32,
       "rms_norm_eps": 1e-6, "num_experts": 1, "num_key_value_heads": 1}


def test_a_fifth_stack_is_a_module_and_one_row(monkeypatch, tmp_path):
    mod = types.ModuleType("hydragnn_tpu.models.toy_lm")
    mod.Config, mod.Stack = ToyConfig, ToyStack
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    monkeypatch.setitem(SEQUENCE_MODELS, "ToyLm", "toy_lm")     # THE row
    # the data pipeline reads the same table: no edge list for it either
    assert "ToyLm" in transform.EDGE_FREE_MODELS

    section = test_laguna.nn_section("bfloat16")
    arch = section["Architecture"]
    del arch["laguna"]
    arch.update(model_type="ToyLm", toy_lm=TOY, share={})
    cfg = ModelConfig.from_config(section)
    assert cfg.lm == ToyConfig(16, 64, 32, 1e-6)
    assert cfg.share.vocab_rows == 64 and cfg.share.experts_held == 1
    model = create_model(cfg)
    assert isinstance(model, ToyStack)
    with pytest.raises(ValueError, match="ToyLm requires Architecture.toy_lm"):
        create_model(dataclasses.replace(cfg, lm=None))

    rng = np.random.default_rng(0)
    docs = [test_laguna.sample(rng.integers(0, 64, size=n))
            for n in (5, 20, 3, 12)]
    batch = collate(docs, PadSpec(48, 8, 5), [HeadSpec("next", "node", 1)])
    opt = select_optimizer({"type": "AdamW", "learning_rate": 1e-2})
    state = create_train_state(model, batch, opt)
    assert set(state.batch_stats) == {"ffn_kept_mb"}
    step = jax.jit(make_scan_train_step(model, cfg, opt, steps=2,
                                        telemetry_metrics=True))
    stacked = jax.tree.map(lambda a: np.stack([a, a]), batch)
    out_dir = str(tmp_path / "telemetry")
    tele = MetricsLogger(TelemetryConfig(enable=True, sinks=("jsonl",)),
                         run_name="toy", out_dir=out_dir)
    tele.begin_epoch(0)
    losses = []
    for _ in range(3):
        state, metrics = step(state, stacked)
        tele.on_step(metrics, stacked)
        losses.append(float(metrics["loss"]))
    tele.flush_steps()
    tele.finalize()
    assert losses[-1] < losses[0]
    steps = [r for r in map(json.loads, open(
        os.path.join(out_dir, "events.jsonl"))) if r["event"] == "step"]
    assert len(steps) == 3
    # two up-products of [48, 32] bfloat16, in ONE step of the two
    want = 2 * 48 * 32 * 2 / 1e6
    for r in steps:
        assert r["ffn"] == {"kept_mb": pytest.approx(want)}
        assert not {"moe", "attention", "ssm", "sconv", "gdn"} & set(r)


# ---------------------------------------------------------------------------
# (ii) the five rows
# ---------------------------------------------------------------------------


def test_the_table_is_the_list_of_sequence_models():
    assert dict(SEQUENCE_MODELS) == {
        "Laguna": "laguna", "GlmMoeLite": "glm_moe_lite",
        "NemotronH": "nemotron_h", "Lfm2Moe": "lfm2_moe",
        "Qwen3Next": "qwen3_next"}
    assert ALL_MODEL_TYPES[-5:] == list(SEQUENCE_MODELS)
    assert len(set(ALL_MODEL_TYPES)) == len(ALL_MODEL_TYPES) == 14


@pytest.mark.parametrize("model_type", list(SEQUENCE_MODELS))
def test_a_row_names_a_module_with_a_config_and_a_stack(model_type):
    section = SEQUENCE_MODELS[model_type]
    mod = importlib.import_module(f"hydragnn_tpu.models.{section}")
    assert dataclasses.is_dataclass(mod.Config)
    assert callable(mod.Config.from_arch)
    assert isinstance(mod.Config.experts_key, str)
    assert issubclass(mod.Stack, sequence.SequenceStack)
    assert mod.Stack is not sequence.SequenceStack
    # the trainer reads the three switches off the instance
    stack = mod.Stack(cfg=None)
    assert (stack.casts_at_boundary, stack.jit_init,
            stack.cost_model_sees_flops) == (False, True, False)
    assert [f.name for f in dataclasses.fields(mod.Stack)][:4] == [
        "cfg", "attention_backend", "moe_backend", "interpret"]


@pytest.mark.parametrize("model_type", list(SEQUENCE_MODELS))
def test_no_stack_reads_another_stack(model_type):
    section = SEQUENCE_MODELS[model_type]
    with open(os.path.join(MODELS, f"{section}.py")) as f:
        source = f.read()
    read = set(re.findall(
        r"^\s*(?:from|import)\s+hydragnn_tpu\.models\.(\w+)", source, re.M))
    assert not re.search(
        r"^\s*from\s+hydragnn_tpu\.models\s+import", source, re.M)
    # its own reference's rotation at most (ROADMAP D19)
    assert {"sequence"} <= read <= {"sequence", f"{section}_reference"}


def test_what_the_stacks_share_is_written_once():
    """The trainer's three switches are assigned in one place under
    models/, and neither the trainer nor the logger spells a counter."""
    sources = {name: open(os.path.join(MODELS, name)).read()
               for name in os.listdir(MODELS) if name.endswith(".py")}
    for switch in ("casts_at_boundary", "jit_init", "cost_model_sees_flops"):
        homes = [name for name, s in sources.items()
                 if re.search(rf"^\s*{switch}\s*=", s, re.M)]
        assert homes == ["sequence.py"], (switch, homes)
    prefixes = sorted(block.prefix for block in counters.BLOCKS.values())
    assert prefixes == ["attn_", "ffn_", "gdn_", "moe_", "sconv_", "ssm_"]
    for path in ("train/trainer.py", "telemetry/logger.py"):
        with open(os.path.join(REPO, "hydragnn_tpu", path)) as f:
            code = "\n".join(line.split("#")[0] for line in f)
        assert not re.search(r"[\"'`](?:%s)" % "|".join(prefixes), code), path


# ---------------------------------------------------------------------------
# (iii) the counters' table
# ---------------------------------------------------------------------------


class _Cells(nn.Module):
    block: str
    keys: tuple

    @nn.compact
    def __call__(self, train):
        counters.keep(self, self.block, train, self.keys,
                      lambda: range(1, len(self.keys) + 1))
        return jnp.zeros(())


def test_keep_declares_fills_and_refuses():
    cells = _Cells("sconv", ("rows", "kept_mb"))
    variables = cells.init(jax.random.PRNGKey(0), True)
    assert variables["batch_stats"] == {"sconv_rows": 0.0,
                                        "sconv_kept_mb": 0.0}
    _, eval_out = cells.apply(variables, False, mutable=["batch_stats"])
    assert eval_out["batch_stats"] == variables["batch_stats"]
    _, out = cells.apply(variables, True, mutable=["batch_stats"])
    assert out["batch_stats"] == {"sconv_rows": 1.0, "sconv_kept_mb": 2.0}
    assert all(v.dtype == jnp.float32 and v.shape == ()
               for v in out["batch_stats"].values())
    assert set(model_counters({**out["batch_stats"],
                               "bias_layer_0": jnp.zeros((4,)),
                               "sconv_other": jnp.zeros(())})
               ) == {"sconv_rows", "sconv_kept_mb"}
    with pytest.raises(KeyError, match="taps_met"):
        _Cells("sconv", ("rows", "taps_met")).init(
            jax.random.PRNGKey(0), True)
    with pytest.raises(KeyError):
        _Cells("conv", ("rows",)).init(jax.random.PRNGKey(0), True)


@pytest.mark.parametrize("name,rule,want", [
    ("moe_slots_all", counters.SUM, 30.0),
    ("ssm_chunks", counters.SUM, 30.0),
    ("moe_load_max_over_mean", counters.MEAN, 17.5),    # by real graphs
    ("moe_bias_abs_max", counters.MEAN, 17.5),
    ("attn_kept_mb", counters.SAME, 10.0),
    ("ffn_kept_mb", counters.SAME, 10.0),
    ("nodes_real", None, 30.0),         # the trainer's own counts
    ("loss", None, 17.5),               # and everything else
    ("moe_not_in_the_table", None, 17.5)])
def test_a_dispatch_merges_each_key_by_its_rule(name, rule, want):
    assert counters.rule(name) == rule
    ms = {"num_graphs": jnp.array([1.0, 3.0]), name: jnp.array([10.0, 20.0])}
    merged = merge_scanned_metrics(ms)
    assert float(merged[name]) == want and float(merged["num_graphs"]) == 4.0


def test_a_record_block_is_written_by_its_first_key():
    m = {"moe_slots_held": 1, "moe_slots_all": 2, "moe_dense_steps": 0,
         "moe_load_max_over_mean": 1.5, "ffn_kept_mb": 3, "loss": 0.1,
         "attn_blocks_band": 4}
    assert counters.record_blocks(m) == {
        "moe": {"slots_held": 1.0, "slots_all": 2.0,
                "load_max_over_mean": 1.5, "dense_steps": 0.0},
        "ffn": {"kept_mb": 3.0}}
    m.update(moe_bias_abs_max=0.25)
    assert counters.record_blocks(m)["moe"]["bias_abs_max"] == 0.25
    assert counters.record_blocks({"loss": 0.1}) == {}
