"""The Gated DeltaNet cell's benchmark files (PR 44): the cell rehearsed on
the CPU through ``run.py`` (a correct line with its counter); every
per-layer reader this cell brought returns None (and raises nothing) over a
program that lacks its scopes and counters, over an untraced run and over
the other cells' facts, and reads a recorded trace where the scopes are;
readers, counts and the reference copy import nothing of the program; the
configuration file holds the catalog's numbers but the ``reduced``; the
program counts the issue's 625,667,136 parameters; the counts are the
mathematics and the roofline share cannot pass 100 by construction of its
two bounds; the comparison's groups cover every parameter once; and,
anchored BY NAME and tolerant of whatever a later PR appends behind them:
this cell's configuration, cell and four per-layer entries, the parent's
``BENCHMARK.json`` byte for byte once they are taken out, and every
benchmark file that existed before, as this PR found it."""

import filecmp
import hashlib
import json
import os
import subprocess
import sys

import pytest

from benchload import BENCH, REPO, cpu_env, load

CELL = "qwen3_next_80b_a3b-packed4k_d6"
CONFIG = "qwen3_next_80b_a3b"
SOURCE = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
          "config.json")
LAYER_NAME = "gated delta rule over each graph's nodes (ops/gdn.py)"
# name -> (unit, better, source), in the order they were appended
NEW = {
    "gdn_ms": ("ms", "lower", "device_trace"),
    "gdn_scan_ms": ("ms", "lower", "device_trace"),
    "gdn_scan_roofline_pct": ("%", "higher", "device_trace"),
    "gdn_pad_chunk_pct": ("%", "lower", "program_counter"),
}
# how many entries the parent's lists held, and sha256[:16] of its file
PARENT_COUNTS = {"configs": 5, "workloads": 6, "end_to_end": 2,
                 "per_layer": 49}
BENCHMARK_BEFORE = "983ecc87214d1bad"
# sha256[:16] of every benchmark file as this PR (44) found it
FILES_BEFORE = {
    "benchmark/configs/glm_4_7_flash.json": "ea5999293f564e34",
    "benchmark/configs/laguna_s_2_1.json": "7a7b513341c60bd8",
    "benchmark/configs/lfm2_24b_a2b.json": "be48001474ffd4e5",
    "benchmark/configs/nemotron_3_super.json": "4c75a64b78e55d97",
    "benchmark/configs/schnet_qm9.json": "14011d19fc7af3e8",
    "benchmark/corpora/packed_docs.py": "eeac2de1ab301b26",
    "benchmark/corpora/packed_docs_mtp.py": "30683a379e221d00",
    "benchmark/corpora/qm9_shaped.py": "a638171510789c19",
    "benchmark/drivers/train_epochs.py": "50af84cb1dae70e1",
    "benchmark/drivers/train_epochs_lm.py": "f1db0d8958c1c84a",
    "benchmark/drivers/train_epochs_mla.py": "44bef20a42f4dde9",
    "benchmark/drivers/train_epochs_sconv.py": "4422a389a5516bf3",
    "benchmark/drivers/train_epochs_ssm.py": "70bded3eaca7da1b",
    "benchmark/layer_metrics/attn_core_ms.py": "65d8f31271464573",
    "benchmark/layer_metrics/attn_core_mxu_pct.py": "92f69eb777d57aea",
    "benchmark/layer_metrics/collective_exposed_pct.py": "a5cd465a426195a7",
    "benchmark/layer_metrics/device_idle_pct.py": "51a24519025162ff",
    "benchmark/layer_metrics/dispatch_host_ms.py": "e8321834cdf14254",
    "benchmark/layer_metrics/epoch_tail_ms.py": "50c21e304709d520",
    "benchmark/layer_metrics/eval_share_pct.py": "4b9e2b816483ad93",
    "benchmark/layer_metrics/gather_mul_seg_bwd_ms.py": "f810bde5bd67af78",
    "benchmark/layer_metrics/gather_mul_seg_fwd_ms.py": "07f983269e50bea0",
    "benchmark/layer_metrics/hbm_live_peak_gb.py": "632a0d05f0c63d16",
    "benchmark/layer_metrics/hbm_peak_gb.py": "e36d2ba2b34103d6",
    "benchmark/layer_metrics/hbm_step_need_gb.py": "2883ec4854e64c2d",
    "benchmark/layer_metrics/hbm_step_temp_gb.py": "c6cc64361688c647",
    "benchmark/layer_metrics/lm_head_ms.py": "cf1f845ee2aeed2b",
    "benchmark/layer_metrics/loader_wait_pct.py": "6a946968e62f0d9e",
    "benchmark/layer_metrics/mla_core_ms.py": "8710d2dde99ae6d4",
    "benchmark/layer_metrics/mla_core_mxu_pct.py": "5c9aec1fe8e2c8f6",
    "benchmark/layer_metrics/mla_latent_ms.py": "7333f23fd5b47ba6",
    "benchmark/layer_metrics/moe_all_load_max_over_mean.py": "b4012624dc23c69a",
    "benchmark/layer_metrics/moe_gmm_mxu_pct.py": "5f7137fa9c3aaf65",
    "benchmark/layer_metrics/moe_held_share_pct.py": "34d6c869f130040c",
    "benchmark/layer_metrics/moe_latent_ms.py": "00ecc2c6dedec14d",
    "benchmark/layer_metrics/moe_load_max_over_mean.py": "9e812df08385f7e4",
    "benchmark/layer_metrics/moe_route_ms.py": "0998fb6848545473",
    "benchmark/layer_metrics/moe_routed_ms.py": "94fba2f97d6be43b",
    "benchmark/layer_metrics/moe_shared_ms.py": "0fb413ed43fa7229",
    "benchmark/layer_metrics/mosaic_busy_pct.py": "285778766e548d54",
    "benchmark/layer_metrics/mtp_ms.py": "459e5708b151dbf2",
    "benchmark/layer_metrics/pad_edges_waste_pct.py": "70b51bcd7881911b",
    "benchmark/layer_metrics/pad_nodes_waste_pct.py": "8662d3fabf2d2b6b",
    "benchmark/layer_metrics/sconv_core_ms.py": "591253dd262e84cc",
    "benchmark/layer_metrics/sconv_core_roofline_pct.py": "4003ed8f5028c9c3",
    "benchmark/layer_metrics/sconv_ms.py": "dab753843800de05",
    "benchmark/layer_metrics/sconv_taps_cut_pct.py": "e931742127ed55e3",
    "benchmark/layer_metrics/setup_cache_load_s.py": "de7f471e3fc0aa7b",
    "benchmark/layer_metrics/setup_collate_s.py": "2a91546fe96efa12",
    "benchmark/layer_metrics/setup_compile_s.py": "b9d79d7021a343d9",
    "benchmark/layer_metrics/setup_epoch0_s.py": "2600af74f5dcbac1",
    "benchmark/layer_metrics/setup_mfu_cost_s.py": "381c9bd62105789a",
    "benchmark/layer_metrics/setup_programs_built.py": "71122411cbe59ce3",
    "benchmark/layer_metrics/setup_trace_lower_s.py": "7160b5863eda39ef",
    "benchmark/layer_metrics/ssm_ms.py": "b6221e22c51d69cc",
    "benchmark/layer_metrics/ssm_pad_chunk_pct.py": "0a68bc96a484ce3d",
    "benchmark/layer_metrics/ssm_scan_ms.py": "f080c0e41cb4799b",
    "benchmark/layer_metrics/ssm_scan_roofline_pct.py": "593dc77567a19656",
    "benchmark/layer_metrics/step_bwd_ms.py": "3977592741416557",
    "benchmark/layer_metrics/step_device_ms.py": "997f95e3a45d2af8",
    "benchmark/layer_metrics/step_fwd_ms.py": "d426a87f70788e0c",
    "benchmark/layer_metrics/step_named_pct.py": "be5e399ef8088dac",
    "benchmark/layer_metrics/step_opt_ms.py": "0f65420ccedc6603",
    "benchmark/lm_counts.py": "4e5a1aece547ea2d",
    "benchmark/mla_counts.py": "b038e2ddbc7b8b2e",
    "benchmark/peaks.py": "541cd680d4811e95",
    "benchmark/program_records.py": "119e9cfb687ff35c",
    "benchmark/reference/glm_moe_lite_reference.py": "6121a5f09373895e",
    "benchmark/reference/laguna_reference.py": "a419d905e38b933f",
    "benchmark/reference/lfm2_moe_reference.py": "922674143b4d61e4",
    "benchmark/reference/nemotron_h_reference.py": "684e688ccf753416",
    "benchmark/run.py": "766ceea451b0dca3",
    "benchmark/sconv_counts.py": "48385815669b3db0",
    "benchmark/ssm_counts.py": "45240cf2fc172153",
    "benchmark/trace_lm.py": "d3561b2bed50e6e0",
    "benchmark/trace_reduce.py": "a250de61a9ab9541",
    "benchmark/trace_scopes.py": "43e910d9aa3ba75b",
    "benchmark/traffic/dp4.json": "f198a9692996ca51",
    "benchmark/traffic/hostfed.json": "e092de852e795a0e",
    "benchmark/traffic/packed4k.json": "7f03da3c4a4d5a4a",
    "benchmark/traffic/packed4k_d12.json": "a086b06e50f71991",
    "benchmark/traffic/packed4k_d4.json": "84b746b1a73d1dc1",
    "benchmark/traffic/packed8k.json": "244ebf71a0d4c447",
    "benchmark/traffic/resident.json": "52b9a3ce5265f878",
    "tests/benchmark/benchload.py": "953a5f78bc25932c",
    "tests/benchmark/conftest.py": "1f2db2c8357daafc",
    "tests/benchmark/test_add_by_file.py": "01845b9d1b4f7703",
    "tests/benchmark/test_corpus.py": "dda32ec1121a7fc2",
    "tests/benchmark/test_driver_matches_run_training.py": "fe4990d651350306",
    "tests/benchmark/test_epoch_rate.py": "da6975423611541d",
    "tests/benchmark/test_harness_contract.py": "3bafa7f34bea6c2b",
    "tests/benchmark/test_lm_cell.py": "393f94fa3e4e833d",
    "tests/benchmark/test_mla_cell.py": "2d3851ecebd42ec7",
    "tests/benchmark/test_moe_route_metric.py": "0ba7e1fea3a2e448",
    "tests/benchmark/test_parked_hostfed.py": "d3ae07191cd555cb",
    "tests/benchmark/test_program_records.py": "34132080a2e0efd2",
    "tests/benchmark/test_sconv_cell.py": "cdd3ea7d29269beb",
    "tests/benchmark/test_ssm_cell.py": "ef2e7270a509c784",
    "tests/benchmark/test_trace_reduce.py": "37ee3c9e42a1931b",
    "tests/benchmark/test_trace_scopes.py": "f15f8304272016d9",
}
# the catalog's ``config`` of row Qwen3-Next-80B-A3B-Instruct
# (/opt/skills/guides/model-configs/architectures.jsonl), key by key
CATALOG = {
    "decoder_sparse_step": 1,
    "full_attention_interval": 4,
    "head_dim": 256,
    "hidden_act": "silu",
    "hidden_size": 2048,
    "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 128,
    "linear_num_key_heads": 16,
    "linear_num_value_heads": 32,
    "linear_value_head_dim": 128,
    "max_position_embeddings": 262144,
    "mlp_only_layers": [],
    "model_type": "qwen3_next",
    "moe_intermediate_size": 512,
    "norm_topk_prob": True,
    "num_attention_heads": 16,
    "num_experts": 512,
    "num_experts_per_tok": 10,
    "num_hidden_layers": 48,
    "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06,
    "rope_scaling": None,
    "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False,
    "use_sliding_window": False,
    "vocab_size": 151936
}
HELD = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config(bench):
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    with open(os.path.join(REPO, entry["file"])) as f:
        return json.load(f)


STOCK_EPOCH = {"epoch": 1, "t0": 0.0, "t1": 1.0, "graphs": 10, "steps": 2,
               "skipped": 0, "nonfinite": 0, "edges_real": 5,
               "edges_padded": 8}
# what the language-model driver sums over a program without the rule, and
# the other four language-model cells' ``lm`` blocks
LM_EPOCH = dict(STOCK_EPOCH, nodes_real=9, nodes_padded=12,
                moe_slots_held=3, moe_slots_all=40, moe_dense_steps=0,
                moe_load_max_over_mean=1.5)
OWN_LM = {"gdn": {"layers": 3, "chunk": 64, "key_heads": 16,
                  "value_heads": 32, "key_dim": 128, "value_dim": 128,
                  "operand_bytes": 2, "tokens_per_step": 8600.0}}
STEP = "jit(scan_step)/while/body/closed_call/step.loss/"
LAYER = "jvp(Qwen3NextStack)/layer_1/checkpoint/mixer/"


def _reduced(ops):
    """Facts as ``trace_scopes.load`` leaves them: ``ops`` (scope, seconds
    of self time) in a step of 10 s of self time that takes 0.1 s."""
    return {"_trace_scopes": {
        "resolved": True, "devices": 1, "step": {"total": 10.0},
        "step_device_s": 0.1,
        "ops": [(f"fusion.{i}", (t, scope, "ops/gdn.py:1"))
                for i, (scope, t) in enumerate(ops)]}}


@pytest.mark.parametrize("facts", [
    {},
    {"epochs": [], "spans": [], "trace": None},
    {"epochs": [dict(STOCK_EPOCH)], "spans": [("train", 0.0, 1.0)],
     "trace": {"step_device_s": 0.01, "busy_s": 1.0, "mosaic_s": 0.5},
     "trace_dir": "/nonexistent", "trace_window": (0.0, 1.0),
     "mono_to_unix_ns": 0.0, "train_module_regex": "jit_"},
    {"epochs": [dict(LM_EPOCH)], "lm": None, "trace": {}},
    {"epochs": [dict(LM_EPOCH)], "trace": None,
     "lm": {"attention": {}, "head_dim": 128, "hidden_size": 3072,
            "moe_intermediate_size": 1024}},
    {"epochs": [dict(LM_EPOCH, ssm_chunks=568.0, ssm_chunks_padding=236.0,
                     ssm_resets=30.0)], "trace": None,
     "lm": {"ssm": {"layers": 5, "chunk": 128, "heads": 16, "head_dim": 64,
                    "groups": 1, "state": 128, "operand_bytes": 2,
                    "tokens_per_step": 5000.0}}},
    {"epochs": [dict(LM_EPOCH, sconv_rows=272000.0, sconv_starts=48.0,
                     sconv_taps_cut=576.0)], "trace": None,
     "lm": {"sconv": {"layers": 4, "channels": 2048, "taps": 3,
                      "operand_bytes": 2, "tokens_per_step": 17000.0}}},
    # a traced program whose scopes are another stack's (the state-space
    # cell's trace: a scan, and no ``gdn.*``)
    dict(_reduced([(STEP + "jvp(NemotronHStack)/layer_0/ssm.scan/dot", 4.0),
                   (STEP + "jvp(NemotronHStack)/layer_0/ssm.in/dot", 2.0)]),
         epochs=[dict(LM_EPOCH, ssm_chunks=568.0, ssm_chunks_padding=236.0)],
         lm={"ssm": {}}),
    # this cell's own facts over the PARENT's program (no gdn block in its
    # step records, no gdn scope in its trace)
    {"epochs": [dict(LM_EPOCH, gdn_chunks=None, gdn_chunks_padding=None,
                     gdn_resets=None)], "trace": None, "lm": OWN_LM},
], ids=["empty", "no_trace", "stock_driver_untraced_scopes", "lm_none",
        "grouped_query_cell", "state_space_cell", "short_convolution_cell",
        "another_stacks_trace", "own_cell_parent_program"])
@pytest.mark.parametrize("name", list(NEW))
def test_new_reader_gives_none_where_its_source_is_absent(name, facts):
    assert load("layer_metrics", name).read(dict(facts)) is None


def test_the_readers_over_a_recorded_trace():
    """2.5 of 10 s of self time under ``gdn.*`` in a step of 100 ms, 1.5 of
    them under ``gdn.scan`` (forward and transposed alike); a scope that
    only begins like one is not read."""
    epochs = [dict(LM_EPOCH, gdn_chunks=4872.0, gdn_chunks_padding=1608.0,
                   gdn_resets=144.0, steps=8)]
    facts = dict(_reduced([
        (STEP + LAYER + "gdn.in/dot_general", 0.5),
        (STEP + LAYER + "gdn.conv/mul", 0.25),
        (STEP + LAYER + "gdn.scan/while/body/dot_general", 1.0),
        (STEP + "transpose(" + LAYER[:-1] + ")/gdn.scan/dot_general", 0.5),
        (STEP + LAYER + "gdn.norm/mul", 0.125),
        (STEP + LAYER + "gdn.out/dot_general", 0.125),
        (STEP + LAYER + "gdn.scanner/dot_general", 1.0),  # no such scope
        (STEP + "jvp(Qwen3NextStack)/layer_3/checkpoint/moe/moe.route/dot",
         2.0),
        (None, 2.0)]), epochs=epochs, lm=OWN_LM)
    assert load("layer_metrics", "gdn_ms").read(dict(facts)) == (
        pytest.approx(25.0))
    assert load("layer_metrics", "gdn_scan_ms").read(dict(facts)) == (
        pytest.approx(15.0))
    counts = load("", "gdn_counts")
    least, bound = counts.rule_least_seconds(
        OWN_LM, (4872.0 - 1608.0) / 8, 197e12, 819e9)
    assert bound == "memory"
    assert load("layer_metrics", "gdn_scan_roofline_pct").read(
        dict(facts)) == pytest.approx(100.0 * least / 0.015)
    assert load("layer_metrics", "gdn_pad_chunk_pct").read(
        dict(facts)) == pytest.approx(100.0 * 1608 / 4872)
    # an untraced run of the cell reads the counter and nothing else
    for name in ("gdn_ms", "gdn_scan_ms", "gdn_scan_roofline_pct"):
        assert load("layer_metrics", name).read(
            {"epochs": epochs, "lm": OWN_LM, "trace": None}) is None


def test_new_files_import_nothing_of_the_program():
    for rel in [f"layer_metrics/{n}.py" for n in NEW] + [
            "gdn_counts.py", "reference/qwen3_next_reference.py"]:
        with open(os.path.join(BENCH, rel)) as f:
            text = f.read()
        assert "import hydragnn" not in text, rel
        assert "from hydragnn" not in text, rel


def test_reference_copy_is_byte_identical():
    assert filecmp.cmp(
        os.path.join(BENCH, "reference", "qwen3_next_reference.py"),
        os.path.join(REPO, "hydragnn_tpu", "models",
                     "qwen3_next_reference.py"), shallow=False)


def test_files_that_were_there_are_as_this_pr_found_them():
    for rel, digest in FILES_BEFORE.items():
        with open(os.path.join(REPO, rel), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest()[:16] == digest, rel


def test_the_cell_came_as_appended_entries_found_by_name(bench):
    """By NAME, wherever later PRs' entries come to stand behind them: the
    configuration, the cell and each of the four metrics is there once,
    behind every entry the parent had; and the parent's lists, these taken
    out, dump to the parent's file byte for byte.  A later ``benchmark`` PR
    that edits an accepted entry anchors this anew."""
    names = [m["name"] for m in bench["per_layer"]]
    for name, (unit, better, source) in NEW.items():
        assert names.count(name) == 1, name
        assert names.index(name) >= PARENT_COUNTS["per_layer"], name
        assert bench["per_layer"][names.index(name)] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": LAYER_NAME, "moves": "train_graphs_per_s",
            "workloads": [CELL]}, name
    assert sorted(NEW, key=names.index) == list(NEW)
    for key, mine in (("configs", CONFIG), ("workloads", CELL)):
        listed = [e["name"] for e in bench[key]]
        assert listed.count(mine) == 1
        assert listed.index(mine) >= PARENT_COUNTS[key]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config=CONFIG, traffic="packed4k_d6", chips=1)
    # one line of <= 200 characters that says both loads
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert "per held expert" in cell["why"] and "16x" in cell["why"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    for text in (entry["source"], entry["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert len(entry["reduced"]) <= 16
    # no other cell asks for this PR's metrics, and one chip in four at most
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    before = dict(bench)
    for key, n in PARENT_COUNTS.items():
        before[key] = before[key][:n]
    assert hashlib.sha256(json.dumps(before, indent=1).encode()
                          ).hexdigest()[:16] == BENCHMARK_BEFORE


def test_config_file_holds_the_catalog_numbers_but_the_reduced(bench, config):
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE == config["Provenance"]["source"]
    assert sorted(entry["reduced"]) == sorted(list(HELD) + ["corpus"])
    for key, want in CATALOG.items():
        if key in HELD:
            assert config[key] == HELD[key] and key in entry["reduced"], key
            assert HELD[key] != want, key
        else:
            assert config[key] == want and key not in entry["reduced"], key
    # no width is among the reduced
    assert not [k for k in entry["reduced"]
                if k.endswith(("_dim", "_size")) and k != "vocab_size"]
    share = config["share"]
    assert (share["chips_per_layer"], share["expert_parallel_ranks"],
            share["num_experts_total"], share["vocab_total"],
            share["num_hidden_layers_total"], share["first_layer"]) == (
                16, 16, 512, 151936, 48, 0)
    assert share["vocab_total"] == 8 * config["vocab_size"]
    assert share["num_experts_total"] == 16 * config["num_experts"]
    for word in ("16 chips", "16 expert-parallel ranks", "16-way", "18,992",
                 "44 layers", "shared expert"):
        assert word in config["Provenance"]["deployment"], word
    assumed = config["Provenance"]["assumed"]
    assert len(assumed) >= 8
    for word in ("full_attention_interval", "multi-token-prediction",
                 "auxiliary", "linear_chunk_size", "A_log"):
        assert any(word in a for a in assumed), word
    # every reduced key is explained in the file
    told = " ".join(config["Provenance"]["reduced"])
    assert all(key in told for key in entry["reduced"])
    # the dtypes the rule reads and writes are stated, and who counts them
    assert "bfloat16" in config["Provenance"]["precision"]
    assert "gdn_counts.py" in config["Provenance"]["precision"]
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["model_type"] == "Qwen3Next"
    assert arch["compute_dtype"] == "bfloat16"
    assert config["corpus"]["generator"] == "packed_docs"
    # GLM's length law: the cells differ by model and rows, not by the law
    glm = next(c for c in bench["configs"] if c["name"] == "glm_4_7_flash")
    with open(os.path.join(REPO, glm["file"])) as f:
        assert config["corpus"]["params"] == json.load(f)["corpus"]["params"]
    # the rehearsal keeps the whole period
    dry = config["dry_cpu"]
    assert dry["num_hidden_layers"] == 4 and dry["linear_chunk_size"] == 8


def test_parameter_count_is_the_programs_own(config):
    """The program's own count at the published widths, from shapes alone
    (``jax.eval_shape``: nothing is allocated): 625,667,136, the issue's
    arithmetic, and the file says the same number."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hydragnn_tpu.graph.batch import (
        GraphSample, HeadSpec, PadSpec, collate)
    from hydragnn_tpu.models.base import ModelConfig
    from hydragnn_tpu.models.create import create_model

    skip = load("drivers", "train_epochs_gdn")._lm._HF_SKIP
    arch = dict(config["NeuralNetwork"]["Architecture"],
                qwen3_next={k: v for k, v in config.items() if k not in skip},
                share=config["share"], input_dim=1, output_dim=[1],
                output_type=["node"], max_graph_nodes=16)
    cfg = ModelConfig.from_config({
        "Architecture": arch, "Training": config["NeuralNetwork"]["Training"]})
    ids = np.arange(16, dtype=np.float32)[:, None]
    batch = collate([GraphSample(x=ids, pos=np.zeros((16, 3)),
                                 node_y=np.zeros((16, 1), np.float32))],
                    PadSpec(24, 8, 2), [HeadSpec("a", "node", 1)])
    shapes = jax.eval_shape(
        lambda b: create_model(cfg).init(
            {"params": jax.random.PRNGKey(0)}, b, train=False),
        jax.tree.map(jnp.asarray, batch))

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))

    p = shapes["params"]
    assert count(p) == 625_667_136
    assert "625,667,136" in config["Provenance"]["parameters_here"]
    assert set(p) == {"embed", "layer_0", "layer_1", "layer_2", "layer_3",
                      "final_norm", "head"}
    # the issue's arithmetic, half by half
    for layer in ("layer_0", "layer_1", "layer_2"):
        assert count(p[layer]["mixer"]) == 33_720_512, layer
        assert count(p[layer]) == 138_582_208, layer
    assert count(p["layer_3"]["mixer"]) == 27_265_536
    assert count(p["layer_3"]) == 132_127_232
    for layer in ("layer_0", "layer_3"):
        assert count(p[layer]["moe"]) == 104_861_696
    assert p["embed"].shape == (18992, 2048)
    assert p["head"].shape == (2048, 18992)
    assert p["layer_0"]["mixer"]["w_qkvz"].shape == (2048, 12288)
    assert p["layer_0"]["mixer"]["w_ba"].shape == (2048, 64)
    assert p["layer_0"]["mixer"]["conv_w"].shape == (4, 8192)
    assert p["layer_0"]["mixer"]["w_out"].shape == (4096, 2048)
    assert p["layer_3"]["mixer"]["wq"].shape == (2048, 16 * 512)
    assert p["layer_3"]["mixer"]["wk"].shape == (2048, 2 * 256)
    assert p["layer_3"]["mixer"]["q_norm"].shape == (256,)
    assert p["layer_3"]["moe"]["experts_w1"].shape == (32, 2048, 512)
    assert p["layer_3"]["moe"]["router"].shape == (2048, 512)
    assert p["layer_3"]["moe"]["shared_gate"].shape == (2048, 1)
    # no correction bias: the only state beside the parameters is counters
    assert not [k for k in shapes["batch_stats"] if k.startswith("bias_")]


def test_counts_are_the_mathematics():
    counts = load("", "gdn_counts")
    # a hand-sized case: one node, one key head and two value heads of 4,
    # bfloat16.  Forward: q, k (4 each) and v (8) read = 16 x 2 B, g and
    # beta 2 x 2 x 4 B, o written 8 x 4 B = 80; backward: those and do read
    # (80), dq, dk, dv written (32), dg and dbeta (16) = 128
    assert counts.rule_bytes_per_node(1, 2, 4, 4, 2) == 80 + 128
    # one chunk of 2 nodes, one key head, one value head of 4: K K^T and
    # Q K^T 2 x 2*2*2*4, the solve 2 * 8 / 3, W and the two [C, C] x
    # [C, d_v] products 3 x 2*2*2*4, three products with the state
    # 3 x 2*2*4*4; the backward twice the forward
    forward = 2 * 32 + 16 / 3 + 3 * 32 + 3 * 64
    assert counts.rule_flops_per_chunk(2, 1, 1, 4, 4) == pytest.approx(
        3 * forward)
    config = {"num_hidden_layers": 4, "full_attention_interval": 4,
              "linear_chunk_size": 64, "linear_num_key_heads": 16,
              "linear_num_value_heads": 32, "linear_key_head_dim": 128,
              "linear_value_head_dim": 128, "num_attention_heads": 16,
              "head_dim": 256, "hidden_size": 2048,
              "moe_intermediate_size": 512, "NeuralNetwork": {
                  "Architecture": {"compute_dtype": "bfloat16"}}}
    lm = counts.lm_facts(config, [100, 300], 2)
    assert lm["gdn"] == {"layers": 3, "chunk": 64, "key_heads": 16,
                         "value_heads": 32, "key_dim": 128, "value_dim": 128,
                         "operand_bytes": 2, "tokens_per_step": 200.0}
    # and the keys trace_lm.py reads of a grouped-query cell
    assert lm["attention"] == {"full_attention": {
        "pairs_per_step": (100 * 101 + 300 * 301) / 4, "heads_summed": 16}}
    assert (lm["head_dim"], lm["hidden_size"],
            lm["moe_intermediate_size"]) == (256, 2048, 512)
    # 420 real chunks a step over the three layers: the bytes bind
    least, bound = counts.rule_least_seconds(lm, 420.0, 197e12, 819e9)
    assert bound == "memory"
    assert least == pytest.approx(
        420 * 64 * counts.rule_bytes_per_node(16, 32, 128, 128, 2) / 819e9)
    # the share is least time over measured time: whichever bound is
    # larger, a time at or above it reads at most 100
    assert least >= 420 * counts.rule_flops_per_chunk(
        64, 16, 32, 128, 128) / 197e12


def test_comparison_groups_cover_every_parameter_once():
    group_of = load("drivers", "train_epochs_gdn").group_of
    kinds = ["linear_attention", "full_attention"]
    rule = {leaf: group_of(f"layer_0/mixer/{leaf}", kinds) for leaf in (
        "norm", "w_qkvz", "w_ba", "conv_w", "A_log", "dt_bias", "gate_norm",
        "w_out")}
    assert rule == {"norm": "layer_0.w_in", "w_qkvz": "layer_0.w_in",
                    "w_ba": "layer_0.w_in", "conv_w": "layer_0.conv",
                    "A_log": "layer_0.decay", "dt_bias": "layer_0.decay",
                    "gate_norm": "layer_0.w_out", "w_out": "layer_0.w_out"}
    attn = {leaf: group_of(f"layer_1/mixer/{leaf}", kinds) for leaf in (
        "norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")}
    assert attn == dict.fromkeys(("norm", "wq", "wk", "wv", "wo"),
                                 "layer_1.attn") | {
        "q_norm": "layer_1.qk_norm", "k_norm": "layer_1.qk_norm"}
    moe = {leaf: group_of(f"layer_1/moe/{leaf}", kinds) for leaf in (
        "norm", "router", "experts_w1", "experts_w3", "experts_w2",
        "shared_w1", "shared_w3", "shared_w2", "shared_gate")}
    assert moe == {"norm": "layer_1.router", "router": "layer_1.router",
                   "experts_w1": "layer_1.experts",
                   "experts_w3": "layer_1.experts",
                   "experts_w2": "layer_1.experts",
                   "shared_w1": "layer_1.shared",
                   "shared_w3": "layer_1.shared",
                   "shared_w2": "layer_1.shared",
                   "shared_gate": "layer_1.shared"}
    assert [group_of(p, kinds) for p in ("embed", "final_norm", "head")] == [
        "embed", "head", "head"]


def test_traffic_file_states_the_issues_traffic(config):
    with open(os.path.join(BENCH, "traffic", "packed4k_d6.json")) as f:
        traffic = json.load(f)
    assert traffic["driver"] == "train_epochs_gdn"
    assert traffic["env"]["HYDRAGNN_RESIDENT_DATASET"] == "1"
    # the epoch's train steps are ONE dispatch
    n_train = int(config["corpus"]["n"] * 0.8)
    batch = config["NeuralNetwork"]["Training"]["batch_size"]
    assert int(traffic["env"]["HYDRAGNN_STEPS_PER_DISPATCH"]) == -(
        -n_train // batch) >= 3
    assert batch == 6 and "6 documents a step" in traffic["why"]
    assert traffic["driver_params"] == {"loader_seed": 0, "batch_scale": 1}
    assert config["corpus"]["params"]["layout_seed"] == 0
    assert config["NeuralNetwork"]["Training"]["Optimizer"] == {
        "type": "AdamW", "learning_rate": 1e-06}
    assert config["NeuralNetwork"]["Training"]["perc_train"] == 0.8
    assert traffic["expect"]["moe_dense_steps"] == 0
    assert traffic["expect"]["pipeline"] == {
        "resident": True, "use_mesh_dp": False, "dp_extent": 1}


def test_dry_cpu_cell_end_to_end():
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 44), "--seconds", "2", "--trace", "1",
         "--dry-cpu"], cwd=REPO, env=cpu_env(), capture_output=True,
        text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    # counts only on the CPU: the cell's program counters
    assert set(line["metrics"]) == {"pad_edges_waste_pct",
                                    "gdn_pad_chunk_pct"}
    assert 0 <= line["metrics"]["gdn_pad_chunk_pct"]["value"] < 60
    assert "parity highest" in r.stdout and "parity as_shipped" in r.stdout
    # the comparison is built AFTER the window, as the other language-model
    # drivers build theirs: set-up holds the trainer's builds alone
    out = r.stdout
    assert (out.index("parity: weights made and both programs traced")
            > out.index("memory_stats[0]"))
    assert "gdn: chunks / of them padding / resets" in r.stdout
    assert "x 3 DeltaNet layers" in r.stdout
    assert "CHECK FAILED" not in r.stdout
