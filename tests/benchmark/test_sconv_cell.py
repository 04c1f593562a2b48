"""The short-convolution cell's benchmark files (PR 40): the cell rehearsed
on the CPU through ``run.py`` (a correct line with its counter); every
per-layer reader this cell brought returns None (and raises nothing) over a
program that lacks its scopes and counters, over an untraced run and over
the other cells' facts; readers, counts and the reference copy import
nothing of the program; the configuration file holds the catalog's numbers
but the ``reduced``; the counts are the mathematics and the roofline share
cannot pass 100 by construction of its two bounds; the comparison's groups
cover every parameter once; and, anchored BY NAME and tolerant of whatever a
later PR appends behind them: this cell's configuration, cell and four
per-layer entries, the parent's ``BENCHMARK.json`` byte for byte once they
are taken out, and every benchmark file that existed before, as this PR
found it."""

import filecmp
import hashlib
import json
import os
import subprocess
import sys

import pytest

from benchload import BENCH, REPO, cpu_env, load

CELL = "lfm2_24b_a2b-packed4k_d12"
CONFIG = "lfm2_24b_a2b"
SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
LAYER = "short convolution over each graph's nodes (ops/sconv.py)"
# name -> (unit, better, source), in the order they were appended
NEW = {
    "sconv_ms": ("ms", "lower", "device_trace"),
    "sconv_core_ms": ("ms", "lower", "device_trace"),
    "sconv_core_roofline_pct": ("%", "higher", "device_trace"),
    "sconv_taps_cut_pct": ("%", "lower", "program_counter"),
}
# how many entries the parent's lists held, and sha256[:16] of its file
PARENT_COUNTS = {"configs": 4, "workloads": 5, "end_to_end": 2,
                 "per_layer": 45}
BENCHMARK_BEFORE = "37a59d1151ed9952"
# sha256[:16] of every benchmark file as this PR (40) found it
FILES_BEFORE = {
    "benchmark/configs/glm_4_7_flash.json": "ea5999293f564e34",
    "benchmark/configs/laguna_s_2_1.json": "7a7b513341c60bd8",
    "benchmark/configs/nemotron_3_super.json": "4c75a64b78e55d97",
    "benchmark/configs/schnet_qm9.json": "14011d19fc7af3e8",
    "benchmark/corpora/packed_docs.py": "eeac2de1ab301b26",
    "benchmark/corpora/packed_docs_mtp.py": "30683a379e221d00",
    "benchmark/corpora/qm9_shaped.py": "a638171510789c19",
    "benchmark/drivers/train_epochs.py": "50af84cb1dae70e1",
    "benchmark/drivers/train_epochs_lm.py": "f1db0d8958c1c84a",
    "benchmark/drivers/train_epochs_mla.py": "44bef20a42f4dde9",
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
    "benchmark/reference/nemotron_h_reference.py": "684e688ccf753416",
    "benchmark/run.py": "766ceea451b0dca3",
    "benchmark/ssm_counts.py": "45240cf2fc172153",
    "benchmark/trace_lm.py": "d3561b2bed50e6e0",
    "benchmark/trace_reduce.py": "a250de61a9ab9541",
    "benchmark/trace_scopes.py": "43e910d9aa3ba75b",
    "benchmark/traffic/dp4.json": "f198a9692996ca51",
    "benchmark/traffic/hostfed.json": "e092de852e795a0e",
    "benchmark/traffic/packed4k.json": "7f03da3c4a4d5a4a",
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
    "tests/benchmark/test_ssm_cell.py": "ef2e7270a509c784",
    "tests/benchmark/test_trace_reduce.py": "37ee3c9e42a1931b",
    "tests/benchmark/test_trace_scopes.py": "f15f8304272016d9",
}
# the catalog's ``config`` of row LFM2-24B-A2B
# (/opt/skills/guides/model-configs/architectures.jsonl), key by key
CATALOG = {
    "conv_L_cache": 3,
    "conv_bias": False,
    "hidden_size": 2048,
    "intermediate_size": 11776,
    # attention at 2, 6, ..., 38
    "layer_types": ["conv", "conv", "full_attention", "conv"] * 10,
    "max_position_embeddings": 128000,
    "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536,
    "norm_eps": 1e-05,
    "norm_topk_prob": True,
    "num_attention_heads": 32,
    "num_dense_layers": 2,
    "num_experts": 64,
    "num_experts_per_tok": 4,
    "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {
        "rope_theta": 1000000,
        "rope_type": "default"
    },
    "routed_scaling_factor": 1,
    "use_expert_bias": True,
    "vocab_size": 65536
}
HELD = {"num_hidden_layers": 5,
        "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
        "num_dense_layers": 1, "num_experts": 8, "vocab_size": 8192}


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
# what the language-model driver sums over a program without short
# convolutions, and the other three language-model cells' ``lm`` blocks
LM_EPOCH = dict(STOCK_EPOCH, nodes_real=9, nodes_padded=12,
                moe_slots_held=3, moe_slots_all=40, moe_dense_steps=0,
                moe_load_max_over_mean=1.5)
OWN_LM = {"sconv": {"layers": 4, "channels": 2048, "taps": 3,
                    "operand_bytes": 2, "tokens_per_step": 17000.0}}


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
    {"epochs": [dict(LM_EPOCH, moe_load_all_max_over_mean=2.0)],
     "trace": None,
     "lm": {"mla": {"pairs_per_step": 10.0, "heads": 20, "qk_dim": 256,
                    "v_dim": 256, "layers": 6}}},
    {"epochs": [dict(LM_EPOCH, ssm_chunks=568.0, ssm_chunks_padding=236.0,
                     ssm_resets=30.0)], "trace": None,
     "lm": {"ssm": {"layers": 5, "chunk": 128, "heads": 16, "head_dim": 64,
                    "groups": 1, "state": 128, "operand_bytes": 2,
                    "tokens_per_step": 5000.0}}},
    # this cell's own facts over the PARENT's program (no sconv block in
    # its step records, no sconv scope in its trace)
    {"epochs": [dict(LM_EPOCH, sconv_rows=None, sconv_starts=None,
                     sconv_taps_cut=None)], "trace": None, "lm": OWN_LM},
], ids=["empty", "no_trace", "stock_driver_untraced_scopes", "lm_none",
        "grouped_query_cell", "latent_attention_cell", "state_space_cell",
        "own_cell_parent_program"])
@pytest.mark.parametrize("name", list(NEW))
def test_new_reader_gives_none_where_its_source_is_absent(name, facts):
    assert load("layer_metrics", name).read(dict(facts)) is None


def test_the_counter_reads_what_the_driver_sums():
    epochs = [dict(LM_EPOCH, sconv_rows=272000.0, sconv_taps_cut=576.0),
              dict(LM_EPOCH, sconv_rows=272000.0, sconv_taps_cut=576.0)]
    assert load("layer_metrics", "sconv_taps_cut_pct").read(
        {"epochs": epochs, "lm": OWN_LM}) == pytest.approx(
            100.0 * 1152 / (3 * 544000))
    # an untraced run of the cell reads the counter and nothing else
    for name in ("sconv_ms", "sconv_core_ms", "sconv_core_roofline_pct"):
        assert load("layer_metrics", name).read(
            {"epochs": epochs, "lm": OWN_LM, "trace": None}) is None


def test_new_files_import_nothing_of_the_program():
    for rel in [f"layer_metrics/{n}.py" for n in NEW] + [
            "sconv_counts.py", "reference/lfm2_moe_reference.py"]:
        with open(os.path.join(BENCH, rel)) as f:
            text = f.read()
        assert "import hydragnn" not in text, rel
        assert "from hydragnn" not in text, rel


def test_reference_copy_is_byte_identical():
    assert filecmp.cmp(
        os.path.join(BENCH, "reference", "lfm2_moe_reference.py"),
        os.path.join(REPO, "hydragnn_tpu", "models",
                     "lfm2_moe_reference.py"), shallow=False)


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
            "layer": LAYER, "moves": "train_graphs_per_s",
            "workloads": [CELL]}, name
    assert sorted(NEW, key=names.index) == list(NEW)
    for key, mine in (("configs", CONFIG), ("workloads", CELL)):
        listed = [e["name"] for e in bench[key]]
        assert listed.count(mine) == 1
        assert listed.index(mine) >= PARENT_COUNTS[key]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config=CONFIG, traffic="packed4k_d12", chips=1)
    # one line of <= 200 characters that says both loads
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert "per held expert" in cell["why"] and "8x" in cell["why"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    for text in (entry["source"], entry["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert len(entry["reduced"]) <= 16
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
    # the held layers are the published list's entries 1-5: one leading
    # dense conv layer and one whole period
    assert CATALOG["layer_types"][1:6] == HELD["layer_types"]
    assert len(CATALOG["layer_types"]) == 40
    assert CATALOG["layer_types"].count("full_attention") == 10
    share = config["share"]
    assert (share["chips_per_layer"], share["expert_parallel_ranks"],
            share["num_experts_total"], share["vocab_total"],
            share["num_hidden_layers_total"], share["first_layer"]) == (
                8, 8, 64, 65536, 40, 1)
    assert share["vocab_total"] == 8 * config["vocab_size"]
    assert share["num_experts_total"] == 8 * config["num_experts"]
    for word in ("8 chips", "8 expert-parallel ranks", "8-way", "8,192",
                 "35 layers", "no shared expert"):
        assert word in config["Provenance"]["deployment"], word
    assert len(config["Provenance"]["assumed"]) >= 7
    assert any("tie_word_embeddings" in a
               for a in config["Provenance"]["assumed"])
    # every reduced key is explained in the file
    told = " ".join(config["Provenance"]["reduced"])
    assert all(key in told for key in entry["reduced"])
    # the dtypes the gates and the convolution read and write are stated
    assert "bfloat16" in config["Provenance"]["precision"]
    assert "sconv_counts.py" in config["Provenance"]["precision"]
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["model_type"] == "Lfm2Moe"
    assert arch["compute_dtype"] == "bfloat16"
    assert config["corpus"]["generator"] == "packed_docs"
    # GLM's length law: the cells differ by model and rows, not by the law
    glm = next(c for c in bench["configs"] if c["name"] == "glm_4_7_flash")
    with open(os.path.join(REPO, glm["file"])) as f:
        assert config["corpus"]["params"] == json.load(f)["corpus"]["params"]
    # the rehearsal keeps one dense conv, one attention and one conv
    # expert layer
    dry = config["dry_cpu"]
    assert dry["layer_types"] == ["conv", "full_attention", "conv"]
    assert dry["num_dense_layers"] == 1 and dry["num_hidden_layers"] == 3


def test_parameter_count_is_the_programs_own(config):
    """The program's own count at the published widths, from shapes alone
    (``jax.eval_shape``: nothing is allocated): 469.3 M, the issue's
    arithmetic, and the file says the same number."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hydragnn_tpu.graph.batch import (
        GraphSample, HeadSpec, PadSpec, collate)
    from hydragnn_tpu.models.base import ModelConfig
    from hydragnn_tpu.models.create import create_model

    skip = load("drivers", "train_epochs_sconv")._lm._HF_SKIP
    arch = dict(config["NeuralNetwork"]["Architecture"],
                lfm2_moe={k: v for k, v in config.items() if k not in skip},
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
    count = sum(int(np.prod(a.shape))
                for a in jax.tree.leaves(shapes["params"]))
    assert count == 469_284_992
    assert "469,284,992" in config["Provenance"]["parameters_here"]
    p = shapes["params"]
    # five unrolled layers, ONE table and no head matrix
    assert set(p) == {"embed", "layer_0", "layer_1", "layer_2", "layer_3",
                      "layer_4", "final_norm"}
    assert p["embed"].shape == (8192, 2048)
    assert p["layer_0"]["op"]["w_in"].shape == (2048, 6144)
    assert p["layer_0"]["op"]["conv_w"].shape == (3, 2048)
    assert p["layer_0"]["op"]["w_out"].shape == (2048, 2048)
    assert p["layer_0"]["ffn"]["w1"].shape == (2048, 11776)
    assert p["layer_1"]["op"]["wq"].shape == (2048, 32 * 64)
    assert p["layer_1"]["op"]["wk"].shape == (2048, 8 * 64)
    assert p["layer_1"]["op"]["q_norm"].shape == (64,)
    for layer in ("layer_1", "layer_2", "layer_3", "layer_4"):
        assert p[layer]["moe"]["experts_w1"].shape == (8, 2048, 1536)
        assert p[layer]["moe"]["router"].shape == (2048, 64)
        assert not any(k.startswith("shared") for k in p[layer]["moe"])
    assert "conv_w" in p["layer_4"]["op"]
    assert sorted(k for k in shapes["batch_stats"] if k.startswith("bias_")
                  ) == [f"bias_layer_{i}" for i in (1, 2, 3, 4)]
    assert shapes["batch_stats"]["bias_layer_4"].shape == (64,)


def test_counts_are_the_mathematics():
    counts = load("", "sconv_counts")
    # a hand-sized case: one row of 4 channels at three taps in bfloat16.
    # Forward: B, C, X read and y written = 4 x 4 x 2 B = 32; backward:
    # those three and dy read, three gradients written = 7 x 4 x 2 = 56
    assert counts.core_bytes_per_row(4, 2) == 32 + 56
    assert counts.core_bytes_per_row(2048, 4) == 2048 * 4 * 11
    # forward: 1 + (3 products + 2 sums) + 1 = 7 a channel; backward twice
    assert counts.core_flops_per_row(4, 3) == 3 * 4 * 7
    config = {"layer_types": ["conv", "full_attention", "conv", "conv",
                              "conv"], "num_hidden_layers": 5,
              "hidden_size": 2048, "conv_L_cache": 3, "NeuralNetwork": {
                  "Architecture": {"compute_dtype": "bfloat16"}}}
    lm = counts.lm_facts(config, [100, 300], 2)
    assert lm == {"sconv": {"layers": 4, "channels": 2048, "taps": 3,
                            "operand_bytes": 2, "tokens_per_step": 200.0}}
    # 68,000 rows a step over the four layers: the bytes bind
    least, bound = counts.core_least_seconds(lm, 68000.0, 197e12, 819e9)
    assert bound == "memory"
    assert least == pytest.approx(68000 * 2048 * 2 * 11 / 819e9)
    # the share is least time over measured time: whichever bound is
    # larger, a time at or above it reads at most 100
    assert least >= 68000 * counts.core_flops_per_row(2048, 3) / 197e12


def test_comparison_groups_cover_every_parameter_once():
    group_of = load("drivers", "train_epochs_sconv").group_of
    kinds = ["conv", "full_attention", "conv"]
    conv = {leaf: group_of(f"layer_0/op/{leaf}", kinds)
            for leaf in ("norm", "w_in", "conv_w", "w_out")}
    assert conv == {"norm": "layer_0.w_in", "w_in": "layer_0.w_in",
                    "conv_w": "layer_0.conv", "w_out": "layer_0.w_out"}
    attn = {leaf: group_of(f"layer_1/op/{leaf}", kinds) for leaf in (
        "norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")}
    assert attn == dict.fromkeys(("norm", "wq", "wk", "wv", "wo"),
                                 "layer_1.attn") | {
        "q_norm": "layer_1.qk_norm", "k_norm": "layer_1.qk_norm"}
    assert {group_of(f"layer_0/ffn/{leaf}", kinds) for leaf in (
        "norm", "w1", "w3", "w2")} == {"layer_0.ffn"}
    moe = {leaf: group_of(f"layer_2/moe/{leaf}", kinds) for leaf in (
        "norm", "router", "experts_w1", "experts_w3", "experts_w2")}
    assert moe == {"norm": "layer_2.router", "router": "layer_2.router",
                   "experts_w1": "layer_2.experts",
                   "experts_w3": "layer_2.experts",
                   "experts_w2": "layer_2.experts"}
    assert [group_of(p, kinds) for p in ("embed", "final_norm")] == [
        "table", "table"]


def test_traffic_file_states_the_issues_traffic(config):
    with open(os.path.join(BENCH, "traffic", "packed4k_d12.json")) as f:
        traffic = json.load(f)
    assert traffic["driver"] == "train_epochs_sconv"
    assert traffic["env"]["HYDRAGNN_RESIDENT_DATASET"] == "1"
    # the epoch's train steps are ONE dispatch
    n_train = int(config["corpus"]["n"] * 0.8)
    batch = config["NeuralNetwork"]["Training"]["batch_size"]
    assert int(traffic["env"]["HYDRAGNN_STEPS_PER_DISPATCH"]) == -(
        -n_train // batch) >= 3
    assert batch == 12 and "12 documents a step" in traffic["why"]
    assert config["NeuralNetwork"]["Training"]["Optimizer"] == {
        "type": "AdamW", "learning_rate": 1e-06}
    assert config["NeuralNetwork"]["Training"]["perc_train"] == 0.8
    assert traffic["expect"]["moe_dense_steps"] == 0
    assert traffic["expect"]["pipeline"] == {
        "resident": True, "use_mesh_dp": False, "dp_extent": 1}


def test_dry_cpu_cell_end_to_end():
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 40), "--seconds", "2", "--trace", "1",
         "--dry-cpu"], cwd=REPO, env=cpu_env(), capture_output=True,
        text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    # counts only on the CPU: the cell's program counters
    assert set(line["metrics"]) == {"pad_edges_waste_pct",
                                    "sconv_taps_cut_pct"}
    # three taps cut a document and a layer: 100 / the mean length
    assert 2 < line["metrics"]["sconv_taps_cut_pct"]["value"] < 25
    assert "parity highest" in r.stdout and "parity as_shipped" in r.stdout
    assert "the bias's step by layer" in r.stdout
    # the comparison is built AFTER the window, as the other language-model
    # drivers build theirs: set-up holds the trainer's builds alone
    out = r.stdout
    assert (out.index("parity: weights made and both programs traced")
            > out.index("memory_stats[0]"))
    assert "sconv: rows / starts / taps cut" in r.stdout
    assert "CHECK FAILED" not in r.stdout
