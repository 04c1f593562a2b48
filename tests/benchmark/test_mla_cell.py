"""The latent-attention cell's benchmark files: the cell rehearsed on the
CPU through ``run.py``; every per-layer reader this cell brought returns
None (and raises nothing) over a program that lacks its scopes and
counters, as the parent of the PR that added them does, over an untraced
run and over the stock driver's facts; readers, counts and the reference
copy import nothing of the program; the configuration file holds the
catalog's numbers but the ``reduced``; the counts are the mathematics; and,
anchored BY NAME and not by position: every benchmark file that existed
before this cell's PR hashes as that PR found it, and ``BENCHMARK.json``
less this cell's named entries is the parent's."""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchload import BENCH, REPO, cpu_env, load

CELL = "glm_4_7_flash-packed4k"
CONFIG = "glm_4_7_flash"
NEW_READERS = ("mla_core_ms", "mla_core_mxu_pct", "mla_latent_ms", "mtp_ms",
               "moe_held_share_pct", "moe_all_load_max_over_mean")
# sha256[:16] of every benchmark file as the cell's PR (32) found it
FILES_BEFORE = {
    "benchmark/configs/laguna_s_2_1.json": "7a7b513341c60bd8",
    "benchmark/configs/schnet_qm9.json": "14011d19fc7af3e8",
    "benchmark/corpora/packed_docs.py": "eeac2de1ab301b26",
    "benchmark/corpora/qm9_shaped.py": "a638171510789c19",
    "benchmark/drivers/train_epochs.py": "50af84cb1dae70e1",
    "benchmark/drivers/train_epochs_lm.py": "f1db0d8958c1c84a",
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
    "benchmark/layer_metrics/lm_head_ms.py": "cf1f845ee2aeed2b",
    "benchmark/layer_metrics/loader_wait_pct.py": "6a946968e62f0d9e",
    "benchmark/layer_metrics/moe_gmm_mxu_pct.py": "5f7137fa9c3aaf65",
    "benchmark/layer_metrics/moe_load_max_over_mean.py": "9e812df08385f7e4",
    "benchmark/layer_metrics/moe_routed_ms.py": "94fba2f97d6be43b",
    "benchmark/layer_metrics/mosaic_busy_pct.py": "285778766e548d54",
    "benchmark/layer_metrics/pad_edges_waste_pct.py": "70b51bcd7881911b",
    "benchmark/layer_metrics/pad_nodes_waste_pct.py": "8662d3fabf2d2b6b",
    "benchmark/layer_metrics/setup_collate_s.py": "2a91546fe96efa12",
    "benchmark/layer_metrics/setup_epoch0_s.py": "2600af74f5dcbac1",
    "benchmark/layer_metrics/setup_mfu_cost_s.py": "381c9bd62105789a",
    "benchmark/layer_metrics/step_bwd_ms.py": "3977592741416557",
    "benchmark/layer_metrics/step_device_ms.py": "997f95e3a45d2af8",
    "benchmark/layer_metrics/step_fwd_ms.py": "d426a87f70788e0c",
    "benchmark/layer_metrics/step_named_pct.py": "be5e399ef8088dac",
    "benchmark/layer_metrics/step_opt_ms.py": "0f65420ccedc6603",
    "benchmark/lm_counts.py": "4e5a1aece547ea2d",
    "benchmark/peaks.py": "541cd680d4811e95",
    "benchmark/reference/laguna_reference.py": "a419d905e38b933f",
    "benchmark/run.py": "766ceea451b0dca3",
    "benchmark/trace_lm.py": "d3561b2bed50e6e0",
    "benchmark/trace_reduce.py": "a250de61a9ab9541",
    "benchmark/trace_scopes.py": "43e910d9aa3ba75b",
    "benchmark/traffic/dp4.json": "f198a9692996ca51",
    "benchmark/traffic/hostfed.json": "e092de852e795a0e",
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
    "tests/benchmark/test_parked_hostfed.py": "d3ae07191cd555cb",
    "tests/benchmark/test_trace_reduce.py": "37ee3c9e42a1931b",
    "tests/benchmark/test_trace_scopes.py": "f15f8304272016d9"
}
# ... and of BENCHMARK.json's content as it was, canonically dumped
BENCHMARK_BEFORE = "c8c20d9f0c5c267b"
# the catalog's ``config`` of row GLM-4.7-Flash
# (/opt/skills/guides/model-configs/architectures.jsonl), number by number
CATALOG = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
    "vocab_size": 154880}
HELD = {"num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 19360}


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
# what the language-model driver sums over a program that has no bias
LM_EPOCH = dict(STOCK_EPOCH, nodes_real=9, nodes_padded=12,
                moe_slots_held=0, moe_slots_all=0, moe_dense_steps=0,
                moe_load_max_over_mean=None)


@pytest.mark.parametrize("facts", [
    {},
    {"epochs": [], "spans": [], "trace": None},
    # the stock driver over a program without the scopes: epochs without
    # routing sums, a trace summary, no trace file
    {"epochs": [dict(STOCK_EPOCH)], "spans": [("train", 0.0, 1.0)],
     "trace": {"step_device_s": 0.01, "busy_s": 1.0, "mosaic_s": 0.5},
     "trace_dir": "/nonexistent", "trace_window": (0.0, 1.0),
     "mono_to_unix_ns": 0.0, "train_module_regex": "jit_"},
    {"epochs": [dict(LM_EPOCH)], "lm": None, "trace": {}},
    # the grouped-query cell's facts: its ``lm`` block has no ``mla`` key
    {"epochs": [dict(LM_EPOCH)], "trace": None,
     "lm": {"attention": {}, "head_dim": 128, "hidden_size": 3072,
            "moe_intermediate_size": 1024}},
], ids=["empty", "no_trace", "stock_driver_untraced_scopes", "lm_none",
        "grouped_query_cell_untraced"])
@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_gives_none_where_its_source_is_absent(name, facts):
    assert load("layer_metrics", name).read(dict(facts)) is None


def test_counter_readers_read_what_the_driver_sums():
    epochs = [dict(LM_EPOCH, moe_slots_held=10, moe_slots_all=80,
                   moe_load_all_max_over_mean=2.0),
              dict(LM_EPOCH, moe_slots_held=14, moe_slots_all=80,
                   moe_load_all_max_over_mean=3.0)]
    assert load("layer_metrics", "moe_held_share_pct").read(
        {"epochs": epochs}) == pytest.approx(15.0)
    assert load("layer_metrics", "moe_all_load_max_over_mean").read(
        {"epochs": epochs}) == pytest.approx(2.5)


def test_new_files_import_nothing_of_the_program():
    for rel in [f"layer_metrics/{n}.py" for n in NEW_READERS] + [
            "mla_counts.py", "reference/glm_moe_lite_reference.py"]:
        with open(os.path.join(BENCH, rel)) as f:
            text = f.read()
        assert "import hydragnn" not in text, rel
        assert "from hydragnn" not in text, rel


def test_files_that_were_there_are_as_this_pr_found_them():
    for rel, digest in FILES_BEFORE.items():
        with open(os.path.join(REPO, rel), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest()[:16] == digest, rel


def test_benchmark_json_less_this_cells_named_entries_is_the_parents(bench):
    """By NAME: whatever later PRs append behind them, taking out this
    cell's configuration, cell and six per-layer entries leaves, in the
    parent's order, what the parent had (later PRs' own entries are theirs
    to take out first: ``LATER``)."""
    LATER = {"configs": (), "workloads": (), "per_layer": ()}
    mine = {"configs": (CONFIG,), "workloads": (CELL,),
            "per_layer": NEW_READERS}
    before = json.loads(json.dumps(bench))
    for key in mine:
        names = [e["name"] for e in before[key]]
        for name in mine[key]:
            assert names.count(name) == 1, (key, name)
        # appended: behind every entry the parent had
        first = min(names.index(n) for n in mine[key])
        assert all(n in mine[key] or n in LATER[key]
                   for n in names[first:]), key
        before[key] = [e for e in before[key]
                       if e["name"] not in mine[key] + tuple(LATER[key])]
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL], m["name"]
            assert m["moves"] == "train_graphs_per_s"
    assert hashlib.sha256(json.dumps(before, sort_keys=True).encode()
                          ).hexdigest()[:16] == BENCHMARK_BEFORE


def test_config_file_holds_the_catalog_numbers_but_the_reduced(bench, config):
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == ("https://huggingface.co/zai-org/"
                               "GLM-4.7-Flash/blob/main/config.json")
    assert sorted(entry["reduced"]) == sorted(list(HELD) + ["corpus"])
    for key, want in CATALOG.items():
        if key in HELD:
            assert config[key] == HELD[key] and key in entry["reduced"], key
        else:
            assert config[key] == want and key not in entry["reduced"], key
    share = config["share"]
    assert (share["num_experts_total"], share["vocab_total"],
            share["num_hidden_layers_total"], share["chips_per_layer"],
            share["expert_parallel_ranks"]) == (64, 154880, 47, 8, 8)
    assert share["vocab_total"] == 8 * config["vocab_size"]
    for word in ("8 chips", "8 expert-parallel ranks", "data-parallel",
                 "19,360"):
        assert word in config["Provenance"]["deployment"], word
    assert len(config["Provenance"]["assumed"]) >= 6
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["task_weights"] == [1.0, 0.3]
    assert arch["model_type"] == "GlmMoeLite"
    assert config["corpus"]["generator"] == "packed_docs_mtp"
    assert config["corpus"]["params"] | {} == {
        "median_tokens": 1024, "sigma": 1.0, "min_tokens": 64,
        "max_tokens": 4096, "zipf_a": 1.1, "markov_mix": 0.5,
        "layout_seed": 0}


def test_parameter_count_is_the_issues_arithmetic(config):
    """The program's own count at the published widths, from shapes alone
    (``jax.eval_shape``: nothing is allocated), within 1 % of 706.5 M."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.graph.batch import (
        GraphSample, HeadSpec, PadSpec, collate)
    from hydragnn_tpu.models.base import ModelConfig
    from hydragnn_tpu.models.create import create_model

    skip = load("drivers", "train_epochs_mla")._lm._HF_SKIP
    arch = dict(config["NeuralNetwork"]["Architecture"],
                glm_moe_lite={k: v for k, v in config.items()
                              if k not in skip},
                share=config["share"], input_dim=1, output_dim=[1, 1],
                output_type=["node", "node"], max_graph_nodes=16)
    cfg = ModelConfig.from_config({
        "Architecture": arch, "Training": config["NeuralNetwork"]["Training"]})
    ids = np.arange(16, dtype=np.float32)[:, None]
    batch = collate([GraphSample(x=ids, pos=np.zeros((16, 3)),
                                 node_y=np.zeros((16, 2), np.float32))],
                    PadSpec(24, 8, 2), [HeadSpec("a", "node", 1),
                                        HeadSpec("b", "node", 1)])
    shapes = jax.eval_shape(
        lambda b: create_model(cfg).init(
            {"params": jax.random.PRNGKey(0)}, b, train=False),
        jax.tree.map(jnp.asarray, batch))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree.leaves(shapes["params"]))
    assert abs(count - 706.5e6) < 0.01 * 706.5e6
    by_top = {}
    for path, a in jax.tree_util.tree_leaves_with_path(shapes["params"]):
        by_top[path[0].key] = by_top.get(path[0].key, 0) + int(
            np.prod(a.shape))
    assert by_top["embed"] == by_top["head"] == 19360 * 2048
    assert abs(by_top["layer_0"] - 84.67e6) < 0.01e6       # MLA + dense FFN
    assert abs(by_top["layer_1"] - 106.82e6) < 0.01e6      # MLA + experts
    assert abs(by_top["mtp"] - 115.22e6) < 0.01e6          # + eh_proj
    assert sorted(k for k in shapes["batch_stats"] if k.startswith("bias_")
                  ) == ["bias_layer_1", "bias_layer_2", "bias_layer_3",
                        "bias_layer_4", "bias_mtp"]
    assert shapes["batch_stats"]["bias_mtp"].shape == (64,)


def test_second_label_column_comes_from_the_plug_in_alone():
    gen, docs = load("corpora", "packed_docs_mtp"), load(
        "corpora", "packed_docs")
    params = {"median_tokens": 64, "sigma": 1.0, "min_tokens": 2,
              "max_tokens": 256, "zipf_a": 1.1, "markov_mix": 0.5,
              "layout_seed": 0, "vocab_size": 200}
    a = gen.generate(50, 2 ** 31 + 5, params)
    b = docs.generate(50, 2 ** 31 + 5, params)
    assert all(np.array_equal(a[k], b[k]) for k in b)
    for s, plain in zip(gen.to_samples(a, {"vocab_size": 200}),
                        docs.to_samples(b, {"vocab_size": 200})):
        assert np.array_equal(s.x, plain.x)
        assert np.array_equal(s.node_y[:, :2], plain.node_y)
        assert s.node_y.shape == (s.num_nodes, 3)
        assert np.array_equal(s.node_y[:-2, 2], s.x[2:, 0])
        assert (s.node_y[-2:, 2] == -1.0).all()


def test_counts_are_the_mathematics():
    counts = load("", "mla_counts")
    assert counts.visible_pairs(5) == 15
    assert counts.visible_pairs(700) == sum(
        1 for i in range(700) for j in range(700) if 0 <= i - j)
    # per visible pair and head: q.k, p.v forward; dq, dk, dp, dv backward
    assert counts.attention_core_flops(10, 20, 256, 256) == (
        10 * 20 * (2 * 256 + 2 * 256 + 2 * 2 * 256 + 2 * 2 * 256))
    config = {"num_attention_heads": 20, "qk_nope_head_dim": 192,
              "qk_rope_head_dim": 64, "v_head_dim": 256,
              "num_hidden_layers": 5, "num_nextn_predict_layers": 1}
    facts = counts.lm_facts(config, [5, 20], 2)
    assert facts == {"mla": {"pairs_per_step": (15 + 210) / 2, "heads": 20,
                             "qk_dim": 256, "v_dim": 256, "layers": 6}}
    assert counts.mla_core_flops_per_step(facts) == (
        6 * 112.5 * 20 * 3 * 2 * 512)


def test_comparison_groups_cover_every_parameter_once():
    group_of = load("drivers", "train_epochs_mla").group_of
    got = {p: group_of(p) for p in (
        "embed", "head", "final_norm", "layer_0/attn/wdq",
        "layer_0/attn/q_norm", "layer_0/attn/norm", "layer_0/attn/wuq",
        "layer_0/attn/wukv", "layer_0/attn/wo", "layer_0/ffn/w1",
        "layer_3/moe/router", "layer_3/moe/norm", "layer_3/moe/experts_w2",
        "layer_3/moe/shared_w1", "mtp/eh_proj", "mtp/enorm",
        "mtp/final_norm", "mtp/layer/attn/wdkv", "mtp/layer/attn/wo",
        "mtp/layer/moe/router", "mtp/layer/moe/experts_w1")}
    assert got == {
        "embed": "embed", "head": "head", "final_norm": "head",
        "layer_0/attn/wdq": "layer_0.mla_down",
        "layer_0/attn/q_norm": "layer_0.mla_down",
        "layer_0/attn/norm": "layer_0.mla_down",
        "layer_0/attn/wuq": "layer_0.mla_up",
        "layer_0/attn/wukv": "layer_0.mla_up",
        "layer_0/attn/wo": "layer_0.mla_out", "layer_0/ffn/w1": "layer_0.ffn",
        "layer_3/moe/router": "layer_3.router",
        "layer_3/moe/norm": "layer_3.router",
        "layer_3/moe/experts_w2": "layer_3.experts",
        "layer_3/moe/shared_w1": "layer_3.shared",
        "mtp/eh_proj": "mtp.eh_proj", "mtp/enorm": "mtp.eh_proj",
        "mtp/final_norm": "mtp.eh_proj",
        "mtp/layer/attn/wdkv": "mtp.mla_down",
        "mtp/layer/attn/wo": "mtp.mla_out",
        "mtp/layer/moe/router": "mtp.router",
        "mtp/layer/moe/experts_w1": "mtp.experts"}


def test_dry_cpu_cell_end_to_end():
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 11), "--seconds", "2", "--trace", "1",
         "--dry-cpu"], cwd=REPO, env=cpu_env(), capture_output=True,
        text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    # counts only on the CPU: the cell's program counters
    assert set(line["metrics"]) == {
        "pad_edges_waste_pct", "moe_held_share_pct",
        "moe_all_load_max_over_mean"}
    assert 0 < line["metrics"]["moe_held_share_pct"]["value"] < 100
    assert line["metrics"]["moe_all_load_max_over_mean"]["value"] >= 1.0
    assert "parity highest" in r.stdout and "parity as_shipped" in r.stdout
    assert "the bias's step by layer" in r.stdout
    assert "CHECK FAILED" not in r.stdout
