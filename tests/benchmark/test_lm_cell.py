"""The language-model cell's benchmark files: every per-layer reader this
cell brought returns None (and raises nothing) over a program that lacks
its spans, counters and trace, as the parent of the PR that added them
does; the cell was added by new files and appended entries alone; the
corpus generator keeps the layout apart from the seed; the counts the MXU
shares divide by are what the mathematics needs."""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchload import BENCH, REPO, cpu_env, load

CELL = "laguna_s_2_1-packed8k"
NEW_READERS = ("pad_nodes_waste_pct", "attn_core_ms", "attn_core_mxu_pct",
               "moe_routed_ms", "moe_gmm_mxu_pct", "moe_load_max_over_mean",
               "lm_head_ms")
# sha256[:16] of every benchmark file as the cell's PR found it
FILES_BEFORE = {
    "benchmark/configs/schnet_qm9.json": "14011d19fc7af3e8",
    "benchmark/corpora/qm9_shaped.py": "a638171510789c19",
    "benchmark/drivers/train_epochs.py": "50af84cb1dae70e1",
    "benchmark/layer_metrics/collective_exposed_pct.py": "a5cd465a426195a7",
    "benchmark/layer_metrics/device_idle_pct.py": "51a24519025162ff",
    "benchmark/layer_metrics/dispatch_host_ms.py": "e8321834cdf14254",
    "benchmark/layer_metrics/epoch_tail_ms.py": "50c21e304709d520",
    "benchmark/layer_metrics/eval_share_pct.py": "4b9e2b816483ad93",
    "benchmark/layer_metrics/gather_mul_seg_bwd_ms.py": "f810bde5bd67af78",
    "benchmark/layer_metrics/gather_mul_seg_fwd_ms.py": "07f983269e50bea0",
    "benchmark/layer_metrics/hbm_live_peak_gb.py": "632a0d05f0c63d16",
    "benchmark/layer_metrics/hbm_peak_gb.py": "e36d2ba2b34103d6",
    "benchmark/layer_metrics/loader_wait_pct.py": "6a946968e62f0d9e",
    "benchmark/layer_metrics/mosaic_busy_pct.py": "285778766e548d54",
    "benchmark/layer_metrics/pad_edges_waste_pct.py": "70b51bcd7881911b",
    "benchmark/layer_metrics/setup_collate_s.py": "2a91546fe96efa12",
    "benchmark/layer_metrics/setup_epoch0_s.py": "2600af74f5dcbac1",
    "benchmark/layer_metrics/setup_mfu_cost_s.py": "381c9bd62105789a",
    "benchmark/layer_metrics/step_bwd_ms.py": "3977592741416557",
    "benchmark/layer_metrics/step_device_ms.py": "997f95e3a45d2af8",
    "benchmark/layer_metrics/step_fwd_ms.py": "d426a87f70788e0c",
    "benchmark/layer_metrics/step_named_pct.py": "be5e399ef8088dac",
    "benchmark/layer_metrics/step_opt_ms.py": "0f65420ccedc6603",
    "benchmark/peaks.py": "541cd680d4811e95",
    "benchmark/run.py": "766ceea451b0dca3",
    "benchmark/trace_reduce.py": "a250de61a9ab9541",
    "benchmark/trace_scopes.py": "43e910d9aa3ba75b",
    "benchmark/traffic/dp4.json": "f198a9692996ca51",
    "benchmark/traffic/hostfed.json": "e092de852e795a0e",
    "benchmark/traffic/resident.json": "52b9a3ce5265f878",
    "tests/benchmark/benchload.py": "953a5f78bc25932c",
    "tests/benchmark/test_add_by_file.py": "01845b9d1b4f7703",
    "tests/benchmark/test_corpus.py": "dda32ec1121a7fc2",
    "tests/benchmark/test_driver_matches_run_training.py": "fe4990d651350306",
    "tests/benchmark/test_epoch_rate.py": "da6975423611541d",
    "tests/benchmark/test_harness_contract.py": "3bafa7f34bea6c2b",
    "tests/benchmark/test_parked_hostfed.py": "d3ae07191cd555cb",
    "tests/benchmark/test_trace_reduce.py": "37ee3c9e42a1931b",
    "tests/benchmark/test_trace_scopes.py": "f15f8304272016d9"
}
# ... and of BENCHMARK.json's content as it was, canonically dumped
BENCHMARK_BEFORE = "fcbb6e8d87892f23"


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


STOCK_EPOCH = {"epoch": 1, "t0": 0.0, "t1": 1.0, "graphs": 10, "steps": 2,
               "skipped": 0, "nonfinite": 0, "edges_real": 5,
               "edges_padded": 8}


@pytest.mark.parametrize("facts", [
    {},
    {"epochs": [], "spans": [], "trace": None},
    # what the stock driver hands over from a program without the scopes:
    # epochs without node and routing sums, a trace summary, no trace file
    {"epochs": [dict(STOCK_EPOCH)], "spans": [("train", 0.0, 1.0)],
     "trace": {"step_device_s": 0.01, "busy_s": 1.0, "mosaic_s": 0.5},
     "trace_dir": "/nonexistent", "trace_window": (0.0, 1.0),
     "mono_to_unix_ns": 0.0, "train_module_regex": "jit_"},
    {"epochs": [dict(STOCK_EPOCH)], "lm": None, "trace": {}},
], ids=["empty", "no_trace", "stock_driver_untraced_scopes", "lm_none"])
@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_gives_none_where_its_source_is_absent(name, facts):
    assert load("layer_metrics", name).read(dict(facts)) is None


def test_new_readers_import_nothing_of_the_program():
    for name in NEW_READERS + ("../trace_lm", "../lm_counts"):  # noqa
        with open(os.path.join(BENCH, "layer_metrics", name + ".py")) as f:
            assert "hydragnn_tpu" not in f.read().replace(
                "``hydragnn_tpu``", ""), name
    with open(os.path.join(BENCH, "reference",
                           "laguna_reference.py")) as f:
        text = f.read()
    assert "import hydragnn" not in text and "from hydragnn" not in text


def test_the_cell_came_as_new_files_and_appended_entries(bench):
    for rel, digest in FILES_BEFORE.items():
        with open(os.path.join(REPO, rel), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest()[:16] == digest, rel
    # undo what the PR appended; what is left is what was there, but for
    # the one edit a cell-adding PR may make: a ``workloads`` list on an
    # entry the new cell cannot report
    before = json.loads(json.dumps(bench))
    assert before["configs"].pop()["name"] == "laguna_s_2_1"
    assert before["workloads"].pop()["name"] == CELL
    for name in reversed(NEW_READERS):
        m = before["per_layer"].pop()
        assert m["name"] == name and m["workloads"] == [CELL]
    for m in before["per_layer"]:
        if m["name"] == "setup_mfu_cost_s":     # this stack leaves the
            assert m.pop("workloads") == [      # in-run estimate out
                "schnet_qm9-resident", "schnet_qm9-dp4"]
    assert hashlib.sha256(json.dumps(before, sort_keys=True).encode()
                          ).hexdigest()[:16] == BENCHMARK_BEFORE


def test_config_file_holds_the_catalog_numbers_but_the_reduced(bench):
    entry = bench["configs"][-1]
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    published = {
        "hidden_size": 3072, "intermediate_size": 12288, "head_dim": 128,
        "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "sliding_window": 512,
        "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-06,
        "max_position_embeddings": 1048576, "decoder_sparse_step": 1,
        "moe_router_logit_softcapping": 0}
    for key, want in published.items():
        assert config[key] == want and key not in entry["reduced"], key
    cut = {"num_hidden_layers": (5, 48), "num_experts": (8, 256),
           "num_key_value_heads": (1, 8), "num_attention_heads": (6, 48),
           "vocab_size": (12544, 100352)}
    for key, (here, _published) in cut.items():
        assert config[key] == here and key in entry["reduced"], key
    share = config["share"]
    assert (share["num_experts_total"], share["kv_heads_total"],
            share["vocab_total"], share["chips_per_layer"]) == (
                256, 8, 100352, 32)
    assert config["layer_types"] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert config["num_attention_heads_per_layer"] == [6, 9, 9, 9, 6]
    assert config["rope_parameters"]["full_attention"]["factor"] == 128
    assert len(config["Provenance"]["assumed"]) >= 5
    for width in ("hidden_size", "head_dim", "intermediate_size"):
        assert width not in entry["reduced"]


def test_corpus_layout_is_fixed_and_ids_are_seeded():
    gen = load("corpora", "packed_docs")
    params = {"median_tokens": 64, "sigma": 1.2, "min_tokens": 16,
              "max_tokens": 512, "zipf_a": 1.1, "markov_mix": 0.5,
              "layout_seed": 0, "vocab_size": 200}
    a = gen.generate(300, 1, params)
    b = gen.generate(300, 2 ** 31 + 5, params)
    assert np.array_equal(a["n_tokens"], b["n_tokens"])
    assert not np.array_equal(a["ids"], b["ids"])
    assert np.array_equal(a["ids"], gen.generate(300, 1, params)["ids"])
    n = a["n_tokens"]
    assert n.min() >= 16 and n.max() <= 512 and (n == 512).any()
    assert 50 < np.median(n) < 80 and a["ids"].max() < 200
    assert not np.array_equal(
        n, gen.generate(300, 1, dict(params, layout_seed=1))["n_tokens"])
    # the Markov half: far more than chance of the bigrams repeat
    ids = a["ids"]
    pairs = set(zip(ids[:-1].tolist(), ids[1:].tolist()))
    assert len(pairs) < 0.8 * (len(ids) - 1)
    samples = gen.to_samples(a, {"vocab_size": 200})
    s = samples[3]
    assert s.x.shape == (n[3], 1) and s.node_y.shape == (n[3], 2)
    assert s.num_edges == 0 and s.node_y[-1, 1] == -1.0
    assert np.array_equal(s.node_y[:-1, 1], s.x[1:, 0])
    with pytest.raises(ValueError, match="outside the held slice"):
        gen.to_samples(a, {"vocab_size": 100})


def test_counts_are_the_mathematics():
    counts = load("", "lm_counts")
    assert counts.visible_pairs(5) == 15
    assert counts.visible_pairs(5, 8) == 15
    # 20 tokens, window 8: rows of 1..8 then twelve rows of 8
    assert counts.visible_pairs(20, 8) == 36 + 12 * 8
    brute = sum(1 for i in range(700) for j in range(700)
                if 0 <= i - j < 512)
    assert counts.visible_pairs(700, 512) == brute
    assert counts.attention_core_flops(10, 3, 128) == 10 * 3 * 6 * 256
    assert counts.grouped_ffn_flops(2, 3072, 1024) == 2 * 9 * 2 * 3072 * 1024
    config = {"sliding_window": 8, "num_hidden_layers": 3, "head_dim": 16,
              "hidden_size": 32, "moe_intermediate_size": 16,
              "layer_types": ["full_attention", "sliding_attention",
                              "full_attention", "sliding_attention"],
              "num_attention_heads_per_layer": [2, 3, 2, 3]}
    facts = counts.lm_facts(config, [5, 20], 2)
    assert facts["attention"]["full_attention"] == {
        "pairs_per_step": (15 + 210) / 2, "heads_summed": 4}
    assert facts["attention"]["sliding_attention"] == {
        "pairs_per_step": (15 + 132) / 2, "heads_summed": 3}


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
    # counts only on the CPU: the cell's program counters.  A batch of
    # documents has edge slots (the pad spec's floor) and no edge
    assert set(line["metrics"]) == {
        "pad_nodes_waste_pct", "moe_load_max_over_mean",
        "pad_edges_waste_pct"}
    assert line["metrics"]["pad_edges_waste_pct"]["value"] == 100.0
    assert 0 < line["metrics"]["pad_nodes_waste_pct"]["value"] < 100
    assert "parity highest" in r.stdout and "parity as_shipped" in r.stdout
