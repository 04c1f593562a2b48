"""The build and memory records' benchmark files (PR 35): each of the six
readers over hand-written records; None, and no exception, over facts
without them (the parent's program writes none) in the four fact shapes
``test_lm_cell.py`` uses; the two memory readers None where the backend
reports no memory; nothing of the program imported; and, anchored BY NAME
and tolerant of whatever a later PR appends behind them: the six entries of
``BENCHMARK.json``, the parent's ``BENCHMARK.json`` byte for byte once they
are taken out, and every benchmark file that existed before, as this PR
found it."""

import hashlib
import json
import os

import pytest

from benchload import BENCH, REPO, load

SETUP = "set-up (create_dataloaders, create_train_state, trainer epoch 0)"
# name -> (unit, source, layer, moves), in the order they were appended
NEW = {
    "setup_trace_lower_s": ("s", "program_span", SETUP, "setup_s"),
    "setup_compile_s": ("s", "program_span", SETUP, "setup_s"),
    "setup_cache_load_s": ("s", "program_span", SETUP, "setup_s"),
    "setup_programs_built": ("programs", "program_span", SETUP, "setup_s"),
    "hbm_step_temp_gb": ("GB", "program_counter", "device memory",
                         "train_graphs_per_s"),
    "hbm_step_need_gb": ("GB", "program_counter", "device memory",
                         "train_graphs_per_s"),
}
# how many entries the parent's lists held, and sha256[:16] of its file
PARENT_COUNTS = {"configs": 3, "workloads": 4, "end_to_end": 2,
                 "per_layer": 32}
BENCHMARK_BEFORE = "7798bac5c2480845"
# sha256[:16] of every benchmark file as this PR (35) found it
FILES_BEFORE = {
    "benchmark/configs/glm_4_7_flash.json": "ea5999293f564e34",
    "benchmark/configs/laguna_s_2_1.json": "7a7b513341c60bd8",
    "benchmark/configs/schnet_qm9.json": "14011d19fc7af3e8",
    "benchmark/corpora/packed_docs.py": "eeac2de1ab301b26",
    "benchmark/corpora/packed_docs_mtp.py": "30683a379e221d00",
    "benchmark/corpora/qm9_shaped.py": "a638171510789c19",
    "benchmark/drivers/train_epochs.py": "50af84cb1dae70e1",
    "benchmark/drivers/train_epochs_lm.py": "f1db0d8958c1c84a",
    "benchmark/drivers/train_epochs_mla.py": "44bef20a42f4dde9",
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
    "benchmark/layer_metrics/mla_core_ms.py": "8710d2dde99ae6d4",
    "benchmark/layer_metrics/mla_core_mxu_pct.py": "5c9aec1fe8e2c8f6",
    "benchmark/layer_metrics/mla_latent_ms.py": "7333f23fd5b47ba6",
    "benchmark/layer_metrics/moe_all_load_max_over_mean.py": "b4012624dc23c69a",
    "benchmark/layer_metrics/moe_gmm_mxu_pct.py": "5f7137fa9c3aaf65",
    "benchmark/layer_metrics/moe_held_share_pct.py": "34d6c869f130040c",
    "benchmark/layer_metrics/moe_load_max_over_mean.py": "9e812df08385f7e4",
    "benchmark/layer_metrics/moe_routed_ms.py": "94fba2f97d6be43b",
    "benchmark/layer_metrics/mosaic_busy_pct.py": "285778766e548d54",
    "benchmark/layer_metrics/mtp_ms.py": "459e5708b151dbf2",
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
    "benchmark/mla_counts.py": "b038e2ddbc7b8b2e",
    "benchmark/peaks.py": "541cd680d4811e95",
    "benchmark/reference/glm_moe_lite_reference.py": "6121a5f09373895e",
    "benchmark/reference/laguna_reference.py": "a419d905e38b933f",
    "benchmark/run.py": "766ceea451b0dca3",
    "benchmark/trace_lm.py": "d3561b2bed50e6e0",
    "benchmark/trace_reduce.py": "a250de61a9ab9541",
    "benchmark/trace_scopes.py": "43e910d9aa3ba75b",
    "benchmark/traffic/dp4.json": "f198a9692996ca51",
    "benchmark/traffic/hostfed.json": "e092de852e795a0e",
    "benchmark/traffic/packed4k.json": "7f03da3c4a4d5a4a",
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
    "tests/benchmark/test_parked_hostfed.py": "d3ae07191cd555cb",
    "tests/benchmark/test_trace_reduce.py": "37ee3c9e42a1931b",
    "tests/benchmark/test_trace_scopes.py": "f15f8304272016d9",
}

T_OPEN = 100.0                      # the window opens, on time.monotonic
TO_UNIX_NS = 1.7e18                 # ... and what brings it to unix time
UNIX_OPEN = T_OPEN + TO_UNIX_NS * 1e-9
GB = 10 ** 9


def _program(name, t, *, trace_s=0.0, lower_s=0.0, build_s=0.0,
             cache="hit", cache_load_s=0.0, region=None, epoch=None):
    return {"event": "program", "seq": 0, "name": name,
            "t_start": t - build_s - trace_s - lower_s, "t": t,
            "trace_s": trace_s, "lower_s": lower_s, "build_s": build_s,
            "cache": cache, "cache_load_s": cache_load_s, "region": region,
            "epoch": epoch, "step": None}


def _memory(name, *, argument, output, alias, temp, code):
    return {"event": "program_memory", "name": name,
            "argument_bytes": argument, "output_bytes": output,
            "alias_bytes": alias, "temp_bytes": temp,
            "generated_code_bytes": code, "peak_bytes": argument + temp}


RECORDS = [
    {"event": "run_start", "t": UNIX_OPEN - 50},
    # before the logger: an eager op and the jitted init, read from the cache
    _program("convert_element_type", UNIX_OPEN - 60, trace_s=0.01,
             lower_s=0.02, build_s=0.05, cache_load_s=0.04),
    _program("init", UNIX_OPEN - 55, trace_s=1.0, lower_s=0.5, build_s=0.8,
             cache_load_s=0.7, region="setup.init_state"),
    # epoch 0: the train step missed the cache, the eval step was not asked
    _program("scan_step", UNIX_OPEN - 20, trace_s=4.0, lower_s=2.0,
             build_s=30.0, cache="miss", region="train.dispatch", epoch=0),
    _program("eval_step", UNIX_OPEN - 10, trace_s=1.0, lower_s=0.5,
             build_s=5.0, cache="off", region="eval.dispatch", epoch=0),
    {"event": "step", "t": UNIX_OPEN - 5, "epoch": 0},
    # after the job: the driver's comparison, not set-up
    _program("reference", UNIX_OPEN + 40, trace_s=9.0, lower_s=9.0,
             build_s=99.0, cache="miss"),
    _memory("jit_scan_step", argument=8 * GB, output=8 * GB + 1000,
            alias=8 * GB, temp=2 * GB, code=10 ** 6),
    # the same program at a larger bucket: this is the one that binds
    _memory("jit_scan_step", argument=8 * GB, output=8 * GB + 1000,
            alias=8 * GB, temp=6 * GB, code=2 * 10 ** 6),
    _memory("jit_eval_step", argument=3 * GB, output=100, alias=0,
            temp=7 * GB, code=10 ** 6),
]
WANT = {
    "setup_trace_lower_s": 0.03 + 1.5 + 6.0 + 1.5,
    "setup_compile_s": 30.0 + 5.0,
    "setup_cache_load_s": 0.04 + 0.7,
    "setup_programs_built": 4,
    "hbm_step_temp_gb": 6.0,
    "hbm_step_need_gb": (8 * GB + 1000 + 6 * GB + 2 * 10 ** 6) / GB,
}


@pytest.fixture
def facts(tmp_path):
    """What a driver hands over from a traced run whose program wrote
    RECORDS: the trace directory beside ``logs``, the window's opening on
    the monotonic clock and what brings it to unix time."""
    tel = tmp_path / "logs" / "run" / "telemetry"
    tel.mkdir(parents=True)
    with open(tel / "events.jsonl", "w") as f:
        for rec in RECORDS:
            f.write(json.dumps(rec) + "\n")
    return {"epochs": [{"epoch": 1, "t0": T_OPEN, "t1": T_OPEN + 5.0}],
            "spans": [("train", T_OPEN - 30.0, T_OPEN - 12.0)],
            "trace_dir": str(tmp_path / "trace"),
            "mono_to_unix_ns": TO_UNIX_NS, "memory_peak_bytes": 9 * GB,
            "train_module_regex": r"jit_(scan_step|train_step|multi)\b"}


@pytest.mark.parametrize("name", list(NEW))
def test_reader_over_hand_written_records(name, facts):
    assert load("layer_metrics", name).read(facts) == pytest.approx(
        WANT[name])


@pytest.mark.parametrize("name", ["hbm_step_temp_gb", "hbm_step_need_gb"])
def test_memory_readers_give_nothing_where_the_backend_reports_none(
        name, facts):
    """The CPU: ``memory_peak_bytes`` is None, and a CPU rehearsal's line
    (which reads every ``program_counter`` metric) must not grow."""
    assert load("layer_metrics", name).read(
        dict(facts, memory_peak_bytes=None)) is None


STOCK_EPOCH = {"epoch": 1, "t0": 0.0, "t1": 1.0, "graphs": 10, "steps": 2,
               "skipped": 0, "nonfinite": 0, "edges_real": 5,
               "edges_padded": 8}


@pytest.mark.parametrize("absent", [
    {},
    {"epochs": [], "spans": [], "trace": None},
    # what the stock driver hands over from a program without the records
    {"epochs": [dict(STOCK_EPOCH)], "spans": [("train", 0.0, 1.0)],
     "trace": {"step_device_s": 0.01, "busy_s": 1.0, "mosaic_s": 0.5},
     "trace_dir": "/nonexistent", "trace_window": (0.0, 1.0),
     "mono_to_unix_ns": 0.0, "train_module_regex": "jit_",
     "memory_peak_bytes": GB},
    {"epochs": [dict(STOCK_EPOCH)], "lm": None, "trace": {}},
], ids=["empty", "no_trace", "stock_driver_untraced_scopes", "lm_none"])
@pytest.mark.parametrize("name", list(NEW))
def test_new_reader_gives_none_where_its_source_is_absent(name, absent):
    assert load("layer_metrics", name).read(dict(absent)) is None


def test_a_run_whose_program_writes_other_events_only_reads_as_none(facts):
    """The parent's program under this PR's benchmark files: an
    ``events.jsonl`` with step and epoch records and no ``program``."""
    tel = os.path.join(os.path.dirname(facts["trace_dir"]), "logs", "run",
                       "telemetry", "events.jsonl")
    with open(tel, "w") as f:
        f.write(json.dumps({"event": "step", "t": 1.0, "epoch": 0}) + "\n")
    for name in NEW:
        assert load("layer_metrics", name).read(dict(facts)) is None, name


def test_new_files_import_nothing_of_the_program():
    for rel in [f"layer_metrics/{n}.py" for n in NEW] + [
            "program_records.py"]:
        with open(os.path.join(BENCH, rel)) as f:
            text = f.read()
        assert "import hydragnn" not in text, rel
        assert "from hydragnn" not in text, rel
        assert "import jax" not in text, rel


def test_files_that_were_there_are_as_this_pr_found_them():
    for rel, digest in FILES_BEFORE.items():
        with open(os.path.join(REPO, rel), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest()[:16] == digest, rel


def test_the_six_entries_are_found_by_name_and_the_rest_is_the_parents():
    """By NAME, wherever later PRs' entries come to stand behind them: each
    of the six is there once, behind every entry the parent had, with no
    ``workloads`` list (every cell's program writes the records); and the
    parent's lists, these six taken out, dump to the parent's file byte for
    byte.  (What ``test_mla_cell.py``'s
    ``test_benchmark_json_less_this_cells_named_entries_is_the_parents``
    guarded until this PR appended behind GLM's entries.)  A later
    ``benchmark`` PR that edits an accepted entry anchors this anew."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    for name, (unit, source, layer, moves) in NEW.items():
        assert names.count(name) == 1, name
        assert names.index(name) >= PARENT_COUNTS["per_layer"], name
        assert bench["per_layer"][names.index(name)] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": moves}, name
    assert sorted(NEW, key=names.index) == list(NEW)
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    assert {SETUP, "device memory"} <= layers
    before = dict(bench)
    before["per_layer"] = [m for m in bench["per_layer"]
                           if m["name"] not in NEW]
    for key, n in PARENT_COUNTS.items():
        before[key] = before[key][:n]
    assert hashlib.sha256(json.dumps(before, indent=1).encode()
                          ).hexdigest()[:16] == BENCHMARK_BEFORE
