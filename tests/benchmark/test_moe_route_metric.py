"""``moe_route_ms`` (PR 38): the router's own scope read alone, where the
accepted ``moe_routed_ms`` and ``moe_latent_ms`` read it with the experts.
On a hand-made reduction it reads ``moe.route`` and nothing beside it; over
a program without the scope, an untraced run and the other cells' facts it
gives None and raises nothing; it imports nothing of the program; and,
anchored BY NAME and tolerant of whatever a later PR appends behind it:
the one entry, the parent's ``BENCHMARK.json`` byte for byte once it is
taken out, and the files PR 37 brought, as this PR found them (the older
ones: test_ssm_cell.py)."""

import hashlib
import json
import os

import pytest

from benchload import BENCH, REPO, load

NAME = "moe_route_ms"
ENTRY = {
    "name": NAME, "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "routed experts of one rank (ops/moe.py)",
    "moves": "train_graphs_per_s",
    "workloads": ["laguna_s_2_1-packed8k", "glm_4_7_flash-packed4k",
                  "nemotron_3_super-packed4k_d4"]}
# how many entries the parent's lists held, and sha256[:16] of its file
PARENT_COUNTS = {"configs": 4, "workloads": 5, "end_to_end": 2,
                 "per_layer": 44}
BENCHMARK_BEFORE = "19c92edf5e3a5f5c"
# sha256[:16] of the benchmark files PR 37 added, as this PR (38) found them
FILES_BEFORE = {
    "benchmark/configs/nemotron_3_super.json": "4c75a64b78e55d97",
    "benchmark/drivers/train_epochs_ssm.py": "70bded3eaca7da1b",
    "benchmark/layer_metrics/moe_latent_ms.py": "00ecc2c6dedec14d",
    "benchmark/layer_metrics/moe_shared_ms.py": "0fb413ed43fa7229",
    "benchmark/layer_metrics/ssm_ms.py": "b6221e22c51d69cc",
    "benchmark/layer_metrics/ssm_pad_chunk_pct.py": "0a68bc96a484ce3d",
    "benchmark/layer_metrics/ssm_scan_ms.py": "f080c0e41cb4799b",
    "benchmark/layer_metrics/ssm_scan_roofline_pct.py": "593dc77567a19656",
    "benchmark/reference/nemotron_h_reference.py": "684e688ccf753416",
    "benchmark/ssm_counts.py": "45240cf2fc172153",
    "benchmark/traffic/packed4k_d4.json": "84b746b1a73d1dc1",
    "tests/benchmark/test_ssm_cell.py": "ef2e7270a509c784",
}

STEP = "jit(scan_step)/while/body/closed_call/step.loss/"
LAYER = "jvp(NemotronHStack)/layers_0_9/while/body/checkpoint/unit_0/"


def _reduced(ops):
    """Facts as ``trace_scopes.load`` leaves them: ``ops`` (scope, seconds of
    self time) in a step of 10 s of self time that takes 0.1 s."""
    return {"_trace_scopes": {
        "resolved": True, "devices": 1, "step": {"total": 10.0},
        "step_device_s": 0.1,
        "ops": [(f"fusion.{i}", (t, scope, "ops/moe.py:1"))
                for i, (scope, t) in enumerate(ops)]}}


def test_reads_the_routers_scope_and_nothing_beside_it():
    facts = _reduced([
        (STEP + LAYER + "moe.route/dot_general", 1.5),
        (STEP + "transpose(" + LAYER[:-1] + ")/moe.route/select_n", 0.5),
        (STEP + LAYER + "moe.experts/moe.gmm/gmm", 3.0),
        (STEP + LAYER + "moe.latent/dot_general", 2.0),
        (STEP + LAYER + "moe.router/dot_general", 1.0),   # no such scope
        (None, 2.0)])
    # 2.0 of 10 s of self time in a step of 100 ms
    assert load("layer_metrics", NAME).read(dict(facts)) == pytest.approx(20.0)
    assert load("layer_metrics", "moe_routed_ms").read(dict(facts)) == (
        pytest.approx(50.0))
    assert load("layer_metrics", "moe_latent_ms").read(dict(facts)) == (
        pytest.approx(70.0))


@pytest.mark.parametrize("facts", [
    {},
    {"epochs": [], "spans": [], "trace": None},
    {"epochs": [], "spans": [("train", 0.0, 1.0)],
     "trace": {"step_device_s": 0.01, "busy_s": 1.0, "mosaic_s": 0.5},
     "trace_dir": "/nonexistent", "trace_window": (0.0, 1.0),
     "mono_to_unix_ns": 0.0, "train_module_regex": "jit_"},
    # a program that routes nowhere (the SchNet cells)
    _reduced([("jit(scan_step)/while/body/step.loss/jvp(SCFStack)/"
               "encoder_conv_1/gather_mul_seg_fwd", 4.0)]),
    # a trace whose scope file did not join
    {"_trace_scopes": dict(_reduced([(STEP + "moe.route/top_k", 1.0)])[
        "_trace_scopes"], resolved=False)},
], ids=["empty", "no_trace", "untraced_scopes", "no_router", "unresolved"])
def test_gives_none_where_its_source_is_absent(facts):
    assert load("layer_metrics", NAME).read(dict(facts)) is None


def test_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "layer_metrics", NAME + ".py")) as f:
        text = f.read()
    assert "import hydragnn" not in text and "from hydragnn" not in text


def test_files_that_were_there_are_as_this_pr_found_them():
    for rel, digest in FILES_BEFORE.items():
        with open(os.path.join(REPO, rel), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest()[:16] == digest, rel


def test_the_metric_came_as_one_appended_entry_found_by_name():
    """By NAME, wherever later PRs' entries come to stand behind it; and
    the parent's lists, this one taken out, dump to the parent's file byte
    for byte.  A later ``benchmark`` PR that edits an accepted entry
    anchors this anew."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    assert names.count(NAME) == 1
    assert names.index(NAME) >= PARENT_COUNTS["per_layer"]
    assert bench["per_layer"][names.index(NAME)] == ENTRY
    cells = {w["name"] for w in bench["workloads"]}
    assert set(ENTRY["workloads"]) <= cells
    # the layer's name is the accepted one, letter for letter
    assert ENTRY["layer"] == next(
        m["layer"] for m in bench["per_layer"] if m["name"] == "moe_routed_ms")
    before = dict(bench)
    for key, n in PARENT_COUNTS.items():
        before[key] = before[key][:n]
    assert hashlib.sha256(json.dumps(before, indent=1).encode()
                          ).hexdigest()[:16] == BENCHMARK_BEFORE
