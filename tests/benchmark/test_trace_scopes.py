"""The scope reduction on hand-made events: every number below is worked
out by hand in the comment beside it.  Then the readers over facts as the
PARENT of PR 23 gives them (no scope file, no new region): nothing is
reported and nothing raises."""

import json

import pytest

from benchload import load

load("", "trace_reduce")        # as run.py has, before any reader runs
ts = load("", "trace_scopes")

FWD = "jit(scan_step)/while/body/closed_call/step.loss/jvp(SCFStack)"
BWD = "jit(scan_step)/while/body/closed_call/step.loss/transpose(jvp(SCFStack))"


@pytest.mark.parametrize("scope, want", [
    (FWD + "/encoder_conv_3/filter_0/dot_general",
     ("fwd", "encoder_conv_3/filter_0/dot_general")),
    (BWD + "/encoder_conv_3/lin2/reduce_sum",
     ("bwd", "encoder_conv_3/lin2/reduce_sum")),
    # a kernel's name is the last module-like part
    (BWD + "/encoder_conv_1/gather_mul_seg_bwd",
     ("bwd", "encoder_conv_1/gather_mul_seg_bwd")),
    ("jit(scan_step)/while/body/closed_call/step.optimizer/mul",
     ("optimizer", "mul")),
    ("step.metrics/reduce_sum", ("metrics", "reduce_sum")),
    ("jit(multi)/while/body/shard_map/comm.dp_psum/psum",
     ("comm.dp_psum", "psum")),
    ("jit(eval_step)/step.eval/SCFStack/encoder_conv_0/lin1/dot_general",
     ("eval", "SCFStack/encoder_conv_0/lin1")),
    # XLA joined the names of ops it merged: the first scope counts and
    # the path stops where the next begins
    (FWD + "/encoder_conv_3/jit(searchsorted)/step.loss/jvp(SCFStack)"
     "/encoder_conv_2/jit(searchsorted)/gather",
     ("fwd", "encoder_conv_3/jit(searchsorted)")),
    ("jit(scan_step)/while/body/dynamic_slice", (None, "")),
    ("my_step.lossy/thing", (None, "")),      # not a declared scope
    ("", (None, "")), (None, (None, "")),
])
def test_classify(scope, want):
    assert ts.classify(scope) == want


def test_kernel_of():
    assert ts.kernel_of("gather_mul_seg_fwd.63 custom-call f32[14976,128]",
                        "custom-call") == "gather_mul_seg_fwd"
    assert ts.kernel_of("scf_bwd_p custom-call f32[8,128]",
                        "custom-call") == "scf_bwd_p"
    # XLA's own custom calls, and ops that are none
    assert ts.kernel_of("custom-call.192 custom-call s32[15360,1]",
                        "custom-call") is None
    assert ts.kernel_of("fusion.892 fusion f32[128]", "fusion") is None


def test_pick_executable_by_name_and_shape():
    small = {"fusion.1": ["f32[100,128]", FWD + "/a", 0, ""],
             "fusion.2": ["f32[128]", FWD + "/b", 0, ""]}
    large = {"fusion.1": ["f32[400,128]", BWD + "/c", 0, ""],
             "fusion.2": ["f32[128]", BWD + "/d", 0, ""]}
    # fusion.2 fits both buckets' executables; fusion.1's shape decides
    seen = {"fusion.1": "f32[400,128]", "fusion.2": "f32[128]"}
    assert ts.pick_executable(seen, [small, large]) is large
    assert ts.pick_executable({"fusion.9": "f32[1]"}, [small, large]) is None
    assert ts.pick_executable(seen, []) is None


ROWS = [
    # self time, scope, inherited, kernel
    (30.0, FWD + "/encoder_conv_0/filter_0/dot_general", 0, None),
    (10.0, FWD + "/encoder_conv_0/gather_mul_seg_fwd", 0,
     "gather_mul_seg_fwd"),
    (40.0, BWD + "/encoder_conv_0/filter_0/dot_general", 0, None),
    (8.0, BWD + "/encoder_conv_0/gather_mul_seg_bwd", 0,
     "gather_mul_seg_bwd"),
    (2.0, BWD + "/encoder_conv_0/gather_mul_seg_bwd", 1, None),  # a copy
    (4.0, "jit(scan_step)/while/body/closed_call/step.optimizer/mul", 0,
     None),
    (1.0, "jit(scan_step)/while/body/closed_call/step.metrics/reduce_sum",
     0, None),
    (5.0, "jit(scan_step)/while/body/dynamic_slice", 0, None),  # no phase
    (0.0, None, 0, None),               # a container: no self time
    (-1.0, None, 0, None),              # clock jitter: dropped
]


def test_split_step_shares_sum_to_the_step():
    s = ts.split_step(ROWS)
    assert s["total"] == 100.0
    assert s["phase"] == {"fwd": 40.0, "bwd": 50.0, "optimizer": 4.0,
                          "metrics": 1.0, "unnamed": 5.0}
    assert sum(s["phase"].values()) == s["total"]
    assert s["unnamed"] == 5.0 and s["inherited"] == 2.0
    assert s["kernel"] == {"gather_mul_seg_fwd": 10.0,
                           "gather_mul_seg_bwd": 8.0}
    assert s["scope"][("bwd", "encoder_conv_0/gather_mul_seg_bwd")] == 10.0


OPS = [
    # label, opcode, start, end; one scan_step execution (0, 100) whose
    # while (10, 90) encloses two steps' ops, then an eval program
    ("while.5 while (s32[],f32[8])", "while", 10, 90),
    ("fusion.1 fusion f32[400,128]", "fusion", 10, 40),
    ("gather_mul_seg_fwd.3 custom-call f32[64,128]", "custom-call", 40, 50),
    ("fusion.2 fusion f32[128]", "fusion", 50, 85),
    ("copy.7 copy f32[8]", "copy", 92, 96),
    ("fusion.1 fusion f32[400,128]", "fusion", 110, 130),   # eval's own
]
MODULES = [("jit_scan_step(77)", 0, 100), ("jit_eval_step(5)", 105, 135)]
PROGRAMS = {
    "jit_scan_step": [
        {"fusion.1": ["f32[100,128]", BWD + "/wrong_bucket", 0, ""]},
        {"while.5": ["", "jit(scan_step)/while", 0, ""],
         "fusion.1": ["f32[400,128]", FWD + "/encoder_conv_0/lin1/dot", 0,
                      "models/schnet.py:117"],
         "gather_mul_seg_fwd.3": [
             "f32[64,128]", FWD + "/encoder_conv_0/gather_mul_seg_fwd", 0,
             "ops/fused_mp.py:171"],
         "fusion.2": ["f32[128]", BWD + "/encoder_conv_0/lin1/dot", 0, ""]},
    ],
    "jit_eval_step": [
        {"fusion.1": ["f32[400,128]",
                      "jit(eval_step)/step.eval/SCFStack/lin1/dot", 0,
                      ""]}],
}


def test_reduce_device_by_hand():
    d = ts.reduce_device(OPS, MODULES, PROGRAMS, 0, 140,
                         r"jit_(scan_step|multi)\b")
    assert d["resolved"]
    step = ts.split_step(d["rows"])
    # while.5's self time: 80 - (30 + 10 + 35) = 5, its scope names no
    # phase; copy.7 (4) is in no executable map: unnamed too
    assert step["phase"] == {"fwd": 40.0, "bwd": 35.0, "unnamed": 9.0}
    assert step["total"] == 84.0
    assert step["kernel"] == {"gather_mul_seg_fwd": 10.0}
    # the fusion takes the scope its instruction has in the executable
    # whose SHAPES match (the second), not the first of that name
    assert d["ops"]["fusion.1 fusion f32[400,128]"] == (
        30.0, FWD + "/encoder_conv_0/lin1/dot", "models/schnet.py:117")
    # the eval program's fusion.1 is another instruction of that name: it
    # is under step.eval, and in no train-step row
    assert d["eval_self"] == 20.0
    # a window that cuts the eval program leaves its ops out
    d = ts.reduce_device(OPS, MODULES, PROGRAMS, 0, 120,
                         r"jit_(scan_step|multi)\b")
    assert d["eval_self"] == 0.0
    # no scope file: rows without scope, nothing resolved
    d = ts.reduce_device(OPS, MODULES, {}, 0, 140, r"jit_scan_step\b")
    assert not d["resolved"]
    assert ts.split_step(d["rows"])["unnamed"] == 84.0


def test_gap_takes_the_innermost_region():
    regions = [("train", 0, 40), ("train.dispatch", 30, 39),
               ("metrics_fetch", 50, 80), ("epoch.fetch", 51, 60),
               ("telemetry.flush", 60, 79), ("epoch.tail", 80, 90)]
    # metrics_fetch covers all of (55, 78) but its own time there is 0:
    # epoch.fetch has 5 of it, telemetry.flush 18
    assert ts.label_gap_by_region((55, 78), regions) == (
        "telemetry.flush", 18)
    # (35, 52): train.dispatch 4, train itself 1 (39..40), metrics_fetch
    # itself 1 (50..51), epoch.fetch 1
    assert ts.label_gap_by_region((35, 52), regions) == (
        "train.dispatch", 4)
    assert ts.label_gap_by_region((82, 85), regions) == ("epoch.tail", 3)
    assert ts.label_gap_by_region((95, 99), regions) == ("none", 0.0)
    assert ts.label_gap_by_region((1, 2), []) == ("none", 0.0)


# -- the readers ----------------------------------------------------------------

EPOCHS = [{"t0": 100.0, "t1": 110.0}, {"t0": 110.0, "t1": 120.0}]
SPANS = [
    ("setup.mfu_cost", 80.0, 83.0), ("data.collate", 60.0, 60.5),
    ("data.collate", 61.0, 61.5), ("data.stack", 62.0, 62.25),
    ("data.h2d", 63.0, 63.25), ("train", 55.0, 70.0),
    ("train", 100.0, 100.2), ("train.dispatch", 100.0, 100.004),
    ("train.dispatch", 100.1, 100.106), ("telemetry.flush", 109.0, 109.003),
    ("epoch.tail", 109.5, 109.501), ("train", 110.0, 110.2),
    ("train.dispatch", 110.0, 110.005), ("telemetry.flush", 119.0, 119.003),
    ("epoch.tail", 119.5, 119.501),
    ("train.dispatch", 120.0, 120.5),   # after the counted epochs
]
NEW_READERS = [
    "step_fwd_ms", "step_bwd_ms", "step_opt_ms", "step_named_pct",
    "eval_share_pct", "gather_mul_seg_fwd_ms", "gather_mul_seg_bwd_ms",
    "dispatch_host_ms", "epoch_tail_ms", "setup_epoch0_s",
    "setup_collate_s", "setup_mfu_cost_s"]


def _value(name, facts):
    return load("layer_metrics", name).read(facts)


def test_span_readers_by_hand():
    facts = {"epochs": EPOCHS, "spans": SPANS}
    # (4 + 6 + 5) ms over three dispatches of the counted epochs
    assert _value("dispatch_host_ms", facts) == pytest.approx(5.0)
    # (3 + 1) ms in each of the two counted epochs
    assert _value("epoch_tail_ms", facts) == pytest.approx(4.0)
    assert _value("setup_epoch0_s", facts) == pytest.approx(45.0)
    assert _value("setup_collate_s", facts) == pytest.approx(1.5)
    assert _value("setup_mfu_cost_s", facts) == pytest.approx(3.0)


def test_parent_program_reports_nothing_and_raises_nothing(tmp_path):
    """The driver lays this PR's readers over the parent's checkout: its
    trace has no scope file beside it and its spans are the four old
    regions."""
    old_spans = [(n, a, b) for n, a, b in SPANS
                 if n in ("train", "validate", "test", "metrics_fetch")]
    untraced = {"epochs": EPOCHS, "spans": old_spans, "trace": None}
    traced = {"epochs": EPOCHS, "spans": old_spans,
              "trace": {"step_device_s": 0.05, "busy_s": 3.5},
              "trace_dir": str(tmp_path / "trace"),
              "trace_window": (100.0, 104.0), "mono_to_unix_ns": 0.0,
              "train_module_regex": r"jit_scan_step\b"}
    for facts in (untraced, traced, {"epochs": [], "spans": []}):
        got = {n: _value(n, facts) for n in NEW_READERS}
        want = 45.0 if facts["epochs"] else None    # "train" is an old region
        assert got.pop("setup_epoch0_s") == want
        assert set(got.values()) == {None}, got


def test_new_entries_are_appended_and_found(tmp_path):
    from benchload import BENCH, REPO
    import os
    import subprocess

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW_READERS):] == NEW_READERS
    for m in bench["per_layer"][-len(NEW_READERS):]:
        assert m["source"] in ("device_trace", "program_span")
        assert os.path.isfile(
            os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    head = subprocess.run(
        ["git", "show", "121536ead6e3049db0a9315713712d6d8401beb2:"
         "BENCHMARK.json"], cwd=REPO, capture_output=True, text=True)
    if head.returncode == 0:            # a checkout with its history
        old = json.loads(head.stdout)
        assert bench["per_layer"][:len(old["per_layer"])] == old["per_layer"]
        assert {k: v for k, v in bench.items() if k != "per_layer"} == {
            k: v for k, v in old.items() if k != "per_layer"}
