"""The trace reduction on hand-made interval lists: every number below is
worked out by hand in the comment beside it."""

import pytest

from benchload import load

tr = load("", "trace_reduce")


def test_union_clip_subtract():
    # (0,4) and (2,6) overlap -> (0,6); (8,9) apart; (5,5) empty
    assert tr.union([(2, 6), (0, 4), (8, 9), (5, 5)]) == [(0, 6), (8, 9)]
    assert tr.total([(0, 6), (8, 9)]) == 7
    assert tr.clip([(0, 6), (8, 9)], 5, 8.5) == [(5, 6), (8, 8.5)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                        (7, 10)]
    assert tr.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
    assert tr.gaps([(1, 2), (4, 9)], 0, 10) == [(0, 1), (2, 4), (9, 10)]


def test_gap_labels():
    host = [("train", 0, 3), ("validate", 3, 8), ("metrics_fetch", 8, 20)]
    assert tr.label_gap((4, 6), host) == "validate"
    # 2.5..3 lies in train (0.5), 3..4.5 in validate (1.5)
    assert tr.label_gap((2.5, 4.5), host) == "validate"
    assert tr.label_gap((30, 31), host) == "none"


OPS = [
    # label, opcode, start, end — window is [0, 100]
    ("while.9", "while", 20, 40),            # encloses the kernel and the
    ("fusion.1", "fusion", 0, 10),           # first all-reduce
    ("fusion.2", "fusion", 12, 20),
    ("encoder_conv_0", "custom-call", 20, 30),
    ("all-reduce.1", "all-reduce", 30, 40),  # alone: exposed 10
    ("fusion.3", "fusion", 55, 70),
    ("fusion.4", "fusion", 90, 120),         # runs past the window
]
# an asynchronous all-reduce: started at 50, done at 60; fusion.3 hides the
# last 5 of it
ASYNC = [("all-reduce-start.2", "all-reduce-start", 50, 60)]
MODULES = [("jit_scan_step(123)", 0, 40), ("jit_eval_step(9)", 50, 70),
           ("jit_scan_step(123)", 90, 120)]  # the last is cut by the window
HOST = [("train", 0, 45), ("validate", 40, 50), ("metrics_fetch", 70, 95)]


def test_reduce_device_by_hand():
    d = tr.reduce_device(OPS, MODULES, 0, 100, r"jit_(scan_step|multi)\b",
                         HOST, async_ops=ASYNC)
    # busy union of the sequential line: (0,10) (12,40) (55,70) (90,100)
    assert d["busy"] == 63 and d["window"] == 100
    assert d["mosaic"] == 10
    assert d["collective"] == 20
    # all-reduce.1 alone (10; the while round it is a container, not
    # compute) + the async one before fusion.3 starts (5)
    assert d["collective_exposed"] == 15
    # no instruction repeats inside either scan_step execution: these are
    # no scanned programs, and no step time is made up for them
    assert d["train_steps"] == 0 and d["train_busy"] == 0
    # idle: (70,90) under metrics_fetch, (40,55) mostly under validate,
    # (10,12) under train
    assert d["gaps"] == [("metrics_fetch", 20), ("validate", 15),
                         ("train", 2)]
    ops = dict(d["ops"])
    # self time: the while's body covers all of it, so it has none;
    # fusion.4 is cut by the window and is left out whole
    assert "while.9" not in ops and ops["fusion.3"] == 15
    assert "fusion.4" not in ops
    assert d["ops"][0] == ("fusion.3", 15)


def test_steps_are_the_scan_loops_iterations():
    # a scanned program: one top-level while, body = two ops, 10 units an
    # iteration, 5 iterations from t=100; a gap of 2 idle in every body
    ops = [("while.1", "while", 100, 150)]
    for i in range(5):
        t = 100 + 10 * i
        ops += [("fusion.a", "fusion", t, t + 5),
                ("inner.while", "while", t + 5, t + 8),
                ("tiny.1", "fusion", t + 5, t + 6),   # inner loop body:
                ("tiny.1", "fusion", t + 6, t + 7)]   # repeats, depth 2
    modules = [("jit_scan_step(1)", 99, 151)]
    # the window cuts the first iteration: the body's inner.while begins at
    # 105, 115 ... 145 inside it (fusion.a only four times, the inner
    # loop's own body does not count: it is no direct child) -> 4 whole
    # steps between 105 and 145
    d = tr.reduce_device(ops, modules, 104, 160, r"scan_step")
    assert d["train_steps"] == 4
    # the while is busy throughout (a container's event covers its gaps)
    assert d["train_busy"] == 40
    own, parent = tr.nest(ops)
    assert own[0] == 50 - 5 * (5 + 3) and parent[0] == -1
    assert parent[1] == 0 and parent[3] == 2
    # the trace began inside the loop: no event for the while, its body
    # lies at top level, and the steps are still counted
    cut = [op for op in ops[1:] if op[2] >= 110]
    d = tr.reduce_device(cut, [("jit_scan_step(1)", 110, 151)], 110, 160,
                         r"scan_step")
    assert d["train_steps"] == 3 and d["train_busy"] == 30 - 3 * 2
    # an unscanned step repeats nothing (its small inner loop's body lies
    # two levels down): no step time is made up for it
    plain = [("fusion.a", "fusion", 0, 30), ("w", "while", 30, 40),
             ("t", "fusion", 30, 35), ("t", "fusion", 35, 40)]
    d = tr.reduce_device(plain, [("jit_train_step(2)", 0, 40)], 0, 50,
                         r"train_step")
    assert d["train_steps"] == 0


def test_instruction_text_to_label_and_opcode():
    text = ("%encoder_conv_4.20 = f32[5120,128]{1,0:T(8,128)S(1)} "
            "custom-call(s32[286]{0:T(512)S(1)} %broadcast_minimum_fusion.5,"
            " f32[105472,1]{1,0:T(8,128)} %custom-call.3)")
    assert tr.parse_instruction(text) == (
        "encoder_conv_4.20 custom-call f32[5120,128]", "custom-call")
    loop = ("%while.681 = (s32[]{:T(128)}, f32[128]{0:T(128)}, "
            "/*index=5*/f32[1,128]{1,0:T(1,128)}) while((s32[]{:T(128)}, "
            "f32[128]{0:T(128)}) %tuple.1), condition=%c, body=%b")
    assert tr.parse_instruction(loop)[1] == "while"
    copy = ("%copy-start.203 = (s32[4]{0:T(128)S(1)}, s32[4]{0:T(128)}, "
            "u32[]{:S(2)}) copy-start(s32[4]{0:T(128)} %custom-call.141)")
    assert tr.parse_instruction(copy)[1] == "copy-start"
    assert tr.parse_instruction("5")[1] == ""


def test_combine_shares_and_step_time():
    d = tr.reduce_device(OPS, MODULES, 0, 100, r"jit_scan_step", HOST,
                         async_ops=ASYNC)
    out = tr.combine([d, d], unit=1e-3)
    assert out["devices"] == 2
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx(0.063)      # idle share 37 %
    assert out["mosaic_s"] / out["busy_s"] == pytest.approx(10 / 63)
    assert out["collective_exposed_s"] / out["window_s"] \
        == pytest.approx(0.15)
    assert out["step_device_s"] is None and out["train_steps"] == 0
    assert len(out["device_ops"]) == 5 and len(out["idle_gaps"]) == 5
    assert out["idle_gaps"][0] == ("metrics_fetch", pytest.approx(0.02))


def test_nothing_on_the_device_is_nothing_to_report():
    d = tr.reduce_device([], [], 0, 100, r"jit_scan_step")
    assert d["busy"] == 0
    assert tr.combine([d]) is None
    assert tr.reduce_run({"trace_dir": None, "trace_window": None}) is None


def test_kinds_are_found_by_what_the_trace_says():
    assert tr.is_collective("all-reduce-start")
    assert tr.is_collective("all-gather") and tr.is_collective("all-to-all")
    assert not tr.is_collective("fusion") and not tr.is_collective("copy")
    assert tr.is_mosaic("custom-call")
    # an operand called %custom-call.3 does not make a copy a kernel
    assert not tr.is_mosaic("copy-start") and not tr.is_mosaic("fusion")
