"""The trace-to-metrics reduction: interval arithmetic and attribution
on made-up events, and the whole reduction on a small trace recorded on
a TPU v5e chip: 1.5 s of the code-overload cell, prefill and decode
(``code_small.xplane.pb``)."""
import os


from benchmarks.chip import devtrace

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP_TRACE = os.path.join(HERE, "code_small.xplane.pb")


def test_union_merges_overlaps_and_sorts():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)]) == \
        [(0, 3), (5, 9), (10, 11)]


def test_clip():
    assert devtrace.clip((0, 10), 2, 5) == (2, 5)
    assert devtrace.clip((0, 1), 2, 5) is None


def test_module_name_drops_the_program_id():
    assert devtrace.module_name("jit_decode_tokens(1234)") == \
        "jit_decode_tokens"


def test_attribute_takes_the_innermost_span():
    line = [("bench.step", 0, 100), ("PjitFunction(f)", 10, 40),
            ("inner", 20, 30), ("bench.wait", 100, 200),
            (devtrace.WINDOW_SPAN, 0, 300)]
    got = devtrace.attribute([5, 25, 35, 150, 250], [line])
    assert got == ["bench.step", "inner", "PjitFunction(f)", "bench.wait",
                   "host: no span"]


def test_top_and_module_seconds():
    red = {"ops_s": {"a": 1.0, "b": 3.0}, "idle_gaps_s": {"x": 2.0},
           "modules_s": {"jit_decode_tokens": 2.0, "jit_prefill_paged": 1.0},
           "module_calls": {"jit_decode_tokens": 4, "jit_prefill_paged": 2}}
    assert devtrace.breakdown(red) == {"device_ops": [["b", 3.0], ["a", 1.0]],
                                       "idle_gaps": [["x", 2.0]]}
    assert devtrace.module_seconds(red, "jit_decode") == (2.0, 4)


def test_reduction_of_a_recorded_chip_trace():
    red = devtrace.reduce_xplane(CHIP_TRACE)
    assert red is not None and red["chips"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    # Every op lies inside the window and on one chip: op time sums to
    # at least the union of busy intervals.
    assert sum(red["ops_s"].values()) >= red["busy_s"] * 0.999
    for prog in ("jit_decode_tokens", "jit_prefill_paged"):
        secs, calls = devtrace.module_seconds(red, prog)
        assert calls >= 1 and 0 < secs <= red["window_s"]
    # The kernels are found by the names their ops carry.
    assert devtrace.op_seconds(red, r"^%paged_decode\.\d+$") > 0
    assert devtrace.op_seconds(red, r"^%flash_attention\.\d+$") > 0
    gaps = sum(red["idle_gaps_s"].values())
    assert gaps <= red["window_s"] - red["busy_s"] + 1e-9
    # Idle time is put down to host events (here the balancer's eager
    # scans and the step's drain), none to "no span".
    assert red["idle_gaps_s"] and "host: no span" not in red["idle_gaps_s"]


def test_self_times_take_nested_ops_out_of_their_parent():
    evs = [("%while.1", 0, 100), ("%a", 10, 30), ("%b", 40, 50),
           ("%while.2", 60, 90), ("%c", 70, 80), ("%d", 100, 120)]
    own = {n: t for n, s, e, t in devtrace.self_times(evs)}
    assert own == {"%while.1": 100 - 20 - 10 - 30, "%a": 20, "%b": 10,
                   "%while.2": 30 - 10, "%c": 10, "%d": 20}


def test_op_name_keeps_the_instruction_name():
    assert devtrace.op_name("%paged_decode.8 = bf16[32,2,6,128] custom-call("
                            "s32[32,128] %x)") == "%paged_decode.8"
