"""Idle time put down to the program's own spans (``devspans``), and the
readers of the engine's always-on counters: on made-up events, on two
traces recorded on a TPU v5e chip in the code-overload cell, one without
program spans (``code_small.xplane.pb``) and one with ``ProfilerTracer``
in the engine and balancer (``code_spans.xplane.pb``, a 2.5 s window),
and on observations with and without the counters."""
import os

from benchmarks.chip import devspans, devtrace, harness

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP_TRACE = os.path.join(HERE, "code_small.xplane.pb")
SPANS_TRACE = os.path.join(HERE, "code_spans.xplane.pb")


def test_a_jax_event_inside_a_program_span_goes_to_the_span():
    line = [("bench.step", 0, 100), ("repro.engine_step", 5, 95),
            ("repro.drain", 50, 90), ("np.asarray(jax.Array)", 60, 80),
            ("PjitFunction(scan)", 10, 40), ("bench.wait", 100, 200)]
    mids = [25, 70, 97, 150]
    assert devtrace.attribute(mids, [line]) == [
        "PjitFunction(scan)", "np.asarray(jax.Array)", "bench.step",
        "bench.wait"]
    assert devspans.attribute_to_program(mids, [line]) == [
        "repro.engine_step", "repro.drain", devspans.NO_SPAN,
        devspans.NO_SPAN]


def test_a_trace_without_program_spans_puts_all_idle_time_under_none():
    red = devtrace.reduce_xplane(CHIP_TRACE)
    spans = devspans.idle_by_span_s(CHIP_TRACE)
    assert set(spans) == {devspans.NO_SPAN}
    assert abs(spans[devspans.NO_SPAN] - sum(red["idle_gaps_s"].values())) \
        < 1e-9
    assert devspans.idle_in_pct(spans, red["window_s"],
                                devspans.ENGINE_SPANS) is None


def test_a_trace_with_program_spans_names_each_gap_by_its_phase():
    red = devtrace.reduce_xplane(SPANS_TRACE)
    spans = devspans.idle_by_span_s(SPANS_TRACE)
    # The same gaps: JAX's events name them in idle_gaps_s, the program's
    # phases here, and every gap lies under some repro.* span.
    assert abs(sum(spans.values()) - sum(red["idle_gaps_s"].values())) \
        < 1e-9
    assert devspans.NO_SPAN not in spans
    assert spans["repro.match_steals"] >= \
        red["idle_gaps_s"]["PjitFunction(scan)"] > 0
    idle = 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    bal = devspans.idle_in_pct(spans, red["window_s"],
                               devspans.BALANCER_SPANS)
    eng = devspans.idle_in_pct(spans, red["window_s"], devspans.ENGINE_SPANS)
    assert bal > 0 and eng > 0
    assert 0.8 * idle <= bal + eng <= idle


def test_idle_in_pct_sums_the_named_spans():
    spans = {"repro.balance": 0.5, "repro.match_steals": 0.25,
             "repro.decode": 0.1, devspans.NO_SPAN: 0.15}
    assert devspans.idle_in_pct(spans, 5.0, devspans.BALANCER_SPANS) == 15.0
    assert devspans.idle_in_pct(spans, 5.0, devspans.ENGINE_SPANS) == 2.0


def _obs(program):
    return {"program": program, "engine": {"max_slots": 32}}


def test_counter_readers_read_the_engine_snapshot():
    hold = harness.load_reader("first_token_hold_ms.chat")
    occ = harness.load_reader("decode_occupancy_pct.code")
    snap = {"first_token_hold_ms_count": 4.0,
            "first_token_hold_ms_mean": 512.5,
            "decode_calls_backlogged": 10.0,
            "decode_slots_backlogged": 240.0}
    assert hold.read(_obs(snap)) == 512.5
    assert occ.read(_obs(snap)) == 75.0
    # A program without the counters (or a run that never met a queue)
    # gives nothing to read, and the reader does not raise.
    for obs in (_obs({}), {"program": {}}, {}):
        assert hold.read(obs) is None and occ.read(obs) is None
