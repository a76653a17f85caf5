"""Trace analytics, flight recorder, and SLO monitor (DESIGN.md §14):
attribution completeness over randomized synthetic request lifecycles
(property test + fixed-seed fallback), flight-ring wraparound producing
validator-clean dumps at every capacity, analyzer results over real
engine runs (preemption buckets, cross-replica migration stitches,
steal efficiency), burn-rate alert state transitions, and the
``python -m repro.obs.analyze`` CLI gate."""
import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from tests._optional_hypothesis import HAVE_HYPOTHESIS, given, settings, st

from repro.configs import ARCHS
from repro.models import init_lm
from repro.obs import (FlightRecorder, MetricsRegistry, SLOMonitor,
                       SLOTarget, Tracer, analyze_trace, check_invariants,
                       parse_slo_spec, render_markdown, render_summary,
                       validate_chrome_trace)
from repro.obs.analyze import BUCKETS, headline, main as analyze_main
from repro.serve.engine import Engine, GLBReplicaBalancer, Request

CFG = ARCHS["tinyllama-1.1b"].smoke()
PARAMS = init_lm(jax.random.key(0), CFG)

PROMPT16 = [7, 3, 9, 2, 5, 8, 6, 4, 1, 2, 3, 4, 9, 9, 8, 7]


# ===================================================== synthetic lifecycles
class FakeClock:
    """Deterministic now_us(): each call returns the scripted time, so a
    synthetic lifecycle's phase transitions are atomic (both the close
    and the open of a transition read the SAME tick) and bucket sums
    equal wall-clock exactly."""

    def __init__(self, t0=1_000.0):
        self.t = t0

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt
        return self.t


@pytest.fixture()
def clock(monkeypatch):
    clk = FakeClock()
    monkeypatch.setattr("repro.obs.trace.now_us", clk)
    return clk


def _run_lifecycle(tr, clk, rid, ops):
    """Drive one request through the REAL tracer API from an op list of
    (action, dwell_us) pairs; returns the expected bucket sums."""
    expect = {b: 0.0 for b in BUCKETS}
    tr.req_begin(rid, pid=0)
    tr.req_phase(rid, "queued", pid=0)
    cur, pid = "queued", 0
    preempted = False
    for action, dwell in ops:
        clk.tick(dwell)
        if cur == "queued":
            expect["preempted" if preempted else "queued"] += dwell
            preempted = False
        elif cur == "migrate":
            expect["migrating"] += dwell
        else:
            expect[cur] += dwell
        if action == "prefill":
            tr.req_phase(rid, "prefill", pid=pid)
            cur = "prefill"
        elif action == "decode":
            tr.req_phase(rid, "decode", pid=pid)
            cur = "decode"
        elif action == "preempt":
            tr.req_instant(rid, "preempted", pid=pid)
            tr.req_phase(rid, "queued", pid=pid)
            cur, preempted = "queued", True
        elif action == "migrate":
            tr.req_instant(rid, "migrated_out", pid=pid,
                           args={"bytes": 2048})
            tr.req_phase(rid, "migrate", pid=pid)
            cur, pid = "migrate", pid + 1
        elif action == "land":
            tr.req_instant(rid, "migrated_in", pid=pid)
            tr.req_phase(rid, "decode", pid=pid)
            cur = "decode"
    clk.tick(10.0)
    if cur == "queued":
        expect["preempted" if preempted else "queued"] += 10.0
    elif cur == "migrate":
        expect["migrating"] += 10.0
    else:
        expect[cur] += 10.0
    tr.req_end(rid, pid=pid)
    return expect


def _random_ops(rng, n):
    """Random legal op sequence: prefill -> decode, then any mix of
    preempt->prefill->decode cycles and migrate->land hops."""
    ops = [("prefill", float(rng.integers(1, 500))),
           ("decode", float(rng.integers(1, 500)))]
    for _ in range(n):
        r = rng.random()
        if r < 0.4:
            ops.append(("preempt", float(rng.integers(1, 500))))
            ops.append(("prefill", float(rng.integers(1, 500))))
            ops.append(("decode", float(rng.integers(1, 500))))
        elif r < 0.7:
            ops.append(("migrate", float(rng.integers(1, 500))))
            ops.append(("land", float(rng.integers(1, 200))))
        else:
            ops.append(("decode", float(rng.integers(1, 500))))
    return ops


def _check_attribution(tr, clk, n_reqs, rng):
    expects = {}
    for rid in range(n_reqs):
        expects[rid] = _run_lifecycle(tr, clk, rid,
                                      _random_ops(rng,
                                                  int(rng.integers(0, 6))))
    a = analyze_trace(tr)
    assert a.validator_problems == []
    assert check_invariants(a, max_unattributed=0.01,
                            abs_slack_us=1e-6) == []
    for rid, expect in expects.items():
        r = a.request(rid)
        assert r is not None
        wall = sum(expect.values())
        assert abs(r.wall_us - wall) < 1e-6
        for b in BUCKETS:
            assert abs(r.buckets[b] - expect[b]) < 1e-6, (
                rid, b, r.buckets, expect)
        # exhaustive under the fake clock: transitions are atomic
        assert abs(r.unattributed_us) < 1e-6


def test_attribution_exhaustive_fixed_seeds(clock):
    """Fixed-seed fallback for the property test below: ~25 randomized
    multi-request lifecycle tapes, buckets must equal wall-clock
    exactly under the fake clock (runs with or without hypothesis)."""
    for seed in range(25):
        tr = Tracer()
        _check_attribution(tr, clock, n_reqs=3,
                           rng=np.random.default_rng(seed))


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@given(st.integers(min_value=0, max_value=2**32 - 1))
@settings(max_examples=50, deadline=None)
def test_attribution_exhaustive_property(seed):
    """Property form: any legal preempt/resume/migrate sequence is
    attributed exhaustively (no fixture — hypothesis reuses the test)."""
    clk = FakeClock()
    import repro.obs.trace as trace_mod
    orig = trace_mod.now_us
    trace_mod.now_us = clk
    try:
        tr = Tracer()
        _check_attribution(tr, clk, n_reqs=2,
                           rng=np.random.default_rng(seed))
    finally:
        trace_mod.now_us = orig


def test_preempted_bucket_distinct_from_arrival_queueing(clock):
    """Arrival queueing and post-preemption requeue time land in
    different buckets even though both are 'queued' phases."""
    tr = Tracer()
    tr.req_begin(0, pid=0)
    tr.req_phase(0, "queued", pid=0)
    clock.tick(100.0)
    tr.req_phase(0, "prefill", pid=0)
    clock.tick(50.0)
    tr.req_phase(0, "decode", pid=0)
    clock.tick(200.0)
    tr.req_instant(0, "preempted", pid=0)
    tr.req_phase(0, "queued", pid=0)
    clock.tick(70.0)
    tr.req_instant(0, "resumed", pid=0)
    tr.req_phase(0, "decode", pid=0)
    clock.tick(30.0)
    tr.req_end(0, pid=0)
    r = analyze_trace(tr).request(0)
    assert r.buckets["queued"] == pytest.approx(100.0)
    assert r.buckets["preempted"] == pytest.approx(70.0)
    assert r.buckets["decode"] == pytest.approx(230.0)
    assert r.preemptions == 1
    assert r.unattributed_us == pytest.approx(0.0, abs=1e-9)


# ======================================================== flight recorder
def _emit_workload(tr):
    """Mixed-vocabulary workload: nested duration spans, async request
    lifecycles with preemption + migration, instants, counters, and
    still-open spans at dump time."""
    tr.process_name(0, "replica 0")
    tr.process_name(1, "replica 1")
    tr.thread_name(0, 0, "engine")
    for rid in range(4):
        tr.req_begin(rid, pid=0)
        tr.req_phase(rid, "queued", pid=0)
    for step in range(8):
        tr.begin("engine_step", pid=0)
        tr.begin("prefill", pid=0)
        tr.end(pid=0)
        tr.end(pid=0)
        tr.counter("load", {"running": float(step)}, pid=0)
    tr.req_phase(0, "prefill", pid=0)
    tr.req_phase(0, "decode", pid=0)
    tr.req_instant(1, "preempted", pid=0)
    tr.req_phase(1, "queued", pid=0)
    tr.req_instant(0, "migrated_out", pid=0, args={"bytes": 4096})
    tr.req_phase(0, "migrate", pid=0)
    tr.req_instant(0, "migrated_in", pid=1)
    tr.req_phase(0, "decode", pid=1)
    tr.req_end(0, pid=1, args={"tokens": 9})
    tr.req_end(1, pid=0)
    tr.instant("steal_queued", pid=2, args={"n": 2})
    tr.begin("superstep", pid=2)        # left open at dump time


@pytest.mark.parametrize("capacity",
                         [1, 2, 3, 5, 8, 13, 21, 40, 64, 128, 999, 5000])
def test_flight_dump_valid_at_every_capacity(capacity):
    """The ISSUE acceptance criterion: a wrapped (or not) ring ALWAYS
    dumps a balanced, validator-clean trace."""
    fr = FlightRecorder(capacity=capacity)
    _emit_workload(fr)
    dump = fr.dump()
    assert validate_chrome_trace(dump) == []
    fl = dump["otherData"]["flight"]
    assert fl["capacity"] == capacity
    assert len(fr.events) <= capacity


def test_flight_drop_count_matches_plain_tracer():
    plain = Tracer()
    _emit_workload(plain)
    ring_eligible = sum(1 for e in plain.events if e.get("ph") != "M")
    for capacity in (1, 7, 33, 1000):
        fr = FlightRecorder(capacity=capacity)
        _emit_workload(fr)
        assert fr.dropped == max(0, ring_eligible - capacity)


def test_flight_ample_capacity_drops_and_synthesizes_nothing():
    fr = FlightRecorder(capacity=100_000)
    _emit_workload(fr)
    dump = fr.dump()
    assert dump["otherData"]["flight"]["dropped"] == 0
    assert dump["otherData"]["flight"]["synthesized_opens"] == 0
    assert validate_chrome_trace(dump) == []


def test_flight_dump_is_non_destructive():
    fr = FlightRecorder(capacity=64)
    _emit_workload(fr)
    a = fr.dump()
    b = fr.dump()
    assert len(a["traceEvents"]) == len(b["traceEvents"])
    assert validate_chrome_trace(b) == []
    fr.begin("more", pid=0)             # still recording after dumps
    fr.end(pid=0)
    assert validate_chrome_trace(fr.dump()) == []


def test_flight_capacity_validation():
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)
    with pytest.raises(ValueError):
        FlightRecorder(capacity=-5)


def test_flight_write_is_atomic_and_valid(tmp_path):
    fr = FlightRecorder(capacity=16)
    _emit_workload(fr)
    path = tmp_path / "flight.json"
    fr.write(str(path))
    loaded = json.loads(path.read_text())
    assert validate_chrome_trace(loaded) == []
    assert loaded["otherData"]["flight"]["dropped"] == fr.dropped
    assert not list(tmp_path.glob(".trace.*"))  # no temp litter


def test_flight_truncated_requests_flagged_not_gated():
    """Requests whose begin fell off the ring are marked truncated and
    exempt from the attribution invariant (their history is a suffix)."""
    fr = FlightRecorder(capacity=8)
    _emit_workload(fr)
    a = analyze_trace(fr)
    assert a.validator_problems == []
    assert any(r.truncated for r in a.requests)
    assert check_invariants(a) == []


# ================================================== analyzer, real engine
def test_analyzer_real_engine_preemption():
    """Block-starved paged engine: preemptions happen, and the analyzer
    attributes >=99% of every request's wall-clock with a nonzero
    preempted bucket."""
    tr = Tracer()
    eng = Engine(CFG, PARAMS, block_size=8, num_blocks=5,
                 max_slots=2, max_seq=32, pad_len=8, steps_per_sync=8,
                 tracer=tr)
    reqs = [Request(rid=i, prompt=[3, i + 1, 4, 2], max_new=14 + i % 4)
            for i in range(5)]
    for r in reqs:
        eng.submit(r)
    guard = 500
    while eng.load > 0 and guard > 0:
        eng.step()
        guard -= 1
    assert eng.sched.preemptions > 0
    a = analyze_trace(tr)
    assert a.validator_problems == []
    assert check_invariants(a, max_unattributed=0.01) == []
    assert len(a.requests) == 5
    for r in a.requests:
        assert r.unattributed_frac <= 0.01
    assert a.bucket_totals()["preempted"] > 0
    assert sum(r.preemptions for r in a.requests) == eng.sched.preemptions
    rep = a.replicas[0]
    assert rep.steps == eng.steps
    assert rep.busy_us > 0 and rep.utilization > 0
    # reports render without error and carry the headline facts
    md = render_markdown(a)
    assert "Request time attribution" in md and "preempted" in md
    assert "p99" in render_summary(a) or "request" in render_summary(a)
    assert "analysis:" in headline(a)


def test_analyzer_real_engine_migration_stitch():
    """Live migration: the analyzer stitches the request across pids,
    reports the migrating bucket, migration bytes, and post-migration
    decode time (steal-efficiency numerator)."""
    tr = Tracer()
    kw = dict(max_slots=1, max_seq=64, pad_len=16, steps_per_sync=4)
    victim = Engine(CFG, PARAMS, block_size=8, tracer=tr,
                    replica_id=0, **kw)
    thief = Engine(CFG, PARAMS, block_size=8, tracer=tr,
                   replica_id=1, **kw)
    req = Request(rid=0, prompt=list(PROMPT16), max_new=30)
    victim.submit(req)
    for _ in range(7):
        victim.step()
    assert thief.migrate_in(victim.migrate_out(0)) == "live"
    guard = 200
    while thief.load > 0 and guard > 0:
        thief.step()
        guard -= 1
    a = analyze_trace(tr)
    assert a.validator_problems == []
    assert check_invariants(a) == []
    r = a.request(0)
    assert r.replicas == [0, 1]
    assert r.migrations == 1
    assert r.migration_bytes > 0
    assert r.buckets["migrating"] > 0
    assert r.post_migration_decode_us > 0
    assert r.unattributed_frac <= 0.01
    assert {rep.pid for rep in a.replicas} == {0, 1}
    s = a.steal
    assert s.migration_bytes == r.migration_bytes
    assert s.moved_decode_us == pytest.approx(r.post_migration_decode_us)
    assert s.moved_decode_us_per_kib > 0


def test_analyzer_fabric_steal_efficiency():
    """Balancer-driven fabric: steal instants inside superstep spans
    count as steal rounds; tier-1 moves come from the instants' n."""
    tr = Tracer()
    engines = [Engine(CFG, PARAMS, block_size=8, max_slots=2,
                      max_seq=32, pad_len=8, steps_per_sync=4, tracer=tr,
                      replica_id=i) for i in range(2)]
    bal = GLBReplicaBalancer(engines, migrate=True, tracer=tr)
    for i in range(6):
        engines[0].submit(Request(rid=i, prompt=[3, i + 1, 4, 2],
                                  max_new=8))
    bal.run(max_steps=200)
    assert bal.terminated
    a = analyze_trace(tr)
    assert a.validator_problems == []
    assert check_invariants(a) == []
    assert a.steal.supersteps == bal.supersteps + 1  # + the final pass
    assert a.steal.tier1_moves + a.steal.tier2_moves == bal.moves
    if bal.moves:
        assert a.steal.steal_rounds > 0
        assert a.steal.moves_per_steal_round > 0


def test_analyze_cli_gate(tmp_path):
    """The CLI is the CI gate: exit 0 + report files on a good trace,
    exit 1 on a corrupted one."""
    tr = Tracer()
    eng = Engine(CFG, PARAMS, max_slots=2, max_seq=32, pad_len=8,
                 steps_per_sync=8, tracer=tr)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=[3, i + 1, 4], max_new=6))
    guard = 200
    while eng.load > 0 and guard > 0:
        eng.step()
        guard -= 1
    trace_path = tmp_path / "trace.json"
    tr.write(str(trace_path))
    out_md = tmp_path / "report.md"
    summary = tmp_path / "summary.md"
    rc = analyze_main([str(trace_path), "--out", str(out_md),
                       "--summary", str(summary)])
    assert rc == 0
    assert "Request time attribution" in out_md.read_text()
    assert summary.read_text().startswith("# Trace analysis")
    rc_json = analyze_main([str(trace_path), "--json"])
    assert rc_json == 0
    # corrupt the trace: drop an async close -> validator + gate fail
    trace = json.loads(trace_path.read_text())
    victim_i = next(i for i, e in enumerate(trace["traceEvents"])
                    if e.get("ph") == "e")
    del trace["traceEvents"][victim_i]
    bad_path = tmp_path / "bad.json"
    bad_path.write_text(json.dumps(trace))
    assert analyze_main([str(bad_path)]) == 1


def test_analyze_cli_subprocess_entrypoint(tmp_path):
    """`python -m repro.obs.analyze` (the exact CI invocation) works."""
    tr = Tracer()
    tr.req_begin(0, pid=0)
    tr.req_phase(0, "queued", pid=0)
    tr.req_phase(0, "decode", pid=0)
    tr.req_end(0, pid=0)
    path = tmp_path / "t.json"
    tr.write(str(path))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.obs.analyze", str(path)],
        capture_output=True, text=True, env={"PYTHONPATH": "src",
                                             "PATH": "/usr/bin:/bin"},
        cwd="/root/repo")
    assert proc.returncode == 0, proc.stderr
    assert "Trace analysis" in proc.stdout


# ====================================================== slo monitor
def test_slo_parse_spec():
    targets = parse_slo_spec("ttft_ms=250,tpot_ms=50@0.999")
    assert targets[0] == SLOTarget("ttft_ms", 250.0, 0.99)
    assert targets[1] == SLOTarget("tpot_ms", 50.0, 0.999)
    for bad in ("ttft", "x=0", "x=5@1.5", "x=5@0"):
        with pytest.raises(ValueError):
            parse_slo_spec(bad)


def test_slo_validation():
    with pytest.raises(ValueError):
        SLOMonitor([])
    with pytest.raises(ValueError):
        SLOMonitor([SLOTarget("a", 1.0), SLOTarget("a", 2.0)])
    with pytest.raises(ValueError):
        SLOMonitor([SLOTarget("a", 1.0)], windows=((5.0, 60.0, 10.0),))
    with pytest.raises(ValueError):
        SLOMonitor([SLOTarget("a", 1.0)], windows=((60.0, 5.0, 0.5),))


def test_slo_burn_alert_transitions():
    """Multi-window burn alerting: healthy stream -> no alert; sustained
    50% violation rate -> ONE alert instant; recovery -> one clear."""
    tr = Tracer()
    reg = MetricsRegistry()
    m = SLOMonitor([SLOTarget("ttft_ms", 100.0, 0.99)],
                   windows=((60.0, 5.0, 10.0),), tracer=tr, metrics=reg,
                   pid=9)
    t0 = 1e6
    for i in range(100):
        m.observe("ttft_ms", 10.0, ts_us=t0 + i * 1e4)
    assert m.check(ts_us=t0 + 1e6) == []
    for i in range(100):
        m.observe("ttft_ms", 500.0 if i % 2 else 10.0,
                  ts_us=t0 + 2e6 + i * 1e4)
    assert m.check(ts_us=t0 + 3e6) == ["ttft_ms"]
    assert m.check(ts_us=t0 + 3.1e6) == ["ttft_ms"]   # sustained: 1 alert
    assert m.alerts_fired == 1
    for i in range(600):
        m.observe("ttft_ms", 10.0, ts_us=t0 + 4e6 + i * 1e4)
    assert m.check(ts_us=t0 + 10e6) == []
    names = [e["name"] for e in tr.events if e.get("ph") == "i"]
    assert names == ["slo_burn", "slo_burn_clear"]
    burn = next(e for e in tr.events if e.get("name") == "slo_burn")
    assert burn["pid"] == 9
    assert burn["args"]["metric"] == "ttft_ms"
    snap = reg.snapshot()
    assert snap["slo_burn_alerts"] == 1.0
    assert snap["slo_ttft_ms_violations"] == 50.0
    assert m.attainment()["ttft_ms"]["attained"] == pytest.approx(750 / 800)


def test_slo_single_window_no_flap():
    """A short burst that clears before the long window fills must NOT
    alert (the long window is the flap damper)."""
    m = SLOMonitor([SLOTarget("ttft_ms", 100.0, 0.99)],
                   windows=((60.0, 5.0, 10.0),))
    t0 = 1e6
    for i in range(1000):
        m.observe("ttft_ms", 10.0, ts_us=t0 + i * 1e4)
    # 3 bad samples right at the end: short-window burn spikes, long
    # window stays healthy
    for i in range(3):
        m.observe("ttft_ms", 500.0, ts_us=t0 + 1e7 + i * 1e3)
    assert m.check(ts_us=t0 + 1e7 + 3e3) == []
    assert m.alerts_fired == 0


def test_slo_ignores_undeclared_metrics():
    m = SLOMonitor([SLOTarget("ttft_ms", 100.0)])
    m.observe("tpot_ms", 1e9, ts_us=1.0)     # no target: ignored
    assert m.attainment().keys() == {"ttft_ms"}


def test_slo_engine_integration():
    """Engine + balancer wiring: slo= threads to every engine and its
    scheduler, observations flow, collect() grows _slo, report() states
    attainment, and fabric_summary skips the _slo sub-dict."""
    from repro.core import fabric_summary
    slo = SLOMonitor([SLOTarget("ttft_ms", 0.001),    # unmeetable
                      SLOTarget("tpot_ms", 1e6)])     # unmissable
    engines = [Engine(CFG, PARAMS, block_size=8, max_slots=2,
                      max_seq=32, pad_len=8, steps_per_sync=4,
                      replica_id=i) for i in range(2)]
    bal = GLBReplicaBalancer(engines, slo=slo)
    assert all(e.slo is slo for e in engines)
    assert all(e.sched.slo is slo for e in engines)
    assert slo.pid == bal._fabric_pid
    for i in range(4):
        engines[0].submit(Request(rid=i, prompt=[3, i + 1, 4, 2],
                                  max_new=6))
    bal.run(max_steps=200)
    col = bal.collect()
    assert col["_slo"]["slo_ttft_ms_violations"] == 4.0
    assert col["_slo"]["slo_ttft_ms_met"] == 0.0
    assert col["_slo"]["slo_tpot_ms_met"] == 1.0
    report = bal.report()
    assert "slo ttft_ms" in report and "[MISSED]" in report
    assert "slo tpot_ms" in report and "[MET]" in report
    fabric_summary(col)                  # _-prefixed sub-dicts skipped
