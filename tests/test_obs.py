"""Observability layer (DESIGN.md §10): Chrome-trace schema validity and
span nesting under forced preemption+resume and forced migration,
histogram bucket math vs numpy quantiles, NullTracer greedy-token
identity (tracing must not perturb results), stats()/collect() as
registry views, and the traced GLB sim loop matching the jitted one."""
import json

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import GLB, GLBParams, fabric_summary, merge_place_stats
from repro.models import init_lm
from repro.obs import (DEFAULT_MS_BUCKETS, NULL_TRACER, Histogram,
                       MetricsRegistry, Tracer, clock_sync,
                       quantiles_from_values, validate_chrome_trace)
from repro.obs.metrics import QUEUE_WAIT_MS_BUCKETS
from repro.problems.uts import uts_problem
from repro.serve.engine import Engine, GLBReplicaBalancer, Request

CFG = ARCHS["tinyllama-1.1b"].smoke()
PARAMS = init_lm(jax.random.key(0), CFG)

PROMPT16 = [7, 3, 9, 2, 5, 8, 6, 4, 1, 2, 3, 4, 9, 9, 8, 7]
KW = dict(max_slots=2, max_seq=32, pad_len=8, steps_per_sync=8)


def _drive(engine, reqs, guard=500):
    for r in reqs:
        engine.submit(r)
    while engine.load > 0 and guard > 0:
        engine.step()
        guard -= 1
    assert all(r.done for r in reqs)
    return [list(r.out) for r in reqs]


def _req_events(tracer, rid):
    """(ph, name) sequence of one request's async lifecycle events."""
    return [(e["ph"], e["name"]) for e in tracer.events
            if e.get("cat") == "request" and e.get("id") == f"req{rid}"]


# ===================================================== metrics primitives
def test_histogram_quantiles_vs_numpy_fixed_seed():
    """Estimated quantiles land within one covering-bucket width of the
    true sample quantile, on fixed-seed lognormal-ish latency streams."""
    rng = np.random.default_rng(7)
    values = np.exp(rng.normal(1.5, 1.2, size=2000))    # ms scale
    h = Histogram(DEFAULT_MS_BUCKETS)
    for v in values:
        h.observe(v)
    bounds = (0.0,) + tuple(DEFAULT_MS_BUCKETS) + (float(values.max()),)
    for q in (0.1, 0.5, 0.9, 0.99):
        est = h.quantile(q)
        true = float(np.quantile(values, q))
        i = np.searchsorted(bounds, true, side="left")
        width = bounds[min(i, len(bounds) - 1)] - bounds[max(i - 1, 0)]
        assert abs(est - true) <= width + 1e-9, (q, est, true, width)
    assert h.count == 2000
    assert np.isclose(h.total, values.sum())
    assert h.quantile(0.0) >= values.min() - 1e-9
    assert h.quantile(1.0) <= values.max() + 1e-9


def test_histogram_merge_is_exact():
    rng = np.random.default_rng(3)
    a, b = rng.exponential(5.0, 500), rng.exponential(40.0, 300)
    ha, hb, hall = Histogram(), Histogram(), Histogram()
    for v in a:
        ha.observe(v)
        hall.observe(v)
    for v in b:
        hb.observe(v)
        hall.observe(v)
    ha.merge_from(hb)
    assert ha.counts == hall.counts
    assert ha.count == hall.count == 800
    assert np.isclose(ha.total, hall.total)
    assert ha.quantile(0.5) == hall.quantile(0.5)


def test_histogram_bounds_validation_raises():
    """User-input validation must be real exceptions, not asserts —
    asserts vanish under `python -O` and a silently-accepted bad bucket
    layout corrupts every merge downstream."""
    with pytest.raises(ValueError):
        Histogram(())
    with pytest.raises(ValueError):
        Histogram((1.0, 1.0, 2.0))          # not strictly ascending
    with pytest.raises(ValueError):
        Histogram((5.0, 1.0))


def test_histogram_merge_mismatched_bounds_raises():
    """Regression: merging histograms with different bucket layouts is a
    ValueError (the counts would be meaningless bucket-for-bucket)."""
    a = Histogram((1.0, 2.0, 4.0))
    b = Histogram((1.0, 2.0, 8.0))
    a.observe(1.5)
    b.observe(3.0)
    with pytest.raises(ValueError, match="different bucket layouts"):
        a.merge_from(b)
    # the failed merge must not have corrupted the target
    assert a.count == 1 and a.counts == [0, 1, 0, 0]


def test_quantiles_from_values_matches_histogram():
    vals = [1.0, 2.0, 4.0, 8.0, 100.0]
    h = Histogram()
    for v in vals:
        h.observe(v)
    assert quantiles_from_values(vals, [0.5, 0.99]) == [h.quantile(0.5),
                                                        h.quantile(0.99)]


def test_registry_merge_and_kind_uniqueness():
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    r1.counter("reqs").inc(3)
    r2.counter("reqs").inc(4)
    r1.gauge("peak").set(5)
    r2.gauge("peak").set(9)
    r1.histogram("lat_ms").observe(2.0)
    r2.histogram("lat_ms").observe(200.0)
    m = MetricsRegistry.merged([r1, r2])
    snap = m.snapshot()
    assert snap["reqs"] == 7.0            # counters add
    assert snap["peak"] == 9.0            # gauges keep the high-water mark
    assert snap["lat_ms_count"] == 2.0    # histograms merge buckets
    with pytest.raises(ValueError):
        r1.gauge("reqs")                  # name already a counter
    text = m.render_prometheus()
    assert "# TYPE repro_reqs counter" in text
    assert 'repro_lat_ms_bucket{le="+Inf"} 2' in text
    assert text.endswith("\n")


# ================================================== tracer schema contract
def test_chrome_trace_schema_and_flush_balance():
    tr = Tracer()
    tr.begin("outer", pid=1)
    tr.begin("inner", pid=1)
    tr.end(pid=1)
    tr.instant("tick", pid=1)
    tr.counter("load", {"q": 3}, pid=1)
    tr.req_begin(7, pid=1)
    tr.req_phase(7, "queued", pid=1)
    tr.req_phase(7, "decode", pid=2)      # phase ownership moves pids
    # "outer" and req 7's decode phase left open: flush must close both.
    tr.flush()
    trace = tr.to_chrome()
    assert validate_chrome_trace(trace) == []
    assert trace["displayTimeUnit"] == "ms"
    assert "clock_sync" in trace["otherData"]
    # the closing "e" of a phase is stamped with the OPENING pid
    evs = _req_events(tr, 7)
    assert ("e", "queued") in evs
    queued_end = next(e for e in tr.events if e.get("ph") == "e"
                      and e.get("name") == "queued")
    assert queued_end["pid"] == 1
    json.dumps(trace)                     # serializable as-is


def test_tracer_write_is_atomic(tmp_path):
    """write() lands via temp-file + os.replace: the previous complete
    file survives any interruption, no temp litter remains, and the
    written JSON round-trips through the validator."""
    path = tmp_path / "trace.json"
    path.write_text('{"traceEvents": "PREVIOUS COMPLETE FILE"}')
    tr = Tracer()
    tr.begin("outer", pid=0)
    tr.req_begin(1, pid=0)
    tr.req_phase(1, "queued", pid=0)
    tr.write(str(path))
    loaded = json.loads(path.read_text())
    assert validate_chrome_trace(loaded) == []
    assert not list(tmp_path.glob(".trace.*")), "temp file left behind"
    # a failing serialization must not clobber the existing file
    tr2 = Tracer()
    tr2.events.append({"ph": "i", "name": "bad", "ts": 1, "pid": 0,
                       "tid": 0, "args": {"x": object()}})
    with pytest.raises(TypeError):
        tr2.write(str(path))
    assert json.loads(path.read_text()) == loaded
    assert not list(tmp_path.glob(".trace.*"))


def test_tracer_dump_is_non_destructive():
    """dump() exports a balanced copy of a LIVE tracer: open spans and
    phases are closed in the export only, and tracing continues."""
    tr = Tracer()
    tr.begin("outer", pid=0)
    tr.req_begin(3, pid=0)
    tr.req_phase(3, "decode", pid=0)
    n_before = len(tr.events)
    dump = tr.dump()
    assert validate_chrome_trace(dump) == []
    assert len(tr.events) == n_before           # tracer untouched
    assert tr._stacks[(0, 0)] == ["outer"]      # still open
    tr.end(pid=0)                               # still usable
    tr.req_end(3, pid=0)
    tr.flush()
    assert validate_chrome_trace(tr.to_chrome()) == []


def test_validator_catches_malformed_traces():
    bad = {"traceEvents": [{"ph": "E", "ts": 1, "pid": 0, "tid": 0},
                           {"ph": "B", "ts": 2, "pid": 0, "tid": 0,
                            "name": "x"},
                           {"ph": "b", "ts": 3, "pid": 0, "tid": 0,
                            "name": "y", "cat": "request"},
                           {"ts": 4, "pid": 0, "tid": 0}]}
    problems = validate_chrome_trace(bad)
    assert any("E without open B" in p for p in problems)
    assert any("unclosed duration" in p for p in problems)
    assert any("missing id" in p for p in problems)
    assert any("missing 'ph'" in p for p in problems)
    assert validate_chrome_trace({}) == ["traceEvents missing or not a list"]


def test_clock_sync_anchors_agree():
    s1, s2 = clock_sync(), clock_sync()
    u1 = s1["unix_ts"] - s1["perf_us"] / 1e6
    u2 = s2["unix_ts"] - s2["perf_us"] / 1e6
    assert abs(u1 - u2) < 0.5             # same clock-domain offset


# ================================================ lifecycle: preempt/resume
def test_preemption_resume_span_ordering():
    """A pool too small for both sequences forces watermark preemption;
    the preempted request's lifecycle must read
    queued -> prefill -> decode -> preempted -> queued -> ... -> resumed
    -> decode -> end, and the full trace must validate."""
    tr = Tracer()
    e = Engine(CFG, PARAMS, block_size=8, num_blocks=5,
               tracer=tr, **KW)
    reqs = [Request(rid=i, prompt=[3, i + 1, 4, 2], max_new=14 + i % 4)
            for i in range(5)]
    _drive(e, reqs)
    assert e.sched.preemptions > 0, "pool sizing must force preemption"
    tr.flush()
    assert validate_chrome_trace(tr.to_chrome()) == []
    preempted_rids = [ev.get("id") for ev in tr.events
                      if ev.get("ph") == "n" and ev["name"] == "preempted"]
    assert preempted_rids
    rid = int(preempted_rids[0][len("req"):])
    evs = _req_events(tr, rid)
    # begins/ends balanced and the request span closed exactly once
    assert evs[0] == ("b", "request") and evs[-1] == ("e", "request")
    assert evs.count(("e", "request")) == 1
    # preempted -> back to queued -> eventually resumed -> decode again
    i_pre = evs.index(("n", "preempted"))
    assert ("b", "decode") in evs[:i_pre]
    # the transition closes the open phase first, then re-opens queued
    assert ("b", "queued") in evs[i_pre + 1:i_pre + 3]
    i_res = evs.index(("n", "resumed"))
    assert i_res > i_pre
    assert ("b", "decode") in evs[i_res:]
    # metrics observed at request boundaries
    snap = e.stats()
    assert snap["ttft_ms_count"] == len(reqs)
    assert snap["tpot_ms_count"] == len(reqs)
    assert snap["queue_wait_ms_count"] >= len(reqs) + 1  # re-queued waits
    assert snap["preemptions"] == e.sched.preemptions


# =================================================== lifecycle: migration
def test_migration_span_ownership_across_replicas():
    """Forced live migration: the victim opens the migrate phase, the
    thief closes it — one shared tracer keeps the request's async span
    chain valid across both pids."""
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
    assert not req.done
    mode = thief.migrate_in(victim.migrate_out(0))
    assert mode == "live"
    guard = 200
    while thief.load > 0 and guard > 0:
        thief.step()
        guard -= 1
    assert req.done
    tr.flush()
    assert validate_chrome_trace(tr.to_chrome()) == []
    evs = _req_events(tr, 0)
    i_out = evs.index(("n", "migrated_out"))
    assert ("b", "migrate") in evs[i_out:]
    i_in = evs.index(("n", "migrated_in"))
    assert i_in > i_out
    assert evs[-1] == ("e", "request")
    # the migrate phase was opened on pid 0 and closed by pid 0's stamp
    # when pid 1 transitioned the request to decode
    mig_b = next(ev for ev in tr.events if ev.get("ph") == "b"
                 and ev["name"] == "migrate")
    assert mig_b["pid"] == 0
    dec_after = [ev for ev in tr.events if ev.get("ph") == "b"
                 and ev["name"] == "decode" and ev["ts"] > mig_b["ts"]]
    assert dec_after and dec_after[-1]["pid"] == 1
    # migration payload metrics observed on both ends
    assert victim.stats()["migrate_pack_ms_count"] == 1
    assert victim.stats()["migration_bytes_count"] == 1
    assert victim.stats()["migration_bytes_sum"] > 0
    assert thief.stats()["migrate_land_ms_count"] == 1
    # TTFT was stamped on the victim; the thief reports the finish
    assert thief.stats()["requests_finished"] == 1


# ======================================================= identity & stats
def test_nulltracer_and_tracer_token_identity():
    """Tracing must not perturb scheduling or sampling: untraced (the
    NullTracer default), and fully traced runs of the same workload emit
    identical greedy tokens."""
    def outs(tracer):
        e = Engine(CFG, PARAMS, block_size=8, num_blocks=5,
                   prefix_cache=True, prefill_chunk=4, tracer=tracer,
                   **KW)
        return _drive(e, [Request(rid=i, prompt=[3, i + 1, 4, 2],
                                  max_new=14 + i % 4) for i in range(5)])

    assert Engine(CFG, PARAMS, **KW).tracer is NULL_TRACER
    assert outs(None) == outs(Tracer())


def test_queue_wait_p95_within_ten_percent_across_merged_replicas():
    """queue_wait_ms has its own geometric buckets (no wider than 10%,
    1 ms .. 60 s), made wherever the histogram is first asked for, so
    replica registries merge and the merged p95 is within 10% of the
    sample's."""
    b = QUEUE_WAIT_MS_BUCKETS
    assert b[0] == 1.0 and b[-1] >= 60_000.0
    assert max(hi / lo for lo, hi in zip(b, b[1:])) <= 1.1
    rng = np.random.default_rng(11)
    regs, draws = [MetricsRegistry() for _ in range(3)], []
    for i, reg in enumerate(regs):
        v = np.exp(rng.normal(np.log(300.0) + i, 1.0, size=700))   # ms
        for x in v:
            reg.histogram("queue_wait_ms").observe(x)
        draws.append(v)
    merged = MetricsRegistry.merged(regs).histogram("queue_wait_ms")
    assert merged.bounds == b
    true = float(np.percentile(np.concatenate(draws), 95))
    assert abs(merged.quantile(0.95) - true) <= 0.1 * true, (
        merged.quantile(0.95), true)
    # Other histograms keep the default layout.
    assert MetricsRegistry().histogram("ttft_ms").bounds == \
        DEFAULT_MS_BUCKETS


def test_first_token_hold_once_per_fresh_admission_never_on_resume():
    """Forced preemption: each request is admitted fresh once and
    resumed at least once in all; only the fresh admissions observe a
    first-token hold."""
    kw = dict(max_slots=2, max_seq=32, pad_len=8, steps_per_sync=8)
    reqs = [Request(rid=i, prompt=[3, i + 1, 4, 2], max_new=14 + i % 4)
            for i in range(5)]
    e = Engine(CFG, PARAMS, block_size=8, num_blocks=5, **kw)
    _drive(e, reqs)
    assert e.sched.preemptions > 0, "pool sizing must force preemption"
    hold = e.metrics.histogram("first_token_hold_ms")
    assert hold.count == len(reqs) == \
        e.sched.admissions - e.sched.preemptions
    assert hold.vmin >= 0.0
    # Admissions with no preemption observe it once each.
    c = Engine(CFG, PARAMS, **kw)
    _drive(c, [Request(rid=i, prompt=[3, i + 1], max_new=5)
               for i in range(3)])
    assert c.metrics.histogram("first_token_hold_ms").count == 3


def test_backlogged_decode_counters_count_only_calls_with_a_queue():
    e = Engine(CFG, PARAMS, **KW)
    seen = []
    decode = e._decode_n

    def spy(params, tokens, cache, bt, lens, budget, key):
        seen.append((len(e.queue), int((np.asarray(lens) >= 0).sum())))
        return decode(params, tokens, cache, bt, lens, budget, key)

    e._decode_n = spy
    _drive(e, [Request(rid=i, prompt=[3, i + 1, 4], max_new=6 + i)
               for i in range(5)])
    backlogged = [active for queued, active in seen if queued]
    assert backlogged and len(backlogged) < len(seen)
    snap = e.stats()
    assert snap["decode_calls_backlogged"] == len(backlogged)
    assert snap["decode_slots_backlogged"] == sum(backlogged)


def test_paged_decode_kv_step_counters_follow_slot_lengths():
    """Each paged decode dispatch adds slots x grid steps a slot per
    position to ``paged_decode_kv_steps``, and to ``..._live`` the steps
    that the windows of the tokens each slot emitted reach (lens+1 ..
    lens+k for k tokens); live never exceeds the total. The smoke model's
    16-wide heads walk one block of one kv head a step."""
    from repro.kernels.paged_decode import paged_decode_grid

    e = Engine(CFG, PARAMS, **KW)
    P, steps = paged_decode_grid(*e._decode_grid)
    assert (P, steps) == (1, CFG.n_kv_heads * e.max_blocks)
    seen = []
    decode = e._decode_n

    def spy(params, tokens, cache, bt, lens, budget, key):
        out = decode(params, tokens, cache, bt, lens, budget, key)
        seen.append((np.asarray(lens).copy(), np.asarray(out[0]).copy()))
        return out

    e._decode_n = spy
    _drive(e, [Request(rid=i, prompt=[3, i + 1, 4], max_new=6 + i)
               for i in range(5)])
    snap = e.stats()
    n = e.steps_per_sync
    assert seen and snap["paged_decode_pages_per_step"] == P
    assert snap["paged_decode_kv_steps"] == (
        len(seen) * n * e.max_slots * steps)
    live = 0
    for lens, buf in seen:
        for i, L in enumerate(lens.tolist()):
            k = int((buf[:, i] >= 0).sum())
            assert k == 0 or L >= 0
            live += CFG.n_kv_heads * sum(
                -(-w // e.block_size) for w in range(L + 1, L + k + 1))
    assert snap["paged_decode_kv_steps_live"] == live
    assert 0 < live < snap["paged_decode_kv_steps"]


def test_paged_decode_kv_steps_live_multi_step_table_and_idle():
    """At a 128-lane head dim a grid step copies many pages of every kv
    head: windows that cross a step edge inside the burst count the extra
    step; a dispatch where no slot emitted counts no live step, only the
    grid."""
    import dataclasses

    from repro.kernels.paged_decode import paged_decode_grid

    cfg = dataclasses.replace(CFG, head_dim=128)
    e = Engine(cfg, init_lm(jax.random.key(1), cfg),
               max_slots=4, max_seq=2048, pad_len=8, steps_per_sync=8,
               block_size=8)
    P, steps = paged_decode_grid(*e._decode_grid)
    step_tokens = P * e.block_size
    assert P > 1 and steps == e.max_blocks // P > 1
    grid = 8 * 4 * steps
    lens = np.array([-1, 0, step_tokens - 3, 2040], np.int32)
    emitted = np.zeros((8, 4), bool)
    emitted[:, 1] = emitted[:, 2] = True
    emitted[:7, 3] = True       # slot 3 stops at max_seq - 1
    e._count_kv_steps(lens, emitted)
    snap = e.stats()
    assert snap["paged_decode_pages_per_step"] == P
    assert snap["paged_decode_kv_steps"] == grid
    # slot 1: windows 1..8, one step each; slot 2: windows step_tokens-2
    # .. step_tokens+5, the last 5 cross into a second step; slot 3:
    # windows 2041..2047.
    want = 8 + (3 + 5 * 2) + 7 * -(-2041 // step_tokens)
    assert snap["paged_decode_kv_steps_live"] == want
    e._count_kv_steps(np.array([0, 9, 100, 7], np.int32),
                      np.zeros((8, 4), bool))
    snap = e.stats()
    assert snap["paged_decode_kv_steps"] == 2 * grid
    assert snap["paged_decode_kv_steps_live"] == want


def test_stats_is_registry_view_and_merge_superset():
    """Engine.stats() == metrics snapshot; merged fabric keys are a
    superset of every per-replica snapshot's keys (the satellite
    regression: no more hand-rolled drift between the three report
    sites)."""
    engines = [Engine(CFG, PARAMS, block_size=8,
                      prefix_cache=True, replica_id=i, **KW)
               for i in range(2)]
    bal = GLBReplicaBalancer(engines, migrate=True)
    for i in range(6):
        bal.submit(Request(rid=i, prompt=[3, i + 1, 4, 2], max_new=8))
    bal.run(max_steps=300)
    snaps = [e.stats() for e in engines]
    for e, snap in zip(engines, snaps):
        assert snap == e.metrics.snapshot()
        assert snap["prefix_hit_rate_pct"] == round(
            100 * e.prefix_cache.hit_rate, 1)
    merged = bal.collect()
    for snap in snaps:
        assert set(merged) >= set(snap), set(snap) - set(merged)
    # fabric_summary accepts the pre-merged registry view directly
    text = fabric_summary(merged, title="replica fabric", places=2)
    assert text.splitlines()[0] == "replica fabric: 2 places"
    assert "ttft_ms_p99" in text
    assert fabric_summary(snaps, title="replica fabric") .splitlines()[0] \
        == "replica fabric: 2 places"
    # merged registry: histogram quantiles of the merged distribution
    msnap = bal.merged_metrics().snapshot()
    assert msnap["ttft_ms_count"] == merge_place_stats(snaps)[
        "ttft_ms_count"]["total"]


# ===================================================== GLB core sim tracing
def test_run_sim_traced_matches_untraced():
    prob = uts_problem(depth=4)
    g1 = GLB(prob, GLBParams(n=8), P=4)
    r1 = g1.run(seed=0)
    tr = Tracer()
    g2 = GLB(prob, GLBParams(n=8), P=4)
    r2 = g2.run(seed=0, tracer=tr)
    assert int(np.asarray(r1)) == int(np.asarray(r2))
    assert g1.supersteps == g2.supersteps
    for f in g1.stats:
        assert np.array_equal(np.asarray(g1.stats[f]),
                              np.asarray(g2.stats[f])), f
    tr.flush()
    assert validate_chrome_trace(tr.to_chrome()) == []
    spans = [e for e in tr.events if e.get("ph") == "B"
             and e["name"] == "superstep"]
    assert len(spans) == g2.supersteps
    loads = [e for e in tr.events if e.get("ph") == "C"
             and e["name"] == "glb_load"]
    assert loads and loads[-1]["args"]["total"] == 0.0
