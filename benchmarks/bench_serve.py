"""Serving engine throughput: tokens/s and host syncs per token for the
jitted multi-step ``lax.fori_loop`` decode over the paged KV pool
(on-device sampling, one host drain per N positions), and max concurrent
sequences at a fixed cache memory of SLOTS full-length rows (the paged
packing win) — plus the PR 4 policy layer: the shared-system-prompt
workload (radix prefix cache: hit rate and prefill tokens saved) and
TTFT p50/p99 for short requests arriving behind long-prompt
admissions, with and without chunked prefill — plus the PR 5 replica fabric: a skewed workload (one
hot replica wedged on long RUNNING sequences, one cold) comparing
queue-only stealing (the cold replica can only pick up sequences the hot
one preempt-thrashes back to its queue, paying a chunked recompute
prefill per move) against live KV migration (running sequences ship
their written blocks at the first balance pass). Makespan in supersteps
is the deterministic headline metric for that pair — plus the PR 6
observability contract: the same paged-loop workload driven tracer-off
vs tracer-on (``serve_obs_overhead``: the disabled path is one
attribute check, the on-path must stay within a few percent and add
ZERO host syncs), registry-derived TTFT quantiles printed beside the
numpy ones on the TTFT rows, and the live-migration arm run under a
real ``Tracer`` whose validated Chrome trace JSON is written to
``BENCH_serve_trace.json`` (uploaded by CI next to the bench JSON).

PR 10 adds the predictive-balancing pair on a wedge+backlog workload:
short requests queued behind a hot replica's wedged slots, reactive
stealing vs cost-modeled diffusion (``serve_skew_predictive``, TTFT in
deterministic supersteps) plus the reactive-parity row
(``serve_skew_parity``: cost model attached, predictor off, decision
log byte-identical to plain reactive — the §16 contract).

Steady-state measurement: all slots admitted and kernels compiled before
the timer starts, so the numbers isolate the engine decode loop itself.
The model is a deliberately tiny 1-layer config — on CPU the per-token
*dispatch + host-sync* overhead is the quantity the fast path removes, and
a small model keeps it from being buried under compute that a TPU would
finish orders of magnitude faster.
"""
import dataclasses
import time

import jax
import numpy as np

from repro.configs import ARCHS
from repro.models import init_lm
from repro.obs import (FlightRecorder, Tracer, quantiles_from_values,
                       validate_chrome_trace)
from repro.serve.engine import Engine, GLBReplicaBalancer, Request

STEPS_PER_SYNC = 16
MAX_NEW = 96
MAX_SEQ = 128
SLOTS = 4
PAGED_BS = 8                      # pool block size (tokens)
SHORT_MAX_NEW = 16                # packing workload: short requests

# shared-prefix workload: a long system prompt every request begins with
SYS_PROMPT = [(7 * k + 3) % 250 + 1 for k in range(40)]
PREFIX_PAD = 64                   # prompt bucket for the prefix workload
N_PREFIX_REQS = 16
# TTFT workload: short requests arriving behind long-prompt admissions
TTFT_LONG_PROMPT = [(5 * k + 2) % 250 + 1 for k in range(120)]
TTFT_CHUNK = 16
# skewed-workload fabric: one hot replica wedged on long RUNNING
# sequences (queue empty, slots saturated), one cold — the scenario
# queue-only stealing cannot fix and live KV migration can
SKEW_REPLICAS = 2
SKEW_SLOTS = 4
SKEW_MAX_NEW = 110
SKEW_BLOCKS = 36        # fits 2 full seqs + lookahead comfortably, NOT 4:
                        # the queue-only arm must preempt-thrash instead
SKEW_CHUNK = 16         # chunked prefill makes a recompute resume COST
                        # supersteps — the work live migration avoids
TRACE_PATH = "BENCH_serve_trace.json"   # Chrome trace artifact (CI upload)
# predictive-vs-reactive arm (DESIGN.md §16): the same wedged fabric
# plus a backlog of short requests queued behind the wedge. Reactive
# stealing only moves the backlog when the cold replica starves;
# predictive diffusion moves it as soon as predicted block-seconds are
# imbalanced. TTFT is measured in SUPERSTEPS (first-token superstep per
# short request), so the headline comparison is deterministic and gates
# hard; the parity arm re-runs the reactive scenario with the cost
# model ATTACHED but predictive OFF and must reproduce the reactive
# decision log byte-for-byte.
PRED_LONG_MAX_NEW = 64
PRED_SHORT_MAX_NEW = 8
PRED_SHORTS = 4
PRED_TRACE_PATH = "BENCH_serve_predictive_trace.json"
# crash-recovery chaos arm (DESIGN.md §15): a 3-replica fabric loses one
# replica mid-flight; the deterministic acceptance metrics are zero lost
# requests, greedy-token-identical outputs vs an identical clean fabric,
# and termination (no wedge)
CHAOS_REPLICAS = 3
CHAOS_REQS = 6
CHAOS_MAX_NEW = 24
CHAOS_CRASH_AT = 1
FLIGHT_CAPACITY = 32    # below the run's event count: the flight row
                        # must exercise ring WRAPAROUND, not ample
                        # capacity, and still dump a valid trace
                        # (asserted — the steady-state workload emits
                        # ~60 ring events)


def _bench_cfg():
    return dataclasses.replace(
        ARCHS["tinyllama-1.1b"].smoke(), name="bench-serve-tiny",
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
        d_ff=64, vocab=256, scan_layers=False,
    )


def _drive(engine):
    for r in range(engine.max_slots):
        engine.submit(Request(rid=r, prompt=[3, r + 1, 4], max_new=MAX_NEW))
    engine.step()  # admits every slot + compiles prefill/decode
    toks0, syncs0 = engine.tokens_out, engine.host_syncs
    t0 = time.time()
    while engine.load > 0:
        engine.step()
    dt = time.time() - t0
    toks = engine.tokens_out - toks0
    return toks / dt, (engine.host_syncs - syncs0) / max(toks, 1)


def _best_of(make_engine, drive, repeats=2):
    """Best tokens/s over fresh runs — engine-vs-engine ratios on a noisy
    shared CPU need the envelope, not one sample."""
    best = None
    for _ in range(repeats):
        out = drive(make_engine())
        if best is None or out[0] > best[0]:
            best = out
    return best


def _drive_packing(engine, n_reqs):
    """Flood with short requests; measure steady throughput and the peak
    number of concurrently-running sequences."""
    for r in range(n_reqs):
        engine.submit(Request(rid=r, prompt=[3, r % 250 + 1, 4],
                              max_new=SHORT_MAX_NEW))
    engine.step()  # compile + first admissions
    toks0 = engine.tokens_out
    t0 = time.time()
    while engine.load > 0:
        engine.step()
    dt = time.time() - t0
    return (engine.tokens_out - toks0) / dt, engine.peak_running


def _drive_prefix(engine, n_reqs):
    """Shared-system-prompt workload: every request = SYS_PROMPT + a short
    unique tail. One warm-up request compiles the miss and hit paths and
    seeds the cache (the steady-state a shared system prompt lives in),
    then counters reset and the timed wave runs. Returns
    (tokens/s, prefill tokens saved fraction)."""
    for wid in (10_000, 10_001):    # first = miss path, second = hit path
        engine.submit(Request(rid=wid, prompt=SYS_PROMPT + [wid % 250, 5, 7],
                              max_new=2))
        while engine.load > 0:
            engine.step()
    if engine.prefix_cache is not None:
        c = engine.prefix_cache
        c.hits = c.misses = c.tokens_reused = 0
    reqs = [Request(rid=r, prompt=SYS_PROMPT + [r % 250 + 1, 5, 7],
                    max_new=SHORT_MAX_NEW) for r in range(n_reqs)]
    for r in reqs:
        engine.submit(r)
    toks0 = engine.tokens_out
    t0 = time.time()
    while engine.load > 0:
        engine.step()
    dt = time.time() - t0
    total_prefix = sum(min(len(r.prompt), engine.pad_len) for r in reqs)
    saved = (engine.prefix_cache.tokens_reused / total_prefix
             if engine.prefix_cache is not None else 0.0)
    return (engine.tokens_out - toks0) / dt, saved


def _drive_ttft(engine):
    """Staggered arrivals: a stream of short requests with long-prompt
    requests landing mid-stream. TTFT = wall-clock from submit to first
    output token, reported for the SHORT requests (the ones a monolithic
    long prefill starves — the long request itself legitimately pays for
    its own chunking). max_prefill_tokens is the largest single-step
    prefill the engine ever ran — THE quantity chunking bounds (on this
    deliberately tiny CPU model, per-step dispatch overhead swamps
    prefill compute, so the wall-clock columns mostly show that
    overhead; on a real model the per-step work bound is what keeps
    decode latency flat). Returns (short p50 ms, short p99 ms,
    max step ms, max prefill tokens in one step)."""
    schedule = []                   # (arrival_step, request, is_short)
    rid = 0
    for s in range(24):
        if s % 8 == 3:
            schedule.append((s, Request(
                rid=rid, prompt=list(TTFT_LONG_PROMPT), max_new=4), False))
            rid += 1
        schedule.append((s, Request(
            rid=rid, prompt=[3, rid % 250 + 1, 4], max_new=4), True))
        rid += 1
    # Warm-up outside the timer: same prompt shapes as the schedule, so
    # every prefill/chunk/decode trace is compiled before TTFT is measured.
    for req in (Request(rid=10_000, prompt=list(TTFT_LONG_PROMPT),
                        max_new=2),
                Request(rid=10_001, prompt=[3, 5, 4], max_new=2)):
        engine.submit(req)
    while engine.load > 0:
        engine.step()
    per_step_prefill = {}
    orig_chunk = engine._run_prefill_chunk

    def spy(slot, req, start, end, last):
        per_step_prefill[engine.steps] = (
            per_step_prefill.get(engine.steps, 0) + (end - start)
        )
        return orig_chunk(slot, req, start, end, last)

    engine._run_prefill_chunk = spy
    submit_t, first_t = {}, {}
    pending = list(schedule)
    step, max_step = 0, 0.0
    while pending or engine.load > 0:
        while pending and pending[0][0] <= step:
            _, req, _ = pending.pop(0)
            submit_t[req.rid] = time.time()
            engine.submit(req)
        t0 = time.time()
        engine.step()
        max_step = max(max_step, time.time() - t0)
        now = time.time()
        for _, req, _ in schedule:
            if req.rid not in first_t and req.out:
                first_t[req.rid] = now
        step += 1
        if step > 5000:
            break
    shorts = [
        1e3 * (first_t[req.rid] - submit_t[req.rid])
        for _, req, is_short in schedule
        if is_short and req.rid in first_t
    ]
    # Same samples through the metrics registry's fixed-bucket histogram:
    # the registry quantiles must agree with numpy's to within a bucket,
    # proving the Prometheus/merged view reports the numbers the bench does.
    reg_p50, reg_p99 = quantiles_from_values(shorts, (0.5, 0.99))
    return (float(np.percentile(shorts, 50)),
            float(np.percentile(shorts, 99)), 1e3 * max_step,
            max(per_step_prefill.values(), default=0), reg_p50, reg_p99)


def _mk_skew_engines(cfg, params, tracer=None):
    """One fabric: identical paged replicas whose pool fits ~2 full-length
    sequences with lookahead, not 4. pad_len == max_seq keeps every
    recompute prefill on ONE trace so wall-clock compares engines, not
    retraces."""
    return [
        Engine(cfg, params, max_slots=SKEW_SLOTS, max_seq=MAX_SEQ,
               pad_len=MAX_SEQ, steps_per_sync=STEPS_PER_SYNC,
               block_size=PAGED_BS, num_blocks=SKEW_BLOCKS,
               prefill_chunk=SKEW_CHUNK,
               token_budget=SKEW_SLOTS * STEPS_PER_SYNC,
               tracer=tracer, replica_id=i)
        for i in range(SKEW_REPLICAS)
    ]


def _drive_skew(engines, migrate, rid0=0, tracer=None):
    """All requests land on replica 0 and are admitted there BEFORE the
    balancer runs — the wedged state: queue empty, every slot busy on a
    long sequence, N-1 cold replicas idle. Queue-only stealing can only
    move work after watermark preemption kicks a sequence back to the
    queue (losing its written KV to a recompute on the thief); live
    migration sheds running sequences with their KV intact at the first
    balance pass. Returns (makespan_s, supersteps, preemptions,
    migrations)."""
    bal = GLBReplicaBalancer(engines, migrate=migrate, tracer=tracer)
    reqs = [Request(rid=rid0 + r, prompt=[3, r + 1, 4],
                    max_new=SKEW_MAX_NEW) for r in range(SKEW_SLOTS)]
    for r in reqs:
        bal.submit(r, rr=0)
    engines[0].step()           # wedge: hot replica admits every slot
    p0 = sum(e.sched.preemptions for e in engines)
    t0 = time.time()
    bal.run(max_steps=2000)
    dt = time.time() - t0
    assert all(r.done for r in reqs)
    preempts = sum(e.sched.preemptions for e in engines) - p0
    return dt, bal.supersteps, preempts, bal.migrations


def _skew_arm(cfg, params, migrate, tracer=None):
    """Warm run on fresh engines (compiles every trace the arm hits),
    then the timed run REUSES the drained engines so both arms measure
    steady-state scheduling, not per-engine jit closures compiling.
    ``tracer`` records BOTH runs (the warm wave reads as a second
    request batch in the artifact); scheduling is deterministic so the
    gated superstep/preemption counts are tracer-independent."""
    engines = _mk_skew_engines(cfg, params, tracer=tracer)
    _drive_skew(engines, migrate, rid0=10_000, tracer=tracer)
    return _drive_skew(engines, migrate, rid0=0, tracer=tracer)


def _drive_skew_pred(engines, bal, rid0=0):
    """Wedge + backlog: PRED long requests admitted into every replica-0
    slot, then short requests queued behind them, cold replica idle.
    Drives the fabric superstep-by-superstep recording the superstep at
    which each short request produced its first token — TTFT in
    SUPERSTEPS, deterministic under greedy decode + deterministic
    matching. Returns (wall_s, supersteps, preemptions, short TTFT p99
    in supersteps, decision log)."""
    longs = [Request(rid=rid0 + r, prompt=[3, r + 1, 4],
                     max_new=PRED_LONG_MAX_NEW, tenant="long")
             for r in range(SKEW_SLOTS)]
    for r in longs:
        bal.submit(r, rr=0)
    engines[0].step()           # wedge: hot replica admits every slot
    shorts = [Request(rid=rid0 + 100 + r, prompt=[5, r + 1, 6],
                      max_new=PRED_SHORT_MAX_NEW, tenant="short")
              for r in range(PRED_SHORTS)]
    for r in shorts:
        bal.submit(r, rr=0)
    p0 = sum(e.sched.preemptions for e in engines)
    first = {}
    t0 = time.time()
    for _ in range(2000):
        if bal.balance():
            break
        for e in engines:
            e.step()
        bal.supersteps += 1
        for r in shorts:
            if r.rid not in first and r.out:
                first[r.rid] = bal.supersteps
    dt = time.time() - t0
    assert all(r.done for r in longs + shorts)
    preempts = sum(e.sched.preemptions for e in engines) - p0
    ttfts = [first[r.rid] for r in shorts]
    return (dt, bal.supersteps, preempts,
            float(np.percentile(ttfts, 99)), list(bal.decisions))


def _pred_arm(cfg, params, cost_model=None, predictive=False,
              tracer=None):
    """Warm run compiles every trace (and, with a cost model, seeds the
    per-tenant decode histograms — the steady-state an online predictor
    lives in), then the timed run reuses the drained engines under a
    FRESH balancer so its decision log covers exactly one scenario."""
    engines = _mk_skew_engines(cfg, params, tracer=tracer)

    def mk_bal():
        return GLBReplicaBalancer(engines, migrate=True, tracer=tracer,
                                  cost_model=cost_model,
                                  predictive=predictive)

    _drive_skew_pred(engines, mk_bal(), rid0=20_000)
    bal = mk_bal()
    return _drive_skew_pred(engines, bal, rid0=0), bal


def _chaos_arm(cfg, params, faults=None):
    """One fabric run for the crash-recovery row: CHAOS_REQS requests
    round-robined over CHAOS_REPLICAS paged replicas; with ``faults``,
    replica 0 crashes at superstep CHAOS_CRASH_AT while its work is
    still in flight. Scheduling and recovery are deterministic (greedy
    decode, heartbeat window on the superstep clock), so everything but
    wall-clock gates hard."""
    engines = [
        Engine(cfg, params, max_slots=2, max_seq=MAX_SEQ, pad_len=8,
               steps_per_sync=STEPS_PER_SYNC, block_size=PAGED_BS,
               num_blocks=32, replica_id=i)
        for i in range(CHAOS_REPLICAS)
    ]
    bal = GLBReplicaBalancer(engines, migrate=True, faults=faults)
    reqs = [Request(rid=r, prompt=[3, r + 1, 4], max_new=CHAOS_MAX_NEW)
            for r in range(CHAOS_REQS)]
    for r in reqs:
        bal.submit(r)
    t0 = time.time()
    status = bal.run(max_steps=2000)
    dt = time.time() - t0
    lost = sum(1 for r in reqs if not r.done)
    return dt, status, bal, lost, [list(r.out) for r in reqs]


def run():
    cfg = _bench_cfg()
    params = init_lm(jax.random.key(0), cfg)

    # The paged fori_loop engine at steady state: SLOTS sequences of
    # MAX_SEQ rows' worth of pool blocks, one host drain per
    # STEPS_PER_SYNC positions. One untimed run first: the process's
    # first decode pays warm-up the traced arms below would not.
    rows = SLOTS * MAX_SEQ
    loop_kw = dict(max_slots=SLOTS, max_seq=MAX_SEQ, pad_len=8,
                   steps_per_sync=STEPS_PER_SYNC, block_size=PAGED_BS,
                   num_blocks=rows // PAGED_BS)
    _drive(Engine(cfg, params, **loop_kw))
    tps_pg, spt_pg = _best_of(lambda: Engine(cfg, params, **loop_kw),
                              _drive)

    # Observability overhead: the identical paged-loop workload with a
    # LIVE Tracer (engine-step spans, load/pool counters, request
    # lifecycle events, metrics observations). tracer-off IS tps_pg —
    # the disabled path is one attribute check on NULL_TRACER. The
    # deterministic invariant is syncs/token: tracing must add ZERO
    # host syncs (events are host-side dict appends, never device
    # drains); tokens/s overhead gates advisorily in compare.py.
    tps_on, spt_on = _best_of(
        lambda: Engine(cfg, params, tracer=Tracer(), **loop_kw), _drive)
    obs_overhead = 100.0 * (1.0 - tps_on / max(tps_pg, 1e-9))

    # Flight-recorder overhead: the same workload tracing into a ring
    # bounded FAR below the run's event count (forced wraparound), i.e.
    # always-on tracing at fixed memory. Deterministic invariants:
    # syncs/token unchanged (HARD gate) and the wrapped ring still
    # dumps a validator-clean trace (dump_valid, HARD gate).
    flights = []

    def _mk_flight():
        fr = FlightRecorder(capacity=FLIGHT_CAPACITY)
        flights.append(fr)
        return Engine(cfg, params, tracer=fr, **loop_kw)

    tps_fl, spt_fl = _best_of(_mk_flight, _drive)
    flight = flights[-1]
    flight_valid = int(validate_chrome_trace(flight.dump()) == [])
    assert len(flight.events) <= FLIGHT_CAPACITY
    assert flight.dropped > 0, (
        "flight bench must wrap the ring; raise the workload or shrink "
        f"FLIGHT_CAPACITY (events={len(flight.events)})"
    )

    # Packing at fixed HBM: `rows` of cache memory would hold SLOTS
    # full-length (max_seq) reservations; the paged engine packs by
    # actual length.
    n_reqs = 3 * rows // (PAGED_BS + SHORT_MAX_NEW)
    tps_pp, conc_p = _best_of(
        lambda: Engine(cfg, params, **dict(loop_kw,
                                           max_slots=rows // PAGED_BS)),
        lambda e: _drive_packing(e, n_reqs),
    )

    # Shared-system-prompt workload: identical engine/pool, prefix cache
    # off vs on. "saved" = fraction of prefill positions served from
    # cached blocks instead of recomputed.
    pool_kw = dict(max_slots=SLOTS, max_seq=MAX_SEQ, pad_len=PREFIX_PAD,
                   steps_per_sync=STEPS_PER_SYNC,
                   block_size=PAGED_BS, num_blocks=rows // PAGED_BS)
    tps_nc, _ = _best_of(lambda: Engine(cfg, params, **pool_kw),
                         lambda e: _drive_prefix(e, N_PREFIX_REQS))
    (tps_cache, saved) = _best_of(
        lambda: Engine(cfg, params, prefix_cache=True, **pool_kw),
        lambda e: _drive_prefix(e, N_PREFIX_REQS),
    )

    # TTFT with and without chunked prefill. Both arms run the chunk-mode
    # admission path with pad_len = MAX_SEQ (the 120-token prompt must
    # not be bucket-truncated). The baseline admits each prompt as ONE
    # monolithic chunk with no token budget (pre-chunking behavior:
    # unbounded per-step prefill); the chunked arm bounds every step by
    # the shared token budget.
    ttft_kw = dict(pool_kw, pad_len=MAX_SEQ)
    p50_nc_t, p99_nc_t, step_nc, pf_nc, rp50_nc, rp99_nc = _drive_ttft(
        Engine(cfg, params, prefill_chunk=MAX_SEQ, **ttft_kw)
    )
    p50_ck, p99_ck, step_ck, pf_ck, rp50_ck, rp99_ck = _drive_ttft(
        Engine(cfg, params, prefill_chunk=TTFT_CHUNK,
               token_budget=SLOTS * STEPS_PER_SYNC, **ttft_kw)
    )

    # Skewed fabric: queue-only stealing vs live KV migration. Makespan
    # in SUPERSTEPS is the deterministic acceptance metric (greedy
    # decode + deterministic matching); wall-clock rides along.
    dt_q, steps_q, pre_q, _ = _skew_arm(cfg, params, migrate=False)
    # The live-migration arm doubles as the trace artifact: the whole
    # fabric run (admissions, preemptions, steal/migration timeline)
    # lands in BENCH_serve_trace.json for the CI upload. Scheduling is
    # deterministic, so the gated superstep counts are unaffected; only
    # the advisory wall-clock column carries the (small) tracer cost.
    tracer = Tracer()
    dt_m, steps_m, pre_m, migs = _skew_arm(cfg, params, migrate=True,
                                           tracer=tracer)
    tracer.write(TRACE_PATH)
    problems = validate_chrome_trace(tracer.to_chrome())
    assert not problems, problems

    # Predictive vs reactive on the wedge+backlog scenario. Everything
    # gated is deterministic (supersteps, preemptions, first-token
    # supersteps, decision-log identity), so the ISSUE contract asserts
    # inline AND gates hard in compare.py: predictive must terminate in
    # no more supersteps with no more preemptions and no worse short
    # TTFT, and the parity arm (cost model attached, predictor OFF)
    # must reproduce the reactive decision log exactly.
    from repro.serve.cost import CostModel
    (dt_r, steps_r, pre_r, ttft_r, dec_r), _ = _pred_arm(cfg, params)
    (dt_par, steps_par, _, _, dec_par), _ = _pred_arm(
        cfg, params, cost_model=CostModel())
    ptracer = Tracer()
    (dt_p, steps_p, pre_p, ttft_p, _), bal_p = _pred_arm(
        cfg, params, cost_model=CostModel(), predictive=True,
        tracer=ptracer)
    ptracer.write(PRED_TRACE_PATH)
    assert not validate_chrome_trace(ptracer.to_chrome())
    parity = int(dec_par == dec_r and steps_par == steps_r)
    assert parity == 1, (
        f"reactive parity broken: {dec_par} != {dec_r} "
        f"or {steps_par} != {steps_r}"
    )
    assert steps_p <= steps_r, (steps_p, steps_r)
    assert pre_p <= pre_r, (pre_p, pre_r)
    assert ttft_p <= ttft_r, (ttft_p, ttft_r)
    cost_snap = bal_p.cost_model.snapshot()

    # Crash recovery: identical fabric clean vs one replica crashed
    # mid-flight. The crashed arm must terminate with zero lost
    # requests and greedy-token-identical outputs (HARD gates); the
    # superstep makespan quantifies the recovery detour.
    from repro.serve.faults import FaultInjector
    _chaos_arm(cfg, params)                       # warm/compile
    dt_cl, st_cl, bal_cl, lost_cl, outs_cl = _chaos_arm(cfg, params)
    assert st_cl == "terminated" and lost_cl == 0
    dt_cr, st_cr, bal_cr, lost_cr, outs_cr = _chaos_arm(
        cfg, params,
        faults=FaultInjector().crash(0, at=CHAOS_CRASH_AT),
    )
    assert st_cr == "terminated", "crashed fabric wedged"
    readmitted = bal_cr.readmitted_queued + bal_cr.readmitted_running
    greedy_identical = int(outs_cr == outs_cl)

    # syncs per decoded *position* is the architectural constant: the
    # fori_loop engine drains once per steps_per_sync positions.
    return [
        ("serve_obs_overhead", 1e6 / max(tps_on, 1e-9),
         f"tok_s_on={tps_on:.1f};tok_s_off={tps_pg:.1f};"
         f"overhead_pct={obs_overhead:.1f};"
         f"syncs_per_tok_on={spt_on:.3f};"
         f"syncs_per_tok_off={spt_pg:.3f}"),
        ("serve_flight_overhead", 1e6 / max(tps_fl, 1e-9),
         f"tok_s={tps_fl:.1f};"
         f"vs_untraced={tps_fl / max(tps_pg, 1e-9):.2f}x;"
         f"syncs_per_tok={spt_fl:.3f};"
         f"ring_capacity={FLIGHT_CAPACITY};"
         f"ring_events={len(flight.events)};"
         f"dropped_events={flight.dropped};"
         f"dump_valid={flight_valid}"),
        ("serve_paged_loop", 1e6 / max(tps_pg, 1e-9),
         f"tok_s={tps_pg:.1f};syncs_per_tok={spt_pg:.3f};"
         f"syncs_per_pos={1.0 / STEPS_PER_SYNC:.3f};"
         f"block_size={PAGED_BS}"),
        ("serve_packing_paged", 1e6 / max(tps_pp, 1e-9),
         f"tok_s={tps_pp:.1f};max_concurrent={conc_p};"
         f"hbm_rows={rows}"),
        ("serve_prefix_cache", 1e6 / max(tps_cache, 1e-9),
         f"tok_s={tps_cache:.1f};vs_no_cache="
         f"{tps_cache / max(tps_nc, 1e-9):.2f}x;"
         f"prefill_tokens_saved={saved:.0%};"
         f"sys_prompt_len={len(SYS_PROMPT)};reqs={N_PREFIX_REQS}"),
        ("serve_ttft_nochunk", 1e3 * p50_nc_t,
         f"short_ttft_p50_ms={p50_nc_t:.1f};"
         f"short_ttft_p99_ms={p99_nc_t:.1f};"
         f"reg_p50_ms={rp50_nc:.1f};reg_p99_ms={rp99_nc:.1f};"
         f"max_step_ms={step_nc:.1f};"
         f"max_prefill_tokens_per_step={pf_nc};"
         f"long_prompt={len(TTFT_LONG_PROMPT)}"),
        ("serve_ttft_chunked", 1e3 * p50_ck,
         f"short_ttft_p50_ms={p50_ck:.1f};short_ttft_p99_ms={p99_ck:.1f};"
         f"reg_p50_ms={rp50_ck:.1f};reg_p99_ms={rp99_ck:.1f};"
         f"max_step_ms={step_ck:.1f};"
         f"max_prefill_tokens_per_step={pf_ck};chunk={TTFT_CHUNK};"
         f"p99_vs_nochunk={p99_ck / max(p99_nc_t, 1e-9):.2f}x;"
         f"max_step_vs_nochunk={step_ck / max(step_nc, 1e-9):.2f}x"),
        ("serve_skew_queue_steal", 1e6 * dt_q,
         f"makespan_s={dt_q:.2f};makespan_steps={steps_q};"
         f"preemptions={pre_q};replicas={SKEW_REPLICAS};"
         f"slots={SKEW_SLOTS};pool_blocks={SKEW_BLOCKS}"),
        ("serve_skew_live_migration", 1e6 * dt_m,
         f"makespan_s={dt_m:.2f};makespan_steps={steps_m};"
         f"preemptions={pre_m};migrations={migs};"
         f"steps_vs_queue_steal={steps_m / max(steps_q, 1):.2f}x;"
         f"wall_vs_queue_steal={dt_m / max(dt_q, 1e-9):.2f}x;"
         f"trace_events={len(tracer.events)};trace={TRACE_PATH}"),
        ("serve_skew_predictive", 1e6 * dt_p,
         f"makespan_s={dt_p:.2f};makespan_steps={steps_p};"
         f"reactive_steps={steps_r};"
         f"steps_vs_reactive={steps_p / max(steps_r, 1):.2f}x;"
         f"preemptions={pre_p};reactive_preemptions={pre_r};"
         f"ttft_p99_steps={ttft_p:.0f};"
         f"reactive_ttft_p99_steps={ttft_r:.0f};"
         f"diffusion_moves={bal_p.diffusion_moves};"
         f"predictions={cost_snap['cost_predictions']};"
         f"mean_abs_err_tokens={cost_snap['cost_mean_abs_err_tokens']:.1f};"
         f"wall_vs_reactive={dt_p / max(dt_r, 1e-9):.2f}x;"
         f"trace_events={len(ptracer.events)};trace={PRED_TRACE_PATH}"),
        ("serve_skew_parity", 1e6 * dt_par,
         f"decisions_identical={parity};decisions={len(dec_par)};"
         f"makespan_steps={steps_par};reactive_steps={steps_r}"),
        ("serve_crash_recovery", 1e6 * dt_cr,
         f"makespan_s={dt_cr:.2f};makespan_steps={bal_cr.supersteps};"
         f"clean_steps={bal_cl.supersteps};"
         f"requests_lost={lost_cr};readmitted={readmitted};"
         f"replicas_dead={bal_cr.replicas_dead};"
         f"terminated={int(st_cr == 'terminated')};"
         f"greedy_identical={greedy_identical};"
         f"steps_vs_clean="
         f"{bal_cr.supersteps / max(bal_cl.supersteps, 1):.2f}x;"
         f"wall_vs_clean={dt_cr / max(dt_cl, 1e-9):.2f}x;"
         f"crash_at={CHAOS_CRASH_AT};replicas={CHAOS_REPLICAS}"),
    ]


if __name__ == "__main__":
    for r in run():
        print(",".join(map(str, r)))
