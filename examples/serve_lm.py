"""Serving example: continuous batching across N replicas with the GLB
request balancer (paper's library applied to serving). All requests land on
replica 0; the balancer's lifeline matching redistributes them.

    PYTHONPATH=src python examples/serve_lm.py            # paged KV pool
    PYTHONPATH=src python examples/serve_lm.py --prefix-cache \
        --prefill-chunk 8                                 # radix cache +
                                                          # chunked prefill
    PYTHONPATH=src python examples/serve_lm.py --replicas 3 \
        --migrate                                         # live KV migration
    PYTHONPATH=src python examples/serve_lm.py --replicas 3 \
        --chaos                                           # crash a replica,
                                                          # recover losslessly

Each replica runs the block-granular KV pool + the continuous-batching
scheduler (admission, watermark preemption) and the exit report
includes pool occupancy/fragmentation. ``--prefix-cache``
adds the radix prefix cache — requests here share a system prompt, so
later admissions fork the cached blocks instead of re-prefilling them —
and the report gains hit-rate / prefill-tokens-saved lines.
``--prefill-chunk N`` splits long prompt prefills into N-token chunks
interleaved with decode. ``--migrate`` arms the balancer's second steal
tier: a replica whose queue is empty but whose slots are all busy sheds
*running* sequences — their written KV blocks travel to the thief and
decoding resumes there greedy-token-identically (DESIGN.md §9). The
run ends via GLB termination detection (the balance pass's load vector)
and prints the fabric-level merged stats report.

``--predictive`` attaches the per-tenant decode-length cost model
(DESIGN.md §16): the balancer diffuses predicted block-seconds off
overloaded replicas BEFORE anyone starves, with the reactive lifeline
tiers as backstop; the exit report gains a predictive line (diffusion
moves, predictions scored, mean |error|). ``--slo-admission`` (needs
``--slo`` with a ``ttft_ms`` or ``queue_wait_ms`` target) makes each
scheduler admit urgent-first by predicted SLO slack and pace relaxed
admissions.

``--trace PATH`` records the whole run — request lifecycle spans across
replicas, engine steps, prefill chunks, steal/migration events — as
Chrome trace_event JSON: open the file at https://ui.perfetto.dev.
``--flight N`` records into a bounded ring of N events instead (the
black-box default for always-on tracing; the dump is balanced even
after wraparound). ``--slo ttft_ms=250,tpot_ms=50`` declares latency
targets — the exit report then states attainment and any burn-rate
alerts. ``--metrics`` prints the merged fabric metrics registry (TTFT /
TPOT / queue-wait percentiles and all counters) in Prometheus text
format at exit. Traced runs finish with the analyzer's fabric report:
request time attribution, per-replica utilization, steal efficiency
(DESIGN.md §14). See also README "Analyzing a trace".
"""
import argparse
import time

import jax

from repro.configs import ARCHS
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_lm
from repro.obs import (FlightRecorder, SLOMonitor, Tracer, analyze_trace,
                       parse_slo_spec, render_summary,
                       validate_chrome_trace)
from repro.serve.engine import Engine, GLBReplicaBalancer, Request

SYSTEM_PROMPT = [7, 3, 9, 2, 5, 8, 6, 4, 1, 2, 3, 4, 9, 9, 8, 7]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill budget")
    ap.add_argument("--replicas", type=int, default=2,
                    help="number of engine replicas in the fabric")
    ap.add_argument("--migrate", action="store_true",
                    help="steal LIVE sequences (KV migration) when a "
                         "victim's queue is empty but its slots are "
                         "saturated")
    ap.add_argument("--predictive", action="store_true",
                    help="cost-modeled diffusive balancing: move "
                         "predicted block-seconds off overloaded "
                         "replicas before starvation fires "
                         "(DESIGN.md §16)")
    ap.add_argument("--slo-admission", action="store_true",
                    help="SLO-aware admission ordering/pacing per "
                         "replica (requires --slo with a ttft_ms or "
                         "queue_wait_ms target)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write a Perfetto-loadable Chrome trace JSON "
                         "of the run to PATH")
    ap.add_argument("--flight", metavar="N", type=int, default=None,
                    help="trace into a bounded ring of N events "
                         "(FlightRecorder) instead of an unbounded "
                         "tracer; implies tracing even without --trace")
    ap.add_argument("--slo", metavar="SPEC", default=None,
                    help="declare SLO targets, e.g. "
                         "'ttft_ms=250,tpot_ms=50' (optionally "
                         "'ttft_ms=250@0.999'); the exit report states "
                         "attainment and burn-rate alerts")
    ap.add_argument("--metrics", action="store_true",
                    help="print the merged fabric metrics registry "
                         "(Prometheus text format) at exit")
    ap.add_argument("--chaos", action="store_true",
                    help="crash replica 0 at superstep 2 (DESIGN.md "
                         "§15): the heartbeat detector fences it, "
                         "lifelines re-wire, and its requests are "
                         "re-admitted on the survivors with identical "
                         "greedy tokens (requires >= 2 replicas)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = ARCHS["tinyllama-1.1b"].smoke()
    params = init_lm(jax.random.key(0), cfg)
    kw = dict(max_slots=2, max_seq=64, pad_len=32, steps_per_sync=4,
              block_size=8, prefix_cache=args.prefix_cache,
              prefill_chunk=args.prefill_chunk)
    if args.chaos and args.replicas < 2:
        ap.error("--chaos needs at least 2 replicas to survive")
    # ONE tracer for the whole fabric: request spans cross replicas.
    # --flight bounds it to a ring; a plain --trace keeps everything.
    if args.flight is not None:
        tracer = FlightRecorder(capacity=args.flight)
    elif args.trace:
        tracer = Tracer()
    else:
        tracer = None
    slo = SLOMonitor(parse_slo_spec(args.slo)) if args.slo else None
    if args.slo_admission:
        if slo is None:
            ap.error("--slo-admission requires --slo with a ttft_ms or "
                     "queue_wait_ms target")
        kw.update(slo=slo, slo_admission=True)
    cost_model = None
    if args.predictive:
        from repro.serve.cost import CostModel
        cost_model = CostModel()
    faults = None
    if args.chaos:
        from repro.serve.faults import FaultInjector
        faults = FaultInjector().crash(0, at=2)
    engines = [Engine(cfg, params, tracer=tracer, replica_id=i, **kw)
               for i in range(args.replicas)]
    bal = GLBReplicaBalancer(engines, migrate=args.migrate, tracer=tracer,
                             slo=slo, faults=faults,
                             cost_model=cost_model,
                             predictive=args.predictive)

    # Heterogeneous lengths: the first few requests run long, so replicas
    # that drew short ones go hungry while a peer is still wedged on
    # running sequences — the state only the --migrate tier can fix.
    reqs = [
        Request(rid=i, prompt=SYSTEM_PROMPT + [2 + i, 7, (3 * i) % cfg.vocab],
                max_new=(36 if i < 4 else 4) + (i % 3))
        for i in range(10)
    ]
    for r in reqs:
        bal.submit(r, rr=0)  # adversarial: everything on replica 0
    if args.migrate:
        # Wedge replica 0 first: drain its queue into running slots so
        # the balancer's LIVE tier (not just queue steals) is exercised.
        engines[0].step()

    t0 = time.time()
    status = bal.run(max_steps=500)
    dt = time.time() - t0
    assert status == "terminated", f"fabric {status}, not terminated"
    assert all(r.done for r in reqs)
    assert bal.terminated, "GLB termination must fire, not max_steps"
    if args.chaos:
        assert bal.replicas_dead == 1 and not bal.alive[0]
        print(f"chaos: replica 0 crashed and was declared dead; "
              f"{bal.readmitted_queued} queued + "
              f"{bal.readmitted_running} running request(s) re-admitted "
              f"on the survivors; zero requests lost")
    total = sum(e.tokens_out for e in engines)
    mode = "paged"
    if args.prefix_cache:
        mode += "+prefix-cache"
    if args.prefill_chunk:
        mode += f"+chunk{args.prefill_chunk}"
    if args.migrate:
        mode += "+migrate"
    if args.chaos:
        mode += "+chaos"
    if args.predictive:
        mode += "+predictive"
    if args.slo_admission:
        mode += "+slo-admission"
    print(f"[{mode}] completed {len(reqs)} requests, {total} tokens "
          f"in {dt:.1f}s over {args.replicas} replicas")
    for i, e in enumerate(engines):
        line = (f"  replica {i}: {e.tokens_out} tokens, {e.steps} steps, "
                f"peak {e.peak_running} concurrent, "
                f"peak pool occupancy {e.peak_occupancy:.2f}, "
                f"peak fragmentation {e.peak_fragmentation:.2f}, "
                f"{e.sched.admissions} admissions, "
                f"{e.sched.preemptions} preemptions")
        if e.migrations_out or e.migrations_in:
            line += (f", {e.migrations_out} migrated out / "
                     f"{e.migrations_in} in")
        print(line)
        if e.prefix_cache is not None:
            c = e.prefix_cache
            print(f"    prefix cache: {c.hits} hits / {c.misses} misses "
                  f"(hit rate {c.hit_rate:.0%}), "
                  f"{c.tokens_reused} prefill tokens saved, "
                  f"{c.evictions} evictions, "
                  f"{e.pool.cached_blocks} blocks cached now")
        if e.sched.chunks_scheduled:
            print(f"    chunked prefill: {e.sched.chunks_scheduled} chunks")
    print()
    print(bal.report())
    for r in reqs[:3]:
        print(f"  req {r.rid}: {r.prompt} -> {r.out}")
    if tracer is not None:
        # Post-run analytics over the live tracer (dump() is balanced
        # and non-destructive): the fabric report from our own trace.
        analysis = analyze_trace(tracer)
        print()
        print(render_summary(analysis))
        if args.trace:
            tracer.write(args.trace)
            problems = validate_chrome_trace(tracer.dump())
            assert not problems, problems
            extra = (f" (ring: {tracer.dropped} dropped)"
                     if args.flight is not None else "")
            print(f"\nwrote {len(tracer.events)} trace events{extra} to "
                  f"{args.trace} — load it at https://ui.perfetto.dev, "
                  f"or: PYTHONPATH=src python -m repro.obs.analyze "
                  f"{args.trace}")
    if args.metrics:
        print("\n# merged fabric metrics registry")
        print(bal.merged_metrics().render_prometheus(), end="")


if __name__ == "__main__":
    main()
