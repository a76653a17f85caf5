"""Benchmark orchestrator — one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV; with ``--json`` also writes
``BENCH_<suite>.json`` next to the CSV so the perf trajectory is
machine-readable (CI uploads the kernels and serve suites per PR).

  bench_uts              — Fig 2/3/4: UTS-G scaling + efficiency
  bench_bc               — Fig 5/7/9: BC-G vs static scaling
  bench_bc_distribution  — Fig 6/8/10: workload distribution std-dev
  bench_params           — §2.4: w/z/n tuning space
  bench_kernels          — Pallas kernels vs oracles + CPU timings
  bench_moe_glb          — GLB applied to MoE expert placement
  bench_serve            — engine decode loop: tokens/s + host syncs/token,
                           paged KV pool vs contiguous slots (throughput +
                           max concurrency at fixed HBM)

Each ``--json`` artifact carries a ``meta`` block — wall-clock start/end
(unix), host name, jax version, and the observability layer's
``clock_sync`` anchor (unix ↔ ``perf_counter`` µs) — so a bench row and
a ``BENCH_serve_trace.json`` event from the same run can be placed on
one timeline.

Usage: python benchmarks/run.py [suite-substring] [--json]
"""
import json
import platform
import sys
import time
import traceback


def main() -> None:
    import jax

    from benchmarks import (
        bench_bc, bench_bc_distribution, bench_kernels, bench_moe_glb,
        bench_params, bench_serve, bench_uts,
    )
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import clock_sync

    enable_compile_cache()
    modules = [
        ("uts_scaling", bench_uts),
        ("bc_scaling", bench_bc),
        ("bc_distribution", bench_bc_distribution),
        ("glb_params", bench_params),
        ("kernels", bench_kernels),
        ("moe_glb", bench_moe_glb),
        ("serve", bench_serve),
    ]
    argv = sys.argv[1:]
    as_json = "--json" in argv
    argv = [a for a in argv if a != "--json"]
    only = argv[0] if argv else None
    failed = []
    print("name,us_per_call,derived")
    for name, mod in modules:
        if only and only not in name:
            continue
        t0 = time.time()
        rows = []
        try:
            for row in mod.run():
                n, us, derived = row
                rows.append({"name": n, "us_per_call": float(us),
                             "derived": str(derived)})
                print(f"{n},{us:.1f},{derived}", flush=True)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            print(f"{name},nan,ERROR", flush=True)
            rows.append({"name": name, "us_per_call": None,
                         "derived": "ERROR"})
            failed.append(name)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        if as_json:
            meta = {
                "started_unix": t0,
                "ended_unix": time.time(),
                "host": platform.node(),
                "jax_version": jax.__version__,
                # same clock domain the tracer stamps events in: lets a
                # trace ts line up against this suite's wall-clock rows
                "clock_sync": clock_sync(),
            }
            path = f"BENCH_{name}.json"
            with open(path, "w") as f:
                json.dump({"suite": name, "meta": meta, "rows": rows},
                          f, indent=2)
            print(f"# wrote {path}", flush=True)
    if failed:
        # A crashing suite must fail CI, not just leave an ERROR row in
        # the artifact.
        sys.exit(f"benchmark suites errored: {', '.join(failed)}")


if __name__ == "__main__":
    main()
