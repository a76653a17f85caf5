"""Find a serving cell's knee: run its traffic at several offered rates,
one window each, in one process, and print what each rate gave.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 2,3,4,6

The knee is the highest rate the system sustains: its queue does not
grow through the window, so the time to first token stays near what a
light load gives and the backlog at the window's end stays small. The
cells offer fixed rates below and above it (``traffic/<mix>.json``);
this is run once, when a cell is defined, and its output is kept in
PERF.md. It needs the chip the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from benchmarks.chip import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    bench = harness.load_benchmark()
    mix = harness.load_mix(harness.find_cell(bench, args.workload)["traffic"])
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        t0 = time.perf_counter() if i else T_START
        res = harness.run_cell(args.workload, args.seed + i, args.seconds,
                               False, t_start=t0, bench=bench,
                               mix_override=dict(mix, rate_per_s=rate),
                               keep_obs=True)
        obs = res.pop("_obs")
        reqs = obs["requests"]
        w_end = obs["window_start"] + obs["window_s"]
        ttft = [(r["first"] - r["due"]) * 1e3 for r in reqs if r["first"]]
        tpot = [(r["last"] - r["first"]) * 1e3 / (r["n_out"] - 1)
                for r in reqs if r["last"] and r["n_out"] > 1]
        row = {
            "rate_per_s": rate,
            "attempted": res["attempted"], "failed": res["failed"],
            "correct": res["correct"],
            "backlog_at_close": sum(1 for r in reqs
                                    if r["first"] is None or r["first"] > w_end),
            "ttft_ms_p50": float(np.percentile(ttft, 50)),
            "ttft_ms_p95": float(np.percentile(ttft, 95)),
            "tpot_ms_p50": float(np.percentile(tpot, 50)) if tpot else None,
            "tpot_ms_p95": float(np.percentile(tpot, 95)) if tpot else None,
            "output_tokens_per_s": obs["window_tokens"] / obs["window_s"],
            "drain_s": obs["drain_end"] - w_end,
            "setup_s": obs["setup_s"],
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"sweep": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
