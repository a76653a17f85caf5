"""The control of a serving cell: the reading that sets the upper end of
its correctness limit.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds 11,12,13

Each seed is one run of the cell (a short window at the cell's own
load), all in one process, with the reference computed in fp8 (the step
below the configuration's bf16) put in the program's place: the number
the harness compares is the gap of the token fp8 puts first at each
position of the served prompts and tokens, read against the float32
logits, and the run has to come out not correct. Each row also gives
the program's own gap on the same requests (the lower reading). The
benchmark's own runs never read the control. It needs the chips the
cell asks for.
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
    from benchmarks.chip import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        notes = {}

        def log(msg, notes=notes):
            if ": " in msg:
                k, v = msg.rsplit(": ", 1)
                notes[k] = v
            harness.log_stderr(msg)

        res = harness.run_cell(
            args.workload, seed, args.seconds, False,
            t_start=time.perf_counter() if i else T_START,
            control=True, log=log)
        harness.emit_checks(res)
        row = {"seed": seed, "correct": res["correct"],
               "checks": res["checks"],
               "program": notes.get("program max_logit_gap"),
               "compared": notes.get("check: requests/served tokens compared")}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"control": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
