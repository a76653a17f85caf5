"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for. The last line of stdout is the result as one JSON object;
the last lines of stderr are the numbers that decide ``correct``, each
beside its limit. Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits non-zero.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        print(f"benchmark: the program is not in this checkout "
              f"({ROOT / 'src' / 'repro'} is missing)", file=sys.stderr)
        return 2
    # The script's own directory goes: its module names would shadow
    # the standard library's.
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    # libtpu logs under /tmp/tpu_logs unless told otherwise; keep them in
    # this run's own temporary directory.
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    from benchmarks.chip import harness

    return harness.main(t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
