"""Cell lookup, device checks, compile counting and the result line.

A run is ``run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``.
The harness reads ``BENCHMARK.json``, finds the cell's configuration file
and traffic mix by name, hands them to the configuration's driver, then
turns the driver's observations into metrics with one reader per metric
(``metrics/<name>.py``). ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer ones.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in bench['workloads']]}")


def find_config(bench: dict, name: str) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            return cfg
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def load_mix(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return load_json(bench_dir / "traffic" / f"{name}.json")


def reader_path(metric: str, bench_dir: Path = BENCH_DIR) -> Path:
    """``metrics/<metric>.py``, or else the reader of the metric's base
    name (``step_mfu.chat`` -> ``metrics/step_mfu.py``), which serves
    every cell group the metric is split into."""
    path = bench_dir / "metrics" / f"{metric}.py"
    if not path.is_file():
        path = bench_dir / "metrics" / f"{metric.split('.')[0]}.py"
    return path


def load_reader(metric: str, bench_dir: Path = BENCH_DIR):
    """The reader module of one metric (``reader_path``)."""
    path = reader_path(metric, bench_dir)
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics._{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, traced: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: end-to-end ones untraced,
    per-layer ones traced. A metric with a ``workloads`` list applies to
    those cells; a per-layer metric without one applies wherever the
    end-to-end metric it moves is reported."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not traced:
        return e2e
    e2e_names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e_names)]


def read_metrics(bench: dict, cell: str, traced: bool, obs: Dict[str, Any],
                 bench_dir: Path = BENCH_DIR) -> Dict[str, dict]:
    """Each of the run's metrics from its reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    out: Dict[str, dict] = {}
    for m in cell_metrics(bench, cell, traced):
        value = load_reader(m["name"], bench_dir).read(obs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class CompileCounter:
    """Counts programs JAX obtains (compiled, or loaded from the
    persistent cache) and persistent-cache hits, through
    ``jax.monitoring``. ``compiled = obtained - cache hits``."""

    def __init__(self):
        import jax

        self.obtained = 0
        self.hits = 0
        self.seconds = 0.0
        self.names: List[str] = []       # every program obtained
        self.compiled_names: List[str] = []   # those not from the cache
        self._hits_seen = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, fun_name: str = "?", **_) -> None:
        # A cache hit is reported inside the compile it ends, so a
        # compile event with no hit since the last one compiled.
        if event == BACKEND_COMPILE_EVENT:
            self.obtained += 1
            self.seconds += secs
            self.names.append(fun_name)
            if self.hits == self._hits_seen:
                self.compiled_names.append(fun_name)
            self._hits_seen = self.hits

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def snapshot(self) -> Dict[str, float]:
        return {"obtained": self.obtained, "cache_hits": self.hits,
                "compiled": len(self.compiled_names),
                "seconds": self.seconds}


ZERO = {"obtained": 0, "cache_hits": 0, "compiled": 0, "seconds": 0.0}


def delta(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    return {k: b[k] - a[k] for k in a}


def compile_note(counter: "CompileCounter", a: Dict[str, float],
                 b: Dict[str, float]) -> str:
    """``obtained/compiled [names]`` between two snapshots."""
    d = delta(a, b)
    names = counter.names[int(a["obtained"]):int(b["obtained"])]
    compiled = counter.compiled_names[int(a["compiled"]):int(b["compiled"])]
    return (f"{d['obtained']}/{d['compiled']} {sorted(set(names))}"
            f" compiled: {sorted(set(compiled))}")


def chips_for(cell: dict, rehearsal: bool):
    """The devices the cell runs on. The measured path needs TPUs, at
    least as many as the cell asks for; the CPU rehearsal used by the
    tests takes whatever devices there are."""
    import jax

    devs = jax.devices()
    if rehearsal:
        return devs[: cell["chips"]]
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found platform {devs[0].platform!r}")
    if len(devs) < cell["chips"]:
        raise NoChip(f"needs {cell['chips']} chips, found {len(devs)}")
    return devs[: cell["chips"]]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache, at its fixed path
    inside the checkout (or ``$JAX_COMPILATION_CACHE_DIR``), with every
    program written to it however fast it compiled, so that only the
    first run of a cell in a checkout compiles. The cache never evicts:
    with eviction on, every read takes a file lock and writes an access
    time, and the serving path reads the cache on every engine step (see
    PERF.md), which stalled the host for seconds in some runs."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def peaks_for(kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = load_json(bench_dir / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


def device_record(devices) -> dict:
    import jax

    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": peak}


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, its configuration and mix, the run's
    arguments, the devices and the compile counter."""
    cell: dict
    config: dict          # the configuration file's contents
    mix: dict             # the traffic mix file's contents
    seed: int
    seconds: float
    trace: bool
    devices: list
    compiles: CompileCounter
    t_start: float        # perf_counter at process start
    rehearsal: bool = False
    control: bool = False  # the fp8 control in the program's place (control.py)


@dataclasses.dataclass
class Outcome:
    """A driver's result: what it observed for the readers, the checks
    that decide ``correct`` (name -> (value, limit)), and the counts."""
    obs: Dict[str, Any]
    checks: Dict[str, tuple]
    attempted: int
    failed: int
    breakdown: Optional[dict] = None
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            v is not None and v <= lim for v, lim in self.checks.values())


def log_stderr(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, rehearsal: bool = False,
             bench: Optional[dict] = None, config_override: Optional[dict] = None,
             mix_override: Optional[dict] = None, control: bool = False,
             log: Callable[[str], None] = log_stderr,
             keep_obs: bool = False, root: Path = ROOT) -> dict:
    """One run of one cell; returns the result object. ``root`` is the
    checkout whose ``BENCHMARK.json`` and ``benchmarks/chip`` files are
    read. ``rehearsal`` and the overrides exist for the CPU tests, and
    ``control`` and ``keep_obs`` for the tools beside the harness: a
    rehearsal reports no metrics, since a CPU run measures nothing of the
    chip."""
    bench_dir = root / BENCH_DIR.relative_to(ROOT)
    bench = bench if bench is not None else load_benchmark(root)
    cell = find_cell(bench, name)
    cfg_entry = find_config(bench, cell["config"])
    config = config_override or load_json(root / cfg_entry["file"])
    mix = mix_override or load_mix(cell["traffic"], bench_dir)
    devices = chips_for(cell, rehearsal)
    if not rehearsal:
        log(f"compile cache: {enable_compile_cache()}")
    ctx = Context(cell=cell, config=config, mix=mix, seed=seed,
                  seconds=seconds, trace=trace, devices=devices,
                  compiles=CompileCounter(), t_start=t_start,
                  rehearsal=rehearsal, control=control)
    driver = importlib.import_module(
        f"benchmarks.chip.drivers.{config['driver']}")
    out: Outcome = driver.run(ctx)
    device = out.obs.pop("device")
    metrics: Dict[str, dict] = {}
    if not rehearsal:
        out.obs["peaks"] = peaks_for(device["kind"], bench_dir)
        metrics = read_metrics(bench, name, trace, out.obs, bench_dir)
    for k, v in out.notes.items():
        log(f"{k}: {v}")
    result = {"correct": out.correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if trace and out.breakdown is not None:
        result["breakdown"] = out.breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in out.checks.items()}
    if keep_obs:
        result["_obs"] = out.obs
    return result


def emit_checks(result: dict) -> None:
    """Each number compared beside its limit, and the verdict, on
    stderr."""
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)


def emit(result: dict) -> None:
    """The checks as the last lines of stderr, the result as the last
    line of stdout."""
    emit_checks(result)
    print(json.dumps(result), flush=True)


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None
         ) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t0 = t_start if t_start is not None else time.perf_counter()
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t0)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    emit(result)
    return 0
