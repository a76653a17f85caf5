"""Bench regression gate: compare freshly-written ``BENCH_<suite>.json``
files against the baselines committed under ``benchmarks/baselines/`` and
fail (exit 1) when a tracked metric regresses beyond its tolerance.

Two classes of metric, because CI runners are noisy:

* **deterministic** — structural quantities a code change moves and noise
  cannot (supersteps, syncs/token, max prefill tokens per step, max
  concurrency, kernel error vs oracle). These gate tightly;
* **wall-clock** — tokens/s and µs/call on a shared CPU runner. These
  gate loosely AND advisorily: a breach lands in the step summary as a
  warning but does not fail the job, because committed baselines may
  come from a different machine class than the CI runner (a dropped
  row still hard-fails — disappearance is structural).

A tracked row missing from the fresh run fails the gate (a silently
dropped benchmark is itself a regression); a tracked row missing from
the baseline is reported as NEW and passes. The full diff is written as
a markdown table to ``--summary`` (the CI step summary) and stdout.

  PYTHONPATH=src python benchmarks/compare.py \
      --baseline-dir benchmarks/baselines [--current-dir .] \
      [--summary out.md] [suites...]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class Gate:
    suite: str
    row: str
    metric: str        # key inside `derived`, or "us_per_call"
    better: str        # "higher" | "lower"
    rel_tol: float     # allowed relative regression (0.10 = 10% worse ok)
    abs_tol: float = 0.0   # additionally allowed absolute slack
    note: str = ""
    hard: bool = True  # False: report the breach in the summary but do
                       # not fail the job — wall-clock metrics gate soft
                       # because committed baselines may come from a
                       # different machine class than the CI runner;
                       # deterministic metrics stay hard.


# ---------------------------------------------------------------- tracked
GATES = [
    # --- serve: deterministic structure -------------------------------
    Gate("serve", "serve_paged_loop", "syncs_per_tok", "lower", 0.01,
         note="host syncs per token is the fast path's invariant"),
    Gate("serve", "serve_packing_paged", "max_concurrent", "higher", 0.0,
         note="paged packing at fixed HBM"),
    Gate("serve", "serve_prefix_cache", "prefill_tokens_saved", "higher",
         0.0, abs_tol=5.0, note="shared-prefix reuse (% points)"),
    Gate("serve", "serve_ttft_chunked", "max_prefill_tokens_per_step",
         "lower", 0.0, abs_tol=2.0,
         note="THE bound chunked prefill exists to enforce"),
    Gate("serve", "serve_skew_live_migration", "makespan_steps", "lower",
         0.0, abs_tol=1.0,
         note="skewed-fabric makespan with live KV migration"),
    Gate("serve", "serve_skew_live_migration", "steps_vs_queue_steal",
         "lower", 0.0, abs_tol=0.15,
         note="live migration must keep beating queue-only stealing"),
    # rel_tol 0.5 of baseline 2 => floor 1: the intent is only "the
    # queue-only arm still preempts at all", not "as often as baseline"
    # (a benign scheduler improvement may preempt less).
    Gate("serve", "serve_skew_queue_steal", "preemptions", "higher", 0.5,
         note="the queue-only arm must still thrash (else the scenario "
              "no longer exercises the contrast)"),
    # Tracing must never add host syncs — events are host-side dict
    # appends. Deterministic, so it gates hard like the off-arm's.
    Gate("serve", "serve_obs_overhead", "syncs_per_tok_on", "lower", 0.01,
         note="a live tracer adds ZERO device drains"),
    # The bounded flight ring keeps both deterministic contracts: no
    # extra host syncs, and a WRAPPED ring still dumps a balanced,
    # validator-clean trace (dump_valid is 0/1).
    Gate("serve", "serve_flight_overhead", "syncs_per_tok", "lower", 0.01,
         note="a live flight recorder adds ZERO device drains"),
    Gate("serve", "serve_flight_overhead", "dump_valid", "higher", 0.0,
         note="wrapped ring must dump a validator-clean trace"),
    # Predictive balancing (DESIGN.md §16): supersteps, preemptions and
    # first-token supersteps are deterministic under greedy decode +
    # deterministic diffusion/matching, so the predictive-vs-reactive
    # contract gates hard at its committed values; the parity row is
    # THE regression tripwire for "predictor off == today's balancer".
    Gate("serve", "serve_skew_predictive", "steps_vs_reactive", "lower",
         0.0, abs_tol=0.05,
         note="predictive makespan must stay <= reactive supersteps"),
    Gate("serve", "serve_skew_predictive", "ttft_p99_steps", "lower",
         0.0, abs_tol=1.0,
         note="short-request TTFT p99 in supersteps, predictive arm"),
    Gate("serve", "serve_skew_predictive", "preemptions", "lower", 0.0,
         abs_tol=1.0, note="diffusion moves work BEFORE thrash"),
    Gate("serve", "serve_skew_predictive", "diffusion_moves", "higher",
         0.0, note="the predictive arm must actually diffuse (else the "
                   "scenario no longer exercises the cost model)"),
    Gate("serve", "serve_skew_parity", "decisions_identical", "higher",
         0.0, note="predictor off must reproduce the reactive decision "
                   "log byte-for-byte (0/1)"),
    # Crash recovery (DESIGN.md §15): deterministic fabric — greedy
    # decode + heartbeat window on the superstep clock — so the loss
    # and identity contracts gate hard at exactly their ideal values.
    Gate("serve", "serve_crash_recovery", "requests_lost", "lower", 0.0,
         note="a crashed replica's requests are re-admitted, never lost"),
    Gate("serve", "serve_crash_recovery", "terminated", "higher", 0.0,
         note="the crashed fabric must still terminate (0/1)"),
    Gate("serve", "serve_crash_recovery", "greedy_identical", "higher",
         0.0, note="re-admitted outputs token-identical to a clean run"),
    Gate("serve", "serve_crash_recovery", "readmitted", "higher", 0.0,
         note="the crash must actually cost recovery work (else the "
              "scenario no longer exercises the ledger)"),
    # --- serve: wall-clock, loose + advisory --------------------------
    Gate("serve", "serve_paged_loop", "tok_s", "higher", 0.60,
         note="decode throughput cliff detector", hard=False),
    # Tracer-on overhead vs the committed baseline: warn past +5 points
    # (wall-clock on a shared runner, so advisory — the contract itself
    # lives in the tracer-off row and the syncs gate above).
    Gate("serve", "serve_obs_overhead", "overhead_pct", "lower", 0.0,
         abs_tol=5.0, note="tracer-on tokens/s cost (% points)",
         hard=False),
    Gate("serve", "serve_obs_overhead", "tok_s_off", "higher", 0.60,
         note="tracer-off throughput must track serve_paged_loop",
         hard=False),
    Gate("serve", "serve_flight_overhead", "tok_s", "higher", 0.60,
         note="ring-buffer tracing throughput cliff detector",
         hard=False),
    # --- kernels: oracle agreement is deterministic -------------------
    Gate("kernels", "attn_chunked_1k", "err", "lower", 0.0, abs_tol=1e-5,
         note="flash attention vs reference"),
    Gate("kernels", "flash_decode_interp", "err", "lower", 0.0,
         abs_tol=1e-5, note="decode kernel vs oracle"),
    # --- kernels: wall-clock, loose + advisory ------------------------
    Gate("kernels", "attn_chunked_1k", "us_per_call", "lower", 2.0,
         hard=False),
    Gate("kernels", "ssd_chunked_512", "us_per_call", "lower", 2.0,
         hard=False),
]

_NUM = re.compile(r"^-?\d+(\.\d+)?([eE][+-]?\d+)?$")


def _parse_val(raw: str) -> Optional[float]:
    """'34964.4' / '93%' / '5.43x' / '1.2e-07' -> float; else None."""
    s = raw.strip().rstrip("%x")
    if _NUM.match(s):
        return float(s)
    return None


def _load(path: str) -> Dict[str, dict]:
    with open(path) as f:
        data = json.load(f)
    rows = {}
    for r in data["rows"]:
        metrics = {}
        if r.get("us_per_call") is not None:
            metrics["us_per_call"] = float(r["us_per_call"])
        for kv in str(r.get("derived", "")).split(";"):
            if "=" in kv:
                k, v = kv.split("=", 1)
                val = _parse_val(v)
                if val is not None:
                    metrics[k] = val
        rows[r["name"]] = metrics
    return rows


def _check(gate: Gate, base: Optional[float],
           cur: Optional[float]) -> tuple:
    """-> (status, detail). status in {'ok','REGRESSED','MISSING','new'}"""
    if cur is None:
        return "MISSING", "row/metric absent from fresh run"
    if base is None:
        return "new", "no committed baseline yet"
    if gate.better == "higher":
        floor = base * (1 - gate.rel_tol) - gate.abs_tol
        if cur < floor:
            return "REGRESSED", f"{cur:g} < floor {floor:g}"
    else:
        ceil = base * (1 + gate.rel_tol) + gate.abs_tol
        if cur > ceil:
            return "REGRESSED", f"{cur:g} > ceiling {ceil:g}"
    return "ok", ""


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline-dir", default="benchmarks/baselines")
    ap.add_argument("--current-dir", default=".")
    ap.add_argument("--summary", default=None,
                    help="append the markdown diff table here "
                         "(e.g. $GITHUB_STEP_SUMMARY)")
    ap.add_argument("suites", nargs="*", default=None,
                    help="suite names to gate (default: all tracked)")
    args = ap.parse_args()

    suites = sorted({g.suite for g in GATES})
    if args.suites:
        suites = [s for s in suites if s in args.suites]

    lines = ["| suite | row | metric | baseline | current | status |",
             "|---|---|---|---|---|---|"]
    failed = []
    for suite in suites:
        cur_path = os.path.join(args.current_dir, f"BENCH_{suite}.json")
        base_path = os.path.join(args.baseline_dir, f"BENCH_{suite}.json")
        if not os.path.exists(cur_path):
            failed.append(f"{suite}: {cur_path} missing (suite not run?)")
            continue
        cur_rows = _load(cur_path)
        base_rows = _load(base_path) if os.path.exists(base_path) else {}
        for g in (g for g in GATES if g.suite == suite):
            base = base_rows.get(g.row, {}).get(g.metric)
            cur = cur_rows.get(g.row, {}).get(g.metric)
            status, detail = _check(g, base, cur)
            if status == "REGRESSED" and not g.hard:
                status = "advisory"
            mark = {"ok": "✅ ok", "new": "🆕 new",
                    "advisory": "⚠️ slow (advisory, not gating)",
                    "REGRESSED": "❌ REGRESSED",
                    "MISSING": "❌ MISSING"}[status]
            lines.append(
                f"| {suite} | {g.row} | {g.metric} | "
                f"{'-' if base is None else f'{base:g}'} | "
                f"{'-' if cur is None else f'{cur:g}'} | {mark}"
                f"{' — ' + detail if detail else ''} |"
            )
            if status in ("REGRESSED", "MISSING"):
                msg = f"{suite}/{g.row}/{g.metric}: {detail}"
                failed.append(f"{msg} ({g.note})" if g.note else msg)
    table = "\n".join(["## Bench regression gate", ""] + lines + [""])
    print(table)
    if args.summary:
        with open(args.summary, "a") as f:
            f.write(table + "\n")
    if failed:
        print("REGRESSIONS:", file=sys.stderr)
        for f_ in failed:
            print(f"  {f_}", file=sys.stderr)
        return 1
    print("bench gate: all tracked metrics within tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main())
