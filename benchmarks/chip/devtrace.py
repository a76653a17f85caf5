"""From a profiler trace to the numbers the per-layer readers take.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. A TPU chip is a plane named ``/device:TPU:<n>``; its ``XLA Ops``
line holds one event per operation that ran, its ``XLA Modules`` line one
event per program (``jit_<function>(<id>)``). The host's Python thread
holds the benchmark's own ``TraceAnnotation`` spans, among them the one
that marks the traced window (``WINDOW_SPAN``).

Everything here is a sum, a union or a lookup over those events:

- busy time: the union of a chip's op intervals inside the window,
  averaged over the chips; idle share is one minus busy over the window;
- time by op (its self time: nested ops are taken out of the ``while``
  that holds them) and by program, in the window, averaged over the
  chips;
- idle gaps: the stretches between busy intervals, each put down to the
  innermost host event that covers its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 20_000          # gaps shorter than 20 us are not attributed

Interval = Tuple[float, float]


def start(trace_dir: str) -> None:
    """Start the profiler with the host's Python tracer off: it would
    record every Python call (hundreds of thousands a second), slow the
    host it measures and fill the trace. The benchmark's annotations and
    JAX's own host events are still recorded."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def latest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(iv: Interval, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(iv[0], lo), min(iv[1], hi)
    return (s, e) if e > s else None


def op_name(event_name: str) -> str:
    """An op event is named by its HLO instruction, with its whole text
    (``%paged_decode.8 = bf16[...] custom-call(...)``): keep the name."""
    return event_name.split(" = ", 1)[0]


def self_times(events: List[Tuple[str, float, float]]
               ) -> List[Tuple[str, float, float, float]]:
    """Ops of one chip, sorted, each with its self time: its duration less
    the ops nested in it (a ``while`` holds its body's ops)."""
    evs = sorted(events, key=lambda x: (x[1], -x[2]))
    out = [[n, s, e, e - s] for n, s, e in evs]
    stack: list = []
    for rec in out:
        while stack and stack[-1][2] <= rec[1]:
            stack.pop()
        if stack and rec[2] <= stack[-1][2]:
            stack[-1][3] -= rec[2] - rec[1]
        stack.append(rec)
    return [tuple(r) for r in out]


def module_name(event_name: str) -> str:
    """``jit_decode_tokens(123)`` -> ``jit_decode_tokens``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            for ev in line.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def host_lines(pd) -> List[List[Tuple[str, float, float]]]:
    """Events of the host's Python threads, one list per thread: the
    benchmark's annotations and JAX's own (dispatch, tracing,
    compilation)."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if line.name.startswith("python"):
                out.append([(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events])
    return out


def attribute(mids: List[float], lines) -> List[str]:
    """For each time in ``mids`` (ascending), the innermost host span
    that covers it. Spans of one thread nest, so one sweep with a stack
    per thread finds them."""
    best = [("host: no span", float("inf"))] * len(mids)
    for spans in lines:
        spans = sorted((s for s in spans if s[0] != WINDOW_SPAN),
                       key=lambda x: (x[1], -x[2]))
        stack: list = []
        i = 0
        for k, mid in enumerate(mids):
            while i < len(spans) and spans[i][1] <= mid:
                while stack and stack[-1][2] < spans[i][1]:
                    stack.pop()
                stack.append(spans[i])
                i += 1
            while stack and stack[-1][2] < mid:
                stack.pop()
            if stack and stack[-1][2] - stack[-1][1] < best[k][1]:
                best[k] = (stack[-1][0], stack[-1][2] - stack[-1][1])
    return [b[0] for b in best]


def reduce_xplane(path: str) -> Optional[dict]:
    """The traced window's device numbers, or None where the trace holds
    no TPU plane or no window span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    hosts = host_lines(pd)
    window = [(s, e) for line in hosts for n, s, e in line
              if n == WINDOW_SPAN]
    devices = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    if not window or not devices:
        return None
    w0, w1 = window[0]
    n_dev = len(devices)
    ops: Dict[str, float] = defaultdict(float)
    modules: Dict[str, float] = defaultdict(float)
    module_calls: Dict[str, float] = defaultdict(float)
    busy_ns = 0.0
    gaps: Dict[str, float] = defaultdict(float)
    for plane in devices:
        ivs = []
        inside = []
        for name, s, e in _events(plane, OPS_LINE):
            c = clip((s, e), w0, w1)
            if c:
                inside.append((op_name(name), c[0], c[1]))
                ivs.append(c)
        for name, s, e, own in self_times(inside):
            ops[name] += own / n_dev
        for name, s, e in _events(plane, MODULES_LINE):
            c = clip((s, e), w0, w1)
            if c:
                key = module_name(name)
                modules[key] += (c[1] - c[0]) / n_dev
                module_calls[key] += 1 / n_dev
        merged = union(ivs)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                if e - s >= MIN_GAP_NS]
        names = attribute([(s + e) / 2 for s, e in idle], hosts)
        for name, (s, e) in zip(names, idle):
            gaps[name] += (e - s) / n_dev
    ns = 1e-9
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": busy_ns / n_dev * ns,
        "chips": n_dev,
        "ops_s": {k: v * ns for k, v in ops.items()},
        "modules_s": {k: v * ns for k, v in modules.items()},
        "module_calls": dict(module_calls),
        "idle_gaps_s": {k: v * ns for k, v in gaps.items()},
    }


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(red: dict) -> dict:
    return {"device_ops": top(red["ops_s"]),
            "idle_gaps": top(red["idle_gaps_s"])}


def module_seconds(red: dict, prefix: str) -> Tuple[float, float]:
    """Device seconds and calls of the programs whose name starts with
    ``prefix`` (``jit_decode_tokens``...)."""
    secs = sum(v for k, v in red["modules_s"].items() if k.startswith(prefix))
    calls = sum(v for k, v in red["module_calls"].items()
                if k.startswith(prefix))
    return secs, calls


def op_seconds(red: dict, pattern: str) -> float:
    """Device seconds of the ops whose name matches ``pattern``. A Pallas
    kernel's op is named after the jitted function that calls it
    (``%paged_decode.8``)."""
    rx = re.compile(pattern)
    return sum(v for k, v in red["ops_s"].items() if rx.search(k))


def describe(path: str, per_line: int = 25) -> str:
    """A plain listing of a trace's planes, lines and busiest event
    names, for reading a trace by hand."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            tot: Dict[str, float] = defaultdict(float)
            cnt: Dict[str, int] = defaultdict(int)
            first = None
            t_min, t_max = float("inf"), 0.0
            for ev in line.events:
                tot[ev.name] += ev.duration_ns
                cnt[ev.name] += 1
                t_min = min(t_min, ev.start_ns)
                t_max = max(t_max, ev.start_ns + ev.duration_ns)
                if first is None:
                    first = (ev.name, dict(ev.stats))
            out.append(f"  LINE {line.name!r}: {sum(cnt.values())} events, "
                       f"t [{t_min:.0f}, {t_max:.0f}] ns; first {first}")
            for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:per_line]:
                out.append(f"    {v / 1e6:10.3f} ms  x{cnt[k]:6d}  {k[:150]}")
    return "\n".join(out)
