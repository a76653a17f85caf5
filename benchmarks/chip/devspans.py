"""The device's idle time put down to the program's own spans.

``repro.obs.ProfilerTracer`` writes the program's duration spans into a
``jax.profiler`` capture as host events named ``repro.<span>``, on the
same clock as the device's ops. ``devtrace.attribute`` puts each idle gap
down to the innermost host event of any kind, most often one of JAX's own
(``PjitFunction(scan)``, ``DevicePut``), which does not say which part of
the program made the call. Here each gap goes to the innermost
``repro.*`` span that covers its midpoint, or to ``"none"``.

The gaps are those ``devtrace.reduce_xplane`` finds: the stretches of
the traced window, 20 us or longer, in which no op runs on a chip,
averaged over the chips. So the seconds here sum to the sum of its
``idle_gaps_s``.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Sequence

from benchmarks.chip import devtrace

PROGRAM_PREFIX = "repro."
NO_SPAN = "none"
# The spans each layer's idle share sums (PERF.md section 3).
BALANCER_SPANS = ("repro.balance", "repro.match_steals")
ENGINE_SPANS = ("repro.engine_step", "repro.plan", "repro.prefill",
                "repro.decode", "repro.drain")


def attribute_to_program(mids: List[float], lines) -> List[str]:
    """For each time in ``mids`` (ascending), the innermost ``repro.*``
    span of ``lines`` (host events per thread) that covers it, or
    ``NO_SPAN``."""
    program = [[ev for ev in line if ev[0].startswith(PROGRAM_PREFIX)]
               for line in lines]
    return [n if n.startswith(PROGRAM_PREFIX) else NO_SPAN
            for n in devtrace.attribute(mids, program)]


def idle_gaps(plane, w0: float, w1: float) -> List[devtrace.Interval]:
    """The idle stretches of one chip inside the window [w0, w1], as
    ``devtrace.reduce_xplane`` finds them."""
    ivs = []
    for _name, s, e in devtrace._events(plane, devtrace.OPS_LINE):
        c = devtrace.clip((s, e), w0, w1)
        if c:
            ivs.append(c)
    merged = devtrace.union(ivs)
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2])
            if e - s >= devtrace.MIN_GAP_NS]


def idle_by_span_s(path: str) -> Optional[Dict[str, float]]:
    """Seconds of the traced window's idle time under each ``repro.*``
    span (``NO_SPAN`` for the rest), averaged over the chips; None where
    the trace holds no TPU plane or no window span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    hosts = devtrace.host_lines(pd)
    window = [(s, e) for line in hosts for n, s, e in line
              if n == devtrace.WINDOW_SPAN]
    devices = [p for p in pd.planes if devtrace.DEVICE_PLANE.match(p.name)]
    if not window or not devices:
        return None
    w0, w1 = window[0]
    out: Dict[str, float] = defaultdict(float)
    for plane in devices:
        gaps = idle_gaps(plane, w0, w1)
        names = attribute_to_program([(s + e) / 2 for s, e in gaps], hosts)
        for name, (s, e) in zip(names, gaps):
            out[name] += (e - s) * 1e-9 / len(devices)
    return dict(out)


def idle_in_pct(spans_s: Dict[str, float], window_s: float,
                names: Sequence[str]) -> Optional[float]:
    """Idle time under the spans ``names``, in percent of the window;
    None where no ``repro.*`` span was traced at all."""
    if window_s <= 0 or not any(k != NO_SPAN for k in spans_s):
        return None
    return 100.0 * sum(spans_s.get(n, 0.0) for n in names) / window_s
