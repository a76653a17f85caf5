"""The profiler sink (``obs.ProfilerTracer``, DESIGN.md §10): under a
``jax.profiler`` capture a paged engine behind the replica balancer
writes its phases as ``repro.*`` events to the host's Python line of the
``.xplane.pb``, nested as the program runs them; a span raised through
is closed; and the default ``NULL_TRACER`` makes no profiler call."""
import glob
import os

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs import ARCHS
from repro.models import init_lm
from repro.obs import NULL_TRACER, ProfilerTracer, Tracer, \
    validate_chrome_trace
from repro.serve.engine import Engine, GLBReplicaBalancer, Request

CFG = ARCHS["tinyllama-1.1b"].smoke()
PARAMS = init_lm(jax.random.key(0), CFG)
KW = dict(max_slots=2, max_seq=32, pad_len=8, steps_per_sync=4)


def _serve(tracer, n=3):
    eng = Engine(CFG, PARAMS, tracer=tracer, **KW)
    bal = GLBReplicaBalancer([eng], tracer=tracer)
    reqs = [Request(rid=i, prompt=[3, i + 1, 4, 2], max_new=6)
            for i in range(n)]
    for r in reqs:
        bal.submit(r)
    assert bal.run(max_steps=200) == "terminated"
    assert all(r.done for r in reqs)
    return [list(r.out) for r in reqs]


def _python_line_events(trace_dir):
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                if line.name.startswith("python"):
                    out += [(ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for ev in line.events]
    return out


def _inside(ev, parents):
    return any(p[1] <= ev[1] and ev[2] <= p[2] for p in parents)


def test_profiler_spans_land_nested_in_the_xplane(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        outs = _serve(ProfilerTracer())
    finally:
        jax.profiler.stop_trace()
    assert outs == _serve(NULL_TRACER)   # tracing does not perturb tokens
    evs = [e for e in _python_line_events(tmp_path)
           if e[0].startswith("repro.")]
    by = {}
    for e in evs:
        by.setdefault(e[0], []).append(e)
    assert set(by) == {f"repro.{n}" for n in (
        "superstep", "balance", "match_steals", "engine_step", "plan",
        "prefill", "decode", "drain")}
    assert len(by["repro.prefill"]) == 3           # one per admission
    for phase in ("plan", "prefill", "decode", "drain"):
        for e in by[f"repro.{phase}"]:
            assert _inside(e, by["repro.engine_step"]), e
    for e in by["repro.match_steals"]:
        assert _inside(e, by["repro.balance"]), e
    for name in ("repro.balance", "repro.engine_step"):
        for e in by[name]:
            assert _inside(e, by["repro.superstep"]), e


class _Recorder:
    """A stand-in annotation that logs its enters and exits."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def test_a_span_raised_through_is_closed():
    t = ProfilerTracer()
    t._annotation = _Recorder
    _Recorder.log = []
    with pytest.raises(RuntimeError):
        with t.span("outer", pid=1):
            t.begin("phase", pid=1)          # its end() never runs
            raise RuntimeError("boom")
    assert _Recorder.log == [("enter", "repro.outer"),
                             ("enter", "repro.phase"),
                             ("exit", "repro.phase"),
                             ("exit", "repro.outer")]
    assert t._stacks[(1, 0)] == []
    t.end(pid=1)                             # unmatched: dropped
    assert len(_Recorder.log) == 4
    # The Chrome tracer keeps the same discipline: balanced after a raise.
    c = Tracer()
    with pytest.raises(RuntimeError):
        with c.span("outer"):
            c.begin("phase")
            raise RuntimeError("boom")
    assert validate_chrome_trace(c.to_chrome()) == []
    assert [e["ph"] for e in c.events] == ["B", "B", "E", "E"]


def test_null_tracer_makes_no_profiler_call(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("profiler called with tracing off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    _serve(NULL_TRACER)
    with pytest.raises(AssertionError, match="tracing off"):
        _serve(ProfilerTracer())             # the patch does bite
