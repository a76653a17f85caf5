"""Replica-crash fault tolerance (DESIGN.md §15): heartbeat detection,
lifeline re-wiring, recompute re-admission — plus the balancer
accounting fixes that rode along (sterile steals, move-counter split,
wedge reporting).

One chaos harness (``repro.serve.faults.FaultInjector``), two workload
shapes: the serving fabric (``GLBReplicaBalancer``) and the taskbag
simulator (``run_sim(faults=...)``). The headline invariants, asserted
by the crash-at-every-superstep sweep:

  * the fabric still terminates (no wedge, no silent loss);
  * every submitted request finishes — the ledger re-admits the dead
    replica's queued AND running sequences;
  * re-admitted sequences are greedy-token-identical to a clean run
    (recompute migration replays the surviving ``req.out`` prefix);
  * no surviving lifeline ever references the dead place.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import GLB, GLBParams, rewire_lifelines, run_sim
from repro.core.lifeline import lifeline_buddies
from repro.problems.bc import bc_problem
from repro.problems.fib import fib_problem, fib_oracle
from repro.problems.uts import uts_oracle, uts_problem
from repro.serve.engine import Engine, GLBReplicaBalancer, Request
from repro.serve.faults import Fault, FaultInjector

CFG = ARCHS["tinyllama-1.1b"].smoke()
_P = {}


def _params():
    if "p" not in _P:
        from repro.models import init_lm
        _P["p"] = init_lm(jax.random.key(0), CFG)
    return _P["p"]


PROMPT16 = [7, 3, 9, 2, 5, 8, 6, 4, 1, 2, 3, 4, 9, 9, 8, 7]
KW = dict(max_slots=2, max_seq=64, pad_len=16, steps_per_sync=4)


def _fabric(n=3, faults=None, tracer=None, heartbeat_misses=None, **over):
    kw = dict(block_size=8, num_blocks=64, **KW)
    kw.update(over)
    engines = [Engine(CFG, _params(), replica_id=i, tracer=tracer, **kw)
               for i in range(n)]
    bal = GLBReplicaBalancer(engines, migrate=True, faults=faults,
                             tracer=tracer,
                             heartbeat_misses=heartbeat_misses)
    return engines, bal


def _reqs(n=4, max_new=6):
    return [Request(rid=i, prompt=list(PROMPT16), max_new=max_new)
            for i in range(n)]


_CLEAN = {}


def _clean_outputs(n_req=4, max_new=6):
    """Outputs of an identical fabric with no faults (cached)."""
    key = (n_req, max_new)
    if key not in _CLEAN:
        _, bal = _fabric()
        reqs = _reqs(n_req, max_new)
        for r in reqs:
            bal.submit(r)
        assert bal.run(max_steps=300) == "terminated"
        _CLEAN[key] = [list(r.out) for r in reqs]
    return _CLEAN[key]


# ------------------------------------------------------------- injector
def test_fault_injector_semantics():
    inj = FaultInjector().crash(0, at=2).hang(1, at=1, duration=2) \
                         .slow(2, at=0, factor=3)
    inj.begin_superstep(0)
    assert inj.responsive(0) and inj.should_step(0)
    assert inj.responsive(2) and inj.should_step(2)       # slow: step 0
    inj.begin_superstep(1)
    assert not inj.responsive(1) and not inj.should_step(1)
    assert inj.responsive(2) and not inj.should_step(2)   # slow skips
    inj.begin_superstep(2)
    assert not inj.responsive(0)                          # crashed
    assert not inj.responsive(1)                          # still hung
    inj.begin_superstep(3)
    assert not inj.responsive(0)                          # crash is forever
    assert inj.responsive(1) and inj.should_step(1)       # hang resumed
    assert inj.responsive(2) and inj.should_step(2)       # slow: step 3
    assert {(f.kind, f.place) for f in inj.fired} == {
        ("crash", 0), ("hang", 1), ("slow", 2)}
    with pytest.raises(ValueError):
        Fault("meteor", 0, 0)
    with pytest.raises(ValueError):
        Fault("slow", 0, 0, factor=1)


# ------------------------------------------------------ lifeline rewire
@pytest.mark.parametrize("dead", [(3,), (0, 5), (1, 2, 6, 7)])
def test_rewire_lifelines_invariants(dead):
    P, z = 8, 3
    alive = np.ones(P, bool)
    alive[list(dead)] = False
    bud = rewire_lifelines(alive, z)
    assert bud.shape == (P, z)
    surv = set(np.flatnonzero(alive).tolist())
    for p in range(P):
        if p in surv:
            assert set(bud[p].tolist()) <= surv      # only survivors
            assert p not in bud[p]                   # never self (S > 1)
        else:
            assert set(bud[p].tolist()) == {p}       # dead rows inert
    # connectivity over survivors: z-hypercube edges reach everyone
    reach = {min(surv)}
    for _ in range(P):
        reach |= {int(b) for p in reach for b in bud[p]}
    assert reach == surv


def test_rewire_lifelines_edge_cases():
    # sole survivor self-points (inert but well-formed)
    alive = np.array([False, True, False, False])
    assert rewire_lifelines(alive, 2).tolist()[1] == [1, 1]
    with pytest.raises(ValueError):
        rewire_lifelines(np.zeros(4, bool), 2)
    # no deaths == the static table
    np.testing.assert_array_equal(
        rewire_lifelines(np.ones(8, bool), 3), lifeline_buddies(8, 3))


# ------------------------------------------------- the headline: sweep
@pytest.mark.parametrize("crash_at", [0, 1, 2, 4])
def test_crash_sweep_no_request_lost(crash_at):
    """Crash replica 0 at superstep ``crash_at``: the fabric must
    terminate with every request finished, greedy-token-identical to a
    clean run, and no surviving lifeline referencing the dead place."""
    engines, bal = _fabric(faults=FaultInjector().crash(0, at=crash_at))
    # long enough that the victim's work is still in flight when the
    # 3-miss window expires (steps_per_sync=4 tokens per engine step)
    reqs = _reqs(max_new=24)
    for r in reqs:
        bal.submit(r)
    assert bal.run(max_steps=300) == "terminated"
    assert bal.terminated
    assert bal.replicas_dead == 1
    assert not bal.alive[0]
    assert all(r.done for r in reqs)                      # zero lost
    assert [list(r.out) for r in reqs] == _clean_outputs(4, 24)
    bud = np.asarray(bal._buddies)
    for p in np.flatnonzero(bal.alive):
        assert 0 not in bud[p], "survivor lifeline points at the corpse"
    assert not np.asarray(bal._pending)[0].any()
    assert not np.asarray(bal._pending)[:, 0].any()
    # the ledger balances: everything submitted is accounted done
    assert set(bal._ledger) == {r.rid for r in reqs}
    assert bal.readmitted_queued + bal.readmitted_running >= 1


def test_crash_readmits_queued_requests():
    """Crash before the victim ever steps: its casualties are all still
    queued and come back via plain re-submission (tier-1 recovery)."""
    engines, bal = _fabric(faults=FaultInjector().crash(0, at=0))
    reqs = _reqs()
    for r in reqs:
        bal.submit(r)
    assert bal.run(max_steps=300) == "terminated"
    assert all(r.done for r in reqs)
    assert bal.readmitted_queued >= 1
    assert [list(r.out) for r in reqs] == _clean_outputs()


def test_hang_shorter_than_window_recovers():
    """A 2-superstep hang under the default 3-miss window is absorbed:
    nobody is declared dead and nothing is re-admitted."""
    engines, bal = _fabric(faults=FaultInjector().hang(0, at=1, duration=2))
    reqs = _reqs()
    for r in reqs:
        bal.submit(r)
    assert bal.run(max_steps=300) == "terminated"
    assert bal.replicas_dead == 0
    assert bal.readmitted_queued == bal.readmitted_running == 0
    assert all(bal.alive)
    assert all(r.done for r in reqs)
    assert [list(r.out) for r in reqs] == _clean_outputs()


def test_slow_replica_not_declared_dead():
    """Slow is a compute property, not a liveness one: the place answers
    every gather, so the detector must leave it alone (specificity)."""
    engines, bal = _fabric(faults=FaultInjector().slow(0, at=0, factor=3))
    reqs = _reqs()
    for r in reqs:
        bal.submit(r)
    assert bal.run(max_steps=600) == "terminated"
    assert bal.replicas_dead == 0
    assert all(r.done for r in reqs)
    assert [list(r.out) for r in reqs] == _clean_outputs()


def test_zombie_is_fenced_after_declaration():
    """A hang LONGER than the window is declared dead; when the place
    'wakes up' it must stay fenced — a zombie double-producing tokens
    would corrupt the fabric (its work was already re-admitted)."""
    engines, bal = _fabric(faults=FaultInjector().hang(0, at=1, duration=8),
                           heartbeat_misses=2)
    reqs = _reqs()
    for r in reqs:
        bal.submit(r)
    assert bal.run(max_steps=300) == "terminated"
    assert bal.replicas_dead == 1
    assert not bal.alive[0]                    # still fenced
    assert all(r.done for r in reqs)
    assert [list(r.out) for r in reqs] == _clean_outputs()
    # the hang is long over; the place answers gathers again — but a
    # declared death is permanent: new work routes around the zombie
    # and it is never stepped again
    steps0 = engines[0].steps
    late = Request(rid=99, prompt=list(PROMPT16), max_new=4)
    bal.submit(late)
    assert bal.run(max_steps=300) == "terminated"
    assert late.done
    assert not bal.alive[0]
    assert engines[0].steps == steps0


def test_running_readmission_needs_compatible_host():
    """A running casualty can only recompute-land on a survivor whose
    max_seq fits its recompute prefix; a fabric whose only survivor can't
    host must fail loudly, not drop the request."""
    tr = None
    victim = Engine(CFG, _params(), replica_id=0, block_size=8,
                    num_blocks=64, tracer=tr, **KW)
    # max_seq 16 holds no prefix of the 16-token prompt plus its output
    survivor = Engine(CFG, _params(), replica_id=1, block_size=8,
                      tracer=tr, **dict(KW, max_seq=16))
    bal = GLBReplicaBalancer([victim, survivor], migrate=True,
                             faults=FaultInjector().crash(0, at=2))
    req = Request(rid=0, prompt=list(PROMPT16), max_new=40)
    bal.submit(req, rr=0)                  # pin to the doomed replica
    victim.step()                          # now RUNNING in a slot
    with pytest.raises(RuntimeError, match="no surviving paged"):
        bal.run(max_steps=100)


def test_all_replicas_dead_raises():
    engines, bal = _fabric(n=2, faults=FaultInjector().crash(0, at=0)
                                                      .crash(1, at=0))
    bal.submit(Request(rid=0, prompt=list(PROMPT16), max_new=4))
    with pytest.raises(RuntimeError):
        bal.run(max_steps=100)


# --------------------------------------------- satellite 1: sterile steal
def test_incompatible_thief_no_sterile_steal():
    """_stealable must advertise only what the present thieves can host:
    a victim whose sequences exceed every thief's max_seq produces NO
    match at all, not a sterile one (pre-fix: matched every round,
    moved nothing, moves counter still climbed)."""
    victim = Engine(CFG, _params(), replica_id=0, block_size=8,
                    num_blocks=64, max_slots=2, max_seq=64, pad_len=16,
                    steps_per_sync=4)
    thief = Engine(CFG, _params(), replica_id=1, block_size=8,
                   num_blocks=64, max_slots=2, max_seq=32, pad_len=16,
                   steps_per_sync=4)
    bal = GLBReplicaBalancer([victim, thief], migrate=True)
    for i in range(2):
        bal.submit(Request(rid=i, prompt=list(PROMPT16), max_new=40), rr=0)
    for _ in range(6):                     # grow written past thief's 32
        victim.step()
    for _ in range(8):
        bal.balance()
        victim.step()
    assert bal.sterile_steals == 0
    assert bal.migrations == 0
    assert bal.moves == 0


# ------------------------------------------- satellite 2: counter split
def test_move_counter_split_and_report():
    """moves == queue_moves + migrations, the trace's per-tier counts
    agree, and the report spells the split out."""
    from repro.obs import Tracer
    from repro.obs.analyze import analyze_trace, check_invariants
    tr = Tracer()
    engines, bal = _fabric(n=2, tracer=tr, block_size=8, num_blocks=32,
                           max_seq=32, pad_len=8)
    for i in range(6):
        engines[0].submit(Request(rid=i, prompt=[3, i + 1, 4, 2],
                                  max_new=8))
    assert bal.run(max_steps=200) == "terminated"
    assert bal.moves == bal.queue_moves + bal.migrations
    assert bal.moves > 0
    a = analyze_trace(tr)
    assert check_invariants(a) == []
    assert a.steal.tier1_moves == bal.queue_moves
    assert a.steal.tier2_moves == bal.migrations
    if a.steal.tier1_rounds:
        assert a.steal.tier1_moves_per_round > 0
    assert "queued" in bal.report()


# --------------------------------------------- satellite 3: wedge status
def test_run_returns_wedged_and_traces_it():
    from repro.obs import Tracer
    from repro.obs.analyze import analyze_trace
    tr = Tracer()
    engines, bal = _fabric(n=1, tracer=tr)
    bal.submit(Request(rid=0, prompt=list(PROMPT16), max_new=40))
    assert bal.run(max_steps=2) == "wedged"
    assert not bal.terminated
    assert analyze_trace(tr).steal.wedged
    # a fresh fabric that drains reports success
    engines2, bal2 = _fabric(n=1)
    bal2.submit(Request(rid=0, prompt=list(PROMPT16), max_new=4))
    assert bal2.run(max_steps=300) == "terminated"


# -------------------------------------------------- analyzer attribution
def test_analyzer_recovery_attribution():
    """A crash trace analyzes clean: the re-admitted request carries a
    readmissions count and a 'recovering' bucket, the steal report sees
    the death, and the invariant checker stays green."""
    from repro.obs import Tracer
    from repro.obs.analyze import analyze_trace, check_invariants
    tr = Tracer()
    engines, bal = _fabric(tracer=tr,
                           faults=FaultInjector().crash(0, at=1))
    reqs = _reqs()
    for r in reqs:
        bal.submit(r)
    assert bal.run(max_steps=300) == "terminated"
    a = analyze_trace(tr)
    assert check_invariants(a) == []
    assert a.steal.replicas_dead == 1
    total_readmit = bal.readmitted_queued + bal.readmitted_running
    assert a.steal.readmissions == total_readmit >= 1
    readmitted = [r for r in a.requests if r.readmissions > 0]
    assert len(readmitted) == total_readmit
    assert not a.steal.wedged
    d = a.to_dict()
    assert d["steal"]["replicas_dead"] == 1
    from repro.obs.analyze import render_markdown, render_summary
    assert "failures" in render_markdown(a)
    assert "failures" in render_summary(a)


# --------------------------------------------------- taskbag sim chaos
def test_sim_fib_crash_exact():
    """fib survives a mid-run crash with the exact same answer: the dead
    place's bag is drained wholesale into the survivors."""
    prob = fib_problem(16)
    want = int(run_sim(prob, 4, GLBParams(n=16, steal_k=16), seed=0)
               .result)
    got = run_sim(prob, 4, GLBParams(n=16, steal_k=16), seed=0,
                  faults=FaultInjector().crash(1, at=2))
    assert int(got.result) == want == fib_oracle(16)
    assert bool(got.converged)


def test_sim_uts_crash_at_root_holder():
    """Crash place 0 — the root holder — after it has expanded a bit:
    its remaining subtree must migrate and the count stays exact."""
    prob = uts_problem(depth=5)
    want = int(run_sim(prob, 4, GLBParams(n=32, steal_k=16), seed=0)
               .result)
    got = run_sim(prob, 4, GLBParams(n=32, steal_k=16), seed=0,
                  faults=FaultInjector().crash(0, at=2))
    assert int(got.result) == want == uts_oracle(depth=5)


def test_sim_bc_crash_evacuates_in_state_vertex():
    """BC holds an in-progress vertex in state (§2.6's interruptable
    state machine); evacuate() re-bags it so the crash loses nothing."""
    from repro.problems.rmat import rmat_graph
    adj, n = rmat_graph(scale=4, seed=7)
    prob = bc_problem(adj, capacity=256)
    want = np.asarray(run_sim(prob, 4, GLBParams(n=4, steal_k=8),
                              seed=0).result)
    got = run_sim(prob, 4, GLBParams(n=4, steal_k=8), seed=0,
                  faults=FaultInjector().crash(2, at=3))
    np.testing.assert_allclose(np.asarray(got.result), want,
                               rtol=1e-4, atol=1e-4)


def test_sim_hang_shorter_than_window_is_absorbed():
    prob = fib_problem(14)
    clean = run_sim(prob, 4, GLBParams(n=16, steal_k=16), seed=0)
    got = run_sim(prob, 4, GLBParams(n=16, steal_k=16), seed=0,
                  faults=FaultInjector().hang(1, at=1, duration=2))
    assert int(got.result) == int(clean.result)


def test_sim_faults_require_evacuate_hook():
    """A problem with in-state work but no evacuate hook cannot be run
    under fault injection — its mid-item window isn't survivable."""
    from repro.problems.rmat import rmat_graph
    adj, _ = rmat_graph(scale=4, seed=7)
    prob = dataclasses.replace(bc_problem(adj, capacity=256),
                               evacuate=None)
    with pytest.raises(ValueError, match="evacuate"):
        run_sim(prob, 4, GLBParams(n=4), seed=0,
                faults=FaultInjector().crash(1, at=1))
    # ...and GLB.run forwards the injector only in sim mode
    glb = GLB(fib_problem(12), GLBParams(n=16), P=2)
    assert int(glb.run(seed=0, faults=FaultInjector().crash(1, at=50))) \
        == fib_oracle(12)


def test_sim_all_places_dead_raises():
    prob = fib_problem(14)
    with pytest.raises(RuntimeError, match="died"):
        run_sim(prob, 2, GLBParams(n=4, steal_k=4), seed=0,
                faults=FaultInjector().crash(0, at=0).crash(1, at=0))
