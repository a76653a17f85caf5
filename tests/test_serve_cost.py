"""Cost model + predictive balancing (DESIGN.md §16): the per-tenant
decode-length predictor must converge on a synthetic length mix (with the
prior/global cold-start fallbacks), prediction error must shrink as the
online updates land, SLO-aware admission must reorder and pace by slack,
and — the hard contract — a balancer with the predictor OFF must
reproduce today's steal/shed decisions exactly on the skewed-fabric
scenario."""
import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.lifeline import diffusion_pairs
from repro.models import init_lm
from repro.obs.slo import SLOMonitor, parse_slo_spec
from repro.serve import (CostModel, CostParams, DecodeLengthPredictor,
                         Engine, GLBReplicaBalancer, Request)

CFG = ARCHS["tinyllama-1.1b"].smoke()
PARAMS = init_lm(jax.random.key(0), CFG)


# ------------------------------------------------------------- predictor
def test_cold_start_uses_prior():
    p = CostParams(prior_decode_tokens=40.0)
    cm = CostModel(p)
    assert cm.predict_decode("anyone", max_new=128) == 40.0
    # prior is clipped into the request's feasible range
    assert cm.predict_decode("anyone", max_new=16) == 16.0
    assert cm.predictor.source("anyone") == "prior"


def test_global_fallback_before_tenant_history():
    pred = DecodeLengthPredictor(CostParams(min_samples=3))
    for _ in range(5):
        pred.observe("veteran", 10)
    # a brand-new tenant answers from the pooled global histogram
    assert pred.source("newcomer") == "global"
    assert pred.predict("newcomer") == pytest.approx(10.0, abs=3.0)
    assert pred.source("veteran") == "tenant"


def test_predictor_converges_on_tenant_mix():
    """Synthetic per-tenant mix: short chat turns vs long completions.
    Each tenant's prediction must converge to its own distribution, not
    the pooled mean."""
    pred = DecodeLengthPredictor()
    for _ in range(20):
        pred.observe("chat", 8)
        pred.observe("long", 100)
    short, long_ = pred.predict("chat"), pred.predict("long")
    assert short < long_
    assert short == pytest.approx(8.0, abs=4.0)       # bucket resolution
    assert long_ == pytest.approx(100.0, abs=30.0)
    assert pred.samples("chat") == pred.samples("long") == 20


def test_prediction_error_shrinks_over_a_run():
    """Online loop: a tenant that always decodes 12 tokens starts at the
    prior (way off) and must be predicted near-exactly once min_samples
    finishes have landed — late-half error < early-half error."""
    cm = CostModel(CostParams(prior_decode_tokens=64.0, min_samples=3))
    for i in range(12):
        req = Request(rid=i, prompt=[1, 2, 3], max_new=96, tenant="t")
        cm.stamp(req)
        req.out = [7] * 12
        cm.observe_finish(req)
    snap = cm.snapshot()
    assert snap["cost_samples"] == 12
    assert snap["cost_late_abs_err_tokens"] \
        < snap["cost_early_abs_err_tokens"]
    # steady state: predictions are within a bucket of the truth
    assert cm.errors[-1] <= 2.0


def test_estimate_monotone_in_inputs():
    cm = CostModel()
    base = cm.estimate(64, 0, 0, "t", 96, 8)
    assert cm.estimate(128, 0, 0, "t", 96, 8) > base     # longer prompt
    assert cm.estimate(64, 32, 0, "t", 96, 8) < base     # warmer cache
    assert base > 0.0
    # a running request is cheaper than a queued one (prefill sunk)
    assert cm.estimate(64, 0, 10, "t", 96, 8) < base


def test_stamp_survives_resubmit():
    cm = CostModel()
    req = Request(rid=0, prompt=[1], max_new=32, tenant="t")
    first = cm.stamp(req)
    cm.predictor.observe("t", 5)
    cm.predictor.observe("t", 5)
    cm.predictor.observe("t", 5)
    assert cm.stamp(req) == first          # steal re-submit keeps stamp
    assert cm.predictions == 1


def test_cost_params_validation():
    with pytest.raises(ValueError):
        CostParams(quantile=1.5)
    with pytest.raises(ValueError):
        CostParams(us_per_decode_token=0.0)
    with pytest.raises(ValueError):
        CostParams(min_samples=0)


# ------------------------------------------------------------- diffusion
def test_diffusion_pairs_deterministic_and_balanced():
    assert diffusion_pairs([10.0, 1.0, 1.0, 1.0], 0.25) == [(0, 1)]
    assert diffusion_pairs([1.0, 1.0, 1.0], 0.25) == []
    assert diffusion_pairs([0.0, 0.0], 0.25) == []       # empty fabric
    # two donors, two recipients: richest donor gets poorest recipient
    pairs = diffusion_pairs([10.0, 8.0, 1.0, 2.0], 0.25)
    assert pairs == [(0, 2), (1, 3)]
    # ineligible recipients are skipped
    assert diffusion_pairs([10.0, 1.0, 1.0], 0.25,
                           [True, False, True]) == [(0, 2)]


# ------------------------------------------------------ SLO admission
def _slo_engine(**kw):
    slo = SLOMonitor(parse_slo_spec(kw.pop("spec", "ttft_ms=1000000")))
    return Engine(CFG, PARAMS, max_slots=kw.pop("max_slots", 2),
                  max_seq=64, pad_len=8, steps_per_sync=4,
                  block_size=8, num_blocks=24, slo=slo,
                  slo_admission=True, **kw), slo


def test_slo_admission_requires_monitor_and_target():
    with pytest.raises(ValueError):
        Engine(CFG, PARAMS, block_size=8, max_seq=64,
               slo_admission=True)                       # no monitor
    with pytest.raises(ValueError):
        slo = SLOMonitor(parse_slo_spec("tpot_ms=50"))   # wrong metric
        Engine(CFG, PARAMS, block_size=8, max_seq=64,
               slo=slo, slo_admission=True)


def test_slo_admission_orders_by_slack():
    """With one slot free, the request whose TTFT budget is most blown
    is admitted first even though it arrived last."""
    eng, _ = _slo_engine(max_slots=1)
    reqs = [Request(rid=i, prompt=[1 + i] * 4, max_new=20)
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    # rid 2 has been waiting "forever": most negative slack
    reqs[2].t_queued -= 1e12
    eng.step()
    assert eng.slots[0] is not None and eng.slots[0].rid == 2


def test_slo_admission_paces_relaxed_tail():
    """Non-urgent admissions are paced to one per step while work runs;
    the deferral is counted and the deferred request is admitted on a
    later step — pacing delays, never starves."""
    eng, _ = _slo_engine(max_slots=4)
    r0 = Request(rid=0, prompt=[9] * 4, max_new=8)
    eng.submit(r0)
    eng.step()                               # r0 running
    assert any(s is not None for s in eng.slots)
    late = [Request(rid=i, prompt=[i] * 4, max_new=4) for i in (1, 2, 3)]
    for r in late:
        eng.submit(r)
    eng.step()
    assert eng.sched.paced_deferrals >= 1
    assert len(eng.queue) >= 1               # relaxed tail still queued
    while eng.load > 0 and eng.steps < 60:
        eng.step()
    assert all(r.done for r in [r0] + late)  # nobody starved


def test_fifo_admission_unchanged_without_flag():
    """Reactive-parity at the scheduler level: no flag, strict FIFO."""
    eng = Engine(CFG, PARAMS, max_slots=1, max_seq=64, pad_len=8,
                 steps_per_sync=4, block_size=8,
                 num_blocks=24)
    reqs = [Request(rid=i, prompt=[1 + i] * 4, max_new=20)
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    reqs[2].t_queued -= 1e12                 # would be urgent under SLO
    eng.step()
    assert eng.slots[0] is not None and eng.slots[0].rid == 0


# --------------------------------------------- skewed-fabric scenarios
def _skew_fabric(cost_model=None, predictive=False, n=6):
    engines = [Engine(CFG, PARAMS, max_slots=2, max_seq=64, pad_len=16,
                      steps_per_sync=4, block_size=8,
                      num_blocks=16, prefix_cache=True, prefill_chunk=8,
                      cost_model=cost_model)
               for _ in range(2)]
    bal = GLBReplicaBalancer(engines, migrate=True,
                             cost_model=cost_model, predictive=predictive)
    reqs = [Request(rid=i, prompt=[1 + i] * 12, max_new=10,
                    tenant=f"t{i % 2}") for i in range(n)]
    for r in reqs:
        bal.submit(r, rr=0)                  # everything on replica 0
    return bal, reqs


def test_reactive_parity_predictor_off():
    """THE regression gate: attaching a cost model with predictive=False
    must reproduce the plain balancer's steal/shed decisions exactly on
    the skewed fabric — same decision log, same supersteps, same
    outputs."""
    plain, plain_reqs = _skew_fabric()
    assert plain.run() == "terminated"
    parity, parity_reqs = _skew_fabric(cost_model=CostModel())
    assert parity.run() == "terminated"
    assert parity.decisions == plain.decisions
    assert parity.supersteps == plain.supersteps
    assert parity.diffusion_moves == 0
    assert ([r.out for r in parity_reqs]
            == [r.out for r in plain_reqs])
    # ... while the model itself DID observe the run
    assert len(parity.cost_model.errors) == len(parity_reqs)


def test_predictive_moves_before_starvation():
    """Predictive mode diffuses queued work off the overloaded replica
    proactively, terminates in no more supersteps than reactive, and
    keeps greedy outputs identical."""
    reactive, r_reqs = _skew_fabric()
    assert reactive.run() == "terminated"
    predictive, p_reqs = _skew_fabric(cost_model=CostModel(),
                                      predictive=True)
    assert predictive.run() == "terminated"
    assert predictive.diffusion_moves > 0
    assert predictive.supersteps <= reactive.supersteps
    assert ([r.out for r in p_reqs] == [r.out for r in r_reqs])


def test_predictive_requires_cost_model():
    engines = [Engine(CFG, PARAMS, max_slots=2, max_seq=64,
                      block_size=8)]
    with pytest.raises(ValueError):
        GLBReplicaBalancer(engines, predictive=True)


def test_predictive_load_vector_and_report():
    bal, _ = _skew_fabric(cost_model=CostModel(), predictive=True)
    costs = bal._fabric_costs()
    assert costs[0] > 0.0 and costs[1] == 0.0    # all work on replica 0
    assert bal.run() == "terminated"
    merged = bal.collect()
    assert merged["_balancer"]["diffusion_moves"] == bal.diffusion_moves
    assert merged["_cost"]["cost_samples"] > 0
    assert "predictive:" in bal.report()


def test_request_cost_credits_prefix_cache():
    """The same queued request is cheaper on a replica whose radix cache
    already holds its prefix."""
    eng = Engine(CFG, PARAMS, max_slots=2, max_seq=64, pad_len=16,
                 steps_per_sync=4, block_size=8,
                 num_blocks=24, prefix_cache=True, prefill_chunk=8,
                 cost_model=CostModel())
    shared = [5] * 16
    warm = Request(rid=0, prompt=shared, max_new=6)
    eng.submit(warm)
    while eng.load > 0 and eng.steps < 40:
        eng.step()
    assert warm.done
    again = Request(rid=1, prompt=shared, max_new=6)
    cold = Request(rid=2, prompt=[9] * 16, max_new=6)
    assert eng.prefix_cache.hit_length(eng._prefix_tokens(again)) > 0
    assert (eng.request_cost(again, True)
            < eng.request_cost(cold, True))
