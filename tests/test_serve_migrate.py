"""Live KV migration between replica engines (DESIGN.md §9): the
lifeline protocol's "steal work in progress" applied to serving.

Covers the Migration ownership contract (migrate_out frees the victim,
migrate_in must land every sequence somewhere), the three landing modes
(live / radix-seeded / recompute) each preserving greedy token identity,
mid-prefill rejection, the shed policies, the balancer's two-tier steal
order (queue first, live sequences only when the victim's queue is empty
but its slots are saturated), GLB termination detection, and fabric-level
result collection."""
import jax
import pytest

from _serve_reference import greedy_reference
from repro.configs import ARCHS
from repro.core import merge_place_stats, terminated
from repro.serve.engine import Engine, GLBReplicaBalancer, Request
from repro.serve.kvpool import KVPool, PoolExhausted

CFG = ARCHS["tinyllama-1.1b"].smoke()
_P = {}


def _params():
    if "p" not in _P:
        from repro.models import init_lm
        _P["p"] = init_lm(jax.random.key(0), CFG)
    return _P["p"]


PROMPT16 = [7, 3, 9, 2, 5, 8, 6, 4, 1, 2, 3, 4, 9, 9, 8, 7]
KW = dict(max_slots=2, max_seq=64, pad_len=16, steps_per_sync=4)


def _reference_baseline(reqs):
    return greedy_reference(CFG, _params(), reqs, pad_len=KW["pad_len"],
                            max_seq=KW["max_seq"])


def _drain(*engines, guard=600):
    while any(e.load > 0 for e in engines) and guard > 0:
        for e in engines:
            e.step()
        guard -= 1
    assert guard > 0, "fabric failed to drain"


# ------------------------------------------------------------ pool extract
def test_extract_inject_roundtrip_pool_level():
    """extract names exactly the written blocks (lookahead reservations
    excluded); inject re-registers the sequence atomically on a peer."""
    pool = KVPool(8, 4)
    pool.alloc(1, 10)                       # 3 blocks written
    pool.reserve(1, 14)                     # +1 lookahead block
    blocks, written = pool.extract(1)
    assert written == 10
    assert blocks == pool.block_table(1)[:3]
    peer = KVPool(8, 4)
    table = peer.inject(1, 10)
    assert len(table) == 3 and peer.seq_len(1) == 10
    tiny = KVPool(2, 4)
    with pytest.raises(PoolExhausted):
        tiny.inject(7, 12)                  # needs 3 > 2 blocks
    assert tiny.free_blocks == 2            # atomic: nothing leaked


# ------------------------------------------------------- mid-prefill guard
def test_mid_prefill_slot_cannot_migrate():
    """A half-prefilled slot owns half-written blocks and a chunk plan;
    it is excluded from shed_candidates and migrate_out rejects it."""
    e = Engine(CFG, _params(), block_size=8, prefill_chunk=4,
               token_budget=4, **KW)
    e.submit(Request(rid=0, prompt=list(PROMPT16), max_new=5))
    e.step()                                # first chunk only (budget 4)
    assert e.sched.mid_prefill(0)
    assert e.migratable_slots() == []
    with pytest.raises(ValueError):
        e.migrate_out(0)
    _drain(e)


# --------------------------------------------------------- fallback modes
BLOCKER_PROMPT = [9, 8, 7, 6, 5, 4, 3, 2, 1, 2, 3, 4, 5]   # 13 tokens


def _wedged_victim(steps=7, max_new=30):
    """One long-running sequence mid-decode on a paged engine: after
    ``steps`` bursts of 4 its written length is 16 + 4*steps (44 by
    default — 6 pool blocks)."""
    e = Engine(CFG, _params(), block_size=8,
               **dict(KW, max_slots=1))
    req = Request(rid=0, prompt=list(PROMPT16), max_new=max_new)
    e.submit(req)
    for _ in range(steps):
        e.step()
    assert not req.done
    return e, req


def _tight_thief(**extra):
    """Thief (8-block pool) where a blocker pins 3 blocks at migration
    time (written 17, capacity 24 tokens) and then finishes WITHOUT ever
    reserving another block — so the pool is tight when the migrant
    arrives, but nothing later forces an eviction of seeded blocks, and
    the pool drains naturally for the resume admission."""
    e = Engine(CFG, _params(), block_size=8, num_blocks=8,
               **dict(KW, max_slots=2), **extra)
    blocker = Request(rid=50, prompt=list(BLOCKER_PROMPT), max_new=8)
    e.submit(blocker)
    e.step()                    # lens 17, 3 blocks held, 5 free
    assert not blocker.done
    assert e.pool.available_blocks == 5
    return e, blocker


def test_pool_exhausted_falls_back_to_recompute():
    base = _reference_baseline([Request(rid=0, prompt=list(PROMPT16),
                                     max_new=30),
                             Request(rid=50, prompt=list(BLOCKER_PROMPT),
                                     max_new=8)])
    victim, req = _wedged_victim()
    thief, blocker = _tight_thief()
    mig = victim.migrate_out(victim.migratable_slots()[0])
    assert mig.written == 44                # needs 6 blocks > 5 available
    mode = thief.migrate_in(mig)
    assert mode == "recompute"
    assert thief.queue and thief.queue[0] is req   # front of the queue
    _drain(victim, thief)
    assert [list(req.out), list(blocker.out)] == base
    assert thief.migrations_recompute == 1


def test_radix_seeded_resume():
    """When the whole sequence cannot fit, the thief plants however many
    full blocks DO fit in its radix cache, and the recompute admission
    hits the planted prefix instead of re-prefilling from scratch."""
    base = _reference_baseline([Request(rid=0, prompt=list(PROMPT16),
                                     max_new=30),
                             Request(rid=50, prompt=list(BLOCKER_PROMPT),
                                     max_new=8)])
    victim, req = _wedged_victim()
    thief, blocker = _tight_thief(prefix_cache=True)
    mig = victim.migrate_out(victim.migratable_slots()[0])
    mode = thief.migrate_in(mig)
    assert mode == "seeded"
    assert thief.migrations_seeded == 1
    assert thief.migrations_recompute == 0   # seeded is NOT a recompute
    assert thief.prefix_cache.seeded_tokens >= 8
    hits0 = thief.prefix_cache.hits
    _drain(victim, thief)
    assert thief.prefix_cache.hits > hits0, \
        "resume admission must hit the seeded prefix"
    assert thief.prefix_cache.tokens_reused >= 8
    assert [list(req.out), list(blocker.out)] == base


def test_migration_between_block_size_mismatch_recomputes():
    """Different pool geometries cannot exchange raw blocks; the move
    degrades to resume-by-recompute, never to corruption."""
    base = _reference_baseline([Request(rid=0, prompt=list(PROMPT16),
                                     max_new=30)])
    victim, req = _wedged_victim()
    thief = Engine(CFG, _params(), block_size=16, **KW)
    mode = thief.migrate_in(victim.migrate_out(0))
    assert mode == "recompute"
    _drain(victim, thief)
    assert [list(req.out)] == base


def test_migration_longer_than_thief_capacity_is_refused():
    """A sequence whose cache prefix cannot fit the thief's max_seq can
    never decode there (live landing would overflow _device_tables, a
    recompute requeue would crash the thief's admission): migrate_in
    refuses outright — ownership stays with the caller — and the
    balancer's can_host pre-filter never sheds to such a thief."""
    victim, req = _wedged_victim()          # written 44
    thief = Engine(CFG, _params(), block_size=8,
                   **dict(KW, max_seq=32))  # can host < 32 cache tokens
    assert not thief.can_host(44)
    mig = victim.migrate_out(0)
    with pytest.raises(ValueError):
        thief.migrate_in(mig)
    # the Migration still owns the request; the victim can take it back
    victim._requeue_migrated(req)
    _drain(victim)
    assert req.done


def test_balancer_skips_incompatible_thief():
    """_steal_live's can_host filter: a saturated victim facing a thief
    with a smaller max_seq keeps its sequences instead of crashing."""
    victim = Engine(CFG, _params(), block_size=8, **KW)
    thief = Engine(CFG, _params(), block_size=8,
                   **dict(KW, max_seq=32))
    bal = GLBReplicaBalancer([victim, thief], migrate=True)
    reqs = [Request(rid=i, prompt=list(PROMPT16), max_new=40)
            for i in range(2)]
    for r in reqs:
        bal.submit(r, rr=0)
    for _ in range(6):
        victim.step()                   # written grows past thief max_seq
    assert all(int(victim.lens[s]) >= 32 for s in range(2))
    bal.run(max_steps=200)
    assert bal.migrations == 0          # nothing compatible to shed
    assert all(r.done for r in reqs)


# ----------------------------------------------------------- shed policy
def test_shed_policy_orders_candidates():
    def mk(policy):
        e = Engine(CFG, _params(), block_size=8,
                   shed_policy=policy, **KW)
        e.submit(Request(rid=0, prompt=list(PROMPT16), max_new=20))
        e.submit(Request(rid=1, prompt=list(PROMPT16), max_new=6))
        e.step()
        return e
    young = mk("youngest")
    # slot 1 (rid 1) admitted last => youngest-first leads with it
    assert young.migratable_slots()[0] == 1
    budget = mk("budget")
    # rid 0 has far more budget left => budget policy leads with slot 0
    assert budget.migratable_slots()[0] == 0
    with pytest.raises(AssertionError):
        Engine(CFG, _params(), block_size=8,
               shed_policy="bogus", **KW)
    _drain(young, budget)


# ------------------------------------------------------ two-tier balancer
def test_balancer_steals_queue_before_live_sequences():
    """A victim with queued requests sheds its queue (tier 1); live
    sequences move only when the queue is empty."""
    mk = lambda: Engine(CFG, _params(), block_size=8,
                        **dict(KW, max_slots=1))
    engines = [mk(), mk()]
    bal = GLBReplicaBalancer(engines, migrate=True)
    for i in range(3):
        bal.submit(Request(rid=i, prompt=[3, i + 1, 4], max_new=8), rr=0)
    engines[0].step()                   # 1 running + 2 queued on victim
    bal.balance()
    assert bal.moves > 0 and bal.migrations == 0, \
        "queued work must move before running work"


def test_balancer_saturated_victim_sheds_live_sequence():
    mk = lambda: Engine(CFG, _params(), block_size=8, **KW)
    engines = [mk(), mk()]
    bal = GLBReplicaBalancer(engines, migrate=True)
    reqs = [Request(rid=i, prompt=[3, i + 1, 4], max_new=20)
            for i in range(2)]
    for r in reqs:
        bal.submit(r, rr=0)
    engines[0].step()                   # both running, queue empty
    assert engines[0].free_slots == 0 and not engines[0].queue
    bal.run(max_steps=100)
    assert bal.migrations >= 1 and bal.migration_modes["live"] >= 1
    assert all(r.done for r in reqs)
    assert engines[1].migrations_in >= 1


def test_balancer_migrate_off_never_moves_live():
    mk = lambda: Engine(CFG, _params(), block_size=8, **KW)
    engines = [mk(), mk()]
    bal = GLBReplicaBalancer(engines)   # migrate defaults off
    reqs = [Request(rid=i, prompt=[3, i + 1, 4], max_new=20)
            for i in range(2)]
    for r in reqs:
        bal.submit(r, rr=0)
    engines[0].step()
    bal.run(max_steps=100)
    assert bal.migrations == 0
    assert all(r.done for r in reqs)


# ------------------------------------- termination + result collection
def test_termination_via_size_vector_and_result_collection():
    assert terminated([0, 0, 0]) and not terminated([0, 2, 0])
    mk = lambda: Engine(CFG, _params(), block_size=8, **KW)
    engines = [mk(), mk(), mk()]
    bal = GLBReplicaBalancer(engines, migrate=True)
    reqs = [Request(rid=i, prompt=[3, i + 1, 4], max_new=6 + i % 4)
            for i in range(7)]
    for r in reqs:
        bal.submit(r, rr=0)
    bal.run(max_steps=200)
    assert bal.terminated, "balance pass must detect the all-zero loads"
    assert all(r.done for r in reqs)
    merged = bal.collect()
    assert merged["tokens_out"]["total"] == sum(
        e.tokens_out for e in engines
    )
    assert merged["_balancer"]["supersteps"] == bal.supersteps
    assert "moves" in merged["_balancer"]
    report = bal.report()
    assert "replica fabric: 3 places" in report
    assert "terminated=True" in report


def test_merge_place_stats_heterogeneous_fields():
    merged = merge_place_stats([{"a": 1, "b": 2}, {"a": 3}])
    assert merged["a"] == {"total": 4.0, "mean": 2.0, "max": 3.0,
                           "argmax": 1}
    assert merged["b"]["total"] == 2.0 and merged["b"]["argmax"] == 0
