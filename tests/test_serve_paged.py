"""Paged serving contract: the engine (block pool + scheduler + paged
decode) must emit exactly what the plain greedy reference emits — across
mixed per-slot lengths, forced preemption and resume, and per-step token
budgets — while packing more sequences into a fixed KV memory than
full-length rows would hold. Plus the balancer satellites: FIFO steals
and capacity-based hunger."""
import jax

from _serve_reference import greedy_reference
from repro.configs import ARCHS
from repro.models import init_lm
from repro.serve.engine import Engine, GLBReplicaBalancer, Request

CFG = ARCHS["tinyllama-1.1b"].smoke()
PARAMS = init_lm(jax.random.key(0), CFG)


def _reqs(n=5, max_new=10):
    # mixed budgets => mixed final lengths across slots
    return [Request(rid=i, prompt=[3, i + 1, 4, 2], max_new=max_new + i % 4)
            for i in range(n)]


def _run(engine, reqs):
    for r in reqs:
        engine.submit(r)
    guard = 0
    while engine.load > 0 and guard < 500:
        engine.step()
        guard += 1
    assert all(r.done for r in reqs)
    return [list(r.out) for r in reqs]


def _reference(reqs_fn, **kw):
    return greedy_reference(CFG, PARAMS, reqs_fn(), pad_len=kw["pad_len"],
                            max_seq=kw["max_seq"])


def test_paged_matches_contiguous_greedy():
    """Against the contiguous-cache greedy reference (one request at a
    time, no engine)."""
    kw = dict(max_slots=2, max_seq=64, pad_len=8, steps_per_sync=8)
    out_c = _reference(_reqs, **kw)
    e_p = Engine(CFG, PARAMS, **kw)
    out_p = _run(e_p, _reqs())
    assert out_c == out_p
    # everything released: pool drains to empty
    assert e_p.pool.free_blocks == e_p.pool.num_blocks
    assert e_p.sched.preemptions == 0


def test_paged_preempt_and_resume_token_identical():
    """A pool too small for both sequences' growth forces watermark
    preemption; resume-by-recompute must keep greedy outputs identical
    to the never-preempted reference run."""
    kw = dict(max_slots=2, max_seq=32, pad_len=8, steps_per_sync=8)
    out_c = _reference(lambda: _reqs(5, 14), **kw)
    e_t = Engine(CFG, PARAMS, block_size=8, num_blocks=5, **kw)
    out_t = _run(e_t, _reqs(5, 14))
    assert e_t.sched.preemptions > 0, "pool sizing must force preemption"
    assert out_t == out_c
    assert e_t.pool.free_blocks == 5


def test_watermark_starved_pool_stays_live():
    """Regression: a sole sequence whose growth collides with the
    watermark must keep decoding via partial reservations (it must never
    preempt itself into a permanent admit/preempt loop)."""
    kw = dict(max_slots=2, max_seq=64, pad_len=8, steps_per_sync=8)
    out_c = _reference(
        lambda: [Request(rid=0, prompt=[3, 1, 4, 2], max_new=60)], **kw)
    # pool of exactly max_blocks, watermark 1: full lookahead reservation
    # is impossible near max_seq.
    e_p = Engine(CFG, PARAMS, block_size=8, num_blocks=8,
                 watermark_blocks=1, **kw)
    req_p = Request(rid=0, prompt=[3, 1, 4, 2], max_new=60)
    out_p = _run(e_p, [req_p])
    assert out_p == out_c


def test_paged_token_budget_paces_slots():
    """token_budget < slots * steps_per_sync pauses the youngest slots
    each step without changing any sequence's tokens."""
    kw = dict(max_slots=2, max_seq=32, pad_len=8, steps_per_sync=8)
    out_c = _reference(lambda: _reqs(4, 12), **kw)
    e_b = Engine(CFG, PARAMS, token_budget=8, **kw)
    out_b = _run(e_b, _reqs(4, 12))
    assert out_b == out_c


def test_paged_packs_more_sequences_at_fixed_memory():
    """With KV memory for 4 full-length (max_seq) rows, the paged engine
    runs at least twice as many sequences concurrently as 4 full-length
    reservations would allow."""
    max_seq, rows = 64, 4 * 64            # 4 full-length rows of KV
    bs = 8
    e_p = Engine(CFG, PARAMS, max_slots=rows // bs, max_seq=max_seq,
                 pad_len=8, steps_per_sync=4, block_size=bs,
                 num_blocks=rows // bs)   # the same rows, as blocks
    reqs_p = _reqs(12, 8)
    _run(e_p, reqs_p)
    assert e_p.peak_running >= 2 * (rows // max_seq)
    # and the tokens are still identical per request
    want = _reference(lambda: _reqs(12, 8), max_seq=max_seq, pad_len=8)
    assert [r.out for r in reqs_p] == want


def test_scheduler_exports_occupancy():
    e = Engine(CFG, PARAMS, max_slots=2, max_seq=32, pad_len=8,
               steps_per_sync=4, block_size=8)
    assert e.pool_occupancy == 0.0
    for r in _reqs(2, 8):
        e.submit(r)
    e.step()
    assert 0.0 < e.pool_occupancy <= 1.0
    s = e.pool.stats()
    assert s.live_blocks == s.num_blocks - s.free_blocks
    while e.load > 0:
        e.step()
    assert e.pool_occupancy == 0.0


# --------------------------------------------------------------- balancer
def test_balancer_steals_oldest_first():
    """Stolen requests must leave the victim's queue in arrival order
    (FIFO), not inverted from the tail."""
    engines = [Engine(CFG, PARAMS, max_slots=1, max_seq=32, pad_len=8,
                      steps_per_sync=4) for _ in range(2)]
    bal = GLBReplicaBalancer(engines)
    reqs = _reqs(6, 6)
    for r in reqs:
        bal.submit(r, rr=0)               # adversarial: all on replica 0
    bal.balance()
    assert bal.moves > 0
    stolen = [r.rid for r in engines[1].queue]
    assert stolen == sorted(stolen), "steals must preserve arrival order"
    remaining = [r.rid for r in engines[0].queue]
    assert remaining == sorted(remaining)
    # the thief got the OLDEST requests, not the newest
    assert stolen and stolen[0] == min(r.rid for r in reqs)


def test_balancer_hungry_on_free_capacity_not_total_idleness():
    """A replica with a running slot but spare capacity must steal; one
    with no free slots must not."""
    engines = [Engine(CFG, PARAMS, max_slots=2, max_seq=32, pad_len=8,
                      steps_per_sync=4) for _ in range(2)]
    bal = GLBReplicaBalancer(engines)
    # occupy ONE slot of replica 1 -> still hungry (a free slot remains)
    busy = Request(rid=100, prompt=[3, 5, 4, 2], max_new=30)
    engines[1].submit(busy)
    engines[1].step()
    assert engines[1].load > 0            # not idle -- old rule: not hungry
    assert engines[1].can_accept()
    for r in _reqs(6, 6):
        bal.submit(r, rr=0)
    bal.balance()
    assert bal.moves > 0, "partially-busy replica with capacity must steal"


def test_balancer_round_robin_ignores_rid_density():
    """Regression: placement used rid % P, so strided rids (all even, or
    clustered ids from an upstream sharder) piled every request onto one
    replica. The internal submission counter must spread them evenly
    regardless of rid values; the rr override still pins placement."""
    engines = [Engine(CFG, PARAMS, max_slots=1, max_seq=32, pad_len=8,
                      steps_per_sync=4) for _ in range(2)]
    bal = GLBReplicaBalancer(engines)
    for i in range(8):
        # adversarial rids: all even => rid % 2 == 0 for every request
        bal.submit(Request(rid=2 * i, prompt=[3, i + 1, 4], max_new=4))
    qs = [len(e.queue) for e in engines]
    assert qs == [4, 4], f"strided rids skewed placement: {qs}"
    bal2 = GLBReplicaBalancer(
        [Engine(CFG, PARAMS, max_slots=1, max_seq=32, pad_len=8,
                steps_per_sync=4) for _ in range(2)]
    )
    for i in range(4):
        bal2.submit(Request(rid=2 * i, prompt=[3, 1, 4], max_new=4), rr=0)
    assert [len(e.queue) for e in bal2.engines] == [4, 0]


def test_balancer_completes_all_requests_paged():
    """End-to-end: paged replicas + balancer drain an adversarial queue;
    pool pressure feeds hunger via can_accept."""
    engines = [Engine(CFG, PARAMS, max_slots=2, max_seq=32, pad_len=8,
                      steps_per_sync=4, block_size=8)
               for _ in range(2)]
    bal = GLBReplicaBalancer(engines)
    reqs = _reqs(10, 6)
    for r in reqs:
        bal.submit(r, rr=0)
    bal.run(max_steps=300)
    assert all(r.done for r in reqs)
    assert bal.moves > 0
    assert all(e.pool.free_blocks == e.pool.num_blocks for e in engines)


# ------------------------------------------------------------- donation
def _spy(engine, name, log):
    """Wrap ``engine.<name>``, logging the k/v pools (and prefill's row)
    each call was handed."""
    fn = getattr(engine, name)

    def spy(*args):
        log.extend(a[n] for a in args if isinstance(a, dict)
                   for n in ("k", "v") if n in a)
        return fn(*args)

    setattr(engine, name, spy)


def test_pool_writing_calls_donate_their_pools():
    """An admission (prefill into the pool) and a decode burst each take
    the pools (and prefill the row cache) as donated buffers: what they
    were handed is deleted once they return, and the engine holds only
    the live arrays they returned."""
    e = Engine(CFG, PARAMS, block_size=8, max_slots=2,
               max_seq=32, pad_len=8, steps_per_sync=4)
    handed = {"prefill": [], "decode": []}
    _spy(e, "_prefill_paged", handed["prefill"])
    _spy(e, "_decode_n", handed["decode"])
    req = Request(rid=0, prompt=[3, 1, 4, 2], max_new=10)
    e.submit(req)
    e.step()                            # admit (prefill), then one burst
    assert len(handed["prefill"]) == 4      # pool k, v; row k, v
    assert len(handed["decode"]) == 2
    assert all(a.is_deleted() for a in handed["prefill"] + handed["decode"])
    live = [e.cache["k"], e.cache["v"], e._row["k"], e._row["v"]]
    assert not any(a.is_deleted() for a in live)
    while e.load:
        e.step()
    assert req.done and not e.cache["k"].is_deleted()


def test_donated_pools_keep_tokens_through_cow_migration_and_radix_hit(
        monkeypatch):
    """On donated pools, a radix-cache hit with its copy-on-write block
    copies and a live migration into a peer's pool give the tokens the
    reference gives; the copies and the migration's scatter donate what
    they were handed."""
    import repro.serve.engine as engine_mod

    kw = dict(max_slots=2, max_seq=64, pad_len=16, steps_per_sync=4)
    prompt = [7, 3, 9, 2, 5, 8, 6, 4, 1, 2, 3, 4]     # 1.5 blocks of 8
    mk = lambda base: [Request(rid=base + i, prompt=list(prompt),
                               max_new=6 + 8 * i) for i in range(2)]
    want = _reference(lambda: mk(0), **kw)
    e0 = Engine(CFG, PARAMS, block_size=8, prefix_cache=True,
                **kw)
    e1 = Engine(CFG, PARAMS, block_size=8, **kw)
    copied, scattered = [], []
    _spy(e0, "_copy_block", copied)
    scatter = engine_mod._scatter_kv

    def spy_scatter(ck, cv, *rest):
        scattered.extend([ck, cv])
        return scatter(ck, cv, *rest)

    monkeypatch.setattr(engine_mod, "_scatter_kv", spy_scatter)
    assert _run(e0, mk(0)) == want             # misses, fills the tree
    second = mk(100)
    for r in second:
        e0.submit(r)
    e0.step()                   # both hit the cached prefix, COW the tail
    assert e0.prefix_cache.hits >= 2 and copied
    slot = e0.migratable_slots()[0]
    assert e1.migrate_in(e0.migrate_out(slot)) == "live"
    assert scattered
    guard = 0
    while (e0.load or e1.load) and guard < 200:
        e0.step()
        e1.step()
        guard += 1
    assert [list(r.out) for r in second] == want
    assert all(a.is_deleted() for a in copied + scattered)
    for e in (e0, e1):
        assert not (e.cache["k"].is_deleted() or e.cache["v"].is_deleted())
