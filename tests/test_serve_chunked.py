"""Chunked-prefill edge cases: chunk boundaries vs block boundaries,
chunk budgets smaller than one block, decode-only steps between two
chunks of the same request, and preemption of a half-prefilled sequence
— all while greedy outputs stay token-identical to the plain greedy
reference (chunking is scheduling, never semantics)."""
import jax
import numpy as np

from _serve_reference import greedy_reference
from repro.configs import ARCHS
from repro.models import init_lm
from repro.serve.engine import Engine, Request

CFG = ARCHS["tinyllama-1.1b"].smoke()
PARAMS = init_lm(jax.random.key(0), CFG)

LONG = [7, 3, 9, 2, 5, 8, 6, 4, 1, 2, 3, 4, 9, 9, 8, 7, 2, 2, 3, 3]


def _reqs(n=3, max_new=8, plen=20):
    return [Request(rid=i, prompt=(LONG * 2)[:plen] + [30 + i],
                    max_new=max_new) for i in range(n)]


def _run(engine, reqs, per_step=None):
    for r in reqs:
        engine.submit(r)
    guard = 0
    while engine.load > 0 and guard < 600:
        engine.step()
        if per_step is not None:
            per_step(engine)
        guard += 1
    assert all(r.done for r in reqs)
    return [list(r.out) for r in reqs]


def _reference(reqs, kw):
    return greedy_reference(CFG, PARAMS, reqs, pad_len=kw["pad_len"],
                            max_seq=kw["max_seq"])


KW = dict(max_slots=2, max_seq=64, pad_len=32, steps_per_sync=8)
BASE = _reference(_reqs(), KW)


def test_chunk_on_block_boundary():
    """prefill_chunk == block_size: every chunk ends exactly where a
    block ends, so chunk scatters never straddle and the next chunk
    starts a fresh block."""
    e = Engine(CFG, PARAMS, block_size=8, prefill_chunk=8,
               **KW)
    assert _run(e, _reqs()) == BASE
    assert e.sched.chunks_scheduled >= 3 * (21 // 8)


def test_chunk_smaller_than_block():
    """prefill_chunk < block_size: several chunks land inside ONE pool
    block (the paged_prefill q_offset path mid-block), including a
    1-token chunk budget."""
    for chunk in (3, 1):
        e = Engine(CFG, PARAMS, block_size=8,
                   prefill_chunk=chunk, **KW)
        assert _run(e, _reqs(2)) == BASE[:2], f"chunk={chunk}"
        assert e.sched.chunks_scheduled >= 2 * (21 // max(chunk, 1))


def test_chunk_larger_than_block_unaligned():
    """Chunk spans multiple blocks and ends mid-block (21-token prefix,
    5-token chunks over 8-token blocks: boundaries at 5/10/15/20)."""
    e = Engine(CFG, PARAMS, block_size=8, prefill_chunk=5,
               **KW)
    assert _run(e, _reqs()) == BASE


def test_decode_only_step_between_chunks():
    """token_budget == lookahead: while an older sequence decodes it owns
    the whole step budget, so a younger mid-prefill sequence must sit out
    entire decode-only steps between its chunks — and still finish with
    identical tokens."""
    short = Request(rid=0, prompt=LONG[:4], max_new=4)
    long_ = Request(rid=1, prompt=LONG + [30], max_new=8)
    base = _reference([Request(rid=0, prompt=LONG[:4], max_new=4),
                       Request(rid=1, prompt=LONG + [30], max_new=8)], KW)
    e = Engine(CFG, PARAMS, block_size=8, prefill_chunk=6,
               token_budget=KW["steps_per_sync"], **KW)
    progress, decoded = [], []

    def snoop(engine):
        # (long_'s prefill progress if mid-prefill else None, tokens so far)
        st = [engine.sched._prefill.get(s) for s in range(KW["max_slots"])]
        st = [list(x) for x in st if x is not None]
        progress.append(st[0][0] if st else None)
        decoded.append(engine.tokens_out)

    out = _run(e, [short, long_], per_step=snoop)
    assert out == base
    assert e.sched.chunks_scheduled >= 2
    # find a step where the long request stayed mid-prefill at the SAME
    # offset while decode emitted tokens => a decode-only step between
    # two of its chunks
    stalled = any(
        p1 is not None and p1 == p0 and d1 > d0
        for p0, p1, d0, d1 in zip(progress, progress[1:],
                                  decoded, decoded[1:])
    )
    assert stalled, (progress, decoded)


def test_preempt_half_prefilled_sequence():
    """A pool too small for the older sequence's growth plus a younger
    admission's prefill: the younger one is preempted MID-PREFILL
    (watermark, youngest-first), requeued, and restarted from scratch —
    outputs stay identical to the reference."""
    kw = dict(max_slots=2, max_seq=32, pad_len=32, steps_per_sync=8)
    mk = lambda: [Request(rid=0, prompt=LONG[:4], max_new=20),
                  Request(rid=1, prompt=LONG + [30], max_new=6)]
    base = _reference(mk(), kw)
    # 6 blocks: both admissions fit (2 + 4 blocks with lookahead), then
    # the older sequence's decode growth hits an empty free list and must
    # preempt the youngest — which is still chunking its 21-token prefix.
    e = Engine(CFG, PARAMS, block_size=8, num_blocks=6,
               prefill_chunk=4, **kw)
    reqs = mk()
    trace = []          # (preemptions so far, victim's output length)

    def snoop(engine):
        trace.append((engine.sched.preemptions, len(reqs[1].out)))

    out = _run(e, reqs, per_step=snoop)
    assert out == base
    assert e.sched.preemptions > 0, "pool sizing must force preemption"
    # at the first preemption the young request had produced no token =>
    # it was preempted before its prefill completed (a finished prefill
    # samples the first token immediately)
    first = next(i for i, (p, _) in enumerate(trace) if p > 0)
    assert trace[first][1] == 0, trace
    assert len(reqs[1].out) > 0            # ...but it finished eventually
    # the preempted victim's prefill state is gone and the pool drained
    assert e.sched._prefill == {}
    assert e.pool.free_blocks == e.pool.num_blocks


def test_empty_prompt_rejected_loudly():
    """Regression: an empty prompt in chunked mode used to wedge its slot
    in a zero-token prefill forever (silent livelock); submit must reject
    it up front on every engine flavor."""
    import pytest

    for kw in (dict(), dict(block_size=8),
               dict(block_size=8, prefill_chunk=4)):
        e = Engine(CFG, PARAMS, max_slots=1, max_seq=32, pad_len=8,
                   steps_per_sync=4, **kw)
        with pytest.raises(ValueError, match="empty prompt"):
            e.submit(Request(rid=0, prompt=[], max_new=3))
        assert e.load == 0


def test_chunked_budget_bounds_prefill_work():
    """Acceptance: per-step prefill work is bounded by the token budget —
    no engine step prefills more than token_budget positions in total,
    however long the admission (prefill_chunk deliberately set far above
    the budget so the budget is the binding clamp)."""
    budget = 8
    e = Engine(CFG, PARAMS, block_size=8, prefill_chunk=64,
               token_budget=budget, **KW)
    per_step = {}
    orig = e._run_prefill_chunk

    def spy(slot, req, start, end, last):
        per_step[e.steps] = per_step.get(e.steps, 0) + (end - start)
        return orig(slot, req, start, end, last)

    e._run_prefill_chunk = spy
    out = _run(e, _reqs(2))
    assert out == BASE[:2]
    assert per_step, "chunks must have been scheduled"
    assert max(per_step.values()) <= budget
    # a 21-token prefix under an 8-token budget needs >= 3 chunks
    assert e.sched.chunks_scheduled >= 2 * 3
