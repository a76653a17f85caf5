"""Host-sync-free engine steps: the jitted fori_loop multi-step decode must
emit exactly what the plain greedy reference emits, honor per-slot
budgets and cache-length caps, thread PRNG keys deterministically for
temperature sampling, and cut host drains by ~steps_per_sync. The engine
refuses the removed contiguous cache."""
import jax
import pytest

from _serve_reference import greedy_reference
from repro.configs import ARCHS
from repro.models import init_lm
from repro.serve.engine import Engine, Request

CFG = ARCHS["tinyllama-1.1b"].smoke()
PARAMS = init_lm(jax.random.key(0), CFG)


def _reqs(n=5, max_new=10):
    return [Request(rid=i, prompt=[3, i + 1, 4, 2], max_new=max_new)
            for i in range(n)]


def _run(engine, reqs, step):
    for r in reqs:
        engine.submit(r)
    guard = 0
    while engine.load > 0 and guard < 500:
        step()
        guard += 1
    assert all(r.done for r in reqs)
    return [list(r.out) for r in reqs]


def test_multi_step_matches_legacy_greedy():
    """The fori_loop decode at one token per sync and at eight must both
    produce token-identical outputs to the plain greedy reference (same
    prefill, same greedy argmax, same cache limits)."""
    ref = greedy_reference(CFG, PARAMS, _reqs(), pad_len=8, max_seq=64)
    for n in (1, 8):
        e = Engine(CFG, PARAMS, max_slots=2, max_seq=64, pad_len=8,
                   steps_per_sync=n)
        assert _run(e, _reqs(), e.step) == ref, n
    # budget contract: prefill token + exactly max_new decode tokens
    assert all(len(o) == 11 for o in ref)


def test_engine_rejects_contiguous():
    """The contiguous cache path is gone: asking for it fails loudly, and
    the default engine is the paged one."""
    with pytest.raises(ValueError, match="contiguous engine was removed"):
        Engine(CFG, PARAMS, max_slots=2, max_seq=64, pad_len=8,
               paged=False)
    e = Engine(CFG, PARAMS, max_slots=2, max_seq=64, pad_len=8)
    assert e.pool.num_blocks == 2 * e.max_blocks
    assert set(e.cache) == {"k", "v"}
    assert e.cache["k"].shape[1] == e.pool.num_blocks


def test_host_syncs_reduced_by_steps_per_sync():
    e1 = Engine(CFG, PARAMS, max_slots=2, max_seq=64, pad_len=8,
                steps_per_sync=1)
    _run(e1, _reqs(), e1.step)
    e8 = Engine(CFG, PARAMS, max_slots=2, max_seq=64, pad_len=8,
                steps_per_sync=8)
    _run(e8, _reqs(), e8.step)
    assert e8.tokens_out == e1.tokens_out
    assert e8.host_syncs < e1.host_syncs / 3


def test_cache_length_cap_frees_slot():
    """A request whose budget exceeds the cache stops at max_seq - 1."""
    e = Engine(CFG, PARAMS, max_slots=1, max_seq=16, pad_len=8,
               steps_per_sync=4)
    req = Request(rid=0, prompt=[1, 2, 3, 4, 5, 6, 7, 8], max_new=1000)
    out = _run(e, [req], e.step)[0]
    # prompt fills 8 cache rows; decode stops once lens hits 15:
    # 1 prefill token + 7 decode tokens
    assert len(out) == 8
    assert e.lens[0] == -1 and e.slots[0] is None


def test_temperature_sampling_deterministic_in_seed():
    outs = []
    for seed in (0, 0, 1):
        e = Engine(CFG, PARAMS, max_slots=2, max_seq=64, pad_len=8,
                   steps_per_sync=4, temperature=1.0, seed=seed)
        outs.append(_run(e, _reqs(2, 12), e.step))
    assert outs[0] == outs[1], "same seed must replay the same tokens"
    assert outs[0] != outs[2], "different seed must explore differently"


def test_prefill_row_cache_isolated_between_requests():
    """The preallocated row cache is reused across admissions; a second
    request must decode exactly as if it had a fresh cache (greedy run
    twice in different admission orders must agree per-rid)."""
    a = _reqs(4, 8)
    e1 = Engine(CFG, PARAMS, max_slots=1, max_seq=64, pad_len=8,
                steps_per_sync=4)
    out_serial = _run(e1, a, e1.step)  # one slot: strictly sequential reuse
    b = _reqs(4, 8)
    e2 = Engine(CFG, PARAMS, max_slots=4, max_seq=64, pad_len=8,
                steps_per_sync=4)
    out_batch = _run(e2, b, e2.step)   # all four admitted on a zeroed pool
    assert out_serial == out_batch
