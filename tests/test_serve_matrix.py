"""Cross-implementation identity matrix: under greedy sampling every
serving engine variant — paged, paged + prefix cache (with and without
chunked prefill), a live migration mid-decode — must emit exactly the
tokens of the plain greedy reference (``tests/_serve_reference.py``: one
request at a time through ``models.forward`` / ``decode_step``, no
engine), across several registry architectures (dense, dense+qkv-bias,
MoE — not just the one cfg earlier PRs pinned), including a
forced-eviction run where a cached prefix is reclaimed under pool
pressure and transparently recomputed. The reference itself is checked
against a cache-free forward over each emitted prefix.

The MoE arch runs with a raised capacity_factor (dropless): with
capacity-bounded dispatch a token's output depends on which OTHER slots
share its decode step (drops are batch-global), so exact cross-engine
identity is only well-defined when nothing is dropped — the router,
sort-dispatch, and paged-attention stack are still fully exercised."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from _serve_reference import greedy_reference
from repro.configs import ARCHS
from repro.models import forward, init_lm
from repro.serve.engine import Engine, Request

# dense / dense+qkv_bias / MoE — three distinct attention+ffn stacks
MATRIX_ARCHS = ["tinyllama-1.1b", "qwen2-1.5b", "moonshot-v1-16b-a3b"]

_PARAMS = {}


def _setup(arch):
    if arch not in _PARAMS:
        cfg = ARCHS[arch].smoke()
        if cfg.family == "moe":
            cfg = dataclasses.replace(cfg, capacity_factor=8.0)
        _PARAMS[arch] = (cfg, init_lm(jax.random.key(0), cfg))
    return _PARAMS[arch]


SHARED = [7, 3, 9, 2, 5, 8, 6, 4, 1, 2, 3, 4]   # 12-token system prompt


def _reqs(n=4, max_new=8):
    return [
        Request(rid=i, prompt=SHARED + [10 + i, 11, 12 + i % 3],
                max_new=max_new + i % 3)
        for i in range(n)
    ]


def _run(engine, reqs):
    for r in reqs:
        engine.submit(r)
    guard = 0
    while engine.load > 0 and guard < 600:
        engine.step()
        guard += 1
    assert all(r.done for r in reqs)
    return [list(r.out) for r in reqs]


KW = dict(max_slots=2, max_seq=64, pad_len=16, steps_per_sync=4)
# float32 logits of the smoke models: a tie broken differently by two
# summation orders differs by a few ulps of a logit of order 1.
LOGIT_TOL = 1e-5


def _reference(cfg, params, reqs, kw=KW):
    return greedy_reference(cfg, params, reqs, pad_len=kw["pad_len"],
                            max_seq=kw["max_seq"])


@pytest.fixture(scope="module", params=MATRIX_ARCHS)
def baseline(request):
    cfg, params = _setup(request.param)
    return request.param, _reference(cfg, params, _reqs())


@pytest.mark.parametrize("arch", MATRIX_ARCHS)
def test_greedy_reference_agrees_with_cache_free_forward(arch):
    """Each token the reference emits is the best next token of a full
    forward with no cache over the (truncated) prompt and the tokens
    emitted before it: its logit is within LOGIT_TOL of that forward's
    best logit, the rule by which the chip benchmark judges a served
    token (``max_logit_gap``). This checks the reference's own prefill,
    cache writes and decode positions."""
    cfg, params = _setup(arch)
    reqs = _reqs()
    outs = _reference(cfg, params, reqs)
    fwd = jax.jit(lambda p, t: forward(p, cfg, tokens=t)[0])
    checked = 0
    for req, out in zip(reqs, outs):
        assert len(out) == req.max_new + 1
        seq = list(req.prompt[:KW["pad_len"]]) + out
        logits = fwd(params, jnp.asarray([seq], jnp.int32))[0]
        logits = logits[..., :cfg.vocab]
        for k, tok in enumerate(out):
            row = logits[len(seq) - len(out) - 1 + k]
            gap = float(row.max() - row[tok])
            assert 0.0 <= gap <= LOGIT_TOL, (req.rid, k, gap)
            checked += 1
    assert checked == sum(r.max_new + 1 for r in reqs)


def test_paged_matches_reference(baseline):
    arch, base = baseline
    cfg, params = _setup(arch)
    e = Engine(cfg, params, block_size=8, **KW)
    assert _run(e, _reqs()) == base
    assert e.pool.free_blocks == e.pool.num_blocks


def test_paged_prefix_cache_matches_reference(baseline):
    """Hit + miss paths: the first wave misses and seeds the radix tree,
    the second wave hits the shared prompt's cached blocks — and both
    waves' outputs are token-identical to the reference."""
    arch, base = baseline
    cfg, params = _setup(arch)
    e = Engine(cfg, params, block_size=8, prefix_cache=True,
               **KW)
    assert _run(e, _reqs()) == base
    assert e.prefix_cache.misses > 0
    hits0 = e.prefix_cache.hits
    second = _reqs()
    for r in second:
        r.rid += 100
    assert _run(e, second) == base
    assert e.prefix_cache.hits > hits0, "second wave must hit the cache"
    assert e.prefix_cache.tokens_reused >= 8
    # all seq refs dropped: everything left is reclaimable cache
    assert (e.pool.free_blocks + e.pool.cached_blocks
            == e.pool.num_blocks)


def test_paged_prefix_cache_chunked_matches_reference(baseline):
    arch, base = baseline
    cfg, params = _setup(arch)
    e = Engine(cfg, params, block_size=8, prefix_cache=True,
               prefill_chunk=4, token_budget=8, **KW)
    assert _run(e, _reqs()) == base
    assert e.sched.chunks_scheduled >= len(_reqs())


def test_forced_live_migration_matches_reference(baseline):
    """A sequence yanked mid-decode from one replica and re-materialized
    block-for-block on another (Engine.migrate_out -> migrate_in) must
    emit exactly the tokens an uninterrupted single-engine run does —
    across the same arch matrix as every other engine variant."""
    arch, base = baseline
    cfg, params = _setup(arch)
    e0 = Engine(cfg, params, block_size=8, **KW)
    e1 = Engine(cfg, params, block_size=8, **KW)
    reqs = _reqs()
    for r in reqs:
        e0.submit(r)
    e0.step()                          # admit 2, decode a burst: mid-decode
    cands = e0.migratable_slots()
    assert cands, "a running slot must be sheddable"
    mode = e1.migrate_in(e0.migrate_out(cands[0]))
    assert mode == "live", f"KV must move intact, got {mode!r}"
    guard = 0
    while (e0.load > 0 or e1.load > 0) and guard < 600:
        e0.step()
        e1.step()
        guard += 1
    assert all(r.done for r in reqs)
    assert [list(r.out) for r in reqs] == base
    assert e1.migrations_in == 1 and e0.migrations_out == 1


def test_partial_hit_that_cannot_fit_falls_back_to_miss():
    """Regression: a mid-block cache hit whose fork would pin the very
    blocks the availability check counted as reclaimable used to pass
    admission, fail in reserve, and retry the queue head forever (and
    inflate hit stats every retry). The scheduler must instead admit the
    request as a plain miss — evicting the cached prefix — and finish."""
    cfg, params = _setup("tinyllama-1.1b")
    kw = dict(max_slots=1, max_seq=32, pad_len=16, steps_per_sync=16)
    pa = SHARED                                  # 12 tokens
    pb = SHARED[:10] + [90, 91]                  # mid-block divergence
    mk = lambda: [Request(rid=0, prompt=list(pa), max_new=5),
                  Request(rid=1, prompt=list(pb), max_new=5)]
    base = _reference(cfg, params, mk(), kw)
    # 4 blocks total: A's release caches 2 blocks; B's hit-credited
    # admission needs 3 fresh blocks but pinning the 2 matched blocks
    # leaves only 2 available — the credited path cannot fit.
    e = Engine(cfg, params, block_size=8, num_blocks=4,
               prefix_cache=True, **kw)
    reqs = mk()
    out = _run(e, reqs)                          # must not livelock
    assert out == base
    assert e.prefix_cache.evictions > 0          # miss path evicted A
    assert e.prefix_cache.hits == 0
    assert e.prefix_cache.tokens_reused == 0     # stats stay honest
    assert e.pool.free_blocks + e.pool.cached_blocks == e.pool.num_blocks


def test_forced_eviction_recomputes_transparently(baseline):
    """A pool sized so that caching request A's blocks leaves too little
    for B's growth: B's admission/reservation must evict A's cached
    prefix (reclaimable accounting), and a later request with A's prompt
    misses and recomputes — token-identical throughout."""
    arch, base = baseline
    cfg, params = _setup(arch)
    kw = dict(KW, max_slots=1, max_seq=32)
    base3 = _reference(cfg, params, _reqs(3), kw)
    e = Engine(cfg, params, block_size=8, num_blocks=4,
               prefix_cache=True, **kw)
    assert _run(e, _reqs(3)) == base3
    assert e.prefix_cache.evictions > 0, \
        "pool sizing must force cache eviction"
