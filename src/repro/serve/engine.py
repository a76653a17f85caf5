"""Serving engine: continuous batching over decode slots and a paged KV
pool + GLB request balancing across replicas.

Each replica owns a fixed pool of decode slots (static shapes) and the
paged KV subsystem (DESIGN.md §7): attention caches are flat block pools
(``models.make_paged_cache``) mapped per sequence by ``serve.kvpool``,
and every engine step is planned by the continuous-batching scheduler
(``serve.scheduler`` — lookahead block reservation, watermark-based
preempt-and-requeue of the youngest sequence, per-step token budget,
strict-FIFO admission). An admitted request prefills into a reused
preallocated row cache (prompts padded to a bucket length — no
``make_cache`` allocation churn per admission) whose KV blocks are
scattered into the pool. All active slots then advance
``steps_per_sync`` tokens per engine step inside ONE jitted
``lax.fori_loop`` decode: sampling (greedy or temperature, device-side
PRNG key threading) happens on device, per-slot done masks gate cache
writes and length/budget accounting, decode attention walks the block
table through the paged decode kernel (``ops.attention(...,
block_tables=)``), and each step emits an (N, slots) token buffer the
host drains with a single device->host sync. Per-slot cache lengths (-1
marks an idle slot: its cache/state is untouched) flow through to the
kernel. A preempted request resumes by recomputing its cache from
prompt + generated-so-far, so greedy outputs are token-identical to an
uninterrupted run.

``prefix_cache=True`` adds the radix prefix cache (DESIGN.md §8): a
finished sequence's full KV blocks stay in a radix tree over its tokens,
a new admission forks the longest cached prefix (zero recompute, COW on
the partial tail) and prefills only its suffix, and cached blocks are
evicted LRU on pool pressure. ``prefill_chunk=N`` splits long prefills
into N-token chunks charged against the step token budget and
interleaved with decode (the ``paged_prefill`` kernel attends chunk
[s, e) to pool window [0, e)), so a long admission no longer stalls
co-scheduled decodes for one giant forward. Both features are
attention-family only (recurrent conv/ssm state cannot be forked).

The multi-replica balancer treats per-replica load as the GLB size
vector and steals work from overloaded to hungry replicas with the same
deterministic matching the task scheduler uses — the paper's library
applied to serving (DESIGN.md §4/§6/§9). Stealing is two-tier: queued
(unstarted) requests move first; with ``migrate=True`` a victim whose
queue is empty but whose slots are saturated sheds *live* sequences —
their written KV blocks travel as a dense buffer (``KVPool.extract`` /
``inject``) and decoding resumes on the thief greedy-token-identically
(falling back to radix-seeded or plain resume-by-recompute when the
thief's pool is tight). Hungry means "has a free slot and free KV
blocks", so replicas steal on memory headroom, not only when fully idle.
Termination and result collection are GLB-style: the load vector the
matching already gathers detects completion, and per-replica stats merge
into one fabric report.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (GLBParams, diffusion_pairs, fabric_summary,
                        lifeline_buddies, match_steals, merge_place_stats,
                        rewire_lifelines, terminated)
from repro.core.autotune import paged_block_kv
from repro.kernels.paged_decode import (paged_decode_grid,
                                        paged_decode_live_steps)
from repro.models import (decode_step, forward, make_cache,
                          make_paged_cache, sample_tokens)
from repro.models.config import ModelConfig
from repro.obs import (DEFAULT_BYTE_BUCKETS, NULL_TRACER, MetricsRegistry,
                       now_us)

from .kvpool import KVPool, PoolExhausted
from .radix import RadixPrefixCache
from .scheduler import ContinuousBatchingScheduler


@dataclasses.dataclass
class Request:
    """One serving request: prompt tokens in, up to ``max_new`` decoded
    tokens out, plus the lifecycle stamps the observability and cost
    layers read. The same object travels with the request through
    steals, migrations, and crash re-admission — whoever holds it owns
    the request."""

    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # Cost-model inputs (DESIGN.md §16): the tenant keys the per-tenant
    # decode-length histogram; predicted_decode is the at-submit length
    # prediction (-1 = never stamped), kept across re-submits like
    # t_submit so finish-time scoring judges the ORIGINAL prediction.
    tenant: str = ""
    predicted_decode: float = -1.0
    # Observability stamps (obs clock domain, µs): submission, the last
    # time the request entered a queue (submit / preempt / migrate
    # requeue), and the first output token (TTFT anchor).
    t_submit: float = 0.0
    t_queued: float = 0.0
    t_first: float = 0.0


@dataclasses.dataclass
class Migration:
    """A live sequence in flight between replicas (DESIGN.md §9). The
    victim's ``migrate_out`` owns the only copy of the request and its
    packed KV until the thief's ``migrate_in`` lands it — the victim has
    already freed its blocks and slot, so dropping a Migration loses the
    request. ``kv`` is the dense transfer buffer: k/v pool blocks
    covering exactly the WRITTEN tokens, in logical order (None for
    recurrent families, which resume by recompute)."""
    req: Request
    tokens: List[int]          # cache contents = prompt bucket + out[:-1]
    written: int               # == len(tokens), the cache fill level
    block_size: int
    kv: Optional[dict]         # {"k","v"}: np (layers, n, heads, bs, hd)


# Module-level jits (NOT per-engine closures): every engine with the same
# cache/buffer shapes shares one compiled gather/scatter, so a fabric of N
# replicas compiles the migration path once, not N times. Like every jit
# that writes the pools, the scatter donates them: it updates them in
# place, and the caller keeps only what it returns.
@jax.jit
def _gather_kv(cache_k, cache_v, ids):
    return cache_k[:, ids], cache_v[:, ids]


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_kv(cache_k, cache_v, ids, bk, bv):
    return (cache_k.at[:, ids].set(bk.astype(cache_k.dtype)),
            cache_v.at[:, ids].set(bv.astype(cache_v.dtype)))


def _scrub_row(row):
    # The reused row cache carries the previous request's state.
    # Attention k/v tails are harmless (masked by cache length), but
    # recurrent conv/ssm state feeds prefill directly and must be zero.
    return {
        name: (leaf if name in ("k", "v") else jnp.zeros_like(leaf))
        for name, leaf in row.items()
    }


def _make_decode_loop(cfg: ModelConfig, max_seq: int, steps_per_sync: int,
                      temperature: float):
    """The jitted fori_loop decode (``bt`` is the (slots, max_blocks)
    block table of the paged pool)."""
    vocab = cfg.vocab

    @functools.partial(jax.jit, donate_argnames=("cache",))
    def decode_tokens(params, tokens, cache, bt, lens, budget, key):
        """steps_per_sync decode steps entirely on device. Carries per-slot
        done masks (idle: lens < 0; finished: budget == 0) and fills an
        (N, slots) token buffer (-1 where a slot emitted nothing) that the
        host drains with one sync. The cache is donated: each step writes
        its tokens into it in place."""
        B = tokens.shape[0]
        buf = jnp.full((steps_per_sync, B), -1, jnp.int32)

        def body(t, carry):
            tokens, cache, lens, budget, key, buf = carry
            active = (lens >= 0) & (budget > 0)
            step_lens = jnp.where(active, lens, -1)
            logits, cache = decode_step(params, cfg, tokens, cache,
                                        step_lens, block_tables=bt)
            key, sub = jax.random.split(key)
            nxt = sample_tokens(logits[:, 0, ..., :vocab], sub, temperature)
            nxt = jnp.where(active, nxt, -1)
            buf = buf.at[t].set(nxt)
            lens = jnp.where(active, lens + 1, lens)
            budget = jnp.where(active, budget - 1, budget)
            budget = jnp.where(lens >= max_seq - 1, 0, budget)  # cache full
            tokens = jnp.where(active[:, None], nxt[:, None], tokens)
            return tokens, cache, lens, budget, key, buf

        carry = (tokens, cache, lens, budget, key, buf)
        tokens, cache, lens, budget, key, buf = jax.lax.fori_loop(
            0, steps_per_sync, body, carry
        )
        return buf, cache, key

    return decode_tokens


def _make_paged_fns(cfg: ModelConfig, max_seq: int, block_size: int,
                    temperature: float):
    vocab = cfg.vocab
    max_blocks = max_seq // block_size

    @functools.partial(jax.jit, donate_argnames=("cache", "row"))
    def prefill_paged(params, tokens, cache, bt_scatter, slot, row,
                      true_len, key):
        """Prefill into the reused row cache, then scatter the row's KV
        blocks into the pool through ``bt_scatter`` ((max_blocks,) i32,
        out-of-bounds sentinel past the prompt's blocks => dropped).
        Recurrent conv/ssm leaves stay slot-dense and write at ``slot``.
        Pool and row are donated and updated in place. Retraces once per
        prompt bucket length (tokens.shape[1])."""
        logits, row, _ = forward(
            params, cfg, tokens=tokens, cache=_scrub_row(row),
            cache_len=jnp.int32(0), mode="prefill",
        )

        def put(name, c, r):
            if name in ("k", "v"):
                # (layers, max_seq, heads, hd) row -> head-major blocks
                na, heads, hd = c.shape[0], c.shape[2], c.shape[-1]
                rb = r[:, 0].reshape(na, max_blocks, block_size, heads, hd)
                return c.at[:, bt_scatter].set(
                    jnp.swapaxes(rb, 2, 3).astype(c.dtype), mode="drop"
                )
            start = (0, slot) + (0,) * (c.ndim - 2)
            return jax.lax.dynamic_update_slice(c, r.astype(c.dtype), start)

        cache = {name: put(name, cache[name], row[name]) for name in cache}
        first = sample_tokens(
            logits[0, true_len - 1, ..., :vocab], key, temperature
        )
        return first, cache, row

    @functools.partial(jax.jit, donate_argnames=("cache",))
    def copy_block(cache, src, dst):
        """Apply one COW copy, in place in the donated pools: physical
        block dst := src in the k/v pools (recurrent slot state is never
        shared, nothing to copy)."""
        out = dict(cache)
        for name in ("k", "v"):
            if name in cache:
                out[name] = cache[name].at[:, dst].set(cache[name][:, src])
        return out

    return prefill_paged, copy_block


def _make_chunk_fn(cfg: ModelConfig, temperature: float):
    """Chunked-prefill forward: tokens [start, start+C) of one sequence,
    writing k/v straight into the pool blocks through the block table and
    attending to the paged window [0, start+C) (paged_prefill kernel /
    oracle). Chunk shapes are exact (no bucket padding), so this retraces
    once per distinct chunk length. Returns the sampled token from the
    chunk's last position — callers use it only on the final chunk. The
    pools are donated and written in place."""
    vocab = cfg.vocab

    @functools.partial(jax.jit, donate_argnames=("cache",))
    def prefill_chunk(params, tokens, cache, bt, start, key):
        logits, cache, _ = forward(
            params, cfg, tokens=tokens, cache=cache, cache_len=start,
            mode="prefill", block_tables=bt[None, :],
        )
        last = sample_tokens(logits[0, -1, ..., :vocab], key, temperature)
        return last, cache

    return prefill_chunk


class Engine:
    """One serving replica: continuous batching over a fixed pool of
    decode slots, jitted multi-token decode between host syncs, and the
    paged KV subsystem (block pool, scheduler, radix prefix cache,
    chunked prefill, live migration). See the module docstring for the
    architecture; a fabric of Engines is driven by
    :class:`GLBReplicaBalancer`. ``paged`` is accepted for callers that
    still pass ``paged=True``; ``paged=False`` is refused."""

    def __init__(self, cfg: ModelConfig, params, max_slots: int = 4,
                 max_seq: int = 256, pad_len: int = 32,
                 steps_per_sync: int = 8, temperature: float = 0.0,
                 seed: int = 0, paged: bool = True,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 watermark_blocks: int = 0,
                 token_budget: Optional[int] = None,
                 prefix_cache: bool = False,
                 prefill_chunk: Optional[int] = None,
                 shed_policy: str = "youngest",
                 tracer=None, metrics=None, slo=None,
                 slo_admission: bool = False, cost_model=None,
                 replica_id: int = 0):
        if not paged:
            raise ValueError(
                "the contiguous engine was removed: Engine always serves "
                "from the paged KV pool (drop paged=False)"
            )
        self.cfg = cfg
        self.params = params
        # Observability (DESIGN.md §10): tracer defaults to the no-op
        # NullTracer — every emit site guards on `.enabled`, so the
        # disabled hot path pays one attribute check. The metrics
        # registry is always real (per-request observations only, never
        # per token); `stats()` is a view over it. A fabric shares ONE
        # tracer (request spans cross replicas) but each replica keeps
        # its own registry, merged at result collection.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        # Optional SLOMonitor (obs.slo): fed the same per-request
        # latencies the histograms get; one monitor is shared fabric-wide
        # the way the tracer is.
        self.slo = slo
        # Optional CostModel (serve.cost, DESIGN.md §16): stamps a
        # decode-length prediction at submit and scores it at finish.
        # Shared fabric-wide like the tracer/SLO monitor; None costs one
        # attribute check per request boundary.
        self.cost_model = cost_model
        self.replica_id = replica_id
        if self.tracer.enabled:
            self.tracer.process_name(replica_id, f"replica {replica_id}")
            self.tracer.thread_name(replica_id, 0, "engine")
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.pad_len = pad_len
        self.steps_per_sync = steps_per_sync
        self.prefix_cache = None       # set below with prefix_cache=True
        self.queue: deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * max_slots
        self.lens = np.full(max_slots, -1, np.int32)    # -1 => idle slot
        self.budget = np.zeros(max_slots, np.int32)     # tokens still owed
        self._row = make_cache(cfg, 1, max_seq, dtype=jnp.float32)
        self.tokens = np.zeros((max_slots, 1), np.int32)
        self._key = jax.random.key(seed)
        self.steps = 0
        self.tokens_out = 0
        self.host_syncs = 0    # blocking device->host transfer points
        self.peak_running = 0  # max concurrent sequences observed
        self.peak_occupancy = 0.0   # max pool occupancy observed
        self.peak_fragmentation = 0.0
        self.migrations_out = 0     # live sequences shipped to a peer
        self.migrations_in = 0      # live sequences landed with their KV
        self.migrations_seeded = 0  # landed via a planted radix prefix
        self.migrations_recompute = 0   # landed WITHOUT KV (recompute)
        self._seed_sid = -1         # temp seq ids for radix seeding
        # Fresh first tokens synced this step: the step's end observes
        # how long each was held (first_token_hold_ms).
        self._first_tokens: List[Request] = []
        bs = block_size or paged_block_kv(max_seq, cfg.hd)
        assert max_seq % bs == 0, (max_seq, bs)
        self.block_size = bs
        self.max_blocks = max_seq // bs
        self.num_blocks = num_blocks or max_slots * self.max_blocks
        assert self.num_blocks >= self.max_blocks, \
            "pool must fit at least one full-length sequence"
        self.pool = KVPool(self.num_blocks, bs)
        if prefix_cache or prefill_chunk is not None:
            # Recurrent conv/ssm state is not block-addressable: a
            # cached prefix (or an earlier chunk) carries hidden
            # state the pool cannot fork, so prefix reuse and
            # chunked prefill are attention-family features.
            assert cfg.family not in ("ssm", "hybrid"), (
                "prefix cache / chunked prefill need stateless "
                f"attention KV, not family={cfg.family!r}"
            )
        if prefix_cache:
            self.prefix_cache = RadixPrefixCache(
                self.pool, tracer=self.tracer, pid=replica_id
            )
        self.sched = ContinuousBatchingScheduler(
            self.pool, max_slots, lookahead=steps_per_sync,
            max_seq=max_seq, watermark_blocks=watermark_blocks,
            token_budget=token_budget, prefill_chunk=prefill_chunk,
            cache=self.prefix_cache, shed_policy=shed_policy,
            tracer=self.tracer, metrics=self.metrics, slo=self.slo,
            slo_admission=slo_admission, cost_model=cost_model,
            pid=replica_id,
        )
        self.cache = make_paged_cache(
            cfg, self.num_blocks, bs, max_slots, dtype=jnp.float32
        )
        self._prefill_paged, self._copy_block = _make_paged_fns(
            cfg, max_seq, bs, temperature
        )
        # How paged_decode walks a slot's table: it copies pages in
        # the pool's dtype. None: no attention layer, no kernel.
        self._decode_grid = (
            (self.max_blocks, bs, cfg.hd, cfg.n_kv_heads,
             self.cache["k"].dtype)
            if "k" in self.cache else None
        )
        self._prefill_chunk_fn = (
            _make_chunk_fn(cfg, temperature)
            if self.sched.chunked_mode else None
        )
        self._decode_n = _make_decode_loop(
            cfg, max_seq, steps_per_sync, temperature
        )

    def submit(self, req: Request):
        # An empty prompt has no position to sample a first token from:
        # a single-shot prefill would crash and a chunked admission would
        # wedge its slot in a zero-token prefill — reject it loudly.
        if not req.prompt:
            raise ValueError(f"request {req.rid} has an empty prompt")
        # A stolen request is re-submitted on the thief: keep the
        # original submission stamp (TTFT measures from first submit) and
        # count it once, but restart its queue-wait clock.
        if not req.t_submit:
            req.t_submit = now_us()
            self.metrics.counter("requests_submitted").inc()
        req.t_queued = now_us()
        if self.tracer.enabled:
            self.tracer.req_begin(req.rid, pid=self.replica_id,
                                  args={"prompt_tokens": len(req.prompt),
                                        "max_new": req.max_new})
            self.tracer.req_phase(req.rid, "queued", pid=self.replica_id)
        if self.cost_model is not None:
            self.cost_model.stamp(req)
        self.queue.append(req)

    @property
    def load(self) -> int:
        return len(self.queue) + sum(s is not None for s in self.slots)

    # --------------------------------------------------------- cost model
    def request_cost(self, req: Request, queued: bool) -> float:
        """Predicted remaining block-seconds for one request ON THIS
        replica (requires a cost model). A queued request is priced at
        its full recompute prefix minus this replica's radix-cache hit
        length plus its predicted decode; a running one at its remaining
        decode only — so the same request is cheaper on a replica whose
        cache already holds its prefix, which is exactly the signal the
        diffusive balancer wants."""
        cm = self.cost_model
        bs = self.block_size
        if queued:
            ptoks = self._prefix_tokens(req)
            cached = (self.prefix_cache.hit_length(ptoks)
                      if self.prefix_cache is not None else 0)
            return cm.estimate(len(ptoks), cached, 0, req.tenant,
                               req.max_new, bs)
        return cm.estimate(min(len(req.prompt), self.pad_len), 0,
                           len(req.out), req.tenant, req.max_new, bs)

    @property
    def predicted_cost(self) -> float:
        """This replica's entry in the predictive load vector: summed
        predicted remaining block-seconds over its queue and running
        slots (0.0 without a cost model — the balancer falls back to
        integer counts)."""
        if self.cost_model is None:
            return 0.0
        cost = sum(self.request_cost(r, True) for r in self.queue)
        cost += sum(self.request_cost(r, False)
                    for r in self.slots if r is not None)
        return cost

    @property
    def free_slots(self) -> int:
        return sum(s is None for s in self.slots)

    @property
    def pool_occupancy(self) -> float:
        """Memory-pressure signal for the replica balancer: fraction of
        KV capacity in use (live pool blocks)."""
        return self.pool.occupancy

    def can_accept(self) -> bool:
        """Whether one more typical admission fits right now: a free slot
        and the scheduler's own admission predicate for a
        prompt-bucket-sized request (one policy, no drift)."""
        if self.free_slots == 0:
            return False
        return self.sched.can_admit(
            self.pad_len, all(s is None for s in self.slots)
        )

    def _finish_check(self, i: int, req: Request):
        if (len(req.out) > req.max_new
                or self.lens[i] >= self.max_seq - 1
                or self.budget[i] <= 0):
            req.done = True
            t_fin = now_us()
            self.metrics.counter("requests_finished").inc()
            if req.t_first:
                # Steady-state decode pace: TTFT is excluded, and the
                # first token itself emits no inter-token gap.
                tpot_ms = ((t_fin - req.t_first) / 1e3
                           / max(len(req.out) - 1, 1))
                self.metrics.histogram("tpot_ms").observe(tpot_ms)
                if self.slo is not None:
                    self.slo.observe("tpot_ms", tpot_ms)
            if self.cost_model is not None:
                # Close the prediction loop: score the stamped estimate
                # and feed the actual length back into the per-tenant
                # histogram. The cost_sample instant is what the
                # analyzer's prediction-error attribution parses.
                err = self.cost_model.observe_finish(req)
                if self.tracer.enabled and err is not None:
                    self.tracer.instant(
                        "cost_sample", pid=self.replica_id,
                        args={"rid": req.rid, "tenant": req.tenant,
                              "predicted": round(req.predicted_decode, 1),
                              "actual": len(req.out),
                              "err": round(err, 1)})
            if self.tracer.enabled:
                self.tracer.req_end(req.rid, pid=self.replica_id,
                                    args={"tokens": len(req.out)})
            if self.prefix_cache is not None:
                # Thread the written prefix into the radix cache BEFORE
                # freeing: the tree takes refs, free drops the seq's, and
                # the cached blocks survive at refcount 1 (reclaimable).
                toks = (list(req.prompt[: self.pad_len])
                        + list(req.out[:-1]))[: int(self.lens[i])]
                self.prefix_cache.insert(
                    toks, self.pool.block_table(req.rid), int(self.lens[i])
                )
            self.slots[i] = None
            self.lens[i] = -1
            self.budget[i] = 0
            self.sched.release(req.rid)
            self.sched.slot_released(i)

    def _drain(self, buf: np.ndarray):
        """Extend per-request outputs from the (N, slots) token buffer and
        mirror the device lens/budget recurrence on the host. Mid-prefill
        slots emitted nothing and still owe chunks — their finish checks
        (budget == 0 would misread as done) are skipped."""
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            if self.sched.mid_prefill(i):
                continue
            toks = buf[:, i]
            toks = toks[toks >= 0]
            req.out.extend(int(t) for t in toks)
            n = len(toks)
            if n:
                self.tokens[i, 0] = toks[-1]
            self.lens[i] += n
            self.budget[i] -= n
            self.tokens_out += n
            if n:
                self.pool.advance(req.rid, int(self.lens[i]))
            self._finish_check(i, req)

    # ------------------------------------------------------------ admission
    def _prefix_tokens(self, req: Request) -> List[int]:
        """Tokens an admission must have in cache before decoding: the
        (bucket-truncated) prompt, plus all-but-the-last generated token
        when resuming a preempted request (the last one is the next feed
        token). This is also the prefix-cache lookup key."""
        return list(req.prompt[: self.pad_len]) + list(req.out[:-1])

    def _arm_decode(self, slot: int, req: Request, first):
        """Make a slot decodable once its prefill has landed: a resumed
        request re-feeds its last generated token (its first ``first``
        was sampled before preemption); a fresh one syncs the prefill's
        sampled first token. The ONLY place the resume-budget and
        first-token bookkeeping live — the single-shot and chunked
        admission paths both call it, so they cannot drift."""
        if req.out:                     # resume after preemption
            self.tokens[slot, 0] = req.out[-1]
            self.budget[slot] = req.max_new - (len(req.out) - 1)
            if self.tracer.enabled:
                self.tracer.req_instant(req.rid, "resumed",
                                        pid=self.replica_id,
                                        args={"slot": slot,
                                              "out": len(req.out)})
                self.tracer.req_phase(req.rid, "decode",
                                      pid=self.replica_id)
        else:
            first = int(first)          # one sync per fresh admission
            self.host_syncs += 1
            req.out.append(first)
            self.tokens[slot, 0] = first
            self.budget[slot] = req.max_new
            self.tokens_out += 1
            req.t_first = now_us()
            self._first_tokens.append(req)
            if req.t_submit:
                ttft_ms = (req.t_first - req.t_submit) / 1e3
                self.metrics.histogram("ttft_ms").observe(ttft_ms)
                if self.slo is not None:
                    self.slo.observe("ttft_ms", ttft_ms)
            if self.tracer.enabled:
                self.tracer.req_phase(req.rid, "decode",
                                      pid=self.replica_id)

    def _admit_paged(self, slot: int, req: Request):
        """Prefill a scheduler-admitted request into ``slot``. Fresh
        requests sample their first token from the prefill logits; a
        preempted request resumes by recomputing its cache from
        prompt + generated-so-far (greedy-token-identical to never having
        been preempted) and re-feeds its last generated token."""
        prefix = self._prefix_tokens(req)
        true_len = len(prefix)
        bucket = min(-(-true_len // self.pad_len) * self.pad_len,
                     self.max_seq)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :true_len] = prefix
        # Scatter table: physical blocks for the prefix, OOB sentinel for
        # everything past it (lookahead blocks are written by decode).
        table = self.pool.block_table(req.rid)
        n_pb = -(-true_len // self.block_size)
        bt_scatter = np.full(self.max_blocks, self.num_blocks, np.int32)
        bt_scatter[:n_pb] = table[:n_pb]
        self._key, sub = jax.random.split(self._key)
        t0 = now_us()
        if self.tracer.enabled:
            self.tracer.thread_name(self.replica_id, 1 + slot,
                                    f"slot {slot}")
            self.tracer.begin("prefill", pid=self.replica_id,
                              tid=1 + slot,
                              args={"rid": req.rid, "tokens": true_len})
        first, self.cache, self._row = self._prefill_paged(
            self.params, jnp.asarray(toks), self.cache,
            jnp.asarray(bt_scatter), slot, self._row, true_len, sub,
        )
        self._arm_decode(slot, req, first)
        if self.tracer.enabled:
            self.tracer.end(pid=self.replica_id, tid=1 + slot)
        self.metrics.histogram("prefill_chunk_ms").observe(
            (now_us() - t0) / 1e3
        )
        self.lens[slot] = true_len

    def _run_prefill_chunk(self, slot: int, req: Request, start: int,
                           end: int, last: bool):
        """Prefill tokens [start, end) of the slot's prefix straight into
        the pool blocks (exact shapes, no bucket padding — one retrace
        per distinct chunk length). On the final chunk the sequence
        becomes decodable: a fresh request samples its first token from
        the chunk's last logits; a resumed one re-feeds its last
        generated token."""
        prefix = self._prefix_tokens(req)
        toks = np.asarray([prefix[start:end]], np.int32)
        table = self.pool.block_table(req.rid)
        bt = np.full(self.max_blocks, self.num_blocks, np.int32)
        bt[: len(table)] = table
        self._key, sub = jax.random.split(self._key)
        t0 = now_us()
        if self.tracer.enabled:
            self.tracer.thread_name(self.replica_id, 1 + slot,
                                    f"slot {slot}")
            self.tracer.begin("prefill_chunk", pid=self.replica_id,
                              tid=1 + slot,
                              args={"rid": req.rid, "start": start,
                                    "end": end, "last": last})
        first, self.cache = self._prefill_chunk_fn(
            self.params, jnp.asarray(toks), self.cache, jnp.asarray(bt),
            jnp.int32(start), sub,
        )
        if self.tracer.enabled:
            self.tracer.end(pid=self.replica_id, tid=1 + slot)
        self.metrics.histogram("prefill_chunk_ms").observe(
            (now_us() - t0) / 1e3
        )
        self.pool.advance(req.rid, end)
        self.lens[slot] = end
        if not last:
            self.budget[slot] = 0           # not decodable yet
            return
        self._arm_decode(slot, req, first)

    # ------------------------------------------------------- live migration
    def can_host(self, written: int) -> bool:
        """Whether a migrated sequence with ``written`` cache tokens can
        run here at all: it needs at least one free position below
        ``max_seq`` to decode into (regardless of landing mode — even
        the recompute resume prefills the full prefix). The balancer
        checks this before shedding so an incompatible thief is never
        handed a Migration it cannot land."""
        return written < self.max_seq

    def migratable_slots(self) -> List[int]:
        """Slots the balancer may shed, best victim first (the
        scheduler's shed policy), excluding mid-prefill slots."""
        return self.sched.shed_candidates(self.slots, self.budget)

    def migrate_out(self, slot: int) -> Migration:
        """Ship the live sequence in ``slot`` to a peer replica: pack its
        written KV blocks into a dense transfer buffer (one gather, one
        host sync), free its blocks and slot here, and hand ownership of
        the request to the returned Migration. Greedy token identity is
        preserved because the buffer holds exactly the cache prefix
        positions [0, written) — the thief re-feeds the last generated
        token at position ``written``, just like a preemption resume.
        Mid-prefill slots are rejected: their KV is half-written and
        their chunk plan cannot move."""
        req = self.slots[slot]
        assert req is not None, f"slot {slot} is idle"
        if self.sched.mid_prefill(slot):
            raise ValueError(
                f"slot {slot} is mid-prefill and cannot migrate"
            )
        tokens = self._prefix_tokens(req)
        written = int(self.lens[slot])
        assert len(tokens) == written, (len(tokens), written)
        t0 = now_us()
        if self.tracer.enabled:
            self.tracer.begin("migrate_out", pid=self.replica_id,
                              args={"rid": req.rid, "written": written})
        kv = None
        if self.cfg.family not in ("ssm", "hybrid"):
            blocks, _ = self.pool.extract(req.rid)
            ids = jnp.asarray(np.asarray(blocks, np.int32))
            bk, bv = _gather_kv(self.cache["k"], self.cache["v"], ids)
            kv = {"k": np.asarray(bk), "v": np.asarray(bv)}
            self.host_syncs += 1
        mig = Migration(req=req, tokens=tokens, written=written,
                        block_size=self.block_size, kv=kv)
        self.slots[slot] = None
        self.lens[slot] = -1
        self.budget[slot] = 0
        self.tokens[slot, 0] = 0
        self.sched.release(req.rid)
        self.sched.slot_released(slot)
        self.migrations_out += 1
        nbytes = (kv["k"].nbytes + kv["v"].nbytes) if kv else 0
        self.metrics.histogram("migrate_pack_ms").observe(
            (now_us() - t0) / 1e3
        )
        self.metrics.histogram(
            "migration_bytes", DEFAULT_BYTE_BUCKETS
        ).observe(nbytes)
        if self.tracer.enabled:
            self.tracer.end(pid=self.replica_id)
            self.tracer.req_instant(req.rid, "migrated_out",
                                    pid=self.replica_id,
                                    args={"written": written,
                                          "bytes": nbytes})
            # The request is in flight: the victim opens the migrate
            # phase, the thief's landing path closes it (span ownership
            # travels with the request, DESIGN.md §10).
            self.tracer.req_phase(req.rid, "migrate", pid=self.replica_id)
        return mig

    def _requeue_migrated(self, req: Request) -> None:
        # Front of the queue: the sequence was already running and must
        # not wait behind fresh arrivals (same rule as preemption).
        req.t_queued = now_us()
        if self.tracer.enabled:
            self.tracer.req_phase(req.rid, "queued", pid=self.replica_id)
        self.queue.appendleft(req)

    def migrate_in(self, mig: Migration) -> str:
        """Land a migrated sequence (span + landing metrics around
        :meth:`_migrate_in`, which picks the mode — see its docstring)."""
        t0 = now_us()
        if self.tracer.enabled:
            self.tracer.begin("migrate_in", pid=self.replica_id,
                              args={"rid": mig.req.rid,
                                    "written": mig.written})
        try:
            mode = self._migrate_in(mig)
        finally:
            if self.tracer.enabled:
                self.tracer.end(pid=self.replica_id)
        self.metrics.histogram("migrate_land_ms").observe(
            (now_us() - t0) / 1e3
        )
        if self.tracer.enabled:
            self.tracer.req_instant(mig.req.rid, "migrated_in",
                                    pid=self.replica_id,
                                    args={"mode": mode})
        return mode

    def _migrate_in(self, mig: Migration) -> str:
        """Land a migrated sequence. Three outcomes, best first:

        * ``"live"`` — a free slot and enough pool blocks: inject fresh
          blocks, scatter the transfer buffer into them, and adopt the
          sequence as a running slot (zero recompute);
        * ``"seeded"`` — the pool cannot fit the whole sequence, but a
          prefix cache exists: inject however many full blocks DO fit
          under a temporary seq id, seed them into the radix tree, and
          requeue — the resume-by-recompute admission then *hits* the
          planted prefix and recomputes only the suffix;
        * ``"recompute"`` — no KV came along (recurrent family), block
          sizes differ, or nothing fits: plain resume-by-recompute.

        Every path preserves greedy token identity — they differ only in
        how much prefill work the move costs."""
        req = mig.req
        if not self.can_host(mig.written):
            # Requeueing here would wedge/crash a later admission (the
            # prefix cannot fit this engine's max_seq); the caller still
            # owns the Migration and must pick a compatible host.
            raise ValueError(
                f"sequence with {mig.written} cache tokens cannot run "
                f"under max_seq={self.max_seq}; check can_host() first"
            )
        # A block-size mismatch (or no KV: recurrent family) makes the
        # raw buffer unusable; degrade to resume-by-recompute.
        if mig.kv is None or mig.block_size != self.block_size:
            self._requeue_migrated(req)
            self.migrations_recompute += 1
            return "recompute"
        slot = next((i for i in range(self.max_slots)
                     if self.slots[i] is None), None)
        if slot is not None and self.pool.can_alloc(mig.written):
            try:
                table = self.pool.inject(req.rid, mig.written)
            except PoolExhausted:   # eviction under-delivered (pinned)
                table = None
            if table is not None:
                self._scatter_migrated(table, mig.kv)
                self.sched.adopt(slot)
                self.slots[slot] = req
                self.lens[slot] = mig.written
                # req.out is non-empty (mid-decode), so this takes the
                # resume branch — one bookkeeping path with preemption.
                self._arm_decode(slot, req, None)
                self.peak_running = max(
                    self.peak_running,
                    sum(s is not None for s in self.slots),
                )
                self.migrations_in += 1
                return "live"
        if self.prefix_cache is not None:
            full = mig.written // self.block_size
            fit = min(full, self.pool.available_blocks)
            if fit > 0:
                sid = self._seed_sid
                self._seed_sid -= 1
                seeded = fit * self.block_size
                try:
                    table = self.pool.inject(sid, seeded)
                except PoolExhausted:   # reclaimables pinned mid-evict
                    table = None
                if table is not None:
                    self._scatter_migrated(
                        table,
                        {n: b[:, :fit] for n, b in mig.kv.items()},
                    )
                    self.prefix_cache.seed(mig.tokens[:seeded], table,
                                           seeded)
                    self.pool.free(sid)  # tree refs keep blocks cached
                    self._requeue_migrated(req)
                    self.migrations_seeded += 1
                    return "seeded"
        self._requeue_migrated(req)
        self.migrations_recompute += 1
        return "recompute"

    def _scatter_migrated(self, table: List[int], kv: dict) -> None:
        ids = jnp.asarray(np.asarray(table, np.int32))
        self.cache = dict(self.cache)
        self.cache["k"], self.cache["v"] = _scatter_kv(
            self.cache["k"], self.cache["v"], ids,
            jnp.asarray(kv["k"]), jnp.asarray(kv["v"]),
        )

    def _device_tables(self) -> jax.Array:
        bt = np.zeros((self.max_slots, self.max_blocks), np.int32)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            t = self.pool.block_table(req.rid)
            bt[i, : len(t)] = t
        return jnp.asarray(bt)

    def _decode_and_drain(self, lens: np.ndarray, budget: np.ndarray):
        """The step's decode burst: block tables and inputs uploaded,
        one ``_decode_n`` dispatch (the ``decode`` span), then the single
        drain (the ``drain`` span). A call made while requests wait in
        the queue counts toward ``decode_calls_backlogged``, and its
        active slots toward ``decode_slots_backlogged``: how full the
        batch is when work is waiting."""
        if self.queue:
            self.metrics.counter("decode_calls_backlogged").inc()
            self.metrics.counter("decode_slots_backlogged").inc(
                int((lens >= 0).sum()))
        traced = self.tracer.enabled
        if traced:
            self.tracer.begin("decode", pid=self.replica_id)
        bt = self._device_tables()
        buf, self.cache, self._key = self._decode_n(
            self.params, jnp.asarray(self.tokens), self.cache, bt,
            jnp.asarray(lens), jnp.asarray(budget), self._key,
        )
        if traced:
            self.tracer.end(pid=self.replica_id)
            self.tracer.begin("drain", pid=self.replica_id)
        buf = np.asarray(buf)               # the single drain
        self.host_syncs += 1
        if self._decode_grid:
            self._count_kv_steps(lens, buf >= 0)
        self._drain(buf)
        if traced:
            self.tracer.end(pid=self.replica_id)

    def _count_kv_steps(self, lens: np.ndarray, emitted: np.ndarray):
        """paged_decode grid steps of one dispatch, reckoned on the host
        from the slots' lengths before it and the (steps_per_sync, slots)
        mask of the tokens they emitted: a slot emits at each position it
        decodes, at windows lens+1, lens+2, ... ``paged_decode_kv_steps``
        counts every grid step of every position, ``..._live`` those that
        read pool blocks."""
        n = emitted.shape[0]
        windows = np.where(
            emitted, lens.astype(np.int64) + 1 + np.arange(n)[:, None], 0)
        _, steps = paged_decode_grid(*self._decode_grid)
        self.metrics.counter("paged_decode_kv_steps").inc(
            n * self.max_slots * steps)
        self.metrics.counter("paged_decode_kv_steps_live").inc(
            paged_decode_live_steps(windows, *self._decode_grid))

    def _step_paged(self):
        if self.tracer.enabled:
            self.tracer.begin("plan", pid=self.replica_id)
        plan = self.sched.plan_step(self.queue, self.slots, self.lens,
                                    self._prefix_tokens)
        if self.tracer.enabled:
            self.tracer.end(pid=self.replica_id)
        for slot, _req in plan.preempted:
            self.lens[slot] = -1
            self.budget[slot] = 0
            self.tokens[slot, 0] = 0
        for src, dst in plan.copies:
            self.cache = self._copy_block(
                self.cache, jnp.int32(src), jnp.int32(dst)
            )
        for slot, req in plan.admit:
            self._admit_paged(slot, req)
        for slot, req, start, end, last in plan.prefill:
            self._run_prefill_chunk(slot, req, start, end, last)
        running = sum(s is not None for s in self.slots)
        self.peak_running = max(self.peak_running, running)
        s = self.pool.stats()
        self.peak_occupancy = max(self.peak_occupancy, s.occupancy)
        self.peak_fragmentation = max(self.peak_fragmentation,
                                      s.fragmentation)
        if running == 0:
            return
        if plan.active.any():
            step_lens = np.where(plan.active, self.lens,
                                 -1).astype(np.int32)
            # A partial reservation (watermark-starved pool) caps this
            # step's writes at the granted capacity, and plan.quota at
            # the slot's slice of the shared token budget; the real
            # budget is decremented by the drain, so the remainder
            # carries to the next step.
            cap_left = np.maximum(plan.granted - self.lens, 0)
            step_budget = np.where(
                plan.active,
                np.minimum(np.minimum(self.budget, cap_left), plan.quota),
                self.budget,
            ).astype(np.int32)
            self._decode_and_drain(step_lens, step_budget)
        self.steps += 1

    # ------------------------------------------------------------------ step
    def step(self):
        """One engine iteration: admit, then `steps_per_sync` batched
        decode steps on device with ONE host drain at the end (idle slots
        carry lens=-1 and stay untouched). The continuous-batching
        scheduler plans admission and preemption.
        Traced runs wrap the iteration in an ``engine_step`` span and
        emit load/pool counter tracks; the untraced path dispatches
        straight to the implementation (one attribute check). The step's
        end is when the front end can see its tokens: each fresh first
        token's hold, from its sync to here, goes to the always-on
        ``first_token_hold_ms`` histogram."""
        if not self.tracer.enabled:
            self._step_paged()
        else:
            with self.tracer.span("engine_step", pid=self.replica_id,
                                  args={"step": self.steps}):
                self._step_paged()
                self.tracer.counter(
                    "load",
                    {"running": float(sum(s is not None
                                          for s in self.slots)),
                     "queued": float(len(self.queue))},
                    pid=self.replica_id,
                )
                ps = self.pool.stats()
                self.tracer.counter(
                    "pool",
                    {"occupancy_pct": round(100 * ps.occupancy, 2),
                     "available_blocks": float(self.pool.available_blocks),
                     "watermark": float(self.sched.watermark)},
                    pid=self.replica_id,
                )
        if self._first_tokens:
            t_end = now_us()
            hold = self.metrics.histogram("first_token_hold_ms")
            for req in self._first_tokens:
                hold.observe((t_end - req.t_first) / 1e3)
            self._first_tokens.clear()

    def stats(self) -> dict:
        """Per-replica counters for fabric-level result collection
        (``core.stats.merge_place_stats``). Numeric-only, flat — the
        union across heterogeneous replicas merges field-wise.

        A view over the metrics registry (DESIGN.md §10): engine /
        scheduler / prefix-cache attribute counters sync into gauges
        (idempotent ``set``, so repeated calls never double-count) and
        the returned dict is the registry snapshot — which also carries
        the live request histograms (``ttft_ms_*``, ``tpot_ms_*``,
        ``queue_wait_ms_*``, ...) and counters observed at request
        boundaries. One source of truth; no drift between ``stats()``,
        ``collect()``, and a Prometheus scrape."""
        m = self.metrics
        sync = dict(
            tokens_out=self.tokens_out,
            steps=self.steps,
            host_syncs=self.host_syncs,
            peak_running=self.peak_running,
            migrations_out=self.migrations_out,
            migrations_in=self.migrations_in,
            migrations_seeded=self.migrations_seeded,
            migrations_recompute=self.migrations_recompute,
            admissions=self.sched.admissions,
            preemptions=self.sched.preemptions,
            adoptions=self.sched.adoptions,
            chunks_scheduled=self.sched.chunks_scheduled,
            peak_occupancy_pct=round(100 * self.peak_occupancy, 1),
            paged_decode_pages_per_step=(
                paged_decode_grid(*self._decode_grid)[0]
                if self._decode_grid else 0),
        )
        if self.prefix_cache is not None:
            pc = self.prefix_cache
            sync.update(
                cache_hits=pc.hits,
                cache_misses=pc.misses,
                tokens_reused=pc.tokens_reused,
                cache_evictions=pc.evictions,
                seeded_tokens=pc.seeded_tokens,
                # The one canonical hit-rate field (previously computed
                # ad hoc with different names in benches and examples).
                prefix_hit_rate_pct=round(100 * pc.hit_rate, 1),
            )
        for name, v in sync.items():
            m.gauge(name).set(v)
        return m.snapshot()


class GLBReplicaBalancer:
    """GLB over replicas — the paper's two-tier lifeline protocol applied
    to serving (DESIGN.md §9): steal *unstarted* work first, then *work
    in progress*.

    Per balance pass the per-replica loads are the GLB size vector and
    hungry replicas are matched to victims by the same deterministic
    lifeline matching the task scheduler uses (``core.lifeline``). A
    matched thief steals in two tiers:

    * **tier 1 — queued requests**: drained from the victim's queue
      oldest-first (FIFO), preserving arrival order;
    * **tier 2 — live sequences** (``migrate=True``): when
      the victim's queue is empty but its slots are saturated, the
      victim's shed policy picks running sequences and their KV state
      migrates block-for-block (``Engine.migrate_out`` →
      ``Engine.migrate_in``) — the paper's "steal work in progress", so a
      replica wedged on long-running sequences can still shed load. The
      victim always keeps at least one running sequence (a bare handoff
      helps nobody).

    Hungry = "can admit more work right now": a free decode slot AND free
    KV blocks above the watermark, with an empty local queue — so a
    replica under memory pressure never steals, and a busy replica with
    spare capacity does.

    Termination is GLB-style: the load vector gathered for the matching
    doubles as the termination detector (``core.lifeline.terminated`` —
    all loads zero), so ``run`` has no second polling loop over the
    engines; ``collect`` merges per-replica stats into the fabric-level
    result (the paper's hidden termination + result collection, §2.4).

    Failure semantics (DESIGN.md §15): the same load-vector gather is the
    heartbeat. With a ``faults`` injector attached, a replica that misses
    ``heartbeat_misses`` consecutive gathers is declared dead — fenced
    forever (never stepped again, even if it later wakes), its lifelines
    re-wired over the survivors (``core.rewire_lifelines``), its pending
    rows/columns cleared — and its lost requests are re-admitted from the
    balancer's submission ledger: queued casualties re-enter a survivor's
    queue, running casualties land as recompute resumes (the PR 5
    migration mode with ``kv=None``). While a replica is unresponsive but
    not yet declared dead its last-known load stands in, so a wedged
    replica holding all remaining work can never trigger spurious
    termination."""

    def __init__(self, engines: List[Engine],
                 params: GLBParams = GLBParams(),
                 migrate: bool = False, tracer=None, slo=None,
                 faults=None, heartbeat_misses: Optional[int] = None,
                 cost_model=None, predictive: bool = False,
                 imbalance_threshold: float = 0.25):
        self.engines = engines
        self.params = params
        self.migrate = migrate
        self.faults = faults
        # Predictive, cost-modeled balancing (DESIGN.md §16): with a
        # cost model attached the load vector can become predicted
        # block-seconds and a diffusive pre-pass moves work while any
        # replica exceeds the mean by ``imbalance_threshold`` — BEFORE
        # starvation fires; the reactive lifeline path below stays as
        # the backstop. predictive=False is the reactive-parity
        # contract: every decision site runs the exact pre-cost code
        # path (the model then only stamps/scores predictions).
        if predictive and cost_model is None:
            raise ValueError("predictive balancing requires a cost_model")
        self.cost_model = cost_model
        self.predictive = predictive
        self.imbalance_threshold = imbalance_threshold
        self.diffusion_moves = 0       # moves made by the diffusive pass
        # Decision log: one tuple per steal/shed/diffusion decision, in
        # execution order — the reactive-parity regression and the bench
        # parity row compare these across balancer configurations.
        self.decisions: List[tuple] = []
        if cost_model is not None:
            for e in engines:
                if e.cost_model is None:
                    e.cost_model = cost_model
        self.heartbeat_misses = (heartbeat_misses if heartbeat_misses
                                 is not None else params.heartbeat_misses)
        # Fabric-level trace track: supersteps, the load vector, steal
        # and termination instants live on their own pid, one past the
        # highest replica id (replica tracks keep their own pids).
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._fabric_pid = 1 + max(
            (e.replica_id for e in engines), default=-1
        )
        if self.tracer.enabled:
            self.tracer.process_name(self._fabric_pid, "fabric balancer")
            self.tracer.thread_name(self._fabric_pid, 0, "balance")
        # SLO monitor (obs.slo): attach it to every engine that doesn't
        # have its own, bind the fabric tracer/pid for burn-rate
        # instants, and check() it once per balance pass.
        self.slo = slo
        if slo is not None:
            slo.bind(tracer=self.tracer, pid=self._fabric_pid)
            for e in engines:
                if e.slo is None:
                    e.slo = slo
                    e.sched.slo = slo
        P = len(engines)
        z = params.resolve_z(P)
        self._buddies = jnp.asarray(lifeline_buddies(P, z))
        self._pending = jnp.zeros((P, P), bool)
        self._step = 0
        self._rr = 0                   # submission counter: placement must
                                       # not depend on rid density
        self.queue_moves = 0           # tier-1: queued requests stolen
        self.migrations = 0            # tier-2: live sequences migrated
        self.sterile_steals = 0        # matched pairs where nothing moved
        self.migration_modes = {"live": 0, "seeded": 0, "recompute": 0}
        self.supersteps = 0
        self.terminated = False
        # --------------------------- failure detection / recovery state
        self.metrics = MetricsRegistry()
        self._alive = [True] * P
        self._misses = [0] * P          # consecutive missed heartbeats
        self._last_load = [0] * P       # load at last answered gather
        self._last_cost = [0.0] * P     # predicted cost, same stand-in rule
        self._ledger: dict = {}         # rid -> Request, every submission
        self.replicas_dead = 0
        self.readmitted_queued = 0
        self.readmitted_running = 0

    @property
    def moves(self) -> int:
        """Total requests moved between replicas (both tiers). Tier-1
        queue steals and tier-2 live migrations are counted separately
        (``queue_moves`` / ``migrations``) — this is their sum, never a
        double-count."""
        return self.queue_moves + self.migrations

    @property
    def alive(self) -> List[bool]:
        return list(self._alive)

    def submit(self, req: Request, rr: Optional[int] = None):
        """Round-robin placement by an internal submission counter —
        ``rid % P`` skews badly when rids are strided or clustered (e.g.
        all-even rids land every request on replica 0 of 2). ``rr``
        overrides the counter for adversarial test placement.

        Every submission is recorded in the recovery ledger: if the
        hosting replica later dies, the ledger (minus finished requests
        and requests observed live on survivors) is exactly the lost
        set. Placement only considers replicas still alive."""
        self._ledger[req.rid] = req
        alive = [i for i in range(len(self.engines)) if self._alive[i]]
        if not alive:
            raise RuntimeError("replica fabric has no surviving replica")
        if rr is None:
            i = alive[self._rr % len(alive)]
            self._rr += 1
        else:
            i = alive[rr % len(alive)]
        self.engines[i].submit(req)
        # Keep the stand-in load fresh: a submission is balancer-local
        # knowledge, not something a heartbeat needs to discover.
        self._last_load[i] = self.engines[i].load

    def _stealable(self, e: Engine, thieves: List[Engine]) -> int:
        """One replica's entry in the GLB size vector: its queue depth,
        or — migration tier — its shed-candidate count when the queue is
        empty but every slot is busy (minus the one sequence a victim
        always keeps).

        The migration-tier count only includes candidates at least one
        currently-hungry thief ``can_host`` — advertised load must be
        load that can actually move. The unfiltered count made a victim
        whose only hungry peer is incompatible (block-size/max_seq
        mismatch) advertise forever, producing a sterile steal match
        every superstep that starved other edges of the matching."""
        q = len(e.queue)
        if q:
            return q
        if self.migrate and e.free_slots == 0:
            cands = [s for s in e.migratable_slots()
                     if any(t.can_host(int(e.lens[s])) for t in thieves
                            if t is not e)]
            return max(len(cands) - 1, 0)
        return 0

    def _steal_live(self, thief: Engine, victim: Engine) -> None:
        """Tier 2: migrate running sequences victim -> thief. Takes up to
        half of what the victim can shed (the GLB steal-half rule), one
        per free thief slot; ``migrate_in`` decides per sequence whether
        it lands live, radix-seeded, or as a recompute resume."""
        cands = [s for s in victim.migratable_slots()
                 if thief.can_host(int(victim.lens[s]))]
        if self.predictive:
            # Cost-weighted shedding: move the sequences with the most
            # predicted work left (rid tie-break), not the shed policy's
            # cheapest-transfer order — maximizing offloaded block-
            # seconds per migration. Predictive mode only; the default
            # path keeps the policy order bit-for-bit.
            cands = sorted(
                cands,
                key=lambda s: (-victim.request_cost(victim.slots[s],
                                                    False),
                               victim.slots[s].rid))
        running = sum(s is not None for s in victim.slots)
        sheddable = max(len(cands) - 1, 0)      # victim keeps one running
        # GLB steal-half: ship half the victim's running set, bounded by
        # what it may shed and the slots the thief can absorb into.
        take = min(running // 2, sheddable, thief.free_slots)
        if take == 0:
            # A matched edge that moved nothing: the size vector promised
            # load this thief cannot absorb. _stealable()'s hungry-aware
            # filter makes this unreachable for single-thief fabrics;
            # counted so tests (and ops) can see residual mismatches.
            self.sterile_steals += 1
        for slot in cands[:take]:
            rid = victim.slots[slot].rid
            mode = thief.migrate_in(victim.migrate_out(slot))
            self.migrations += 1
            self.migration_modes[mode] += 1
            self.decisions.append(("live", victim.replica_id,
                                   thief.replica_id, rid, mode))
            if self.tracer.enabled:
                self.tracer.instant(
                    "steal_live", pid=self._fabric_pid,
                    args={"victim": victim.replica_id,
                          "thief": thief.replica_id, "mode": mode},
                )

    # ------------------------------------------- predictive diffusion
    def _fabric_costs(self) -> np.ndarray:
        """The predictive load vector: per-replica summed predicted
        block-seconds, gathered with the same stand-in rule as the
        integer loads (an unresponsive replica's last-known cost holds;
        a dead one reads 0)."""
        costs = np.zeros(len(self.engines))
        for i, e in enumerate(self.engines):
            if not self._alive[i]:
                continue
            if not self._responsive(i):
                costs[i] = self._last_cost[i]
                continue
            self._last_cost[i] = e.predicted_cost
            costs[i] = self._last_cost[i]
        return costs

    def _diffuse(self, active: List[bool]) -> None:
        """The diffusive pre-pass (DESIGN.md §16): pair replicas whose
        predicted cost exceeds the fabric mean by ``imbalance_threshold``
        with under-mean recipients (``core.diffusion_pairs``) and move
        work toward the mean — queued requests chosen greedily to
        minimize post-move cost imbalance, then at most one live
        sequence per pair as the tier-2 analogue. Runs BEFORE the
        reactive matching each pass, so starvation-driven stealing
        remains the backstop for whatever the predictions miss."""
        costs = self._fabric_costs()
        if self.tracer.enabled:
            self.tracer.counter(
                "fabric_cost",
                {f"replica{i}": round(float(c), 3)
                 for i, c in enumerate(costs)},
                pid=self._fabric_pid,
            )
        eligible = np.asarray(
            [active[i] and self.engines[i].can_accept()
             for i in range(len(self.engines))]
        )
        pairs = diffusion_pairs(costs, self.imbalance_threshold, eligible)
        mean = float(costs.mean())
        for d, r in pairs:
            if active[d]:
                self._diffuse_pair(d, r, costs, mean)

    def _diffuse_pair(self, d: int, r: int, costs: np.ndarray,
                      mean: float) -> None:
        """Move work donor ``d`` → recipient ``r`` until the donor drops
        back under the diffusion threshold: queued requests first (each
        pick minimizes ``|donor-mean| + |recipient-mean|`` after the
        move, rid tie-break, and a move must strictly improve it), then
        at most one live sequence when the donor's queue had nothing to
        give. Cost updates are local to the gathered vector — the next
        pass re-gathers from the engines."""
        donor, recip = self.engines[d], self.engines[r]
        hi = mean * (1.0 + self.imbalance_threshold)
        moved = 0
        while donor.queue and costs[d] > hi and recip.can_accept():
            cur = abs(costs[d] - mean) + abs(costs[r] - mean)
            best = best_c = None
            best_key = None
            for req in donor.queue:
                c = donor.request_cost(req, True)
                gain = cur - (abs(costs[d] - c - mean)
                              + abs(costs[r] + c - mean))
                key = (gain, -req.rid)
                if gain > 1e-9 and (best_key is None or key > best_key):
                    best, best_c, best_key = req, c, key
            if best is None:
                break
            donor.queue.remove(best)
            recip.submit(best)
            costs[d] -= best_c
            costs[r] += best_c
            self.queue_moves += 1
            self.diffusion_moves += 1
            moved += 1
            self.decisions.append(("diffuse", d, r, best.rid))
            if self.tracer.enabled:
                self.tracer.instant(
                    "diffuse_queued", pid=self._fabric_pid,
                    args={"donor": donor.replica_id,
                          "recipient": recip.replica_id,
                          "rid": best.rid,
                          "cost": round(best_c, 3)})
        if (moved == 0 and costs[d] > hi and self.migrate
                and recip.free_slots > 0 and donor.free_slots == 0
                and not donor.queue):
            cands = [s for s in donor.migratable_slots()
                     if recip.can_host(int(donor.lens[s]))]
            if len(cands) > 1:          # the donor keeps one running
                slot = min(cands,
                           key=lambda s: (-donor.request_cost(
                               donor.slots[s], False),
                               donor.slots[s].rid))
                rid = donor.slots[slot].rid
                c = donor.request_cost(donor.slots[slot], False)
                mode = recip.migrate_in(donor.migrate_out(slot))
                costs[d] -= c
                costs[r] += c
                self.migrations += 1
                self.migration_modes[mode] += 1
                self.diffusion_moves += 1
                self.decisions.append(("diffuse_live", d, r, rid, mode))
                if self.tracer.enabled:
                    self.tracer.instant(
                        "diffuse_live", pid=self._fabric_pid,
                        args={"donor": donor.replica_id,
                              "recipient": recip.replica_id,
                              "rid": rid, "mode": mode,
                              "cost": round(c, 3)})

    # ------------------------------------------------- failure detection
    def _responsive(self, i: int) -> bool:
        return self.faults is None or self.faults.responsive(i)

    def _observed_load(self, i: int) -> int:
        """The load-vector entry for replica i: its real load when it
        answers the gather, its last-known load while unresponsive (a
        wedged replica holding all remaining work must not read as 0 —
        that would fire spurious termination), and 0 once declared
        dead (its work has been re-admitted elsewhere)."""
        if not self._alive[i]:
            return 0
        if not self._responsive(i):
            return self._last_load[i]
        self._last_load[i] = self.engines[i].load
        return self._last_load[i]

    def _detect_failures(self) -> None:
        """Heartbeat bookkeeping riding the load gather: a replica that
        misses ``heartbeat_misses`` CONSECUTIVE gathers is declared
        dead. One answered gather resets the window, so a slow replica
        (responsive, little progress) is never declared dead and a hang
        shorter than the window is absorbed with no recovery."""
        if self.faults is None:
            return
        for i in range(len(self.engines)):
            if not self._alive[i]:
                continue
            if self.faults.responsive(i):
                self._misses[i] = 0
                continue
            self._misses[i] += 1
            if self._misses[i] >= self.heartbeat_misses:
                self._declare_dead(i)

    def _declare_dead(self, i: int) -> None:
        """Fence replica i forever and run loss recovery: re-wire the
        lifeline topology over the survivors, clear the dead replica's
        pending rows/columns, and re-admit its lost requests. The dead
        engine object is never touched again — a zombie that wakes up
        after declaration is ignored (it is not stepped, not gathered,
        and its requests already have a new single owner)."""
        self._alive[i] = False
        self._misses[i] = 0
        if not any(self._alive):
            raise RuntimeError("every replica has died")
        self.replicas_dead += 1
        self.metrics.counter("replicas_dead").inc()
        if self.tracer.enabled:
            self.tracer.instant(
                "replica_dead", pid=self._fabric_pid,
                args={"replica": self.engines[i].replica_id,
                      "superstep": self.supersteps,
                      "window": self.heartbeat_misses},
            )
        z = int(self._buddies.shape[1])
        self._buddies = jnp.asarray(
            rewire_lifelines(np.asarray(self._alive), z)
        )
        pend = np.asarray(self._pending).copy()
        pend[i, :] = False     # its remembered requests die with it
        pend[:, i] = False     # nobody waits on a dead buddy
        self._pending = jnp.asarray(pend)
        self._recover(i)

    def _recover(self, dead: int) -> None:
        """Re-admit every request lost with replica ``dead``. Lost = in
        the submission ledger, not finished, and not observed live on
        any survivor — computed WITHOUT reading the dead engine (its
        state is unreachable by assumption; steals and migrations mean
        its original placement says nothing about current ownership).

        Queued casualties re-enter a survivor's queue (plain submit);
        running casualties (``req.out`` non-empty) are reconstructed as
        recompute resumes via the migration landing path with
        ``kv=None`` — the prompt and the already-streamed tokens are all
        that is needed, so greedy outputs stay token-identical to a
        crash-free run."""
        if not any(self._alive):
            raise RuntimeError("replica fabric lost every replica")
        live_rids = set()
        for j, e in enumerate(self.engines):
            if not self._alive[j]:
                continue
            live_rids.update(r.rid for r in e.queue)
            live_rids.update(r.rid for r in e.slots if r is not None)
        lost = sorted(
            (r for rid, r in self._ledger.items()
             if not r.done and rid not in live_rids),
            key=lambda r: r.rid,
        )
        for req in lost:
            if req.out:
                self._readmit_running(req, dead)
            else:
                self._readmit_queued(req, dead)

    def _trace_readmit(self, req: Request, dead: int, mode: str,
                       to: int) -> None:
        if not self.tracer.enabled:
            return
        self.tracer.req_instant(
            req.rid, "readmitted", pid=self._fabric_pid,
            args={"from": self.engines[dead].replica_id, "mode": mode},
        )
        self.tracer.instant(
            "request_readmitted", pid=self._fabric_pid,
            args={"rid": req.rid, "mode": mode,
                  "from": self.engines[dead].replica_id,
                  "to": self.engines[to].replica_id},
        )

    def _readmit_queued(self, req: Request, dead: int) -> None:
        alive = [i for i in range(len(self.engines)) if self._alive[i]]
        to = alive[self._rr % len(alive)]
        self._trace_readmit(req, dead, "queued", to)
        self.submit(req)        # advances _rr, lands on `to`
        self.readmitted_queued += 1
        self.metrics.counter("requests_readmitted").inc()

    def _readmit_running(self, req: Request, dead: int) -> None:
        target = None
        for j, e in enumerate(self.engines):
            if not self._alive[j]:
                continue
            if e.can_host(len(e._prefix_tokens(req))):
                target = j
                break
        if target is None:
            # The non-survivable case (DESIGN.md §15): a running
            # sequence needs a survivor whose max_seq fits the
            # recompute prefix.
            raise RuntimeError(
                f"request {req.rid} ({len(req.out)} tokens in) lost with "
                f"replica {dead}: no surviving paged replica can host "
                f"its recompute resume"
            )
        eng = self.engines[target]
        tokens = eng._prefix_tokens(req)
        self._trace_readmit(req, dead, "recompute", target)
        mig = Migration(req=req, tokens=tokens, written=len(tokens),
                        block_size=0, kv=None)
        eng.migrate_in(mig)     # kv=None -> recompute requeue, front
        self._ledger[req.rid] = req
        self.readmitted_running += 1
        self.metrics.counter("requests_readmitted").inc()

    def balance(self) -> bool:
        """One balancing pass. Returns True when the fabric is done —
        the load vector gathered for the steal matching doubles as the
        GLB termination detector, so callers need no separate poll (and,
        with a fault injector attached, the same gather is the
        heartbeat: see ``_detect_failures``). Traced runs wrap the pass
        in a ``balance`` span on the fabric track."""
        if not self.tracer.enabled:
            return self._balance()
        with self.tracer.span("balance", pid=self._fabric_pid):
            return self._balance()

    def _balance(self) -> bool:
        if self.faults is not None:
            self.faults.begin_superstep(self.supersteps)
        self._detect_failures()
        loads = np.asarray(
            [self._observed_load(i) for i in range(len(self.engines))],
            np.int32,
        )
        if self.slo is not None:
            self.slo.check()
        if self.tracer.enabled:
            # The GLB size vector as a counter track — the measurement a
            # cost-modeled balancer will regress on.
            self.tracer.counter(
                "fabric_load",
                {f"replica{i}": int(v) for i, v in enumerate(loads)},
                pid=self._fabric_pid,
            )
        if terminated(loads):
            self.terminated = True
            if self.tracer.enabled:
                self.tracer.instant("terminated", pid=self._fabric_pid,
                                    args={"superstep": self.supersteps})
            return True
        # Dead and unresponsive replicas neither give nor take: their
        # sizes are 0 and they are never hungry, so the matching routes
        # around them; pending edges toward them were cleared at death.
        active = [self._alive[i] and self._responsive(i)
                  for i in range(len(self.engines))]
        if self.predictive:
            # Diffusive pre-pass on predicted cost — proactive moves
            # first, the reactive matching below mops up anything the
            # predictions missed (including replicas the diffusion left
            # starving). Strictly additive: with predictive off nothing
            # here runs and the pass below is byte-identical to the
            # pre-cost balancer.
            self._diffuse(active)
        thieves = [e for i, e in enumerate(self.engines)
                   if active[i] and e.can_accept() and len(e.queue) == 0]
        sizes = np.asarray(
            [self._stealable(e, thieves) if active[i] else 0
             for i, e in enumerate(self.engines)],
            np.int32,
        )
        hungry = np.asarray(
            [active[i] and e.can_accept() and len(e.queue) == 0
             for i, e in enumerate(self.engines)]
        )
        if self.tracer.enabled:
            self.tracer.begin("match_steals", pid=self._fabric_pid)
        m = match_steals(
            jnp.asarray(sizes), jnp.asarray(hungry), self._pending,
            jax.random.fold_in(jax.random.key(17), self._step),
            self._buddies, self.params,
        )
        self._pending = m.pending
        src = np.asarray(m.src)
        if self.tracer.enabled:
            self.tracer.end(pid=self._fabric_pid)
        for thief, victim in enumerate(src):
            if victim < 0:
                continue
            v = self.engines[int(victim)]
            if v.queue:
                # Tier 1: steal queued (unstarted) requests first.
                take = max(1, len(v.queue) // 2)
                took = min(take, len(v.queue))
                if self.predictive:
                    # Cost-weighted selection: ship the most expensive
                    # queued requests (rid tie-break) so each steal
                    # moves the most predicted work. Predictive-only
                    # branch; the default path below is untouched.
                    ranked = sorted(
                        v.queue,
                        key=lambda q: (-v.request_cost(q, True), q.rid))
                    for q in ranked[:took]:
                        v.queue.remove(q)
                        self.engines[thief].submit(q)
                        self.queue_moves += 1
                else:
                    for _ in range(took):
                        # Oldest-first: stolen requests keep their
                        # arrival order on the thief, not the victim's
                        # inverted tail.
                        self.engines[thief].submit(v.queue.popleft())
                        self.queue_moves += 1
                self.decisions.append(("q", v.replica_id,
                                       self.engines[thief].replica_id,
                                       took))
                if self.tracer.enabled:
                    self.tracer.instant(
                        "steal_queued", pid=self._fabric_pid,
                        args={"victim": v.replica_id,
                              "thief": self.engines[thief].replica_id,
                              "n": took},
                    )
            elif self.migrate:
                self._steal_live(self.engines[thief], v)
        self._step += 1
        return False

    def run(self, max_steps: int = 10_000) -> str:
        """Drive the fabric to completion: balance, superstep every
        engine, repeat until the balance pass reports termination. Each
        iteration is a ``superstep`` span on the fabric track (a no-op
        context manager when tracing is off — per superstep, not per
        token).

        Returns ``"terminated"`` (GLB termination fired) or
        ``"wedged"`` (``max_steps`` exhausted with work outstanding —
        also emitted as a ``fabric_wedged`` trace instant, so a stuck
        fabric is distinguishable from a finished one without poking at
        internals). Dead replicas are fenced (never stepped); a faulted
        replica only steps when the injector says it makes progress."""
        while max_steps > 0:
            with self.tracer.span("superstep", pid=self._fabric_pid,
                                  args={"n": self.supersteps}):
                if self.balance():
                    break
                for i, e in enumerate(self.engines):
                    if not self._alive[i]:
                        continue
                    if self.faults is not None \
                            and not self.faults.should_step(i):
                        continue
                    e.step()
                self.supersteps += 1
            max_steps -= 1
        if self.terminated:
            return "terminated"
        if self.tracer.enabled:
            self.tracer.instant(
                "fabric_wedged", pid=self._fabric_pid,
                args={"supersteps": self.supersteps,
                      "loads": [int(self._observed_load(i))
                                for i in range(len(self.engines))]},
            )
        return "wedged"

    # ------------------------------------------------------ result collection
    def collect(self) -> dict:
        """Fabric-level result collection: merge per-replica stats into
        one report (total/mean/max per field) plus the balancer's own
        counters."""
        merged = merge_place_stats([e.stats() for e in self.engines])
        merged["_balancer"] = {
            "moves": self.moves,
            "queue_moves": self.queue_moves,
            "migrations": self.migrations,
            "diffusion_moves": self.diffusion_moves,
            "sterile_steals": self.sterile_steals,
            "supersteps": self.supersteps,
            "replicas_dead": self.replicas_dead,
            "readmitted_queued": self.readmitted_queued,
            "readmitted_running": self.readmitted_running,
            **{f"mig_{k}": v for k, v in self.migration_modes.items()},
        }
        if self.slo is not None:
            merged["_slo"] = self.slo.snapshot()
        if self.cost_model is not None:
            merged["_cost"] = self.cost_model.snapshot()
        return merged

    def merged_metrics(self) -> MetricsRegistry:
        """Fabric-level metrics registry: counters add, gauges keep the
        high-water mark, histograms merge bucket counts — so quantiles
        are of the MERGED latency distribution, not averages of
        per-replica quantiles. Feed to ``render_prometheus()`` for a
        fabric scrape."""
        for e in self.engines:
            e.stats()               # sync attr-backed gauges first
        return MetricsRegistry.merged(
            [e.metrics for e in self.engines] + [self.metrics]
        )

    def report(self) -> str:
        """Human-readable fabric summary (``core.stats.fabric_summary``
        over the merged registry view ``collect()`` produces) plus the
        balancer counters."""
        lines = [fabric_summary(self.collect(), title="replica fabric",
                                places=len(self.engines))]
        lines.append(
            f"  balancer: {self.moves} moves ({self.queue_moves} queued "
            f"+ {self.migrations} live migrations: "
            f"{self.migration_modes['live']} live / "
            f"{self.migration_modes['seeded']} seeded / "
            f"{self.migration_modes['recompute']} recompute), "
            f"{self.supersteps} supersteps, terminated={self.terminated}"
        )
        if self.predictive:
            cm = self.cost_model
            lines.append(
                f"  predictive: {self.diffusion_moves} diffusion moves "
                f"(threshold {self.imbalance_threshold:g}), "
                f"{len(cm.errors)} predictions scored, "
                f"mean |err| {cm.mean_abs_error():.1f} tokens"
            )
        if self.replicas_dead:
            lines.append(
                f"  failures: {self.replicas_dead} replica(s) dead, "
                f"{self.readmitted_queued + self.readmitted_running} "
                f"requests re-admitted ({self.readmitted_queued} queued "
                f"/ {self.readmitted_running} recompute)"
            )
        if self.slo is not None:
            lines += [f"  {ln}" for ln in self.slo.report_lines()]
        return "\n".join(lines)
