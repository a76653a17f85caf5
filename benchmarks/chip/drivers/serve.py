"""Driver for serving configurations: the LM serving fabric under an
open-loop traffic mix.

What runs is the program's normal serving path: one paged ``Engine``
replica behind ``GLBReplicaBalancer``, fed through ``submit`` and
driven by ``run(max_steps=1)``, as a front end would drive it. The
configuration file holds the published model config's keys and, under
``engine``, the deployment's engine settings.

A run:

1. set-up: weights on the device from the seed (``weights.make_dense``),
   the engine, and a warm-up that compiles (or loads from the persistent
   cache) every program the window will run: the prefill at each prompt
   bucket the engine can use, the decode loop and the balancer's step;
2. the window: requests from ``traffic/openloop.py`` are submitted when
   they fall due, and the engine steps in between. The host clock stamps
   each request's due time, the step after which its first token is
   visible, and the step after which its last one is. A traced run
   profiles the window's last ``TRACE_SECONDS``;
3. the drain: every request that arrived in the window is followed to
   completion, up to ``drain_limit_s`` past the window;
4. the check: device memory is read and the engine freed; then a sample
   of finished requests, drawn from the seed and always holding the
   longest, is run through the plain reference (``reference.dense_lm``)
   and each served token's logit gap is compared with the limit the
   configuration states.
"""
from __future__ import annotations

import dataclasses
import shutil
import gc
import tempfile
import time
from typing import List, Optional

import numpy as np

from benchmarks.chip import devtrace, weights
from benchmarks.chip.harness import (ZERO, Context, Outcome, compile_note,
                                     device_record)
from benchmarks.chip.reference import dense_lm
from benchmarks.chip.traffic import openloop

PREFILL_BLOCK = 512      # the engine's prefill attention block (flash_attention)
TRACE_SECONDS = 8.0      # the traced part of a window: its end


def model_config(hf: dict):
    """The program's ModelConfig for a published dense config."""
    from repro.models.config import ModelConfig

    D, H = hf["hidden_size"], hf["num_attention_heads"]
    kw = dict(
        name=hf["name"], family="dense",
        n_layers=hf["num_hidden_layers"], d_model=D, n_heads=H,
        n_kv_heads=hf["num_key_value_heads"], d_ff=hf["intermediate_size"],
        vocab=hf["vocab_size"], head_dim=hf.get("head_dim") or D // H,
        qkv_bias=weights.has_qkv_bias(hf), rope_theta=float(hf["rope_theta"]),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        norm_eps=float(hf["rms_norm_eps"]), dtype=hf["torch_dtype"],
    )
    return ModelConfig(**kw)


@dataclasses.dataclass
class Tracked:
    """One request as the front end sees it (host clock, seconds)."""
    req: object
    plen: int
    due: float
    submitted: float = 0.0
    first: Optional[float] = None
    last: Optional[float] = None
    seen: int = 0              # tokens seen after the previous step
    in_window: int = 0         # tokens visible when the window closed


def prompt_bucket(mix: dict, max_seq: int) -> int:
    """The engine's prompt bucket: the mix's longest prompt rounded up to
    the prefill block. The engine cuts prompts to this length, so it
    must hold every prompt the traffic sends."""
    longest = mix["prompt_tokens"]["max"]
    return min(-(-longest // PREFILL_BLOCK) * PREFILL_BLOCK, max_seq)


def _step_work(live: List[Tracked], now: float, work: dict) -> None:
    """Stamp first and last tokens after a step, and add the work the
    step did (prompt tokens prefilled; decode tokens and the context each
    attended to) to ``work``."""
    for t in live:
        n = len(t.req.out)
        if n > t.seen:
            if t.seen == 0:
                t.first = now
                work["prefills"] += 1
                work["prefill_tokens"] += t.plen
                work["prefill_sq"] += t.plen * t.plen
            lo = max(t.seen, 1)
            k = n - lo                       # tokens decoded this step
            work["decode_tokens"] += k
            # Token i (i >= 1) is decoded against plen + i cached tokens.
            work["decode_context"] += k * t.plen + (lo + n - 1) * k // 2
            t.seen = n
        if t.req.done and t.last is None:
            t.last = now


def _new_work() -> dict:
    return dict(prefills=0, prefill_tokens=0, prefill_sq=0,
                decode_tokens=0, decode_context=0, steps=0)


def run(ctx: Context) -> Outcome:
    import jax

    from repro.core.autotune import paged_block_kv
    from repro.serve.engine import Engine, GLBReplicaBalancer, Request

    hf, mix = ctx.config, ctx.mix
    eng_kw = dict(hf["engine"])
    max_seq = eng_kw["max_seq"]
    pad_len = prompt_bucket(mix, max_seq)
    cfg = model_config(hf)
    t_in = time.perf_counter()
    w = weights.make_dense(hf, ctx.seed, hf["torch_dtype"])
    params = weights.program_params(w)
    jax.block_until_ready(params)
    t_w = time.perf_counter()
    bs = paged_block_kv(max_seq, cfg.hd)
    num_blocks = eng_kw.pop("kv_pool_tokens") // bs
    eng = Engine(cfg, params, paged=True, pad_len=pad_len, block_size=bs,
                 num_blocks=num_blocks, **eng_kw)
    bal = GLBReplicaBalancer([eng])
    plan = openloop.generate(mix, ctx.seed, ctx.seconds, hf["vocab_size"])
    t_eng = time.perf_counter()

    # Warm-up: one request per prompt bucket the engine can use (a fresh
    # prompt fills the first; a request resumed after preemption
    # recomputes prompt + output and may need the larger one), each
    # decoding a few steps.
    warm_rng = np.random.default_rng(ctx.seed + 1)
    buckets = sorted({pad_len, min(2 * pad_len, max_seq)})
    for i, b in enumerate(buckets):
        plen = min(b, max_seq - 2 * eng.steps_per_sync - 1)
        toks = warm_rng.integers(0, hf["vocab_size"], plen).tolist()
        if b > pad_len:
            # A prompt is cut to pad_len; a resumed request carries its
            # output too, so warm that bucket through the same program
            # with a request that has already produced tokens.
            req = Request(rid=-1 - i, prompt=toks[:pad_len],
                          max_new=2 * eng.steps_per_sync)
            req.out = toks[pad_len:]
        else:
            req = Request(rid=-1 - i, prompt=toks,
                          max_new=2 * eng.steps_per_sync)
        bal.submit(req)
        while bal.run(max_steps=1) != "terminated" or not req.done:
            pass
    jax.effects_barrier()
    eng.metrics.reset()
    warm = ctx.compiles.snapshot()

    live: List[Tracked] = []
    tracked = [Tracked(req=Request(rid=p.rid, prompt=p.prompt.tolist(),
                                   max_new=p.max_new),
                       plen=len(p.prompt), due=p.due_s) for p in plan]
    work = _new_work()
    traced_work = _new_work()
    trace_s = min(TRACE_SECONDS, ctx.seconds)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if ctx.trace else None
    tracing = None
    ann = (jax.profiler.TraceAnnotation if ctx.trace
           else lambda name: _Null())
    window_tokens = None
    nxt = 0
    w0 = time.perf_counter()
    setup_s = w0 - ctx.t_start
    w_end = w0 + ctx.seconds
    limit = w_end + float(mix["drain_limit_s"])
    while True:
        now = time.perf_counter()
        if window_tokens is None and now >= w_end:
            window_tokens = sum(t.in_window for t in tracked)
            if tracing is not None:
                tracing.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = None
            in_window = ctx.compiles.snapshot()
        if now >= w_end and (not live and nxt == len(tracked) or now > limit):
            break
        if (ctx.trace and tracing is None and window_tokens is None
                and now >= w_end - trace_s):
            devtrace.start(trace_dir)
            tracing = jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN)
            tracing.__enter__()
        while nxt < len(tracked) and w0 + tracked[nxt].due <= now:
            t = tracked[nxt]
            with ann("bench.submit"):
                bal.submit(t.req)
            t.submitted = time.perf_counter()
            live.append(t)
            nxt += 1
        if not live:
            until = w0 + tracked[nxt].due if nxt < len(tracked) else w_end
            with ann("bench.wait"):
                time.sleep(max(0.0, until - time.perf_counter()))
            continue
        with ann("bench.step"):
            bal.run(max_steps=1)
        now = time.perf_counter()
        step_work = _new_work()
        _step_work(live, now, step_work)
        step_work["steps"] = 1
        for k, v in step_work.items():
            work[k] += v
            if tracing is not None:
                traced_work[k] += v
        if now <= w_end:
            for t in live:
                t.in_window = t.seen
        live = [t for t in live if not t.req.done]
    t_stop = time.perf_counter()
    device = device_record(ctx.devices)
    program = eng.stats()
    quantiles = {}
    for name in ("queue_wait_ms", "ttft_ms", "tpot_ms", "prefill_chunk_ms"):
        h = eng.metrics.histogram(name)
        quantiles[name] = {"count": h.count, "p50": h.quantile(0.5),
                           "p95": h.quantile(0.95)}
    preemptions = int(eng.sched.preemptions)
    del bal, eng, params
    gc.collect()

    red = None
    if ctx.trace:
        try:
            red = devtrace.reduce_xplane(devtrace.latest_xplane(trace_dir))
        except FileNotFoundError:
            red = None
        shutil.rmtree(trace_dir, ignore_errors=True)
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]

    checks, notes = _check(ctx, w, hf, tracked, max_seq)
    failed = [t for t in tracked if not t.req.done]
    lateness = np.array([t.submitted - (w0 + t.due) for t in tracked
                         if t.submitted])
    ttft = np.array([t.first - (w0 + t.due) for t in tracked
                     if t.first is not None]) * 1e3
    notes.update({
        "setup_s": setup_s,
        "set-up s (start, weights, engine+traffic, warm-up)":
            [round(x, 3) for x in (t_in - ctx.t_start, t_w - t_in,
                                   t_eng - t_w, w0 - t_eng)],
        "compiles in set-up (obtained/compiled)":
            compile_note(ctx.compiles, ZERO, warm),
        "compiles in window (obtained/compiled)":
            compile_note(ctx.compiles, warm, in_window),
        "requests (attempted/finished/failed)":
            f"{len(tracked)}/{len(tracked) - len(failed)}/{len(failed)}",
        "ttft_ms p50/p85/p95": [float(np.percentile(ttft, q)) for q in
                                (50, 85, 95)] if len(ttft) else None,
        "generator lateness ms (median/max)":
            f"{np.median(lateness) * 1e3:.3f}/{lateness.max() * 1e3:.3f}"
            if len(lateness) else None,
        "engine steps in window+drain": work["steps"],
        "preemptions": preemptions,
        "drain_s": t_stop - w_end,
        "memory_peak_bytes": device["memory_peak_bytes"],
    })
    obs = {
        "device": device,
        "setup_s": setup_s,
        "window_s": ctx.seconds,
        "window_start": w0,
        "requests": [dict(due=w0 + t.due, first=t.first, last=t.last,
                          n_out=len(t.req.out), plen=t.plen,
                          done=t.req.done) for t in tracked],
        "window_tokens": window_tokens,
        "drain_end": t_stop,
        "program": program,
        "program_quantiles": quantiles,
        "trace": red,
        "traced_work": traced_work,
        "config": hf,
        "engine": {"steps_per_sync": hf["engine"]["steps_per_sync"],
                   "max_slots": hf["engine"]["max_slots"],
                   "pad_len": pad_len},
    }
    return Outcome(obs=obs, checks=checks, attempted=len(tracked),
                   failed=len(failed),
                   breakdown=devtrace.breakdown(red) if red else None,
                   notes=notes)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def sample(tracked: List[Tracked], seed: int, k: int) -> List[Tracked]:
    """k finished requests drawn from the seed, the longest among them."""
    fin = [t for t in tracked if t.req.done]
    if not fin:
        return []
    longest = max(fin, key=lambda t: t.plen + len(t.req.out))
    rest = [t for t in fin if t is not longest]
    rng = np.random.default_rng(seed + 2)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _check(ctx: Context, w: dict, hf: dict, tracked: List[Tracked],
           max_seq: int):
    """The widest logit gap of a served token over the sample, against
    the configuration's limit. With ``ctx.control`` (``control.py``) the
    reference computed in fp8 takes the program's place: the number
    compared is the gap of the token fp8 puts first at each position of
    the same prompts and served tokens, and the program's own gap is a
    note."""
    spec = hf["correct"]
    picked = sample(tracked, ctx.seed, int(ctx.mix["check_requests"]))
    rows_pad = ctx.mix["output_tokens"]["max"] + 1
    worst, control, n_tok = None, None, 0
    t0 = time.perf_counter()
    for t in picked:
        out = list(t.req.out)
        g = float(dense_lm.served_gaps(w, hf, t.req.prompt, out, max_seq,
                                       rows_pad).max())
        worst = g if worst is None else max(worst, g)
        n_tok += len(out)
        if ctx.control:
            c = float(dense_lm.served_gaps(w, hf, t.req.prompt, out, max_seq,
                                           rows_pad, control=True).max())
            control = c if control is None else max(control, c)
    notes = {"check: requests/served tokens compared":
             f"{len(picked)}/{n_tok}",
             "check: reference seconds": time.perf_counter() - t0}
    if ctx.control:
        notes["program max_logit_gap"] = worst
        worst = control
    return {"max_logit_gap": (worst, float(spec["max_logit_gap"]))}, notes
