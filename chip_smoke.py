"""Chip smoke run: the serving fabric and the GLB executor, end to end on
one TPU chip, through their normal entry points.

    python chip_smoke.py                # one chip: serving + executor
    python chip_smoke.py --four-chips   # four chips: UTS over 4 places only

This is a smoke run, not a benchmark. It proves that the main paths start
on the chip, that the Pallas kernels compile and run there, and that the
answers are right. The times it prints are single cold runs.

Phases, in order. There is no CPU fallback, and a failed phase exits
non-zero:

1. Device: the first JAX device must be a TPU.
2. Compile cache: ``repro.launch.compile_cache``.
3. Serving: TinyLlama-1.1B at its published widths (22 layers, d=2048,
   32/4 heads, vocab 32000), random weights from ``--seed``. Two
   ``Engine(paged=True)`` replicas sit behind ``GLBReplicaBalancer``, the
   way ``examples/serve_lm.py`` builds them. Eight requests of 192-256
   prompt tokens and 32-39 new tokens are all submitted to replica 0.
   The fabric must terminate with every request done.
4. Kernels: the compiled paged decode program holds ``tpu_custom_call``.
5. Correctness: the same weights with ``attn_impl = decode_impl = "ref"``
   on the same chip. Logits at three points must agree within
   ``LOGIT_TOL`` (relative L2 error): the prompt's last position from a
   contiguous prefill (flash_attention), the same from a paged chunked
   prefill (paged_prefill), and the first decode step on that pool
   (paged_decode). The greedy-token agreement of the served outputs with
   a reference fabric is printed.
6. Executor, on a 1-place mesh through ``core.executor.run_shardmap``:
   one BC instance (SSCA2 R-MAT, scale 10) must match
   ``brandes_bc_oracle``; UTS (b0=4, r=19, depth 13) must count
   ``UTS_NODES`` nodes.

With ``--four-chips`` only the executor's multi-place path runs: UTS on
a 4-place mesh of ``jax.devices()`` with ``routing="dense"`` and
``routing="lifeline"``; the count must equal ``UTS_NODES`` and every place
must receive stolen work (``items_recv > 0``).

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Geometric UTS tree b0=4, r=19, depth 13: the node count from the
# sequential pure-Python oracle, which takes about 25 minutes on one core:
#   PYTHONPATH=src python -c \
#     "from repro.problems.uts import uts_oracle; print(uts_oracle(4.0, 13, 19))"
UTS_DEPTH = 13
UTS_NODES = 125_350_485
BC_SCALE = 10
LOGIT_TOL = 2e-2   # ||kernel - ref|| / ||ref|| over the vocab, bf16 model

N_REQUESTS = 8
REPLICAS = 2


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    """A failed check ends the run with a non-zero exit."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


class CompileClock:
    """Sums JAX's compile-phase durations (trace, lower, backend compile)
    reported through ``jax.monitoring``; ``take()`` returns and resets."""

    def __init__(self):
        import jax

        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.secs += secs

    def take(self) -> float:
        s, self.secs = self.secs, 0.0
        return s


def make_requests(vocab: int, seed: int, n: int, prompt_lo: int,
                  prompt_hi: int, new_lo: int):
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(prompt_lo, prompt_hi + 1))
        prompt = rng.integers(1, vocab, plen).tolist()
        reqs.append(Request(rid=i, prompt=prompt, max_new=new_lo + i))
    return reqs


def serve(cfg, params, reqs, engine_kw, replicas: int):
    """Requests -> a fabric of paged replicas, all placed on replica 0;
    returns the engines after GLB termination."""
    from repro.serve.engine import Engine, GLBReplicaBalancer

    engines = [Engine(cfg, params, paged=True, replica_id=i, **engine_kw)
               for i in range(replicas)]
    bal = GLBReplicaBalancer(engines)
    for r in reqs:
        bal.submit(r, rr=0)
    status = bal.run(max_steps=2000)
    check(status == "terminated", f"fabric {status}, not terminated")
    check(all(r.done for r in reqs), "a request did not finish")
    return engines


def decode_program_text(eng) -> str:
    """Compiled HLO text of the engine's paged decode program at the
    shapes it served with."""
    import jax.numpy as jnp

    return eng._decode_n.lower(
        eng.params, jnp.asarray(eng.tokens), eng.cache,
        eng._device_tables(), jnp.asarray(eng.lens),
        jnp.asarray(eng.budget), eng._key,
    ).compile().as_text()


def probe_logits(cfg, params, prompt, pad_len: int, block_size: int,
                 max_seq: int):
    """Logits at the three kernel-bearing points of the serving path:
    the prompt's last position from a contiguous prefill over the prompt
    padded to ``pad_len`` (as the engine pads it), the same from a paged
    chunked prefill of the exact prompt, and the first decode step on
    that pool."""
    import jax
    import jax.numpy as jnp

    from repro.models import decode_step, forward, make_paged_cache

    @jax.jit
    def probe(params, toks, padded):
        n = toks.shape[1]
        dense, _, _ = forward(params, cfg, tokens=padded)
        nb = max_seq // block_size
        pool = make_paged_cache(cfg, nb, block_size, 1,
                                dtype=jnp.dtype(cfg.dtype))
        bt = jnp.arange(nb, dtype=jnp.int32)[None]
        paged, pool, _ = forward(params, cfg, tokens=toks, cache=pool,
                                 cache_len=jnp.int32(0), mode="prefill",
                                 block_tables=bt)
        nxt = jnp.argmax(paged[:, -1:, :cfg.vocab], axis=-1)
        step, _ = decode_step(params, cfg, nxt.astype(jnp.int32), pool,
                              jnp.full((1,), n, jnp.int32), block_tables=bt)
        return jnp.stack([dense[0, n - 1, :cfg.vocab],
                          paged[0, -1, :cfg.vocab],
                          step[0, 0, :cfg.vocab]]).astype(jnp.float32)

    padded = np.zeros((1, pad_len), np.int32)
    padded[0, :len(prompt)] = prompt
    return np.asarray(probe(params, jnp.asarray([prompt], jnp.int32),
                            jnp.asarray(padded)))


def serving_phase(cfg, params, *, seed: int, engine_kw: dict,
                  prompt_range, new_lo: int, expect_kernels: bool):
    """Phases 3-5: serve, check the kernels are in the decode program,
    compare against the ref implementation."""
    clock = CompileClock()
    reqs = make_requests(cfg.vocab, seed, N_REQUESTS, *prompt_range, new_lo)
    t0 = time.perf_counter()
    engines = serve(cfg, params, reqs, engine_kw, REPLICAS)
    t_serve = time.perf_counter() - t0
    c_serve = clock.take()
    tokens = sum(len(r.out) for r in reqs)
    log(f"serving: {len(reqs)} requests, {tokens} tokens over {REPLICAS} "
        f"replicas in {t_serve:.1f}s wall (compile {c_serve:.1f}s of it), "
        f"per-replica tokens {[e.tokens_out for e in engines]}")

    if expect_kernels:
        text = decode_program_text(engines[0])
        n_calls = text.count("tpu_custom_call")
        check(n_calls > 0, "paged decode program holds no Pallas kernel")
        log(f"kernels: paged decode program holds {n_calls} "
            f"tpu_custom_call ops")

    ref_cfg = dataclasses.replace(cfg, attn_impl="ref", decode_impl="ref")
    pad = engine_kw["pad_len"]
    probe_kw = dict(prompt=reqs[0].prompt[:pad], pad_len=pad,
                    block_size=engines[0].block_size,
                    max_seq=engine_kw["max_seq"])
    got = probe_logits(cfg, params, **probe_kw)
    want = probe_logits(ref_cfg, params, **probe_kw)
    errs = {}
    for name, g, w in zip(("prefill", "paged_prefill", "paged_decode"),
                          got, want):
        check(np.isfinite(g).all(), f"{name}: non-finite logits")
        errs[name] = float(np.linalg.norm(g - w) / np.linalg.norm(w))
        log(f"logits {name}: rel L2 err {errs[name]:.3e}, max |diff| "
            f"{np.abs(g - w).max():.3e} (|ref| max {np.abs(w).max():.3e}), "
            f"argmax {'equal' if g.argmax() == w.argmax() else 'differs'}")
    check(all(e <= LOGIT_TOL for e in errs.values()),
          f"kernel logits off the reference by more than {LOGIT_TOL}: {errs}")

    ref_reqs = make_requests(cfg.vocab, seed, N_REQUESTS, *prompt_range,
                             new_lo)
    serve(ref_cfg, params, ref_reqs, engine_kw, REPLICAS)
    same = sum(a == b for r, q in zip(reqs, ref_reqs)
               for a, b in zip(r.out, q.out))
    log(f"greedy tokens equal to the ref fabric: {same}/{tokens} "
        f"({same / tokens:.1%}); compile {clock.take():.1f}s for the "
        f"probes and the ref fabric")


def uts_run(mesh, depth: int, routing: str):
    from repro.core import GLBParams
    from repro.core.executor import run_shardmap
    from repro.problems.uts import MAX_CHILD, uts_problem

    # Child intervals never exceed MAX_CHILD, so a MAX_CHILD-wide
    # expansion block suffices; n sets the nodes per superstep.
    prob = uts_problem(4.0, depth, 19, capacity=1024, gen_width=MAX_CHILD)
    params = GLBParams(n=1024, steal_k=64)
    t0 = time.perf_counter()
    out = run_shardmap(prob, mesh, params, routing=routing)
    nodes = int(out.result)
    dt = time.perf_counter() - t0
    check(bool(out.converged), "UTS did not terminate")
    recv = np.asarray(out.stats["items_recv"]).tolist()
    log(f"UTS depth {depth} on {mesh.shape['place']} place(s), "
        f"routing={routing}: {nodes} nodes, {int(out.supersteps)} "
        f"supersteps, {dt:.1f}s wall incl. compile "
        f"({nodes / dt:.3e} nodes/s), items_recv per place {recv}")
    return nodes, recv


def executor_phase(mesh, uts_depth: int, uts_nodes: int, bc_scale: int):
    """Phase 6: UTS and BC through run_shardmap on a 1-place mesh."""
    from repro.core import GLBParams
    from repro.core.executor import run_shardmap
    from repro.problems.bc import bc_problem
    from repro.problems.rmat import brandes_bc_oracle, rmat_graph

    adj, n = rmat_graph(scale=bc_scale, seed=7)
    t0 = time.perf_counter()
    out = run_shardmap(bc_problem(adj, capacity=512), mesh,
                       GLBParams(n=16, steal_k=16))
    bc = np.asarray(out.result)
    dt = time.perf_counter() - t0
    oracle = brandes_bc_oracle(adj)
    err = float(np.abs(bc - oracle).max())
    log(f"BC R-MAT scale {bc_scale} (N={n}, {int(adj.sum())} edges): "
        f"{int(out.supersteps)} supersteps, {dt:.1f}s wall incl. compile, "
        f"max |bc - brandes| {err:.3e}")
    np.testing.assert_allclose(bc, oracle, rtol=1e-4, atol=1e-3)

    nodes, _ = uts_run(mesh, uts_depth, "dense")
    check(nodes == uts_nodes, f"UTS counted {nodes}, oracle {uts_nodes}")


def four_chip_phase(uts_depth: int, uts_nodes: int):
    import jax

    from repro.launch.mesh import make_host_mesh

    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices, need 4")
    mesh = make_host_mesh(4)
    for routing in ("dense", "lifeline"):
        nodes, recv = uts_run(mesh, uts_depth, routing)
        check(nodes == uts_nodes,
              f"{routing}: UTS counted {nodes}, oracle {uts_nodes}")
        check(all(r > 0 for r in recv),
              f"{routing}: a place received no stolen work: {recv}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for weights and prompts")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only UTS over a 4-place mesh of 4 chips")
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")

    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    t_all = time.perf_counter()
    if args.four_chips:
        four_chip_phase(UTS_DEPTH, UTS_NODES)
    else:
        from repro.configs import ARCHS
        from repro.launch.mesh import make_host_mesh
        from repro.models import init_lm

        cfg = ARCHS["tinyllama-1.1b"]
        params = jax.jit(init_lm, static_argnums=1)(
            jax.random.key(args.seed), cfg)
        engine_kw = dict(max_slots=4, max_seq=512, pad_len=256,
                         steps_per_sync=8)
        serving_phase(cfg, params, seed=args.seed, engine_kw=engine_kw,
                      prompt_range=(192, 256), new_lo=32,
                      expect_kernels=True)
        del params
        executor_phase(make_host_mesh(1), UTS_DEPTH, UTS_NODES, BC_SCALE)
    stats = dev.memory_stats() or {}
    log(f"smoke run done in {time.perf_counter() - t_all:.1f}s; device "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
