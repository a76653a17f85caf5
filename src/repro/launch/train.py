"""End-to-end training driver.

Features exercised by tests/examples:
  - presets (tiny / 100m / full) scaled from any --arch config
  - deterministic, checkpointable data pipeline
  - periodic (optionally async) checkpoints; --resume restores params, opt
    state, data-iterator state and PRNG and replays bit-identically
  - --fail-at-step N simulates a node failure (the FT drill: launcher
    restarts with --resume and must reach the same final state)
  - GLB-MoE expert rebalancing every --rebalance-every steps (moe archs)
  - elastic: restore works under a different device mesh (shardings are
    applied at device_put time)

Example:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
      --preset tiny --steps 60 --ckpt-dir /tmp/ckpt --ckpt-every 20
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data.pipeline import DataState, SyntheticTokens
from repro.ft import checkpoint as ckpt
from repro.models.config import ModelConfig
from repro.train.optimizer import OptConfig
from repro.train.trainer import init_train_state, make_train_step


def preset_config(cfg: ModelConfig, preset: str) -> ModelConfig:
    if preset == "full":
        return cfg
    if preset == "tiny":
        return dataclasses.replace(
            cfg.smoke(), name=cfg.name + "-tiny", dtype="float32",
        )
    if preset == "100m":
        return dataclasses.replace(
            cfg,
            name=cfg.name + "-100m",
            n_layers=12,
            d_model=768,
            n_heads=12 if cfg.n_heads else 0,
            n_kv_heads=4 if cfg.n_kv_heads else 0,
            head_dim=64 if cfg.n_heads else 0,
            d_ff=2048 if cfg.d_ff else 0,
            vocab=32000,
            n_experts=min(cfg.n_experts, 8),
            top_k=min(cfg.top_k, 2),
            remat="none",
            dtype="float32",
        )
    raise ValueError(preset)


def train(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--preset", default="tiny",
                    choices=["tiny", "100m", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-async", action="store_true")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at-step", type=int, default=-1)
    ap.add_argument("--rebalance-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args(argv)

    cfg = preset_config(get_config(args.arch), args.preset)
    oc = OptConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5 + 1),
                   total_steps=args.steps)
    data = SyntheticTokens(cfg, args.batch, args.seq, seed=args.seed)

    start_step = 0
    if args.resume and args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        tree, extra, step = ckpt.restore(args.ckpt_dir)
        params, opt = tree["params"], tree["opt"]
        params = jax.tree.map(jnp.asarray, params)
        opt = jax.tree.map(jnp.asarray, opt)
        data.state = DataState.from_dict(extra["data"])
        start_step = step
        print(f"[train] resumed from step {step}")
    else:
        params, opt = init_train_state(jax.random.key(args.seed), cfg)

    step_fn = jax.jit(make_train_step(cfg, oc), donate_argnums=(0, 1))
    history = []
    expert_perm = (np.arange(cfg.n_experts) if cfg.family == "moe" else None)

    t0 = time.time()
    for step in range(start_step, args.steps):
        if step == args.fail_at_step:
            raise RuntimeError(
                f"[train] simulated node failure at step {step}"
            )
        batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
        params, opt, metrics = step_fn(params, opt, batch)
        if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
            loss = float(metrics["loss"])
            history.append({"step": step + 1, "loss": loss})
            print(f"[train] step {step+1:5d} loss {loss:.4f} "
                  f"({(time.time()-t0):.1f}s)", flush=True)
        if (cfg.family == "moe" and args.rebalance_every
                and (step + 1) % args.rebalance_every == 0):
            from repro.models.glb_moe import glb_expert_rebalance

            counts = np.asarray(metrics["expert_counts"])
            res = glb_expert_rebalance(counts, expert_perm, n_ranks=4)
            expert_perm = res.perm
            print(f"[train] GLB-MoE rebalance: load std "
                  f"{res.loads_before.std():.1f} -> {res.loads_after.std():.1f}"
                  f" ({len(res.swaps)} swaps)")
        if (args.ckpt_dir and args.ckpt_every
                and (step + 1) % args.ckpt_every == 0):
            ckpt.save(
                args.ckpt_dir, step + 1, {"params": params, "opt": opt},
                extra={"data": data.state.to_dict(),
                       "arch": cfg.name, "seed": args.seed},
                async_=args.ckpt_async,
            )
    if args.metrics_out:
        fingerprint = float(
            sum(float(jnp.sum(jnp.abs(x.astype(jnp.float32))))
                for x in jax.tree.leaves(params))
        )
        with open(args.metrics_out, "w") as f:
            json.dump({"history": history, "fingerprint": fingerprint}, f)
    return params, opt, history


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    train()
