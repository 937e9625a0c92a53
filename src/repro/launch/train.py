"""End-to-end training driver.

Wires every substrate together: config registry -> model -> sharded init ->
OCF-dedup data pipeline -> pjit train_step -> checkpoint/restart loop with
straggler watchdog.  Works identically on the CPU smoke mesh (tests,
examples/quickstart.py) and the production mesh (via dryrun for compile-only
validation).

  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --smoke \
      --steps 20 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

log = logging.getLogger("repro.train")


def build_state(arch: str, *, smoke: bool, mesh, parallel, seed: int = 0):
    from repro.configs.registry import get_config, get_smoke_config
    from repro.distributed.sharding import make_shardings
    from repro.launch.specs import abstract_init
    from repro.models.transformer import Transformer
    from repro.optim.adamw import AdamW, cosine_schedule

    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = Transformer(cfg)
    shapes, specs = abstract_init(model)
    shardings = make_shardings(mesh, specs, shapes, parallel)
    with mesh:
        params = jax.jit(
            lambda k: model.init(k)[0],
            out_shardings=shardings)(jax.random.PRNGKey(seed))
    tx = AdamW(lr=cosine_schedule(3e-4, 20, 10000))
    opt_state = jax.jit(tx.init)(params)
    return cfg, model, tx, params, opt_state, shardings, specs


def train(arch: str, *, steps: int, batch: int, seq: int, smoke: bool = True,
          ckpt_dir: str | None = None, ckpt_every: int = 10,
          resume: bool = True, data_seed: int = 0, mesh=None, parallel=None,
          inject_failure_at: int | None = None):
    from repro.checkpoint import ckpt as ckpt_mod
    from repro.data.pipeline import DedupPipeline, SyntheticDocs
    from repro.distributed.fault import StragglerWatchdog
    from repro.distributed.sharding import ParallelConfig
    from repro.launch.mesh import make_mesh
    from repro.train.step import make_train_step

    if mesh is None:
        mesh = make_mesh((1, 1), ("data", "model"))
    parallel = parallel or ParallelConfig()
    cfg, model, tx, params, opt_state, shardings, specs = build_state(
        arch, smoke=smoke, mesh=mesh, parallel=parallel)

    start_step = 0
    if ckpt_dir and resume:
        last = ckpt_mod.latest_step(ckpt_dir)
        if last is not None:
            params, _ = ckpt_mod.restore(ckpt_dir, last, params)
            opt_state, _ = ckpt_mod.restore(ckpt_dir + "/opt", last, opt_state)
            start_step = last
            log.info("resumed from step %d", last)

    pipe = DedupPipeline(
        SyntheticDocs(cfg.vocab_size, doc_len=seq + 1, seed=data_seed),
        batch=batch, seq=seq)
    data = iter(pipe)

    step_fn = jax.jit(make_train_step(model, tx, parallel))
    watchdog = StragglerWatchdog()
    history = []
    for step in range(start_step, steps):
        t0 = time.time()
        raw = next(data)
        batch_d = {k: jnp.asarray(v) for k, v in raw.items()}
        if cfg.prefix_embed_len:
            batch_d["prefix_embeds"] = jnp.zeros(
                (batch, cfg.prefix_embed_len, cfg.d_model), jnp.bfloat16)
        if cfg.cross_attn_memory_len:
            batch_d["memory"] = jnp.zeros(
                (batch, cfg.cross_attn_memory_len, cfg.cross_attn_memory_dim),
                jnp.bfloat16)
        if inject_failure_at is not None and step == inject_failure_at:
            raise RuntimeError(f"injected node failure at step {step}")
        params, opt_state, metrics = step_fn(params, opt_state, batch_d)
        dt = time.time() - t0
        watchdog.observe(dt)
        history.append({k: float(v) for k, v in metrics.items()})
        if ckpt_dir and (step + 1) % ckpt_every == 0:
            ckpt_mod.save(ckpt_dir, step + 1, params, ocf=pipe.ocf)
            ckpt_mod.save(ckpt_dir + "/opt", step + 1, opt_state)
    return {
        "params": params, "opt_state": opt_state, "history": history,
        "pipeline_stats": pipe.stats, "dedup_ocf_stats": pipe.ocf.stats,
        "straggler_flags": watchdog.flagged, "model": model, "cfg": cfg,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    out = train(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
                smoke=args.smoke, ckpt_dir=args.ckpt_dir)
    losses = [h["loss"] for h in out["history"]]
    print(f"loss: first={losses[0]:.4f} last={losses[-1]:.4f}")
    print(f"dedup: {out['pipeline_stats']}")


if __name__ == "__main__":
    main()
