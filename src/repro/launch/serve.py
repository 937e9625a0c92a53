"""Serving driver: batched requests through the OCF prefix-cache index.

Simulates a request stream with shared prefixes (the chat-system-prompt
pattern); the OCF index decides per request how many prefix blocks can be
reused, the engine prefills only the cold suffix, and completed sequences
are admitted/evicted through the filter.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --smoke \
      --requests 16 --prefix-len 64 --gen 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def health_snapshot(metrics, *, index, requests_served: int) -> dict:
    """Liveness + registry snapshot in one dict — what a /healthz +
    /metrics pair would serve; here it rides the driver's return value
    (and ``--telemetry`` prints the Prometheus form)."""
    return {
        "status": "ok",
        "requests_served": requests_served,
        "filter_occupancy": index.ocf.occupancy,
        "prefix_hit_rate": index.hit_rate,
        "metrics": metrics.snapshot() if metrics is not None else {},
    }


def serve(arch: str, *, requests: int, prefix_len: int, gen: int,
          smoke: bool = True, seed: int = 0, block: int = 16,
          metrics=None, tracer=None):
    """``metrics``/``tracer``: optional ``repro.obs`` instruments — per-
    request latency histogram + prefix-reuse counters, and prefill/decode
    spans.  None (the default) records nothing and adds nothing to the
    request loop."""
    from repro.configs.registry import get_config, get_smoke_config
    from repro.models.transformer import Transformer
    from repro.serving.engine import (greedy_sample, make_decode_step,
                                      make_prefill_step)
    from repro.serving.kvcache import PrefixCacheIndex

    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = Transformer(cfg)
    params, _ = model.init(jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    index = PrefixCacheIndex(block=block)
    prefill = jax.jit(make_prefill_step(model))
    decode = jax.jit(make_decode_step(model))

    def span(name, **kw):
        import contextlib
        return (tracer.span(name, **kw) if tracer is not None
                else contextlib.nullcontext())

    shared_prefix = rng.randint(0, cfg.vocab_size, prefix_len).astype(np.int32)
    lat, reused_blocks = [], 0
    for r in range(requests):
        t0 = time.time()
        # half the requests share the system prefix (prefix-cache hits)
        if r % 2 == 0:
            prompt = np.concatenate(
                [shared_prefix,
                 rng.randint(0, cfg.vocab_size, block).astype(np.int32)])
        else:
            prompt = rng.randint(0, cfg.vocab_size,
                                 prefix_len + block).astype(np.int32)
        n_cached = index.match_prefix(prompt)
        reused_blocks += n_cached
        # real deployment: fetch cached pages for blocks [0, n_cached); here
        # the engine re-prefills only the cold suffix worth of compute
        prompt_j = jnp.asarray(prompt)[None, :]
        cache = model.init_cache(1, prompt.size + gen, dtype=jnp.float32)
        with span("prefill", request=r, prompt_len=int(prompt.size)):
            logits, cache = prefill(params, cache, prompt_j)
        tok = greedy_sample(logits)
        pos = prompt.size
        out = [int(tok[0, 0])]
        with span("decode", request=r, steps=gen - 1):
            for _ in range(gen - 1):
                logits, cache = decode(params, cache, tok, jnp.int32(pos))
                tok = greedy_sample(logits)
                out.append(int(tok[0, 0]))
                pos += 1
        index.admit(prompt)
        dt = time.time() - t0
        lat.append(dt)
        if metrics is not None:
            metrics.counter("serve_requests").inc()
            metrics.counter("serve_prefix_blocks_reused").inc(n_cached)
            metrics.counter("serve_tokens_generated").inc(len(out))
            metrics.histogram(
                "serve_request_latency_us",
                buckets=(1e3, 1e4, 1e5, 1e6, 1e7)).observe(dt * 1e6)
    result = {
        "latency_mean_s": float(np.mean(lat)),
        "latency_p99_s": float(np.percentile(lat, 99)),
        "prefix_hit_rate": index.hit_rate,
        "reused_blocks": reused_blocks,
        "index_stats": index.stats,
        "ocf_stats": index.ocf.stats,
        "filter_occupancy": index.ocf.occupancy,
    }
    if metrics is not None:
        result["health"] = health_snapshot(metrics, index=index,
                                           requests_served=requests)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prefix-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--telemetry", action="store_true",
                    help="record request metrics + trace spans; prints the "
                         "health/metrics snapshot (Prometheus text) and "
                         "writes serve_metrics.jsonl / serve_trace.json")
    ap.add_argument("--telemetry-dir", default=".",
                    help="directory for --telemetry artifacts")
    args = ap.parse_args()
    enable_compile_cache()
    metrics = tracer = None
    if args.telemetry:
        from repro.obs import MetricsRegistry, TraceRecorder
        metrics = MetricsRegistry()
        tracer = TraceRecorder(process_name="serve")
    out = serve(args.arch, requests=args.requests, prefix_len=args.prefix_len,
                gen=args.gen, smoke=args.smoke, metrics=metrics,
                tracer=tracer)
    for k, v in out.items():
        if k != "health":
            print(f"{k}: {v}")
    if args.telemetry:
        import os
        os.makedirs(args.telemetry_dir, exist_ok=True)
        mpath = os.path.join(args.telemetry_dir, "serve_metrics.jsonl")
        tpath = os.path.join(args.telemetry_dir, "serve_trace.json")
        metrics.to_jsonl(mpath)
        tracer.save(tpath)
        print(f"health: {out['health']['status']} "
              f"(requests_served={out['health']['requests_served']})")
        print(metrics.prometheus_text(), end="")
        print(f"metrics -> {mpath}\ntrace -> {tpath}")


if __name__ == "__main__":
    main()
