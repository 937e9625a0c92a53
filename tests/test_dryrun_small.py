"""Small-mesh dry-run integration: lower+compile the production code path on
8 host devices (2×2×2 pod/data/model), one arch per family, both step kinds.

The full 512-device sweep is artifacts/dryrun (deliverable e); this test
keeps the machinery honest in CI time.
"""
import json
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.registry import get_smoke_config
    from repro.distributed.sharding import (ParallelConfig, batch_pspec,
                                            cache_pspec, make_shardings)
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import abstract_cache, abstract_init
    from repro.models.transformer import Transformer
    from repro.optim.adamw import AdamW, AdamWState
    from repro.serving.engine import make_decode_step
    from repro.train.step import make_train_step
    from repro.roofline.analysis import parse_collectives

    arch = %r
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    parallel = ParallelConfig(pod_axis="pod", remat="dots",
                              compress_grads=True)
    cfg = get_smoke_config(arch)
    model = Transformer(cfg)
    shapes, specs = abstract_init(model)
    shard = make_shardings(mesh, specs, shapes, parallel)
    tx = AdamW(lr=1e-3)
    o_shapes = jax.eval_shape(tx.init, shapes)
    rep = NamedSharding(mesh, P())
    o_shard = AdamWState(step=rep, m=shard, v=shard)
    B, S = 8, 32
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "targets": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    bs = {k: NamedSharding(mesh, batch_pspec(B, 2, mesh, parallel))
          for k in batch}
    if cfg.prefix_embed_len:
        batch["prefix_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.prefix_embed_len, cfg.d_model), jnp.bfloat16)
        bs["prefix_embeds"] = NamedSharding(
            mesh, batch_pspec(B, 3, mesh, parallel))
    if cfg.cross_attn_memory_len:
        batch["memory"] = jax.ShapeDtypeStruct(
            (B, cfg.cross_attn_memory_len, cfg.cross_attn_memory_dim),
            jnp.bfloat16)
        bs["memory"] = NamedSharding(mesh, batch_pspec(B, 3, mesh, parallel))
    step = make_train_step(model, tx, parallel)
    with mesh:
        lowered = jax.jit(step, in_shardings=(shard, o_shard, bs)).lower(
            shapes, o_shapes, batch)
        compiled = lowered.compile()
    coll = parse_collectives(compiled.as_text())
    cost = compiled.cost_analysis() or {}

    # decode step
    cache_shapes = abstract_cache(model, B, 64, dtype=jnp.bfloat16)
    c_shard = jax.tree.map(
        lambda s: NamedSharding(mesh, cache_pspec(s.shape, mesh, parallel)),
        cache_shapes)
    dec = make_decode_step(model)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    dargs = [shapes, cache_shapes, tok, pos]
    dsh = [shard, c_shard, NamedSharding(mesh, batch_pspec(B, 2, mesh,
                                                            parallel)), rep]
    if cfg.cross_attn_memory_len:
        def dec2(p, c, t, q, mem):
            return dec(p, c, t, q, memory=mem)
        dargs.append(batch["memory"]); dsh.append(bs["memory"])
        dfn = dec2
    else:
        dfn = dec
    with mesh:
        dc = jax.jit(dfn, in_shardings=tuple(dsh)).lower(*dargs).compile()
    print(json.dumps({
        "train_collectives": coll.count,
        "train_flops": cost.get("flops", 0.0),
        "decode_ok": True,
    }))
""")

FAMILIES = ["mistral_nemo_12b", "qwen3_moe_235b_a22b", "mamba2_1p3b",
            "recurrentgemma_2b", "deepseek_v2_lite_16b", "musicgen_large",
            "llava_next_mistral_7b"]


@pytest.mark.parametrize("arch", FAMILIES)
def test_small_mesh_dryrun(arch):
    r = subprocess.run([sys.executable, "-c", SCRIPT % arch],
                       capture_output=True, text=True, timeout=900,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"})
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["decode_ok"]
    assert out["train_collectives"] > 0, "sharded training must communicate"
