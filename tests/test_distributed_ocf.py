"""Distributed OCF: shard_map all_to_all routing on an 8-device test mesh.

Runs in a subprocess so the 8 host devices don't leak into other tests."""
import json
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import distributed as dist, hashing
    from repro.core import filter as jf

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    n_shards, n_buckets = 8, 512
    rng = np.random.RandomState(1)
    keys = rng.randint(0, 2**63, size=4096, dtype=np.int64).astype(np.uint64)
    hi, lo = hashing.key_to_u32_pair_np(keys)
    owner = np.asarray(hashing.owner_shard_np(hi, lo, n_shards))
    tables = np.zeros((n_shards, n_buckets, 4), np.uint32)
    for s in range(n_shards):
        m = owner == s
        fs = jf.make_state(n_buckets, 4)
        fs, ok = jf.bulk_insert(fs, jnp.asarray(hi[m]), jnp.asarray(lo[m]),
                                fp_bits=16)
        assert bool(np.asarray(ok).all())
        tables[s] = np.asarray(fs.table)
    st = dist.ShardedFilterState(tables=jnp.asarray(tables))
    hits, overflow = dist.distributed_lookup(
        mesh, "data", st, jnp.asarray(hi), jnp.asarray(lo), fp_bits=16)
    absent = rng.randint(0, 2**63, size=4096, dtype=np.int64).astype(np.uint64)
    ahi, alo = hashing.key_to_u32_pair_np(absent)
    ahits, _ = dist.distributed_lookup(
        mesh, "data", st, jnp.asarray(ahi), jnp.asarray(alo), fp_bits=16)
    # tiny capacity -> overflow counters fire (burst signal), answers stay
    # conservative (True)
    thits, toverflow = dist.distributed_lookup(
        mesh, "data", st, jnp.asarray(hi), jnp.asarray(lo), fp_bits=16,
        capacity_factor=0.25)
    rep = dist.replicated_lookup(st.tables, jnp.asarray(hi), jnp.asarray(lo),
                                 fp_bits=16)
    print(json.dumps({
        "present_found": int(np.asarray(hits).sum()),
        "n": int(keys.size),
        "absent_hits": int(np.asarray(ahits).sum()),
        "overflow_total": int(np.asarray(overflow).sum()),
        "tight_found": int(np.asarray(thits).sum()),
        "tight_overflow": int(np.asarray(toverflow).sum()),
        "replicated_found": int(np.asarray(rep).sum()),
    }))
""")


def test_distributed_lookup_subprocess():
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["present_found"] == res["n"], "no false negatives"
    assert res["absent_hits"] < 20, "fp rate sane"
    assert res["overflow_total"] == 0
    assert res["tight_found"] == res["n"], "overflow answers conservative"
    assert res["tight_overflow"] > 0, "congestion signal fires under burst"
    assert res["replicated_found"] == res["n"]
