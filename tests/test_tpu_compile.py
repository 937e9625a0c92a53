"""Compile the filter ops for a TPU v5e without a chip attached.

The TPU compiler is installed on CPU hosts and compiles for a described,
unattached chip.  These tests hold ``kernels.ops.LOWERING`` to what Mosaic
does: a "mosaic" op's ``pallas_call`` lowers (the compiled text holds a
``tpu_custom_call``), an "xla" op's ``pallas_call`` is refused while its
emulation compiles.  They also check that the one-chip smoke's programs at
2^26 buckets, its largest size, fit one v5e's 16 GiB.  Shapes only: nothing runs.

The topology is described inside a fixture, never at import, so test
collection never loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.kernels.delete import delete_bulk, delete_bulk_adaptive
from repro.kernels.fingerprint import fingerprint_hash, fingerprint_hash_family
from repro.kernels.insert import insert_bulk, insert_bulk_adaptive
from repro.kernels.probe import probe, probe_adaptive, probe_multi

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding, donate=()):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


# Small shapes for the lowering contract: 4096 buckets, 2048 keys in two
# 1024-key blocks (a 1-D block matches the u32 layout tile from 1024 up),
# K=2 generations, a 128-slot stash.
NB, N, BLOCK, K, S = 4096, 2048, 1024, 2, 128
U32, BOOL = jnp.uint32, jnp.bool_
TABLE, KEYS, STASH = ((NB, 4), U32), ((N,), U32), ((2, S), U32)
SELS = ((NB, 1), U32)


def _op_case(op):
    """(kernel call taking form kwargs, its argument shapes) for ``op``."""
    ev = dict(evict_rounds=32)
    cases = {
        "fingerprint": (lambda f: lambda h, l: fingerprint_hash(
            h, l, fp_bits=16, n_buckets=NB, block=BLOCK, **f),
            [KEYS, KEYS]),
        "fingerprint_family": (lambda f: lambda h, l: fingerprint_hash_family(
            h, l, fp_bits=16, n_buckets=NB, block=BLOCK, **f),
            [KEYS, KEYS]),
        "probe": (lambda f: lambda t, h, l: probe(
            t, h, l, fp_bits=16, block=BLOCK, **f), [TABLE, KEYS, KEYS]),
        "probe_multi": (lambda f: lambda t, h, l: probe_multi(
            t, h, l, fp_bits=16, block=BLOCK, **f),
            [((K, NB, 4), U32), KEYS, KEYS]),
        "insert": (lambda f: lambda t, h, l: insert_bulk(
            t, h, l, fp_bits=16, block=BLOCK, **ev, **f),
            [TABLE, KEYS, KEYS]),
        "insert_stash": (lambda f: lambda t, s, h, l: insert_bulk(
            t, h, l, fp_bits=16, stash=s, block=BLOCK, **ev, **f),
            [TABLE, STASH, KEYS, KEYS]),
        "delete": (lambda f: lambda t, h, l: delete_bulk(
            t, h, l, fp_bits=16, block=BLOCK, **f), [TABLE, KEYS, KEYS]),
        "probe_adaptive": (lambda f: lambda t, s, h, l: probe_adaptive(
            t, s, h, l, fp_bits=16, block=BLOCK, **f),
            [TABLE, SELS, KEYS, KEYS]),
        "insert_adaptive": (
            lambda f: lambda t, s, kh, kl, h, l: insert_bulk_adaptive(
                t, s, kh, kl, h, l, fp_bits=16, block=BLOCK, **ev, **f),
            [TABLE, SELS, TABLE, TABLE, KEYS, KEYS]),
        "delete_adaptive": (
            lambda f: lambda t, s, kh, kl, h, l: delete_bulk_adaptive(
                t, s, kh, kl, h, l, fp_bits=16, block=BLOCK, **f),
            [TABLE, SELS, TABLE, TABLE, KEYS, KEYS]),
    }
    return cases[op]


def test_every_op_has_a_case():
    for op in kops.LOWERING:
        _op_case(op)


@pytest.mark.parametrize("op", sorted(kops.LOWERING))
def test_lowering_table_matches_mosaic(op, one_chip):
    make, shapes = _op_case(op)
    mosaic = dict(interpret=False, emulate=False)
    if kops.LOWERING[op] == "mosaic":
        text = _compile(make(mosaic), shapes, one_chip).as_text()
        assert "tpu_custom_call" in text
        return
    assert kops.LOWERING[op] == "xla"
    with pytest.raises(Exception):
        _compile(make(mosaic), shapes, one_chip)
    emulated = _compile(make(dict(interpret=False, emulate=True)), shapes,
                        one_chip)
    assert "tpu_custom_call" not in emulated.as_text()


# The one-chip smoke's programs at its largest size (``--buckets-log2 26``):
# a 2^26-bucket table, 65,536-key load batches into a 1024-slot stash,
# 512-key served waves, 2^20-key check lookups.
BIG = ((1 << 26, 4), U32)
BIG_STASH = ((2, 1024), U32)


def _smoke_program(name):
    nb = 1 << 26
    if name == "insert_stash":
        return (lambda t, s, h, l: kops.filter_insert(
            t, h, l, fp_bits=16, n_buckets=nb, stash=s, evict_rounds=32,
            use_pallas="always", schedule=True),
            [BIG, BIG_STASH, ((65536,), U32), ((65536,), U32)], (0, 1))
    if name == "delete":
        return (lambda t, s, h, l, v: kops.filter_delete(
            t, h, l, fp_bits=16, n_buckets=nb, valid=v, stash=s,
            use_pallas="always"),
            [BIG, BIG_STASH, ((512,), U32), ((512,), U32), ((512,), BOOL)],
            ())
    return (lambda t, s, h, l: kops.probe_dispatch(
        t, h, l, fp_bits=16, n_buckets=nb, stash=s),
        [BIG, BIG_STASH, ((1 << 20,), U32), ((1 << 20,), U32)], ())


@pytest.mark.parametrize("name", ["insert_stash", "delete", "probe"])
def test_smoke_programs_fit_one_chip(name, one_chip):
    fn, shapes, donate = _smoke_program(name)
    m = _compile(fn, shapes, one_chip, donate).memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert peak < V5E_HBM_BYTES, (name, peak)
