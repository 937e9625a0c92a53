"""Seeded key streams: distinct 64-bit keys numbered by (class, index).

A key is a 4-round Feistel permutation of the 64-bit word
``(class << 32) | index`` under round keys drawn from ``--seed``.  A Feistel
network is a bijection whatever its round function, so distinct
(class, index) pairs give distinct keys: the member stream, the absent
probes and the fresh inserts of a run never share a key, and the plain
reference can hold keys by their (class, index) alone.

Every function has a numpy spelling (host: traffic, reference) and a jnp
spelling (device: set-up), bit for bit the same; both work in uint32 halves,
so neither needs 64-bit integers on the device.
"""
from __future__ import annotations

import numpy as np

MEMBER = 1    # set-up stream: offered to the placement, members where placed
ABSENT = 2    # never inserted: the false-positive probes
FRESH = 3     # inserted during the window
WARM = 4      # warm-up inserts (set-up)

_M32 = 0xFFFFFFFF
_C1, _C2 = 0x85EBCA77, 0xC2B2AE3D     # xxhash32 avalanche constants
_ROUNDS = 4


def _mix32_int(x: int) -> int:
    x &= _M32
    x ^= x >> 15
    x = (x * _C1) & _M32
    x ^= x >> 13
    x = (x * _C2) & _M32
    x ^= x >> 16
    return x


def round_keys(seed: int) -> np.ndarray:
    """The four Feistel round keys of ``seed`` (any int, 64 bits used)."""
    lo, hi = seed & _M32, (seed >> 32) & _M32
    return np.array([_mix32_int(lo ^ _mix32_int(hi + 0x9E3779B9 * (r + 1)))
                     for r in range(_ROUNDS)], np.uint32)


def _mix32_np(x):
    with np.errstate(over="ignore"):
        x = x ^ (x >> np.uint32(15))
        x = x * np.uint32(_C1)
        x = x ^ (x >> np.uint32(13))
        x = x * np.uint32(_C2)
        x = x ^ (x >> np.uint32(16))
    return x


def keys_hilo_np(seed: int, cls: int, idx) -> tuple[np.ndarray, np.ndarray]:
    """(hi, lo) uint32 halves of keys ``idx`` of class ``cls``."""
    rk = round_keys(seed)
    left = np.full(np.shape(idx), cls, np.uint32)
    right = np.asarray(idx, np.uint64).astype(np.uint32)
    for r in range(_ROUNDS):
        left, right = right, left ^ _mix32_np(right ^ rk[r])
    return left, right


def keys_np(seed: int, cls: int, idx) -> np.ndarray:
    """uint64 keys ``idx`` of class ``cls`` (what a client submits)."""
    hi, lo = keys_hilo_np(seed, cls, idx)
    return (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)


def keys_hilo_jnp(rk, cls: int, idx):
    """Device twin of ``keys_hilo_np``: ``rk`` from ``round_keys``, ``idx``
    a uint32 array."""
    import jax.numpy as jnp

    def mix(x):
        x = x ^ (x >> 15)
        x = x * jnp.uint32(_C1)
        x = x ^ (x >> 13)
        x = x * jnp.uint32(_C2)
        return x ^ (x >> 16)

    left = jnp.full(idx.shape, cls, jnp.uint32)
    right = idx.astype(jnp.uint32)
    for r in range(_ROUNDS):
        left, right = right, left ^ mix(right ^ rk[r])
    return left, right


def split_np(keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """uint64 keys -> (hi, lo) uint32 halves."""
    keys = np.asarray(keys, np.uint64)
    return ((keys >> np.uint64(32)).astype(np.uint32),
            (keys & np.uint64(_M32)).astype(np.uint32))
