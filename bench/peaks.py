"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The table is ``peaks.json``, each entry with its source.  A device kind the
table does not hold is an error: a share of a guessed peak is no number.
"""
from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; add it to {_TABLE}")
    return table[device_kind]
