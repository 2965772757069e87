"""Published peaks of the chips the benchmark runs on, keyed by device kind.

The table is ``peaks.json`` beside this file; each entry names its source.
A device kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import json
import pathlib

TABLE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str, table=TABLE) -> dict:
    """The peaks of ``device_kind`` (``jax.Device.device_kind``)."""
    with open(table) as fh:
        known = json.load(fh)
    if device_kind not in known:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{table}; known: {sorted(known)}")
    return known[device_kind]
