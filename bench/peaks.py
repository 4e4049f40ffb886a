"""Published peaks of each chip, keyed by JAX's ``device_kind``.

Nothing in the benchmark reads them yet; a kernel's roofline share will
divide its time into the least time these peaks allow.  A device that is
not in ``peaks.json`` is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(_TABLE.read_text())
    try:
        return table[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(table)}") from None
