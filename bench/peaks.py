"""Published peaks of the chips the benchmark runs on (``peaks.json``),
keyed by JAX's ``device_kind``. A chip that is not in the table is an error:
a roofline share against a guessed peak would be a guess."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peak_for(device_kind: str) -> dict:
    devices = json.loads(TABLE.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in {TABLE.name}")
    return devices[device_kind]
