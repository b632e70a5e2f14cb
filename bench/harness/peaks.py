"""The one table of chip peaks, keyed by JAX's ``device_kind``."""

from __future__ import annotations

import json
import os
from typing import Dict

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of one chip of this kind; an unknown kind is an error."""
    with open(PATH) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PATH}")
    return table[device_kind]
