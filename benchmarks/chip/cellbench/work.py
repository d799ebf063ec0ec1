"""Work counts from shapes, and the peaks they are held against.

Nothing here measures: every count follows from the configuration's sizes
(its model family's ``macs_per_image``), so that a change to the program
cannot change what a unit of work is.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict

from cellbench import model

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add them with their source")
    return dict(table["devices"][device_kind])


def step_ops_per_image(cfg: Dict, parties: int = 2) -> int:
    """Implementation-independent operations of one private inference:
    2 per MAC of the plaintext forward, once per party."""
    return 2 * model.family(cfg).macs_per_image(cfg) * parties
