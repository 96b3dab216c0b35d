"""Plain references, found by name.

`reference/<family>.py` holds one model family (the configuration's
"family"): its model in jax.numpy, and what the harness needs to know of
it (parameter and FLOP counts, extra batch inputs, smoke sizes).
`reference/<round>.py` holds one training round (the traffic file's
"reference"): a `Round` class and its `FAULTS`.
"""
from __future__ import annotations

import importlib


def family(cfg: dict):
    """The module of the configuration's model family."""
    return importlib.import_module(f"{__name__}.{cfg['family']}")


def round_module(traffic: dict):
    """The module of the reference round the traffic file names."""
    return importlib.import_module(f"{__name__}.{traffic['reference']}")
