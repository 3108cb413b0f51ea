"""The chip's published peaks, from ``peaks.json`` beside this file, keyed by
the ``device_kind`` JAX reports.  A kind that is not in the table is an
error: no metric is read against a guessed peak."""
from __future__ import annotations

import json
import os

_TABLE = json.load(open(os.path.join(os.path.dirname(__file__), "peaks.json")))


def for_kind(kind: str) -> dict:
    if kind not in _TABLE or kind == "source":
        raise KeyError(f"no published peaks for device kind {kind!r} in bench/peaks.json")
    return _TABLE[kind]
