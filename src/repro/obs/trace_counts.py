"""Trace-time counts: which path a traced program took.

A jitted function runs its Python body once per trace, not once per
call, so a count taken there says how many programs chose a path (by
shape, say), never how often the device ran it.  The counts are
process-wide, as the jit caches they mirror are, and deterministic:
the same programs traced give the same counts.  Nothing reads them
back into a computation.
"""
from __future__ import annotations

import threading
from typing import Dict

_LOCK = threading.Lock()
_COUNTS: Dict[str, int] = {}


def count_trace(name: str, n: int = 1) -> None:
    """Add ``n`` to the trace-time count ``name``."""
    with _LOCK:
        _COUNTS[name] = _COUNTS.get(name, 0) + n


def trace_counts() -> Dict[str, int]:
    """A snapshot of every trace-time count so far."""
    with _LOCK:
        return dict(_COUNTS)
