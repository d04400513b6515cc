"""The program's own host spans (``repro.obs``), as the per-layer readers
see them: the records of the newest tree that holds a span of a given
name.  A program without spans, or a tree the ring dropped records of,
gives None."""
from __future__ import annotations

import numpy as np


def newest(name: str) -> dict | None:
    """The records under the root of the newest span named ``name``."""
    try:
        from repro import obs
    except ImportError:                 # a program that records no spans
        return None
    every = obs.RING.records()
    roots = every["root_id"][every["name"] == name]
    return obs.RING.records(int(roots.max())) if roots.size else None


def durations_ms(recs: dict, name: str) -> np.ndarray:
    """Durations of the spans named ``name``, in start order (ms)."""
    hit = recs["name"] == name
    order = np.argsort(recs["start_ns"][hit], kind="stable")
    return ((recs["end_ns"][hit] - recs["start_ns"][hit])[order]) * 1e-6
