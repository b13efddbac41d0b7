"""Helpers the metric readers share: what a run's record holds, sliced.

The record (``run.py``) keeps times in seconds from the window's start:

  ``requests``     rows ``[due, send, done, status, pool index]`` of every
                   request the generators made (warm-up and drain included)
  ``traces``       the program's spans of each request submitted in the
                   window: ``{"t_start", "t_end", "status", "spans"}`` with
                   ``spans`` as ``(name, start, end, args)``
  ``stats_delta``  ``NetStats`` counters, end of window minus start
  ``device``       with ``--trace 1``: the reduced profiler trace
                   (``xtrace.reduce``) of the slice ``device["slice"]``
"""

from __future__ import annotations

import numpy as np

# the datapath precision of each engine configuration
PRECISION = {"nv_small": "int8", "nv_full": "bf16"}


def due_in_window(rec) -> np.ndarray:
    r = rec["requests"]
    return r[(r[:, 0] >= 0) & (r[:, 0] < rec["seconds"])]


def latencies_s(rec) -> np.ndarray:
    """Due time to response of every request due in the window; a request
    that failed counts as waiting until the last response of the run."""
    r = due_in_window(rec)
    end = rec["requests"][:, 2].max() if len(rec["requests"]) else 0.0
    done = np.where(r[:, 3] == 200, r[:, 2], end)
    return done - r[:, 0]


def completed_in_window(rec) -> int:
    r = rec["requests"]
    return int(((r[:, 3] == 200) & (r[:, 2] >= 0)
                & (r[:, 2] <= rec["seconds"])).sum())


def spans(rec, name: str) -> list:
    """``(start, end, args)`` of every span called ``name``."""
    return [(a, b, args) for tr in rec["traces"]
            for n, a, b, args in tr["spans"] if n == name]


def per_launch(rec, bucket=None) -> list:
    """``device_execute`` spans, one per launch (every request of a batch
    carries the same span), optionally of one bucket size."""
    seen = {}
    for a, b, args in spans(rec, "device_execute"):
        if bucket is None or args.get("bucket") == bucket:
            seen[(a, b)] = args
    return sorted(seen.items())
