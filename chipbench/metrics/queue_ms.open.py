"""Scheduler wait, ms per request: mean ``queue`` span, submit to launch
(the dispatcher's ``hold`` for stragglers lies inside it)."""

import numpy as np

import readlib


def read(rec):
    q = [b - a for a, b, _ in readlib.spans(rec, "queue")]
    return float(np.mean(q)) * 1e3 if q else None
