"""Median latency in ms, from each request's due time to its response, over
every request due in the window (a failed one counts as the longest)."""

import numpy as np

import readlib


def read(rec):
    lat = readlib.latencies_s(rec)
    return float(np.percentile(lat, 50)) * 1e3 if lat.size else None
