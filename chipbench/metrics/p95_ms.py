"""95th percentile latency in ms, over the same requests as ``p50_ms``."""

import numpy as np

import readlib


def read(rec):
    lat = readlib.latencies_s(rec)
    return float(np.percentile(lat, 95)) * 1e3 if lat.size else None
