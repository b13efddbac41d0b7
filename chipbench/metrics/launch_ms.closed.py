"""Executor launch, ms: mean ``device_execute`` span of the window's
bucket-8 launches.  Host clock around the blocking call, so it holds the
input's transfer and the output's fetch as well as device time."""

import numpy as np

import readlib


def read(rec):
    spans = readlib.per_launch(rec, bucket=8)
    return float(np.mean([b - a for (a, b), _ in spans])) * 1e3 \
        if spans else None
