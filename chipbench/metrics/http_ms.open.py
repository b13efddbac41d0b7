"""HTTP front end, ms per request: the handler's ``decode`` (body read and
parsed) + ``encode`` (result encoded and the reply written) spans, mean over
the window's requests."""

import phaselib


def read(rec):
    return phaselib.mean_ms([
        (s["decode"][1] - s["decode"][0]) + (s["encode"][1] - s["encode"][0])
        for s in phaselib.request_spans(rec, ("decode", "encode"))])
