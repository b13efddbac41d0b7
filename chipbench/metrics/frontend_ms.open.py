"""HTTP front end, ms per request: mean client time from send to response,
less the mean ``request`` span (scheduler submit to result) of the same
window.  What is left is the request's transport, decode and encode."""

import numpy as np

import readlib


def read(rec):
    r = readlib.due_in_window(rec)
    r = r[r[:, 3] == 200]
    req = [b - a for a, b, _ in readlib.spans(rec, "request")]
    if not len(r) or not req:
        return None
    return (float(np.mean(r[:, 2] - r[:, 1])) - float(np.mean(req))) * 1e3
