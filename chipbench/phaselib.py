"""Helpers of the readers of launch phases and front-end spans.

A traced launch's spans (``pad``, ``device_execute``, its phases
``quantise``, ``h2d``, ``enqueue``, ``device_wait``, ``d2h``, and
``respond``) carry ``launch``, the dispatcher's launch number, and are
copied onto every traced request of the batch.  The host steps also carry
``cpu_s``, the dispatcher's or launcher's CPU seconds over the span.  A
program without these args yields no launches, and the readers then
return None.
"""

from __future__ import annotations


def launches(rec) -> dict:
    """``{launch: {span name: (start, end, args)}}``.  Copies of a span on
    the requests of one batch are one span; of ``respond``, which each
    request ends in turn, the longest is the launch's."""
    out = {}
    for tr in rec["traces"]:
        for name, a, b, args in tr["spans"]:
            if "launch" not in args:
                continue
            phases = out.setdefault(args["launch"], {})
            if name not in phases or b - a > phases[name][1] - phases[name][0]:
                phases[name] = (a, b, args)
    return out


def bucket_launches(rec, bucket: int) -> list:
    """The phases of every launch of ``bucket`` images, as in
    ``launches``."""
    return [ph for ph in launches(rec).values()
            if ph.get("device_execute", (0, 0, {}))[2].get("bucket") == bucket]


def mean_ms(seconds: list):
    """The mean of ``seconds`` in ms, or None when there are none."""
    return 1e3 * sum(seconds) / len(seconds) if seconds else None


def request_spans(rec, names) -> list:
    """``{name: (start, end)}`` of the spans ``names``, for every request
    that has them all."""
    out = []
    for tr in rec["traces"]:
        got = {n: (a, b) for n, a, b, _ in tr["spans"] if n in names}
        if len(got) == len(names):
            out.append(got)
    return out
