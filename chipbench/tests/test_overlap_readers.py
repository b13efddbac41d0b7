"""The readers of the launch pipeline's share, ``overlap_share.closed``
and ``.open``, on a synthetic record.

    python -m pytest chipbench/tests/test_overlap_readers.py
"""

from __future__ import annotations

import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import spec  # noqa: E402

METRICS = ("overlap_share.closed", "overlap_share.open")


def read(metric, rec):
    return spec.Benchmark(HERE.parent).reader(metric)(rec)


def launch(n, t, ahead=None, device_ms=10.0):
    """The launch-level spans of launch ``n`` enqueued at ``t`` + 1 ms:
    ``enqueue`` carries ``ahead`` unless it is None (a program without the
    pipeline's arg)."""
    enq = {"launch": n, "cpu_s": 1e-4}
    if ahead is not None:
        enq["ahead"] = ahead
    dw = t + 1e-3 + device_ms * 1e-3
    return [("pad", t, t + 1e-3, {"launch": n, "cpu_s": 1e-4}),
            ("enqueue", t + 1e-3, t + 2e-3, enq),
            ("device_wait", t + 2e-3, dw, {"launch": n}),
            ("device_execute", t + 1e-3, dw + 1e-3,
             {"bucket": 8, "lanes": 8, "launch": n}),
            ("respond", dw + 1e-3, dw + 2e-3, {"launch": n, "cpu_s": 1e-4})]


def rec_of(traces):
    return {"traces": [{"t_start": 0.0, "t_end": 1.0, "status": "ok",
                        "spans": s} for s in traces], "device": None}


@pytest.mark.parametrize("metric", METRICS)
def test_interleaved_launches(metric):
    # launch 1 alone, then 2..4 each enqueued while the one before is on
    # the device (their spans interleave in time), then 5 alone; each
    # launch's spans are copied onto two requests
    ahead = {1: 0, 2: 1, 3: 1, 4: 1, 5: 0}
    traces = []
    for n, a in ahead.items():
        spans = launch(n, 0.008 * n, ahead=a)
        traces += [spans, spans]
    assert read(metric, rec_of(traces)) == pytest.approx(60.0)


@pytest.mark.parametrize("metric", METRICS)
def test_no_launch_goes_ahead(metric):
    rec = rec_of([launch(n, 0.02 * n, ahead=0) for n in range(1, 4)])
    assert read(metric, rec) == 0.0


@pytest.mark.parametrize("metric", METRICS)
def test_program_without_the_ahead_arg_reads_nothing(metric):
    rec = rec_of([launch(n, 0.02 * n) for n in range(1, 4)])
    assert read(metric, rec) is None
    assert read(metric, rec_of([])) is None
