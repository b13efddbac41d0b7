"""The readers of the launch-phase and front-end metrics, on a synthetic
record: launches grouped by ``launch``, ``host_wait``'s ratio, and idle
counted only while a request is in the server.  Also ``run.breakdown``'s
label of an idle gap.

    python -m pytest chipbench/tests/test_readers.py
"""

from __future__ import annotations

import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import run  # noqa: E402
import spec  # noqa: E402


def read(metric, rec):
    return spec.Benchmark(HERE.parent).reader(metric)(rec)


def launch(n, t, bucket=8, cpu=0.5):
    """The launch-level spans of launch ``n`` starting at ``t``: pad 1 ms,
    quantise 2, h2d 3, enqueue 1, device_wait 10, d2h 0.5, respond 1; each
    host step's ``cpu_s`` is ``cpu`` of its wall time."""
    out, at = [], t
    for name, ms in (("pad", 1.0), ("quantise", 2.0), ("h2d", 3.0),
                     ("enqueue", 1.0), ("device_wait", 10.0), ("d2h", 0.5),
                     ("respond", 1.0)):
        args = {"launch": n}
        if name != "device_wait":
            args["cpu_s"] = cpu * ms * 1e-3
        out.append((name, at, at + ms * 1e-3, args))
        at += ms * 1e-3
    out.append(("device_execute", t + 1e-3, t + 17.5e-3,
                {"bucket": bucket, "lanes": bucket, "launch": n}))
    return out


def rec_of(traces, device=None):
    return {"traces": [{"t_start": 0.0, "t_end": 1.0, "status": "ok",
                        "spans": s} for s in traces],
            "device": device}


def test_phases_grouped_by_launch():
    # launch 1 copied onto three requests, launch 2 onto two; a bucket-4
    # launch is left out
    one, two = launch(1, 0.0), launch(2, 0.1)
    rec = rec_of([one, one, one, two, two, launch(3, 0.2, bucket=4)])
    assert read("prep_ms.closed", rec) == pytest.approx(6.0)
    assert read("fetch_ms.closed", rec) == pytest.approx(0.5)


def test_respond_of_a_launch_is_its_longest_copy():
    # each request of a batch ends its respond span in turn
    base = launch(1, 0.0)
    spans = [[s if s[0] != "respond" else
              (s[0], s[1], s[1] + k * 1e-3, s[3]) for s in base]
             for k in (1, 2, 4)]
    rec = rec_of(spans)
    # host steps: 1 + 2 + 3 + 1 + 0.5 ms at half CPU, respond 4 ms with
    # cpu_s 0.5 ms (its args), so cpu = 3.75 + 0.5 of 11.5 ms of wall time
    assert read("host_wait.closed", rec) == pytest.approx(
        100 * (1 - 4.25 / 11.5))


@pytest.mark.parametrize("cpu", [0.0, 0.25, 1.0])
def test_host_wait_is_the_share_off_the_cpu(cpu):
    rec = rec_of([launch(1, 0.0, cpu=cpu), launch(2, 0.1, cpu=cpu)])
    assert read("host_wait.closed", rec) == pytest.approx(100 * (1 - cpu))


def test_program_without_launch_phases_reads_nothing():
    # the spans of a program that records no ``launch`` args
    old = [[(n, a, b, {k: v for k, v in args.items()
                       if k not in ("launch", "cpu_s")})
            for n, a, b, args in launch(1, 0.0)
            if n not in ("quantise", "h2d", "enqueue", "device_wait", "d2h")]]
    rec = rec_of(old, device={"chips": 1, "window_s": 1.0, "gaps": [(0, 1)]})
    for metric in ("prep_ms.closed", "fetch_ms.closed", "host_wait.closed",
                   "http_ms.open", "idle_with_work.open"):
        assert read(metric, rec) is None, metric


def test_http_ms_is_decode_plus_encode_per_request():
    reqs = [[("decode", 0.0, 1e-3, {}), ("request", 1e-3, 9e-3, {}),
             ("encode", 9e-3, 10e-3, {})],
            [("decode", 0.0, 2e-3, {}), ("request", 2e-3, 5e-3, {}),
             ("encode", 5e-3, 8e-3, {})],
            [("request", 0.0, 5e-3, {})]]          # no front-end spans
    assert read("http_ms.open", rec_of(reqs)) == pytest.approx(3.5)


def test_idle_with_work_counts_idle_only_while_a_request_is_present():
    # slice of 1 s; the device idles over [0, 0.4) and [0.6, 1.0); one
    # request is in the server over [0.3, 0.7), another over [0.35, 0.5)
    reqs = [[("decode", 0.3, 0.31, {}), ("encode", 0.69, 0.7, {})],
            [("decode", 0.35, 0.36, {}), ("encode", 0.49, 0.5, {})]]
    device = {"chips": 1, "window_s": 1.0,
              "gaps": [(0.6, 1.0), (0.0, 0.4)]}
    got = read("idle_with_work.open", rec_of(reqs, device))
    assert got == pytest.approx(100 * (0.1 + 0.1))
    # the whole idle share is larger: the empty server's idle is left out
    assert got < read("device_idle.open", rec_of(reqs, dict(
        device, busy_s=0.2)))


# one idle gap, [1.0, 1.1) s, and the spans around it
GAP_SPANS = {
    # 90 % under a hold (inside its queue span), a 10 % sliver of pad
    "hold": [("request", 0.9, 1.3), ("queue", 0.95, 1.09),
             ("hold", 1.0, 1.09), ("pad", 1.09, 1.2)],
    # mostly pad, then the launch; the next request waits in a hold
    "pad": [("queue", 0.95, 1.0), ("pad", 1.0, 1.08),
            ("device_execute", 1.08, 1.3), ("queue", 1.07, 1.1),
            ("hold", 1.07, 1.1), ("request", 0.9, 1.3)],
    # waiting outside a hold while nothing launched
    "queue": [("queue", 0.9, 1.095), ("hold", 0.9, 1.02),
              ("pad", 1.095, 1.2), ("request", 0.9, 1.3)],
    "request": [("request", 0.9, 1.3), ("encode", 1.2, 1.3)],
    "unattributed": [("decode", 1.2, 1.25), ("request", 1.25, 1.3)]}


@pytest.mark.parametrize("label", sorted(GAP_SPANS))
def test_breakdown_labels_a_gap_by_what_covers_most_of_it(label):
    spans = [(n, a, b, {}) for n, a, b in GAP_SPANS[label]]
    rec = rec_of([spans], device={"ops": {"conv": 0.5},
                                  "gaps": [(1.0, 1.1)]})
    got = run.breakdown(rec)
    assert got["device_ops"] == [["conv", 0.5]]
    assert [g[0] for g in got["idle_gaps"]] == [f"{label} at +1.0000s"]
