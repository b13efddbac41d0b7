"""Device idle while a request waits, % of the traced slice: the device runs
no operation (the profiler trace's idle gaps) while at least one request is
in the server, from its ``decode`` start to its ``encode`` end.  Idle with
the server empty is not counted."""

import phaselib
import xtrace


def read(rec):
    dv = rec["device"]
    if not dv or not dv["chips"] or dv["window_s"] <= 0:
        return None
    present = xtrace.union(
        (s["decode"][0], s["encode"][1])
        for s in phaselib.request_spans(rec, ("decode", "encode")))
    if not present:
        return None
    idle = 0.0
    for a, b in dv["gaps"]:
        idle += sum(max(0.0, min(b, p1) - max(a, p0)) for p0, p1 in present)
    return 100.0 * idle / dv["window_s"]
