"""Fused CONV/FC kernels' share of their roofline, %.

Over the launches that lie wholly in the traced slice: the roofline floor of
each launch (per CONV/FC layer at the launch's bucket, the larger of its
operations over the dtype's peak and its bytes over HBM bandwidth, from
``opcount``) summed, over the device time of the fused kernels' events in
those launches.  Each launch's bucket comes from the program's
``device_execute`` span that holds it."""

import opcount
import readlib


def read(rec):
    dv = rec["device"]
    if not dv or not dv["modules"]:
        return None
    cfg = rec["config"]
    launches = readlib.per_launch(rec)
    ideal = kernel = 0.0
    floors = {}
    for a, b, k_s in dv["modules"]:
        mid = (a + b) / 2
        bucket = next((args.get("bucket") for (s0, s1), args in launches
                       if s0 <= mid <= s1), None)
        if bucket is None or k_s <= 0:
            continue
        if bucket not in floors:
            floors[bucket] = opcount.ideal_seconds(
                cfg["arch"], cfg["input_shape"],
                readlib.PRECISION[cfg["engine"]], bucket, rec["peak"])[0]
        ideal += floors[bucket]
        kernel += k_s
    return 100.0 * ideal / kernel if kernel else None
