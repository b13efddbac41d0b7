"""Device idle, % of the traced slice: 1 - (union of the device's operation
intervals) / slice, from the profiler trace."""


def read(rec):
    dv = rec["device"]
    if not dv or not dv["chips"] or dv["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dv["busy_s"] / dv["window_s"])
