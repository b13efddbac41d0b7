"""Executor output fetch, ms per bucket-8 launch: ``d2h`` (the output
surface copied to the host, its live lanes sliced and unpacked), mean over
the window's bucket-8 launches."""

import phaselib


def read(rec):
    return phaselib.mean_ms([
        ph["d2h"][1] - ph["d2h"][0]
        for ph in phaselib.bucket_launches(rec, 8) if "d2h" in ph])
