"""Executor input preparation, ms per bucket-8 launch: ``pad`` (the
dispatcher stacks the batch) + ``quantise`` + ``h2d`` (the executor casts it
to the engine's dtype and hands it to the device), mean over the window's
bucket-8 launches."""

import phaselib

STEPS = ("pad", "quantise", "h2d")


def read(rec):
    return phaselib.mean_ms([
        sum(ph[n][1] - ph[n][0] for n in STEPS)
        for ph in phaselib.bucket_launches(rec, 8)
        if all(n in ph for n in STEPS)])
