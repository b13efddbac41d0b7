"""Dispatcher host steps off the CPU, %: 100 x (1 - sum of ``cpu_s`` / sum of
wall time) over the ``pad``, ``quantise``, ``h2d``, ``enqueue``, ``d2h`` and
``respond`` spans of the window's launches.  ``cpu_s`` is the thread's own
CPU time, so the rest is time the dispatcher or launcher thread waited,
for the GIL in the main."""

import phaselib

STEPS = ("pad", "quantise", "h2d", "enqueue", "d2h", "respond")


def read(rec):
    wall = cpu = 0.0
    for ph in phaselib.launches(rec).values():
        for n in STEPS:
            if n in ph and "cpu_s" in ph[n][2]:
                wall += ph[n][1] - ph[n][0]
                cpu += ph[n][2]["cpu_s"]
    return 100.0 * (1.0 - cpu / wall) if wall > 0 else None
