"""Launches enqueued ahead, %: the share of the window's traced launches
whose ``enqueue`` span has ``ahead`` 1, enqueued behind a launch still on
the device (the dispatcher's two-deep launch pipeline).  A program that
records no ``ahead`` reads nothing."""

import phaselib


def read(rec):
    enqueued = [ph["enqueue"][2] for ph in phaselib.launches(rec).values()
                if "enqueue" in ph]
    ahead = [args["ahead"] for args in enqueued if "ahead" in args]
    return 100.0 * sum(a == 1 for a in ahead) / len(ahead) if ahead else None
