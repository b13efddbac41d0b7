"""Whole served step's share of the chip's peak, %: the operations of the
images answered in the window (2 x MACs of every CONV/FC layer, padding
lanes not counted) per second, over the dtype's peak."""

import opcount
import readlib


def read(rec):
    if "peak" not in rec:
        return None
    cfg = rec["config"]
    ops = opcount.ops_per_image(cfg["arch"], cfg["input_shape"])
    rate = readlib.completed_in_window(rec) / rec["seconds"]
    peak = rec["peak"]["ops_per_s"][readlib.PRECISION[cfg["engine"]]]
    return 100.0 * rate * ops / peak
