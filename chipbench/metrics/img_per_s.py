"""Images answered per second: answers completed inside the window over the
window's seconds."""

import readlib


def read(rec):
    return readlib.completed_in_window(rec) / rec["seconds"]
