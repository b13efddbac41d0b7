"""Set-up seconds: process start to the window's start.  JAX start, the
bundle (compiled, or loaded from the cache), ``Session.load``, warm-up of
every bucket, the generators' start and the warm traffic of the cell."""


def read(rec):
    return rec["setup_s"]
