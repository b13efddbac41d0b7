"""Profiler trace -> device busy time, kernel time, launches and idle gaps.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Each TPU is a plane ``/device:TPU:<n>`` whose ``XLA Ops`` line
holds one event per operation run and whose ``XLA Modules`` line holds one
event per program launched.  Host threads are lines of ``/host:CPU``; there a
``TraceAnnotation`` made by the benchmark at a known ``time.perf_counter()``
reading ties the trace's clock to the host's, so device intervals can be set
against the program's own spans.

Everything returned is in host ``perf_counter`` seconds, clipped to the
slice ``[t0, t1]`` that the benchmark traced.
"""

from __future__ import annotations

import bisect
import gzip
import pathlib
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


_HLO = re.compile(r"^(%\S+) = (\w+\[[^\]]*\])\S* ([\w-]+)\(")


def short_name(name: str) -> str:
    """A TPU op event is named by its whole HLO instruction; keep the
    instruction's name, result shape and opcode (``%fusion.29 s8[12544,3]
    fusion``), and mark Pallas kernels (``tpu_custom_call``)."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    out = " ".join(m.groups())
    return out + " tpu_custom_call" if "tpu_custom_call" in name else out


def find_xplane(log_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals) -> list:
    """Merged, sorted ``[(a, b)]`` of the given intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce(path, marker: str, marker_t: float, t0: float, t1: float,
           kernel_names) -> dict:
    """Reduce one trace (``.xplane.pb``, or gzipped ``.xplane.pb.gz``) to
    what the metrics read.

    ``marker``/``marker_t``: the annotation's name and the ``perf_counter``
    reading taken inside it.  ``kernel_names``: substrings of an
    operation's event name that mark it as one of the fused kernels.
    Returns ``chips`` (TPU planes found), ``busy_s`` (union of operation
    intervals, averaged over chips), ``window_s``, ``ops`` ({short name:
    seconds} summed over chips), ``kernel_s`` and ``kernel_count``,
    ``modules`` ([start, end, kernel seconds] of each launch inside the
    slice, chip 0) and ``gaps`` ([start, end] of chip 0's idle intervals,
    longest first).  ``chips`` is 0 where the trace has no
    TPU plane, and the rest is then empty.
    """
    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    pd = (ProfileData.from_serialized_xspace(gzip.decompress(
        path.read_bytes())) if path.suffix == ".gz"
          else ProfileData.from_file(str(path)))
    offset = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:") and offset is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == marker:
                        offset = ev.start_ns * 1e-9 - marker_t
                        break
    out = {"chips": len(devices), "window_s": t1 - t0, "busy_s": 0.0,
           "ops": {}, "kernel_s": 0.0, "kernel_count": 0, "modules": [],
           "gaps": []}
    if not devices:
        return out
    if offset is None:
        raise ValueError(f"trace {path} lacks the clock marker {marker!r}")
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    busy = 0.0
    for chip, plane in enumerate(devices):
        lines = {line.name: line for line in plane.lines}
        spans, kernels = [], []
        for ev in lines[OPS_LINE].events if OPS_LINE in lines else ():
            a = ev.start_ns * 1e-9 - offset
            b = a + ev.duration_ns * 1e-9
            a, b = max(a, t0), min(b, t1)
            if b <= a:
                continue
            spans.append((a, b))
            name = short_name(ev.name)
            out["ops"][name] = out["ops"].get(name, 0.0) + (b - a)
            if any(k in ev.name for k in kernel_names):
                kernels.append((a, b))
        merged = union(spans)
        busy += sum(b - a for a, b in merged)
        out["kernel_s"] += sum(b - a for a, b in kernels)
        out["kernel_count"] += len(kernels)
        if chip:
            continue
        edges = [t0] + [x for ab in merged for x in ab] + [t1]
        out["gaps"] = sorted(
            ((a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a),
            key=lambda g: g[0] - g[1])
        kernels.sort()
        starts = [ka for ka, _ in kernels]
        for ev in lines[MODULES_LINE].events if MODULES_LINE in lines else ():
            a = ev.start_ns * 1e-9 - offset
            b = a + ev.duration_ns * 1e-9
            if a < t0 or b > t1:
                continue
            inside = kernels[bisect.bisect_left(starts, a):
                             bisect.bisect_left(starts, b)]
            out["modules"].append(
                (a, b, sum(min(kb, b) - ka for ka, kb in inside)))
    out["busy_s"] = busy / len(devices)
    return out
