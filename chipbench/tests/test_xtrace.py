"""The trace reduction, on a small trace recorded on the chip.

``data/resnet50_int8_b1.xplane.pb.gz`` holds two bucket-1 launches of ResNet-50
int8 on one TPU v5e, recorded by ``record_trace.py`` with the benchmark's
clock marker; ``data/resnet50_int8_b1.json`` the marker's reading and each
launch's host interval."""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import xtrace  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
MARKER = "chipbench_clock_marker"
FUSED = ['custom_call_target="tpu_custom_call"']


@pytest.fixture(scope="module")
def reduced():
    meta = json.loads((DATA / "resnet50_int8_b1.json").read_text())
    t0, t1 = meta["slice"]
    return meta, xtrace.reduce(DATA / "resnet50_int8_b1.xplane.pb.gz", MARKER,
                               meta["marker_t"], t0, t1, FUSED)


def test_one_chip_two_launches_54_fused_kernels_each(reduced):
    meta, dv = reduced
    assert dv["chips"] == 1
    assert len(dv["modules"]) == 2
    assert dv["kernel_count"] == 2 * 54
    for _, _, kernel_s in dv["modules"]:
        assert kernel_s > 0


def test_launches_sit_inside_their_host_calls(reduced):
    """The marker puts device time on the host's clock: each launch lies
    within the host interval of the call that made it (1 ms of slack)."""
    meta, dv = reduced
    for (a, b, _), (h0, h1) in zip(sorted(dv["modules"]), meta["launches"]):
        assert h0 - 1e-3 <= a < b <= h1 + 1e-3


def test_busy_and_gaps_fill_the_slice(reduced):
    meta, dv = reduced
    t0, t1 = meta["slice"]
    idle = sum(b - a for a, b in dv["gaps"])
    assert 0 < dv["busy_s"] < dv["window_s"]
    assert abs(dv["busy_s"] + idle - (t1 - t0)) < 1e-6
    assert dv["kernel_s"] <= dv["busy_s"]
    assert sum(dv["ops"].values()) >= dv["busy_s"] - 1e-9
    assert dv["gaps"] == sorted(dv["gaps"], key=lambda g: g[0] - g[1])


def test_a_trace_without_tpu_reduces_to_nothing(tmp_path):
    """A trace with no TPU plane (the CPU) reads as no chip, not as 0."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(8)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    import time
    with jax.profiler.TraceAnnotation(MARKER):
        mt = time.perf_counter()
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    dv = xtrace.reduce(xtrace.find_xplane(tmp_path), MARKER, mt, mt,
                       time.perf_counter(), FUSED)
    assert dv["chips"] == 0 and dv["modules"] == [] and dv["busy_s"] == 0.0


def test_union_merges_overlaps():
    assert xtrace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)]
