"""The harness end to end on the CPU on a net that only the ``graph`` kind
states: a depthwise conv, a two-group conv, a basic block's two-input add
and a two-branch concat, served through the program's whole path.

The program's graph builder for it is registered inside the test; the
reference builds the same net from the configuration's layer list."""

from __future__ import annotations

import numpy as np
import pytest

import test_harness
from test_harness import make_checkout, on_cpu, run_cell  # noqa: F401

import control  # noqa: E402
import reference  # noqa: E402

SHAPE = (3, 12, 12)
CONV = {"type": "conv", "k": 3, "stride": 1, "pad": 1, "relu": True}
LAYERS = [
    {"name": "data", "type": "input", "inputs": []},
    dict(CONV, name="c1", inputs=["data"], out=8),
    dict(CONV, name="dw", inputs=["c1"], out=8, groups=8),
    dict(CONV, name="g2", inputs=["dw"], out=8, groups=2, relu=False),
    {"name": "block", "type": "add", "inputs": ["g2", "c1"], "relu": True},
    dict(CONV, name="br_a", inputs=["block"], out=4, k=1, pad=0),
    {"name": "br_pool", "type": "pool", "mode": "max", "k": 3, "stride": 1,
     "pad": 1, "inputs": ["block"]},
    dict(CONV, name="br_b", inputs=["br_pool"], out=4, k=1, pad=0),
    {"name": "cat", "type": "concat", "inputs": ["br_a", "br_b"]},
    {"name": "gap", "type": "pool", "mode": "gap", "inputs": ["cat"]},
    {"name": "fc", "type": "fc", "inputs": ["gap"], "out": 10,
     "relu": False}]
ARCH = {"kind": "graph", "layers": LAYERS}


def tiny_graph():
    """The program's ``NetGraph`` of ``LAYERS``."""
    from repro.core.graph import NetGraph
    g = NetGraph("tiny_graph", SHAPE)
    for l in LAYERS:
        g.layer(name=l["name"], type=l["type"], inputs=list(l["inputs"]),
                out_channels=l.get("out", 0), kernel=l.get("k", 0),
                stride=l.get("stride", 1), pad=l.get("pad", 0),
                groups=l.get("groups", 1), relu=l.get("relu", False),
                pool_mode=l.get("mode", ""))
    return g.infer_shapes()


def graph_config(engine: str) -> dict:
    layers = reference.build(ARCH)
    params = reference.make_weights(layers, SHAPE, 0)
    images = np.random.default_rng(1).normal(0, 1, (2,) + SHAPE).astype(
        np.float32)
    cfg = test_harness.lenet_config(engine)
    cfg.update(name=f"tiny_graph_{engine}", graph="tiny_graph",
               input_shape=list(SHAPE), arch=ARCH,
               control="int4" if engine == "nv_small" else "int8")
    cfg["calibration"]["scales"] = reference.calibrate(layers, SHAPE, params,
                                                       images)
    return cfg


@pytest.fixture
def builder(monkeypatch):
    from repro.core import graph
    monkeypatch.setattr(graph, "tiny_graph", tiny_graph, raising=False)


@pytest.mark.parametrize("engine", ["nv_small", "nv_full"])
def test_graph_net_is_correct(tmp_path, on_cpu, builder, capsys,  # noqa: F811
                              engine):
    cfg = graph_config(engine)
    assert cfg["calibration"]["scales"]["br_a"] == \
        cfg["calibration"]["scales"]["cat"]
    root = make_checkout(tmp_path, engine=engine, cfg=cfg)
    res, err = run_cell(root, capsys)
    assert res["correct"] is True, err[-2000:]
    assert res["attempted"] > 0 and res["failed"] == 0
    if engine == "nv_small":
        assert res["checks"]["max_diff_steps"]["value"] == 0.0


def test_int4_control_is_not_correct():
    cfg = graph_config("nv_small")
    got = control.control_readings(cfg, 2 ** 31 + 11, pool_size=4)
    assert got["max_diff_steps"] > cfg["checks"]["max_diff_steps"]["limit"]

