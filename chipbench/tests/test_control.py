"""The comparison that decides ``correct`` fails its control.

At the cells' own sizes (ResNet-50, 3x224x224) on two images: the reference
computed one precision below the configuration's reads past the limit of at
least one compared number.  And the reference itself agrees with the
program's own numpy datapath, byte for byte on int8."""

from __future__ import annotations

import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import control  # noqa: E402
import reference  # noqa: E402


@pytest.mark.parametrize("name", ["resnet50_int8", "resnet50_bf16"])
def test_control_is_not_correct(name):
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    got = control.control_readings(cfg, 2 ** 31 + 3, pool_size=2)
    failed = [k for k, c in cfg["checks"].items() if got[k] > c["limit"]]
    assert failed, (got, cfg["checks"])


@pytest.mark.parametrize("engine", ["nv_small", "nv_full"])
def test_reference_matches_the_programs_oracle(engine):
    """LeNet-5 through the program's compiler and its numpy VP, against the
    reference given the same weights and scales."""
    from repro.core import engine as eng, graph
    from repro.core.pipeline import CompilerPipeline
    from repro.core.quant import CalibrationTable
    from test_harness import LENET, SHAPE, lenet_config

    cfg = lenet_config(engine)
    layers = reference.build(LENET)
    params = reference.make_weights(layers, SHAPE, 5)
    x = np.random.default_rng(9).normal(0, 1, (1,) + SHAPE).astype(np.float32)
    art = CompilerPipeline(graph.lenet5(), params=params, calib_samples=x,
                           cfg=eng.CONFIGS[engine],
                           calibration=CalibrationTable(
                               cfg["calibration"]["scales"])).run()
    prec = "int8" if engine == "nv_small" else "bf16"
    want = reference.forward(layers, SHAPE, params,
                             cfg["calibration"]["scales"], x, prec)[0]
    if prec == "int8":
        assert np.array_equal(art.vp_output_int8.astype(np.float64)
                              * art.output_scale, want)
    else:
        assert np.array_equal(art.vp_output.astype(np.float64), want)


def test_weights_are_the_programs_seeded_draw():
    from repro.core import graph
    layers = reference.build(json.loads(
        (HERE / "configs" / "resnet50_int8.json").read_text())["arch"])
    mine = reference.make_weights(layers, (3, 224, 224), 77)
    theirs = graph.resnet50().init_params(77)
    assert mine.keys() == theirs.keys()
    for k in mine:
        assert np.array_equal(mine[k]["w"], theirs[k]["w"])
        assert np.array_equal(mine[k]["b"], theirs[k]["b"])
