"""The reference's ``graph`` kind: grouped convolutions, concat, and every
net of the paper written as an explicit layer list.

The nets' lists are derived here from the program's ``NetGraph`` builders;
the reference itself imports nothing of the program."""

from __future__ import annotations

import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import opcount  # noqa: E402
import reference  # noqa: E402


def arch_of(g) -> dict:
    """A ``graph`` architecture holding the program's ``NetGraph`` ``g``
    layer by layer, in the reference's vocabulary."""
    layers = []
    for l in g.layers:
        d = {"name": l.name, "type": l.type, "inputs": list(l.inputs)}
        if l.type == "conv":
            d.update(out=l.out_channels, k=l.kernel, stride=l.stride,
                     pad=l.pad, relu=l.relu)
            if l.groups != 1:
                d["groups"] = l.groups
        elif l.type == "fc":
            d.update(out=l.out_channels, relu=l.relu)
        elif l.type == "pool":
            d["mode"] = l.pool_mode
            if l.pool_mode != "gap":
                d.update(k=l.kernel, stride=l.stride, pad=l.pad)
        elif l.type == "add":
            d["relu"] = l.relu
        layers.append(d)
    return {"kind": "graph", "layers": layers}


def resnet50_config() -> dict:
    return json.loads((HERE / "configs" / "resnet50_int8.json").read_text())


@pytest.mark.parametrize("precision", ["int8", "bf16"])
def test_resnet50_as_a_graph_gives_the_same_logits(precision):
    cfg = resnet50_config()
    shape = (3, 64, 64)
    as_graph = {"kind": "graph", "layers": reference.build(cfg["arch"])}
    assert reference.build(as_graph) == reference.build(cfg["arch"])
    x = np.random.default_rng(0).normal(0, 1, (2,) + shape).astype(
        np.float32)
    scales = cfg["calibration"]["scales"]
    want = reference.logits(cfg["arch"], shape, 0, scales, x, precision)
    got = reference.logits(as_graph, shape, 0, scales, x, precision)
    assert got.tobytes() == want.tobytes()


def naive_grouped_conv(x, w, groups, stride, pad):
    """Integer convolution by its definition, one output value at a time."""
    n, c, h, wd = x.shape
    kk, cg, r, s = w.shape
    kg = kk // groups
    xp = np.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    p, q = (h + 2 * pad - r) // stride + 1, (wd + 2 * pad - s) // stride + 1
    y = np.zeros((n, kk, p, q), np.int64)
    for b in range(n):
        for o in range(kk):
            g = o // kg
            for i in range(p):
                for j in range(q):
                    win = xp[b, g * cg:(g + 1) * cg, i * stride:i * stride + r,
                             j * stride:j * stride + s]
                    y[b, o, i, j] = int((win * w[o]).sum())
    return y


@pytest.mark.parametrize("c,out,groups,k,stride,pad", [
    (6, 6, 6, 3, 1, 1),        # depthwise 3x3
    (8, 8, 8, 3, 2, 1),        # depthwise, strided
    (6, 4, 2, 3, 1, 1),        # two groups
    (8, 6, 2, 1, 1, 0),        # two groups, pointwise
])
def test_grouped_gemm_matches_a_naive_loop(c, out, groups, k, stride, pad):
    rng = np.random.default_rng(7)
    x = rng.integers(-128, 128, (2, c, 7, 7)).astype(np.int64)
    w = rng.integers(-127, 128, (out, c // groups, k, k)).astype(np.int64)
    got = reference._conv_gemm(x, w.reshape(out, -1), k, stride, pad,
                               np.float64, groups)
    want = naive_grouped_conv(x, w, groups, stride, pad)
    assert np.array_equal(got.astype(np.int64), want.reshape(got.shape))


def test_every_net_of_the_paper_is_expressible():
    from repro.core import graph
    assert set(graph.BUILDERS) == {"lenet5", "resnet18", "resnet50",
                                   "alexnet", "mobilenet", "googlenet"}
    for name, builder in graph.BUILDERS.items():
        g = builder()
        arch = arch_of(g)
        layers = reference.build(arch)
        assert reference.shapes(layers, g.input_shape) == {
            l.name: tuple(l.out_shape) for l in g.layers}, name
        assert opcount.ops_per_image(arch, g.input_shape) == 2 * g.macs(), \
            name
    mobilenet = graph.mobilenet_v1()
    assert opcount.ops_per_image(arch_of(mobilenet), (3, 224, 224)) \
        == 1_137_480_704


def tiny_concat_arch() -> dict:
    conv = dict(type="conv", k=1, stride=1, pad=0, relu=True)
    return {"kind": "graph", "layers": [
        {"name": "data", "type": "input", "inputs": []},
        dict(conv, name="a", inputs=["data"], out=4),
        dict(conv, name="b", inputs=["data"], out=2),
        {"name": "cat", "type": "concat", "inputs": ["a", "b"]},
        {"name": "gap", "type": "pool", "mode": "gap", "inputs": ["cat"]},
        {"name": "fc", "type": "fc", "inputs": ["gap"], "out": 3,
         "relu": False}]}


def test_calibrate_gives_concat_members_the_concats_scale():
    arch = tiny_concat_arch()
    layers = reference.build(arch)
    shape = (3, 6, 6)
    params = reference.make_weights(layers, shape, 0)
    images = np.random.default_rng(1).normal(0, 1, (2,) + shape).astype(
        np.float32)
    scales = reference.calibrate(layers, shape, params, images)
    assert scales["a"] == scales["b"] == scales["cat"]
    # the unified scales serve the int path
    y = reference.logits(arch, shape, 0, scales, images, "int8")
    assert y.shape == (2, 3) and np.all(np.isfinite(y))


def test_concat_members_with_other_scales_raise():
    arch = tiny_concat_arch()
    layers = reference.build(arch)
    shape = (3, 6, 6)
    params = reference.make_weights(layers, shape, 0)
    x = np.random.default_rng(1).normal(0, 1, (1,) + shape).astype(
        np.float32)
    scales = reference.calibrate(layers, shape, params, x)
    scales["b"] *= 2
    with pytest.raises(ValueError, match="concat 'cat'"):
        reference.logits(arch, shape, 0, scales, x, "int8")
    # the float path has no scales and concatenates
    assert reference.logits(arch, shape, 0, scales, x, "bf16").shape == (1, 3)


@pytest.mark.parametrize("bad,match", [
    (lambda ls: ls[1:], "start with the input layer"),
    (lambda ls: ls + [dict(ls[1])], "duplicate"),
    (lambda ls: [ls[0], dict(ls[1], inputs=["cat"])] + ls[2:],
     "not an earlier layer"),
    (lambda ls: ls[:3] + [dict(ls[1], name="dead")] + ls[3:],
     "read by no later layer"),
    (lambda ls: ls[:3] + [dict(ls[3], inputs=["a"])] + ls[4:],
     r"\(concat\) has 1 inputs"),
    (lambda ls: ls[:1] + [dict(ls[1], type="lrn")] + ls[2:], "type 'lrn'"),
])
def test_build_rejects_a_malformed_graph(bad, match):
    layers = tiny_concat_arch()["layers"]
    with pytest.raises(ValueError, match=match):
        reference.build({"kind": "graph", "layers": bad(layers)})


@pytest.mark.parametrize("groups,cat_rows,match", [
    (3, 6, "groups 3 must divide"),
    (1, 5, "different H, W"),
])
def test_shapes_rejects_bad_groups_and_concat(groups, cat_rows, match):
    conv = dict(type="conv", k=3, stride=1, pad=1, relu=True)
    layers = reference.build({"kind": "graph", "layers": [
        {"name": "data", "type": "input", "inputs": []},
        dict(conv, name="a", inputs=["data"], out=4, groups=groups),
        dict(conv, name="b", inputs=["data"], out=4,
             pad=1 if cat_rows == 6 else 0),
        {"name": "cat", "type": "concat", "inputs": ["a", "b"]}]})
    with pytest.raises(ValueError, match=match):
        reference.shapes(layers, (4, 6, 6))
