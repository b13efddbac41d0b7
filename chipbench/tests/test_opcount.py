"""Operation and byte counts, against counts made by hand."""

from __future__ import annotations

import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import opcount  # noqa: E402


def test_one_3x3_conv_by_hand():
    # 64 -> 64 channels, 3x3, stride 1, pad 1, on 56x56, two images
    arch = {"kind": "sequential", "layers": [
        {"name": "c", "type": "conv", "out": 64, "k": 3, "stride": 1,
         "pad": 1, "relu": True}]}
    [(name, ops, nbytes)] = opcount.layer_costs(arch, (64, 56, 56), "int8", 2)
    assert name == "c"
    assert ops == 2 * 2 * 64 * 64 * 3 * 3 * 56 * 56 == 462_422_016
    # int8 weights once, input and output per image, bias + scale words
    assert nbytes == 64 * 64 * 9 + 2 * (64 * 56 * 56 * 2) + 64 * 8
    [(_, _, nbytes16)] = opcount.layer_costs(arch, (64, 56, 56), "bf16", 2)
    assert nbytes16 == 2 * 64 * 64 * 9 + 2 * (64 * 56 * 56 * 2) * 2 + 64 * 4


def resnet50_macs_by_hand() -> int:
    """He et al. Table 1, 50-layer, at 224x224, counted from the table."""
    macs = 64 * 3 * 7 * 7 * 112 * 112                     # conv1
    cin, size = 64, 56
    for mid, blocks, first_stride in ((64, 3, 1), (128, 4, 2), (256, 6, 2),
                                      (512, 3, 2)):
        for b in range(blocks):
            s = first_stride if b == 0 else 1
            out = size // s
            macs += mid * cin * size * size                # 1x1 reduce
            macs += mid * mid * 9 * out * out              # 3x3, stride s
            macs += 4 * mid * mid * out * out              # 1x1 expand
            if b == 0:
                macs += 4 * mid * cin * out * out          # projection
            cin, size = 4 * mid, out
    return macs + 1000 * 2048                              # fc


def test_resnet50_is_8_18_gop():
    cfg = json.loads((HERE / "configs" / "resnet50_int8.json").read_text())
    ops = opcount.ops_per_image(cfg["arch"], cfg["input_shape"])
    assert ops == 2 * resnet50_macs_by_hand() == 8_178_368_512


def test_roofline_floor_of_a_bucket_8_launch():
    cfg = json.loads((HERE / "configs" / "resnet50_int8.json").read_text())
    peak = json.loads((HERE / "peaks.json").read_text())["devices"][
        "TPU v5 lite"]
    t8, n_c, n_m = opcount.ideal_seconds(cfg["arch"], cfg["input_shape"],
                                         "int8", 8, peak)
    t16, _, _ = opcount.ideal_seconds(cfg["arch"], cfg["input_shape"],
                                      "bf16", 8, peak)
    assert n_c + n_m == 54
    assert 0.2e-3 < t8 < 0.35e-3 and 0.45e-3 < t16 < 0.65e-3


def test_mobilenet_depthwise_layers_are_memory_bound_at_bucket_8():
    """MobileNet v1 at 224, stated as a ``graph`` list: at bucket 8 on a
    TPU v5e each of its 13 depthwise 3x3 layers moves more bytes than its
    few operations can hide, in int8 and in bf16."""
    from repro.core import graph
    from test_reference_graph import arch_of
    arch = arch_of(graph.mobilenet_v1())
    shape = (3, 224, 224)
    peak = json.loads((HERE / "peaks.json").read_text())["devices"][
        "TPU v5 lite"]
    for dtype in ("int8", "bf16"):
        costs = opcount.layer_costs(arch, shape, dtype, 8)
        dw = [(ops, nbytes) for name, ops, nbytes in costs
              if name.startswith("dw")]
        assert len(dw) == 13
        assert all(nbytes / peak["hbm_bytes_per_s"]
                   > ops / peak["ops_per_s"][dtype] for ops, nbytes in dw)
        _, n_compute, n_memory = opcount.ideal_seconds(arch, shape, dtype,
                                                       8, peak)
        assert n_compute + n_memory == len(costs) == 28
        assert n_memory >= 13
    # dw0 by hand: 32 channels, 3x3 stride 1 on 112x112, eight images
    [(name, ops, nbytes)] = [c for c in opcount.layer_costs(
        arch, shape, "int8", 8) if c[0] == "dw0"]
    assert ops == 2 * 8 * 32 * 9 * 112 * 112 == 57_802_752
    assert nbytes == 32 * 9 + 8 * (2 * 32 * 112 * 112) + 32 * 8
