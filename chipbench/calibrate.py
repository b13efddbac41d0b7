#!/usr/bin/env python3
"""Write a configuration's activation scales into its file.

    python chipbench/calibrate.py chipbench/configs/<config>.json

The scales are part of the configuration, as an NVDLA calibration table is
part of a deployed model: measured once by ``reference.calibrate`` on the
float network with the weights of ``calibration.seed`` and two N(0, 1)
images drawn from ``default_rng(calibration.seed + 1)``, then fixed for every
run, whatever its ``--seed``.  Both the program (as its ``calibration``
table) and the reference read them from the file.
"""

from __future__ import annotations

import json
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import reference  # noqa: E402


def main(path: str) -> None:
    p = pathlib.Path(path)
    cfg = json.loads(p.read_text())
    cal = cfg["calibration"]
    shape = tuple(cfg["input_shape"])
    layers = reference.build(cfg["arch"])
    params = reference.make_weights(layers, shape, cal["seed"])
    images = np.random.default_rng(cal["seed"] + 1).normal(
        0, 1, (cal["images"],) + shape).astype(np.float32)
    cal["scales"] = reference.calibrate(layers, shape, params, images,
                                        cal["percentile"])
    p.write_text(json.dumps(cfg, indent=1) + "\n")
    print(f"{p}: {len(cal['scales'])} scales")


if __name__ == "__main__":
    main(sys.argv[1])
