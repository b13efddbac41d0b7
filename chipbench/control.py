#!/usr/bin/env python3
"""The control of a configuration's comparison, at the cell's own size.

    python chipbench/control.py <config> <seed> [<seed> ...]

The reference put in the program's place and computed one precision below
the configuration's (``control`` in its file: int4 for the int8 datapath,
int8 for bf16), on the same pool of images a run of that seed serves.  It
prints, per seed, each number the configuration compares and its limit; a
sound comparison sees the control fail at least one.
"""

from __future__ import annotations

import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import loadgen  # noqa: E402
import readlib  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

POOL = 8


def control_readings(cfg: dict, seed: int, pool_size: int = POOL) -> dict:
    shape = tuple(cfg["input_shape"])
    layers = reference.build(cfg["arch"])
    params = reference.make_weights(layers, shape, seed)
    pool = loadgen.make_pool(seed, pool_size, shape)
    scales = cfg["calibration"]["scales"]
    want = reference.forward(layers, shape, params, scales, pool,
                             readlib.PRECISION[cfg["engine"]])
    got = reference.forward(layers, shape, params, scales, pool,
                            cfg["control"])
    return run.readings(cfg, zip(got, want))


def main(argv) -> None:
    name, seeds = argv[0], [int(s) for s in argv[1:]]
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    for seed in seeds:
        got = control_readings(cfg, seed)
        print(json.dumps({"config": name, "control": cfg["control"],
                          "seed": seed, "readings": got,
                          "limits": {k: c["limit"] for k, c in
                                     cfg["checks"].items()}}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
