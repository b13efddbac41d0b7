#!/usr/bin/env python3
"""Record the small chip trace that ``test_xtrace.py`` reduces.

    python chipbench/tests/record_trace.py OUT_DIR

On the chip: ResNet-50 int8 (seed 0) at bucket 1, compiled and run once,
then two launches under the profiler with the benchmark's clock marker.
Writes ``OUT_DIR/resnet50_int8_b1.xplane.pb.gz`` and, beside it, a JSON of
the marker's ``perf_counter`` reading and each launch's host interval.
"""

from __future__ import annotations

import gzip
import json
import pathlib
import shutil
import sys
import time

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import numpy as np  # noqa: E402

import run  # noqa: E402
import xtrace  # noqa: E402


def main(out: str) -> None:
    import jax
    from repro.runtime import create_executor

    run.require_chips(1)
    cfg = json.loads((HERE / "configs" / "resnet50_int8.json").read_text())
    art, _ = run.load_bundle(HERE.parent, cfg, 0, jax.default_backend(),
                             run.log)
    ex = create_executor("baremetal", art)
    x = np.random.default_rng(0).normal(0, 1, cfg["input_shape"]).astype(
        np.float32)
    ex.run(x)
    tmp = pathlib.Path(out) / "profile"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation(run.MARKER):
        marker_t = time.perf_counter()
    launches = []
    for _ in range(2):
        t0 = time.perf_counter()
        ex.run(x)
        launches.append((t0, time.perf_counter()))
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    dst = pathlib.Path(out) / "resnet50_int8_b1.xplane.pb.gz"
    dst.write_bytes(gzip.compress(xtrace.find_xplane(tmp).read_bytes(), 9))
    shutil.rmtree(tmp)
    (dst.parent / "resnet50_int8_b1.json").write_text(json.dumps(
        {"marker_t": marker_t, "slice": [marker_t, t1],
         "launches": launches}, indent=1))
    print(f"{dst}: {dst.stat().st_size} bytes")


if __name__ == "__main__":
    main(sys.argv[1])
