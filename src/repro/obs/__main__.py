"""CLI: profile a model's layers and print measured-vs-modeled deltas.

    PYTHONPATH=src python -m repro.obs report --model lenet5 \
        [--backend baremetal] [--iters 5] [--warmup 2] [--batch 1] \
        [--no-calibrate] [--json] [--save-calibration cal.json]

``--model`` accepts anything ``repro.frontend.resolve.resolve_net`` does
(builder name or ONNX/JSON model file).  The run compiles the model, warms
the executor, collects per-layer kernel timings over the profiled path,
fits ``perfmodel.calibrate()``, and prints the per-layer table — the
workflow behind the ROADMAP's perf-model fidelity item.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.obs")
    sub = ap.add_subparsers(dest="cmd", required=True)
    rep = sub.add_parser("report",
                         help="per-layer measured-vs-modeled fidelity report")
    rep.add_argument("--model", default="lenet5", metavar="SPEC",
                     help="builder name or ONNX/JSON model file "
                          "(default: lenet5)")
    rep.add_argument("--backend", default="baremetal",
                     help="executor backend to profile (default: baremetal)")
    rep.add_argument("--iters", type=int, default=5,
                     help="profiled runs per layer stat (median)")
    rep.add_argument("--warmup", type=int, default=2,
                     help="discarded warmup runs (pay per-op compilation)")
    rep.add_argument("--batch", type=int, default=1,
                     help="profile the batched path at this bucket size")
    rep.add_argument("--no-calibrate", action="store_true",
                     help="skip the fit; print uncalibrated deltas only")
    rep.add_argument("--json", action="store_true",
                     help="emit the report as JSON instead of the table")
    rep.add_argument("--save-calibration", default=None, metavar="FILE",
                     help="write the fitted CalibrationProfile as JSON")
    args = ap.parse_args(argv)

    from repro.core import perfmodel
    from repro.core.pipeline import CompilerPipeline
    from repro.frontend.resolve import resolve_net
    from repro.obs.report import fidelity_report, format_report, \
        profile_layers
    from repro.runtime import create_executor

    g, params = resolve_net(args.model)
    art = CompilerPipeline(g, params=params).run()
    ex = create_executor(args.backend, art)
    samples = profile_layers(ex, iters=args.iters, warmup=args.warmup,
                             batch=args.batch)
    cal = None
    if not args.no_calibrate:
        cal = perfmodel.calibrate(samples, ex.descs, dtype=ex.cfg.dtype)
    rep = fidelity_report(ex, samples, cal)
    rep["model"] = args.model
    if args.save_calibration and cal is not None:
        with open(args.save_calibration, "w") as f:
            json.dump(cal.to_dict(), f, indent=1)
        print(f"[repro.obs] calibration -> {args.save_calibration}",
              file=sys.stderr)
    if args.json:
        print(json.dumps(rep, indent=1))
    else:
        print(format_report(rep, name=args.model))
        if cal is not None:
            fams = ", ".join(
                f"{k}: {cal.compute_rate(k):.0f} MACs/us, "
                f"{cal.stream_bw(k):.0f} B/us, "
                f"launch {cal.launch_us(k):.1f}us"
                for k in sorted(cal.families))
            print(f"calibration [{cal.platform}, "
                  f"{cal.samples} samples, "
                  f"fallback {cal.us_per_cycle:.3g} us/cycle] {fams}")
    return 0


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
