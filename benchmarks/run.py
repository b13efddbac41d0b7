"""Benchmark harness: one module per paper table.  Prints name,us_per_call,derived.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--smoke] [--table N]
                                            [--out DIR] [--model SPEC]...

Tables:
  1  storage / resource accounting of the bare-metal artifacts   (paper Table I)
  2  nv_small INT8 inference latency + bare-metal vs linux-stack (paper Table II)
  3  nv_full bf16: LIVE executor latency (LeNet-5, ResNet-18) with
     VP tolerance-parity gate + cycle model, six networks         (paper Table III)
  4  serving microbenchmarks: arena residency, batching, coalesced
     submit through the Session scheduler                        (runtime layer)
  5  serving front-end: open-loop Poisson mixed-priority load over the
     in-process ServeClient — per-priority p50/p99, goodput, FIFO A/B,
     per-net dispatcher isolation                                (serve layer)
  6  saturation search: MLPerf-style offline throughput + binary-searched
     max_rps_under_slo (declared p99 + error-rate SLO judged by the
     windowed telemetry; gated inverted — lower RPS regresses)  (slo layer)
  7  chaos soak: the table-5 trace under injected fault storms —
     goodput retained, watchdog hang containment (hang_count must
     be 0), circuit-breaker outage recovery_ms                   (fault layer)
  8  observability: request-tracing overhead (sampled mode gated
     under its budget), per-layer profiled-path cost, perf-model
     calibration fidelity; --smoke also writes the captured Chrome
     trace as TRACE_table8.json                                  (obs layer)

``--smoke`` runs every table in reduced-size mode (implies ``--fast``) and
writes one ``BENCH_table<N>.json`` per table into ``--out`` (default ``.``) —
CI uploads these as workflow artifacts so perf history rides along with every
run.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="small subset (CI); full run covers all models")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-size run of every table + BENCH_*.json files")
    ap.add_argument("--table", type=int, default=0, help="run one table only")
    ap.add_argument("--model", action="append", default=[], metavar="SPEC",
                    help="extra net for the storage table: builder name or "
                         "ONNX/JSON model file (repro.frontend; repeatable)")
    ap.add_argument("--out", default=".",
                    help="directory for --smoke JSON output")
    args = ap.parse_args()
    fast = args.fast or args.smoke

    from benchmarks import (table1_storage, table2_nvsmall, table3_nvfull,
                            table4_serving, table5_serving_frontend,
                            table6_saturation, table7_chaos,
                            table8_observability)
    tables = {1: table1_storage, 2: table2_nvsmall, 3: table3_nvfull,
              4: table4_serving, 5: table5_serving_frontend,
              6: table6_saturation, 7: table7_chaos,
              8: table8_observability}
    picked = {args.table: tables[args.table]} if args.table else tables

    out_dir = pathlib.Path(args.out)
    if args.smoke:
        out_dir.mkdir(parents=True, exist_ok=True)

    print("name,us_per_call,derived")
    ok = True
    for num, mod in picked.items():
        try:
            kw = {"fast": fast}
            if num == 1 and args.model:
                kw["extra_models"] = args.model
            if num == 8 and args.smoke:
                # ship the captured Chrome trace next to the BENCH files so
                # CI uploads an openable timeline of its own traffic
                kw["trace_out"] = out_dir / "TRACE_table8.json"
            rows = mod.run(**kw)
            for row in rows:
                print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
            if args.smoke:
                (out_dir / f"BENCH_table{num}.json").write_text(
                    json.dumps({"table": num, "mode": "smoke", "rows": rows},
                               indent=1))
        except Exception as e:                      # pragma: no cover
            ok = False
            print(f"{mod.__name__},ERROR,{type(e).__name__}: {e}", file=sys.stderr)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
