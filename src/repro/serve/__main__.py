"""CLI: serve saved artifact bundles — or import+compile models — over HTTP.

    PYTHONPATH=src python -m repro.serve --artifacts lenet5_bundle \
        --artifacts resnet18_bundle --backend baremetal --port 8000 \
        --max-queue 256 --max-batch 8 --max-wait-us 200

    # no pre-compiled bundle needed: builder names and model files
    # (ONNX / repro-net-v1 JSON) compile on startup via repro.frontend
    PYTHONPATH=src python -m repro.serve --model lenet5 \
        --model examples/models/tinynet.json

Each ``--artifacts`` directory is an ``Artifacts.save`` bundle; it becomes
resident under its manifest ``graph_name`` (override one with
``--artifacts dir:name``).  ``--model`` accepts anything
``repro.frontend.resolve.resolve_net`` does (builder name or model file; an
unsupported model fails here at startup, with the frontend's descriptive
error).  Every net gets its own dispatcher thread; ``--max-queue`` bounds
each queue (admission control -> HTTP 429).
"""

from __future__ import annotations

import argparse

from repro.core.pipeline import Artifacts, CompilerPipeline
from repro.runtime import Session, SchedulerConfig
from repro.serve.config import ServeConfig
from repro.serve.http import serve_forever


def _split_name(spec: str) -> tuple:
    """``SPEC[:NAME]`` — the trailing ``:NAME`` must look like a bare name
    (no path separators / suffix dots), so ``dir/net.onnx`` stays a path."""
    head, sep, tail = spec.rpartition(":")
    if sep and tail and "/" not in tail and "\\" not in tail \
            and "." not in tail:
        return head, tail
    return spec, None


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        prog="python -m repro.serve",
        description="multi-tenant HTTP serving front-end over repro.runtime")
    ap.add_argument("--artifacts", action="append", default=[],
                    metavar="DIR[:NAME]",
                    help="saved Artifacts bundle to serve (repeatable)")
    ap.add_argument("--model", action="append", default=[],
                    metavar="SPEC[:NAME]",
                    help="builder name or ONNX/JSON model file to import, "
                         "compile and serve (repeatable)")
    ap.add_argument("--backend", default="baremetal",
                    help="executor backend for every net (default: baremetal)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="0 picks an ephemeral port")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="coalescing ceiling per dispatch")
    ap.add_argument("--max-wait-us", type=float, default=200.0,
                    help="longest the head request is held for stragglers")
    ap.add_argument("--max-queue", type=int, default=256,
                    help="per-net queue bound; past it submits get 429 "
                         "(0 = unbounded)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="failed launches are retried this many times with "
                         "exponential backoff before futures fail")
    ap.add_argument("--fallback-backend", default=None, metavar="BACKEND",
                    help="degraded-mode backend (e.g. 'ref') every net "
                         "falls back to while its circuit breaker is open; "
                         "default: shed with 503 + Retry-After")
    ap.add_argument("--warmup", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="precompile every (net, bucket) program before "
                         "admitting traffic; inference returns 503 and "
                         "/healthz reports 'warming' until done "
                         "(--no-warmup serves immediately, first requests "
                         "may compile-stall)")
    ap.add_argument("--trace", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="record request lifecycle traces (GET /v1/trace; "
                         "--no-trace keeps trace ids but records nothing)")
    ap.add_argument("--trace-sample", type=int, default=1, metavar="N",
                    help="trace every Nth request per net (1 = all, 0 = "
                         "only requests carrying X-Repro-Trace-Id)")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="dump the trace ring buffer as Chrome trace-event "
                         "JSON (DIR/trace.json) on shutdown")
    ap.add_argument("--slo", default=None, metavar="FILE",
                    help="JSON file of SLO policies (per-net latency/"
                         "error-rate/goodput objectives); the burn-rate "
                         "engine evaluates them continuously and surfaces "
                         "state on /metrics, /healthz and GET /v1/slo")
    ap.add_argument("--slo-period-s", type=float, default=5.0,
                    help="background SLO evaluation cadence (seconds)")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress per-request access logs")
    args = ap.parse_args(argv)
    if not args.artifacts and not args.model:
        ap.error("nothing to serve: pass --artifacts and/or --model")

    cfg = SchedulerConfig(max_batch=args.max_batch,
                          max_wait_us=args.max_wait_us,
                          max_queue=args.max_queue or None,
                          max_retries=args.max_retries)
    serve_cfg = ServeConfig(fallback_backend=args.fallback_backend,
                            warmup=args.warmup, trace=args.trace,
                            trace_sample=args.trace_sample,
                            trace_dir=args.trace_dir,
                            slo_path=args.slo,
                            slo_period_s=args.slo_period_s)
    ses = Session(scheduler=cfg, backend=args.backend,
                  trace=serve_cfg.trace_config())
    if serve_cfg.slo_path:
        from repro.obs.slo import load_policies
        policies = load_policies(serve_cfg.slo_path)
        ses.attach_slo(policies, start=True, period_s=serve_cfg.slo_period_s)
        print(f"[repro.serve] slo: {len(policies)} policy(ies) from "
              f"{serve_cfg.slo_path}, evaluating every "
              f"{serve_cfg.slo_period_s:g}s")
    for spec in args.artifacts:
        path, _, name = spec.partition(":")
        loaded = ses.load(Artifacts.load(path), name=name or None,
                          fallback_backend=serve_cfg.fallback_backend)
        print(f"[repro.serve] resident: {loaded} <- {path}")
    for spec in args.model:
        from repro.frontend.resolve import resolve_net
        src, name = _split_name(spec)
        g, params = resolve_net(src)
        art = CompilerPipeline(g, params=params).run()
        loaded = ses.load(art, name=name or None,
                          fallback_backend=serve_cfg.fallback_backend)
        print(f"[repro.serve] resident: {loaded} <- compiled {src}")
    serve_forever(ses, host=args.host, port=args.port,
                  verbose=not args.quiet, warmup=serve_cfg.warmup,
                  trace_dir=serve_cfg.trace_dir)


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
