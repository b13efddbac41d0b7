#!/usr/bin/env python3
"""Smoke run of the served path on a TPU.

    python chip_smoke.py [--seed N]           # one chip
    python chip_smoke.py --four-chips [--seed N]

Default (one chip): ResNet-50 (3x224x224, weights seeded by ``--seed``) is
compiled by ``CompilerPipeline`` at ``nv_small`` (int8) and at ``nv_full``
(bf16).  Both load into one ``Session`` on the ``baremetal`` backend with no
fallback backend, warm up, and are served by the HTTP front end from a
thread of this process.  A few requests per net travel over HTTP, one of
them a concurrent burst that the dispatcher coalesces into a bucket larger
than 1.  Every answer is checked against the numpy ``ref`` backend on the
same input: int8 byte-equal, bf16 within the ``core/tolerances.py`` bound.

``--four-chips`` runs only the lane-sharded path: ResNet-50 int8 and bf16
buckets of 8 with the lanes split over a 4-device ``serving_mesh``, compared
with the same inputs on one device.

Set-up time (pipeline compile, warmup) is printed as set-up time; nothing
here measures speed.  Any failed check exits non-zero without a result
line, as does a run that finds no TPU.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import threading
import time

import numpy as np

SRC = pathlib.Path(__file__).resolve().parent / "src"
NET_INPUT = (3, 224, 224)
FUSED = {"int8": "pallas_fused", "bf16": "pallas_bf16_fused"}
BURST = 6              # concurrent requests per net; coalesce into bucket 8
SEQUENTIAL = 2         # one-at-a-time requests per net
# supervisor counters that must stay zero on a healthy run
FAULT_COUNTERS = ("retries", "backend_failures", "watchdog_timeouts",
                  "arena_resets", "degraded", "circuit_state",
                  "circuit_opens", "circuit_rejected", "rejected", "shed")


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def compile_nets(seed: int) -> dict:
    """{name: (Artifacts, pipeline seconds)} for ResNet-50 int8 and bf16."""
    from repro.core import engine, graph
    from repro.core.pipeline import CompilerPipeline
    arts = {}
    for name, cfg in (("resnet50_int8", engine.NV_SMALL),
                      ("resnet50_bf16", engine.NV_FULL)):
        t0 = time.perf_counter()
        art = CompilerPipeline(graph.resnet50(), cfg=cfg, seed=seed).run()
        arts[name] = (art, time.perf_counter() - t0)
    return arts


def check_parity(name: str, dtype: str, got, want, tol) -> None:
    """int8: byte-equal; bf16: within ``tol`` (``core/tolerances.py``)."""
    from repro.core.tolerances import assert_close
    if dtype == "int8":
        check(np.array_equal(np.asarray(got.output_int8, np.int8),
                              np.asarray(want.output_int8, np.int8)),
              f"{name}: int8 output differs")
        return
    try:
        assert_close(got.output, want.output, tol, name)
    except AssertionError as e:
        raise SmokeFailure(str(e)) from None


def check_plan(name: str, ex, buckets) -> tuple:
    """Every CONV/FC layer of every bucket's plan runs the fused kernel."""
    fused = FUSED[ex.cfg.dtype]
    for b in buckets:
        plan = ex.kernel_plan if b == 1 else ex.batched_kernel_plan(b)
        bad = [(i, c.kernel) for i, (d, c) in enumerate(zip(ex.descs, plan))
               if d.unit in ("CONV", "FC") and c.kernel != fused]
        check(not bad, f"{name}: bucket {b} plan is not all {fused}: "
                       f"{bad[:5]}")
    return ex.capabilities().kernels


def serve_phase(seed: int) -> None:
    import jax
    from repro.core.tolerances import net_tolerance
    from repro.runtime import SchedulerConfig, Session, create_executor
    from repro.serve.client import HttpServeClient
    from repro.serve.http import make_server

    arts = compile_nets(seed)
    # adaptive=False with a long hold: the burst's requests, each a few MB
    # of JSON decoded by the server, coalesce into one bucket
    cfg = SchedulerConfig(max_batch=8, adaptive=False, max_wait_us=2e6)
    ses = Session(scheduler=cfg, backend="baremetal")
    for name, (art, _) in arts.items():
        ses.load(art, name=name)
    for name, (art, secs) in arts.items():
        ex = ses.executor(name)
        kernels = check_plan(name, ex, cfg.buckets)
        log(f"{name}: set-up time: pipeline compile {secs:.1f} s; "
            f"kernels {list(kernels)}")
    warm = ses.warmup()
    compiles_warm = {}
    for name, ms in warm.items():
        snap = ses.stats(name).snapshot()
        compiles_warm[name] = snap["compile_count"]
        log(f"{name}: set-up time: warmup {ms:.0f} ms "
            f"({snap['compile_count']} programs, buckets "
            f"{list(cfg.buckets)})")

    srv = make_server(ses, port=0)
    thread = threading.Thread(target=srv.serve_forever,
                              name="chip-smoke-http", daemon=True)
    thread.start()
    port = srv.server_address[1]
    client = HttpServeClient(f"http://127.0.0.1:{port}", timeout_s=300,
                             workers=BURST)
    rng = np.random.default_rng(seed + 1000)
    answers = {name: [] for name in arts}
    try:
        for name in arts:
            for _ in range(SEQUENTIAL):
                x = rng.normal(0, 1, NET_INPUT).astype(np.float32)
                answers[name].append((x, client.infer(name, x)))
            xs = [rng.normal(0, 1, NET_INPUT).astype(np.float32)
                  for _ in range(BURST)]
            futs = [client.infer_async(name, x) for x in xs]
            answers[name] += [(x, client.resolve_future(f, timeout=300))
                              for x, f in zip(xs, futs)]
    finally:
        client.close()
        srv.shutdown()
        srv.server_close()
        thread.join(timeout=30)
        ses.close()

    for name, (art, _) in arts.items():
        dtype = art.cfg.dtype
        ref = create_executor("ref", art)
        tol = net_tolerance(art.kernel_plan)
        for x, res in answers[name]:
            check(not res.degraded, f"{name}: a response was degraded")
            check_parity(name, dtype, res, ref.run(x), tol)
        snap = ses.stats(name).snapshot()
        check(snap["compile_count"] == compiles_warm[name],
              f"{name}: {snap['compile_count'] - compiles_warm[name]} "
              f"compiles after warmup")
        bad = {k: snap[k] for k in FAULT_COUNTERS if snap[k]}
        check(not bad, f"{name}: supervisor counters non-zero: {bad}")
        coalesced = [b for b, n in snap["bucket_launches"].items()
                     if b > 1 and n]
        check(bool(coalesced), f"{name}: no bucket larger than 1 was "
                               f"launched: {snap['bucket_launches']}")
        check(snap["coalesced_images"] == SEQUENTIAL + BURST,
              f"{name}: served {snap['coalesced_images']} requests")
        parity = ("byte-equal to ref" if dtype == "int8"
                  else f"within rtol {tol.rtol:.3e} of ref")
        log(f"{name}: {len(answers[name])} HTTP answers {parity}; buckets "
            f"launched {snap['bucket_launches']}; compiles after warmup 0; "
            f"degraded/retries/failures/watchdog/circuit all 0")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"device memory: peak_bytes_in_use {stats['peak_bytes_in_use']}")
    else:
        log("device memory: not reported by this backend")


def four_chip_phase(seed: int) -> None:
    import jax
    from repro.core.tolerances import net_tolerance
    from repro.distributed.sharding import lane_sharding, serving_mesh
    from repro.runtime import create_executor

    check(len(jax.devices()) == 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    mesh = serving_mesh()
    lanes = lane_sharding(mesh)
    rng = np.random.default_rng(seed + 2000)
    for name, (art, secs) in compile_nets(seed).items():
        X = rng.normal(0, 1, (8,) + NET_INPUT).astype(np.float32)
        one = create_executor("baremetal", art)
        kernels = check_plan(name, one, (1, 8))
        t0 = time.perf_counter()
        want = one.run_batch(X)
        t_one = time.perf_counter() - t0
        four = create_executor("baremetal", art)
        four.batch_sharding = lanes
        t0 = time.perf_counter()
        launched = four.submit_batch(X)
        y = launched.y
        y.block_until_ready()
        t_four = time.perf_counter() - t0
        shards = sorted((s.device.id, s.data.shape) for s in
                        y.addressable_shards)
        check(len(y.sharding.device_set) == 4
              and not y.sharding.is_fully_replicated
              and all(shape[0] == 2 for _, shape in shards),
              f"{name}: output is not split over 4 devices by lane: "
              f"{y.sharding} {shards}")
        got = four.finish(launched)
        if art.cfg.dtype == "int8":
            check(np.array_equal(got.output_int8, want.output_int8),
                  f"{name}: 4-device bucket differs from 1 device")
            verdict = "byte-equal"
        else:
            check_parity(name, "bf16", got, want,
                         net_tolerance(art.kernel_plan))
            same = np.array_equal(got.output_int8, want.output_int8)
            verdict = "byte-equal" if same else "within tolerance"
        log(f"{name}: set-up time: pipeline compile {secs:.1f} s, first "
            f"bucket-8 call (compile included) {t_one:.1f} s on 1 device, "
            f"{t_four:.1f} s on 4; kernels {list(kernels)}; lane shards "
            f"{shards}; 4-device output {verdict} to 1 device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the lane-sharded bucket-8 path on 4 "
                         "devices, compared with 1 device")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, calibration and inputs")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repro package is not at {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    from repro.runtime.compile_cache import enable_compile_cache
    cache = {"hits": 0, "misses": 0}

    def on_event(event: str, **kw) -> None:
        for k in cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                cache[k] += 1

    jax.monitoring.register_event_listener(on_event)
    log(f"device_kind {dev.device_kind!r}, {len(jax.devices())} device(s); "
        f"compile cache {enable_compile_cache()}")
    try:
        if args.four_chips:
            four_chip_phase(args.seed)
        else:
            serve_phase(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    log(f"compile cache: {cache['hits']} hits, {cache['misses']} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
