#!/usr/bin/env python3
"""Chip benchmark of the served CNNs: one run of one cell.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

One process holds the chip.  It builds the cell's network from its
configuration file (weights from ``--seed``), serves it with
``repro.serve.http.make_server`` on the ``baremetal`` backend from a thread
of its own, and drives it over HTTP from load-generator processes that
import no JAX (``loadgen.py``).  After a warm-up of the cell's own traffic it
measures ``--seconds`` seconds, drains, and then checks every answer of the
run against the plain reference (``reference.py``) on the same input.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from the program's request spans and counters and
from a profiler trace of the window's last seconds.  Which metrics a cell
reports, and how each is read, is data: ``BENCHMARK.json`` names them and
``metrics/<name>.py`` reads each (see ``spec.py``).

Diagnostics go to stderr, ending with each compared number beside its limit.
The last line of stdout is the JSON result.  A run that finds no TPU, or
fewer chips than the cell asks for, exits non-zero and prints no result.

A run bounds itself.  Set-up must end within ``SETUP_LIMIT_S`` of process
start, and the whole run within ``RUN_LIMIT_S``.  Past a bound,
``faulthandler`` prints every thread's traceback and ends the process with
exit code 1 and no result.  It acts from a C thread, so it fires even while
the main thread is inside a compile.  Each set-up stage is named on stderr as
it starts, so the last lines of a stopped run say where it was.
"""

from __future__ import annotations

import argparse
import base64
import faulthandler
import functools
import hashlib
import io
import json
import os
import pathlib
import shutil
import subprocess
import sys
import threading
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import loadgen  # noqa: E402
import readlib  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
import xtrace  # noqa: E402

MARKER = "chipbench_clock_marker"
TRACE_SLICE_S = 4.0          # profiled seconds, at the end of the window
SETUP_LIMIT_S = 300.0        # process start -> window start
RUN_LIMIT_S = 900.0          # process start -> exit
BUNDLES_KEPT = 6             # per configuration, newest first
REFERENCE_PROCS = 4
STATS_KEYS = ("dispatches", "coalesced_images", "compile_count", "rejected",
              "shed", "retries", "backend_failures", "degraded")


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def setup_stage(t_proc: float, name: str) -> None:
    """Name the set-up stage that starts now, with the seconds since process
    start: a run stopped at its set-up bound ends with the stage it was in,
    and the differences give each finished stage's seconds."""
    log(f"set-up: {name} (+{time.monotonic() - t_proc:.1f} s)")


def bound_run(t_proc: float, limit_s: float) -> None:
    """Dump every thread's traceback to stderr and end the process (exit
    code 1) once ``limit_s`` seconds have passed since process start.
    Calling it again re-arms the one timer."""
    faulthandler.dump_traceback_later(t_proc + limit_s - time.monotonic(),
                                      exit=True, file=sys.__stderr__)


def process_start() -> float:
    """This process's start on the monotonic clock (Linux: ``starttime`` of
    /proc/self/stat, in clock ticks since boot)."""
    ticks = int(pathlib.Path("/proc/self/stat").read_text()
                .rsplit(")", 1)[1].split()[19])
    return ticks / os.sysconf("SC_CLK_TCK")


def require_chips(n: int) -> list:
    """The devices, or ``NoChip`` where JAX finds no TPU or fewer than
    ``n`` chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips; JAX found {len(devs)}")
    return devs


def digest(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p if isinstance(p, bytes) else str(p).encode())
    return h.hexdigest()[:20]


def sources_digest(root: pathlib.Path) -> str:
    """Digest of the program's sources: a cache made by other code is stale."""
    files = sorted((root / "src" / "repro").rglob("*.py"))
    return digest(*(f.relative_to(root).as_posix().encode() + f.read_bytes()
                    for f in files))


# ---------------------------------------------------------------------------
# Set-up: bundle, session, server, generators
# ---------------------------------------------------------------------------
def load_bundle(root, cfg: dict, seed: int, backend: str, stage) -> tuple:
    """The served ``Artifacts`` of ``cfg`` at ``seed``, loaded from the
    bundle cache, or compiled, saved and loaded.  ``(art, how)``.
    ``stage(name)`` is told which of the two it does."""
    from repro.core import engine, graph
    from repro.core.pipeline import Artifacts, CompilerPipeline
    from repro.core.quant import CalibrationTable

    shape = tuple(cfg["input_shape"])
    key = digest(json.dumps({k: cfg[k] for k in ("graph", "engine", "arch",
                                                 "input_shape",
                                                 "calibration")},
                            sort_keys=True),
                 seed, backend, sources_digest(root),
                 (HERE / "reference.py").read_bytes())
    cache = root / "chipbench" / ".cache" / "bundles"
    bdir = cache / f"{cfg['name']}-{key}"
    if (bdir / "manifest.json").exists():
        stage("bundle loaded")
        return Artifacts.load(bdir), "loaded"
    stage("bundle compiling")
    layers = reference.build(cfg["arch"])
    params = reference.make_weights(layers, shape, seed)
    g = getattr(graph, cfg["graph"])()
    prog = {l.name: tuple(l.out_shape) for l in g.layers}
    ref = reference.shapes(layers, shape)
    if prog != ref:
        raise ValueError(f"{cfg['name']}: the program's graph "
                         f"{cfg['graph']!r} and the reference's differ")
    sample = loadgen.make_pool(seed, 1, shape)
    art = CompilerPipeline(
        g, params=params, calib_samples=sample, cfg=engine.CONFIGS[
            cfg["engine"]],
        calibration=CalibrationTable(dict(cfg["calibration"]["scales"]))
    ).run()
    tmp = cache / f".{bdir.name}.{os.getpid()}"
    art.save(tmp)
    os.replace(tmp, bdir)
    old = sorted(cache.glob(f"{cfg['name']}-*"),
                 key=lambda p: p.stat().st_mtime)[:-BUNDLES_KEPT]
    for p in old:
        for f in p.iterdir():
            f.unlink()
        p.rmdir()
    return Artifacts.load(bdir), "compiled"


def request_tracer(sample_rate: int, keep: bool):
    """The ``repro.obs`` Tracer the session records into.  With ``keep`` it
    also keeps every finished request trace, so a window's spans are all
    there whatever the ring buffer holds; without, it is the program's own
    (the kept traces would load the server's garbage collector)."""
    from repro.obs.trace import TraceConfig, Tracer

    class WindowTracer(Tracer):
        def __init__(self, config):
            super().__init__(config)
            self.kept = []

        def finish(self, trace, status="ok", error=""):
            super().finish(trace, status, error)
            if trace is not None:
                self.kept.append(trace)

    config = TraceConfig(sample_rate=sample_rate)
    return WindowTracer(config) if keep else Tracer(config)


def warmup(ses, net: str, stage) -> dict:
    """``Session.warmup`` with each executor call timed: {part: seconds}.
    ``stage(name)`` is told of each rung as it starts."""
    ex = ses.executor(net)
    parts = {}
    run1, runb = ex.run, ex.run_batch

    def timed(fn, rung):
        def call(x, *a, **kw):
            stage(f"warm-up {rung(x)}")
            t = time.perf_counter()
            out = fn(x, *a, **kw)
            parts[f"warmup_{rung(x)}_s"] = time.perf_counter() - t
            return out
        return call

    ex.run = timed(run1, lambda x: "b1")
    ex.run_batch = timed(runb, lambda x: f"b{len(x)}")
    try:
        ses.warmup(net)
    finally:
        del ex.run, ex.run_batch
    return parts


def start_generators(traffic: dict, port: int, net: str, seed: int,
                     shape, seconds: float) -> list:
    """Start the load-generator processes; returns them once each is ready."""
    procs = traffic["processes"]
    base = {"host": "127.0.0.1", "port": port, "net": net, "seed": seed,
            "pool_size": traffic["pool_size"], "input_shape": list(shape),
            "warm_s": traffic["warm_s"], "window_s": seconds,
            "timeout_s": traffic["timeout_s"], "loop": traffic["loop"]}
    specs = []
    if traffic["loop"] == "closed":
        per, extra = divmod(traffic["clients"], procs)
        first = 0
        for p in range(procs):
            n = per + (p < extra)
            specs.append(dict(base, clients=n, first_client=first))
            first += n
    else:
        due, idx = loadgen.open_schedule(seed, traffic["rate_per_s"],
                                         traffic["warm_s"], seconds,
                                         traffic["pool_size"])
        for p in range(procs):
            specs.append(dict(base, due=due[p::procs].tolist(),
                              idx=idx[p::procs].tolist(),
                              threads=traffic["threads_per_process"]))
    env = dict(os.environ, OPENBLAS_NUM_THREADS="1", OMP_NUM_THREADS="1")
    gens = []
    for s in specs:
        p = subprocess.Popen([sys.executable, str(HERE / "loadgen.py")],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                             text=True, env=env)
        p.stdin.write(json.dumps(s) + "\n")
        p.stdin.flush()
        gens.append(p)
    for p in gens:
        if p.stdout.readline().strip() != "ready":
            raise RuntimeError("a load generator failed to start")
    return gens


def stop_generators(gens: list, timeout_s: float) -> list:
    """Each generator's result; a generator past ``timeout_s`` is killed."""
    out = []
    deadline = time.monotonic() + timeout_s
    for p in gens:
        try:
            stdout, _ = p.communicate(
                timeout=max(1.0, deadline - time.monotonic()))
            out.append(json.loads(stdout.strip().splitlines()[-1]))
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
            raise
    return out


def kill_all(gens: list) -> None:
    for p in gens:
        if p.poll() is None:
            p.kill()
        p.wait()


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------
def reference_logits(root, cfg: dict, seed: int, pool: np.ndarray) -> tuple:
    """Reference logits of the pool (cached per configuration and seed),
    and the seconds it took.  The images are split over a few processes
    that import no JAX, each drawing the weights itself."""
    key = digest(json.dumps(cfg, sort_keys=True), seed, len(pool),
                 (HERE / "reference.py").read_bytes(),
                 (HERE / "loadgen.py").read_bytes())
    path = root / "chipbench" / ".cache" / "refs" / f"{cfg['name']}-{key}.npy"
    t = time.perf_counter()
    if path.exists():
        return np.load(path), 0.0
    import multiprocessing
    procs = min(REFERENCE_PROCS, len(pool))
    args = [(cfg["arch"], cfg["input_shape"], seed,
             cfg["calibration"]["scales"], part,
             readlib.PRECISION[cfg["engine"]])
            for part in np.array_split(pool, procs)]
    threads = max(1, (os.cpu_count() or 1) // procs)
    env = {k: os.environ.get(k) for k in ("OPENBLAS_NUM_THREADS",
                                          "OMP_NUM_THREADS")}
    os.environ.update(OPENBLAS_NUM_THREADS=str(threads),
                      OMP_NUM_THREADS=str(threads))
    try:
        with multiprocessing.get_context("spawn").Pool(procs) as workers:
            y = np.concatenate(workers.starmap(reference.logits, args))
    finally:
        for k, v in env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    path.parent.mkdir(parents=True, exist_ok=True)
    np.save(path, y)
    return y, time.perf_counter() - t


def decode_answer(body: bytes, engine_name: str, out_scale: float):
    """A response body -> float logits (int8 steps times the output scale,
    or the bf16 values)."""
    raw = np.load(io.BytesIO(body), allow_pickle=False)
    if engine_name == "nv_small":
        return raw.astype(np.int8).astype(np.float64) * out_scale
    import ml_dtypes
    return np.frombuffer(raw.astype(np.uint8).tobytes(),
                         ml_dtypes.bfloat16).astype(np.float64)


def output_scale(cfg: dict) -> float:
    return cfg["calibration"]["scales"][reference.build(cfg["arch"])[-1][
        "name"]]


def readings(cfg: dict, pairs) -> dict:
    """The numbers compared, over ``(got, want)`` float logit vectors:
    ``max_diff_steps``, the widest gap in steps of the int8 output scale,
    and ``max_rel_err``, the widest gap of an answer over its largest
    reference logit."""
    step = output_scale(cfg)
    out = {"max_diff_steps": 0.0, "max_rel_err": 0.0}
    for got, want in pairs:
        if got.shape != want.shape or not np.all(np.isfinite(got)):
            return {k: float("inf") for k in out}
        gap = float(np.max(np.abs(got - want)))
        out["max_diff_steps"] = max(out["max_diff_steps"], gap / step)
        out["max_rel_err"] = max(out["max_rel_err"], gap / max(
            float(np.max(np.abs(want))), 1e-30))
    return out


def compare(cfg: dict, ref: np.ndarray, answers: list) -> dict:
    """Every distinct answer against the reference of its input: the numbers
    the configuration's ``checks`` name, each with its limit."""
    step = output_scale(cfg)
    got = readings(cfg, ((decode_answer(base64.b64decode(b), cfg["engine"],
                                        step), ref[i])
                         for i, _, b in answers))
    return {k: {"value": got[k], "limit": c["limit"]}
            for k, c in cfg["checks"].items()}


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def load_peak(kind: str) -> dict:
    """The chip's published peaks (``peaks.json``); an unknown chip is an
    error, never a default."""
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device_kind {kind!r} in peaks.json")
    return peaks[kind]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: pathlib.Path = ROOT) -> int:
    t_proc = process_start()
    bound_run(t_proc, SETUP_LIMIT_S)
    try:
        return bounded_main(argv, root, t_proc)
    finally:
        faulthandler.cancel_dump_traceback_later()


def bounded_main(argv, root: pathlib.Path, t_proc: float) -> int:
    args = parse(argv)
    bench = spec.Benchmark(root)
    cell = bench.cell(args.workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    metrics = bench.metrics(cell["name"], per_layer=bool(args.trace))
    readers = {m["name"]: bench.reader(m["name"]) for m in metrics}
    # JAX's compile cache lives in the checkout, at one fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        root / "chipbench" / ".cache" / "jax")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path.insert(0, str(root / "src"))
    setup_stage(t_proc, "jax")
    try:
        devs = require_chips(cell["chips"])
    except NoChip as e:
        log(f"no result: {e}")
        return 3
    return serve_and_measure(root, args, t_proc, cell, cfg, traffic, metrics,
                             readers, devs)


def serve_and_measure(root, args, t_proc, cell, cfg, traffic, metrics,
                      readers, devs) -> int:
    import jax
    from repro.runtime import SchedulerConfig, Session
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.serve.http import make_server

    dev = devs[0]
    events = {"compiles": []}
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: events["compiles"].append(time.monotonic())
        if name == "/jax/core/compile/backend_compile_duration" else None)
    log(f"{cell['name']} seed {args.seed}: {dev.platform} {dev.device_kind!r}"
        f" x{len(devs)}; compile cache {enable_compile_cache()}")
    srv_cfg = cfg["server"]
    net = cfg["name"]
    shape = tuple(cfg["input_shape"])
    parts = {"process_to_jax_s": time.monotonic() - t_proc}
    stage = functools.partial(setup_stage, t_proc)
    t = time.monotonic()
    art, how = load_bundle(root, cfg, args.seed, jax.default_backend(), stage)
    parts[f"bundle_{how}_s"] = time.monotonic() - t
    tracer = request_tracer(srv_cfg["trace_sample"], keep=bool(args.trace))
    stage("session load")
    t = time.monotonic()
    ses = Session(scheduler=SchedulerConfig(
        max_batch=srv_cfg["max_batch"], max_wait_us=srv_cfg["max_wait_us"],
        max_queue=srv_cfg["max_queue"], max_retries=srv_cfg["max_retries"]),
        backend=srv_cfg["backend"], trace=tracer)
    ses.load(art, name=net)
    parts["session_load_s"] = time.monotonic() - t
    parts.update(warmup(ses, net, stage))
    srv = make_server(ses, port=0)
    http_thread = threading.Thread(target=srv.serve_forever,
                                   name="chipbench-http", daemon=True)
    http_thread.start()
    gens = []
    try:
        stage("generators")
        t = time.monotonic()
        gens = start_generators(traffic, srv.server_address[1], net,
                                args.seed, shape, args.seconds)
        parts["generators_s"] = time.monotonic() - t
        rec = measure(root, args, cfg, traffic, ses, net, tracer, gens,
                      parts, t_proc, events)
    finally:
        kill_all(gens)
        srv.shutdown()
        srv.server_close()
        http_thread.join(timeout=30)
        ses.close()
    rec["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devs[:cell["chips"]])
    del ses, art, tracer

    pool = loadgen.make_pool(args.seed, traffic["pool_size"], shape)
    ref, ref_s = reference_logits(root, cfg, args.seed, pool)
    answers = [a for g in rec["gens"] for a in g["answers"]]
    checks = compare(cfg, ref, answers)
    # a request that got no answer at all (timed out, connection lost) or
    # a backend fault is for correctness; a refusal is only a failure
    lost = rec["requests"][:, 3]
    checks["unanswered"] = {"value": int(((lost == -1) | (lost == 500)).sum()),
                            "limit": 0}
    checked = sum(n for _, n, _ in answers)
    correct = bool(checked) and all(
        c["value"] <= c["limit"] for c in checks.values())
    log(f"reference: {ref_s:.2f} s ({'cached' if not ref_s else 'computed'})"
        f"; {checked} answers checked, {len(answers)} distinct")

    result_metrics = {}
    for m in metrics:
        v = readers[m["name"]](rec)
        if v is not None:
            result_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes":
              int(rec["memory_peak_bytes"])}
    result = {"correct": correct, "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": result_metrics,
              "device": device}
    if args.trace:
        dv = rec["device"]
        device["busy_s"] = dv["busy_s"]
        device["window_s"] = dv["window_s"]
        result["breakdown"] = breakdown(rec)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


def measure(root, args, cfg, traffic, ses, net, tracer, gens, parts, t_proc,
            events) -> dict:
    """Warm traffic, the window, the drain: the run's record."""
    import jax
    t0 = time.monotonic() + 0.2
    w0 = t0 + traffic["warm_s"]
    w1 = w0 + args.seconds
    setup_stage(t_proc, "warm traffic")
    for p in gens:
        p.stdin.write(f"{t0!r}\n")
        p.stdin.flush()
    time.sleep(max(0.0, w0 - time.monotonic()))
    s0 = ses.stats(net).snapshot()
    backlog0 = ses.queue_depth(net)
    setup_s = time.monotonic() - t_proc
    bound_run(t_proc, RUN_LIMIT_S)
    parts["warm_traffic_s"] = traffic["warm_s"]
    log("set-up " + ", ".join(f"{k} {v:.2f}" for k, v in parts.items())
        + f"; setup_s {setup_s:.2f}")
    log(f"bounds: setup_s {setup_s:.2f} (limit {SETUP_LIMIT_S:.0f}); the "
        f"whole run ends within {RUN_LIMIT_S:.0f} s of process start")
    trace_dir = None
    if args.trace:
        slice0 = max(w0, w1 - TRACE_SLICE_S)
        time.sleep(max(0.0, slice0 - time.monotonic()))
        trace_dir = root / "chipbench" / ".cache" / "trace" / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation(MARKER):
            marker_t = time.perf_counter()
        slice0 = time.perf_counter()
    time.sleep(max(0.0, w1 - time.monotonic()))
    s1 = ses.stats(net).snapshot()
    backlog1 = ses.queue_depth(net)
    if args.trace:
        slice1 = time.perf_counter()
        jax.profiler.stop_trace()
    outs = stop_generators(gens, traffic["timeout_s"] + 30)
    reqs = np.array([r for g in outs for r in g["records"]],
                    np.float64).reshape(-1, 5)
    # times relative to the window's start
    off = w0 - t0
    reqs[:, :3] -= off
    in_window = ((reqs[:, 0] >= 0) & (reqs[:, 0] < args.seconds))
    attempted = int(in_window.sum())
    failed = int((in_window & (reqs[:, 3] != 200)).sum())
    late = (reqs[in_window, 1] - reqs[in_window, 0]) * 1e3
    late = late if late.size else np.zeros(1)
    lat = (reqs[in_window, 2] - reqs[in_window, 0]) * 1e3
    lat = lat if lat.size else np.zeros(1)
    # when the generators ran 20 ms late or more: a stalled host, not server
    stalls = np.unique(np.round(reqs[in_window][late >= 20.0, 0], 1))
    compiles_in_window = sum(w0 <= c <= w1 for c in events["compiles"])
    delta = {k: s1[k] - s0[k] for k in STATS_KEYS}
    log(f"window {args.seconds} s: attempted {attempted}, failed {failed}; "
        f"latency p50 {np.percentile(lat, 50):.3f}, p95 "
        f"{np.percentile(lat, 95):.3f}, p99 {np.percentile(lat, 99):.3f}, "
        f"max {lat.max():.3f} ms; generator lateness p50 "
        f"{np.median(late):.3f} ms, max {late.max():.3f} ms, 20 ms or more at"
        f" {stalls.tolist()[:20]} s; generator "
        f"busy share {[round(g['busy_share'], 3) for g in outs]}; backlog at "
        f"start {backlog0}, at end {backlog1}; counters {delta}; "
        f"compiles in window {compiles_in_window}")
    # request traces whose submit fell inside the window, relative to w0
    traces = [tr for tr in getattr(tracer, "kept", ())
              if w0 <= tr.t_start < w1]
    rec = {"cell": args.workload, "config": cfg, "traffic": traffic,
           "seconds": args.seconds, "setup_s": setup_s, "requests": reqs,
           "attempted": attempted, "failed": failed, "stats_delta": delta,
           "traces": [{"t_start": tr.t_start - w0, "t_end": tr.t_end - w0,
                       "status": tr.status,
                       "spans": [(s.name, s.t0 - w0, s.t1 - w0, s.args)
                                 for s in tr.spans]} for tr in traces],
           "gens": outs, "device": None}
    if args.trace:
        dv = xtrace.reduce(xtrace.find_xplane(trace_dir), MARKER, marker_t,
                           slice0, slice1, cfg["fused_kernels"])
        shift = lambda ab: [x - w0 for x in ab[:2]] + list(ab[2:])
        dv["modules"] = [shift(m) for m in dv["modules"]]
        dv["gaps"] = [shift(g) for g in dv["gaps"]]
        dv["slice"] = (slice0 - w0, slice1 - w0)
        rec["device"] = dv
        rec["peak"] = load_peak(jax.devices()[0].device_kind)
        log(f"trace: slice {slice1 - slice0:.3f} s, {dv['chips']} chip(s), "
            f"busy {dv['busy_s']:.4f} s, fused kernels {dv['kernel_count']} "
            f"({dv['kernel_s']:.4f} s), launches {len(dv['modules'])}")
    return rec


GAP_CAUSES = ("pad", "device_execute", "respond", "backoff", "hold", "queue")


def breakdown(rec) -> dict:
    """The device operations that took most time in the traced slice, and
    its longest idle gaps.  Each gap is put down to what the dispatcher was
    doing: of the launch phases (``pad``, ``device_execute``, ``respond``,
    ``backoff``), the straggler ``hold`` and ``queue`` (requests waiting
    outside a hold while nothing launched), the one whose spans cover most
    of it; else ``request`` where a request was in the server; else
    ``unattributed``."""
    dv = rec["device"]
    ops = sorted(dv["ops"].items(), key=lambda kv: -kv[1])[:10]
    by_name = {}
    for tr in rec["traces"]:
        for name, a, b, _ in tr["spans"]:
            by_name.setdefault(name, []).append((a, b))
    gaps = []
    for a, b in dv["gaps"][:10]:
        cover = {}
        for name, spans in by_name.items():
            ov = sum(min(b, s1) - max(a, s0) for s0, s1 in
                     xtrace.union(x for x in spans if x[0] < b and x[1] > a))
            if ov > 0:
                cover[name] = ov
        # a hold lies inside its request's queue span
        cover["queue"] = cover.get("queue", 0.0) - cover.get("hold", 0.0)
        causes = {n: cover[n] for n in GAP_CAUSES if cover.get(n, 0.0) > 0}
        label = (max(causes, key=causes.get) if causes else
                 "request" if "request" in cover else "unattributed")
        gaps.append([f"{label} at +{a:.4f}s", b - a])
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": gaps}


if __name__ == "__main__":
    sys.exit(main())
