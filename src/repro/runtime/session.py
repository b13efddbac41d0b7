"""Serving-side Session API.

A ``Session`` owns one or more compiled networks, each bound to a registered
executor backend, and serves them through an async request queue with
adaptive micro-batching (``repro.runtime.scheduler``):

    art = CompilerPipeline(graph.lenet5()).run()
    ses = Session(art)                       # default backend: baremetal
    fut = ses.submit(x)                      # async: Future[ExecResult]
    y = fut.result()
    y = ses.run(x)                           # sync sugar over submit
    ys = ses.run_batch(X)                    # (N, ...) batch, bit-exact vs
                                             # N sequential runs

    ses.load(other_art, backend="linuxstack")  # multi-network residency
    ses.run(x2, net=other_art.graph_name)

    ses = Session.from_bundle("bundle_dir/")   # serve a saved bundle,
                                               # no recompilation or VP run

Layering: ``Session`` resolves networks and owns residency; the scheduler
owns queueing, coalescing, padding and lane masking; backends (anything
satisfying ``repro.core.executor.ExecutorBackend``) own execution only.
Concurrent ``submit`` calls against the same network coalesce into one
vmapped batch program on backends that support native batching — results
stay bit-exact versus sequential ``run`` calls.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
import weakref
from concurrent.futures import Future
from typing import Dict, List, Optional

import numpy as np

from repro.core.executor import ExecResult
from repro.core.pipeline import Artifacts
from repro.obs.trace import TraceConfig, Tracer
from repro.obs.timeseries import Telemetry
from repro.runtime import registry
from repro.runtime.scheduler import Scheduler, SchedulerConfig

# NetStats.circuit_state gauge values (Prometheus-friendly ints)
_CIRCUIT_STATES = {"closed": 0, "half_open": 1, "open": 2}


@dataclasses.dataclass
class NetStats:
    """Per-network serving counters, safe to mutate and read concurrently.

    The first block counts API-level traffic (kept from the pre-scheduler
    Session); the second block is filled by the net's dispatcher thread.
    With one dispatcher per resident net *plus* the ``/metrics`` endpoint
    reading from HTTP threads, every mutation goes through a ``note_*``
    method under the internal lock, and readers take a coherent
    ``snapshot()``.  Bare attribute reads remain fine for tests/debugging
    (ints are torn-read-free under CPython), but cross-counter invariants
    are only guaranteed by ``snapshot()``.
    """
    calls: int = 0               # Session.run invocations
    batch_calls: int = 0         # Session.run_batch invocations
    images: int = 0
    submits: int = 0             # requests enqueued (run/run_batch included)
    dispatches: int = 0          # coalesced batches executed
    coalesced_images: int = 0    # requests served through dispatches
    coalesce_max: int = 0        # largest coalesced batch so far
    queue_depth_peak: int = 0
    rejected: int = 0            # admission control (QueueFullError)
    shed: int = 0                # deadline passed before launch
    compile_count: int = 0       # executor program builds observed (warmup +
                                 # dispatch) — nonzero deltas after warmup
                                 # mean a request paid a compile stall
    warmup_ms: float = 0.0       # time spent in Session.warmup for this net
    # -- fault-tolerance counters (dispatcher supervisor) --------------------
    retries: int = 0             # launch attempts beyond each batch's first
    backend_failures: int = 0    # failed launch attempts (incl. retried ones)
    watchdog_timeouts: int = 0   # launches abandoned by the watchdog
    arena_resets: int = 0        # poisoned-arena restores (checksum mismatch)
    degraded: int = 0            # requests served by the fallback backend
    faults_injected: int = 0     # injected faults observed (FaultyExecutor)
    circuit_state: int = 0       # breaker gauge: 0 closed, 1 half-open, 2 open
    circuit_opens: int = 0       # closed/half-open -> open transitions
    circuit_rejected: int = 0    # submits shed while the circuit was open
    launches_ahead: int = 0      # launches enqueued behind one still on the
                                 # device (the two-deep launch pipeline)
    latency_total_us: float = 0.0  # summed submit->result latency: together
    latency_count: int = 0         # with this count, the Prometheus summary
                                   # _sum/_count pair (unwindowed, unlike the
                                   # percentile ring buffer)
    bucket_launches: Dict[int, int] = dataclasses.field(
        default_factory=dict)    # dispatched-batch count per padded bucket
    latencies_us: "collections.deque" = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=2048), repr=False)
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    # -- writers (scheduler + Session threads) -------------------------------
    def note_call(self, images: int = 1, batch: bool = False) -> None:
        with self._lock:
            if batch:
                self.batch_calls += 1
            else:
                self.calls += 1
            self.images += images

    def note_submit(self, n: int, depth: int) -> None:
        with self._lock:
            self.submits += n
            self.queue_depth_peak = max(self.queue_depth_peak, depth)

    def note_reject(self, n: int) -> None:
        with self._lock:
            self.rejected += n

    def note_shed(self, n: int) -> None:
        with self._lock:
            self.shed += n

    def note_dispatch(self, k: int, latencies_us, bucket: Optional[int] = None,
                      compiles: int = 0, degraded: int = 0) -> None:
        with self._lock:
            self.dispatches += 1
            self.coalesced_images += k
            self.coalesce_max = max(self.coalesce_max, k)
            if bucket is not None:
                self.bucket_launches[int(bucket)] = \
                    self.bucket_launches.get(int(bucket), 0) + 1
            self.compile_count += compiles
            self.degraded += degraded
            self.latencies_us.extend(latencies_us)
            self.latency_total_us += float(sum(latencies_us))
            self.latency_count += len(latencies_us)

    def note_ahead(self) -> None:
        with self._lock:
            self.launches_ahead += 1

    def note_warmup(self, ms: float, compiles: int = 0) -> None:
        with self._lock:
            self.warmup_ms += ms
            self.compile_count += compiles

    def note_retry(self, n: int = 1) -> None:
        with self._lock:
            self.retries += n

    def note_failure(self, timeout: bool = False) -> None:
        with self._lock:
            self.backend_failures += 1
            if timeout:
                self.watchdog_timeouts += 1

    def note_arena_reset(self) -> None:
        with self._lock:
            self.arena_resets += 1

    def note_faults(self, total: int) -> None:
        """Mirror the FaultyExecutor's absolute injection count."""
        with self._lock:
            self.faults_injected = max(self.faults_injected, int(total))

    def note_circuit(self, state: str) -> None:
        s = _CIRCUIT_STATES[state]
        with self._lock:
            if s == 2 and self.circuit_state != 2:
                self.circuit_opens += 1
            self.circuit_state = s

    def note_circuit_reject(self, n: int) -> None:
        with self._lock:
            self.circuit_rejected += n

    # -- readers -------------------------------------------------------------
    @property
    def coalesce_mean(self) -> float:
        return self.coalesced_images / self.dispatches if self.dispatches else 0.0

    def latency_us(self, pct: float) -> float:
        """Submit->result latency percentile (e.g. 50, 90, 99) over the
        recent-request window; 0.0 before any request completes."""
        with self._lock:
            samples = list(self.latencies_us)
        if not samples:
            return 0.0
        return float(np.percentile(np.asarray(samples), pct))

    def latency_summary(self) -> Dict[str, float]:
        return {f"p{p:g}": self.latency_us(p) for p in (50, 90, 99)}

    def snapshot(self) -> Dict[str, float]:
        """One coherent copy of every counter plus latency percentiles —
        the unit ``/metrics`` renders.  Taken under the same lock the
        dispatcher mutates under, so no cross-counter tearing."""
        with self._lock:
            out = {}
            for f in dataclasses.fields(self):
                if f.name in ("latencies_us", "_lock"):
                    continue
                v = getattr(self, f.name)
                out[f.name] = dict(v) if isinstance(v, dict) else v
            samples = list(self.latencies_us)
        arr = np.asarray(samples) if samples else None
        for p in (50, 90, 99):
            out[f"latency_p{p}_us"] = (
                float(np.percentile(arr, p)) if arr is not None else 0.0)
        out["latency_samples"] = len(samples)
        return out


@dataclasses.dataclass
class _Net:
    name: str
    backend: str
    executor: object
    artifacts: Artifacts
    stats: NetStats = dataclasses.field(default_factory=NetStats)
    input_elems: Optional[int] = None    # cached expected input size
    dtype: str = "int8"                  # engine datapath (capabilities())
    fallback: object = None              # degraded-mode executor (or None)
    fallback_backend: Optional[str] = None


class Session:
    """Multi-network inference session over registered executor backends."""

    def __init__(self, artifacts: Optional[Artifacts] = None,
                 backend: str = "baremetal", name: Optional[str] = None,
                 scheduler: Optional[SchedulerConfig] = None,
                 warmup: bool = False, trace=None, telemetry=None):
        self._nets: Dict[str, _Net] = {}
        self._order: List[str] = []
        self.default_backend = backend
        # ``trace``: a TraceConfig (or a pre-built Tracer) — every Session
        # gets one; lifecycle spans are a handful of perf_counter calls per
        # request, and TraceConfig(enabled=False) disables recording while
        # keeping the trace-id contract
        self.tracer = trace if isinstance(trace, Tracer) \
            else Tracer(trace if isinstance(trace, TraceConfig)
                        else TraceConfig())
        # ``telemetry``: a Telemetry (or TimeSeriesConfig) — every Session
        # gets one; the scheduler records every resolved request into its
        # sliding windows (a bisect + counters per request), feeding the
        # windowed /metrics series and the SLO burn-rate engine
        self.telemetry = telemetry if isinstance(telemetry, Telemetry) \
            else Telemetry(telemetry)
        self.slo = None                     # SloEngine via attach_slo()
        self._scheduler = Scheduler(scheduler, tracer=self.tracer,
                                    telemetry=self.telemetry)
        # ``warmup=True``: every net precompiles its bucket ladder at load
        # time (see ``warmup()``), so no first request ever compile-stalls
        self._warmup_on_load = bool(warmup)
        # stop the dispatcher thread when the Session is garbage-collected,
        # so un-close()d sessions don't leak threads for the process lifetime
        self._finalizer = weakref.finalize(self, Scheduler.close,
                                           self._scheduler)
        if artifacts is not None:
            self.load(artifacts, name=name, backend=backend)

    # -- residency -----------------------------------------------------------
    def load(self, artifacts: Artifacts, name: Optional[str] = None,
             backend: Optional[str] = None, replace: bool = False,
             fallback_backend: Optional[str] = None, fault_plan=None,
             **executor_kw) -> str:
        """Make ``artifacts`` resident under ``name``; returns the name.

        ``fallback_backend`` names a second registered backend (e.g.
        ``"ref"``) built over the same artifacts: when the net's circuit
        breaker opens, traffic routes there with results marked
        ``degraded=True`` instead of shedding.  ``fault_plan`` wraps the
        primary executor in a :class:`repro.runtime.faults.FaultyExecutor`
        (the chaos/test harness's injection point)."""
        name = name or artifacts.graph_name
        backend = backend or self.default_backend
        if name in self._nets and not replace:
            raise ValueError(f"network {name!r} already resident "
                             f"(pass replace=True or a different name)")
        ex = registry.create(backend, artifacts, **executor_kw)
        if fault_plan is not None:
            from repro.runtime.faults import FaultyExecutor
            ex = FaultyExecutor(ex, fault_plan)
        fallback = (registry.create(fallback_backend, artifacts)
                    if fallback_backend else None)
        if name not in self._nets:
            self._order.append(name)
        else:                               # replace=True: retire the old
            self._scheduler.close_net(self._nets[name])  # net's dispatcher
        stats = NetStats(latencies_us=collections.deque(
            maxlen=self._scheduler.config.latency_window))
        dims = getattr(ex, "input_dims", None)
        # a capabilities() failure must be loud at load time — a silent
        # int8 fallback would mis-handle a bf16 net's inputs at serve time
        dtype = ex.capabilities().dtype
        self._nets[name] = _Net(
            name=name, backend=backend, executor=ex, artifacts=artifacts,
            stats=stats, dtype=dtype,
            input_elems=int(np.prod(dims[1:])) if dims is not None else None,
            fallback=fallback, fallback_backend=fallback_backend)
        if self._warmup_on_load:
            self.warmup(name)
        return name

    def warmup(self, net: Optional[str] = None) -> Dict[str, float]:
        """Precompile every (net, bucket) program before traffic arrives.

        For each targeted net (all resident nets when ``net`` is None): one
        zero-input inference at batch 1, plus one ``run_batch`` per rung of
        the scheduler's bucket ladder (``SchedulerConfig.buckets``) on
        natively batching backends — exactly the shapes the dispatcher pads
        to, so the first real request of any bucket shape never pays a
        compile stall.  Sharding mirrors the dispatcher's lane placement so
        warmed programs are the ones that serve.  Call before admitting
        traffic (the serve front-end holds requests until this returns);
        per-net wall time and compile counts land in ``NetStats``.  Returns
        ``{net_name: warmup_ms}``.
        """
        names = [net] if net is not None else list(self._order)
        out: Dict[str, float] = {}
        for nm in names:
            n = self._resolve(nm)
            ex = n.executor
            dims = getattr(ex, "input_dims", None)
            if dims is None:
                continue
            shape = tuple(dims[1:])
            caps = ex.capabilities()
            compiles0 = getattr(ex, "compile_count", 0)
            t0 = time.perf_counter()
            ex.run(np.zeros(shape, np.float32))
            if caps.native_batching:
                for b in self._scheduler.config.buckets:
                    if b <= 1 or (caps.max_batch is not None
                                  and b > caps.max_batch):
                        continue
                    if caps.shardable:
                        ex.batch_sharding = self._scheduler._lane_sharding(b)
                    ex.run_batch(np.zeros((b,) + shape, np.float32), lanes=b)
            ms = (time.perf_counter() - t0) * 1e3
            n.stats.note_warmup(ms, getattr(ex, "compile_count", 0) - compiles0)
            out[nm] = ms
        return out

    def unload(self, name: str) -> None:
        """Drop a resident network; its dispatcher drains and stops."""
        net = self._resolve(name)
        del self._nets[name]
        self._order.remove(name)
        self._scheduler.close_net(net)

    def attach_slo(self, policies, start: bool = False,
                   period_s: float = 5.0):
        """Attach an SLO burn-rate engine (``repro.obs.slo``) over this
        session's telemetry.  ``policies`` is a sequence of ``SloPolicy``
        (e.g. from ``load_policies(path)``).  ``start=True`` runs the
        evaluator on a daemon thread every ``period_s``; either way
        ``/metrics`` and ``/v1/slo`` evaluate on demand.  A policy with
        ``open_circuit_on_breach`` trips the breached net's circuit breaker
        (same downstream behavior as failure-driven opens: fallback routing
        or fast sheds, then a half-open probe).  Returns the engine."""
        from repro.obs.slo import SloEngine
        if self.slo is not None:
            self.slo.close()
        self.slo = SloEngine(policies, self.telemetry, tracer=self.tracer,
                             breaker=self._trip_circuit)
        if start:
            self.slo.start(period_s)
        return self.slo

    def _trip_circuit(self, name: str) -> None:
        net = self._nets.get(name)
        if net is not None:
            self._scheduler.trip_circuit(net)

    def close(self, drain: bool = False) -> None:
        """Stop the per-net dispatcher threads.  ``drain=False`` (default)
        cancels queued requests; ``drain=True`` completes them first.
        Either way every outstanding future is resolved on return."""
        if self.slo is not None:
            self.slo.close()
        self._scheduler.close(drain=drain)

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @classmethod
    def from_bundle(cls, path, backend: str = "baremetal",
                    name: Optional[str] = None) -> "Session":
        """Build a Session straight from a saved bundle — no recompilation."""
        return cls(Artifacts.load(path), backend=backend, name=name)

    # -- lookup --------------------------------------------------------------
    @property
    def networks(self) -> List[str]:
        return list(self._order)

    @property
    def scheduler(self) -> Scheduler:
        return self._scheduler

    def _resolve(self, net: Optional[str]) -> _Net:
        if net is None:
            if not self._order:
                raise ValueError("session has no resident network; "
                                 "load(artifacts) first")
            net = self._order[0]
        try:
            return self._nets[net]
        except KeyError:
            raise KeyError(f"no resident network {net!r}; resident: "
                           f"{', '.join(self._order) or '(none)'}") from None

    def executor(self, net: Optional[str] = None):
        return self._resolve(net).executor

    def artifacts(self, net: Optional[str] = None) -> Artifacts:
        return self._resolve(net).artifacts

    def stats(self, net: Optional[str] = None) -> NetStats:
        return self._resolve(net).stats

    def health(self, net: Optional[str] = None) -> Dict[str, Dict]:
        """Per-net serving health, derived from the circuit breaker.

        ``{name: {"state", "circuit", "fallback"}}`` where ``state`` is
        ``healthy`` (breaker closed), ``degraded`` (breaker not closed but a
        fallback backend is absorbing traffic), or ``circuit_open`` (breaker
        not closed and nothing to fall back to — submits shed with 503).
        ``/healthz`` renders this, returning non-200 unless all healthy."""
        names = [net] if net is not None else list(self._order)
        out: Dict[str, Dict] = {}
        for nm in names:
            n = self._resolve(nm)
            circuit = self._scheduler.circuit_state(n)
            if circuit == "closed":
                state = "healthy"
            elif n.fallback is not None:
                state = "degraded"
            else:
                state = "circuit_open"
            out[nm] = {"state": state, "circuit": circuit,
                       "fallback": n.fallback_backend}
        return out

    def queue_depth(self, net: Optional[str] = None) -> int:
        """Requests currently queued (not in-flight) — one net's, or every
        resident net's summed when ``net`` is None."""
        return self._scheduler.queue_depth(
            self._resolve(net) if net is not None else None)

    # -- serving -------------------------------------------------------------
    def _check_input(self, n: _Net, x) -> np.ndarray:
        """Fail fast on malformed inputs so one bad submit can never poison
        the futures of well-formed requests coalesced into the same batch,
        and canonicalise shape/dtype so every lane of a coalesced batch
        stacks cleanly: flat, and either int8 (pre-quantised, passed
        through) or float32 (converted by the backend).  The scheduler never
        coalesces int8 with float32 lanes.

        A bf16 (nv_full) net has no pre-quantised int8 notion — every input
        is canonicalised to float32, so all of a bf16 net's lanes share one
        dtype and its batches form their own buckets (a launch never mixes
        engine dtypes; each dispatcher serves exactly one net/config)."""
        x = np.asarray(x)
        want = n.input_elems
        if want is not None and (x.dtype == object or x.size != want):
            raise ValueError(
                f"bad input for network {n.name!r}: got dtype={x.dtype} "
                f"size={x.size}, expected {want} elements")
        if want is not None:
            if x.dtype != np.int8 or n.dtype != "int8":
                x = x.astype(np.float32, copy=False)
            x = x.reshape(-1)
        return x

    def submit(self, x: np.ndarray, net: Optional[str] = None,
               priority: int = 0,
               deadline_us: Optional[float] = None,
               trace_id: Optional[str] = None) -> "Future[ExecResult]":
        """Enqueue one inference; returns a Future resolving to its
        ``ExecResult``.  Concurrent submits against the same network coalesce
        into one padded vmapped batch (bit-exact vs sequential ``run``).

        ``priority`` (higher = more urgent) and ``deadline_us`` (relative
        latency budget) feed the net's SLA-aware queue: urgent-first,
        earliest-deadline within a class; a request still queued past its
        deadline is shed (its future raises ``DeadlineExceededError``), and
        a queue at ``SchedulerConfig.max_queue`` rejects the submit outright
        with ``QueueFullError``.

        The returned future carries ``fut.trace_id``; passing ``trace_id``
        (a client-supplied ``X-Repro-Trace-Id``) forces the request into
        the tracer's sampled set.
        """
        n = self._resolve(net)
        return self._scheduler.submit(n, self._check_input(n, x),
                                      priority=priority,
                                      deadline_us=deadline_us,
                                      trace_id=trace_id)

    def run(self, x: np.ndarray, net: Optional[str] = None) -> ExecResult:
        """One inference on one input image (synchronous ``submit``)."""
        n = self._resolve(net)
        fut = self._scheduler.submit(n, self._check_input(n, x))
        n.stats.note_call()
        return fut.result()

    def run_batch(self, X: np.ndarray, net: Optional[str] = None) -> ExecResult:
        """Batched inference over ``X`` of shape ``(N, ...)``.

        Thin wrapper over N ``submit`` calls: the scheduler coalesces them
        (together with any other pending requests) into padded vmapped batch
        programs.  Bit-exact (INT8) against N sequential ``run`` calls.
        """
        X = np.asarray(X)
        n = self._resolve(net)
        futs = self._scheduler.submit_many(
            n, [self._check_input(n, x) for x in X])
        n.stats.note_call(int(X.shape[0]), batch=True)
        outs = [f.result() for f in futs]
        return ExecResult(output_int8=np.stack([o.output_int8 for o in outs]),
                          output=np.stack([o.output for o in outs]))
