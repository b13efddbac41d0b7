"""Request tracing: ids, lifecycle spans, launch phases, Chrome export.

One ``Tracer`` lives on each ``Session`` and is threaded through the
scheduler and the HTTP front-end.  Every submitted request gets a trace id
(accepted/emitted over HTTP as the ``X-Repro-Trace-Id`` header); a
deterministic every-Nth-request sampler (``TraceConfig.sample_rate``)
decides which requests additionally record a ``RequestTrace`` — monotonic
``time.perf_counter`` spans for queue-wait, coalesce/hold, pad, launch,
device-execute, retry backoff, plus instant events for the fault paths
(shed, watchdog fire, arena reset, circuit transitions).  Inside
``device_execute`` the executor marks the phases of the blocking call
(``quantise``, ``h2d``, ``enqueue``, ``device_wait``, ``d2h``) into a
per-thread ``LaunchPhases`` collector that the scheduler sets only for a
launch holding a traced request; a launch split into an enqueue and a
later wait marks both halves into one collector.  A request whose
id was supplied by the client is ALWAYS traced, so a caller can opt a
specific request into tracing regardless of the sampler.

Completed traces land in a bounded ring buffer and export as Chrome
trace-event JSON (``chrome://tracing`` / Perfetto "JSON Object Format"):
one pid, one tid per trace, ``ph:"X"`` complete events for spans (ts/dur in
microseconds relative to the tracer's epoch) and ``ph:"i"`` instants for
events.  The tracer also aggregates per-(net, phase) latency histograms
that ``/metrics`` renders in Prometheus histogram format.

Everything here is stdlib-only and lock-light: the per-request hot path is
a handful of ``perf_counter`` calls and list appends on the (GIL-atomic)
span list; the tracer lock guards only the sampler counters, the ring
buffer, and the histogram bins.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

TRACE_HEADER = "X-Repro-Trace-Id"

# phase-histogram bucket upper bounds in microseconds (Prometheus ``le``);
# +Inf is implicit as the final bucket
PHASE_BUCKETS_US: Tuple[float, ...] = (
    50.0, 100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0,
    25000.0, 50000.0, 100000.0, 250000.0, 1000000.0)

_ID_ALPHABET = "0123456789abcdef"


def new_trace_id() -> str:
    """16 hex chars of OS randomness — compact, log-greppable, collision-safe
    at any realistic request volume."""
    return os.urandom(8).hex()


def valid_trace_id(tid: str) -> bool:
    """Accept client-supplied ids that are sane header tokens: 1-64 chars of
    [A-Za-z0-9._-] (W3C traceparent ids and uuids both pass)."""
    if not tid or len(tid) > 64:
        return False
    return all(c.isalnum() or c in "._-" for c in tid)


# span+event cap per trace (runaway guard)
_MAX_EVENTS = 512


@dataclasses.dataclass(frozen=True)
class TraceConfig:
    """Tracing knobs (``Session(trace=...)``, ``--trace-sample``).

    ``sample_rate=N`` traces every Nth request per net (1 = all, 0 = only
    requests that arrive with a client-supplied trace id).
    ``enabled=False`` turns the subsystem off entirely: ids are still
    assigned (the HTTP contract keeps holding) but nothing is recorded.
    """
    enabled: bool = True
    sample_rate: int = 1
    capacity: int = 256            # completed-trace ring buffer length

    def __post_init__(self):
        if self.sample_rate < 0:
            raise ValueError(f"sample_rate must be >= 0, got "
                             f"{self.sample_rate}")
        if self.capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {self.capacity}")


@dataclasses.dataclass
class Span:
    name: str
    t0: float                      # time.perf_counter seconds
    t1: float
    args: Dict = dataclasses.field(default_factory=dict)


class LaunchPhases:
    """The phase spans of one launch, marked by the executor on the thread
    that runs it.

    Phases tile the call: ``start`` opens the first phase, each ``mark``
    closes the open phase and opens the next, and ``close`` ends the last
    one when the call has returned.  Every span carries ``launch``, the
    dispatcher's launch number; a host step also carries ``cpu_s``, this
    thread's CPU seconds over the span (``time.thread_time``), so its wall
    time less ``cpu_s`` is time spent off the CPU, waiting for the GIL.
    The CPU clock is read inside the wall-clock bounds, so ``cpu_s`` does
    not exceed the span where that clock is precise; where it advances in
    scheduler ticks (10 ms on some virtual machines) one span's ``cpu_s``
    is a sample, and only sums over many spans are meaningful."""

    __slots__ = ("launch", "spans", "_t", "_cpu")

    def __init__(self, launch: int):
        self.launch = launch
        self.spans: List[Span] = []
        self._t = self._cpu = 0.0

    def start(self) -> float:
        """Open the first phase; returns its start."""
        self._t = time.perf_counter()
        self._cpu = time.thread_time()
        return self._t

    def mark(self, name: str, host: bool = True) -> None:
        """End the open phase as ``name`` and open the next."""
        cpu = time.thread_time()
        t = time.perf_counter()
        args = {"launch": self.launch}
        if host:
            args["cpu_s"] = cpu - self._cpu
        self.spans.append(Span(name, self._t, t, args))
        self._t = t
        self._cpu = time.thread_time()

    def close(self) -> float:
        """Extend the last phase to now, the call's return; returns now."""
        cpu = time.thread_time()
        t = time.perf_counter()
        if self.spans:
            last = self.spans[-1]
            last.t1 = t
            if "cpu_s" in last.args:
                last.args["cpu_s"] += cpu - self._cpu
        return t


class _NoPhases:
    """The collector of an untraced launch: keeps the call's bounds and
    records nothing."""

    __slots__ = ()
    spans = ()

    def start(self) -> float:
        return time.perf_counter()

    def mark(self, name: str, host: bool = True) -> None:
        pass

    def close(self) -> float:
        return time.perf_counter()


_NO_PHASES = _NoPhases()
_thread = threading.local()


def launch_phases():
    """The calling thread's collector for the launch it runs: a
    ``LaunchPhases`` inside ``collect_launch``, else one that records
    nothing."""
    return getattr(_thread, "phases", _NO_PHASES)


@contextlib.contextmanager
def collect_launch(launch: Optional[int]):
    """Collect, into the yielded ``LaunchPhases``, the phases that code on
    this thread marks inside the block; ``launch=None`` (an untraced
    launch) yields the collector that records nothing."""
    with resume_launch(_NO_PHASES if launch is None
                       else LaunchPhases(launch)) as phases:
        yield phases


@contextlib.contextmanager
def resume_launch(phases):
    """Collect into ``phases``, a collector ``collect_launch`` yielded, the
    phases that code on this thread marks inside the block: the second
    half of a launch split over two calls, perhaps on another thread, goes
    on where the first left off."""
    _thread.phases = phases
    try:
        yield phases
    finally:
        _thread.phases = _NO_PHASES


class RequestTrace:
    """Recorded lifecycle of ONE sampled request.

    Mutated from the submitting thread, the dispatcher thread and the
    launcher worker — appends to the span/event lists are GIL-atomic, and
    ``Tracer.finish`` is the only cross-thread ordering point (idempotent
    under the tracer lock, so the fault paths can't double-complete it).
    """

    __slots__ = ("trace_id", "net", "t_start", "t_end", "status", "error",
                 "spans", "events", "finished")

    def __init__(self, trace_id: str, net: str,
                 t_start: Optional[float] = None):
        self.trace_id = trace_id
        self.net = net
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.t_end = 0.0
        self.status = "pending"
        self.error = ""
        self.spans: List[Span] = []
        self.events: List[Tuple[str, float, Dict]] = []
        self.finished = False

    def add_span(self, name: str, t0: float, t1: float, **args) -> None:
        if len(self.spans) < _MAX_EVENTS and t1 >= t0:
            self.spans.append(Span(name, t0, t1, args))

    def add_spans(self, spans: List[Span]) -> None:
        """Attach spans recorded elsewhere (a launch's phases), shared with
        the other traced requests of the same launch."""
        self.spans.extend(spans[:_MAX_EVENTS - len(self.spans)])

    def event(self, name: str, t: Optional[float] = None, **args) -> None:
        if len(self.events) < _MAX_EVENTS:
            self.events.append((name, time.perf_counter() if t is None
                                else t, args))

    @property
    def duration_us(self) -> float:
        end = self.t_end or time.perf_counter()
        return (end - self.t_start) * 1e6

    def phase_us(self) -> Dict[str, float]:
        """Summed span duration per phase name, plus end-to-end ``total``."""
        out: Dict[str, float] = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + (s.t1 - s.t0) * 1e6
        if self.t_end:
            out["total"] = (self.t_end - self.t_start) * 1e6
        return out


# future-outcome exception name -> trace status (name-based so this module
# never imports the runtime layer: no circular imports, and stub errors in
# tests map the same way)
_STATUS_BY_EXC = {
    "DeadlineExceededError": "shed",
    "QueueFullError": "rejected",
    "CircuitOpenError": "rejected",
    "CancelledError": "cancelled",
}


def status_for_exception(exc: BaseException) -> str:
    """Terminal trace status for a request that failed with ``exc``."""
    return _STATUS_BY_EXC.get(type(exc).__name__, "error")


class Tracer:
    """Session-wide trace collector: sampler, ring buffer, histograms."""

    def __init__(self, config: Optional[TraceConfig] = None):
        self.config = config or TraceConfig()
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}     # per-net submit counter
        self._store: List[RequestTrace] = []    # ring, newest last
        self._hist: Dict[Tuple[str, str], List] = {}  # (net,phase)->[bins,sum,n]
        self._global_events: List[Tuple[str, float, Dict]] = []
        self.epoch = time.perf_counter()        # ts=0 of the Chrome export
        self.dropped = 0                        # traces evicted from the ring

    # -- lifecycle ----------------------------------------------------------
    def start(self, net: str, trace_id: Optional[str] = None,
              t_start: Optional[float] = None) -> Tuple[str,
                                                        Optional[RequestTrace]]:
        """Admit one request: always returns its (possibly fresh) trace id,
        plus a ``RequestTrace`` when the sampler (or a client-supplied id)
        selects it for recording.  ``t_start`` pins the trace window to the
        caller's submit timestamp so the queue span nests inside it."""
        cfg = self.config
        forced = trace_id is not None
        tid = trace_id if forced else new_trace_id()
        if not cfg.enabled:
            return tid, None
        with self._lock:
            n = self._counters.get(net, 0)
            self._counters[net] = n + 1
        sampled = cfg.sample_rate > 0 and n % cfg.sample_rate == 0
        if not (sampled or forced):
            return tid, None
        return tid, RequestTrace(tid, net, t_start=t_start)

    def finish(self, trace: Optional[RequestTrace], status: str = "ok",
               error: str = "") -> None:
        """Complete a trace exactly once (idempotent; later calls no-op)."""
        if trace is None:
            return
        with self._lock:
            if trace.finished:
                return
            trace.finished = True
        trace.t_end = time.perf_counter()
        trace.status = status
        trace.error = error
        trace.add_span("request", trace.t_start, trace.t_end,
                       status=status, **({"error": error} if error else {}))
        with self._lock:
            self._store.append(trace)
            if len(self._store) > self.config.capacity:
                self.dropped += len(self._store) - self.config.capacity
                del self._store[:len(self._store) - self.config.capacity]
            for phase, us in trace.phase_us().items():
                key = (trace.net, phase)
                h = self._hist.get(key)
                if h is None:
                    h = self._hist[key] = [[0] * (len(PHASE_BUCKETS_US) + 1),
                                           0.0, 0]
                bins, _, _ = h
                i = 0
                while i < len(PHASE_BUCKETS_US) and us > PHASE_BUCKETS_US[i]:
                    i += 1
                bins[i] += 1
                h[1] += us
                h[2] += 1

    def finish_future(self, trace: RequestTrace, fut) -> None:
        """``Future.add_done_callback`` hook: derive the terminal status from
        the future's outcome — ok / degraded / shed / rejected / cancelled /
        error — so every admitted request completes its trace exactly once
        no matter which path (success, retry-exhaustion, shed, close)
        resolved it."""
        try:
            if fut.cancelled():
                self.finish(trace, status="cancelled")
                return
            exc = fut.exception()
            if exc is None:
                res = fut.result()
                degraded = bool(getattr(res, "degraded", False))
                self.finish(trace, status="degraded" if degraded else "ok")
            else:
                self.finish(trace, status=status_for_exception(exc),
                            error=type(exc).__name__)
        except Exception:                       # pragma: no cover - paranoia
            self.finish(trace, status="error", error="finish_future")

    # -- fault-plane events -------------------------------------------------
    def note_global(self, name: str, **args) -> None:
        """Record a session-wide instant event (not tied to any single
        request's trace): circuit transitions, SLO burn alerts.  Rendered as
        a process-scoped instant in the Chrome export."""
        if not self.config.enabled:
            return
        with self._lock:
            self._global_events.append((name, time.perf_counter(), args))
            del self._global_events[:-256]

    def note_circuit(self, net: str, state: str) -> None:
        """Record a circuit-breaker transition."""
        self.note_global("circuit_" + state, net=net)

    def global_events(self) -> List[Tuple[str, float, Dict]]:
        with self._lock:
            return list(self._global_events)

    # -- export -------------------------------------------------------------
    def traces(self, limit: Optional[int] = None) -> List[RequestTrace]:
        with self._lock:
            out = list(self._store)
        return out[-limit:] if limit else out

    def phase_histograms(self) -> Dict[Tuple[str, str], Dict]:
        """{(net, phase): {"buckets": [(le, cumulative_count)...], "sum",
        "count"}} with the +Inf bucket last — Prometheus histogram shape."""
        with self._lock:
            snap = {k: ([list(v[0])], v[1], v[2]) for k, v in
                    self._hist.items()}
        out = {}
        for key, (bins_w, total, count) in snap.items():
            bins = bins_w[0]
            cum, buckets = 0, []
            for le, n in zip(PHASE_BUCKETS_US + (float("inf"),), bins):
                cum += n
                buckets.append((le, cum))
            out[key] = {"buckets": buckets, "sum": total, "count": count}
        return out

    def _rel_us(self, t: float) -> float:
        return (t - self.epoch) * 1e6

    def chrome_trace(self, limit: Optional[int] = None) -> Dict:
        """Chrome trace-event JSON ("JSON Object Format"): load the result of
        ``json.dumps`` straight into chrome://tracing or ui.perfetto.dev."""
        events: List[Dict] = [
            {"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
             "args": {"name": "repro.serve"}},
            {"ph": "M", "pid": 1, "tid": 0, "name": "thread_name",
             "args": {"name": "scheduler"}},
        ]
        with self._lock:
            glob = list(self._global_events)
        for name, t, args in glob:
            events.append({"ph": "i", "pid": 1, "tid": 0, "name": name,
                           "s": "p", "ts": self._rel_us(t), "args": args})
        for i, tr in enumerate(self.traces(limit)):
            tid = i + 1
            events.append({"ph": "M", "pid": 1, "tid": tid,
                           "name": "thread_name",
                           "args": {"name": f"{tr.net} {tr.trace_id}"}})
            for s in tr.spans:
                events.append({
                    "ph": "X", "pid": 1, "tid": tid, "cat": "request",
                    "name": s.name, "ts": self._rel_us(s.t0),
                    "dur": max((s.t1 - s.t0) * 1e6, 0.001),
                    "args": dict(s.args, trace_id=tr.trace_id)})
            for name, t, args in tr.events:
                events.append({"ph": "i", "pid": 1, "tid": tid, "s": "t",
                               "cat": "request", "name": name,
                               "ts": self._rel_us(t),
                               "args": dict(args, trace_id=tr.trace_id)})
        events.sort(key=lambda e: e.get("ts", 0.0))
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"source": "repro.obs", "dropped": self.dropped}}

    def to_file(self, path) -> None:
        import json
        import pathlib
        p = pathlib.Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(self.chrome_trace(), indent=1))
