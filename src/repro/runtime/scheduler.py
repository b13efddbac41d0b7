"""Async serving scheduler: per-net dispatchers + SLA-aware micro-batching.

``Session.submit(x)`` enqueues one inference request and returns a
``concurrent.futures.Future``.  Every resident network gets its **own
dispatcher thread and queue** (a slow ResNet batch can never head-of-line
block LeNet traffic); each dispatcher drains its queue, coalesces compatible
requests into one batch, pads it to a power-of-two bucket (so each batch
shape compiles exactly once), executes it through the backend's
``run_batch(padded, lanes)``, and resolves each future with its lane's
``ExecResult`` — bit-exact versus running every request through sequential
``run`` calls, because the batch program itself is bit-exact and padding
lanes are sliced off before anyone sees them.

**SLA-aware ordering.**  Requests carry ``priority`` (higher = more urgent)
and an optional ``deadline_us`` latency budget.  The queue is a heap ordered
by ``(-priority, deadline, arrival)``: urgent traffic launches first, and
within a priority class the tightest deadline wins (EDF).  A request whose
deadline has already passed when the dispatcher would launch it is **shed**
— its future fails fast with :class:`DeadlineExceededError` instead of
burning a batch slot on an answer nobody wants.

**Continuous batching.**  The collector holds a forming batch open (up to
``max_wait_us``) and admits late-arriving compatible requests right up to
launch; after the hold it re-reads the queue head, so a high-priority
arrival during the hold window leads the very next dispatch.

**Admission control.**  ``SchedulerConfig.max_queue`` bounds each net's
queue; past it, ``submit`` fails fast with :class:`QueueFullError` (the HTTP
front-end maps it to 429) instead of growing the queue without bound.

Micro-batching is *adaptive*: each dispatcher tracks an EMA of recent
coalesce sizes.  Under solo traffic (EMA ~ 1) it dispatches immediately —
waiting would only add latency; once concurrency is observed it holds the
head request up to ``max_wait_us`` to let the batch fill towards
``max_batch``.

**Two-deep launch pipeline.**  With a backend whose launch comes in two
halves (``capabilities().split_launch``: enqueue, then wait and fetch), the
dispatcher enqueues the next launch behind the one on the device whenever
a full batch is already queued, and only then waits for the first and
answers it: padding, quantising, transferring and enqueueing batch N+1 run
while batch N runs.  At most two launches are in flight.  Without a full
batch queued, or with a backend that launches whole, each launch is waited
for before the next batch is collected, hold included.  If a launch fails
with another enqueued behind it, the one behind is dropped unseen and
launched again once the failed one has gone through its retries.

When several devices are visible and the backend reports
``capabilities().shardable``, a coalesced batch whose bucket divides the
device count is dispatched with its lane axis sharded over a 1-axis data
mesh (``repro.distributed.sharding.serving_mesh``); GSPMD splits the vmapped
program across devices and replicates the resident weight arena.

Padding and lane masking live HERE, not in executors: backends receive an
already-padded batch plus the live-lane count and stay policy-free.
"""

from __future__ import annotations

import dataclasses
import functools
import heapq
import itertools
import math
import queue
import random
import threading
import time
from concurrent.futures import Future, InvalidStateError
from typing import Dict, List, Optional

import numpy as np

from repro.core import perfmodel
from repro.core.executor import ExecResult
from repro.obs.trace import collect_launch, resume_launch, \
    status_for_exception

# EMA of coalesce sizes above which a dispatcher starts holding the head
# request for stragglers (below it, traffic is effectively solo).
_COALESCE_THRESHOLD = 1.25
_EMA_ALPHA = 0.2


class QueueFullError(RuntimeError):
    """Admission control: the target net's queue is at ``max_queue``.

    Raised synchronously by ``submit`` — the request was never enqueued.
    The HTTP front-end maps this to ``429 Too Many Requests``.
    """

    def __init__(self, net_name: str, depth: int, bound: int):
        super().__init__(
            f"queue for network {net_name!r} is full "
            f"({depth}/{bound} queued); retry later or raise "
            f"SchedulerConfig.max_queue")
        self.net_name, self.depth, self.bound = net_name, depth, bound


class DeadlineExceededError(RuntimeError):
    """The request's ``deadline_us`` budget elapsed before launch; it was
    shed by the collector and never executed.  Delivered through the
    request's future."""

    def __init__(self, net_name: str, deadline_us: float, waited_us: float):
        super().__init__(
            f"request for network {net_name!r} shed: deadline_us="
            f"{deadline_us:.0f} elapsed after {waited_us:.0f}us in queue")
        self.net_name = net_name
        self.deadline_us, self.waited_us = deadline_us, waited_us


class LaunchTimeoutError(RuntimeError):
    """A supervised launch exceeded its watchdog timeout and was abandoned
    (the backend call may still be blocked on an orphaned worker thread).
    Retried like any other launch failure; surfaces to futures only inside
    a :class:`BackendFaultError` once retries are exhausted."""

    def __init__(self, net_name: str, timeout_s: float):
        super().__init__(
            f"launch for network {net_name!r} exceeded its watchdog "
            f"timeout ({timeout_s:.3f}s) and was abandoned")
        self.net_name, self.timeout_s = net_name, timeout_s


class BackendFaultError(RuntimeError):
    """The dispatcher exhausted its retry budget for one batch: every
    attempt raised or timed out.  Delivered through each affected request's
    future (never a hang); ``cause`` (also ``__cause__``) carries the last
    attempt's causal exception.  The HTTP front-end maps this to 500."""

    def __init__(self, net_name: str, attempts: int, cause: BaseException):
        super().__init__(
            f"backend for network {net_name!r} failed {attempts} "
            f"launch attempt(s); last: {type(cause).__name__}: {cause}")
        self.net_name, self.attempts, self.cause = net_name, attempts, cause


class CircuitOpenError(RuntimeError):
    """Admission refused: the net's circuit breaker is open (N consecutive
    launch failures) and no fallback backend is configured.  Raised
    synchronously by ``submit`` — the request was never enqueued.  The HTTP
    front-end maps this to 503 with a ``Retry-After`` of ``retry_after_s``
    (the time left until the breaker's half-open probe)."""

    def __init__(self, net_name: str, retry_after_s: float):
        super().__init__(
            f"circuit for network {net_name!r} is open after repeated "
            f"backend failures; retry in {retry_after_s:.2f}s")
        self.net_name, self.retry_after_s = net_name, retry_after_s


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Micro-batching + SLA knobs (per-net dispatchers all share one config).

    ``max_batch``    — coalescing ceiling per dispatch.
    ``max_wait_us``  — longest the head request is held for stragglers.
    ``adaptive``     — skip the wait entirely while traffic is solo
                       (EMA of coalesce sizes stays ~1).
    ``shard``        — shard coalesced batches lane-wise across devices when
                       the backend is shardable and >1 device is visible.
    ``max_queue``    — per-net queue bound; ``submit`` past it raises
                       ``QueueFullError`` (None = unbounded, the pre-serving
                       behaviour).
    ``buckets``      — the batch-shape ladder: every coalesced dispatch pads
                       to the smallest rung >= its size, and ``Session``
                       warmup precompiles exactly these shapes.  Defaults to
                       ``perfmodel.bucket_ladder(max_batch)`` (powers of two
                       up to ``max_batch``).  This is the ONE source of truth
                       for batch shapes — mis-shaped ladders (non-monotonic,
                       rungs past ``max_batch``, non-power-of-two rungs while
                       ``adaptive``) fail here at construction, not deep in
                       the dispatcher.
    ``latency_window`` — ring-buffer size for per-request latency samples.
    ``close_timeout_s`` — the no-progress window ``close()`` allows before
                       force-cancelling outstanding futures: as long as the
                       dispatcher keeps completing work the wait continues
                       (a slow drain is not a hang), but a window in which
                       nothing completes means a hung backend — and a hung
                       backend must never leave a caller blocked on
                       ``result()``.

    Fault-tolerance knobs (the supervisor around every launch):

    ``max_retries``  — failed/timed-out launches are retried up to this many
                       times (inputs are still held, so a retry is idempotent
                       by construction); past it the batch's futures resolve
                       with ``BackendFaultError``.
    ``retry_backoff_s`` — base of the exponential backoff between retries
                       (doubles per attempt, with deterministic ±20% jitter).
    ``watchdog_timeout_s`` — absolute per-launch watchdog timeout; ``None``
                       derives it from the cost model instead:
                       ``max(watchdog_floor_s, predicted_batch_ms/1000 *
                       watchdog_mult)``.  The floor is generous because a
                       cold bucket's first launch pays an XLA compile that
                       dwarfs any modeled execution time.
    ``watchdog_mult`` / ``watchdog_floor_s`` — see above.
    ``breaker_threshold`` — consecutive failed launch attempts that trip the
                       net's circuit breaker open (``None`` disables the
                       breaker).  While open, submits fail fast with
                       ``CircuitOpenError`` (HTTP 503 + Retry-After) unless
                       a fallback backend serves degraded traffic.
    ``breaker_reset_s`` — how long the breaker stays open before the next
                       launch runs as a half-open probe of the primary;
                       a successful probe closes the breaker.
    """
    max_batch: int = 8
    max_wait_us: float = 200.0
    adaptive: bool = True
    shard: bool = True
    max_queue: Optional[int] = None
    buckets: Optional[tuple] = None
    latency_window: int = 2048
    close_timeout_s: float = 30.0
    max_retries: int = 2
    retry_backoff_s: float = 0.01
    watchdog_timeout_s: Optional[float] = None
    watchdog_mult: float = 50.0
    watchdog_floor_s: float = 30.0
    breaker_threshold: Optional[int] = 5
    breaker_reset_s: float = 5.0

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError(
                f"SchedulerConfig.max_batch must be >= 1, got {self.max_batch}")
        if self.max_retries < 0:
            raise ValueError(f"SchedulerConfig.max_retries must be >= 0, "
                             f"got {self.max_retries}")
        if self.retry_backoff_s < 0:
            raise ValueError(f"SchedulerConfig.retry_backoff_s must be >= 0, "
                             f"got {self.retry_backoff_s}")
        if self.watchdog_timeout_s is not None and self.watchdog_timeout_s <= 0:
            raise ValueError(f"SchedulerConfig.watchdog_timeout_s must be "
                             f"> 0 or None, got {self.watchdog_timeout_s}")
        if self.watchdog_floor_s <= 0:
            raise ValueError(f"SchedulerConfig.watchdog_floor_s must be > 0, "
                             f"got {self.watchdog_floor_s}")
        if self.breaker_threshold is not None and self.breaker_threshold < 1:
            raise ValueError(f"SchedulerConfig.breaker_threshold must be "
                             f">= 1 or None, got {self.breaker_threshold}")
        if self.breaker_reset_s <= 0:
            raise ValueError(f"SchedulerConfig.breaker_reset_s must be > 0, "
                             f"got {self.breaker_reset_s}")
        if self.buckets is None:
            object.__setattr__(self, "buckets",
                               perfmodel.bucket_ladder(self.max_batch))
            return
        try:
            bs = tuple(int(b) for b in self.buckets)
        except (TypeError, ValueError):
            raise ValueError(
                f"SchedulerConfig.buckets must be a sequence of ints, got "
                f"{self.buckets!r}") from None
        if not bs or any(b < 1 for b in bs):
            raise ValueError(
                f"SchedulerConfig.buckets must be a non-empty sequence of "
                f"positive batch sizes, got {self.buckets!r}")
        if any(b >= b2 for b, b2 in zip(bs, bs[1:])):
            raise ValueError(
                f"SchedulerConfig.buckets must be strictly increasing "
                f"(each dispatch pads to the smallest rung >= its size), "
                f"got {bs}")
        if bs[-1] > self.max_batch:
            raise ValueError(
                f"SchedulerConfig.buckets rung {bs[-1]} exceeds "
                f"max_batch={self.max_batch} — the dispatcher would pad past "
                f"its own coalescing ceiling")
        if self.adaptive:
            bad = [b for b in bs if b & (b - 1)]
            if bad:
                raise ValueError(
                    f"SchedulerConfig.buckets rungs {bad} are not powers of "
                    f"two; adaptive coalescing assumes the power-of-two "
                    f"compile-once grid (set adaptive=False to use custom "
                    f"rungs)")
        object.__setattr__(self, "buckets", bs)

    def bucket_for(self, n: int) -> int:
        """Smallest ladder rung >= n.  Oversize pre-formed groups (past
        ``max_batch``) still round up to a power of two so batch shapes stay
        drawn from a bounded set."""
        for b in self.buckets:
            if n <= b:
                return b
        return bucket_size(n, self.max_batch)


@dataclasses.dataclass
class _Request:
    net: object                  # the Session's _Net record
    x: np.ndarray
    future: Future
    t_submit: float
    priority: int = 0            # higher = more urgent
    deadline: float = math.inf   # absolute perf_counter() launch deadline
    deadline_us: float = 0.0     # the caller's relative budget (for errors)
    seq: int = 0                 # arrival order (heap tiebreak, FIFO w/in class)
    group_n: int = 1             # size of the submit_many group this came in
                                 # with: a pre-formed batch may exceed
                                 # max_batch and still dispatch as one program
    trace: object = None         # RequestTrace when sampled, else None

    def sort_key(self):
        return (-self.priority, self.deadline, self.seq)


def bucket_size(n: int, max_batch: int) -> int:
    """Smallest power of two >= n, capped at max_batch for coalesced traffic
    (compile-once shapes); oversize pre-formed groups still round up to a
    power of two so batch shapes stay drawn from a bounded set."""
    b = 1
    while b < n:
        b *= 2
    return min(b, max_batch) if n <= max_batch else b


def _resolve_future(future: Future, set_fn, value) -> None:
    """set_result/set_exception tolerant of a concurrent ``cancel()`` from
    ``close()`` — losing that race must not kill the dispatcher thread."""
    if future.cancelled():
        return
    try:
        set_fn(value)
    except InvalidStateError:
        pass                                # cancelled between check and set


@dataclasses.dataclass
class _Launch:
    """One launch attempt of a batch, from its pad to its answers.  With a
    split executor it is in flight between its two halves: enqueued on the
    device (``handle``) and not yet waited for."""
    batch: List[_Request]
    ex: object
    degraded: bool
    attempt: int
    number: int = 0              # the dispatcher's launch number
    ahead: int = 0               # launches in flight when it was enqueued
    bucket: int = 1
    x: object = None             # one image, or the padded batch
    lanes: Optional[int] = None  # live lanes of a batch; None: one image
    compiles: int = 0            # program builds its call made
    handle: object = None        # the executor's ``Launched``
    phases: object = None        # its phase collector
    t0: float = 0.0              # start of its ``device_execute`` span
    exc: Optional[BaseException] = None   # its submit step raised

    @property
    def traced(self) -> List[_Request]:
        return [r for r in self.batch if r.trace is not None]


def pad_batch(xs: List[np.ndarray], bucket: int) -> np.ndarray:
    """Stack request inputs into a (bucket, ...) batch, zero-padding the tail
    lanes.  Padding changes no live lane's bytes — the batch program is
    lane-independent — so results stay bit-exact."""
    X = np.stack([np.asarray(x) for x in xs])
    if X.shape[0] < bucket:
        pad = np.zeros((bucket - X.shape[0],) + X.shape[1:], X.dtype)
        X = np.concatenate([X, pad])
    return X


class _Launcher:
    """Watchdog-supervised executor calls for one dispatcher.

    A persistent worker thread executes launches so the dispatcher can
    *abandon* one that hangs: ``call`` hands the closure to the worker and
    waits up to ``timeout_s``; past it, the worker is orphaned (it may still
    be blocked inside the backend — a sentinel tells it to exit if it ever
    unblocks) and the next call spawns a fresh worker.  One persistent
    thread, not one per dispatch, so the steady-state cost is a queue
    hand-off + event wait, not thread creation."""

    def __init__(self, name: str):
        self.name = name
        self._q: Optional[queue.SimpleQueue] = None
        self._thread: Optional[threading.Thread] = None

    def call(self, fn, timeout_s: float):
        if self._thread is None or not self._thread.is_alive():
            self._spawn()
        done = threading.Event()
        box: dict = {}
        self._q.put((fn, box, done))
        if not done.wait(timeout_s):
            self._q.put(None)        # exit-if-you-ever-unblock sentinel
            self._thread = None      # abandon; next call gets a fresh worker
            raise LaunchTimeoutError(self.name, timeout_s)
        if "exc" in box:
            raise box["exc"]
        return box["res"]

    def _spawn(self) -> None:
        self._q = q = queue.SimpleQueue()

        def loop():
            while True:
                job = q.get()
                if job is None:
                    return
                fn, box, done = job
                try:
                    box["res"] = fn()
                except BaseException as e:   # noqa: BLE001 — relayed to caller
                    box["exc"] = e
                done.set()

        self._thread = threading.Thread(target=loop,
                                        name=f"repro-exec-{self.name}",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        if self._thread is not None:
            self._q.put(None)
            self._thread = None


# circuit-breaker states (per net, owned by its dispatcher)
_CLOSED, _OPEN, _HALF_OPEN = "closed", "open", "half_open"


class _NetDispatcher:
    """One resident network's queue + dispatcher thread.

    The heap orders requests by ``(-priority, deadline, seq)``; the collector
    sheds expired-deadline requests at launch-selection time and admits
    late arrivals into the forming batch until it actually launches.

    Every launch is supervised (``_Launcher`` watchdog + retry with
    exponential backoff), the arena is integrity-checked after failures, and
    a per-net circuit breaker (closed -> open after ``breaker_threshold``
    consecutive failed attempts -> half-open probe after ``breaker_reset_s``)
    sheds fast or routes to the net's fallback executor while open.
    """

    def __init__(self, net, config: SchedulerConfig, scheduler: "Scheduler"):
        self.net = net
        self.config = config
        self.scheduler = scheduler
        # plain Lock (not the default RLock): the condition is hot on submit
        self._cond = threading.Condition(threading.Lock())
        self._heap: List[tuple] = []         # (sort_key, _Request)
        self._thread: Optional[threading.Thread] = None
        self._stop = False                   # exit now, cancel queued
        self._drain = False                  # exit once the queue empties
        self._inflight: List[_Request] = []  # batches launched, unanswered
        self._ema_coalesce = 1.0
        name = getattr(net, "name", "?")
        self._launcher = _Launcher(name)
        self._breaker = _CLOSED              # guarded by _cond
        self._consec_failures = 0
        self._opened_at = 0.0
        self._retry_rng = random.Random(f"repro-retry-{name}")
        self._model_ms: Optional[float] = None   # cost-model batch-1 ms
        self._model_ms_known = False
        self._launches = 0                   # launch attempts, numbered

    def _tel_record(self, latency_us: float, status: str,
                    good: Optional[bool] = None) -> None:
        """Feed the windowed telemetry (every request, unlike the tracer's
        sampled subset).  The telemetry lock is a leaf: safe under _cond."""
        tel = getattr(self.scheduler, "telemetry", None)
        if tel is not None:
            tel.record(getattr(self.net, "name", "?"), latency_us,
                       status=status, good=good)

    # -- client side ---------------------------------------------------------
    def enqueue(self, reqs: List[_Request]) -> None:
        """Admit ``reqs`` (all-or-nothing) and wake the dispatcher if needed.
        Raises ``QueueFullError`` past the configured queue bound."""
        with self._cond:
            if self._stop or self._drain:
                raise RuntimeError("scheduler is closed; create a new Session")
            if self._breaker == _OPEN \
                    and getattr(self.net, "fallback", None) is None:
                # no fallback to absorb traffic: shed fast while open, and
                # let the first submit past the reset window in as the probe
                wait_s = (self._opened_at + self.config.breaker_reset_s
                          - time.perf_counter())
                if wait_s > 0:
                    self.net.stats.note_circuit_reject(len(reqs))
                    for _ in reqs:
                        self._tel_record(0.0, "rejected", good=False)
                    raise CircuitOpenError(getattr(self.net, "name", "?"),
                                           wait_s)
                self._set_breaker(_HALF_OPEN)
            bound = self.config.max_queue
            if bound is not None and len(self._heap) + len(reqs) > bound:
                self.net.stats.note_reject(len(reqs))
                for _ in reqs:
                    self._tel_record(0.0, "rejected", good=False)
                raise QueueFullError(getattr(self.net, "name", "?"),
                                     len(self._heap), bound)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._loop,
                    name=f"repro-dispatch-{getattr(self.net, 'name', '?')}",
                    daemon=True)
                self._thread.start()
            was_empty = not self._heap
            for r in reqs:
                heapq.heappush(self._heap, (r.sort_key(), r))
            depth = len(self._heap)
            self.net.stats.note_submit(len(reqs), depth)
            # wake the dispatcher only on the transitions it acts on — queue
            # went non-empty, or a full batch is now available.  Intermediate
            # submits land silently (the dispatcher's hold-wait re-checks on
            # wake or deadline), avoiding a context switch per request.
            if was_empty or depth >= self.config.max_batch:
                self._cond.notify()

    def queue_depth(self) -> int:
        with self._cond:
            return len(self._heap)

    def circuit_state(self) -> str:
        """``closed`` / ``open`` / ``half_open`` (``Session.health`` input)."""
        with self._cond:
            return self._breaker

    def close(self, drain: bool = False) -> None:
        """Stop the dispatcher.  ``drain=False`` cancels queued requests
        immediately; ``drain=True`` lets the queue empty first.  Either way,
        every future this dispatcher ever accepted is resolved when this
        returns: results for dispatched work, ``CancelledError`` for
        cancelled work — a caller blocked in ``Future.result()`` always
        wakes up, even if the backend hangs (``close_timeout_s``)."""
        with self._cond:
            pending: List[_Request] = []
            if drain:
                self._drain = True
            else:
                self._stop = True
                pending = [r for _, r in self._heap]
                self._heap.clear()
            self._cond.notify_all()
        for req in pending:
            req.future.cancel()
        thread = self._thread
        if thread is not None:
            # the timeout guards a HUNG backend, not a slow drain: keep
            # waiting as long as the dispatcher is making progress, and
            # fall through to force-cancel after ONE full window in which
            # nothing completed
            with self._cond:
                last_remaining = len(self._heap) + len(self._inflight)
            while True:
                thread.join(timeout=self.config.close_timeout_s)
                if not thread.is_alive():
                    break
                with self._cond:
                    remaining = len(self._heap) + len(self._inflight)
                if remaining >= last_remaining:
                    break
                last_remaining = remaining
        with self._cond:
            self._stop = True                # drain path: no further batches
            self._cond.notify_all()
            leftovers = [r for _, r in self._heap] + list(self._inflight)
            self._heap.clear()
        for req in leftovers:
            # join timed out (hung backend) or drain left stragglers: never
            # leave a caller blocked forever on result()
            req.future.cancel()

    # -- dispatcher side -----------------------------------------------------
    def _batch_cap(self, head: _Request) -> int:
        # a pre-formed submit_many group dispatches whole even past the
        # config cap, but a backend's declared hard ceiling always wins
        cap = max(self.config.max_batch, head.group_n)
        try:
            backend_max = self.net.executor.capabilities().max_batch
        except Exception:
            backend_max = None
        if backend_max is not None:
            cap = min(cap, backend_max)
        return max(cap, 1)

    @staticmethod
    def _compatible(head: _Request, r: _Request) -> bool:
        """Requests may share a dispatch when their input dtypes match (int8
        lanes pass through quantisation; stacking them with float32 lanes
        would promote the batch and re-quantise them — wrong bytes).  Same
        net is implied: this dispatcher serves exactly one network."""
        return getattr(r.x, "dtype", None) == getattr(head.x, "dtype", None)

    def _shed(self, req: _Request, now: float) -> None:
        self.net.stats.note_shed(1)
        self._tel_record((now - req.t_submit) * 1e6, "shed", good=False)
        if req.trace is not None:
            req.trace.add_span("queue", req.t_submit, now)
            req.trace.event("shed", deadline_us=req.deadline_us,
                            waited_us=(now - req.t_submit) * 1e6)
        _resolve_future(req.future, req.future.set_exception,
                        DeadlineExceededError(
                            getattr(self.net, "name", "?"), req.deadline_us,
                            (now - req.t_submit) * 1e6))

    def _collect(self) -> Optional[List[_Request]]:
        """Block for the next batch: best-(priority, deadline) head plus
        compatible stragglers, shedding expired-deadline requests.

        Queued requests stay on the heap during the hold so the producer-side
        full-batch wake-up keeps seeing the true depth, and so late arrivals
        (including higher-priority ones, which displace the head) join the
        forming batch right up to launch; the hold ends when a full batch is
        available or the head has waited ``max_wait_us``.  Returns ``None``
        to stop, ``[]`` when a pass shed everything it popped.
        """
        cfg = self.config
        expired: List[_Request] = []
        try:
            with self._cond:
                while not self._heap:
                    if self._stop or self._drain:
                        return None
                    self._cond.wait()
                if self._stop:
                    return None
                head = self._heap[0][1]
                cap = self._batch_cap(head)
                hold = (not self._drain
                        and (not cfg.adaptive
                             or self._ema_coalesce > _COALESCE_THRESHOLD))
                t_hold0 = t_hold1 = 0.0
                if hold:
                    t_hold0 = time.perf_counter()
                    deadline = head.t_submit + cfg.max_wait_us * 1e-6
                    while not self._stop:
                        same = sum(1 for _, r in self._heap
                                   if self._compatible(head, r))
                        if same >= cap:
                            break
                        remaining = deadline - time.perf_counter()
                        if remaining <= 0:
                            break
                        self._cond.wait(remaining)
                    t_hold1 = time.perf_counter()
                if self._stop:
                    return None
                batch = self._pop_batch(expired)
                if t_hold1 > t_hold0:
                    for r in batch:
                        if r.trace is not None:
                            # clamp: a late arrival joined mid-hold, its
                            # wait started at its own submit
                            r.trace.add_span("hold",
                                             max(t_hold0, r.t_submit),
                                             t_hold1)
            return batch
        finally:
            # resolve shed futures outside the lock (done-callbacks may run)
            now = time.perf_counter()
            for r in expired:
                self._shed(r, now)

    def _pop_batch(self, expired: List[_Request]) -> List[_Request]:
        """Pop the launch's batch (``_cond`` held): in (priority, deadline)
        order, shedding expired requests into ``expired`` and pushing
        dtype-incompatible ones back for the next pass; the batch joins
        ``_inflight``."""
        now = time.perf_counter()
        head = self._heap[0][1]
        cap = self._batch_cap(head)
        batch: List[_Request] = []
        putback: List[tuple] = []
        while self._heap and len(batch) < cap:
            _, r = heapq.heappop(self._heap)
            if r.deadline < now:
                expired.append(r)
            elif self._compatible(head, r):
                batch.append(r)
            else:
                putback.append((r.sort_key(), r))
        for item in putback:
            heapq.heappush(self._heap, item)
        self._inflight.extend(batch)
        for r in batch:
            if r.trace is not None:
                r.trace.add_span("queue", r.t_submit, now,
                                 coalesced=len(batch))
        return batch

    def _full_queued(self) -> bool:
        """Whether a full batch — ``_batch_cap`` requests of the head's
        dtype — is queued (``_cond`` held)."""
        if not self._heap:
            return False
        head = self._heap[0][1]
        same = sum(1 for _, r in self._heap if self._compatible(head, r))
        return same >= self._batch_cap(head)

    def _take_full(self) -> List[_Request]:
        """A full batch off the queue for a launch ahead, with no hold, or
        ``[]`` when none is queued."""
        expired: List[_Request] = []
        try:
            with self._cond:
                if self._stop or not self._full_queued():
                    return []
                return self._pop_batch(expired)
        finally:
            now = time.perf_counter()
            for r in expired:
                self._shed(r, now)

    def _retire(self, batch: List[_Request]) -> None:
        """Drop an answered (or failed) batch from ``_inflight``."""
        gone = {id(r) for r in batch}
        with self._cond:
            self._inflight = [r for r in self._inflight if id(r) not in gone]

    # -- supervision ---------------------------------------------------------
    def _set_breaker(self, state: str) -> None:
        """Transition the breaker (``_cond`` held) and mirror it to stats."""
        if state == self._breaker:
            return
        self._breaker = state
        if state == _OPEN:
            self._opened_at = time.perf_counter()
        self.net.stats.note_circuit(state)
        tracer = getattr(self.scheduler, "tracer", None)
        if tracer is not None:      # tracer lock takes no scheduler locks
            tracer.note_circuit(getattr(self.net, "name", "?"), state)

    def force_open(self) -> None:
        """Externally trip the breaker open (the SLO engine's breach
        trigger).  Identical downstream behavior to a failure-driven open:
        fallback routing (or fast sheds) while open, half-open probe after
        ``breaker_reset_s`` — so the breaker self-heals, and a persisting
        breach simply re-trips it on the next evaluation."""
        with self._cond:
            if self._breaker != _OPEN:
                self._set_breaker(_OPEN)

    def _route(self) -> tuple:
        """``(executor, degraded)`` for the next launch attempt.  While the
        breaker is open, traffic routes to the net's fallback executor
        (degraded) — except once per ``breaker_reset_s`` window, when the
        primary gets a half-open probe; a closed/half-open breaker always
        routes primary."""
        with self._cond:
            if self._breaker == _OPEN:
                if (time.perf_counter() - self._opened_at
                        >= self.config.breaker_reset_s):
                    self._set_breaker(_HALF_OPEN)   # this launch is the probe
                    return self.net.executor, False
                fb = getattr(self.net, "fallback", None)
                if fb is not None:
                    return fb, True
            return self.net.executor, False

    def _note_launch_failure(self, ex, degraded: bool, exc) -> bool:
        """Record one failed attempt; returns whether the arena was reset
        (the dispatcher mirrors that onto the affected traces)."""
        stats = self.net.stats
        stats.note_failure(timeout=isinstance(exc, LaunchTimeoutError))
        # a crashed call may have scribbled on the resident arena: verify the
        # preload checksum and restore the pristine image before any retry
        reset = False
        try:
            if hasattr(ex, "arena_ok") and not ex.arena_ok():
                ex.reset_arena()
                stats.note_arena_reset()
                reset = True
        except Exception:        # noqa: BLE001 — never mask the real failure
            pass
        if degraded:
            return reset         # fallback failures don't drive the breaker
        with self._cond:
            self._consec_failures += 1
            bt = self.config.breaker_threshold
            if self._breaker == _HALF_OPEN:
                self._set_breaker(_OPEN)            # failed probe: reopen
            elif self._breaker == _CLOSED and bt is not None \
                    and self._consec_failures >= bt:
                self._set_breaker(_OPEN)
        return reset

    def _note_launch_success(self, degraded: bool) -> None:
        if degraded:
            return               # fallback health says nothing about primary
        with self._cond:
            self._consec_failures = 0
            if self._breaker != _CLOSED:
                self._set_breaker(_CLOSED)          # successful probe

    def _sync_fault_counter(self) -> None:
        n = getattr(self.net.executor, "faults_injected", None)
        if n is not None:
            self.net.stats.note_faults(n)

    def _launch_timeout_s(self, bucket: int) -> float:
        """Watchdog budget for one launch: the absolute override, or the
        cost model's predicted batch time x ``watchdog_mult``, floored
        generously (a cold bucket's first launch pays an XLA compile)."""
        cfg = self.config
        if cfg.watchdog_timeout_s is not None:
            return cfg.watchdog_timeout_s
        if not self._model_ms_known:
            self._model_ms_known = True
            try:
                ex = self.net.executor
                cycles = sum(perfmodel.descriptor_cost(d, ex.cfg).cycles
                             for d in ex.descs)
                self._model_ms = ex.cfg.cycles_to_ms(cycles)
            except Exception:    # stub/opaque backends: floor only
                self._model_ms = None
        if not self._model_ms:
            return cfg.watchdog_floor_s
        return max(cfg.watchdog_floor_s,
                   self._model_ms * 1e-3 * bucket * cfg.watchdog_mult)

    def _backoff_s(self, attempt: int) -> float:
        """Exponential backoff with deterministic ±20% jitter: monotonically
        increasing per attempt (2x base always beats +20% jitter)."""
        base = self.config.retry_backoff_s * (2 ** (attempt - 1))
        return base * self._retry_rng.uniform(0.8, 1.2)

    # -- launches ------------------------------------------------------------
    def _prepare(self, la: _Launch) -> None:
        """Number a launch attempt, choose its bucket and pad its inputs.

        Padding is only for native batch programs (compile-once shapes);
        sequential fallbacks would just discard the pad.  The backend's
        declared hard ceiling bounds even the padded shape (a
        non-power-of-two ceiling beats a ladder rung)."""
        self._launches += 1
        la.number = self._launches
        batch, ex = la.batch, la.ex
        k = len(batch)
        if k == 1:
            la.x = batch[0].x
            return
        caps = ex.capabilities()
        la.bucket = self.config.bucket_for(k) if caps.native_batching else k
        if caps.max_batch is not None:
            la.bucket = min(la.bucket, caps.max_batch)
        la.lanes = k
        # the CPU clock is read inside the wall-clock bounds
        tp0, cpu0 = time.perf_counter(), time.thread_time()
        la.x = pad_batch([r.x for r in batch], la.bucket)
        cpu = time.thread_time() - cpu0
        tp1 = time.perf_counter()
        for r in la.traced:
            r.trace.add_span("pad", tp0, tp1, bucket=la.bucket, lanes=k,
                             launch=la.number, cpu_s=cpu)
        if caps.shardable:
            ex.batch_sharding = self.scheduler._lane_sharding(la.bucket)

    def _run(self, la: _Launch) -> List[ExecResult]:
        """One blocking, supervised executor call: the whole launch.  The
        phases tile the call: the first opens with it, the last ends when
        it returns."""
        ex = la.ex
        compiles0 = getattr(ex, "compile_count", 0)

        def call():
            with collect_launch(la.number if la.traced else None) as phases:
                t0 = phases.start()
                res = (ex.run(la.x) if la.lanes is None
                       else ex.run_batch(la.x, lanes=la.lanes))
                return res, phases, t0, phases.close()

        res, la.phases, la.t0, t1 = self._launcher.call(
            call, self._launch_timeout_s(la.bucket))
        la.compiles = getattr(ex, "compile_count", 0) - compiles0
        return self._answers(la, res, t1)

    def _submit(self, la: _Launch) -> None:
        """The first half of a split launch, supervised like the whole
        call: quantise, transfer and enqueue; the launch is then in flight
        until ``_finish``."""
        ex = la.ex
        compiles0 = getattr(ex, "compile_count", 0)

        def call():
            with collect_launch(la.number if la.traced else None) as phases:
                t0 = phases.start()
                handle = (ex.submit(la.x) if la.lanes is None
                          else ex.submit_batch(la.x, lanes=la.lanes))
                return handle, phases, t0

        la.handle, la.phases, la.t0 = self._launcher.call(
            call, self._launch_timeout_s(la.bucket))
        la.compiles = getattr(ex, "compile_count", 0) - compiles0
        if la.ahead:
            self.net.stats.note_ahead()

    def _finish(self, la: _Launch) -> List[ExecResult]:
        """The second half of a split launch, supervised like the whole
        call: wait for the device and fetch, the phases marked into the
        collector the first half opened."""
        def call():
            with resume_launch(la.phases):
                res = la.ex.finish(la.handle)
                return res, la.phases.close()

        res, t1 = self._launcher.call(call, self._launch_timeout_s(la.bucket))
        return self._answers(la, res, t1)

    def _answers(self, la: _Launch, res: ExecResult,
                 t1: float) -> List[ExecResult]:
        """Trace the finished launch and split its result by lane.  Traced
        requests get a ``device_execute`` span from the first half's start
        to the call's return and, nested in it, the phases the executor
        marked (``obs.trace.collect_launch``, set only when a request of
        the batch is traced); ``enqueue`` carries ``ahead``, the launches
        still in flight when it was enqueued."""
        for s in la.phases.spans:
            if s.name == "enqueue":
                s.args["ahead"] = la.ahead
        for r in la.traced:
            r.trace.add_span("device_execute", la.t0, t1, bucket=la.bucket,
                             lanes=len(la.batch), attempt=la.attempt,
                             degraded=la.degraded, launch=la.number)
            r.trace.add_spans(la.phases.spans)
        if la.lanes is None:
            return [res]
        return [ExecResult(output_int8=res.output_int8[i],
                           output=res.output[i]) for i in range(la.lanes)]

    # -- dispatch ------------------------------------------------------------
    def _dispatch(self, batch: List[_Request], attempt: int = 1,
                  split: bool = True) -> Optional[_Launch]:
        """Launch ``batch``, retrying failed attempts, and answer it.  With
        ``split``, an executor that has the split and a full batch queued
        to follow it, the launch is only enqueued and returned in flight,
        to be settled by ``_settle``; retries run whole."""
        while True:
            ex, degraded = self._route()
            la = _Launch(batch, ex, degraded, attempt)
            try:
                self._prepare(la)
                lead = split and ex.capabilities().split_launch
                if lead:
                    with self._cond:
                        lead = self._full_queued()
                if lead:
                    self._submit(la)
                    return la
                outs = self._run(la)
            except BaseException as e:  # noqa: BLE001 — forwarded to callers
                if self._failed(ex, degraded, batch, attempt, e):
                    attempt += 1
                    split = False
                    continue
                return None
            self._respond(la, outs)
            return None

    def _go_ahead(self, flight: _Launch) -> Optional[_Launch]:
        """Enqueue the next launch behind ``flight``, which is on the device,
        when the executor has the split and a full batch is queued; its
        submit step's failure is kept for ``_settle``."""
        if flight.exc is not None:
            return None
        ex, degraded = self._route()
        if not ex.capabilities().split_launch:
            return None
        batch = self._take_full()
        if not batch:
            return None
        la = _Launch(batch, ex, degraded, 1, ahead=1)
        try:
            self._prepare(la)
            self._submit(la)
        except BaseException as e:  # noqa: BLE001 — settled by _settle
            la.exc = e
        return la

    def _settle(self, la: _Launch) -> bool:
        """Finish an in-flight launch and answer it; a failed one goes
        through the retry path, run whole.  Returns whether it succeeded."""
        err = la.exc
        if err is None:
            try:
                outs = self._finish(la)
            except BaseException as e:  # noqa: BLE001 — forwarded to callers
                err = e
        if err is None:
            self._respond(la, outs)
            return True
        if self._failed(la.ex, la.degraded, la.batch, la.attempt, err):
            self._dispatch(la.batch, la.attempt + 1, split=False)
        return False

    def _relaunch(self, la: _Launch) -> None:
        """Launch again, whole, a batch enqueued behind a launch that
        failed: its output is dropped unseen, and the new attempt counts as
        a retry, not as a failure."""
        self.net.stats.note_retry()
        for r in la.traced:
            r.trace.event("launch_discarded", launch=la.number)
        self._dispatch(la.batch, la.attempt + 1, split=False)

    def _failed(self, ex, degraded: bool, batch: List[_Request],
                attempt: int, exc: BaseException) -> bool:
        """Record one failed attempt; returns True, after the backoff, when
        it is to be retried, else resolves the batch's futures with
        ``BackendFaultError`` and returns False."""
        net = self.net
        traced = [r for r in batch if r.trace is not None]
        reset = self._note_launch_failure(ex, degraded, exc)
        self._sync_fault_counter()
        for r in traced:
            r.trace.event("launch_failure", attempt=attempt,
                          error=type(exc).__name__, degraded=degraded)
            if isinstance(exc, LaunchTimeoutError):
                r.trace.event("watchdog_fire", timeout_s=exc.timeout_s)
            if reset:
                r.trace.event("arena_reset")
        with self._cond:
            stopping = self._stop
        if attempt <= self.config.max_retries and not stopping:
            # the inputs are still held, so a retry is idempotent; an open
            # breaker reroutes the retry to the fallback
            net.stats.note_retry()
            tb0 = time.perf_counter()
            time.sleep(self._backoff_s(attempt))
            tb1 = time.perf_counter()
            for r in traced:
                r.trace.add_span("backoff", tb0, tb1, attempt=attempt)
            return True
        err = BackendFaultError(getattr(net, "name", "?"), attempt, exc)
        err.__cause__ = exc
        now = time.perf_counter()
        for r in batch:
            self._tel_record((now - r.t_submit) * 1e6, "error", good=False)
            _resolve_future(r.future, r.future.set_exception, err)
        return False

    def _respond(self, la: _Launch, outs: List[ExecResult]) -> None:
        """Count the answered launch and resolve its futures."""
        net = self.net
        batch, degraded = la.batch, la.degraded
        self._note_launch_success(degraded)
        self._sync_fault_counter()
        k = len(batch)
        done, done_cpu = time.perf_counter(), time.thread_time()
        net.stats.note_dispatch(
            k, [(done - r.t_submit) * 1e6 for r in batch], bucket=la.bucket,
            compiles=la.compiles, degraded=k if degraded else 0)
        if degraded:
            outs = [dataclasses.replace(o, degraded=True) for o in outs]
        for r in batch:
            lat_us = (done - r.t_submit) * 1e6
            self._tel_record(lat_us, "degraded" if degraded else "ok",
                             good=(not r.deadline_us
                                   or lat_us <= r.deadline_us))
        for r, out in zip(batch, outs):
            if r.trace is not None:
                # recorded before set_result: resolving the future runs the
                # done-callback that seals this trace
                cpu = time.thread_time() - done_cpu
                r.trace.add_span("respond", done, time.perf_counter(),
                                 launch=la.number, cpu_s=cpu)
            _resolve_future(r.future, r.future.set_result, out)
        self._ema_coalesce = ((1 - _EMA_ALPHA) * self._ema_coalesce
                              + _EMA_ALPHA * k)

    def _loop(self) -> None:
        """Collect and launch, two deep: while one launch is on the device
        the next full batch is enqueued behind it (``_go_ahead``), and only
        then is the first waited for and answered.  Without a full batch
        queued the launch in flight is settled first and the next batch
        collected, hold included."""
        flight: Optional[_Launch] = None    # enqueued, not yet waited for
        try:
            while True:
                if flight is None:
                    batch = self._collect()
                    if batch is None:
                        return
                    if batch:
                        flight = self._dispatch(batch)
                        if flight is None:
                            self._retire(batch)
                    continue
                behind = self._go_ahead(flight)
                ok = self._settle(flight)
                self._retire(flight.batch)
                if not ok and behind is not None and behind.exc is None:
                    # the device may have failed under both: drop the
                    # output of the launch behind and launch it again
                    self._relaunch(behind)
                    self._retire(behind.batch)
                    behind = None
                flight = behind
        finally:
            self._launcher.stop()


class Scheduler:
    """Per-net dispatcher threads behind a ``Session``.

    Each resident network owns an independent queue and dispatcher thread
    (created lazily on its first submit), so traffic for one net never
    head-of-line blocks another's.  The public surface is unchanged from the
    single-dispatcher era — ``submit`` / ``submit_many`` / ``queue_depth`` /
    ``close`` — plus per-request ``priority`` and ``deadline_us``.
    """

    def __init__(self, config: Optional[SchedulerConfig] = None, tracer=None,
                 telemetry=None):
        self.config = config or SchedulerConfig()
        self.tracer = tracer            # repro.obs Tracer, or None (untraced)
        self.telemetry = telemetry      # repro.obs Telemetry, or None
        self._lock = threading.Lock()
        self._dispatchers: Dict[int, _NetDispatcher] = {}
        self._retired: Dict[int, object] = {}   # unloaded nets, by id
        self._closed = False
        self._seq = itertools.count()
        self._mesh = None
        self._mesh_checked = False

    # -- client side ---------------------------------------------------------
    def submit(self, net, x: np.ndarray, priority: int = 0,
               deadline_us: Optional[float] = None,
               trace_id: Optional[str] = None) -> Future:
        """Enqueue one request against resident network ``net``."""
        return self.submit_many(net, [x], priority=priority,
                                deadline_us=deadline_us, trace_id=trace_id)[0]

    def submit_many(self, net, xs, priority: int = 0,
                    deadline_us: Optional[float] = None,
                    trace_id: Optional[str] = None) -> List[Future]:
        """Enqueue several requests atomically (one lock hold, one wake-up),
        so a pre-formed batch reaches the dispatcher whole instead of being
        peeled off a request at a time.  When the group reaches the head of
        the queue it may exceed ``max_batch`` and still dispatch as one
        program (explicit ``run_batch`` callers keep the single-program
        semantics; the cap bounds *coalescing* of independent submits).
        Under mixed traffic a group queued behind other requests can split
        across dispatches — results stay bit-exact either way, and batch
        shapes stay on the power-of-two bucket grid.

        ``priority`` (higher = more urgent) and ``deadline_us`` (relative
        latency budget; past it the request is shed with
        ``DeadlineExceededError``) order the per-net queue.  Raises
        ``QueueFullError`` when the net's queue is at ``max_queue``.

        Every returned future carries ``fut.trace_id`` and ``fut.trace``
        (its ``RequestTrace``, or None when unsampled) when a tracer is
        attached; ``trace_id`` (applied to the group's first request)
        forces that request into the sampled set.
        """
        if deadline_us is not None and math.isnan(deadline_us):
            raise ValueError("deadline_us must not be NaN (a NaN sort key "
                             "would corrupt the EDF queue order)")
        now = time.perf_counter()
        # deadline_us=0 means an already-expired budget (shed at launch),
        # NOT "no deadline" — only None/inf disable the deadline entirely
        dl = now + deadline_us * 1e-6 if deadline_us is not None else math.inf
        tracer = self.tracer
        reqs = []
        for i, x in enumerate(xs):
            r = _Request(net=net, x=x, future=Future(), t_submit=now,
                         priority=priority, deadline=dl,
                         deadline_us=deadline_us or 0.0,
                         seq=next(self._seq), group_n=len(xs))
            if tracer is not None:
                tid, trace = tracer.start(getattr(net, "name", "?"),
                                          trace_id if i == 0 else None,
                                          t_start=now)
                r.future.trace_id = tid
                # the HTTP front end adds its decode and encode spans here
                r.future.trace = trace
                if trace is not None:
                    r.trace = trace
                    # the future's terminal state — result, exception or
                    # cancel, whichever path delivers it — completes the
                    # trace exactly once
                    r.future.add_done_callback(
                        functools.partial(tracer.finish_future, trace))
            reqs.append(r)
        try:
            self._dispatcher(net).enqueue(reqs)
        except BaseException as e:
            # rejected at admission (queue full / circuit open / closed):
            # the futures never resolve, so complete the traces here and
            # pin the (first) trace id on the exception for error replies
            if tracer is not None:
                for r in reqs:
                    tracer.finish(r.trace, status=status_for_exception(e),
                                  error=type(e).__name__)
                if reqs:
                    e.trace_id = getattr(reqs[0].future, "trace_id", None)
            raise
        return [r.future for r in reqs]

    def queue_depth(self, net=None) -> int:
        """Queued (not in-flight) requests: one net's, or all nets' summed."""
        with self._lock:
            ds = list(self._dispatchers.values())
        return sum(d.queue_depth() for d in ds
                   if net is None or d.net is net)

    def circuit_state(self, net) -> str:
        """The net's circuit-breaker state: ``closed`` (healthy), ``open``
        (shedding / serving fallback), or ``half_open`` (probing the
        primary).  A net that never dispatched is ``closed``."""
        with self._lock:
            d = self._dispatchers.get(id(net))
        return d.circuit_state() if d is not None else _CLOSED

    def trip_circuit(self, net) -> bool:
        """Force the net's breaker open (the SLO engine's breach trigger).
        Returns False for a net with no dispatcher yet — no traffic means
        nothing to protect."""
        with self._lock:
            d = self._dispatchers.get(id(net))
        if d is None:
            return False
        d.force_open()
        return True

    def close(self, drain: bool = False) -> None:
        """Stop every dispatcher.  ``drain=False`` (default): queued requests
        get ``CancelledError``, the in-flight batch finishes; ``drain=True``:
        queued work completes first.  Every future ever returned by
        ``submit`` is resolved when this returns."""
        with self._lock:
            self._closed = True
            ds = list(self._dispatchers.values())
        for d in ds:
            d.close(drain=drain)

    def close_net(self, net, drain: bool = True) -> None:
        """Stop one net's dispatcher (Session.unload / replace) — without
        this its idle thread would outlive the net's residency.  The net is
        remembered as retired so a racing ``submit`` that already resolved
        it cannot silently respawn a dispatcher for a dead executor."""
        with self._lock:
            d = self._dispatchers.pop(id(net), None)
            self._retired[id(net)] = net    # hold the ref: id() stays unique
        if d is not None:
            d.close(drain=drain)

    # -- internals -----------------------------------------------------------
    def _dispatcher(self, net) -> _NetDispatcher:
        with self._lock:
            if self._closed:
                raise RuntimeError("scheduler is closed; create a new Session")
            if id(net) in self._retired:
                raise RuntimeError(
                    f"network {getattr(net, 'name', '?')!r} was unloaded")
            d = self._dispatchers.get(id(net))
            if d is None:
                d = _NetDispatcher(net, self.config, self)
                self._dispatchers[id(net)] = d
            return d

    def _lane_sharding(self, lanes_padded: int):
        """NamedSharding for a shardable batch, or None.  Called from
        dispatcher threads; the mesh probe is cached after the first call."""
        if not self.config.shard:
            return None
        with self._lock:
            if not self._mesh_checked:
                from repro.distributed import sharding as shard_mod
                self._mesh = shard_mod.serving_mesh()
                self._mesh_checked = True
            mesh = self._mesh
        if mesh is None or lanes_padded % mesh.size != 0:
            return None
        from repro.distributed import sharding as shard_mod
        return shard_mod.lane_sharding(mesh)
