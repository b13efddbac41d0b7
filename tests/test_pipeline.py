"""The two-deep launch pipeline of the scheduler's dispatcher.

With an executor whose launch comes in two halves (``submit`` enqueues,
``finish`` waits and fetches) the dispatcher enqueues launch N+1 behind N
when a full batch is queued, and only then finishes N.  A stub executor
records the order of the halves; a LeNet-5 session checks that answers
stay byte-equal to serial runs.
"""

import threading
from concurrent.futures import CancelledError

import numpy as np
import pytest

from repro.core import engine, graph, pipeline
from repro.core.executor import ExecResult, ExecutorCapabilities
from repro.obs.trace import TraceConfig, launch_phases
from repro.runtime import BackendFaultError, Session, SchedulerConfig
from repro.runtime import scheduler as scheduler_mod

CAP = 4                      # max_batch of every stub session


def _tiny_net() -> graph.NetGraph:
    g = graph.NetGraph("tiny", (2, 8, 8))
    g.layer(name="data", type="input", inputs=[])
    x = g.layer(name="c1", type="conv", inputs=["data"], out_channels=4,
                kernel=3, pad=1, relu=True)
    x = g.layer(name="p1", type="pool", inputs=[x], pool_mode="gap")
    g.layer(name="fc", type="fc", inputs=[x], out_channels=3)
    return g.infer_shapes()


@pytest.fixture(scope="module")
def tiny_art():
    return pipeline.CompilerPipeline(_tiny_net()).run()


def _tagged(i):
    """Input whose first element encodes the request id."""
    x = np.zeros((2, 8, 8), np.float32)
    x[0, 0, 0] = float(i)
    return x


class _SplitStub:
    """An executor with (``split=True``) or without the split.

    Records ``(half, ids)`` per call in order — ``submit`` and ``finish``
    for the split halves, ``run`` for a whole launch — and the most launches
    on the device at once.  It marks the five launch phases as the real
    executor does, and answers each lane with its id.  ``gate_submit``
    holds the first launch until set; ``gate_finish`` holds every second
    half of a split launch; ``fail`` maps a batch's ids to how many of its
    launches fail when waited for, ``hang`` to how many block there until
    ``release`` is set."""

    def __init__(self, split=True, fail=None, hang=None):
        self.split = split
        self.fail = dict(fail or {})
        self.hang = dict(hang or {})
        self.gate_submit = threading.Event()
        self.gate_finish = threading.Event()
        self.gate_finish.set()
        self.release = threading.Event()
        self.entered = threading.Event()
        self.calls = []
        self.on_device = self.max_on_device = 0
        self._lock = threading.Lock()
        self._first = True

    @staticmethod
    def _ids(X, lanes):
        X = np.asarray(X)
        if lanes is None:
            return (int(X.reshape(-1)[0]),)
        return tuple(int(X[i].reshape(-1)[0]) for i in range(lanes))

    def _submit(self, X, lanes, half="submit"):
        if self._first:
            self._first = False
            self.entered.set()
            assert self.gate_submit.wait(timeout=60)
        ph = launch_phases()
        for name in ("quantise", "h2d", "enqueue"):
            ph.mark(name)
        ids = self._ids(X, lanes)
        with self._lock:
            self.calls.append((half, ids))
            self.on_device += 1
            self.max_on_device = max(self.max_on_device, self.on_device)
        return _Handle(ids, lanes)

    def submit(self, x):
        return self._submit(x, None)

    def submit_batch(self, X, lanes=None):
        return self._submit(X, lanes)

    def finish(self, h, half="finish"):
        if half == "finish":
            assert self.gate_finish.wait(timeout=60)
        ph = launch_phases()
        ph.mark("device_wait", host=False)
        with self._lock:
            if half == "finish":
                self.calls.append((half, h.ids))
            self.on_device -= 1
            hangs = self.hang.get(h.ids, 0)
            if hangs:
                self.hang[h.ids] = hangs - 1
            fails = self.fail.get(h.ids, 0)
            if fails:
                self.fail[h.ids] = fails - 1
        if hangs:
            self.release.wait(timeout=60)
            raise RuntimeError(f"hung launch {h.ids} released")
        if fails:
            raise RuntimeError(f"launch {h.ids} failed")
        ph.mark("d2h")
        out = np.asarray(h.ids, np.float32)[:, None] * np.ones((1, 3))
        if h.lanes is None:
            return ExecResult(out[0].astype(np.int8), out[0])
        return ExecResult(out.astype(np.int8), out)

    def run(self, x):
        return self.finish(self._submit(x, None, "run"), "run")

    def run_batch(self, X, lanes=None):
        return self.finish(self._submit(X, lanes, "run"), "run")

    def capabilities(self):
        return ExecutorCapabilities(native_batching=True,
                                    split_launch=self.split)


class _Handle:
    """The stub's enqueued launch: its request ids and live lanes."""

    def __init__(self, ids, lanes):
        self.ids, self.lanes = ids, lanes


def _session(tiny_art, stub, trace=None, **cfg):
    cfg = dict(dict(max_batch=CAP, max_wait_us=0.0, adaptive=False), **cfg)
    ses = Session(tiny_art, scheduler=SchedulerConfig(**cfg), trace=trace)
    ses._resolve(None).executor = stub
    return ses


def _burst(ses, stub, n_queued):
    """One lone request that enters the executor, launched whole, and is
    held there while ``n_queued`` more queue up behind it; then the
    executor is let go."""
    futs = [ses.submit(_tagged(0))]
    assert stub.entered.wait(timeout=60)
    futs += [ses.submit(_tagged(i)) for i in range(1, n_queued + 1)]
    stub.gate_submit.set()
    return futs


def _answers(futs):
    return [int(f.result(timeout=60).output[0]) for f in futs]


class TestPipelineOrder:
    def test_full_batch_queued_goes_ahead(self, tiny_art):
        stub = _SplitStub()
        ses = _session(tiny_art, stub, trace=TraceConfig(sample_rate=1))
        try:
            futs = _burst(ses, stub, 3 * CAP)
            assert _answers(futs) == list(range(3 * CAP + 1))
            stats = ses.stats().snapshot()
        finally:
            ses.close()
        a, b, c, d = (0,), (1, 2, 3, 4), (5, 6, 7, 8), (9, 10, 11, 12)
        # the lone launch runs whole; from then on N+1 is enqueued before
        # N is finished, at every step
        assert stub.calls == [("run", a), ("submit", b), ("submit", c),
                              ("finish", b), ("submit", d), ("finish", c),
                              ("finish", d)]
        assert stats["launches_ahead"] == 2
        assert stats["dispatches"] == 4 and stats["retries"] == 0

    def test_overlapped_launch_keeps_its_spans(self, tiny_art):
        stub = _SplitStub()
        ses = _session(tiny_art, stub, trace=TraceConfig(sample_rate=1))
        try:
            futs = _burst(ses, stub, 3 * CAP)
            _answers(futs)
            by_future = [f.trace for f in futs]
        finally:
            ses.close()
        phases = ("quantise", "h2d", "enqueue", "device_wait", "d2h")
        ahead = {}
        for t in by_future:
            (dx,) = [s for s in t.spans if s.name == "device_execute"]
            kids = [s for s in t.spans if s.name in phases]
            assert [s.name for s in kids] == list(phases)
            assert kids[0].t0 == dx.t0 and kids[-1].t1 == dx.t1
            assert all(x.t1 == y.t0 for x, y in zip(kids, kids[1:]))
            (enq,) = [s for s in kids if s.name == "enqueue"]
            ahead[dx.args["launch"]] = enq.args["ahead"]
        assert sorted(ahead.values()) == [0, 0, 1, 1]


class TestPipelineEngages:
    @pytest.mark.parametrize("n_queued", [0, CAP - 1],
                             ids=["lone", "partial"])
    def test_no_full_batch_never_goes_ahead(self, tiny_art, n_queued):
        stub = _SplitStub()
        ses = _session(tiny_art, stub, trace=TraceConfig(sample_rate=1))
        try:
            futs = _burst(ses, stub, n_queued)
            assert _answers(futs) == list(range(n_queued + 1))
            stats = ses.stats().snapshot()
            traces = [f.trace for f in futs]
        finally:
            ses.close()
        assert stats["launches_ahead"] == 0
        assert stub.max_on_device == 1
        assert {h for h, _ in stub.calls} == {"run"}     # launched whole
        enq = [s.args["ahead"] for t in traces for s in t.spans
               if s.name == "enqueue"]
        assert enq and set(enq) == {0}

    def test_depth_never_exceeds_two(self, tiny_art):
        stub = _SplitStub()
        ses = _session(tiny_art, stub)
        try:
            futs = _burst(ses, stub, 10 * CAP)
            assert _answers(futs) == list(range(10 * CAP + 1))
            stats = ses.stats().snapshot()
        finally:
            ses.close()
        assert stub.max_on_device == 2
        assert stats["launches_ahead"] == 9
        assert stats["coalesced_images"] == 10 * CAP + 1

    def test_executor_without_split_runs_serially(self, tiny_art):
        stub = _SplitStub(split=False)
        ses = _session(tiny_art, stub)
        try:
            futs = _burst(ses, stub, 3 * CAP)
            assert _answers(futs) == list(range(3 * CAP + 1))
            stats = ses.stats().snapshot()
        finally:
            ses.close()
        assert {h for h, _ in stub.calls} == {"run"}
        assert stub.max_on_device == 1
        assert stats["launches_ahead"] == 0


class TestPipelineFailures:
    @pytest.mark.parametrize("fault", ["error", "watchdog"])
    @pytest.mark.parametrize("fails", [1, 2], ids=["recovers", "exhausted"])
    def test_failure_with_a_launch_behind(self, tiny_art, monkeypatch,
                                          fault, fails):
        """N fails with N+1 enqueued behind it: N+1's output is dropped and
        it is launched again; N goes through its retries; every future
        resolves exactly once."""
        b, c = (1, 2, 3, 4), (5, 6, 7, 8)
        plan = {b: fails}
        stub = _SplitStub(**({"fail": plan} if fault == "error"
                             else {"hang": plan}))
        resolved = {}
        real = scheduler_mod._resolve_future

        def counting(future, set_fn, value):
            resolved[id(future)] = resolved.get(id(future), 0) + 1
            real(future, set_fn, value)

        monkeypatch.setattr(scheduler_mod, "_resolve_future", counting)
        ses = _session(tiny_art, stub, max_retries=1, retry_backoff_s=0.001,
                       watchdog_timeout_s=0.5, breaker_threshold=None)
        try:
            futs = _burst(ses, stub, 2 * CAP)
            outcome = {}
            for i, f in enumerate(futs):
                try:
                    outcome[i] = int(f.result(timeout=60).output[0])
                except BackendFaultError:
                    outcome[i] = "fault"
            stats = ses.stats().snapshot()
        finally:
            stub.release.set()
            ses.close()
        assert all(resolved[id(f)] == 1 for f in futs)
        for i in range(len(futs)):
            assert outcome[i] == ("fault" if fails == 2 and i in b else i)
        # N's failed attempts, N's retry, and N+1's relaunch
        assert stats["backend_failures"] == fails
        assert stats["watchdog_timeouts"] == (fails if fault == "watchdog"
                                              else 0)
        assert stats["retries"] == 1 + 1
        assert stats["launches_ahead"] == 1
        # N+1 (``c``) was enqueued, dropped unseen, and launched whole
        assert ("submit", c) in stub.calls and ("finish", c) not in stub.calls
        assert stub.calls.count(("run", c)) == 1
        assert stats["coalesced_images"] == len(futs) - \
            (len(b) if fails == 2 else 0)


class TestPipelineClose:
    @staticmethod
    def _two_in_flight(ses, stub):
        """A lone launch answered, then a full batch enqueued with a second
        behind it, held on the device; a third full batch queued."""
        stub.gate_finish.clear()
        futs = _burst(ses, stub, 3 * CAP)
        futs[0].result(timeout=60)
        for _ in range(6000):
            if stub.max_on_device == 2:
                break
            threading.Event().wait(0.01)
        assert stub.max_on_device == 2
        return futs

    @pytest.mark.parametrize("drain", [True, False], ids=["drain", "cancel"])
    def test_close_resolves_both_launches_in_flight(self, tiny_art, drain):
        stub = _SplitStub()
        ses = _session(tiny_art, stub)
        futs = self._two_in_flight(ses, stub)
        disp = next(iter(ses.scheduler._dispatchers.values()))
        with disp._cond:
            assert len(disp._inflight) == 2 * CAP
        closer = threading.Thread(target=ses.close, kwargs={"drain": drain})
        closer.start()
        stub.gate_finish.set()
        closer.join(timeout=60)
        assert not closer.is_alive()
        done = []
        for f in futs:
            try:
                done.append(int(f.result(timeout=30).output[0]))
            except CancelledError:
                pass
        assert all(f.done() for f in futs)
        assert done == list(range((3 if drain else 2) * CAP + 1))

    def test_hung_pipeline_is_force_cancelled(self, tiny_art):
        stub = _SplitStub()
        ses = _session(tiny_art, stub, close_timeout_s=0.3)
        futs = self._two_in_flight(ses, stub)
        try:
            ses.close()
            for f in futs[1:]:
                with pytest.raises(CancelledError):
                    f.result(timeout=30)
        finally:
            stub.gate_finish.set()


# ---------------------------------------------------------------------------
# The real executor: a burst through the pipeline answers byte-equal
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cfg", [engine.NV_SMALL, engine.NV_FULL],
                         ids=["int8", "bf16"])
def test_pipelined_burst_is_byte_equal_to_serial_runs(cfg):
    art = pipeline.CompilerPipeline(graph.lenet5(), cfg=cfg).run()
    rng = np.random.default_rng(3)
    xs = rng.normal(0, 1, (4 * CAP + 1, 1, 28, 28)).astype(np.float32)
    ses = Session(art, scheduler=SchedulerConfig(
        max_batch=CAP, max_wait_us=0.0, adaptive=False))
    ex = ses.executor()
    want = [ex.run(x).output_int8.tobytes() for x in xs]
    gate, entered = threading.Event(), threading.Event()
    run = ex.run

    def held(x):                      # the lone first launch waits here
        entered.set()
        assert gate.wait(timeout=120)
        return run(x)

    ex.run = held
    try:
        futs = [ses.submit(xs[0])]
        assert entered.wait(timeout=120)
        ex.run = run
        futs += [ses.submit(x) for x in xs[1:]]
        gate.set()
        got = [f.result(timeout=300).output_int8.tobytes() for f in futs]
        stats = ses.stats().snapshot()
    finally:
        ses.close()
    # four full batches: the first enqueued with the next behind it
    assert stats["launches_ahead"] == 3
    assert got == want
