"""Observability tests: tracing spans, sampling, Chrome export, profiling,
perf-model calibration.

Invariants under test:

  * every span is monotonic (``t1 >= t0``) and nested inside its request's
    ``[t_start, t_end]`` window;
  * every submitted request completes EXACTLY ONE trace — on the success,
    retry, shed, rejection and cancellation paths alike;
  * sampling is deterministic (every Nth request per net) and a
    client-supplied trace id always forces tracing;
  * the Chrome trace-event export is schema-valid JSON;
  * a traced launch's phases nest in and cover its ``device_execute`` span,
    share one ``launch`` id, and carry ``cpu_s`` on the host steps, also
    when the launch is enqueued behind another (``enqueue`` carries
    ``ahead``); an untraced launch collects nothing;
  * the executors' profiled path is bit-exact versus the fused path, and
    ``perfmodel.calibrate`` does not worsen per-layer model error.
"""

import json
import threading

import numpy as np
import pytest

from repro.core import graph, perfmodel, pipeline
from repro.obs import (RequestTrace, TraceConfig, Tracer, new_trace_id,
                       profile_layers, fidelity_report, valid_trace_id)
from repro.obs.trace import LaunchPhases, collect_launch, launch_phases
from repro.runtime import (DeadlineExceededError, QueueFullError, Session,
                           SchedulerConfig, create_executor)


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


@pytest.fixture(scope="module")
def tiny_ex(tiny_art):
    return create_executor("baremetal", tiny_art)


def _x(i=0):
    x = np.zeros((2, 8, 8), np.float32)
    x[0, 0, 0] = float(i)
    return x


# ---------------------------------------------------------------------------
# Trace ids + config validation
# ---------------------------------------------------------------------------
class TestIds:
    def test_new_trace_id_shape_and_uniqueness(self):
        ids = {new_trace_id() for _ in range(64)}
        assert len(ids) == 64
        for tid in ids:
            assert len(tid) == 16 and valid_trace_id(tid)

    @pytest.mark.parametrize("tid,ok", [
        ("abc123", True), ("a" * 64, True), ("w3c-trace.id_1", True),
        ("", False), ("a" * 65, False), ("bad id", False),
        ('x"y', False), ("new\nline", False),
    ])
    def test_valid_trace_id(self, tid, ok):
        assert valid_trace_id(tid) is ok

    def test_config_validation(self):
        with pytest.raises(ValueError, match="sample_rate"):
            TraceConfig(sample_rate=-1)
        with pytest.raises(ValueError, match="capacity"):
            TraceConfig(capacity=0)


# ---------------------------------------------------------------------------
# Sampler determinism + ring buffer
# ---------------------------------------------------------------------------
class TestTracerUnits:
    def test_every_nth_sampling_is_deterministic(self):
        def sampled_indices():
            tracer = Tracer(TraceConfig(sample_rate=4))
            hit = []
            for i in range(16):
                _, tr = tracer.start("net")
                if tr is not None:
                    hit.append(i)
            return hit

        a, b = sampled_indices(), sampled_indices()
        assert a == b == [0, 4, 8, 12]

    def test_sample_rate_zero_traces_only_forced(self):
        tracer = Tracer(TraceConfig(sample_rate=0))
        for _ in range(8):
            _, tr = tracer.start("net")
            assert tr is None
        tid, tr = tracer.start("net", "client-id-1")
        assert tid == "client-id-1" and tr is not None

    def test_disabled_keeps_id_contract_records_nothing(self):
        tracer = Tracer(TraceConfig(enabled=False))
        tid, tr = tracer.start("net", "forced-id")
        assert tid == "forced-id" and tr is None

    def test_finish_is_idempotent(self):
        tracer = Tracer()
        _, tr = tracer.start("net")
        tracer.finish(tr, status="ok")
        tracer.finish(tr, status="error", error="late")
        assert len(tracer.traces()) == 1
        assert tracer.traces()[0].status == "ok"

    def test_ring_buffer_evicts_and_counts_drops(self):
        tracer = Tracer(TraceConfig(capacity=4))
        for i in range(10):
            tr = RequestTrace(f"t{i}", "net")
            tracer.finish(tr)
        got = [t.trace_id for t in tracer.traces()]
        assert got == ["t6", "t7", "t8", "t9"]
        assert tracer.dropped == 6

    def test_phase_histograms_are_cumulative_to_inf(self):
        tracer = Tracer()
        for us in (30.0, 700.0, 2e6):
            tr = RequestTrace("t", "net")
            tr.add_span("queue", 0.0, us * 1e-6)
            tracer.finish(tr)
        h = tracer.phase_histograms()[("net", "queue")]
        les, cums = zip(*h["buckets"])
        assert les[-1] == float("inf") and cums[-1] == h["count"] == 3
        assert list(cums) == sorted(cums)          # cumulative
        assert h["sum"] == pytest.approx(30.0 + 700.0 + 2e6, rel=1e-6)


# ---------------------------------------------------------------------------
# Lifecycle spans through a real Session
# ---------------------------------------------------------------------------
class TestLifecycle:
    def test_span_invariants_and_exactly_one_trace_per_request(self,
                                                               tiny_art):
        N = 6
        ses = Session(tiny_art, scheduler=SchedulerConfig(max_batch=4),
                      trace=TraceConfig(sample_rate=1))
        try:
            futs = [ses.submit(_x(i)) for i in range(N)]
            for f in futs:
                f.result(timeout=60)
            traces = ses.tracer.traces()
            assert len(traces) == N
            ids = [getattr(f, "trace_id", None) for f in futs]
            assert sorted(ids) == sorted(t.trace_id for t in traces)
            for t in traces:
                assert t.finished and t.status == "ok"
                names = {s.name for s in t.spans}
                assert {"queue", "device_execute", "respond",
                        "request"} <= names
                for s in t.spans:
                    assert s.t1 >= s.t0                      # monotonic
                    assert s.t0 >= t.t_start - 1e-9          # nested
                    assert s.t1 <= t.t_end + 1e-9
        finally:
            ses.close()

    def test_shed_request_completes_trace_with_shed_status(self, tiny_art):
        ses = Session(tiny_art, trace=TraceConfig(sample_rate=1))
        try:
            fut = ses.submit(_x(), deadline_us=0.0)    # expired at launch
            with pytest.raises(DeadlineExceededError):
                fut.result(timeout=60)
            (t,) = [t for t in ses.tracer.traces()
                    if t.trace_id == fut.trace_id]
            assert t.status == "shed"
            assert "shed" in {name for name, _, _ in t.events}
        finally:
            ses.close()

    def test_rejected_request_completes_trace(self, tiny_art):
        ses = Session(tiny_art, scheduler=SchedulerConfig(max_queue=1),
                      trace=TraceConfig(sample_rate=1))
        net = ses._resolve(None)
        import threading
        from repro.core.executor import ExecResult, ExecutorCapabilities
        blocked, entered = threading.Event(), threading.Event()

        class _Stall:
            def capabilities(self):
                return ExecutorCapabilities(native_batching=True)

            def run(self, x):
                entered.set()
                blocked.wait(timeout=60)
                return ExecResult(np.zeros(3, np.int8),
                                  np.zeros(3, np.float32))

            def run_batch(self, X, lanes=None):
                entered.set()
                blocked.wait(timeout=60)
                z = np.zeros((X.shape[0], 3))
                return ExecResult(z.astype(np.int8), z.astype(np.float32))

        net.executor = _Stall()
        try:
            first = ses.submit(_x())
            assert entered.wait(timeout=60)
            backlog = ses.submit(_x())                 # fills max_queue=1
            with pytest.raises(QueueFullError):
                ses.submit(_x())
            rejected = [t for t in ses.tracer.traces()
                        if t.status == "rejected"]
            assert len(rejected) == 1
            assert rejected[0].error == "QueueFullError"
        finally:
            blocked.set()
            first.result(timeout=60)
            backlog.result(timeout=60)
            ses.close()

    def test_cancelled_on_close_completes_trace(self, tiny_art):
        # short close window: the stalled in-flight launch must not make
        # close() wait the default 30s no-progress window
        ses = Session(tiny_art,
                      scheduler=SchedulerConfig(close_timeout_s=0.5),
                      trace=TraceConfig(sample_rate=1))
        import threading
        from repro.core.executor import ExecResult, ExecutorCapabilities
        blocked, entered = threading.Event(), threading.Event()

        class _Stall:
            def capabilities(self):
                return ExecutorCapabilities()

            def run(self, x):
                entered.set()
                blocked.wait(timeout=60)
                return ExecResult(np.zeros(3, np.int8),
                                  np.zeros(3, np.float32))

        ses._resolve(None).executor = _Stall()
        inflight = ses.submit(_x())
        assert entered.wait(timeout=60)
        queued = ses.submit(_x(1))                     # stuck behind inflight
        ses.close()                                    # cancels queued
        blocked.set()
        statuses = {t.trace_id: t.status for t in ses.tracer.traces()}
        assert statuses.get(queued.trace_id) == "cancelled"
        assert queued.cancelled()
        del inflight

    def test_sampled_mode_traces_every_nth_submit(self, tiny_art):
        ses = Session(tiny_art, trace=TraceConfig(sample_rate=3))
        try:
            futs = [ses.submit(_x(i)) for i in range(9)]
            for f in futs:
                f.result(timeout=60)
            traced_ids = {t.trace_id for t in ses.tracer.traces()}
            # deterministic: submits 0, 3, 6 sampled
            expected = {futs[i].trace_id for i in (0, 3, 6)}
            assert traced_ids == expected
            # every future still carries an id (the contract holds unsampled)
            assert all(getattr(f, "trace_id", None) for f in futs)
        finally:
            ses.close()


# ---------------------------------------------------------------------------
# Chrome trace-event export schema
# ---------------------------------------------------------------------------
class TestChromeExport:
    def test_export_is_schema_valid(self, tiny_art, tmp_path):
        ses = Session(tiny_art, trace=TraceConfig(sample_rate=1))
        try:
            for i in range(3):
                ses.run(_x(i))
            doc = ses.tracer.chrome_trace()
        finally:
            ses.close()
        doc2 = json.loads(json.dumps(doc))             # JSON round-trip
        assert set(doc2) == {"traceEvents", "displayTimeUnit", "otherData"}
        assert doc2["traceEvents"]
        ts = []
        for ev in doc2["traceEvents"]:
            assert ev["ph"] in ("X", "i", "M")
            assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
            assert isinstance(ev["name"], str) and ev["name"]
            if ev["ph"] == "X":
                assert ev["dur"] > 0 and ev["ts"] >= 0
                assert ev["args"]["trace_id"]
                ts.append(ev["ts"])
            elif ev["ph"] == "i":
                assert ev["ts"] >= 0 and ev["s"] in ("t", "p", "g")
        assert ts == sorted(ts)                        # emitted time-ordered
        names = {e["name"] for e in doc2["traceEvents"] if e["ph"] == "X"}
        assert {"queue", "device_execute", "request"} <= names

    def test_to_file_writes_loadable_json(self, tiny_art, tmp_path):
        ses = Session(tiny_art, trace=TraceConfig(sample_rate=1))
        try:
            ses.run(_x())
            out = tmp_path / "traces" / "trace.json"
            ses.tracer.to_file(out)
        finally:
            ses.close()
        doc = json.loads(out.read_text())
        assert doc["traceEvents"]


# ---------------------------------------------------------------------------
# Profiled execution path: bit-exact, and feeds calibration
# ---------------------------------------------------------------------------
class TestProfiledPath:
    def test_run_profiled_bitexact_vs_run(self, tiny_ex):
        x = np.random.default_rng(5).normal(0, 1, (2, 8, 8)).astype(
            np.float32)
        want = np.asarray(tiny_ex.run(x).output_int8)
        res, samples = tiny_ex.run_profiled(x)
        np.testing.assert_array_equal(np.asarray(res.output_int8), want)
        assert len(samples) == len(tiny_ex.descs)
        for i, s in enumerate(samples):
            assert s["index"] == i and s["us"] >= 0 and s["bucket"] == 1
            assert s["kernel"] == tiny_ex.kernel_plan[i].kernel

    def test_run_batch_profiled_bitexact_vs_run_batch(self, tiny_ex):
        X = np.random.default_rng(6).normal(0, 1, (2, 2, 8, 8)).astype(
            np.float32)
        want = np.asarray(tiny_ex.run_batch(X, lanes=2).output_int8)
        res, samples = tiny_ex.run_batch_profiled(X, lanes=2)
        np.testing.assert_array_equal(np.asarray(res.output_int8), want)
        assert all(s["bucket"] == 2 for s in samples)

    def test_capabilities_gate(self, tiny_ex, tiny_art):
        assert tiny_ex.capabilities().profileable is True
        ref = create_executor("ref", tiny_art)
        assert ref.capabilities().profileable is False


# ---------------------------------------------------------------------------
# Launch phases: the executor's steps inside ``device_execute``
# ---------------------------------------------------------------------------
PHASES = ("quantise", "h2d", "enqueue", "device_wait", "d2h")
HOST_STEPS = ("pad", "quantise", "h2d", "enqueue", "d2h", "respond")


@pytest.fixture(scope="module")
def lenet_art():
    return pipeline.CompilerPipeline(graph.lenet5()).run()


def _lenet_x(i=0):
    x = np.random.default_rng(i).normal(0, 1, (1, 28, 28))
    return x.astype(np.float32)


class TestLaunchPhases:
    def test_collector_tiles_and_marks_host_steps(self):
        ph = LaunchPhases(7)
        t0 = ph.start()
        for name in PHASES:
            ph.mark(name, host=name != "device_wait")
        d2h_end = ph.spans[-1].t1
        t1 = ph.close()
        spans = ph.spans
        assert [s.name for s in spans] == list(PHASES)
        assert spans[0].t0 == t0 and spans[-1].t1 == t1 >= d2h_end
        assert all(a.t1 == b.t0 for a, b in zip(spans, spans[1:]))
        assert all(s.args["launch"] == 7 for s in spans)
        assert ["cpu_s" in s.args for s in spans] == \
            [n != "device_wait" for n in PHASES]
        assert all(0.0 <= s.args["cpu_s"] <= s.t1 - s.t0 for s in spans
                   if "cpu_s" in s.args)

    def test_no_collector_outside_a_traced_launch(self):
        ph = launch_phases()
        ph.mark("quantise")
        assert not isinstance(ph, LaunchPhases) and not ph.spans
        with collect_launch(3) as got:
            assert launch_phases() is got
        assert launch_phases() is ph
        with collect_launch(None) as untraced:
            assert untraced is ph and launch_phases() is ph

    @pytest.mark.parametrize("n", [1, 3], ids=["bucket1", "batch"])
    def test_traced_request_gets_phases_inside_device_execute(self,
                                                              lenet_art, n):
        ses = Session(lenet_art, trace=TraceConfig(sample_rate=1))
        try:
            X = np.stack([_lenet_x(i) for i in range(n)])
            if n == 1:
                ses.run(X[0])
            else:
                ses.run_batch(X)            # one launch at bucket 4
            traces = ses.tracer.traces()
        finally:
            ses.close()
        assert len(traces) == n
        for t in traces:
            (dx,) = [s for s in t.spans if s.name == "device_execute"]
            kids = [s for s in t.spans if s.name in PHASES]
            assert [s.name for s in kids] == list(PHASES)
            assert all(dx.t0 <= s.t0 <= s.t1 <= dx.t1 for s in kids)
            covered = sum(s.t1 - s.t0 for s in kids)
            assert covered >= 0.95 * (dx.t1 - dx.t0)
            launch = [s for s in t.spans if "launch" in s.args]
            assert {s.args["launch"] for s in launch} == \
                {dx.args["launch"]}
            want = {"device_execute", "respond", *PHASES} | \
                ({"pad"} if n > 1 else set())
            assert {s.name for s in launch} == want
            for s in launch:
                if s.name in HOST_STEPS:
                    assert 0.0 <= s.args["cpu_s"] <= s.t1 - s.t0 + 1e-6
                else:
                    assert "cpu_s" not in s.args

    def test_overlapped_launch_phases_tile_device_execute(self, lenet_art):
        """A launch enqueued behind one still on the device keeps its five
        phases, tiling ``device_execute``; ``enqueue`` carries ``ahead``."""
        ses = Session(lenet_art, trace=TraceConfig(sample_rate=1),
                      scheduler=SchedulerConfig(max_batch=2, max_wait_us=0.0,
                                                adaptive=False))
        ex = ses.executor()
        run = ex.run
        gate, entered = threading.Event(), threading.Event()

        def held(x):                  # the lone first launch waits here
            entered.set()
            assert gate.wait(timeout=120)
            return run(x)

        ex.run = held
        try:
            futs = [ses.submit(_lenet_x(0))]
            assert entered.wait(timeout=120)
            ex.run = run
            futs += [ses.submit(_lenet_x(i)) for i in range(1, 5)]
            gate.set()
            for f in futs:
                f.result(timeout=300)
        finally:
            ses.close()
        ahead = {}
        for f in futs:
            (dx,) = [s for s in f.trace.spans if s.name == "device_execute"]
            kids = [s for s in f.trace.spans if s.name in PHASES]
            assert [s.name for s in kids] == list(PHASES)
            assert kids[0].t0 == dx.t0 and kids[-1].t1 == dx.t1
            assert all(a.t1 == b.t0 for a, b in zip(kids, kids[1:]))
            assert {s.args["launch"] for s in kids} == {dx.args["launch"]}
            ahead[dx.args["launch"]] = kids[2].args["ahead"]
        # the lone launch whole, a full batch with another queued, and
        # that one enqueued behind it
        assert sorted(ahead.values()) == [0, 0, 1]

    @pytest.mark.parametrize("trace", [TraceConfig(sample_rate=2),
                                       TraceConfig(enabled=False)],
                             ids=["unsampled", "disabled"])
    def test_untraced_launch_records_no_phases(self, lenet_art, trace):
        ses = Session(lenet_art, trace=trace)
        ex = ses.executor()
        seen = []
        run = ex.run

        def spy(x):
            seen.append(launch_phases())
            return run(x)

        ex.run = spy
        try:
            first = ses.submit(_lenet_x(0))
            first.result(timeout=120)
            second = ses.submit(_lenet_x(1))
            second.result(timeout=120)
            traces = ses.tracer.traces()
        finally:
            ses.close()
        # sample_rate=2 traces the first request and not the second
        assert second.trace is None
        assert [isinstance(c, LaunchPhases) for c in seen] == \
            [trace.enabled, False]
        assert [t.trace_id for t in traces] == \
            ([first.trace_id] if trace.enabled else [])
        for t in traces:
            assert len({s.args["launch"] for s in t.spans
                        if "launch" in s.args}) == 1


class TestCalibration:
    @pytest.fixture(scope="class")
    def samples(self, tiny_ex):
        return profile_layers(tiny_ex, iters=2, warmup=1)

    def test_calibrate_does_not_worsen_layer_error(self, tiny_ex, samples):
        cal = perfmodel.calibrate(samples, tiny_ex.descs,
                                  dtype=tiny_ex.cfg.dtype)
        rep = fidelity_report(tiny_ex, samples, cal)
        assert np.isfinite(rep["err_uncal"]) and np.isfinite(rep["err_cal"])
        assert rep["err_cal"] <= rep["err_uncal"] + 1e-6
        assert len(rep["rows"]) == len(tiny_ex.descs)

    def test_profile_roundtrip_and_prediction(self, tiny_ex, samples):
        cal = perfmodel.calibrate(samples, tiny_ex.descs,
                                  dtype=tiny_ex.cfg.dtype)
        assert cal.samples == len(samples)
        cal2 = perfmodel.CalibrationProfile.from_dict(cal.to_dict())
        for s in samples:
            d = tiny_ex.descs[s["index"]]
            macs, sbytes = perfmodel.sample_features(d, tiny_ex.cfg.dtype)
            a = cal.predict_us(s["kernel"], macs, sbytes)
            b = cal2.predict_us(s["kernel"], macs, sbytes)
            assert a == b and a is not None and a > 0

    def test_select_kernel_accepts_calibration(self, tiny_ex, samples):
        cal = perfmodel.calibrate(samples, tiny_ex.descs,
                                  dtype=tiny_ex.cfg.dtype)
        for d in tiny_ex.descs:
            if d.unit not in ("CONV", "FC"):
                continue
            calk = perfmodel.select_kernel(d, dtype=tiny_ex.cfg.dtype,
                                           calibration=cal)
            # the calibrated choice is still a valid applicable kernel,
            # and the decision records that measured costs drove it
            assert calk.kernel
            assert "calibrated" in calk.reason


class TestReportCLI:
    def test_report_json_output(self, capsys):
        from repro.obs.__main__ import main
        rc = main(["report", "--model", "lenet5", "--iters", "1",
                   "--warmup", "1", "--json"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["model"] == "lenet5"
        assert doc["rows"] and "err_uncal" in doc and "err_cal" in doc
        for row in doc["rows"]:
            assert {"unit", "kernel", "measured_us",
                    "modeled_uncal_us"} <= set(row)
