"""Fault-tolerance tests: injection harness, supervised dispatch, breaker.

The acceptance bar: under injected faults (crash / hang / slow / corrupted
output / poisoned arena) every admitted future RESOLVES — with the correct
result after supervisor retries, or a typed ``BackendFaultError`` carrying
the causal exception — and the dispatcher thread survives to serve the next
request.  Recoverable faults heal bit-exactly (the arena checksum restores
the pristine weight image); an open circuit breaker sheds fast with
``CircuitOpenError`` or routes to the fallback backend with results marked
``degraded=True`` that stay within the repo's parity budgets.
"""

import dataclasses
import io
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core import graph, pipeline, tolerances
from repro.core.executor import ExecResult, ExecutorCapabilities
from repro.runtime import (BackendFaultError, CircuitOpenError, FaultPlan,
                           FaultSpec, FaultyExecutor, InjectedFaultError,
                           LaunchTimeoutError, Session, SchedulerConfig,
                           create_executor)
from repro.serve.client import (ClientTimeoutError, ServeClient,
                                UnavailableError)
from repro.serve.http import make_server

BACKENDS = ("baremetal", "ref")


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
def tiny_inputs():
    rng = np.random.default_rng(23)
    return rng.normal(0, 1, (4, 2, 8, 8)).astype(np.float32)


@pytest.fixture(scope="module")
def real_ex(tiny_art, tiny_inputs):
    """One real executor per backend, shared across cases (compiled programs
    amortise); each case wraps it in a fresh ``FaultyExecutor``.  The
    bucket the batched cases launch is compiled here, so no case's watchdog
    has to cover a compile, whichever cases ran before it."""
    exs = {b: create_executor(b, tiny_art) for b in BACKENDS}
    for ex in exs.values():
        ex.run_batch(tiny_inputs)
    return exs


@pytest.fixture(scope="module")
def baselines(real_ex, tiny_inputs):
    """Fault-free golden outputs per backend (scheduler parity is bit-exact
    versus sequential ``run``, so these anchor every recovery check)."""
    return {b: np.stack([np.asarray(real_ex[b].run(x).output_int8)
                         for x in tiny_inputs]) for b in BACKENDS}


def _cfg(**kw) -> SchedulerConfig:
    """Test-speed supervisor defaults: fast backoff, bounded teardown, no
    breaker unless the case is about the breaker."""
    base = dict(max_retries=2, retry_backoff_s=0.001,
                breaker_threshold=None, close_timeout_s=5.0)
    base.update(kw)
    return SchedulerConfig(**base)


def _faulty_session(tiny_art, inner, plan, cfg):
    """Session whose resident net executes through ``FaultyExecutor(inner)``."""
    ses = Session(tiny_art, scheduler=cfg)
    faulty = FaultyExecutor(inner, plan)
    ses._resolve(None).executor = faulty
    return ses, faulty


class _FlakyStub:
    """Backend stub that raises ``exc`` for its first ``fail_times`` calls
    (run and run_batch alike) and then recovers; records call times so the
    backoff schedule is observable."""

    input_dims = (1, 2, 8, 8)

    def __init__(self, fail_times=0, exc=None):
        self.fail_times = fail_times
        self.exc = exc or RuntimeError("flaky backend")
        self.calls = []

    def _maybe_fail(self):
        self.calls.append(time.perf_counter())
        if len(self.calls) <= self.fail_times:
            raise self.exc

    def run(self, x):
        self._maybe_fail()
        z = np.zeros(3)
        return ExecResult(z.astype(np.int8), z.astype(np.float32))

    def run_batch(self, X, lanes=None):
        self._maybe_fail()
        z = np.zeros((X.shape[0], 3))
        return ExecResult(z.astype(np.int8), z.astype(np.float32))

    def capabilities(self):
        return ExecutorCapabilities(native_batching=True)


def _x(i=0):
    x = np.zeros((2, 8, 8), np.float32)
    x[0, 0, 0] = float(i)
    return x


# ---------------------------------------------------------------------------
# FaultPlan / FaultyExecutor units: validation, determinism, delegation
# ---------------------------------------------------------------------------
class TestFaultPlanUnits:
    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultSpec("meltdown")

    def test_probability_range_checked(self):
        with pytest.raises(ValueError, match="probability"):
            FaultSpec("error", probability=1.5)

    def test_schedule_fires_on_exact_call_index(self):
        plan = FaultPlan(specs=(FaultSpec("error", schedule=(2,)),))
        faulty = FaultyExecutor(_FlakyStub(), plan)
        faulty.run(_x())
        faulty.run(_x())
        with pytest.raises(InjectedFaultError) as ei:
            faulty.run(_x())
        assert ei.value.kind == "error" and ei.value.call_index == 2
        faulty.run(_x())                     # only the scheduled index fires
        assert faulty.faults_injected == 1
        assert faulty.faults_by_kind["error"] == 1

    def test_probability_injection_is_seed_deterministic(self):
        plan = FaultPlan(specs=(FaultSpec("error", probability=0.3),), seed=9)

        def fault_indices():
            faulty = FaultyExecutor(_FlakyStub(), plan)
            hit = []
            for i in range(40):
                try:
                    faulty.run(_x())
                except InjectedFaultError:
                    hit.append(i)
            return hit

        a, b = fault_indices(), fault_indices()
        assert a and a == b                  # same seed -> same storm

    def test_max_faults_caps_injections(self):
        plan = FaultPlan(specs=(
            FaultSpec("error", probability=1.0, max_faults=2),))
        faulty = FaultyExecutor(_FlakyStub(), plan)
        for _ in range(2):
            with pytest.raises(InjectedFaultError):
                faulty.run(_x())
        for _ in range(5):                   # storm over, calls pass through
            faulty.run(_x())
        assert faulty.faults_injected == 2

    def test_delegates_executor_surface(self, real_ex):
        inner = real_ex["baremetal"]
        faulty = FaultyExecutor(inner, FaultPlan(specs=()))
        assert faulty.input_dims == inner.input_dims
        # the same capabilities, but the launch is never split past the
        # injection point
        assert faulty.capabilities() == dataclasses.replace(
            inner.capabilities(), split_launch=False)
        assert inner.capabilities().split_launch
        assert faulty.arena_ok()             # __getattr__ reaches the arena API


# ---------------------------------------------------------------------------
# Fault matrix: kind x backend x single/batched — every future resolves
# ---------------------------------------------------------------------------
_MATRIX_CFG = {
    "error": {},
    "hang": dict(watchdog_timeout_s=0.5, max_retries=1),
    "slow": dict(max_retries=0),
    "corrupt_output": dict(max_retries=0),
    "corrupt_arena": {},
}


class TestFaultMatrix:
    @pytest.mark.parametrize("batched", [False, True],
                             ids=["single", "batched"])
    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("kind", list(_MATRIX_CFG))
    def test_fault_resolves_and_recovers(self, kind, backend, batched,
                                         tiny_art, real_ex, baselines,
                                         tiny_inputs):
        spec_kw = {"delay_s": 0.05} if kind == "slow" else {}
        plan = FaultPlan(specs=(
            FaultSpec(kind, schedule=(0,), max_faults=1, **spec_kw),), seed=7)
        ses, faulty = _faulty_session(tiny_art, real_ex[backend], plan,
                                      _cfg(**_MATRIX_CFG[kind]))
        try:
            if batched:
                got = np.asarray(ses.run_batch(tiny_inputs).output_int8)
                want = baselines[backend]
            else:
                got = np.asarray(ses.run(tiny_inputs[0]).output_int8)
                want = baselines[backend][0]
            assert faulty.faults_injected == 1
            if kind == "corrupt_output":
                # the one silent fault: it resolves, with wrong bytes
                assert got.shape == want.shape
                assert not np.array_equal(got, want)
            else:
                np.testing.assert_array_equal(got, want)
            assert real_ex[backend].arena_ok()   # never leaks poison
            snap = ses.stats().snapshot()
            assert snap["faults_injected"] == 1
            if kind in ("error", "hang", "corrupt_arena"):
                assert snap["backend_failures"] >= 1
                assert snap["retries"] >= 1
            if kind == "hang":
                assert snap["watchdog_timeouts"] >= 1
            if kind == "corrupt_arena":
                assert snap["arena_resets"] >= 1
        finally:
            faulty.release_hangs()
            ses.close()


# ---------------------------------------------------------------------------
# Supervisor: retry/backoff ordering, typed exhaustion, watchdog
# ---------------------------------------------------------------------------
class TestRetrySupervision:
    def test_backoff_gaps_grow_monotonically(self, tiny_art):
        stub = _FlakyStub(fail_times=2)
        ses = Session(tiny_art,
                      scheduler=_cfg(max_retries=2, retry_backoff_s=0.05))
        ses._resolve(None).executor = stub
        try:
            res = ses.run(_x())
            assert np.asarray(res.output_int8).shape == (3,)
            assert len(stub.calls) == 3      # 1 attempt + 2 retries
            g1 = stub.calls[1] - stub.calls[0]
            g2 = stub.calls[2] - stub.calls[1]
            assert g1 >= 0.05 * 0.8          # base minus max jitter
            assert g2 > g1                   # exponential beats the jitter
            snap = ses.stats().snapshot()
            assert snap["retries"] == 2 and snap["backend_failures"] == 2
        finally:
            ses.close()

    def test_exhausted_retries_fail_typed_with_cause(self, tiny_art):
        boom = RuntimeError("device wedged")
        stub = _FlakyStub(fail_times=999, exc=boom)
        ses = Session(tiny_art, scheduler=_cfg(max_retries=1))
        ses._resolve(None).executor = stub
        try:
            with pytest.raises(BackendFaultError) as ei:
                ses.run(_x())
            assert ei.value.attempts == 2
            assert ei.value.cause is boom and ei.value.__cause__ is boom
        finally:
            ses.close()

    def test_watchdog_abandons_hung_launch(self, tiny_art):
        plan = FaultPlan(specs=(FaultSpec("hang", schedule=(0,)),))
        ses, faulty = _faulty_session(
            tiny_art, _FlakyStub(), plan,
            _cfg(watchdog_timeout_s=0.3, max_retries=0))
        try:
            t0 = time.perf_counter()
            with pytest.raises(BackendFaultError) as ei:
                ses.run(_x())
            assert time.perf_counter() - t0 < 10.0   # never the full hang
            assert isinstance(ei.value.cause, LaunchTimeoutError)
            assert ses.stats().snapshot()["watchdog_timeouts"] == 1
            assert np.asarray(ses.run(_x()).output_int8).shape == (3,)
        finally:
            faulty.release_hangs()
            ses.close()


# ---------------------------------------------------------------------------
# Regression: an executor exception mid-batch fails ONLY that batch's
# futures (with the causal exception) and the dispatcher survives
# ---------------------------------------------------------------------------
class TestMidBatchFailure:
    def test_batch_futures_carry_cause_dispatcher_survives(self, tiny_art):
        boom = ValueError("bad descriptor")
        stub = _FlakyStub(fail_times=1, exc=boom)
        ses = Session(tiny_art, scheduler=_cfg(max_retries=0))
        n = ses._resolve(None)
        n.executor = stub
        try:
            xs = [_x(i) for i in range(3)]
            futs = ses._scheduler.submit_many(
                n, [ses._check_input(n, x) for x in xs])
            for f in futs:
                with pytest.raises(BackendFaultError) as ei:
                    f.result(timeout=60)
                assert ei.value.cause is boom
                assert ei.value.attempts == 1
            assert len(stub.calls) == 1      # one coalesced attempt, no retry
            # the dispatcher thread survived: the next submit is served
            res = ses.run(_x())
            assert np.asarray(res.output_int8).shape == (3,)
            assert ses.stats().snapshot()["backend_failures"] == 1
        finally:
            ses.close()


# ---------------------------------------------------------------------------
# Arena integrity: checksum detects poison, reset restores bit-exactly
# ---------------------------------------------------------------------------
class TestArenaIntegrity:
    def test_checksum_detects_and_reset_restores(self, real_ex, baselines,
                                                 tiny_inputs):
        ex = real_ex["baremetal"]
        assert ex.arena_ok()
        off, blob = ex._preload[-1]
        ex.arena0[off] ^= 0xFF               # one flipped weight byte
        assert not ex.arena_ok()
        ex.reset_arena()
        assert ex.arena_ok()
        np.testing.assert_array_equal(
            np.asarray(ex.run(tiny_inputs[0]).output_int8),
            baselines["baremetal"][0])

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_poisoned_arena_heals_bitexact_end_to_end(self, backend, tiny_art,
                                                      real_ex, baselines,
                                                      tiny_inputs):
        plan = FaultPlan(specs=(
            FaultSpec("corrupt_arena", schedule=(0,), max_faults=1),))
        ses, faulty = _faulty_session(tiny_art, real_ex[backend], plan,
                                      _cfg(max_retries=1))
        try:
            got = np.asarray(ses.run(tiny_inputs[0]).output_int8)
            np.testing.assert_array_equal(got, baselines[backend][0])
            assert real_ex[backend].arena_ok()
            snap = ses.stats().snapshot()
            assert snap["arena_resets"] == 1 and snap["retries"] == 1
        finally:
            ses.close()


# ---------------------------------------------------------------------------
# Circuit breaker: closed -> open -> half-open probe -> closed
# ---------------------------------------------------------------------------
class TestCircuitBreaker:
    def _session(self, tiny_art, fail_times, **cfg_kw):
        stub = _FlakyStub(fail_times=fail_times)
        cfg = _cfg(max_retries=0, breaker_threshold=2, **cfg_kw)
        ses = Session(tiny_art, scheduler=cfg)
        ses._resolve(None).executor = stub
        return ses, stub

    def test_opens_after_threshold_and_sheds(self, tiny_art):
        ses, _ = self._session(tiny_art, 999, breaker_reset_s=60.0)
        try:
            for _ in range(2):
                with pytest.raises(BackendFaultError):
                    ses.run(_x())
            net = ses._resolve(None)
            assert ses.scheduler.circuit_state(net) == "open"
            with pytest.raises(CircuitOpenError) as ei:
                ses.submit(_x())             # shed synchronously, never queued
            assert 0 < ei.value.retry_after_s <= 60.0
            assert ses.health()["tiny"] == {
                "state": "circuit_open", "circuit": "open", "fallback": None}
            snap = ses.stats().snapshot()
            assert snap["circuit_opens"] == 1
            assert snap["circuit_rejected"] == 1
            assert snap["circuit_state"] == 2
            # the serve client maps the shed to a typed 503
            with pytest.raises(UnavailableError) as ei:
                ServeClient(ses).infer_async(None, _x())
            assert ei.value.status == 503 and ei.value.retry_after_s > 0
        finally:
            ses.close()

    def test_half_open_probe_closes_on_success(self, tiny_art):
        ses, stub = self._session(tiny_art, 2, breaker_reset_s=0.15)
        try:
            for _ in range(2):
                with pytest.raises(BackendFaultError):
                    ses.run(_x())
            net = ses._resolve(None)
            assert ses.scheduler.circuit_state(net) == "open"
            time.sleep(0.2)                  # past the reset window
            res = ses.run(_x())              # admitted as the half-open probe
            assert np.asarray(res.output_int8).shape == (3,)
            assert ses.scheduler.circuit_state(net) == "closed"
            assert ses.health()["tiny"]["state"] == "healthy"
            assert len(stub.calls) == 3
        finally:
            ses.close()

    def test_failed_probe_reopens_then_recovers(self, tiny_art):
        ses, _ = self._session(tiny_art, 3, breaker_reset_s=0.15)
        try:
            net = ses._resolve(None)
            for _ in range(2):
                with pytest.raises(BackendFaultError):
                    ses.run(_x())
            time.sleep(0.2)
            with pytest.raises(BackendFaultError):
                ses.run(_x())                # probe fails -> reopen
            assert ses.scheduler.circuit_state(net) == "open"
            time.sleep(0.2)
            ses.run(_x())                    # second probe heals
            assert ses.scheduler.circuit_state(net) == "closed"
            assert ses.stats().snapshot()["circuit_opens"] == 2
        finally:
            ses.close()


# ---------------------------------------------------------------------------
# Degraded mode: open breaker + fallback backend -> marked, within budget
# ---------------------------------------------------------------------------
class TestFallbackDegraded:
    def test_fallback_serves_degraded_and_parity_holds(self, tiny_art,
                                                       real_ex, baselines,
                                                       tiny_inputs):
        plan = FaultPlan(specs=(FaultSpec("error", probability=1.0),), seed=1)
        ses = Session(scheduler=_cfg(max_retries=0, breaker_threshold=1,
                                     breaker_reset_s=60.0))
        ses.load(tiny_art, fallback_backend="ref", fault_plan=plan)
        try:
            with pytest.raises(BackendFaultError) as ei:
                ses.run(tiny_inputs[0])      # primary fails, breaker opens
            assert isinstance(ei.value.cause, InjectedFaultError)
            res = ses.run(tiny_inputs[1])    # routed to the ref fallback
            assert res.degraded is True
            got = np.asarray(res.output_int8)
            np.testing.assert_array_equal(got, baselines["ref"][1])
            # parity versus the primary path stays inside the repo's budget
            np.testing.assert_array_equal(got, baselines["baremetal"][1])
            tolerances.assert_close(
                res.output, real_ex["baremetal"].run(tiny_inputs[1]).output,
                tolerances.net_tolerance(tiny_art.kernel_plan),
                context="degraded fallback")
            assert ses.health()["tiny"] == {
                "state": "degraded", "circuit": "open", "fallback": "ref"}
            snap = ses.stats().snapshot()
            assert snap["degraded"] == 1 and snap["circuit_opens"] == 1
            client = ServeClient(ses)
            doc = client.healthz()
            assert doc["status"] == "degraded"
            assert doc["net_states"]["tiny"] == "degraded"
            text = client.metrics_text()
            for needle in ("repro_serve_retries_total",
                           "repro_serve_faults_injected_total",
                           "repro_serve_degraded_responses_total",
                           'repro_serve_circuit_state{net="tiny"} 2'):
                assert needle in text
        finally:
            ses.close()


# ---------------------------------------------------------------------------
# Client-side timeout: a wedged server never blocks the caller forever
# ---------------------------------------------------------------------------
class TestClientTimeout:
    def test_timeout_s_bounds_the_wait(self, tiny_art):
        plan = FaultPlan(specs=(
            FaultSpec("hang", schedule=(0,), max_faults=1),))
        # watchdog left at its generous floor: only the CLIENT timeout saves us
        ses, faulty = _faulty_session(tiny_art, _FlakyStub(), plan,
                                      _cfg(max_retries=0))
        client = ServeClient(ses, timeout_s=0.2)
        try:
            t0 = time.perf_counter()
            with pytest.raises(ClientTimeoutError):
                client.infer(None, _x())
            assert time.perf_counter() - t0 < 5.0
            faulty.release_hangs()           # hung attempt raises; moves on
            res = client.infer(None, _x())
            assert np.asarray(res.output_int8).shape == (3,)
        finally:
            faulty.release_hangs()
            ses.close()


# ---------------------------------------------------------------------------
# HTTP surface: Retry-After, degraded marker, unhealthy /healthz
# ---------------------------------------------------------------------------
def _serve(ses):
    srv = make_server(ses, port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    host, port = srv.server_address
    return srv, f"http://{host}:{port}"


def _post_json(base, net="tiny"):
    body = json.dumps({"input": np.zeros((2, 8, 8)).tolist()}).encode()
    req = urllib.request.Request(f"{base}/v1/infer/{net}", data=body,
                                 headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=60)


class TestHTTPFaultSurface:
    def test_circuit_open_503_carries_retry_after(self, tiny_art):
        ses = Session(tiny_art, scheduler=_cfg(max_retries=0,
                                               breaker_threshold=1,
                                               breaker_reset_s=30.0))
        ses._resolve(None).executor = _FlakyStub(fail_times=999)
        srv, base = _serve(ses)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post_json(base)
            assert ei.value.code == 500      # retries exhausted
            assert json.load(ei.value)["error"]["code"] == "backend_fault"
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post_json(base)             # breaker now open: shed fast
            assert ei.value.code == 503
            assert int(ei.value.headers["Retry-After"]) >= 1
            err = json.load(ei.value)["error"]
            assert err["code"] == "circuit_open"
            assert err["retry_after_s"] > 0
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/healthz", timeout=60)
            assert ei.value.code == 503      # orchestrators see the outage
            doc = json.load(ei.value)
            assert doc["status"] == "degraded"
            assert doc["net_states"]["tiny"] == "circuit_open"
            text = urllib.request.urlopen(
                f"{base}/metrics", timeout=60).read().decode()
            assert 'repro_serve_circuit_state{net="tiny"} 2' in text
            assert 'repro_serve_circuit_opens_total{net="tiny"} 1' in text
        finally:
            srv.shutdown()
            srv.server_close()
            ses.close()

    def test_degraded_response_marked_in_body_and_header(self, tiny_art):
        ses = Session(tiny_art, scheduler=_cfg(max_retries=0,
                                               breaker_threshold=1,
                                               breaker_reset_s=30.0))
        n = ses._resolve(None)
        n.executor = _FlakyStub(fail_times=999)
        n.fallback = _FlakyStub()
        n.fallback_backend = "stub"
        srv, base = _serve(ses)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post_json(base)             # opens the breaker
            assert ei.value.code == 500
            r = _post_json(base)             # fallback absorbs traffic
            assert r.status == 200
            assert r.headers["X-Repro-Degraded"] == "1"
            doc = json.loads(r.read())
            assert doc["degraded"] is True
        finally:
            srv.shutdown()
            srv.server_close()
            ses.close()


# ---------------------------------------------------------------------------
# Observability on the fault paths: every admitted request completes
# EXACTLY ONE trace, and the trace records the fault-plane events
# ---------------------------------------------------------------------------
class TestTraceFaultPaths:
    def test_retry_records_failure_event_and_backoff_span(self, tiny_art):
        stub = _FlakyStub(fail_times=1)
        ses = Session(tiny_art, scheduler=_cfg(max_retries=1))
        ses._resolve(None).executor = stub
        try:
            ses.run(_x())
            (t,) = ses.tracer.traces()
            assert t.status == "ok" and t.finished
            evs = [name for name, _, _ in t.events]
            assert evs.count("launch_failure") == 1
            names = {s.name for s in t.spans}
            assert "backoff" in names        # the retry waited out the base
            # only the SUCCESSFUL attempt gets a device_execute span, and
            # it is marked as the second attempt
            (de,) = [s for s in t.spans if s.name == "device_execute"]
            assert de.args["attempt"] == 2
        finally:
            ses.close()

    def test_watchdog_fire_event_on_hung_launch(self, tiny_art):
        plan = FaultPlan(specs=(FaultSpec("hang", schedule=(0,)),))
        ses, faulty = _faulty_session(
            tiny_art, _FlakyStub(), plan,
            _cfg(watchdog_timeout_s=0.3, max_retries=0))
        try:
            with pytest.raises(BackendFaultError):
                ses.run(_x())
            (t,) = ses.tracer.traces()
            assert t.status == "error" and t.error == "BackendFaultError"
            evs = [name for name, _, _ in t.events]
            assert "watchdog_fire" in evs and "launch_failure" in evs
        finally:
            faulty.release_hangs()
            ses.close()

    def test_arena_reset_event_on_poisoned_arena(self, tiny_art, real_ex):
        plan = FaultPlan(specs=(
            FaultSpec("corrupt_arena", schedule=(0,), max_faults=1),))
        ses, _ = _faulty_session(tiny_art, real_ex["baremetal"], plan,
                                 _cfg(max_retries=1))
        try:
            ses.run(_x())
            (t,) = ses.tracer.traces()
            assert t.status == "ok"
            evs = [name for name, _, _ in t.events]
            assert "arena_reset" in evs and "launch_failure" in evs
        finally:
            ses.close()

    def test_circuit_transitions_recorded_globally(self, tiny_art):
        stub = _FlakyStub(fail_times=2)
        ses = Session(tiny_art,
                      scheduler=_cfg(max_retries=0, breaker_threshold=2,
                                     breaker_reset_s=0.15))
        ses._resolve(None).executor = stub
        try:
            for _ in range(2):
                with pytest.raises(BackendFaultError):
                    ses.run(_x())
            time.sleep(0.2)                  # past the reset window
            ses.run(_x())                    # half-open probe heals
            instants = {e["name"]
                        for e in ses.tracer.chrome_trace()["traceEvents"]
                        if e["ph"] == "i"}
            assert {"circuit_open", "circuit_half_open",
                    "circuit_closed"} <= instants
        finally:
            ses.close()

    def test_exactly_one_trace_per_request_under_retries(self, tiny_art):
        stub = _FlakyStub(fail_times=2)
        ses = Session(tiny_art, scheduler=_cfg(max_retries=2))
        ses._resolve(None).executor = stub
        try:
            futs = [ses.submit(_x(i)) for i in range(4)]
            for f in futs:
                f.result(timeout=60)
            traces = ses.tracer.traces()
            assert sorted(t.trace_id for t in traces) == \
                sorted(f.trace_id for f in futs)
            assert all(t.finished and t.status == "ok" for t in traces)
        finally:
            ses.close()
