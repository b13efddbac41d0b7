"""Serving front-end tests: HTTP surface, payload codecs, metrics.

End-to-end over a real socket on an ephemeral port: infer round-trips are
bit-exact versus ``Session.run``, unknown nets 404, malformed payloads 400,
a saturated queue 429s, and ``/metrics`` parses as Prometheus text.  The
in-process ``ServeClient`` drives the same code path minus the socket.
"""

import io
import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core import graph, pipeline
from repro.core.executor import ExecResult, ExecutorCapabilities
from repro.runtime import Session, SchedulerConfig
from repro.serve import payload
from repro.serve.client import (BadRequestError, NotFoundError,
                                OverloadedError, ServeClient)
from repro.serve.http import make_server


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


@pytest.fixture()
def served(tiny_art):
    """(base_url, session, server) over an ephemeral port; torn down after."""
    ses = Session(tiny_art, scheduler=SchedulerConfig(max_queue=64))
    srv = make_server(ses, port=0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    host, port = srv.server_address
    yield f"http://{host}:{port}", ses, srv
    srv.shutdown()
    srv.server_close()
    ses.close()


def _post(url, body, headers, timeout=60):
    req = urllib.request.Request(url, data=body, headers=headers)
    return urllib.request.urlopen(req, timeout=timeout)


_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{((?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*",?)*)\})?'
    r' (-?(?:[0-9]*\.?[0-9]+(?:e[+-]?[0-9]+)?|\+Inf|-Inf|NaN))$')
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape(v: str) -> str:
    # single left-to-right pass: sequential str.replace corrupts r"\\n"
    return re.sub(r"\\(.)", lambda m: "\n" if m.group(1) == "n"
                  else m.group(1), v)


def _parse_prometheus(text: str):
    """Strict parser for the exposition format subset /metrics emits.

    Returns ``(families, samples)`` — ``{name: type}`` from the ``# TYPE``
    lines and ``[(name, labels_dict, float_value)]`` — and asserts the
    contract along the way: every family has # HELP and # TYPE, every
    sample line parses, and every sample belongs to a declared family
    (summary children ``_sum``/``_count``/quantile, histogram children
    ``_bucket``/``_sum``/``_count``)."""
    helped, families, samples = set(), {}, []
    for line in text.strip().splitlines():
        if line.startswith("# HELP "):
            name = line.split(" ", 3)[2]
            assert name not in helped, f"duplicate HELP for {name}"
            helped.add(name)
            continue
        if line.startswith("# TYPE "):
            _, _, name, mtype = line.split(" ", 3)
            assert name in helped, f"TYPE before HELP for {name}"
            assert name not in families, f"duplicate TYPE for {name}"
            assert mtype in ("counter", "gauge", "summary", "histogram")
            families[name] = mtype
            continue
        assert not line.startswith("#"), f"unknown comment line: {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable metric line: {line!r}"
        name, labels_raw, value = m.groups()
        labels = {k: _unescape(v)
                  for k, v in _LABEL_RE.findall(labels_raw or "")}
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in families:
                base = name[:-len(suffix)]
        assert base in families, f"sample {name!r} has no TYPE declaration"
        mtype = families[base]
        if base != name:
            assert mtype in ("summary", "histogram"), \
                f"{name!r} child of non-aggregate family {base!r}"
            assert name.endswith("_bucket") is (mtype == "histogram") \
                or not name.endswith("_bucket")
        if name.endswith("_bucket"):
            assert "le" in labels, f"histogram bucket without le: {line!r}"
        if "quantile" in labels:
            assert mtype == "summary"
        samples.append((name, labels, float(value)))
    assert families, "no metric families rendered"
    return families, samples


class TestHTTPEndToEnd:
    def test_json_infer_bitexact_vs_session_run(self, served):
        base, ses, _ = served
        x = np.random.default_rng(0).normal(0, 1, (2, 8, 8)).astype(np.float32)
        want = np.asarray(ses.run(x).output_int8)
        r = _post(f"{base}/v1/infer/tiny",
                  json.dumps({"input": x.tolist()}).encode(),
                  {"Content-Type": "application/json"})
        doc = json.loads(r.read())
        assert r.status == 200
        np.testing.assert_array_equal(
            np.asarray(doc["output_int8"], np.int8), want)
        assert doc["argmax"] == int(np.argmax(want))
        assert doc["latency_us"] > 0

    def test_npy_infer_roundtrip_bitexact(self, served):
        base, ses, _ = served
        x = np.random.default_rng(1).normal(0, 1, (2, 8, 8)).astype(np.float32)
        want = np.asarray(ses.run(x).output_int8)
        buf = io.BytesIO()
        np.save(buf, x)
        r = _post(f"{base}/v1/infer/tiny?priority=1&deadline_us=60000000",
                  buf.getvalue(),
                  {"Content-Type": "application/x-npy",
                   "Accept": "application/x-npy"})
        got = np.load(io.BytesIO(r.read()))
        np.testing.assert_array_equal(got, want)

    def test_unknown_net_404(self, served):
        base, _, _ = served
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"{base}/v1/infer/nope", b'{"input": [0]}',
                  {"Content-Type": "application/json"})
        assert ei.value.code == 404
        err = json.loads(ei.value.read())["error"]
        assert err["code"] == "not_found" and "nope" in err["message"]

    def test_unknown_route_404(self, served):
        base, _, _ = served
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/v2/whatever", timeout=30)
        assert ei.value.code == 404

    @pytest.mark.parametrize("body,ctype", [
        (b"not json", "application/json"),
        (b'{"noinput": 1}', "application/json"),
        (b'{"input": [1], "dtype": "complex128"}', "application/json"),
        (b"\x00\x01garbage", "application/x-npy"),
        (b'{"input": [1,2], "priority": "urgent"}', "application/json"),
    ])
    def test_malformed_payload_400(self, served, body, ctype):
        base, _, _ = served
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"{base}/v1/infer/tiny", body, {"Content-Type": ctype})
        assert ei.value.code == 400
        assert json.loads(ei.value.read())["error"]["code"] == "bad_request"

    def test_wrong_input_size_400(self, served):
        base, _, _ = served
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"{base}/v1/infer/tiny",
                  json.dumps({"input": [1.0, 2.0]}).encode(),
                  {"Content-Type": "application/json"})
        assert ei.value.code == 400

    def test_saturated_queue_429(self, served):
        base, ses, _ = served
        net = ses._resolve(None)
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

        real = net.executor
        net.executor = _Stall()
        try:
            x = np.zeros((2, 8, 8), np.float32)
            first = ses.submit(x)                  # occupies the dispatcher
            assert entered.wait(timeout=60)
            # fill the queue to max_queue, then the HTTP submit must 429
            backlog = [ses.submit(x) for _ in range(64)]
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(f"{base}/v1/infer/tiny",
                      json.dumps({"input": x.tolist()}).encode(),
                      {"Content-Type": "application/json"})
            assert ei.value.code == 429
            err = json.loads(ei.value.read())["error"]
            assert err["code"] == "overloaded"
            # the rejected request is still correlatable: the 429 carries
            # the trace id in the body AND the response header, and the
            # server-side trace completed with status "rejected"
            assert err["trace_id"]
            assert ei.value.headers["X-Repro-Trace-Id"] == err["trace_id"]
            rej = [t for t in ses.tracer.traces()
                   if t.trace_id == err["trace_id"]]
            assert len(rej) == 1 and rej[0].status == "rejected"
            assert ses.stats().rejected >= 1
        finally:
            blocked.set()
            for f in [first] + backlog:
                f.result(timeout=120)
            net.executor = real

    def test_nets_endpoint(self, served):
        base, _, _ = served
        doc = json.loads(urllib.request.urlopen(f"{base}/v1/nets",
                                                timeout=30).read())
        (net,) = doc["nets"]
        assert net["name"] == "tiny" and net["backend"] == "baremetal"
        assert net["input_shape"] == [2, 8, 8] and net["output_elems"] == 3

    def test_healthz(self, served):
        base, _, _ = served
        doc = json.loads(urllib.request.urlopen(f"{base}/healthz",
                                                timeout=30).read())
        assert doc["status"] == "ok" and doc["nets"] == 1

    def test_metrics_parse_prometheus(self, served):
        """Strict exposition-format round-trip: every sample line parses,
        belongs to a # HELP + # TYPE declared family (summaries via their
        quantile/_sum/_count children, histograms via _bucket/_sum/_count),
        and every histogram is cumulative ending at le="+Inf" == _count."""
        base, ses, _ = served
        ses.run(np.zeros((2, 8, 8), np.float32))
        text = urllib.request.urlopen(f"{base}/metrics",
                                      timeout=30).read().decode()
        families, samples = _parse_prometheus(text)
        names = {s[0] for s in samples}
        for want in ("repro_serve_requests_total", "repro_serve_queue_depth",
                     "repro_serve_latency_us", "repro_serve_rejected_total",
                     "repro_serve_shed_total", "repro_serve_phase_us_bucket"):
            assert want in names, f"missing metric {want}"
        assert families["repro_serve_latency_us"] == "summary"
        assert families["repro_serve_phase_us"] == "histogram"
        # summary invariant: _count samples accompany the quantiles
        counts = [v for n, lbl, v in samples
                  if n == "repro_serve_latency_us_count"]
        assert counts and all(c >= 1 for c in counts)
        # histogram invariant: per (net, phase) series, buckets are
        # cumulative, ordered by le, ending at +Inf == _count
        series = {}
        for n, lbl, v in samples:
            if n == "repro_serve_phase_us_bucket":
                key = (lbl["net"], lbl["phase"])
                le = float("inf") if lbl["le"] == "+Inf" else float(lbl["le"])
                series.setdefault(key, []).append((le, v))
        assert series, "no phase histogram series rendered"
        for key, buckets in series.items():
            les = [le for le, _ in buckets]
            cums = [c for _, c in buckets]
            assert les == sorted(les) and les[-1] == float("inf")
            assert cums == sorted(cums), f"non-cumulative buckets for {key}"
            (count,) = [v for n, lbl, v in samples
                        if n == "repro_serve_phase_us_count"
                        and (lbl["net"], lbl["phase"]) == key]
            assert cums[-1] == count
        m = re.search(r'repro_serve_requests_total\{net="tiny"\} (\d+)', text)
        assert m and int(m.group(1)) >= 1

    def test_metrics_windowed_and_slo_families(self, served):
        """The windowed-telemetry histogram + gauges and the SLO state/burn
        gauges render under the same strict exposition contract."""
        from repro.obs.slo import SloObjective, SloPolicy
        base, ses, _ = served
        ses.attach_slo([SloPolicy(net="tiny", objectives=(
            SloObjective(kind="latency", quantile=0.99, threshold_us=60e6),
            SloObjective(kind="error_rate", budget=0.5),))])
        ses.run(np.zeros((2, 8, 8), np.float32))
        text = urllib.request.urlopen(f"{base}/metrics",
                                      timeout=30).read().decode()
        families, samples = _parse_prometheus(text)
        assert families["repro_serve_request_latency_us"] == "histogram"
        for fam in ("repro_serve_window_latency_us",
                    "repro_serve_window_error_rate",
                    "repro_serve_window_goodput_rps",
                    "repro_serve_window_rps",
                    "repro_serve_slo_state", "repro_serve_slo_burn_rate"):
            assert families[fam] == "gauge", f"missing gauge family {fam}"
        # every-request histogram: cumulative, ends at +Inf == _count
        buckets = sorted(
            ((float("inf") if lbl["le"] == "+Inf" else float(lbl["le"])), v)
            for n, lbl, v in samples
            if n == "repro_serve_request_latency_us_bucket"
            and lbl["net"] == "tiny")
        cums = [c for _, c in buckets]
        assert buckets[-1][0] == float("inf") and cums == sorted(cums)
        (count,) = [v for n, lbl, v in samples
                    if n == "repro_serve_request_latency_us_count"
                    and lbl["net"] == "tiny"]
        assert cums[-1] == count >= 1
        # windowed quantile gauges: one series per (window, quantile)
        wq = {(lbl["window"], lbl["q"])
              for n, lbl, v in samples
              if n == "repro_serve_window_latency_us" and lbl["net"] == "tiny"}
        assert {q for _, q in wq} == {"0.5", "0.9", "0.99"}
        assert len({w for w, _ in wq}) == 3          # 30s/5m/1h ladder
        # slo_state: tiny is healthy (generous objectives) -> 0
        (state,) = [v for n, lbl, v in samples
                    if n == "repro_serve_slo_state" and lbl["net"] == "tiny"]
        assert state == 0.0
        burn_series = [(lbl["objective"], lbl["window"]) for n, lbl, v in samples
                       if n == "repro_serve_slo_burn_rate"]
        assert len(burn_series) == len(set(burn_series)) >= 6  # 2 obj x 3 win

    def test_metrics_label_escaping_parses(self, tiny_art):
        """A net name containing every character the exposition format
        escapes (backslash, quote, newline) still renders parseable text."""
        ses = Session(scheduler=SchedulerConfig())
        try:
            ses.load(tiny_art, name='we"ird\\na\nme')
            from repro.serve.metrics import render
            families, samples = _parse_prometheus(render(ses))
            nets = {lbl["net"] for _, lbl, _ in samples if "net" in lbl}
            assert 'we"ird\\na\nme' in nets
        finally:
            ses.close()


class TestTraceHTTP:
    """The X-Repro-Trace-Id contract over the wire: every inference reply
    (success or error) carries a trace id, client-supplied ids are echoed
    and force tracing, and /v1/trace exports the server-side spans."""

    def test_success_reply_assigns_trace_id(self, served):
        base, ses, _ = served
        x = np.zeros((2, 8, 8), np.float32)
        r = _post(f"{base}/v1/infer/tiny",
                  json.dumps({"input": x.tolist()}).encode(),
                  {"Content-Type": "application/json"})
        tid = r.headers["X-Repro-Trace-Id"]
        assert tid and re.fullmatch(r"[0-9a-f]{16}", tid)
        assert any(t.trace_id == tid for t in ses.tracer.traces())

    def test_client_trace_id_echoed_and_traced(self, served):
        base, ses, _ = served
        x = np.zeros((2, 8, 8), np.float32)
        r = _post(f"{base}/v1/infer/tiny",
                  json.dumps({"input": x.tolist()}).encode(),
                  {"Content-Type": "application/json",
                   "X-Repro-Trace-Id": "my-trace-7"})
        assert r.headers["X-Repro-Trace-Id"] == "my-trace-7"
        (t,) = [t for t in ses.tracer.traces()
                if t.trace_id == "my-trace-7"]
        assert t.status == "ok"
        assert {"queue", "device_execute", "request"} <= \
            {s.name for s in t.spans}

    @pytest.mark.parametrize("npy", [False, True], ids=["json", "npy"])
    def test_decode_before_and_encode_after_request_span(self, served, npy):
        base, ses, _ = served
        x = np.zeros((2, 8, 8), np.float32)
        if npy:
            buf = io.BytesIO()
            np.save(buf, x)
            body, ctype = buf.getvalue(), "application/x-npy"
        else:
            body = json.dumps({"input": x.tolist()}).encode()
            ctype = "application/json"
        tid = f"codec-{int(npy)}"
        _post(f"{base}/v1/infer/tiny", body,
              {"Content-Type": ctype, "X-Repro-Trace-Id": tid})
        (t,) = [t for t in ses.tracer.traces() if t.trace_id == tid]
        # the handler adds ``encode`` after writing the reply
        deadline = time.monotonic() + 10
        while "encode" not in {s.name for s in t.spans} \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        spans = {s.name: s for s in t.spans}
        dec, req, enc = spans["decode"], spans["request"], spans["encode"]
        assert dec.t0 <= dec.t1 <= req.t0
        # encode starts once the result is out (the dispatcher's respond
        # ends before it resolves the future, whose callback seals the
        # request span) and ends with the reply written
        assert spans["respond"].t1 <= enc.t0 <= enc.t1
        assert req.t1 <= enc.t1

    def test_invalid_trace_id_400(self, served):
        base, _, _ = served
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"{base}/v1/infer/tiny", b'{"input": [0]}',
                  {"Content-Type": "application/json",
                   "X-Repro-Trace-Id": "a" * 65})
        assert ei.value.code == 400
        err = json.loads(ei.value.read())["error"]
        assert err["code"] == "bad_request" and "Trace-Id" in err["message"]

    def test_404_error_body_carries_trace_id(self, served):
        base, _, _ = served
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"{base}/v1/infer/ghost", b'{"input": [0]}',
                  {"Content-Type": "application/json"})
        err = json.loads(ei.value.read())["error"]
        assert err["trace_id"]
        assert ei.value.headers["X-Repro-Trace-Id"] == err["trace_id"]

    def test_504_deadline_shed_carries_trace_id(self, served):
        base, ses, _ = served
        x = np.zeros((2, 8, 8), np.float32)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"{base}/v1/infer/tiny?deadline_us=0",
                  json.dumps({"input": x.tolist()}).encode(),
                  {"Content-Type": "application/json"})
        assert ei.value.code == 504
        err = json.loads(ei.value.read())["error"]
        assert err["code"] == "deadline_exceeded" and err["trace_id"]
        assert ei.value.headers["X-Repro-Trace-Id"] == err["trace_id"]
        (t,) = [t for t in ses.tracer.traces()
                if t.trace_id == err["trace_id"]]
        assert t.status == "shed"

    def test_trace_endpoint_exports_chrome_json(self, served):
        base, _, _ = served
        x = np.zeros((2, 8, 8), np.float32)
        _post(f"{base}/v1/infer/tiny",
              json.dumps({"input": x.tolist()}).encode(),
              {"Content-Type": "application/json",
               "X-Repro-Trace-Id": "export-me"})
        doc = json.loads(urllib.request.urlopen(
            f"{base}/v1/trace?limit=10", timeout=30).read())
        assert doc["traceEvents"]
        assert any(e.get("args", {}).get("trace_id") == "export-me"
                   for e in doc["traceEvents"])
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/v1/trace?limit=zap", timeout=30)
        assert ei.value.code == 400


class TestServeClient:
    def test_infer_matches_session_run(self, tiny_art):
        with Session(tiny_art) as ses:
            client = ServeClient(ses)
            x = np.random.default_rng(2).normal(0, 1, (2, 8, 8)).astype(
                np.float32)
            got = client.infer("tiny", x)
            want = ses.run(x)
            np.testing.assert_array_equal(got.output_int8, want.output_int8)

    def test_typed_errors(self, tiny_art):
        with Session(tiny_art,
                     scheduler=SchedulerConfig(max_queue=1)) as ses:
            client = ServeClient(ses)
            with pytest.raises(NotFoundError):
                client.infer("ghost", np.zeros((2, 8, 8), np.float32))
            with pytest.raises(BadRequestError):
                client.infer("tiny", np.zeros(7, np.float32))
            assert OverloadedError.status == 429  # mapping used by http

    def test_nets_and_health(self, tiny_art):
        with Session(tiny_art) as ses:
            client = ServeClient(ses)
            assert client.nets()[0]["name"] == "tiny"
            assert client.healthz()["nets"] == 1


class TestPayloadCodecs:
    def test_json_meta_passthrough(self):
        x, meta = payload.decode_request(
            json.dumps({"input": [[1, 2], [3, 4]], "dtype": "int8",
                        "priority": 3, "deadline_us": 1e5}).encode(),
            "application/json")
        assert x.dtype == np.int8 and x.shape == (2, 2)
        assert meta == {"priority": 3, "deadline_us": 1e5}

    def test_npy_roundtrip(self):
        a = np.arange(12, dtype=np.float32).reshape(3, 4)
        buf = io.BytesIO()
        np.save(buf, a)
        x, meta = payload.decode_request(buf.getvalue(), "application/x-npy")
        np.testing.assert_array_equal(x, a)
        assert meta == {}

    def test_npy_rejects_pickles(self):
        buf = io.BytesIO()
        np.save(buf, np.array([{"a": 1}], dtype=object), allow_pickle=True)
        with pytest.raises(ValueError, match="bad npy"):
            payload.decode_request(buf.getvalue(), "application/x-npy")

    def test_unsupported_content_type(self):
        with pytest.raises(ValueError, match="unsupported Content-Type"):
            payload.decode_request(b"x", "text/csv")

    def test_encode_result_json_exact_ints(self):
        res = ExecResult(output_int8=np.array([-128, 127, 3], np.int8),
                         output=np.array([0.5, 1.5, -2.0], np.float32))
        body, ctype = payload.encode_result("n", res, 12.34)
        doc = json.loads(body)
        assert ctype == "application/json"
        assert doc["output_int8"] == [-128, 127, 3]
        assert doc["argmax"] == 1
