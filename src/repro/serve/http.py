"""Stdlib-only HTTP front-end over :class:`repro.serve.client.ServeClient`.

    POST /v1/infer/<net>[?priority=N&deadline_us=F]  — one inference
         body: JSON {"input": [...], "priority", "deadline_us"} or raw .npy
         (``Content-Type: application/x-npy``); response JSON, or .npy of
         ``output_int8`` under ``Accept: application/x-npy``
    GET  /v1/nets     — resident networks + shapes + queue depths
    GET  /v1/trace[?limit=N] — recent completed traces as Chrome trace-event
                        JSON (chrome://tracing / ui.perfetto.dev)
    GET  /v1/slo      — declared SLO policies + per-net burn-rate states
                        (``{"enabled": false, ...}`` when no --slo attached)
    GET  /healthz     — per-net health (warming / healthy / degraded /
                        circuit_open); non-200 when any net is unhealthy
                        or any SLO is in breach
    GET  /metrics     — Prometheus text format (``NetStats.snapshot()`` +
                        the tracer's per-phase latency histograms + the
                        windowed telemetry and ``slo_state`` gauges)

Every inference response carries ``X-Repro-Trace-Id``: the id the request
arrived with (same header; forces that request into the tracer's sampled
set) or a server-assigned one.  Error replies (429/503/504/500) carry the
header too, plus ``error.trace_id`` in the JSON body, so rejected and shed
requests stay correlatable with their server-side trace.  A traced
request's trace also gets the handler's ``decode`` span (body read and
parsed, before its ``request`` span) and ``encode`` span (result encoded
and written, after it).

Status codes: 400 malformed payload, 404 unknown net/route, 429 queue at
``max_queue`` (admission control), 503 circuit open / warming (with
``Retry-After``), 504 deadline shed or client timeout, 500 backend fault
(retries exhausted).  A response served by a net's fallback backend while
its circuit is open carries ``"degraded": true`` in the JSON body and an
``X-Repro-Degraded: 1`` header.

``ThreadingHTTPServer`` gives one handler thread per in-flight request;
concurrent posts against the same net coalesce in that net's dispatcher,
and different nets proceed on independent dispatcher threads — the HTTP
layer adds transport, never scheduling policy.
"""

from __future__ import annotations

import json
import math
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlparse

from repro.obs.trace import TRACE_HEADER, new_trace_id, valid_trace_id
from repro.serve import payload
from repro.serve.client import BadRequestError, NotFoundError, ServeClient, \
    ServeError

_MAX_BODY = 64 << 20            # 64 MiB — far past any supported input


class ServeHandler(BaseHTTPRequestHandler):
    """One request; ``self.server.client`` is the shared ServeClient."""

    server_version = "repro-serve/1.0"
    protocol_version = "HTTP/1.1"

    # -- plumbing ------------------------------------------------------------
    def log_message(self, fmt, *args):      # pragma: no cover - log noise
        if getattr(self.server, "verbose", False):
            BaseHTTPRequestHandler.log_message(self, fmt, *args)

    def _reply(self, status: int, body: bytes, content_type: str,
               extra_headers: Optional[dict] = None) -> None:
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        for k, v in (extra_headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _reply_json(self, status: int, doc) -> None:
        self._reply(status, json.dumps(doc).encode("utf-8"),
                    payload.JSON_TYPE)

    def _reply_error(self, exc: ServeError,
                     trace_id: Optional[str] = None) -> None:
        # an error reply may be sent before the request body was read
        # (e.g. 404 on the route) — close the connection rather than let a
        # keep-alive client's unread body desync the next request
        self.close_connection = True
        retry_after = getattr(exc, "retry_after_s", None)
        tid = trace_id or getattr(exc, "trace_id", None)
        body, ctype = payload.encode_error(exc.status, exc.code, str(exc),
                                           retry_after_s=retry_after,
                                           trace_id=tid)
        extra = {}
        if exc.status in (429, 503):
            # whole seconds per RFC 9110; a sub-second probe window still
            # tells the client to back off for at least one
            extra["Retry-After"] = str(max(1, math.ceil(retry_after or 1.0)))
        if tid is not None:
            extra[TRACE_HEADER] = tid
        self._reply(exc.status, body, ctype, extra or None)

    # -- routes --------------------------------------------------------------
    def do_GET(self) -> None:               # noqa: N802 (stdlib casing)
        client: ServeClient = self.server.client
        url = urlparse(self.path)
        path = url.path
        try:
            if path == "/healthz":
                doc = client.healthz()
                # non-200 when any resident net is unhealthy, so load
                # balancers/orchestrators act on degraded state
                self._reply_json(200 if doc["status"] == "ok" else 503, doc)
            elif path == "/metrics":
                self._reply(200, client.metrics_text().encode("utf-8"),
                            "text/plain; version=0.0.4")
            elif path == "/v1/nets":
                self._reply_json(200, {"nets": client.nets()})
            elif path == "/v1/trace":
                qs = parse_qs(url.query)
                try:
                    limit = int(qs["limit"][0]) if "limit" in qs else None
                except (TypeError, ValueError):
                    raise BadRequestError("limit must be an int") from None
                self._reply_json(200, client.trace_doc(limit))
            elif path == "/v1/slo":
                self._reply_json(200, client.slo_doc())
            else:
                self._reply_error(NotFoundError(f"no route {path!r}"))
        except ServeError as e:
            self._reply_error(e)
        except Exception as e:              # noqa: BLE001 — last-resort 500
            self._reply_error(ServeError(f"{type(e).__name__}: {e}"))

    def do_POST(self) -> None:              # noqa: N802 (stdlib casing)
        client: ServeClient = self.server.client
        url = urlparse(self.path)
        trace_id = None
        try:
            if not url.path.startswith("/v1/infer/"):
                raise NotFoundError(f"no route {url.path!r}")
            net = url.path[len("/v1/infer/"):]
            if not net or "/" in net:
                raise NotFoundError(f"no route {url.path!r}")
            # a client-supplied trace id forces the request into the
            # tracer's sampled set; absent, the scheduler assigns one (and
            # the sampler decides whether to record)
            trace_id = self.headers.get(TRACE_HEADER)
            if trace_id is not None and not valid_trace_id(trace_id):
                raise BadRequestError(
                    f"{TRACE_HEADER} must be 1-64 chars of [A-Za-z0-9._-]")
            try:
                length = int(self.headers.get("Content-Length", 0))
            except ValueError:
                raise BadRequestError("bad Content-Length") from None
            if not 0 < length <= _MAX_BODY:
                raise BadRequestError(
                    f"Content-Length must be in (0, {_MAX_BODY}]")
            t_read = time.perf_counter()
            body = self.rfile.read(length)
            try:
                x, meta = payload.decode_request(
                    body, self.headers.get("Content-Type", ""))
            except ValueError as e:
                raise BadRequestError(str(e)) from None
            t_decoded = time.perf_counter()
            qs = parse_qs(url.query)
            try:
                priority = int(qs.get("priority", [meta.get("priority", 0)])[0])
                dl = qs.get("deadline_us", [meta.get("deadline_us")])[0]
                deadline_us = float(dl) if dl is not None else None
            except (TypeError, ValueError):
                raise BadRequestError(
                    "priority must be int, deadline_us float") from None
            t0 = time.perf_counter()
            fut = client.infer_async(net, x, priority=priority,
                                     deadline_us=deadline_us,
                                     trace_id=trace_id)
            trace_id = getattr(fut, "trace_id", trace_id)
            trace = getattr(fut, "trace", None)
            if trace is not None:
                trace.add_span("decode", t_read, t_decoded)
            res = client.resolve_future(fut,
                                        timeout=client.timeout_for(deadline_us))
            t_res = time.perf_counter()
            out, ctype = payload.encode_result(
                net, res, (t_res - t0) * 1e6,
                accept=self.headers.get("Accept", ""))
            extra = {}
            if getattr(res, "degraded", False):
                extra["X-Repro-Degraded"] = "1"
            if trace_id is not None:
                extra[TRACE_HEADER] = trace_id
            self._reply(200, out, ctype, extra or None)
            if trace is not None:
                # after the trace was sealed: the kept object still takes it
                trace.add_span("encode", t_res, time.perf_counter())
        except ServeError as e:
            # rejections that never reached the scheduler (404/400/warming)
            # still get a fresh id for the error body/header
            self._reply_error(e, trace_id=getattr(e, "trace_id", None)
                              or trace_id or new_trace_id())
        except Exception as e:              # noqa: BLE001 — last-resort 500
            self._reply_error(ServeError(f"{type(e).__name__}: {e}"),
                              trace_id=trace_id or new_trace_id())


def make_server(session, host: str = "127.0.0.1",
                port: int = 0, verbose: bool = False) -> ThreadingHTTPServer:
    """Build (not start) the HTTP server; ``port=0`` picks an ephemeral
    port — read it back from ``server.server_address``.  The server owns no
    session lifecycle: close the session yourself after ``shutdown()``."""
    srv = ThreadingHTTPServer((host, port), ServeHandler)
    srv.daemon_threads = True
    srv.client = ServeClient(session)
    srv.verbose = verbose
    return srv


def serve_forever(session, host: str = "127.0.0.1", port: int = 8000,
                  verbose: bool = True,
                  ready: Optional[threading.Event] = None,
                  warmup: bool = False,
                  trace_dir: Optional[str] = None) -> None:
    """Blocking serve loop (the ``python -m repro.serve`` entry point).

    With ``warmup=True`` the socket opens immediately but inference returns
    503 (``/healthz`` reports ``"warming"``) until every resident net's
    bucket ladder is precompiled — no first request ever compile-stalls.
    ``trace_dir`` dumps the tracer's ring buffer as Chrome trace-event JSON
    (``<trace_dir>/trace.json``) on shutdown.
    """
    srv = make_server(session, host, port, verbose=verbose)
    bound = srv.server_address
    print(f"[repro.serve] listening on http://{bound[0]}:{bound[1]} "
          f"nets={','.join(session.networks)}")
    if warmup:
        srv.client.begin_warmup()
    if ready is not None:
        ready.set()
    try:
        if warmup:
            thread = threading.Thread(target=srv.serve_forever,
                                      name="repro-serve-http", daemon=True)
            thread.start()
            for name, ms in session.warmup().items():
                print(f"[repro.serve] warmed {name}: {ms:.0f}ms, "
                      f"buckets={list(session.scheduler.config.buckets)}")
            srv.client.finish_warmup()
            thread.join()
        else:
            srv.serve_forever()
    except KeyboardInterrupt:               # pragma: no cover - interactive
        print("[repro.serve] draining...")
    finally:
        srv.shutdown()
        srv.server_close()
        session.close(drain=True)
        if trace_dir is not None:
            import pathlib
            out = pathlib.Path(trace_dir) / "trace.json"
            session.tracer.to_file(out)
            print(f"[repro.serve] trace -> {out}")
