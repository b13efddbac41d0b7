"""Prometheus text-format rendering of per-net serving stats.

``render(session)`` walks every resident network, takes one coherent
``NetStats.snapshot()`` each (the snapshot is the concurrency boundary —
this module only formats), and emits the Prometheus exposition format
(text/plain; version 0.0.4) that ``GET /metrics`` returns.  Stdlib only.

Conformance notes (`tests/test_serve.py` round-trips this through a strict
parser): every series gets ``# HELP`` + ``# TYPE``; label values escape
``\\``, ``"`` and newlines; HELP text escapes ``\\`` and newlines; the
latency summary carries ``_sum``/``_count`` alongside its quantiles; and
the tracer's per-phase latency histograms render as proper cumulative
``_bucket{le=...}`` series ending at ``le="+Inf"`` with ``_sum``/``_count``.
The windowed telemetry adds ``request_latency_us`` (an every-request
cumulative histogram), per-window ``window_*`` gauges, and — when an SLO
engine is attached — the ``slo_state`` (0 ok / 1 warning / 2 breach) and
``slo_burn_rate`` gauges, all under the same conformance rules.
"""

from __future__ import annotations

from typing import List

from repro.obs.slo import STATE_CODES as _STATE_CODES

# (metric suffix, snapshot key, TYPE, HELP)
_COUNTERS = [
    ("requests_total", "submits", "counter",
     "Requests admitted to the queue (run/run_batch included)"),
    ("rejected_total", "rejected", "counter",
     "Requests rejected by admission control (queue at max_queue -> 429)"),
    ("shed_total", "shed", "counter",
     "Requests shed because deadline_us elapsed before launch"),
    ("dispatches_total", "dispatches", "counter",
     "Coalesced batches executed"),
    ("coalesced_images_total", "coalesced_images", "counter",
     "Requests served through coalesced dispatches"),
    ("images_total", "images", "counter",
     "Images served through the synchronous Session API"),
    ("compile_count_total", "compile_count", "counter",
     "Executor program builds observed (warmup + dispatch); a nonzero "
     "delta after warmup means a request paid a compile stall"),
    ("retries_total", "retries", "counter",
     "Batch launch attempts beyond each batch's first (supervisor retries)"),
    ("backend_failures_total", "backend_failures", "counter",
     "Failed launch attempts (exceptions + watchdog timeouts)"),
    ("watchdog_timeouts_total", "watchdog_timeouts", "counter",
     "Launches abandoned by the per-launch watchdog"),
    ("arena_resets_total", "arena_resets", "counter",
     "Poisoned-arena restores (weight checksum mismatch after a failure)"),
    ("degraded_responses_total", "degraded", "counter",
     "Requests served by the fallback backend while the circuit was open"),
    ("faults_injected_total", "faults_injected", "counter",
     "Injected faults observed (FaultyExecutor chaos harness)"),
    ("circuit_opens_total", "circuit_opens", "counter",
     "Circuit-breaker transitions to open"),
    ("circuit_rejected_total", "circuit_rejected", "counter",
     "Submits shed with 503 while the circuit was open"),
    ("launches_ahead_total", "launches_ahead", "counter",
     "Launches enqueued behind one still on the device"),
]
_GAUGES = [
    ("queue_depth_peak", "queue_depth_peak", "gauge",
     "Peak queued requests observed for this net"),
    ("coalesce_max", "coalesce_max", "gauge",
     "Largest coalesced batch so far"),
    ("warmup_ms", "warmup_ms", "gauge",
     "Wall time spent precompiling this net's bucket ladder at startup"),
    ("latency_samples", "latency_samples", "gauge",
     "Latency samples in the percentile window"),
    ("circuit_state", "circuit_state", "gauge",
     "Circuit-breaker state: 0 closed, 1 half-open, 2 open"),
]
_QUANTILES = [("0.5", "latency_p50_us"), ("0.9", "latency_p90_us"),
              ("0.99", "latency_p99_us")]

PREFIX = "repro_serve"


def _escape(label: str) -> str:
    """Label-value escaping per the exposition format: backslash, double
    quote and newline."""
    return label.replace("\\", r"\\").replace('"', r'\"').replace("\n", r"\n")


def _escape_help(text: str) -> str:
    """HELP-text escaping per the exposition format: backslash and newline
    only (quotes are legal in HELP)."""
    return text.replace("\\", r"\\").replace("\n", r"\n")


def _fmt_le(le: float) -> str:
    return "+Inf" if le == float("inf") else f"{le:g}"


def render(session) -> str:
    """Render every resident net's snapshot as Prometheus text."""
    snaps = {name: session.stats(name).snapshot()
             for name in session.networks}
    depths = {name: session.queue_depth(name) for name in session.networks}
    lines: List[str] = []

    def emit(suffix, mtype, help_text, values):
        name = f"{PREFIX}_{suffix}"
        lines.append(f"# HELP {name} {_escape_help(help_text)}")
        lines.append(f"# TYPE {name} {mtype}")
        lines.extend(values)

    for suffix, key, mtype, help_text in _COUNTERS + _GAUGES:
        emit(suffix, mtype, help_text,
             [f'{PREFIX}_{suffix}{{net="{_escape(n)}"}} {snap[key]}'
              for n, snap in snaps.items()])
    emit("queue_depth", "gauge", "Requests currently queued (not in-flight)",
         [f'{PREFIX}_queue_depth{{net="{_escape(n)}"}} {d}'
          for n, d in depths.items()])
    emit("bucket_launches_total", "counter",
         "Dispatched batches per padded bucket size",
         [f'{PREFIX}_bucket_launches_total'
          f'{{net="{_escape(n)}",bucket="{b}"}} {c}'
          for n, snap in snaps.items()
          for b, c in sorted(snap.get("bucket_launches", {}).items())])
    # summary: quantiles over the recent window, _sum/_count over all time
    vals = []
    for n, snap in snaps.items():
        vals.extend(
            f'{PREFIX}_latency_us{{net="{_escape(n)}",quantile="{q}"}} '
            f'{snap[key]:.1f}' for q, key in _QUANTILES)
        vals.append(f'{PREFIX}_latency_us_sum{{net="{_escape(n)}"}} '
                    f'{snap.get("latency_total_us", 0.0):.1f}')
        vals.append(f'{PREFIX}_latency_us_count{{net="{_escape(n)}"}} '
                    f'{snap.get("latency_count", 0)}')
    emit("latency_us", "summary",
         "Submit-to-result latency: percentiles over the recent window, "
         "sum/count over the session lifetime", vals)
    # per-phase latency histograms from the tracer (sampled requests only)
    tracer = getattr(session, "tracer", None)
    hists = tracer.phase_histograms() if tracer is not None else {}
    vals = []
    for (net, phase) in sorted(hists):
        h = hists[(net, phase)]
        lbl = f'net="{_escape(net)}",phase="{_escape(phase)}"'
        vals.extend(
            f'{PREFIX}_phase_us_bucket{{{lbl},le="{_fmt_le(le)}"}} {cum}'
            for le, cum in h["buckets"])
        vals.append(f'{PREFIX}_phase_us_sum{{{lbl}}} {h["sum"]:.1f}')
        vals.append(f'{PREFIX}_phase_us_count{{{lbl}}} {h["count"]}')
    emit("phase_us", "histogram",
         "Per-phase request latency from sampled traces (queue, hold, pad, "
         "device_execute, backoff, respond, request, total)", vals)
    # windowed telemetry: since-boot latency histogram (proper cumulative
    # Prometheus histogram, every request — not the tracer's sampled subset)
    # plus sliding-window scalars per configured window
    telemetry = getattr(session, "telemetry", None)
    if telemetry is not None and telemetry.names():
        vals = []
        for n in telemetry.names():
            buckets, sum_us, count, _ = telemetry.series(n).totals()
            lbl = f'net="{_escape(n)}"'
            vals.extend(
                f'{PREFIX}_request_latency_us_bucket{{{lbl},'
                f'le="{_fmt_le(le)}"}} {cum}' for le, cum in buckets)
            vals.append(f'{PREFIX}_request_latency_us_sum{{{lbl}}} '
                        f'{sum_us:.1f}')
            vals.append(f'{PREFIX}_request_latency_us_count{{{lbl}}} {count}')
        emit("request_latency_us", "histogram",
             "Submit-to-result latency of every completed request "
             "(streaming fixed-boundary histogram; since boot)", vals)
        windowed = [
            ("window_latency_us",
             "Windowed latency quantiles over the sliding window "
             "(label q, not quantile — that label is reserved for summaries)",
             [(f'q="{q}"', lambda w, q=qv: w.quantile(q), "%.1f")
              for q, qv in (("0.5", 0.5), ("0.9", 0.9), ("0.99", 0.99))]),
            ("window_error_rate",
             "Fraction of requests ending error/shed over the window",
             [("", lambda w: w.error_rate, "%.6f")]),
            ("window_goodput_rps",
             "Requests completed ok (within deadline when set) per second "
             "over the window",
             [("", lambda w: w.goodput_rps, "%.3f")]),
            ("window_rps",
             "Request arrival rate over the window",
             [("", lambda w: w.rps, "%.3f")]),
        ]
        wstats = {(n, w): telemetry.window(n, w)
                  for n in telemetry.names()
                  for w in telemetry.config.windows}
        for suffix, help_text, series in windowed:
            vals = []
            for (n, w), stats in wstats.items():
                for extra, fn, fmt in series:
                    lbl = f'net="{_escape(n)}",window="{w:g}s"'
                    if extra:
                        lbl += f',{extra}'
                    vals.append(f'{PREFIX}_{suffix}{{{lbl}}} '
                                + (fmt % fn(stats)))
            emit(suffix, "gauge", help_text, vals)
    # SLO engine: per-net state gauge + per-objective burn rates
    slo = getattr(session, "slo", None)
    if slo is not None:
        slo.evaluate()                      # scrape-fresh states
        snap = slo.snapshot()
        emit("slo_state", "gauge",
             "SLO burn-rate state: 0 ok, 1 warning, 2 breach",
             [f'{PREFIX}_slo_state{{net="{_escape(n)}"}} '
              f'{_STATE_CODES[d["state"]]}'
              for n, d in sorted(snap["nets"].items())])
        vals = []
        for n, d in sorted(snap["nets"].items()):
            for obj in d["objectives"]:
                for w, burn in obj["burn"].items():
                    vals.append(
                        f'{PREFIX}_slo_burn_rate{{net="{_escape(n)}",'
                        f'objective="{_escape(obj["objective"])}",'
                        f'window="{w}"}} {burn:.4f}')
        emit("slo_burn_rate", "gauge",
             "Error-budget burn rate per objective and window "
             "(1.0 = consuming exactly the budget)", vals)
    return "\n".join(lines) + "\n"
