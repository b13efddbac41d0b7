"""Serving-policy configuration shared by the CLI and embedders.

``SchedulerConfig`` owns the *runtime* knobs (batching, queues, retries,
watchdog, breaker); ``ServeConfig`` owns the *front-end* policy layered on
top — what to do when a net's circuit opens, and whether the socket admits
traffic before warmup finishes.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Front-end serving policy.

    ``fallback_backend`` — registered backend name (e.g. ``"ref"``) every
                       loaded net falls back to when its circuit breaker
                       opens; responses served this way carry
                       ``degraded: true``.  ``None`` (default): no fallback,
                       an open circuit sheds with 503 + ``Retry-After``.
    ``warmup``         — hold traffic (503 ``warming``) until every net's
                       bucket ladder is precompiled.

    Observability knobs (``repro.obs``):

    ``trace``          — record request lifecycle traces (the trace-id
                       header contract holds either way).
    ``trace_sample``   — trace every Nth request per net (1 = all, 0 = only
                       requests arriving with an ``X-Repro-Trace-Id``).
    ``trace_dir``      — dump the trace ring buffer as Chrome trace-event
                       JSON (``<dir>/trace.json``) on shutdown.
    ``slo_path``       — JSON file of ``SloPolicy`` declarations
                       (``repro.obs.slo.load_policies``); when set, the
                       burn-rate engine evaluates them continuously and
                       surfaces state on ``/metrics`` / ``/healthz`` /
                       ``/v1/slo``.
    ``slo_period_s``   — background evaluation cadence for the engine.
    """
    fallback_backend: Optional[str] = None
    warmup: bool = True
    trace: bool = True
    trace_sample: int = 1
    trace_dir: Optional[str] = None
    slo_path: Optional[str] = None
    slo_period_s: float = 5.0

    def trace_config(self):
        """The ``repro.obs.TraceConfig`` these knobs describe."""
        from repro.obs.trace import TraceConfig
        return TraceConfig(enabled=self.trace,
                           sample_rate=self.trace_sample)
