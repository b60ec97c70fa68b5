"""repro.obs — observability for the serving stack.

One seam, three consumers:

    Router / Engine / Controller / WorkerCore
        │  (publish spans through one Tracer; every request carries a
        │   trace id "r<rid>" from arrival to reap, workers "w:<wid>")
        ▼
      Tracer ──> JsonlTraceSink   (--trace-out: schema-validated JSONL;
        │                          tools/check_trace.py is the CI gate)
        ├─────> FleetView         (ring-buffer scheduler self-metrics:
        │                          occupancy, steals, demotions, DP
        │                          cache hits, placement latency)
        └─────> MemorySink        (tests; overhead benchmarking)

    FleetView + Router ──> build_frame ──> render_frame (--dashboard)
                                       ├─> dashboard_html (HTML artifact)
                                       └─> DashboardServer (live SSE)

Spans are **derived, never inputs**: nothing in the control path reads
tracer state, so record/replay determinism is untouched (tests assert a
steal-heavy cluster run replays byte-identically with tracing on). The
disabled ``NULL_TRACER`` costs one attribute check per publish site.
Duration spans (``Tracer.span``) time the host work of Router, Engine,
the DP and the pallas backend; ``Tracer(profile=True)`` puts them on the
``jax.profiler`` host plane as ``dype:<name>`` annotations.
See docs/observability.md for the span schema and a walkthrough.
"""
from .trace import (JsonlTraceSink, MemorySink, NULL_SPAN, NULL_TRACER,
                    PROFILE_PREFIX, Tracer, TraceSink)
from .schema import REQUEST_CHAIN, REQUIRED_KEYS, read_jsonl, validate
from .fleet import FleetView
from .dashboard import (DashboardServer, build_frame, dashboard_html,
                        render_frame)

__all__ = [
    "JsonlTraceSink", "MemorySink", "NULL_SPAN", "NULL_TRACER",
    "PROFILE_PREFIX", "Tracer", "TraceSink",
    "REQUEST_CHAIN", "REQUIRED_KEYS", "read_jsonl", "validate",
    "FleetView",
    "DashboardServer", "build_frame", "dashboard_html", "render_frame",
]
