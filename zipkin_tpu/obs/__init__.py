"""Process-wide telemetry: counters, gauges, latency sketches.

The reference wires Finagle/Ostrich stats receivers through every
pipeline stage (ZipkinCollectorFactory's statsReceiver plumbing); this
package is that layer for the reproduction, built on the repo's own
sketch primitives: latency distributions are a host-side twin of
``ops.quantile``'s mergeable log-histogram plus ``models.dependencies``'
streaming Moments (the algebird monoid) — so per-stage sketches stay
mergeable across processes and (later) shards, exactly the
"disaggregation across time and space" property PAPERS.md motivates.

Three consumers:

- ``Registry.render_text()`` — Prometheus text exposition (the API's
  ``GET /metrics``; the JSON form stays at ``/metrics?format=json``);
- ``Registry.as_dict()`` — flat snapshot for BENCH json / debugging;
- self-tracing (api.server + ingest.collector) — the pipeline records
  genuine Zipkin spans about itself into its own store under the
  ``zipkin-tpu`` service name.

Components take a ``registry`` argument defaulting to the process-wide
instance (``default_registry()``); registering a name twice replaces
the earlier metric (newest pipeline object wins — the earlier one keeps
counting into its own, now-unscraped, object).

The fleet layer (``obs.fleet``, r17) extends all three consumers
across process boundaries: causal self-tracing over the ship
protocol, pushed-snapshot metrics federation (``/metrics?fleet=1``),
and the stall watchdog + flight recorder behind ``/api/health`` /
``/debug/events``.

``obs.stage`` (``obs/stages.py``) is the write path's one timing
helper: a span in the profiler's own trace plus a sketch observation
(``zipkin_ingest_stage_seconds{stage}`` where the site names none).
"""

from zipkin_tpu.obs.fleet import (
    FleetObs,
    FlightRecorder,
    FollowerLineage,
    LineageTracker,
    Watchdog,
    merge_sketches,
    registry_snapshot,
    render_federated,
)
from zipkin_tpu.obs.registry import (
    CallbackFamily,
    Counter,
    Gauge,
    LatencySketch,
    Registry,
    default_registry,
)
from zipkin_tpu.obs.stages import stage, stage_family

__all__ = [
    "CallbackFamily",
    "Counter",
    "FleetObs",
    "FlightRecorder",
    "FollowerLineage",
    "Gauge",
    "LatencySketch",
    "LineageTracker",
    "Registry",
    "Watchdog",
    "default_registry",
    "merge_sketches",
    "registry_snapshot",
    "render_federated",
    "stage",
    "stage_family",
]
