"""Runtime observability: causal tracing, metrics, overhead attribution.

Five layers (ISSUE 6 + ISSUE 9):

- ``trace``       ring-buffer tracer emitting typed spans/instants with
                  monotonic timestamps and causal ids (request -> slot ->
                  page chain -> parcel); Chrome trace-event JSON export.
                  Every span, traced or not, is also a profiler
                  annotation on the device trace's clock.
- ``metrics``     unified registry of counters / gauges / streaming
                  histograms under a ``subsystem.metric`` namespace.
- ``attribution`` per-step wall-clock decomposition into kernel compute
                  vs runtime overhead (the paper's Fig. 9 analysis applied
                  online to serving), plus the per-role / per-locality
                  split for disaggregated serving.
- ``slo``         request-level lifecycle flight recorder and TTFT/ITL
                  deadline classification with per-phase blame.
- ``export``      Prometheus text exposition and JSONL interval
                  snapshots over the metrics registry.
"""

from repro.obs.trace import (  # noqa: F401
    NULL_TRACER,
    Tracer,
    get_global,
    set_global,
)
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    MetricsRegistry,
    StreamingHistogram,
)
from repro.obs.slo import (  # noqa: F401
    NULL_RECORDER,
    FlightRecorder,
    build_report,
    classify,
    derive_phases,
    record_verdict,
)
from repro.obs.export import (  # noqa: F401
    JsonlExporter,
    parse_prometheus,
    read_jsonl,
    to_prometheus,
    verify_roundtrip,
)
