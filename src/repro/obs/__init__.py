"""Fleet observability layer — tracing, decomposition, metrics, dashboards.

Five pieces (docs/observability.md):

* :mod:`repro.obs.tracer` — :class:`SpanTracer`, a simulated-clock span
  recorder exported as Chrome trace-event JSON (``<name>.trace.json``,
  Perfetto-loadable, byte-deterministic). Thread one through
  :class:`~repro.controlplane.ControlPlane` (``tracer=``) and
  :func:`~repro.scenarios.campaign.run_campaign` to see tick cadence,
  watchdog silence windows, executor attempt/retry cycles, and per-job
  fault episodes as nested spans.
* :mod:`repro.obs.host` — :func:`~repro.obs.host.span` /
  :func:`~repro.obs.host.step`: wall-clock spans on the ``jax.profiler``
  timeline, beside the device ops, recorded only while a profiler session
  is active. Imported explicitly (``from repro.obs import host``).
* :mod:`repro.obs.collectives` — :class:`CollectiveBreakdown` +
  :func:`decompose`: an iteration's critical path split into
  compute / TP-allreduce / PP-p2p / DP-allreduce with the bottleneck
  collective, profiling group and ring edge named. Attached to every
  onset Diagnosis by the control plane.
* :mod:`repro.obs.metrics` — :class:`MetricsRegistry`
  (counters/gauges/histograms), snapshotted to ``<name>.metrics.json``.
* :mod:`repro.obs.recorder` / :mod:`repro.obs.dashboard` — feed the
  registry from a campaign's typed event pipeline, and render static
  deterministic HTML/SVG dashboards off the serialized event log
  (``python -m repro.launch.obs``). These two sit *above* the control
  plane and scenarios layers, so they are imported explicitly
  (``from repro.obs import recorder``), not re-exported here — this
  package ``__init__`` must stay a leaf (the cluster simulator imports
  :mod:`repro.obs.collectives`).
"""
from repro.obs.collectives import (  # noqa: F401
    COMPONENTS,
    CollectiveBreakdown,
    decompose,
    timing_decomposition,
)
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.tracer import SpanTracer, TraceError  # noqa: F401
