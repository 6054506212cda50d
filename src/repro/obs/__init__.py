"""repro.obs -- the telemetry spine (DESIGN.md 13).

One substrate for every subsystem's measurements:

  metrics   MetricsRegistry: counters/gauges/histograms, null-object
            disabled mode, process-global ``REGISTRY``
  probe     TickProbe: execution-true decode-tick sampling (dispatch_*
            every tick, exec_* via every-Nth-tick fence)
  trace     Tracer: engine spans as profiler annotations, with per-phase
            host-seconds counters
  export    prometheus_text / snapshot / SnapshotWriter / serve_metrics
  spec      ObsSpec: the declarative knob nested in ServeConfig

``Observability`` bundles one spec's worth of live objects; the engines
take a single ``obs=`` parameter instead of four.
"""
from __future__ import annotations

from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               NULL_METRIC, NULL_REGISTRY, NullRegistry,
                               REGISTRY, SECONDS_BUCKETS, TOKENS_BUCKETS,
                               log_buckets)
from repro.obs.probe import TickProbe
from repro.obs.spec import ObsSpec
from repro.obs.trace import PHASES, Tracer


class Observability:
    """Live telemetry bundle built from one ``ObsSpec``.

    ``metrics`` is always a registry object (the null one when counters
    are off) so components bind handles unconditionally; ``tracer`` and
    ``probe`` are ``None`` when their channel is off so hot paths can
    skip them with one truthiness check.
    """

    def __init__(self, spec: ObsSpec = None, registry=None):
        self.spec = spec or ObsSpec()
        if registry is not None:
            self.metrics = registry
        elif self.spec.counters:
            # private by default: engines built side by side in one test
            # process must not share series (serve.py passes REGISTRY)
            self.metrics = MetricsRegistry()
        else:
            self.metrics = NULL_REGISTRY
        self.tracer = Tracer(self.metrics) if self.spec.counters else None
        self.probe = (TickProbe(self.spec.exec_sample_every,
                                self.spec.probe_window,
                                metrics=self.metrics)
                      if self.spec.exec_probe else None)

    @classmethod
    def off(cls) -> "Observability":
        """The overhead-free configuration (ObsSpec.off())."""
        return cls(ObsSpec.off())

    @property
    def enabled(self) -> bool:
        return self.metrics.enabled


__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "NullRegistry",
    "NULL_METRIC", "NULL_REGISTRY", "REGISTRY", "SECONDS_BUCKETS",
    "TOKENS_BUCKETS", "log_buckets", "TickProbe", "ObsSpec", "PHASES",
    "Tracer", "Observability",
]
