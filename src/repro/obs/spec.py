"""ObsSpec -- declarative observability configuration.

Nested in ``ServeConfig`` (``scfg.obs``) the way ``AssistSpec`` nests
assist decisions: configuration only, no imports of the runtime layers,
so every layer can consume it without cycles.

Defaults follow the telemetry-spine contract (DESIGN.md 13): counters ON
(near-zero overhead -- handle-bound attribute adds), the execution probe
ON (a fence every ``exec_sample_every`` ticks).  Engine spans exist
whenever counters are on and record only while a profiler session runs.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ObsSpec:
    """Which telemetry channels run, and at what sampling cost.

    counters           counter/gauge/histogram registry and the engine's
                       phase spans (near-zero cost; OFF makes every metric
                       handle a shared no-op and removes all probe/span
                       work from the hot path)
    exec_probe         execution-true tick probe: fence every Nth tick
    exec_sample_every  N for the probe fence (0 = record dispatch only)
    probe_window       ring size for exact percentile computation
    strict_transfers   wrap the jitted tick dispatch in
                       ``jax.transfer_guard("disallow")`` (DESIGN.md 16):
                       any implicit host<->device transfer inside the
                       dispatch raises.  OFF is fence-free (a shared
                       no-op context, the NULL_REGISTRY pattern)

    ``trace`` is accepted and ignored: spans go to whichever profiler
    session is running, so there is nothing to switch.  It is not a field;
    it stays only while callers written for the old span buffer
    (``bench/harness.py``) still pass it.
    """
    counters: bool = True
    exec_probe: bool = True
    exec_sample_every: int = 4
    probe_window: int = 2048
    strict_transfers: bool = False
    trace: dataclasses.InitVar[bool] = False

    def __post_init__(self, trace):
        if self.exec_sample_every < 0:
            raise ValueError("exec_sample_every must be >= 0")
        if self.probe_window < 1:
            raise ValueError("probe_window must be >= 1")

    @classmethod
    def off(cls) -> "ObsSpec":
        """Everything disabled: the overhead-free hot path."""
        return cls(counters=False, exec_probe=False)
