"""Engine spans on the profiler's clock, with per-phase host counters.

Each span is a ``jax.profiler.TraceAnnotation`` named ``engine.<phase>``:
it costs about a microsecond when no profiler session runs and records
on the profiler's own clock when one does (``jax.profiler.trace`` /
``start_trace``), on the same timeline as the device's programs and
operations.  Span arguments (``rid``, ``bucket``, ``tick``...) land as
stats on the host event.

Every span also adds its host seconds to one counter family,
``engine_phase_seconds_total{phase=...}``, whether or not a profiler
records.  Nested spans count once, in the innermost: a phase's counter
holds its own time, less the time of the spans opened inside it, so the
phases sum to the total of ``engine.step``.

The phase vocabulary is closed (``PHASES``); each phase's counter handle
is bound once, at construction.  ``instant`` marks a point event (retire,
preempt, quarantine...) as a zero-length annotation and feeds no counter.
``read_spans`` reads the ``engine.*`` events back from a written trace.
"""
from __future__ import annotations

import pathlib
import time
from contextlib import contextmanager, nullcontext

import jax

#: engine phases, outermost first: ``step`` holds the rest; ``prefill``
#: nests in ``admit`` and ``harvest_wait`` in ``harvest``
PHASES = ("step", "lanes", "admit", "prefill", "movers", "dispatch",
          "harvest", "harvest_wait")


class Tracer:
    """Opens ``engine.*`` annotations and counts each phase's host time."""

    def __init__(self, metrics):
        self._seconds = {p: metrics.counter(
            "engine_phase_seconds_total",
            "host seconds inside each engine phase, nested phases "
            "counted once (in the innermost)", phase=p) for p in PHASES}
        # seconds of the spans closed inside each open span
        self._inner: list[float] = []

    @contextmanager
    def span(self, phase: str, **args):
        """Annotate the body as ``engine.<phase>`` and count its time."""
        counter = self._seconds[phase]
        self._inner.append(0.0)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"engine.{phase}", **args):
                yield
        finally:
            dt = time.perf_counter() - t0
            counter.inc(max(dt - self._inner.pop(), 0.0))
            if self._inner:
                self._inner[-1] += dt

    def instant(self, name: str, **args):
        """A point event, ``engine.<name>``."""
        with jax.profiler.TraceAnnotation(f"engine.{name}", **args):
            pass


_NO_SPAN = nullcontext()


def no_span(phase: str, **args):
    """``Tracer.span`` of disabled telemetry: one shared no-op context."""
    return _NO_SPAN


def read_spans(log_dir) -> list[tuple[str, int, int, dict]]:
    """The ``engine.*`` host events of the newest profiler trace under
    ``log_dir``: ``(name, start_ns, end_ns, args)`` in start order."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda f: f.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])
