"""repro.obs — unified tracing + metrics for the split-parallel runtime.

One substrate for every *where-does-the-step-time-go* question the repo
asks (DESIGN.md §10, docs/OBSERVABILITY.md):

  * :class:`Obs` bundles a span :class:`~repro.obs.trace.Tracer` and a
    :class:`~repro.obs.metrics.MetricsRegistry` behind one enabled flag.
    Disabled (``NULL_OBS``, the default everywhere) it records nothing and
    adds no host syncs: spans still time their region (the trainer's
    ``EpochStats`` fields read those durations — one code path), metric
    calls return after a single attribute check. Enabled, each span is
    also a ``jax.profiler.TraceAnnotation``, so a profiler trace shows
    the program's spans beside the device ops.
  * ``python -m repro.obs report trace.json`` summarizes a written trace:
    per-stage percentiles plus a producer-bound / staging-bound /
    device-bound stall classification per step.
  * ``python -m repro.obs validate trace.json`` checks the trace schema
    (the CI gate: no unclosed spans, flow ids resolve, monotonic
    timestamps, nothing silently dropped).

Obs calls are host-side only; the splint purity rule HP008 statically pins
that no span/metric call is reachable from jit-traced code.
"""
from __future__ import annotations

import logging

from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Span, Tracer

__all__ = [
    "MetricsRegistry",
    "NULL_OBS",
    "Obs",
    "Span",
    "Tracer",
    "note_hwm_growth",
]

log = logging.getLogger("repro.obs")


class Obs:
    """Tracer + metrics behind one switch; ``NULL_OBS`` is the off state."""

    def __init__(self, enabled: bool = True, ring_capacity: int = 65536):
        self.enabled = enabled
        self.tracer: Tracer | None = Tracer(ring_capacity) if enabled else None
        self.metrics: MetricsRegistry | None = (
            MetricsRegistry() if enabled else None
        )

    # ---- spans -------------------------------------------------------- #
    def span(self, name: str, attrs=None, *, cpu: bool = False,
             step_num: int | None = None) -> Span:
        """A timed region; recorded only when enabled, timed always.

        When enabled, ``cpu`` adds the thread's CPU seconds as ``cpu_s`` and
        ``step_num`` marks the span as a training step on the profiler's
        clock (see ``repro.obs.trace``)."""
        return Span(self.tracer, name, attrs, cpu=cpu, step_num=step_num)

    def record(self, name: str, t0: float, t1: float, attrs=None) -> None:
        if self.tracer is not None:
            self.tracer.record(name, t0, t1, attrs)

    def instant(self, name: str, attrs=None) -> None:
        if self.tracer is not None:
            self.tracer.instant(name, attrs)

    def flow_start(self, flow_id) -> None:
        if self.tracer is not None:
            self.tracer.flow_start(flow_id)

    def flow_end(self, flow_id) -> None:
        if self.tracer is not None:
            self.tracer.flow_end(flow_id)

    # ---- metrics ------------------------------------------------------ #
    def count(self, name: str, n: int = 1) -> None:
        if self.metrics is not None:
            self.metrics.count(name, n)

    def gauge(self, name: str, value: float) -> None:
        if self.metrics is not None:
            self.metrics.gauge(name, value)

    def observe(self, name: str, value: float) -> None:
        if self.metrics is not None:
            self.metrics.observe(name, value)

    def absorb(self, stats: dict, prefix: str = "") -> None:
        if self.metrics is not None:
            self.metrics.absorb(stats, prefix)

    # ---- export ------------------------------------------------------- #
    def write(self, path) -> None:
        """Write the Chrome trace (with the metrics snapshot embedded)."""
        if self.tracer is None:
            raise ValueError("obs is disabled — nothing was recorded")
        self.tracer.write(
            path, self.metrics.snapshot() if self.metrics else {}
        )


#: The shared disabled instance — the default ``obs`` everywhere. One
#: singleton (rather than None checks at every call site) keeps the
#: instrumented code on a single path whether tracing is on or off.
NULL_OBS = Obs(enabled=False)


def note_hwm_growth(obs: Obs, before: dict, hwm: dict, where: str) -> int:
    """Surface high-water-mark growth (previously invisible, DESIGN.md §6).

    Compares a pre-repad snapshot of the shared ``hwm`` dict against its
    post-repad state. A mark that *grows* (existed and increased) means the
    plan that just landed is the largest seen for that axis: the next step
    with this shape pays a full retrace + XLA compile — exactly the event
    that used to be discoverable only by diffing recompile counts after the
    fact. Each growth emits a warning-level log line, a ``hwm/growth``
    counter bump, and an instant trace event; marks seen for the first time
    (warmup establishing the baseline) are recorded as events only.

    Returns the number of grown marks (tests pin the classification).
    """
    grown = 0
    for key, new in hwm.items():
        old = before.get(key)
        if old is None:
            obs.instant("hwm/init", {"key": key, "value": int(new), "where": where})
            continue
        if new > old:
            grown += 1
            log.warning(
                "high-water mark %s grew %d -> %d at %s: the next step at "
                "this shape retraces (recompile) — expected during warmup, "
                "a red flag in steady state",
                key, old, new, where,
            )
            obs.count("hwm/growth")
            obs.instant(
                "hwm/grow",
                {"key": key, "old": int(old), "new": int(new), "where": where},
            )
    return grown
