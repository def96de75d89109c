"""Structured tracing: thread-local span rings + Chrome-trace export.

The repo's whole performance argument is a *where-did-the-microseconds-go*
argument (paper Fig. 7/8): split parallelism wins exactly when the host-side
savings (deduplicated sampling/loading, producer-thread pipelining) exceed
the communication they introduce. This module records that breakdown as
spans — (name, thread, t_start, t_end, attrs) intervals — into per-thread
ring buffers, cheap enough to leave on, and exports one Chrome-trace /
Perfetto timeline where the producer lanes, prefetch-queue dwell, host
staging, and device step of the *same* mini-batch are linked by flow arrows.

Design constraints (docs/OBSERVABILITY.md):

  * **One code path.** ``Span`` always measures ``perf_counter`` start/end —
    the trainer reads ``Span.duration`` to fill the ``EpochStats`` fields it
    has always reported — and only *records* into the ring when a live
    ``Tracer`` is attached. Disabled tracing is therefore not a second
    timing implementation, just a skipped append.
  * **No cross-thread contention on the hot path.** Each recording thread
    owns a ring (``_ThreadRing``); the tracer-level lock is taken only on
    first touch per thread and at export. Rings are bounded: overflow drops
    the *oldest* events and counts the drops (exported, never silent).
  * **Host-only by construction.** Spans wrap host-side stages (producer
    build, repad, staging, the device_get sync). Nothing here may be called
    from jit-traced code — the splint purity rule HP008 pins that statically
    (docs/ANALYSIS.md).

Flow events link a producer thread's ``plan/build`` span to the consumer
``step`` that trains on the resulting plan, keyed by the plan's
``(epoch, batch)`` id: the producer records the *start* point inside its
build span, the consumer records the *finish* point inside its step span,
and the exporter emits a Chrome ``s``/``f`` pair per resolved id.

On the profiler's clock: with a tracer attached, every span also enters a
``jax.profiler.TraceAnnotation`` of its name (a ``StepTraceAnnotation``
named ``train`` for a span given ``step_num``), and its scalar attrs become
the annotation's metadata at exit. Under ``jax.profiler.trace`` the spans
thus land in the profiler's host plane, on the thread that ran them and on
the device ops' clock; outside a profiler session an annotation is a no-op.
A span made with ``cpu=True`` also records ``cpu_s``, the thread's CPU
seconds over the span (``time.thread_time``), so a producer that waits on
the GIL or a lock reads less CPU than wall time.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from dataclasses import dataclass

import jax

__all__ = ["Span", "SpanEvent", "Tracer"]

#: the profiler name of a span given ``step_num`` (xprof's step view)
STEP_TRACE_NAME = "train"


@dataclass(frozen=True)
class SpanEvent:
    """One completed span as stored in a ring (times are ``perf_counter``)."""

    name: str
    t0: float
    t1: float
    attrs: dict | None = None

    @property
    def duration(self) -> float:
        return self.t1 - self.t0


class Span:
    """Context manager that times a region and optionally records it.

    ``duration`` is valid after ``__exit__`` whether or not a tracer is
    attached — the trainer's stage timings (``EpochStats.t_sample`` etc.)
    read it on the disabled path too, so tracing on/off shares one timing
    code path. Without a tracer a span makes the two ``perf_counter`` calls
    and nothing else: no annotation, no ``thread_time``.
    """

    __slots__ = ("_tracer", "name", "attrs", "t0", "t1", "cpu", "step_num",
                 "_annotation", "_c0")

    def __init__(self, tracer: "Tracer | None", name: str, attrs=None, *,
                 cpu: bool = False, step_num: int | None = None):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.cpu = cpu
        self.step_num = step_num
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "Span":
        if self._tracer is not None:
            self._tracer._enter(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        if self._tracer is not None:
            self._tracer._exit(self)

    def set(self, **attrs) -> None:
        """Add attrs (counters known only inside the span) before exit."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)

    @property
    def duration(self) -> float:
        return self.t1 - self.t0


def _scalars(attrs: dict) -> dict:
    """The attrs a profiler annotation can carry as metadata."""
    return {k: v for k, v in attrs.items() if isinstance(v, (int, float, str))}


class _ThreadRing:
    """Bounded event store owned by one recording thread."""

    __slots__ = ("tid", "thread_name", "events", "dropped", "open_depth")

    def __init__(self, tid: int, thread_name: str, capacity: int):
        self.tid = tid
        self.thread_name = thread_name
        self.events: deque = deque(maxlen=capacity)
        self.dropped = 0
        self.open_depth = 0  # spans entered but not yet exited

    def append(self, kind: str, payload) -> None:
        if len(self.events) == self.events.maxlen:
            self.dropped += 1  # deque evicts the oldest on append
        self.events.append((kind, payload))


class Tracer:
    """Thread-safe span/flow recorder with Chrome-trace export.

    Recording threads never share a ring; the registry lock is touched only
    on a thread's first event and at export time. All timestamps are
    ``time.perf_counter()`` — one monotonic process-wide clock, so spans
    from different threads land on one consistent timeline.
    """

    def __init__(self, ring_capacity: int = 65536):
        if ring_capacity < 1:
            raise ValueError(f"ring_capacity must be >= 1, got {ring_capacity}")
        self._capacity = ring_capacity
        self._lock = threading.Lock()
        # a list, NOT an ident-keyed dict: the OS recycles thread idents, so
        # a producer pool respawned next epoch would silently overwrite (and
        # lose) a dead worker's ring if idents were the key
        self._rings: list[_ThreadRing] = []
        self._local = threading.local()
        self.t_origin = time.perf_counter()  # export-relative zero

    # ---- hot path ----------------------------------------------------- #
    def _ring(self) -> _ThreadRing:
        ring = getattr(self._local, "ring", None)
        if ring is None:
            t = threading.current_thread()
            ring = _ThreadRing(t.ident, t.name, self._capacity)
            self._local.ring = ring
            with self._lock:
                self._rings.append(ring)
        return ring

    def span(self, name: str, attrs=None, **kw) -> Span:
        return Span(self, name, attrs, **kw)

    def _enter(self, span: Span) -> None:
        self._ring().open_depth += 1
        if span.step_num is None:
            ann = jax.profiler.TraceAnnotation(span.name)
        else:
            ann = jax.profiler.StepTraceAnnotation(
                STEP_TRACE_NAME, step_num=span.step_num
            )
        ann.__enter__()
        span._annotation = ann
        if span.cpu:
            span._c0 = time.thread_time()

    def _exit(self, span: Span) -> None:
        if span.cpu:
            span.set(cpu_s=time.thread_time() - span._c0)
        ann = span._annotation
        if span.attrs:
            ann.set_metadata(**_scalars(span.attrs))
        ann.__exit__(None, None, None)
        ring = self._ring()
        ring.open_depth -= 1
        ring.append(
            "X", SpanEvent(span.name, span.t0, span.t1, span.attrs)
        )

    def record(self, name: str, t0: float, t1: float, attrs=None) -> None:
        """Record a span with explicit ``perf_counter`` endpoints.

        For intervals that start on one thread and end on another (e.g. the
        prefetch-queue dwell between a producer finishing a batch and the
        consumer taking delivery) — the event lands on the *calling*
        thread's lane.
        """
        self._ring().append("X", SpanEvent(name, t0, t1, attrs))

    def instant(self, name: str, attrs=None) -> None:
        """A zero-duration marker (Chrome ``i`` event) at the current time."""
        self._ring().append(
            "i", SpanEvent(name, time.perf_counter(), 0.0, attrs)
        )

    def flow_start(self, flow_id) -> None:
        """Mark the producer end of a flow (call inside the producing span)."""
        self._ring().append("s", (flow_id, time.perf_counter()))

    def flow_end(self, flow_id) -> None:
        """Mark the consumer end of a flow (call inside the consuming span)."""
        self._ring().append("f", (flow_id, time.perf_counter()))

    # ---- export ------------------------------------------------------- #
    def _snapshot(self) -> list[_ThreadRing]:
        with self._lock:
            return list(self._rings)

    def unclosed_spans(self) -> int:
        """Spans currently entered but not exited, summed over threads."""
        return sum(r.open_depth for r in self._snapshot())

    def dropped_events(self) -> int:
        return sum(r.dropped for r in self._snapshot())

    def to_chrome(self, metrics: dict | None = None) -> dict:
        """The Chrome-trace (Perfetto-loadable) JSON object.

        ``ph: "X"`` complete events carry ts/dur in microseconds relative
        to tracer creation; flows are emitted as ``s``/``f`` pairs only for
        ids with both endpoints recorded (unresolved ids are counted in
        ``otherData`` instead of emitting dangling arrows); thread-name
        metadata events label the producer lanes. The ``otherData`` block
        carries the metrics snapshot plus the integrity counters the
        ``validate`` CLI checks.
        """
        events: list[dict] = []
        starts: dict = {}
        ends: dict = {}
        rings = self._snapshot()
        for ring in rings:
            events.append(
                {
                    "ph": "M",
                    "pid": 0,
                    "tid": ring.tid,
                    "name": "thread_name",
                    "args": {"name": ring.thread_name},
                }
            )
            for kind, payload in list(ring.events):
                if kind in ("X", "i"):
                    ev: SpanEvent = payload
                    rec = {
                        "ph": kind,
                        "pid": 0,
                        "tid": ring.tid,
                        "name": ev.name,
                        "ts": (ev.t0 - self.t_origin) * 1e6,
                    }
                    if kind == "X":
                        rec["dur"] = ev.duration * 1e6
                    if kind == "i":
                        rec["s"] = "t"  # instant scoped to its thread
                    if ev.attrs:
                        rec["args"] = dict(ev.attrs)
                    events.append(rec)
                elif kind == "s":
                    flow_id, ts = payload
                    starts[flow_id] = (ring.tid, ts)
                else:  # "f"
                    flow_id, ts = payload
                    ends[flow_id] = (ring.tid, ts)
        resolved = sorted(
            (k for k in starts if k in ends), key=lambda k: starts[k][1]
        )
        for seq, flow_id in enumerate(resolved):
            for ph, (tid, ts) in (
                ("s", starts[flow_id]),
                ("f", ends[flow_id]),
            ):
                rec = {
                    "ph": ph,
                    "pid": 0,
                    "tid": tid,
                    "id": seq,
                    "cat": "plan",
                    "name": "plan",
                    "ts": (ts - self.t_origin) * 1e6,
                }
                if ph == "f":
                    rec["bp"] = "e"  # bind to the enclosing slice
                events.append(rec)
        unresolved = (set(starts) | set(ends)) - set(resolved)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "unclosed_spans": sum(r.open_depth for r in rings),
                "dropped_events": sum(r.dropped for r in rings),
                "unresolved_flows": len(unresolved),
                "metrics": metrics or {},
            },
        }

    def write(self, path, metrics: dict | None = None) -> None:
        """Write the Chrome-trace JSON to ``path`` (atomic-enough rewrite)."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(metrics), f)
