"""From a JAX profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler.trace`` writes into a
small plain form: the device's operation events (``ops``: ``op_name``, start and
duration in nanoseconds, one list per device) and the host annotations the
benchmark placed (``marks``: name -> [start, end] in nanoseconds), all on
the profiler's one clock. The functions below work on that form only, so a
recorded trace tests them without a chip.

Busy time is the union of the intervals in which an operation ran on the
device, clipped to the traced window; the idle share is one less busy over
the window.
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"  # the line of a TPU plane that holds one event per op
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def load(log_dir: str, marks: tuple[str, ...]) -> dict:
    """The plain form of the one trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {paths}")
    data = ProfileData.from_file(paths[0])
    out = {"ops": [], "marks": {}}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [
                [op_name(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                for line in plane.lines if line.name == OPS_LINE
                for ev in line.events
            ]
            out["ops"].append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in marks:
                        out["marks"][ev.name] = [
                            int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                        ]
    return out


def op_name(hlo: str) -> str:
    """``"%name = <shape> opcode(...), ..."`` (a TPU op event's name is its
    HLO instruction) -> ``"name opcode"``."""
    head, _, rest = hlo.partition(" = ")
    m = re.search(r"\s([a-z][\w-]*)\(", " " + rest)
    return f"{head.lstrip('%')} {m.group(1)}" if m else head


def merged(ops: list, t0: int, t1: int) -> list[tuple[int, int]]:
    """The union of the ops' intervals within ``[t0, t1)``, in order."""
    spans = sorted(
        (max(s, t0), min(s + d, t1)) for _, s, d in ops if s < t1 and s + d > t0
    )
    out: list[list[int]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(trace: dict, t0: int, t1: int) -> float:
    """Busy seconds in the window, averaged over the devices traced."""
    per = [sum(e - s for s, e in merged(ops, t0, t1)) for ops in trace["ops"]]
    return sum(per) / len(per) / 1e9 if per else 0.0


def idle_gaps(trace: dict, t0: int, t1: int) -> list[tuple[int, int]]:
    """The device's idle intervals within the window (first device),
    longest first."""
    if not trace["ops"]:
        return [(t0, t1)]
    busy = merged(trace["ops"][0], t0, t1)
    gaps, at = [], t0
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if at < t1:
        gaps.append((at, t1))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def op_seconds(trace: dict, t0: int, t1: int, patterns=None) -> dict[str, float]:
    """Seconds of device time by op name within the window (averaged over
    devices), optionally only of ops whose name matches one of
    ``patterns`` (regular expressions)."""
    res = [re.compile(p) for p in patterns] if patterns else None
    total: dict[str, float] = {}
    n = max(len(trace["ops"]), 1)
    for ops in trace["ops"]:
        for name, s, d in ops:
            if s >= t1 or s + d <= t0:
                continue
            if res is not None and not any(r.search(name) for r in res):
                continue
            clipped = min(s + d, t1) - max(s, t0)
            total[name] = total.get(name, 0.0) + clipped / 1e9 / n
    return total

