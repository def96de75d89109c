"""From a JAX profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler.trace`` writes into a
small plain form: the device's operation events (``ops``: ``op_name``, start and
duration in nanoseconds, one list per device), the device's program runs
(``modules``: the module's name as the trace gives it, start and duration,
one list per device) and the host annotations the benchmark placed
(``marks``: name -> [start, end] in nanoseconds), all on the profiler's one
clock. The functions below work on that form only, so a recorded trace
tests them without a chip.

Busy time is the union of the intervals in which an operation ran on the
device, clipped to the traced window; the idle share is one less busy over
the window.

Scopes: an op event carries only its HLO instruction, with no metadata. The
optimized HLO text of a program carries each instruction's
``metadata={op_name="jit(step)/jvp(gnn/layer0)/vmap(agg)/gather"}``, the
``jax.named_scope`` path under JAX's transformations. ``hlo_scopes`` reads
one module's text into the scope path of each instruction (a fusion's path
is the deepest one that all the instructions it runs share); ``op_paths``
joins the trace's ops on it, keyed by the module each op ran in.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"  # the line of a TPU plane that holds one event per op
MODULES_LINE = "XLA Modules"  # ... and one event per program run
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def load(log_dir: str, marks: tuple[str, ...]) -> dict:
    """The plain form of the one trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {paths}")
    data = ProfileData.from_file(paths[0])
    out = {"ops": [], "modules": [], "marks": {}}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line.events for line in plane.lines}
            out["ops"].append([[op_name(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                               for ev in lines.get(OPS_LINE, [])])
            out["modules"].append([[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                                   for ev in lines.get(MODULES_LINE, [])])
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


# --------------------------------------------------------------------------- #
# scopes
# --------------------------------------------------------------------------- #
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) \(.*\{$")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([\w.-]+)")
_MODULE = re.compile(r"^HloModule ([\w.-]+)")
_MODULE_RUN = re.compile(r"^(.*)\(\w+\)$")  # a trace's "jit_step(<id>)"


def module_name(text: str) -> str:
    """The name of the module of an HLO text (``jit_step``)."""
    m = _MODULE.match(text)
    return m.group(1) if m else ""


def scope_components(op_path: str) -> list[str]:
    """The named scopes of an ``op_name`` path, outermost first, without
    JAX's transformation wrappers and the primitive at its end:
    ``jit(step)/transpose(jvp(gnn/layer1))/vmap(agg)/mul`` ->
    ``[step, gnn, layer1, agg]``."""
    parts = op_path.split("/")
    if parts and "(" not in parts[-1] and ")" not in parts[-1]:
        parts = parts[:-1]  # the primitive
    names = (p[p.rfind("(") + 1:].replace(")", "") for p in parts)
    return [n for n in names if n]


def hlo_scopes(text: str) -> dict[str, str | None]:
    """``"name opcode"`` (as ``op_name`` makes it) -> scope path of each
    instruction of one optimized HLO module, the components joined by ``/``.

    An instruction's path is that of its own ``op_name`` metadata and of
    every instruction of the computations it ``calls`` (a fusion's body,
    nested fusions within it), cut to the components they all share; None
    where none of them carries a scope. Reductions' ``to_apply`` bodies are
    not followed: their metadata names no scope."""
    comps: dict[str, list[tuple[str, str | None, list[str]]]] = {}
    body = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            body = comps.setdefault(head.group(1), [])
            continue
        if body is None or " = " not in line:
            continue
        inst = line.strip().removeprefix("ROOT ")
        meta = _OP_NAME.search(inst)
        body.append((op_name(inst.split(", metadata=")[0]),
                     meta.group(1) if meta else None, _CALLS.findall(inst)))

    memo: dict[str, list[list[str]]] = {}

    def paths(own, calls) -> list[list[str]]:
        out = [scope_components(own)] if own else []
        for c in calls:
            if c not in memo:
                memo[c] = [p for _, o, cs in comps.get(c, []) for p in paths(o, cs)]
            out += memo[c]
        return out

    table: dict[str, str | None] = {}
    for insts in comps.values():
        for key, own, calls in insts:
            common = os.path.commonprefix([p for p in paths(own, calls) if p])
            table[key] = "/".join(common) or None
    return table


def op_paths(trace: dict, programs: list[tuple[str, dict]]) -> list[list]:
    """The scope path of each op of ``trace["ops"]`` (None where unknown),
    from ``programs``: (module name, ``hlo_scopes`` table) of each program
    kept.

    An op that ran inside a module run of the trace (``modules``, named
    ``<module>(<id>)``) is looked up in the kept programs of that module
    name; an op of a module not kept has no path. The id is not the
    executable's fingerprint (a v5e trace reads ``jit_step(1246...6955)``
    where the fingerprint is 32 bytes that hold no such number), so
    programs of one name are told apart no further. Where the trace names
    no module, the op is looked up in every kept program. An instruction
    name that carries different paths in the programs looked in has none."""
    out = []
    for d, ops in enumerate(trace["ops"]):
        runs = sorted(trace.get("modules", [[]] * len(trace["ops"]))[d],
                      key=lambda r: r[1])
        starts = [r[1] for r in runs]
        paths = []
        for name, s, _ in ops:
            i = bisect.bisect_right(starts, s) - 1
            tables = [t for _, t in programs]
            if i >= 0 and s < runs[i][1] + runs[i][2]:
                m = _MODULE_RUN.match(runs[i][0])
                base = m.group(1) if m else runs[i][0]
                tables = [t for n, t in programs if n == base]
            found = {t[name] for t in tables if name in t}
            paths.append(found.pop() if len(found) == 1 else None)
        out.append(paths)
    return out


def scope_seconds(trace: dict, paths: list[list], t0: int, t1: int,
                  scope: str | None) -> float:
    """Seconds of device time within the window (averaged over devices) of
    ops whose path has ``scope`` as one of its components; with ``None``,
    of ops that have no path."""
    total = 0.0
    for ops, ps in zip(trace["ops"], paths):
        for (_, s, d), p in zip(ops, ps):
            if s >= t1 or s + d <= t0:
                continue
            if (p is None) if scope is None else (p is not None and scope in p.split("/")):
                total += (min(s + d, t1) - max(s, t0)) / 1e9
    return total / max(len(trace["ops"]), 1)
