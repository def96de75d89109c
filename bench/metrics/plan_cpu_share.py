"""Share of plan production's wall time its threads spent on a CPU, in percent.

Layer: plan production (``runtime/plan_source.py``). Source: the program's
``plan/build`` spans that start inside the window, each with ``cpu_s``, the
producer thread's CPU seconds over the span (``time.thread_time``). The rest
of a build's wall time is waiting: for the GIL, a lock or the OS.
"""


def read(run):
    builds = [
        s for s in run["spans"]
        if s["name"] == "plan/build" and "cpu_s" in s["args"]
        and run["t0"] <= s["t0"] < run["t1"]
    ]
    wall = sum(s["t1"] - s["t0"] for s in builds)
    if wall <= 0:
        return None
    return 100.0 * sum(s["args"]["cpu_s"] for s in builds) / wall
