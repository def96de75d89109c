"""Mean host time to stage one step and dispatch it, in milliseconds.

Layer: staging (``train/plan_io.stage_batch`` and the step's dispatch).
Source: the program's ``step/stage`` spans in the window.
"""


def read(run):
    stages = [
        s["t1"] - s["t0"] for s in run["spans"]
        if s["name"] == "step/stage" and s["main"] and run["t0"] <= s["t0"] < run["t1"]
    ]
    if not stages:
        return None
    return 1e3 * sum(stages) / len(stages)
