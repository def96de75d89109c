"""Mean host time to build one batch's plan, in milliseconds.

Layer: plan production (``runtime/plan_source.py``, ``core/splitting.py``,
``graph/sampling.py``, ``train/plan_io.load_features``). Source: the
program's ``plan/build`` spans (sample, split or dp plan, feature load) on
the producer threads that start inside the window.
"""


def read(run):
    builds = [
        s["t1"] - s["t0"] for s in run["spans"]
        if s["name"] == "plan/build" and run["t0"] <= s["t0"] < run["t1"]
    ]
    if not builds:
        return None
    return 1e3 * sum(builds) / len(builds)
