"""Share of the host feature gathers written into a reused block, in percent.

Layer: plan production, its feature load (``train/plan_io.stage_host_features``).
Source: the program's ``plan/load`` spans that start inside the window, each
with ``reused``, 1 when the block its rows were written into came from the
program's pool of feature blocks and 0 when it was allocated.
"""


def read(run):
    flags = [
        s["args"]["reused"] for s in run["spans"]
        if s["name"] == "plan/load" and "reused" in s["args"]
        and run["t0"] <= s["t0"] < run["t1"]
    ]
    if not flags:
        return None
    return 100.0 * sum(flags) / len(flags)
