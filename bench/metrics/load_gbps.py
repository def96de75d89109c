"""Rate of the host's feature gather, in GB/s (1e9 bytes a second).

Layer: plan production, its feature load (``train/plan_io.stage_host_features``).
Source: the program's ``plan/load`` spans that start inside the window, each
with ``bytes``, what its true rows read from the feature table; their sum
over the spans' summed wall time.
"""


def read(run):
    loads = [
        s for s in run["spans"]
        if s["name"] == "plan/load" and "bytes" in s["args"]
        and run["t0"] <= s["t0"] < run["t1"]
    ]
    wall = sum(s["t1"] - s["t0"] for s in loads)
    if wall <= 0:
        return None
    return sum(s["args"]["bytes"] for s in loads) / wall / 1e9
