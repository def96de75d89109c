"""Rate of the host -> device transfer of each step's batch, in GB/s.

Layer: staging (``train/plan_io.stage_batch``). Source: the program's
``step/put`` spans on the training loop's thread that start inside the
window, each with ``bytes``, the bytes of the arrays it sent to the device;
their sum over the spans' summed wall time.
"""


def read(run):
    puts = [
        s for s in run["spans"]
        if s["name"] == "step/put" and s["main"] and "bytes" in s["args"]
        and run["t0"] <= s["t0"] < run["t1"]
    ]
    wall = sum(s["t1"] - s["t0"] for s in puts)
    if wall <= 0:
        return None
    return sum(s["args"]["bytes"] for s in puts) / wall / 1e9
