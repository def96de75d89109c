"""Share of the traced window in which no operation ran on the device.

Layer: the device. Source: the device trace; one less the union of the
op intervals over the window (``bench/trace_reduce.busy_seconds``).
"""


def read(run):
    window = run["trace_window_s"]
    if window <= 0:
        return None
    return 100.0 * (1.0 - run["busy_s"] / window)
