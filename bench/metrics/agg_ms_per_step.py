"""Device time of the step's neighbour aggregation, in milliseconds a step.

Layer: the model step (``models/gnn/layers.py``: every aggregation, forward
and backward, runs under ``jax.named_scope("agg")``). Source: the device
trace; the window's device seconds of ops whose scope path has the
component ``agg`` (``scope_seconds``: the trace's ops joined with the step's
optimized HLO, ``bench/trace_reduce.op_paths``), over the window's steps.
"""


def read(run):
    seconds = run["scope_seconds"]("agg")
    if seconds <= 0 or not run["steps"]:
        return None
    return 1e3 * seconds / run["steps"]
