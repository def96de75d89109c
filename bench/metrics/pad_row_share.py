"""Share of the staged feature rows that are padding, in percent.

Layer: staging (the padded feature block ``train/plan_io.stage_batch`` sends).
Source: the program's ``plan/repad`` spans that start inside the window, each
with ``rows``, the true rows of the batch's feature block, and
``rows_padded``, its height after the repad to the high-water marks.
"""


def read(run):
    repads = [
        s["args"] for s in run["spans"]
        if s["name"] == "plan/repad" and "rows_padded" in s["args"]
        and run["t0"] <= s["t0"] < run["t1"]
    ]
    padded = sum(a["rows_padded"] for a in repads)
    if padded <= 0:
        return None
    return 100.0 * (padded - sum(a["rows"] for a in repads)) / padded
