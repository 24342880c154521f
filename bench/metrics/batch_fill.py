"""Real rows over the rows the dispatched batches could hold
(batches x max_batch), from the scheduler's counters over the window."""


def read(run):
    s = run["stats"]
    cap = s["batches"] * run["config"]["serving"]["max_batch"]
    return 100.0 * s["rows_executed"] / cap if cap else None
