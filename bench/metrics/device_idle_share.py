"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals / window), from the profiler trace."""


def read(run):
    red = run.get("reduction")
    if not red or not red["n_devices"] or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
