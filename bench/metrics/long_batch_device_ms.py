"""Mean time a long-route batch keeps its executor waiting on the device
(the program's ``repro.device_wait`` span), from the scheduler's
per-route ``batch_device_ms`` histogram (``by_route`` in its stats) over
the window's batches. None where the program keeps no per-route
histograms or the route ran no batch."""


def read(run):
    route = (run["stats"].get("by_route") or {}).get("long") or {}
    h = route.get("batch_device_ms") or {}
    return h.get("mean") if h.get("n") else None
