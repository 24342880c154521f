"""Mean wait between a short-route request's due time and its batch's
pick, from the scheduler's per-route ``queue_wait_ms`` histogram
(``by_route`` in its stats) over the window's requests. None where the
program keeps no per-route histograms or the route served nothing."""


def read(run):
    route = (run["stats"].get("by_route") or {}).get("short") or {}
    h = route.get("queue_wait_ms") or {}
    return h.get("mean") if h.get("n") else None
