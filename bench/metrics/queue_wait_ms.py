"""Mean wait between a request's due time and its batch's pick, from the
scheduler's own ``queue_wait_ms`` histogram over the window's requests."""


def read(run):
    q = run["stats"].get("queue_wait_ms") or {}
    return q.get("mean") if q.get("n") else None
