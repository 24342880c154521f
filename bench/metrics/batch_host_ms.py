"""Mean host time an executor spends on one batch outside its wait on
the device: picking it, assembling and padding its rows, dispatching
it, finishing its result and delivering it (the program's ``repro.pick``
/ ``assemble`` / ``dispatch`` / ``finish`` / ``deliver`` spans), from the
scheduler's own ``batch_host_ms`` histogram over the window's batches
(the executor records a batch once its answers are out, so the last
batch's sample may trail the drain and be left out)."""


def read(run):
    h = run["stats"].get("batch_host_ms") or {}
    return h.get("mean") if h.get("n") else None
