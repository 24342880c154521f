"""Reduction of a JAX profiler trace to device busy time and kernel time.

``read_xplane`` turns the ``.xplane.pb`` the profiler writes into plain
events: for each device plane, the operations of its ``XLA Ops`` line
(HLO instruction name, start, duration in ns), and the benchmark's own
host annotations (names that start with ``bench.``). ``reduce`` computes from those events alone:

- ``busy_s``: the union of the device-op intervals inside the traced
  window, averaged over the devices;
- ``window_s``: the traced window, the span of the ``bench.window``
  annotation (the whole trace when it is absent);
- ``device_ops``: device time by op name, largest first, leaving out
  the control-flow ops (``while``, ``conditional``, ``call``) whose
  span holds the ops they run;
- ``idle_gaps``: the longest gaps between busy intervals on device 0,
  each named by the benchmark annotation that overlaps it most (what
  the load generator was doing: submitting, waiting for the next due
  time, draining), ``unannotated`` when none does;
- ``kernel_s(patterns)``: summed device time of the ops whose name
  contains any of the patterns.
"""
from __future__ import annotations

import glob
import os

import numpy as np

OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def read_xplane(path: str) -> dict:
    """{"device": {plane: [[op_name, start_ns, dur_ns], ...]},
        "host": [[name, start_ns, dur_ns], ...]}"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:") and not plane.name.startswith(
                "/device:CUSTOM"):
            ops = [ln for ln in lines if ln.name == OPS_LINE]
            if not ops:
                continue
            # an op's event name is its whole HLO instruction; keep the
            # instruction name (a Pallas kernel's is named after the
            # jitted function that calls it, e.g. ..._guided_score_chunk__.7)
            device[plane.name] = [[e.name.split(" = ", 1)[0].lstrip("%"),
                                   float(e.start_ns), float(e.duration_ns)]
                                  for e in ops[0].events]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    return {"device": device, "host": host}


def _merge(intervals: np.ndarray) -> np.ndarray:
    """Union of [start, end) intervals as disjoint sorted intervals."""
    if not len(intervals):
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > ends[:-1]])
    starts = iv[new, 0]
    last = np.append(np.flatnonzero(new)[1:] - 1, len(iv) - 1)
    return np.stack([starts, ends[last]], axis=1)


def _window(events: dict) -> tuple[float, float]:
    marks = [h for h in events["host"] if h[0] == HOST_PREFIX + "window"]
    if marks:
        return marks[0][1], marks[0][1] + marks[0][2]
    spans = [(e[1], e[1] + e[2]) for evs in events["device"].values()
             for e in evs] + [(h[1], h[1] + h[2]) for h in events["host"]]
    if not spans:
        return 0.0, 0.0
    return min(s for s, _ in spans), max(e for _, e in spans)


def _clipped(evs, lo, hi) -> np.ndarray:
    iv = np.array([[e[1], e[1] + e[2]] for e in evs], np.float64
                  ).reshape(-1, 2)
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def reduce(events: dict, top: int = 10) -> dict:
    lo, hi = _window(events)
    window_s = (hi - lo) * 1e-9
    planes = sorted(events["device"])
    busy, per_op = [], {}
    for name in planes:
        evs = events["device"][name]
        merged = _merge(_clipped(evs, lo, hi))
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()) * 1e-9)
        for e in evs:
            if (e[1] < hi and e[1] + e[2] > lo
                    and not e[0].startswith(CONTAINERS)):
                per_op[e[0]] = per_op.get(e[0], 0.0) + e[2] * 1e-9
    gaps = []
    if planes:
        merged = _merge(_clipped(events["device"][planes[0]], lo, hi))
        edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
        edges = edges[edges[:, 1] > edges[:, 0]]
        longest = edges[np.argsort(edges[:, 0] - edges[:, 1],
                                   kind="stable")[:top]]
        host = [h for h in events["host"]
                if h[0] != HOST_PREFIX + "window"]
        for g0, g1 in longest:
            best, label = 0.0, "unannotated"
            for h in host:
                ov = min(g1, h[1] + h[2]) - max(g0, h[1])
                if ov > best:
                    best, label = ov, h[0][len(HOST_PREFIX):]
            gaps.append([label, float(g1 - g0) * 1e-9])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": float(np.mean(busy)) if busy else None,
            "window_s": window_s, "n_devices": len(planes),
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": gaps[:top]}


def kernel_s(events: dict, patterns) -> float:
    """Device seconds, inside the window, of ops whose name contains one
    of ``patterns``."""
    lo, hi = _window(events)
    total = 0.0
    for evs in events["device"].values():
        for e in evs:
            if any(p in e[0] for p in patterns):
                total += max(0.0, min(hi, e[1] + e[2]) - max(lo, e[1]))
    return total * 1e-9
