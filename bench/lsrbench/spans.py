"""The program's own spans and device scopes in a JAX profiler trace.

``read_xplane`` reads the ``.xplane.pb`` into the events
``xtrace.read_xplane`` gives (each device plane's ``XLA Ops``, the
benchmark's ``bench.*`` annotations), so ``xtrace.reduce`` and
``xtrace.kernel_s`` read them unchanged, and adds two keys:

- ``spans``: the program's host spans (``repro.*``, written by
  ``repro.obs.spans.scope``), ``[name, start_ns, dur_ns, thread]``, the
  thread numbered by its line in the host plane;
- ``op_scopes``: for each device op name, its op-name path (the
  ``jax.named_scope`` and transform names it was traced under, ending
  in the operation), e.g. ``fusion.102`` ->
  ``jit(_retrieve_chunked_impl)/while/body/vmap(gather)/
  vmap(jit(gather_tile))/jit(_take)/gather``. ``ProfileData`` gives an
  op's event stats only (on a v5e: its device offset and duration), so
  the path comes from the compiled modules the profiler stores in the
  ``/host:metadata`` plane (an ``Hlo Proto`` stat per module): each
  instruction's name and ``metadata.op_name``, read from the
  ``.xplane.pb`` by a small protobuf reader. Where two modules hold an
  instruction of one name, the larger module's path is kept: the
  retrieval program holds nearly all device time.

The reductions read those events alone, inside the window
(``bench.window``) and on device 0:

- ``label_gaps``: the longest device-idle gaps, each named by the
  ``repro.*`` span that overlaps it most, else by the ``bench.*``
  annotation that does (what the load generator was doing), else
  ``unannotated``;
- ``covered_idle_share``: the share of device-idle time that some
  ``repro.*`` span overlaps;
- ``idle_host_share``: the share of the window in which the device runs
  nothing while the executor's own host work (``HOST_SPANS``) or a
  collection (``repro.gc``) is under way;
- ``scope_s``: device seconds of the ops whose path holds a scope;
- ``gather_useful_roofline``: the useful bytes of the window's long-route
  requests at the chip's peak bandwidth, over the gather scope's device
  seconds.
"""
from __future__ import annotations

import re

import numpy as np

from .xtrace import HOST_PREFIX, OPS_LINE, _clipped, _merge, _window

SPAN_PREFIX = "repro."
# the executor's host work on a batch (not its wait on the device, not
# parking), and collections, which stop every thread
HOST_SPANS = ("repro.pick", "repro.assemble", "repro.dispatch",
              "repro.finish", "repro.deliver", "repro.gc")


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    device, host, spans = {}, [], []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:") and not plane.name.startswith(
                "/device:CUSTOM"):
            ops = [ln for ln in lines if ln.name == OPS_LINE]
            if ops:
                device[plane.name] = [
                    [e.name.split(" = ", 1)[0].lstrip("%"),
                     float(e.start_ns), float(e.duration_ns)]
                    for e in ops[0].events]
        elif plane.name.startswith("/host:"):
            for i, ln in enumerate(lines):
                for e in ln.events:
                    ev = [e.name, float(e.start_ns), float(e.duration_ns)]
                    if e.name.startswith(HOST_PREFIX):
                        host.append(ev)
                    elif e.name.startswith(SPAN_PREFIX):
                        spans.append(ev + [f"{plane.name}#{i}"])
    with open(path, "rb") as f:
        op_scopes = module_op_names(f.read())
    return {"device": device, "host": host, "spans": spans,
            "op_scopes": op_scopes}


def _fields(buf: memoryview):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field, skipped else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        value |= (b & 0x7F) << shift
        i += 1
        if b < 0x80:
            return value, i
        shift += 7


def _sub(buf, number: int) -> list:
    return [v for f, v in _fields(buf) if f == number]


def _str(buf, number: int) -> str:
    return b"".join(_sub(buf, number)).decode()


def module_op_names(xspace: bytes) -> dict:
    """{instruction name: metadata.op_name} over the compiled modules in
    an XSpace's ``/host:metadata`` plane. Field numbers: XSpace.planes 1;
    XPlane.name 2, .event_metadata 4 and .stat_metadata 5 (map entries,
    value 2); XStatMetadata.name 2; XEventMetadata.stats 5;
    XStat.metadata_id 1, .bytes_value 6; HloProto.hlo_module 1;
    HloModuleProto.computations 3; HloComputationProto.instructions 2;
    HloInstructionProto.name 1, .metadata 7; OpMetadata.op_name 2."""
    modules = []
    for plane in _sub(memoryview(xspace), 1):
        if _str(plane, 2) != "/host:metadata":
            continue
        stat_names = {}
        for entry in _sub(plane, 5):
            for meta in _sub(entry, 2):
                stat_names[dict(_fields(meta)).get(1)] = _str(meta, 2)
        for entry in _sub(plane, 4):
            for em in _sub(entry, 2):
                for stat in _sub(em, 5):
                    fields = dict(_fields(stat))
                    if stat_names.get(fields.get(1)) == "Hlo Proto":
                        modules.append(_instructions(fields[6]))
    out = {}
    for names in sorted(modules, key=len):
        out.update(names)
    return out


def _instructions(hlo_proto) -> dict:
    names = {}
    for module in _sub(hlo_proto, 1):
        for comp in _sub(module, 3):
            for inst in _sub(comp, 2):
                names[_str(inst, 1)] = "".join(
                    _str(meta, 2) for meta in _sub(inst, 7))
    return names


def scopes_of(path: str) -> set:
    """The named scopes on an op-name path: every component but the last
    (the operation), with transform wrappers such as ``vmap(...)``
    taken off."""
    return {re.sub(r"^(\w+\()+|\)+$", "", c) for c in path.split("/")[:-1]}


def _idle(events: dict) -> tuple[np.ndarray, float, float]:
    """Device-0 idle intervals inside the window, and the window."""
    lo, hi = _window(events)
    plane = sorted(events["device"])[0]
    busy = _merge(_clipped(events["device"][plane], lo, hi))
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]], lo, hi


def _overlap_s(a: np.ndarray, b: np.ndarray) -> float:
    """Seconds in both of two sets of disjoint sorted ns intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i, 1], b[j, 1]) - max(a[i, 0], b[j, 0]))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total * 1e-9


def _spans(events: dict, names=None, lo=-np.inf, hi=np.inf) -> np.ndarray:
    evs = [s for s in events.get("spans", [])
           if names is None or s[0] in names]
    return _merge(_clipped(evs, lo, hi))


def label_gaps(events: dict, top: int = 10) -> list:
    """[label, seconds] for the ``top`` longest idle gaps, longest first."""
    if not events["device"]:
        return []
    idle, _, _ = _idle(events)
    longest = idle[np.argsort(idle[:, 0] - idle[:, 1], kind="stable")[:top]]
    named = ([(s[0], s[1], s[2]) for s in events.get("spans", [])],
             [(h[0][len(HOST_PREFIX):], h[1], h[2]) for h in events["host"]
              if h[0] != HOST_PREFIX + "window"])
    out = []
    for g0, g1 in longest:
        label = "unannotated"
        for candidates in named:
            best = 0.0
            for name, start, dur in candidates:
                ov = min(g1, start + dur) - max(g0, start)
                if ov > best:
                    best, label = ov, name
            if best > 0:
                break
        out.append([label, float(g1 - g0) * 1e-9])
    return out


def covered_idle_share(events: dict) -> float | None:
    """Share of device-0 idle time inside the window during which some
    ``repro.*`` span is open."""
    if not events["device"]:
        return None
    idle, lo, hi = _idle(events)
    idle_s = float((idle[:, 1] - idle[:, 0]).sum()) * 1e-9
    if idle_s <= 0:
        return None
    return _overlap_s(idle, _spans(events, None, lo, hi)) / idle_s


def idle_host_share(events: dict) -> float | None:
    """Share of the window in which device 0 runs nothing while one of
    ``HOST_SPANS`` is open: the part of the device's idle share that the
    program's own host work, or a collection, accounts for."""
    if not events["device"]:
        return None
    idle, lo, hi = _idle(events)
    if hi <= lo:
        return None
    return _overlap_s(idle, _spans(events, HOST_SPANS, lo, hi)) / (
        (hi - lo) * 1e-9)


def scope_s(events: dict, scope: str) -> float:
    """Device seconds, inside the window, of the ops whose op-name path
    holds ``scope``."""
    ops = {op for op, path in events.get("op_scopes", {}).items()
           if scope in scopes_of(path)}
    lo, hi = _window(events)
    total = 0.0
    for evs in events["device"].values():
        for e in evs:
            if e[0] in ops:
                total += max(0.0, min(hi, e[1] + e[2]) - max(lo, e[1]))
    return total * 1e-9


def useful_bytes(records: list, roofline: dict) -> float:
    """Bytes the long route's requests needed, as
    ``guided_score_chunk_roofline`` counts them: postings scored times
    the bytes of a posting, plus the visited (term, tile) runs times the
    bytes of a run's offsets."""
    return sum(r["stats"]["postings_touched"] * roofline["bytes_per_posting"]
               + r["stats"]["tiles_visited"] * r["live_terms"]
               * roofline["bytes_per_run"]
               for r in records if r is not None and r["route"] == "long")


def gather_useful_roofline(events: dict, records: list, roofline: dict,
                           hbm_bytes_per_s: float) -> float | None:
    """The useful bytes at peak bandwidth over the gather scope's device
    time, in percent: the same work judges any gather."""
    seconds = scope_s(events, "gather")
    useful = useful_bytes(records, roofline)
    if seconds <= 0 or useful <= 0:
        return None
    return 100.0 * useful / hbm_bytes_per_s / seconds
