"""From a profiler trace to device busy time, top operations and idle gaps.

`load_events` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
keeps two things: the benchmark's own host spans (``TraceAnnotation``
names starting with ``bench.``) and, per device plane, the intervals of
the operations that ran on it (the ``XLA Ops`` line). `reduce` clips both
to the traced window (the ``bench.traced`` span) and returns

- ``busy_s``: the union of the device's operation intervals, averaged over
  the cell's devices; ``window_s``: the window's length;
- ``device_ops``: the ten operations that took most device time, as
  [name, seconds] (seconds averaged over the devices);
- ``idle_gaps``: the ten longest stretches with no operation on a device,
  as [what the host was doing, seconds], the host activity being the
  innermost ``bench.`` span around the gap's midpoint.

The events file (`save_events` / `read_events`) is the reduction's input
in plain JSON, so a recorded trace can be kept small and checked.
"""
from __future__ import annotations

import glob
import gzip
import json
import os

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
NAME_CHARS = 120  # an operation's name is its HLO text: keep its head


def load_events(trace_dir: str) -> dict:
    """{"host": [[name, start_ns, end_ns]], "devices": {plane: [[name,
    start_ns, end_ns]]}} from the newest ``.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    host, devices = [], {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        [e.name[:NAME_CHARS], e.start_ns, e.end_ns]
                        for e in line.events)
        else:
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.end_ns]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"host": host, "devices": devices}


def save_events(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read_events(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _host_label(host: list, t: float) -> str:
    around = [(e - s, n) for n, s, e in host if s <= t <= e]
    if not around:
        return "other"
    return min(around)[1][len(HOST_PREFIX):]


def reduce(events: dict, n_devices: int, window: str = "bench.traced",
           top: int = 10) -> dict:
    """Busy time, top operations and idle gaps inside the ``window``
    span, over the first ``n_devices`` device planes (by name)."""
    spans = [(s, e) for n, s, e in events["host"] if n == window]
    if not spans:
        raise ValueError(f"no {window!r} span in the trace")
    ws, we = spans[0]
    host = [h for h in events["host"] if h[0] != window]
    planes = sorted(events["devices"])[:n_devices]
    if len(planes) < n_devices:
        raise ValueError(f"{n_devices} devices wanted, the trace has "
                         f"{len(planes)} with an {OPS_LINE!r} line")
    busy, ops, gaps = [], {}, []
    for plane in planes:
        clipped = [(n, max(s, ws), min(e, we))
                   for n, s, e in events["devices"][plane]
                   if e > ws and s < we]
        for n, s, e in clipped:
            ops[n] = ops.get(n, 0.0) + (e - s)
        union = _union([[s, e] for _n, s, e in clipped])
        busy.append(sum(e - s for s, e in union))
        edge = ws
        for s, e in union + [[we, we]]:
            if s > edge:
                gaps.append((s - edge, _host_label(host, (s + edge) / 2)))
            edge = max(edge, e)
    ops_top = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[0])
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (we - ws) / 1e9,
        "device_ops": [[n, v / len(planes) / 1e9] for n, v in ops_top],
        "idle_gaps": [[label, d / 1e9] for d, label in gaps[:top]],
    }
