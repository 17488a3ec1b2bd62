"""The program's host stages, on its own clock and on the profiler's.

`ms_per_update` reads a `core.timing` stage over the window. `events`
loads a traced run's profiler trace once: the newest ``.xplane.pb`` under
``results/bench/trace/<cell>``, keeping the host spans named ``ams.``
(the program's stages) and ``bench.`` (the benchmark's) and each device
plane's ``XLA Ops`` intervals, in `trace_reduce.load_events`'s shape.
`idle_by_span` splits the device's idle time in the traced window exactly
among the innermost host spans open over each part of it, and `share`
gives the part under some of them as a share of the window.
"""
import glob
import os

import _stages
import trace_reduce

HOST_PREFIXES = ("ams.", "bench.")
WINDOW = "bench.traced"
_LOADED: dict = {}  # (path, mtime) -> events: the file is read once


def ms_per_update(run, stage: str):
    """ms the program's ``stage`` records put in the window, per update
    trained; None where the run is untraced or the program has no such
    stage."""
    if run.timing is None or all(s != stage for s, _key in run.timing):
        return None
    n = sum(g["width"] for g in run.grants)
    return _stages.seconds(run, stage) / n * 1000 if n else None


def events(run):
    """The run's trace events, or None where it has no device trace."""
    if run.trace is None:
        return None
    trace_dir = os.path.join(run.cell.root, "results", "bench", "trace",
                             run.cell.name)
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    key = (paths[-1], os.path.getmtime(paths[-1]))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = _load(paths[-1])
    return _LOADED[key]


def _load(path: str) -> dict:
    from jax.profiler import ProfileData

    host, devices = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        [e.name[:trace_reduce.NAME_CHARS], e.start_ns,
                         e.end_ns] for e in line.events)
        else:
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.end_ns]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    return {"host": host, "devices": devices}


def _labelled(host: list, ws: float, we: float) -> list:
    """[start, end, label] pieces tiling [ws, we]: each labelled with the
    innermost host span open over it (the latest opened), or "other"."""
    spans = [(max(s, ws), min(e, we), n) for n, s, e in host
             if e > ws and s < we]
    cuts = sorted({ws, we} | {t for s, e, _n in spans for t in (s, e)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        open_ = [(s, -e, n) for s, e, n in spans if s <= a and e >= b]
        out.append([a, b, max(open_)[2] if open_ else "other"])
    return out


def _idle_pieces(pieces: list, busy: list):
    """The parts of the labelled ``pieces`` that no ``busy`` interval
    covers; both lists sorted and disjoint."""
    j = 0
    for a, b, label in pieces:
        while j < len(busy) and busy[j][1] <= a:
            j += 1
        t, k = a, j
        while k < len(busy) and busy[k][0] < b:
            if busy[k][0] > t:
                yield t, min(busy[k][0], b), label
            t = max(t, busy[k][1])
            k += 1
        if t < b:
            yield t, b, label


def idle_by_span(ev: dict, n_devices: int):
    """({innermost span or "other": idle seconds}, window seconds) over the
    ``bench.traced`` span, averaged over the first ``n_devices`` device
    planes by name (those `trace_reduce.reduce` reads); None where the
    trace lacks the window or the devices."""
    window = [(s, e) for n, s, e in ev["host"] if n == WINDOW]
    planes = sorted(ev["devices"])[:n_devices]
    if not window or len(planes) < n_devices:
        return None
    ws, we = window[0]
    pieces = _labelled([h for h in ev["host"] if h[0] != WINDOW], ws, we)
    idle: dict = {}
    for plane in planes:
        busy = trace_reduce._union(
            [[max(s, ws), min(e, we)] for _n, s, e in ev["devices"][plane]
             if e > ws and s < we])
        for a, b, label in _idle_pieces(pieces, busy):
            idle[label] = idle.get(label, 0.0) + (b - a)
    return ({k: v / len(planes) / 1e9 for k, v in idle.items()},
            (we - ws) / 1e9)


def share(ev, n_devices: int, names: tuple):
    """% of the traced window in which the device idles under one of the
    host spans ``names``; None where the trace has none of them."""
    if ev is None or not any(h[0] in names for h in ev["host"]):
        return None
    split = idle_by_span(ev, n_devices)
    if split is None:
        return None
    idle, window_s = split
    return 100 * sum(idle.get(n, 0.0) for n in names) / window_s
