"""Roofline terms from the dry-run (EXPERIMENTS.md §Roofline).

    compute    = FLOPs / (chips * peak_FLOP/s)        [analytic model]
    memory     = HBM bytes / (chips * HBM_bw)         [analytic model]
    collective = collective_bytes / (chips * link_bw) [compiled HLO,
                  depth-1/2 unrolled compiles, linear depth extrapolation]

compiled.cost_analysis() is also recorded ("hlo_*", scan bodies counted once)
— see roofline/analytic.py for why it cannot be used directly for scanned
models, and tests/test_roofline.py for the analytic-vs-HLO validation.
"""
from __future__ import annotations

import math
import re

import numpy as np

from repro.launch.mesh import chip_peaks

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dt: str, dims: str) -> int:
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _line_collective(stripped: str):
    """(kind, bytes) for a collective-op HLO line, else None."""
    for kind in _COLLECTIVES:
        if f" {kind}(" in stripped or f" {kind}-start(" in stripped:
            eq = stripped.find("=")
            if eq < 0:
                return None
            rhs = stripped[eq + 1 :]
            shapes = _SHAPE_RE.findall(rhs.split(kind)[0])
            return kind, sum(_shape_bytes(dt, dims) for dt, dims in shapes)
    return None


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")
_WHILE_RE = re.compile(r"while\(.*?\), condition=%?([\w.\-]+), body=%?([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _parse_computations(hlo_text: str) -> dict:
    """comp name -> list of body lines."""
    comps: dict = {}
    cur = None
    for line in hlo_text.splitlines():
        if not line.startswith(" ") and ("->" in line) and line.rstrip().endswith("{"):
            m = _COMP_RE.match(line.strip())
            if m:
                cur = m.group(1)
                comps[cur] = []
                continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line.strip())
    return comps


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """Collective result bytes, scan-aware: collectives inside a while-loop
    body count once per iteration (trip count = the loop-condition constant).
    HLO cost analysis can't do this (it visits loop bodies once); GSPMD keeps
    our FSDP all-gathers inside the layer scan, so the multiplier matters."""
    comps = _parse_computations(hlo_text)
    own = {}
    whiles = {}  # comp -> list[(cond, body)]
    for name, lines in comps.items():
        totals = {k: 0 for k in _COLLECTIVES}
        counts = {k: 0 for k in _COLLECTIVES}
        wl = []
        for ln in lines:
            got = _line_collective(ln)
            if got:
                totals[got[0]] += got[1]
                counts[got[0]] += 1
            m = _WHILE_RE.search(ln)
            if m:
                wl.append((m.group(1), m.group(2)))
        own[name] = (totals, counts)
        whiles[name] = wl

    def trip_count(cond: str) -> int:
        consts = [int(c) for ln in comps.get(cond, []) for c in _CONST_RE.findall(ln)]
        return max(consts) if consts else 1

    import functools

    @functools.lru_cache(maxsize=None)
    def total(name: str):
        t = dict(own[name][0])
        c = dict(own[name][1])
        for cond, body in whiles.get(name, []):
            n = trip_count(cond)
            bt, bc = total(body)
            for k in _COLLECTIVES:
                t[k] += n * bt[k]
                c[k] += n * bc[k]
        return t, c

    entry = None
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_RE.match(line.strip()[len("ENTRY "):].strip())
            if m:
                entry = m.group(1)
            break
    if entry is None:  # fall back: flat count
        entry = max(comps, key=lambda n: len(comps[n])) if comps else None
    if entry is None:
        z = {k: 0 for k in _COLLECTIVES}
        return {"totals": z, "counts": z, "sum": 0}
    totals, counts = total(entry)
    return {"totals": totals, "counts": counts, "sum": int(sum(totals.values()))}


def hlo_facts(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = collective_bytes_from_hlo(compiled.as_text())
    mem = compiled.memory_analysis()
    return {
        "hlo_flops": float(cost.get("flops", 0.0)),
        "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        "collective": coll,
        "device_arg_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "device_out_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "device_temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
    }


def extrapolate_depth(c1: float, c2: float, n_groups: int) -> float:
    """Linear in depth: total(G) = c1 + (G-1)*(c2-c1)."""
    return c1 + (n_groups - 1) * (c2 - c1)


def roofline_terms(flops: float, hbm_bytes: float, collective_bytes: float,
                   chips: int, *, device_kind: str) -> dict:
    """Lower-bound step terms on ``chips`` chips of ``device_kind`` (its
    `launch.mesh.CHIP_PEAKS` entry; an unknown kind raises)."""
    peaks = chip_peaks(device_kind)
    t_compute = flops / (chips * peaks.flops_bf16)
    t_memory = hbm_bytes / (chips * peaks.hbm_bw)
    t_collective = collective_bytes / (chips * peaks.ici_bw)
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_collective}
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "bottleneck": max(terms, key=terms.get),
        "step_time_lb_s": max(terms.values()),
    }


# ---------------------------------------------------------------------------
# serving hot-path kernel bounds (Pallas masked-Adam + bit-pattern top-k)
# ---------------------------------------------------------------------------


def adam_step_hbm_bytes(n_params: int, *, param_bytes: int = 4) -> int:
    """Analytic minimum HBM traffic of ONE masked-Adam step over
    ``n_params`` coordinates: read p/g/m/v + bool mask, write p/m/v/u —
    every buffer touched exactly once (what the fused Pallas kernel
    streams; 33 B/param for f32 params). Multiply by B sessions and K
    iterations for a fused phase's optimizer-update term."""
    return int(n_params) * (25 + 2 * param_bytes)


def topk_hbm_bytes(n_coords: int, *, passes: int = 1) -> int:
    """Analytic HBM traffic of one session's bit-pattern top-k selection:
    ``passes`` reads of the 4-byte |u| buffer plus the 1-byte mask write.
    The fused Pallas kernel keeps the bits in VMEM across all 32 counting
    passes (``passes=1``); the XLA lowering re-reads per pass
    (``passes=32``)."""
    return int(n_coords) * (4 * passes + 1)


def kernel_roofline_fraction(nbytes: int, measured_s: float, *,
                             device_kind: str, chips: int = 1) -> float | None:
    """Achieved fraction of the HBM roofline of ``device_kind`` (an
    unknown kind raises): the analytic memory-bound time for ``nbytes`` of
    traffic over the measured wall-clock. 1.0 means the launch ran at
    memory-bandwidth speed; the gap is launch overhead, compute, or wasted
    re-reads."""
    hbm_bw = chip_peaks(device_kind).hbm_bw
    if not measured_s or measured_s <= 0:
        return None
    return (nbytes / (chips * hbm_bw)) / measured_s

