"""Formats the dry-run jsonl outputs into the EXPERIMENTS.md roofline table."""
from __future__ import annotations

import json
import os

from benchmarks.common import RESULTS, emit


def load(path):
    with open(path) as f:
        return [json.loads(l) for l in f]


def table(rows) -> str:
    hdr = ("| arch | shape | variant | bottleneck | t_compute | t_memory | "
           "t_collective | useful FLOPs | args/dev |\n"
           "|---|---|---|---|---|---|---|---|---|\n")
    out = [hdr]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['variant']} | **{r['bottleneck']}** | "
            f"{r['t_compute_s']*1e3:.2f} ms | {r['t_memory_s']*1e3:.2f} ms | "
            f"{r['t_collective_s']*1e3:.2f} ms | {r['useful_flops_ratio']:.2f} | "
            f"{r['device_arg_bytes']/2**30:.2f} GiB |\n")
    return "".join(out)


def run(quick: bool = True):
    single = os.path.join(RESULTS, "roofline_single_pod.jsonl")
    if not os.path.exists(single):
        emit("roofline.report", 0.0, "missing=run dryrun --all first")
        return
    rows = load(single)
    md = ["# Roofline table (single-pod 16x16, TPU v5e constants)\n\n", table(rows)]
    optp = os.path.join(RESULTS, "roofline_optimized.jsonl")
    if os.path.exists(optp):
        orows = load(optp)
        md.append("\n# Optimized (§Perf levers: ring caches, m_bf16, moe_shard, decode_ep)\n\n")
        md.append(table(orows))
        base = {(r["arch"], r["shape"]): r for r in rows}
        md.append("\n## Dominant-term speedups vs baseline\n\n")
        md.append("| pair | baseline | optimized | speedup |\n|---|---|---|---|\n")
        def dom(r):
            return max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
        for r in orows:
            b = base.get((r["arch"], r["shape"]))
            if b and dom(r) < 0.98 * dom(b):
                md.append(f"| {r['arch']} x {r['shape']} | {dom(b)*1e3:.2f} ms | "
                          f"{dom(r)*1e3:.2f} ms | {dom(b)/dom(r):.2f}x |\n")
    mp = os.path.join(RESULTS, "roofline_multi_pod.jsonl")
    if os.path.exists(mp):
        mrows = load(mp)
        md.append("\n# Multi-pod (2x16x16) lowering proof\n\n")
        md.append(table(mrows))
    out_path = os.path.join(RESULTS, "roofline.md")
    with open(out_path, "w") as f:
        f.write("".join(md))
    bottle = {}
    for r in rows:
        bottle[r["bottleneck"]] = bottle.get(r["bottleneck"], 0) + 1
    emit("roofline.report", 0.0,
         f"pairs={len(rows)};bottlenecks={bottle};out={os.path.relpath(out_path)}")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run()
