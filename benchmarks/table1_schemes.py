"""Paper Table 1/2: scheme comparison (mIoU, uplink/downlink Kbps)."""
from __future__ import annotations

from benchmarks.common import Timer, default_ams, emit, pretrained, video_cfg
from repro.sim.runner import SCHEMES, SimConfig, run_scheme
from repro.sim.seg_world import SegWorld


def run(quick: bool = True, duration: float = 120.0, seeds=(11, 23)):
    if quick:
        seeds = seeds[:2]
    pre = pretrained()
    sim = SimConfig(eval_stride=4)
    rows = {}
    per_frame = {}
    for scheme in SCHEMES:
        mious, ups, downs, updates = [], [], [], []
        frames_all = []
        for seed in seeds:
            world = SegWorld.make(video_cfg(seed, duration))
            with Timer() as t:
                r = run_scheme(scheme, world, pre, default_ams(), sim, seed=seed)
            up, down = r.bandwidth_kbps(duration)
            mious.append(r.mean_miou)
            ups.append(up)
            downs.append(down)
            updates.append(r.updates)
            frames_all.append(r.miou_per_frame)
        m = sum(mious) / len(mious)
        u = sum(ups) / len(ups)
        d = sum(downs) / len(downs)
        rows[scheme] = (m, u, d, sum(updates))
        per_frame[scheme] = frames_all
        emit(f"table1.{scheme}", t.us, f"miou={m:.4f};up_kbps={u:.1f};down_kbps={d:.1f};"
             f"updates={sum(updates)}")
    return rows, per_frame


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run()
