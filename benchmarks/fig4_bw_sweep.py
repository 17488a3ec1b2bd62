"""Paper Fig. 4: accuracy vs downlink bandwidth — AMS sweeps T_update,
Just-In-Time sweeps its accuracy threshold."""
from __future__ import annotations

from benchmarks.common import Timer, default_ams, emit, pretrained, video_cfg
from repro.sim.runner import SimConfig, run_scheme
from repro.sim.seg_world import SegWorld


def run(quick: bool = True, duration: float = 120.0, seed: int = 11):
    pre = pretrained()
    pts = []
    t_updates = (10.0, 20.0, 40.0)
    # 0.60 is the matched-accuracy point vs AMS (paper methodology §4.1);
    # higher thresholds trace JIT's accuracy-vs-bandwidth curve upward.
    thresholds = (0.60, 0.75, 0.90) if quick else (0.55, 0.60, 0.70, 0.80, 0.90)
    for tu in t_updates:
        world = SegWorld.make(video_cfg(seed, duration))
        with Timer() as t:
            r = run_scheme("ams", world, pre, default_ams(t_update=tu),
                           SimConfig(eval_stride=4), seed=seed)
        _, down = r.bandwidth_kbps(duration)
        pts.append(("ams", tu, r.mean_miou, down))
        emit(f"fig4.ams.tu{int(tu)}", t.us, f"miou={r.mean_miou:.4f};down_kbps={down:.1f}")
    for th in thresholds:
        world = SegWorld.make(video_cfg(seed, duration))
        sim = SimConfig(eval_stride=4, jit_threshold=th)
        with Timer() as t:
            r = run_scheme("jit", world, pre, default_ams(), sim, seed=seed)
        _, down = r.bandwidth_kbps(duration)
        pts.append(("jit", th, r.mean_miou, down))
        emit(f"fig4.jit.th{int(th*100)}", t.us,
             f"miou={r.mean_miou:.4f};down_kbps={down:.1f}")
    return pts


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run()
