"""Paper Fig. 3: adaptive sampling rate tracks scene change (stop-and-go)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import Timer, default_ams, emit, pretrained, video_cfg
from repro.data.video import stop_and_go
from repro.sim.runner import SimConfig, run_scheme
from repro.sim.seg_world import SegWorld


def run(quick: bool = True, duration: float = 180.0):
    pre = pretrained()
    vc = video_cfg(17, duration, motion_schedule=stop_and_go(duration * 0.33,
                                                             duration * 0.66))
    world = SegWorld.make(vc)
    with Timer() as t:
        # asr_eta=2: the compressed timescale needs a faster integral gain
        # for the controller to settle within the 60 s stop window
        r = run_scheme("ams", world, pre, default_ams(asr_eta=2.0),
                       SimConfig(eval_stride=6))
    hist = r.extras["history"]
    rates = [(h["t"], h["rate"]) for h in hist]
    mid = [r_ for tt, r_ in rates if duration * 0.4 < tt < duration * 0.66]
    moving = [r_ for tt, r_ in rates if tt < duration * 0.3 or tt > duration * 0.75]
    r_stop = float(np.mean(mid)) if mid else float("nan")
    r_stop_min = float(np.min(mid)) if mid else float("nan")
    r_move = float(np.mean(moving)) if moving else float("nan")
    emit("fig3.asr", t.us, f"rate_moving={r_move:.3f};rate_stopped={r_stop:.3f};"
         f"rate_stopped_min={r_stop_min:.3f};drops={r_stop < r_move}")
    return rates


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run()
