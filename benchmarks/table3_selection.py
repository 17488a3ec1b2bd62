"""Paper Table 3: coordinate-selection strategy ablation at gamma=5%,
reported as mIoU delta vs full-model updates (and downlink Kbps)."""
from __future__ import annotations

from benchmarks.common import Timer, default_ams, emit, pretrained, video_cfg
from repro.sim.runner import SimConfig, run_scheme
from repro.sim.seg_world import SegWorld

STRATEGIES = ("full", "gradient_guided", "random", "first", "last", "first_last")


def run(quick: bool = True, duration: float = 120.0, gamma: float = 0.05, seed: int = 31):
    pre = pretrained()
    sim = SimConfig(eval_stride=4)
    results = {}
    for strat in STRATEGIES:
        world = SegWorld.make(video_cfg(seed, duration))
        cfg = default_ams(strategy=strat, gamma=1.0 if strat == "full" else gamma)
        with Timer() as t:
            r = run_scheme("ams", world, pre, cfg, sim, seed=seed)
        _, down = r.bandwidth_kbps(duration)
        results[strat] = (r.mean_miou, down)
    base = results["full"][0]
    for strat, (m, down) in results.items():
        emit(f"table3.{strat}", t.us,
             f"miou={m:.4f};delta_vs_full={m - base:+.4f};down_kbps={down:.1f}")
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run()
