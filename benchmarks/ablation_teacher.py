"""Teacher-fidelity ablation (beyond paper): the §Repro conclusions must not
depend on the oracle-teacher substitution (DESIGN.md §5) — re-run
AMS/No-Customization under a *learned* wide-convnet teacher and check the
same ordering and bandwidth."""
from __future__ import annotations

from benchmarks.common import Timer, default_ams, emit, pretrained, video_cfg
from repro.data.video import SyntheticVideo
from repro.models.seg.teacher import train_teacher
from repro.sim.runner import SimConfig, run_scheme
from repro.sim.seg_world import SegWorld


def run(quick: bool = True, duration: float = 120.0, seed: int = 11):
    pre = pretrained()
    vc = video_cfg(seed, duration)
    for kind in ("oracle", "learned"):
        world = SegWorld.make(vc)
        if kind == "learned":
            with Timer() as tt:
                world.teacher = train_teacher(world.video, vc.n_classes,
                                              steps=150 if quick else 400)
            emit("ablation_teacher.fit", tt.us, "wide-convnet teacher fit on GT")
        results = {}
        for scheme in ("no_custom", "ams"):
            with Timer() as t:
                r = run_scheme(scheme, world, pre, default_ams(),
                               SimConfig(eval_stride=5), seed=seed)
            _, down = r.bandwidth_kbps(duration)
            results[scheme] = r.mean_miou
            emit(f"ablation_teacher.{kind}.{scheme}", t.us,
                 f"miou={r.mean_miou:.4f};down_kbps={down:.1f}")
        emit(f"ablation_teacher.{kind}.gain", 0.0,
             f"ams_minus_nocustom={results['ams'] - results['no_custom']:+.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run()
