"""Shared benchmark plumbing: cached pretrained checkpoint, default configs,
CSV emission (contract: ``name,us_per_call,derived``)."""
from __future__ import annotations

import os
import time

import jax

from repro import checkpoint
from repro.core.server import AMSConfig
from repro.data.video import VideoConfig
from repro.models.seg.student import SegConfig, make_student
from repro.sim.seg_world import SegWorld, pretrain_student

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")
SIZE, FPS = 48, 4.0
SEG_CFG = SegConfig(n_classes=5)


def video_cfg(seed: int, duration: float = 150.0, **kw) -> VideoConfig:
    return VideoConfig(height=SIZE, width=SIZE, fps=FPS, duration=duration,
                       seed=seed, drift_period=kw.pop("drift_period", 240.0), **kw)


def default_ams(**kw) -> AMSConfig:
    # calibrated to the compressed timescale (EXPERIMENTS.md §Repro): the
    # paper's T_update=10 s and gamma=5% are kept; K/horizon/lr scale to the
    # 150 s streams with a 240 s drift period.
    # ATR slowdown band shifted up from the paper's 0.25/0.35 fps: our ASR
    # equilibrates at ~0.35 fps on stationary feeds (the oracle teacher's
    # corruption refresh sets a phi noise floor), so the band must sit above
    # that equilibrium to separate stationary from dynamic feeds.
    base = dict(t_update=10.0, t_horizon=40.0, k_iters=25, batch_size=8,
                gamma=0.05, lr=2e-3, phi_target=0.15, asr_eta=1.0,
                atr_gamma0=0.45, atr_gamma1=0.60)
    base.update(kw)
    return AMSConfig(**base)


def pretrained(steps: int = 600):
    """Generic 'No Customization' checkpoint, cached across benchmarks."""
    path = os.path.join(RESULTS, "pretrained_student_v2.npz")
    like = make_student(SEG_CFG, jax.random.PRNGKey(42))
    if checkpoint.exists(path):
        return checkpoint.load(path, like)
    params = pretrain_student(SEG_CFG, n_videos=5, steps=steps, lr=2e-3,
                              video_kw=dict(height=SIZE, width=SIZE, fps=FPS,
                                            duration=60.0))
    checkpoint.save(path, params)
    return params


def roofline_device_kind() -> str | None:
    """The running device's kind when it has published peaks
    (`launch.mesh.CHIP_PEAKS`), else None: a CPU run reports no roofline
    share (not measured)."""
    from repro.launch.mesh import CHIP_PEAKS

    kind = jax.devices()[0].device_kind
    return kind if kind in CHIP_PEAKS else None


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}")


class Timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.s = time.time() - self.t0

    @property
    def us(self):
        return self.s * 1e6
