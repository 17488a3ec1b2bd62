"""Paper Fig. 8 (App. C): training-horizon vs accuracy — train the student on
[t - T_horizon, t), evaluate on [t, t + T_update)."""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import SEG_CFG, Timer, emit, pretrained, video_cfg
from repro.core.masked_adam import adam_update, init_state
from repro.metrics.miou import miou
from repro.sim.seg_world import SegWorld


def _probe(world, pre, t_sec: float, horizon: float, t_update: float,
           iters: int = 30, rng=None):
    fps = world.video.cfg.fps
    t_idx = int(t_sec * fps)
    h_idx = max(0, int((t_sec - horizon) * fps))
    train_idx = np.linspace(h_idx, t_idx - 1, min(24, t_idx - h_idx)).astype(int)
    frames = np.stack([world.video.frame(int(i))[0] for i in train_idx])
    labels = np.stack([world.teacher.label(int(i)) for i in train_idx])
    params = jax.tree.map(lambda x: x, pre)
    opt = init_state(params)
    for _ in range(iters):
        pick = rng.integers(0, len(train_idx), size=6)
        _, g = world.loss_and_grad(params, frames[pick], labels[pick])
        params, opt, _ = adam_update(params, g, opt, lr=1e-3)
    # evaluate on the future window
    scores = []
    for i in range(t_idx, int(t_idx + t_update * fps), 2):
        img, _ = world.video.frame(i)
        pred = np.asarray(world.predict(params, img[None])[0])
        scores.append(miou(pred, world.teacher.label(i), world.video.cfg.n_classes))
    return float(np.mean(scores))


def run(quick: bool = True, duration: float = 240.0):
    pre = pretrained()
    world = SegWorld.make(video_cfg(41, duration))
    rng = np.random.default_rng(0)
    horizons = (8.0, 32.0, 120.0)
    t_updates = (10.0, 30.0)
    probes = (80.0, 140.0, 200.0) if not quick else (120.0, 200.0)
    out = {}
    for h in horizons:
        for tu in t_updates:
            with Timer() as t:
                scores = [_probe(world, pre, ts, h, tu, rng=rng) for ts in probes]
            m = float(np.mean(scores))
            out[(h, tu)] = m
            emit(f"fig8.h{int(h)}.tu{int(tu)}", t.us, f"miou={m:.4f}")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run()
