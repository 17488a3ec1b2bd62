"""Paper Fig. 6/10: accuracy degradation as clients share the server GPUs.

Three sweeps on the event-driven serving runtime (`repro.serving`):
  1. client count x ATR on/off under the fair policy (the seed's sweep);
  2. scheduler comparison (fair / EDF / gain-aware) at the saturating client
     count — the gain-aware policy reclaims cycles from near-static feeds,
     so it should match or beat fair round-robin on mean mIoU while the
     network columns show real (nonzero-latency) delta delivery;
  3. GPU-count sweep — the saturating fleet doubled onto a 4-GPU pool,
     affinity-blind (gain) vs residency-aware (affinity) placement: the
     affinity policy avoids most weight-migration stalls, so it should beat
     blind assignment on mean mIoU (or phases served) at n_gpus=4.
"""
from __future__ import annotations

from benchmarks.common import SEG_CFG, Timer, default_ams, emit, pretrained


def _row(r: dict) -> str:
    up, down = r["mean_up_kbps"], r["mean_down_kbps"]
    return (f"miou={r['mean_miou']:.4f};gpu_util={r['gpu_utilization']:.2f};"
            f"deferred={r['phases_deferred']};drop={r['dropped_requests']};"
            f"up_kbps={up:.1f};down_kbps={down:.1f};"
            f"delta_lat_s={r['delta_latency_mean_s']:.3f}")


def run(quick: bool = True, duration: float = 100.0):
    from repro.sim.multiclient import run_multiclient

    pre = pretrained()
    counts = (1, 4, 8) if quick else (1, 2, 4, 6, 8, 10)
    video_kw = dict(height=48, width=48, fps=4.0)
    out = {}
    base = None
    us = {}

    # -- sweep 1: saturation with/without ATR (fair policy) ---------------
    for atr in (False, True):
        for n in counts:
            # asr_eta=2: stationary feeds must reach the slowdown band
            # (r < 0.25 fps) within the compressed run for ATR to act
            cfg = default_ams(atr_enabled=atr, asr_eta=2.0)
            with Timer() as t:
                r = run_multiclient(n, pre, SEG_CFG, cfg, duration=duration,
                                    video_kw=video_kw)
            if base is None:
                base = r["mean_miou"]
            key = f"fig6.{'atr' if atr else 'noatr'}.n{n}"
            out[(atr, n)] = r
            us[(atr, n)] = t.us
            emit(key, t.us, f"{_row(r)};degradation={base - r['mean_miou']:+.4f}")

    # -- sweep 2: scheduling policies at the saturating count -------------
    n_sat = max(counts)
    for policy in ("fair", "edf", "gain"):
        if policy == "fair":
            # identical config to the noatr/n_sat run above and the engine
            # is deterministic — reuse instead of re-simulating
            r, t_us = out[(False, n_sat)], us[(False, n_sat)]
        else:
            cfg = default_ams(asr_eta=2.0)
            with Timer() as t:
                r = run_multiclient(n_sat, pre, SEG_CFG, cfg, duration=duration,
                                    video_kw=video_kw, policy=policy)
            t_us = t.us
        out[(policy, n_sat)] = r
        emit(f"fig6.sched.{policy}.n{n_sat}", t_us, _row(r))

    # -- sweep 3: GPU pool, affinity-blind vs residency-aware -------------
    n_pool = 2 * n_sat  # the 1-GPU saturating fleet, doubled onto 4 GPUs
    for n_gpus, affinity in ((1, False), (4, False), (4, True)):
        cfg = default_ams(asr_eta=2.0)
        with Timer() as t:
            r = run_multiclient(n_pool, pre, SEG_CFG, cfg, duration=duration,
                                video_kw=video_kw, policy="gain",
                                n_gpus=n_gpus, affinity=affinity)
        out[("pool", n_gpus, affinity)] = r
        tag = "affinity" if affinity else "blind"
        emit(f"fig6.pool.g{n_gpus}.{tag}.n{n_pool}", t.us,
             f"{_row(r)};served={r['phases_served']};"
             f"migrations={r['migrations']};"
             f"migration_s={r['migration_s_total']:.1f}")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run()
