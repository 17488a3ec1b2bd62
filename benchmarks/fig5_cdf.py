"""Paper Fig. 5: per-frame robustness — fraction of frames on which each
scheme beats No Customization."""
from __future__ import annotations

import numpy as np

from benchmarks.common import Timer, emit


def run(per_frame: dict | None = None, quick: bool = True):
    if per_frame is None:
        from benchmarks.table1_schemes import run as t1

        _, per_frame = t1(quick=quick)
    base = np.concatenate([np.asarray(v) for v in per_frame["no_custom"]])
    out = {}
    for scheme, frames in per_frame.items():
        if scheme == "no_custom":
            continue
        cur = np.concatenate([np.asarray(v) for v in frames])
        n = min(len(cur), len(base))
        frac = float((cur[:n] > base[:n]).mean())
        gain_p50 = float(np.median(cur[:n] - base[:n]))
        out[scheme] = (frac, gain_p50)
        emit(f"fig5.{scheme}", 0.0, f"frac_frames_improved={frac:.3f};"
             f"median_gain={gain_p50:+.4f}")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run()
