"""A small copy of the benchmark for CPU tests: the same files, every
configuration and traffic mix cut to a size the CPU runs in seconds, and
the harness imported from that copy."""
from __future__ import annotations

import contextlib
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SRC = ROOT / "src"
MODULES = ("harness", "traffic_gen", "check", "trace_reduce", "flops",
           "calibrate", "_stages")
# the student of the repository's CPU experiments: 24,661 parameters
TINY_STUDENT = {"width": 1.0, "stem": 16, "head": 64,
                "blocks": [[3, 24, 2], [3, 24, 1], [3, 32, 2], [3, 32, 1]]}


def tiny_root(tmp: Path) -> Path:
    """A checkout-like directory: BENCHMARK.json and ``bench/`` (without
    its tests), with tiny configurations and mixes."""
    root = tmp / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for path in (root / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["student"].update(TINY_STUDENT)
        cfg["n_params"] = 24_661
        cfg["frame"].update(height=32, width=64)
        cfg["ams"].update(k_iters=2, batch_size=2)
        cfg.update(fuse_max=2)
        path.write_text(json.dumps(cfg))
    for path in (root / "bench" / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        mix.update(pool_frames=4, frames_per_upload=2)
        path.write_text(json.dumps(mix))
    return root


@contextlib.contextmanager
def bench_modules(root: Path):
    """The benchmark's modules imported from ``root``'s copy; sys.path and
    sys.modules are restored afterwards."""
    saved = list(sys.path)
    for m in MODULES:
        sys.modules.pop(m, None)
    sys.path[:0] = [str(root / "bench"), str(SRC)]
    try:
        import harness

        yield harness
    finally:
        sys.path[:] = saved
        for m in MODULES:
            sys.modules.pop(m, None)


def run(root: Path, cell: str, seed: int = 2 ** 31 + 11,
        seconds: float = 2.0, trace: bool = False) -> dict:
    """One run of ``cell`` from ``root``, the look for a chip skipped."""
    with bench_modules(root) as harness:
        return harness.run_cell(str(root), cell, seed, seconds, trace,
                                time.perf_counter(), require_tpu=False)
