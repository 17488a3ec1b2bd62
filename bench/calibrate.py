#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2,3]

For each seed, in one process: the cell's set-up as a run makes it (with
only the sessions one grant needs), then one grant of the cell's own size,
and the comparison of the update the run would check with
the plain reference. For each control seed, the reference computed in the
precision below the configuration's (bfloat16 at default precision) is put
in the program's place on the same update and compared the same way, and
so is the reference with half of each minibatch left out (a fault). One
JSON line per seed: ``{"seed", "width", "program", "control",
"half_batch"}``. The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def readings(root: str, name: str, seed: int, control: bool,
             t_start: float, require_tpu: bool = True) -> dict:
    """Program (and optionally control) readings for one seed."""
    import gc

    import jax.numpy as jnp

    import check
    import harness
    import traffic_gen

    import jax

    cell = harness.Cell(root, name)
    # only the sessions one grant needs
    cell.traffic = dict(cell.traffic, sessions_per_fuse=1)
    run = harness.Run(cell, 0.0)
    server = harness.Server(cell, seed, t_start, run,
                            jax.devices()[:cell.chips])
    server.warm_up()
    width = len(server.sessions)
    pick = traffic_gen.rng(seed, traffic_gen.STREAM_CHECK)
    server.capture_at = (0, int(pick.integers(0, width)))
    server.grant([(0.0, i) for i in range(width)], server.clock())
    captured = server.captured
    del server
    gc.collect()
    prog, judged, r = harness.compare(cell, captured)
    out = {"seed": seed, "width": width, "program": prog,
           "program_ok": all(j[3] for j in judged)}
    if control:
        frames, labels = captured["batches"]
        low = r["module"].run_phase(cell.cfg["student"], r["ams"],
                                    r["before"], frames, labels,
                                    dtype=jnp.bfloat16)
        upd = check.update_from_reference(low, cell.cfg["ams"]["value_dtype"])
        vals = check.readings(upd, r["out"], r["before"])
        out["control"] = vals
        out["control_ok"] = all(
            j[3] for j in check.judge(vals, cell.cfg["limits"]))
        # a fault planted in the reference put in the program's place:
        # half of each minibatch left out, the mean over the rest
        half = frames.shape[1] // 2
        cut = r["module"].run_phase(cell.cfg["student"], r["ams"],
                                    r["before"], frames[:, :half],
                                    labels[:, :half])
        upd = check.update_from_reference(cut, cell.cfg["ams"]["value_dtype"])
        out["half_batch"] = check.readings(upd, r["out"], r["before"])
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import jax

    import harness

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache(ROOT)
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    for s in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = readings(ROOT, args.workload, s, s in ctrl, T_START)
        r["seconds"] = time.perf_counter() - t0
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
