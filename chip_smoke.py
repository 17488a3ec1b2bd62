#!/usr/bin/env python3
"""Bring-up smoke run of the AMS serving path on a TPU.

Run from the repository root:

    python chip_smoke.py [--seed N]     # one chip
    python chip_smoke.py --chips 4      # the multi-device pool on four chips

One chip: a short seeded pretrain of the paper's 2M-parameter student
(``SegConfig(width=10.0)``, 2,014,885 parameters) on synthetic 256x512
video, then the serving engine as `sim.multiclient` builds it: four clients
on one device, fused grants of up to four sessions, the paper's AMS
settings (T_update 10 s, K=20, batch 8, gamma 5%). Then the same fused
phase once more with the Pallas kernels against XLA on the same batch.

Four chips (``--chips 4``): only the multi-device pool and what it is
compared with: the engine over ``ServingConfig(n_gpus=4,
device_backend="jax")``, and `train_phases_sharded` per-device dispatch
against serial `train_phases_fused` on the same session groups.

Everything runs in this one process; it starts no child. The script exits
non-zero, with no result line, when JAX finds no TPU or any check fails.
The last line of standard output is the JSON result
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The lines before it are bring-up observations (compile and steady seconds
per grant, peak device memory), not benchmark metrics.
"""
from __future__ import annotations

import argparse
import copy
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

N_PARAMS = 2_014_885  # SegConfig(width=10.0, n_classes=5)
HEIGHT, WIDTH, FPS = 256, 512, 4.0
# the paper's AMS operating point (§4.1)
AMS_KW = dict(t_update=10.0, k_iters=20, batch_size=8, gamma=0.05)
DURATION = 40.0  # simulated seconds of serving: >= 2 grants per session
PRETRAIN_STEPS = 20
# Pallas vs XLA on a TPU v5e: one masked-Adam step agrees to float32
# rounding (max |diff| over max |value| per leaf: 3.3e-7 observed); the
# wire values after a whole fused phase of K=20 steps drift further, since
# each step's gradients see the previous step's rounding (relative L2
# 7.7e-4 observed, up to 2.1e-3 absolute, 65% within one fp16 ULP)
STEP_REL_TOL = 1e-6
PHASE_REL_L2_TOL = 1e-3
# sharded vs serial on the chip: the same executable on same-kind
# devices, so wire values are expected byte-identical
SHARDED_ULP_TOL = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def lex16(x):
    """float16 values as integers in value order, so that a difference is a
    distance in units in the last place."""
    import numpy as np

    u = np.asarray(x, np.float16).reshape(-1).view(np.uint16).astype(np.int32)
    return np.where(u >= 0x8000, 0x8000 - u, u)


def compare_deltas(ref: list, got: list, what: str) -> dict:
    """Wire masks byte-identical and values finite (checked); returns how
    far the fp16 values of ``got`` are from ``ref``."""
    import numpy as np

    check(len(ref) == len(got) and all(d is not None for d in ref + got),
          f"{what}: every session yields a delta")
    ulps, identical, within, n, num, den, max_abs = [], 0, 0, 0, 0.0, 0.0, 0.0
    for r, g in zip(ref, got):
        check(r.packed_mask == g.packed_mask,
              f"{what}: wire masks byte-identical")
        a = np.asarray(r.values, np.float32)
        b = np.asarray(g.values, np.float32)
        check(bool(np.isfinite(b).all()), f"{what}: delta values finite")
        u = np.abs(lex16(r.values) - lex16(g.values))
        ulps.append(int(u.max()) if u.size else 0)
        identical += int(np.array_equal(np.asarray(r.values),
                                         np.asarray(g.values)))
        within += int((u <= 1).sum())
        n += u.size
        num += float(np.sum((a - b) ** 2))
        den += float(np.sum(a ** 2))
        max_abs = max(max_abs, float(np.max(np.abs(a - b))) if a.size else 0)
    return {"max_ulp": max(ulps), "byte_identical": f"{identical}/{len(ref)}",
            "within_1_ulp": within / max(n, 1), "max_abs": max_abs,
            "rel_l2": math.sqrt(num / den) if den else 0.0}


def clone_session(s):
    """An independent copy of an `AMSSession` at its current state (jax
    arrays are immutable and shared; host state is copied)."""
    c = copy.copy(s)
    c.rng = copy.deepcopy(s.rng)
    c.buffer = copy.deepcopy(s.buffer)
    c.asr = copy.deepcopy(s.asr)
    c.atr = copy.deepcopy(s.atr)
    c.history = list(s.history)
    return c


def on_device(tree, device) -> bool:
    import jax

    return all(leaf.devices() == {device} for leaf in jax.tree.leaves(tree))


def peak_bytes(device):
    """The device's peak bytes in use so far, where the backend reports
    it."""
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def stage_lines(stats: dict) -> None:
    """Print first-launch and steady seconds per call of each timed stage
    (`core.timing`, each timed around block_until_ready)."""
    for (stage, key), e in sorted(stats.items(), key=lambda kv: str(kv[0])):
        first_n = e["first_calls"]
        steady_n = e["calls"] - first_n
        first = e["first_s"] / first_n if first_n else float("nan")
        steady = e["steady_s"] / steady_n if steady_n else float("nan")
        log(f"  {stage} key={key}: first launch (compile + run) "
            f"{first:.3f} s x{first_n}, steady {steady:.4f} s/call "
            f"x{steady_n}")


def one_chip(seed: int, device) -> None:
    import jax

    from repro.core import batched, timing
    from repro.core.server import AMSConfig
    from repro.kernels import resolve_interpret
    from repro.models.seg.student import SegConfig, seg_param_count
    from repro.sim.multiclient import build_engine
    from repro.sim.seg_world import pretrain_student

    seg = SegConfig(width=10.0, n_classes=5)
    check(seg_param_count(seg) == N_PARAMS, "width-10 student size")
    ams = AMSConfig(**AMS_KW)
    video_kw = dict(height=HEIGHT, width=WIDTH, fps=FPS)
    batched.set_exec_mode("scan")
    batched.set_kernel_mode("xla")
    timing.reset()
    timing.set_enabled(True)

    t0 = time.perf_counter()
    pre = pretrain_student(seg, n_videos=2, steps=PRETRAIN_STEPS,
                           batch=8, seed=seed,
                           video_kw=dict(video_kw, duration=30.0))
    jax.block_until_ready(pre)
    log(f"pretrain: {PRETRAIN_STEPS} steps at {HEIGHT}x{WIDTH}, "
        f"{time.perf_counter() - t0:.1f} s (compile included)")
    check(on_device(pre, device), "pretrained student on the TPU")

    engine = build_engine(4, pre, seg, ams, duration=DURATION,
                          video_kw=video_kw, fuse_train=4, n_gpus=1,
                          seed=seed)
    t0 = time.perf_counter()
    res = engine.run()
    log(f"serving run: {DURATION:.0f} simulated s, "
        f"{time.perf_counter() - t0:.1f} s wall")
    stats = timing.snapshot()
    stage_lines(stats)
    log(f"peak_bytes_in_use: {peak_bytes(device)}")

    log(f"phases_per_client: {res['phases_per_client']}")
    check(min(res["phases_per_client"]) >= 2, "every session got >= 2 phases")
    widths = sorted({key[0] for (stage, key) in stats
                     if stage == "train_fused"})
    upd = res["update_pipeline"]
    log(f"fused stack widths: {widths}; stacked selections: "
        f"{upd['stacked_select_launches']} launches over "
        f"{upd['stacked_select_sessions']} sessions")
    check(any(b > 1 for b in widths), "a fused stack held B > 1")
    check(upd["stacked_select_launches"] >= 1, "a stacked selection ran")
    log(f"miou_per_client: {res['miou_per_client']}")
    check(all(math.isfinite(m) for m in res["miou_per_client"]),
          "mIoU finite")
    min_bytes = 2 * int(ams.gamma * N_PARAMS)  # >= k fp16 values
    for s in engine.sessions:
        ams_s = s.session
        check(on_device((ams_s.params, ams_s.opt_state, ams_s.u_prev),
                        device), f"client {s.idx} student state on the TPU")
        for h in ams_s.history:
            check(math.isfinite(h["loss"]), f"client {s.idx} loss finite")
            check(min_bytes <= h["bytes"] < 10 * N_PARAMS,
                  f"client {s.idx} delta non-empty, {h['bytes']} wire bytes")
    log(f"delta wire bytes per client: "
        f"{[[h['bytes'] for h in s.session.history] for s in engine.sessions]}")

    # the next fused phase of a stack as wide as the widest the run fused
    # (its XLA executable is cached), once per kernel mode
    t_next = DURATION + ams.t_update
    base = [s.session for s in engine.sessions][:max(widths)]
    deltas = {}
    for mode in ("xla", "pallas"):
        batched.set_kernel_mode(mode)
        if mode == "pallas":
            check(resolve_interpret(None) is False,
                  "Pallas kernels compiled, not interpreted")
        t0 = time.perf_counter()
        deltas[mode] = batched.train_phases_fused(
            [clone_session(s) for s in base], t_next, force_stack=True)
        log(f"kernel phase {mode}: {time.perf_counter() - t0:.2f} s "
            f"(first pallas launch compiles)")
    batched.set_kernel_mode("xla")
    d = compare_deltas(deltas["xla"], deltas["pallas"], "pallas vs xla")
    log(f"pallas vs xla, one fused phase (K={ams.k_iters}): selection masks "
        f"byte-identical for {len(base)} sessions; fp16 values {d}")
    kernel_step_check(base, seed)
    check(d["rel_l2"] <= PHASE_REL_L2_TOL,
          f"fused-phase deltas within relative L2 {PHASE_REL_L2_TOL}")
    log(f"peak_bytes_in_use after kernel phase: {peak_bytes(device)}")


def kernel_step_check(sessions, seed: int) -> None:
    """One masked-Adam step, Pallas kernel against the XLA tree_map, on the
    sessions' own stacked state with seeded gradients, the sessions at
    different Adam step counts (each reads its own bias correction)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.batched import stack_trees
    from repro.core.masked_adam import masked_adam_update
    from repro.kernels.masked_adam.ops import masked_adam_stacked

    params = stack_trees([s.params for s in sessions])
    opt = stack_trees([s.opt_state for s in sessions])
    opt = type(opt)(opt.m, opt.v,
                    jnp.asarray([0, 5, 40, 400][:len(sessions)], jnp.int32))
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2 * len(leaves))
    grads = treedef.unflatten([1e-2 * jax.random.normal(k, l.shape)
                               for k, l in zip(keys, leaves)])
    mask = treedef.unflatten([
        jax.random.bernoulli(k, 0.05, l.shape)
        for k, l in zip(keys[len(leaves):], leaves)])
    xla = jax.jit(jax.vmap(masked_adam_update))(params, grads, opt, mask)
    pallas = jax.jit(masked_adam_stacked)(params, grads, opt, mask)
    worst = {}
    for name, a, b in zip(("params", "state", "u"), xla, pallas):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            x = np.asarray(x, np.float64)
            y = np.asarray(y, np.float64)
            scale = max(float(np.max(np.abs(x))), 1e-30)
            err = float(np.max(np.abs(x - y))) / scale
            worst[name] = max(worst.get(name, 0.0), err)
    log(f"masked-Adam step, pallas vs xla (max |diff| / max |xla| per "
        f"leaf): {worst}")
    check(max(worst.values()) <= STEP_REL_TOL,
          f"one Pallas masked-Adam step within {STEP_REL_TOL} of XLA")


def four_chips(seed: int, devices) -> None:
    import jax
    import numpy as np

    from repro.core import batched
    from repro.core.server import AMSConfig
    from repro.models.seg.student import SegConfig, make_student
    from repro.serving import ServingConfig
    from repro.sim.multiclient import build_engine, build_sessions

    seg = SegConfig(width=10.0, n_classes=5)
    ams = AMSConfig(**AMS_KW)
    video_kw = dict(height=HEIGHT, width=WIDTH, fps=FPS)
    batched.set_exec_mode("scan")
    batched.set_kernel_mode("xla")
    pre = make_student(seg, jax.random.PRNGKey(seed))

    # the engine over a pool of four jax-bound slots
    engine = build_engine(4, pre, seg, ams, duration=DURATION,
                          video_kw=video_kw, seed=seed,
                          serving_cfg=ServingConfig(n_gpus=4,
                                                    device_backend="jax"))
    pool = engine.pool
    check(pool.distinct_jax_devices == 4, "pool binds 4 distinct devices")
    t0 = time.perf_counter()
    res = engine.run()
    log(f"engine on 4 devices: {time.perf_counter() - t0:.1f} s wall; "
        f"grants per device {res['per_gpu_grants']}; phases per client "
        f"{res['phases_per_client']}")
    check(all(g > 0 for g in res["per_gpu_grants"]),
          "every device served grants")
    for s in engine.sessions:
        check(s.phase_devices, f"client {s.idx} was served")
        bound = pool.device(s.phase_devices[-1]).jax_device
        ams_s = s.session
        check(on_device((ams_s.params, ams_s.opt_state, ams_s.u_prev),
                        bound),
              f"client {s.idx} state on its last grant's bound device")

    # per-device dispatch against serial fused execution, same groups
    def fleet():
        sessions = build_sessions(4, pre, seg, ams, duration=30.0,
                                  video_kw=video_kw, seed=seed + 1)
        out = []
        for s in sessions:
            frames = np.stack([s.world.video.frame(j)[0] for j in range(8)])
            labels = np.stack([s.world.teacher.label(j) for j in range(8)])
            s.session.receive_labeled(frames, labels, 5.0)
            out.append(s.session)
        return out

    serial, sharded = fleet(), fleet()
    batched.sharded_reset()
    for t in (6.0, 16.0):  # random masks, then stacked gradient-guided
        t0 = time.perf_counter()
        ref = [d for s in serial
               for d in batched.train_phases_fused([s], t, force_stack=True)]
        t1 = time.perf_counter()
        got = [d for grp in batched.train_phases_sharded(
            [[s] for s in sharded], t, devices=list(devices)) for d in grp]
        t2 = time.perf_counter()
        d = compare_deltas(ref, got, f"sharded vs serial t={t}")
        log(f"t={t}: serial {t1 - t0:.2f} s, sharded {t2 - t1:.2f} s; wire "
            f"masks byte-identical; fp16 values {d}")
        check(d["max_ulp"] <= SHARDED_ULP_TOL,
              f"sharded deltas within {SHARDED_ULP_TOL} f16 ULP of serial")
    info = batched.sharded_info()
    log(f"sharded dispatch: {info}")
    check(info["distinct_devices"] == 4, "sharded dispatch on 4 devices")
    for d, s in zip(devices, sharded):
        check(on_device(s.params, d), "sharded group state on its device")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {platform!r}")
    check(len(devices) >= args.chips,
          f"{args.chips} chips wanted, {len(devices)} present")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from importlib import metadata

    from repro.launch.compile_cache import configure_compile_cache

    def version(pkg: str) -> str:
        try:
            return metadata.version(pkg)
        except metadata.PackageNotFoundError:
            return "not installed"

    cache = configure_compile_cache()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"device_kind: {devices[0].device_kind}; devices: {len(devices)}")
    log(f"jax {version('jax')}, jaxlib {version('jaxlib')}, "
        f"libtpu {version('libtpu')}")
    log(f"compile cache: {cache} ({n_cached} entries at start)")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed, devices[:4])
    else:
        one_chip(args.seed, devices[0])
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"total {time.perf_counter() - t0:.1f} s; compile cache holds "
        f"{n_cached} entries")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
