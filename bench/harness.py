"""Set-up, measured window and comparison of one benchmark cell.

Everything that belongs to one configuration, traffic mix or metric is
found by name: ``BENCHMARK.json`` names the cell's configuration file, the
mix is ``bench/traffic/<traffic>.json``, each metric's reader is
``bench/metrics/<metric>.py`` (a ``read(run)`` that returns a number or
None) and the configuration's plain reference is
``bench/reference/<reference>.py``.

The window drives the AMS grant path as a server does on each grant:
uploads enter through ``AMSSession.receive_labeled`` and the granted
sessions train through ``repro.serving.session.train_many`` on one chip,
or ``repro.core.batched.train_phases_sharded`` over the cell's chips
(session i resident on chip i mod chips, one stack per chip per grant).
One single-threaded dispatcher, the same in every cell, grants each chip
its ``fuse_max`` sessions whose upload has waited longest: every session
always has an upload waiting (a closed loop). The clock passed to the
program is wall seconds since the process started.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import time
import traceback

import numpy as np

import traffic_gen

BENCH = os.path.dirname(os.path.abspath(__file__))
TRACE_S = 8.0  # the traced part of a --trace 1 window, at its end


class Refused(Exception):
    """The run cannot be made here (no accelerator, too few chips, a cell
    or file that does not exist): exit non-zero, print no result."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_") \
        .replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise Refused(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One cell of ``BENCHMARK.json`` with its files resolved by name."""

    def __init__(self, root: str, name: str):
        path = os.path.join(root, "BENCHMARK.json")
        if not os.path.exists(path):
            raise Refused(f"no BENCHMARK.json in {root}")
        bm = load_json(path)
        cells = {w["name"]: w for w in bm["workloads"]}
        if name not in cells:
            raise Refused(f"no workload {name!r}; known: {sorted(cells)}")
        self.name = name
        self.root = root
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        self.run_seconds = bm["run_seconds"]
        conf = {c["name"]: c for c in bm["configs"]}[self.entry["config"]]
        self.cfg = load_json(os.path.join(root, conf["file"]))
        self.traffic = load_json(os.path.join(
            BENCH, "traffic", self.entry["traffic"] + ".json"))
        self.end_to_end = [m for m in bm["end_to_end"] if self._has(m)]
        self.per_layer = [m for m in bm["per_layer"] if self._has(m)]

    def _has(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def reader(self, metric: str):
        path = os.path.join(BENCH, "metrics", metric + ".py")
        if os.path.dirname(path) not in sys.path:  # the readers' helpers
            sys.path.insert(0, os.path.dirname(path))
        if not os.path.exists(path):
            raise Refused(f"metric {metric!r} has no reader at {path}")
        return load_module(path).read

    def reference(self):
        return load_module(os.path.join(
            BENCH, "reference", self.cfg["reference"] + ".py"))


class Run:
    """What one run measured; the metric readers read it.

    Times are seconds after the window opened. ``updates`` holds one dict
    per session update due in the window (``session``, ``due``, ``start``
    of its ingest, ``done`` when its delta was encoded, ``bytes``, ``ok``);
    ``grants`` one dict per grant (``start``, ``end``, ``width``, and
    ``train_s``, the wall time of its dispatch). ``timing`` is the
    program's `core.timing` stage record over the window (traced runs
    only), ``trace`` the reduction of the profiler's trace (traced runs
    only)."""

    def __init__(self, cell: Cell, seconds: float):
        self.cell = cell
        self.cfg = cell.cfg
        self.traffic = cell.traffic
        self.chips = cell.chips
        self.seconds = seconds
        self.setup_s = math.nan
        self.updates: list = []
        self.grants: list = []
        self.timing = None
        self.trace = None
        self.memory_peak_bytes = None
        self.peaks = None
        self.compiles_in_window = 0
        self.trace_start = None

    @property
    def images_per_update(self) -> int:
        ams = self.cfg["ams"]
        return int(ams["k_iters"]) * int(ams["batch_size"])

    def window_updates(self) -> list:
        """Updates of the grants begun inside the window."""
        return [u for u in self.updates if u["grant_start"] < self.seconds]

    def busy_end(self) -> float:
        """End of the last grant begun inside the window."""
        return max(g["end"] for g in self.grants if g["start"] < self.seconds)


def _device_info(devices, chips: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def _memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _peaks(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table["devices"]:
        raise Refused(f"no published peaks for device kind {kind!r} in "
                      f"bench/peaks.json; known: {sorted(table['devices'])}")
    return table["devices"][kind]


class Server:
    """The sessions of one cell and the dispatcher that grants them."""

    def __init__(self, cell: Cell, seed: int, t0: float, run: Run,
                 devices: list):
        import jax

        from repro.core.server import AMSConfig
        from repro.models.seg.student import (SegConfig, make_student,
                                              seg_param_count)
        from repro.sim.multiclient import build_sessions

        cfg, traffic = cell.cfg, cell.traffic
        self.t0 = t0
        self.run = run
        self.fuse_max = int(cfg["fuse_max"])
        self.per_upload = int(traffic["frames_per_upload"])
        self.devices = devices
        st = cfg["student"]
        seg = SegConfig(width=float(st["width"]), n_classes=st["n_classes"],
                        in_channels=st["in_channels"], stem=st["stem"],
                        head=st["head"],
                        blocks=tuple(tuple(b) for b in st["blocks"]))
        if seg_param_count(seg) != cfg["n_params"]:
            raise ValueError(f"student has {seg_param_count(seg)} "
                             f"parameters, configuration says "
                             f"{cfg['n_params']}")
        n = traffic_gen.n_sessions(traffic, self.fuse_max, len(devices))
        sess_rng = traffic_gen.rng(seed, traffic_gen.STREAM_SESSIONS)
        key = jax.random.PRNGKey(int(sess_rng.integers(2 ** 31)))
        params = jax.jit(make_student, static_argnums=0)(seg, key)
        fr = cfg["frame"]
        self.sessions = build_sessions(
            n, params, seg, AMSConfig(**cfg["ams"]), duration=600.0,
            video_kw=dict(height=fr["height"], width=fr["width"],
                          fps=fr["fps"]),
            stationary_frac=0.0, seed=int(sess_rng.integers(2 ** 20)))
        for s in self.sessions:  # replay draws and first masks from the seed
            s.session.rng = np.random.default_rng(
                [int(seed), traffic_gen.STREAM_SESSIONS, s.idx])
            s.session.jrng = jax.random.PRNGKey(
                int(s.session.rng.integers(2 ** 31)))
        self.pools = traffic_gen.render_pools(traffic, n, fr,
                                              st["n_classes"], seed)
        self.uploads = [0] * n
        self.capture_at = None
        self.captured = None
        self.deltas: list = []

    def clock(self) -> float:
        return time.perf_counter() - self.t0

    def ingest(self, i: int) -> None:
        frames, labels = traffic_gen.upload(self.pools, i, self.uploads[i],
                                            self.per_upload)
        self.uploads[i] += 1
        self.sessions[i].session.receive_labeled(frames, labels, self.clock())

    def resident(self, chip: int) -> list:
        """Sessions on ``chip``: session i lives on chip i mod chips."""
        return list(range(chip, len(self.sessions), len(self.devices)))

    def warm_up(self) -> None:
        """Every session's first phase (random masks), in full stacks on
        every chip, then one gradient-guided grant, so every program the
        window runs is compiled and loaded. Each warm-up grant's sessions
        ingest an upload just before it, so a set-up that compiles for
        longer than the replay horizon still trains."""
        per_chip = [self.resident(c) for c in range(len(self.devices))]
        rounds = -(-len(per_chip[0]) // self.fuse_max)
        for r in list(range(rounds)) + [0]:
            group = [i for ids in per_chip
                     for i in ids[r * self.fuse_max:(r + 1) * self.fuse_max]]
            for i in group:
                self.ingest(i)
            if any(d is None for d in self._dispatch(group, self.clock())):
                raise RuntimeError("a warm-up phase produced no delta")

    def _dispatch(self, group: list, t: float) -> list:
        """One grant of the sessions ``group`` (indices): `train_many` on one
        chip, one `train_phases_sharded` call with a stack per chip on
        several. Waits for the sessions' new state; returns a delta or None
        per session, in ``group``'s order."""
        import jax

        from repro.core.batched import train_phases_sharded
        from repro.serving.session import train_many

        if len(self.devices) == 1:
            out = train_many([self.sessions[i] for i in group], t)
        else:
            chips = [c for c in range(len(self.devices))
                     if any(i % len(self.devices) == c for i in group)]
            stacks = [[i for i in group if i % len(self.devices) == c]
                      for c in chips]
            res = train_phases_sharded(
                [[self.sessions[i].session for i in st] for st in stacks], t,
                devices=[self.devices[c] for c in chips])
            by_idx = {i: d for st, r in zip(stacks, res)
                      for i, d in zip(st, r)}
            out = [by_idx[i] for i in group]
        jax.block_until_ready([self.sessions[i].session.params for i in group])
        return out

    # ---- the measured window -----------------------------------------
    def grant(self, items: list, w0: float) -> None:
        """One grant: ingest each granted upload, then one dispatch.
        ``items`` are (due, session) pairs, due in window seconds; ``w0`` is
        the window's start on the process clock."""
        import jax

        ann = jax.profiler.TraceAnnotation
        g_start = self.clock() - w0
        starts = []
        with ann("bench.ingest"):
            for _due, i in items:
                starts.append(self.clock() - w0)
                self.ingest(i)
        group = [i for _due, i in items]
        cap = self._arm_capture([self.sessions[i] for i in group])
        t_train = self.clock()
        try:
            with ann("bench.train_many"):
                deltas = self._dispatch(group, t_train)
        except Exception:  # a grant that raises fails its updates
            log("grant raised:\n" + traceback.format_exc())
            deltas = [None] * len(group)
        t_end = self.clock()
        if cap is not None:
            self._finish_capture(cap, [self.sessions[i] for i in group],
                                 deltas)
        with ann("bench.record"):
            end = t_end - w0
            self.run.grants.append({"start": g_start, "end": end,
                                    "width": len(group),
                                    "train_s": t_end - t_train})
            for (due, i), st, d in zip(items, starts, deltas):
                self.run.updates.append({
                    "session": i, "due": due, "start": st, "done": end,
                    "grant_start": g_start,
                    "bytes": d.total_bytes if d is not None else 0,
                    "ok": d is not None})
                self.deltas.append(d)

    def _arm_capture(self, group: list):
        """Before the grant the seed chose: keep the chosen session's state
        and record the replay minibatches its phase draws."""
        if self.capture_at is None or \
                len(self.run.grants) != self.capture_at[0]:
            return None
        s = group[self.capture_at[1] % len(group)].session
        rec = {"session": s, "batches": None,
               "before": (s.params, s.opt_state, s.u_prev)}
        orig = s._prepare_phase_deferred

        def prepare(t_now):
            prep = orig(t_now)
            if prep is not None:
                rec["batches"] = (prep[1], prep[2])
            return prep

        s._prepare_phase_deferred = prepare
        return rec

    def _finish_capture(self, rec: dict, group: list, deltas: list) -> None:
        s = rec.pop("session")
        del s._prepare_phase_deferred
        rec["after"] = (s.params, s.opt_state, s.u_prev)
        rec["loss"] = s.history[-1]["loss"] if s.history else math.nan
        rec["delta"] = deltas[[g.session for g in group].index(s)]
        self.captured = rec

    def closed_loop(self, w0: float, seconds: float, tracer) -> None:
        """Every session always has an upload waiting: grant each chip the
        ``fuse_max`` of its sessions whose upload has waited longest."""
        due = [0.0] * len(self.sessions)
        per_chip = [self.resident(c) for c in range(len(self.devices))]
        while self.clock() - w0 < seconds:
            last = self.run.grants[-1] if self.run.grants else None
            tracer.maybe_start(self.clock() - w0,
                               last["end"] - last["start"] if last else 0.0)
            items = [(due[i], i) for ids in per_chip
                     for i in sorted(ids, key=lambda i: (due[i], i))
                     [:self.fuse_max]]
            self.grant(items, w0)
            for _d, i in items:
                due[i] = self.run.grants[-1]["end"]


class Tracer:
    """Runs the profiler over the last ``TRACE_S`` seconds of the window
    (whole grants), with host annotations around ingest, the grant's
    dispatch and bookkeeping, so idle gaps can be put down to them."""

    def __init__(self, on: bool, seconds: float, out_dir: str):
        self.on = on
        self.start_at = max(seconds - TRACE_S, 0.0)
        self.out_dir = out_dir
        self.started = None
        self.ann = None

    def maybe_start(self, now: float, last_grant_s: float = 0.0) -> None:
        """Start before a grant that begins in the last ``TRACE_S`` seconds
        or, judged by the last grant's length, is the window's last."""
        if not self.on or self.started is not None or (
                now < self.start_at and now + last_grant_s < self.start_at
                + TRACE_S):
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.ann = jax.profiler.TraceAnnotation("bench.traced")
        self.ann.__enter__()
        self.started = now

    def stop(self) -> None:
        if self.started is None:
            return
        import jax

        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()


def _flat_state(ref, params, opt_state, u) -> dict:
    """A session's state as flat float32 host arrays keyed by name."""
    host = lambda t: {k: np.asarray(v, np.float32)  # noqa: E731
                      for k, v in ref.flatten(t).items()}
    return {"params": host(params), "m": host(opt_state.m),
            "v": host(opt_state.v), "count": int(opt_state.count),
            "u_prev": host(u)}


def reference_run(cell: Cell, captured: dict) -> dict:
    """The plain reference on the captured update: the state before, the
    reference's phase, and the program's update in the same terms."""
    ref = cell.reference()
    before = _flat_state(ref, *captured["before"])
    after = _flat_state(ref, *captured["after"])
    want = ref.param_shapes(cell.cfg["student"])
    got = {k: tuple(v.shape) for k, v in before["params"].items()}
    if got != {k: tuple(v) for k, v in want.items()}:
        raise ValueError("the program's parameter layout is not the "
                         "student's: " + str(sorted(set(got) ^ set(want))))
    ams = {k: cell.cfg["ams"][k] for k in ("lr", "b1", "b2", "eps", "gamma")}
    frames, labels = captured["batches"]
    t0 = time.perf_counter()
    out = ref.run_phase(cell.cfg["student"], ams, before, frames, labels)
    log(f"reference phase: {time.perf_counter() - t0:.1f} s")
    d = captured["delta"]
    mask, values = ref.decode_delta(d.values, d.packed_mask, d.n_total)
    update = {"mask_flat": mask, "wire_values": values,
              "params": after["params"], "m": after["m"], "v": after["v"],
              "u": after["u_prev"], "loss": float(captured["loss"])}
    return {"module": ref, "ams": ams, "before": before, "out": out,
            "update": update}


def compare(cell: Cell, captured: dict | None) -> tuple:
    """(readings, [(name, value, limit, ok)]) of the captured update
    against the plain reference, and the reference run itself."""
    import check

    limits = cell.cfg["limits"]
    if captured is None or captured.get("batches") is None \
            or captured.get("delta") is None:
        log("check: the chosen update was not captured or produced no delta")
        return {}, [(k, None, v, False) for k, v in limits.items()], None
    r = reference_run(cell, captured)
    vals = check.readings(r["update"], r["out"], r["before"])
    return vals, check.judge(vals, limits), r


def use_compile_cache(root: str) -> None:
    """JAX's persistent compilation cache at the checkout's fixed path."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def run_cell(root: str, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True) -> dict:
    """Set up, measure and check one cell once; returns the result line's
    object (the ``check`` key last)."""
    import jax

    import trace_reduce
    from repro.core import batched, timing

    cell = Cell(root, name)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < cell.chips:
        raise Refused(f"{cell.chips} chips wanted, {len(devices)} present")
    devices = devices[:cell.chips]
    if require_tpu:
        use_compile_cache(root)
    out_dir = os.path.join(root, "results", "bench")
    trace_dir = os.path.join(out_dir, "trace", name)
    if os.path.isdir(trace_dir):
        import shutil

        shutil.rmtree(trace_dir)
    os.makedirs(out_dir, exist_ok=True)

    run = Run(cell, seconds)
    if require_tpu:
        run.peaks = _peaks(devices[0].device_kind)
    timing.reset()
    timing.set_enabled(trace)
    server = Server(cell, seed, t_start, run, devices)
    server.warm_up()
    log(f"set-up: {len(server.sessions)} sessions on {cell.chips} chips, "
        f"fuse_max {server.fuse_max}, exec mode "
        f"{sorted(set(batched.auto_mode_info().values()))} (the program's "
        f"race)")
    pick = traffic_gen.rng(seed, traffic_gen.STREAM_CHECK)
    server.capture_at = (int(pick.integers(0, 3)),
                         int(pick.integers(0, server.fuse_max * cell.chips)))

    compiles = []

    def on_event(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    snap = timing.snapshot()
    tracer = Tracer(trace, seconds, trace_dir)
    w0 = server.clock()
    run.setup_s = w0
    try:
        server.closed_loop(w0, seconds, tracer)
    finally:
        tracer.stop()
        jax.monitoring.unregister_event_duration_listener(on_event)
    run.compiles_in_window = len(compiles)
    run.trace_start = tracer.started
    if trace:
        run.timing = timing.delta(snap)
        events = trace_reduce.load_events(trace_dir)
        log(f"trace: device planes {sorted(events['devices'])}, "
            f"{sum(map(len, events['devices'].values()))} operations, "
            f"{len(events['host'])} host spans")
        if events["devices"]:
            trace_reduce.save_events(events, os.path.join(
                trace_dir, "events.json.gz"))
            run.trace = trace_reduce.reduce(events, n_devices=cell.chips,
                                            window="bench.traced")
    run.memory_peak_bytes = _memory_peak(devices)
    bad = sum(1 for d in server.deltas if d is None
              or not np.all(np.isfinite(np.asarray(d.values, np.float32))))
    captured = server.captured
    widths = sorted({g["width"] for g in run.grants})
    log(f"window: {len(run.grants)} grants of widths {widths}, "
        f"{len(run.updates)} updates, {run.compiles_in_window} compiles "
        f"inside the window")
    del server
    gc.collect()

    readings, judged, _ref = compare(cell, captured)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = cell.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = _device_info(devices, cell.chips)
    device["memory_peak_bytes"] = run.memory_peak_bytes
    failed = sum(1 for u in run.updates if not u["ok"])
    judged.append(("bad_deltas", bad, 0, bad == 0))
    result = {"correct": failed == 0 and all(j[3] for j in judged),
              "attempted": len(run.updates), "failed": failed,
              "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    with open(os.path.join(out_dir, f"{name}.json"), "w") as f:
        json.dump({"seed": seed, "trace": trace, "setup_s": run.setup_s,
                   "readings": readings, "grants": run.grants,
                   "updates": run.updates,
                   "compiles_in_window": run.compiles_in_window,
                   "timing": [[list(map(str, k)), v] for k, v in
                              (run.timing or {}).items()]}, f)
    result["check"] = {n: {"value": v, "limit": lim}
                       for n, v, lim, _ok in judged}
    for k, v in readings.items():
        if k not in cell.cfg["limits"]:
            log(f"reading {k} {v}")
    for n, v, lim, ok in judged:
        log(f"check {n} {v} limit {lim} {'ok' if ok else 'FAILED'}")
    return result
