"""Stage spans on the grant path: `core.timing.span`, the host stages it
times inside a fused grant (``prepare``, ``place``, ``commit`` beside the
select/train/encode records), and the benchmark readers that turn them
into per-update metrics and split the device's idle time among them."""
import glob
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core import batched, timing
from repro.core.batched import train_phases_fused
from repro.launch import host_mesh
from test_sharded import _seg_sessions

REPO = Path(__file__).resolve().parents[1]
BENCH = REPO / "bench"


@pytest.fixture
def timing_on():
    was = timing.enabled()
    timing.set_enabled(True)
    try:
        yield
    finally:
        timing.set_enabled(was)


def test_span_records_stage_key_and_nbytes(timing_on):
    snap = timing.snapshot()
    with timing.span("place", key=(3,), nbytes=100):
        pass
    with timing.span("encode_stacked", first=True) as sp:
        sp.nbytes = 7
    stats = timing.delta(snap)
    e = stats[("place", (3,))]
    assert e["calls"] == 1 and e["nbytes"] == 100
    assert e["steady_s"] >= 0.0 and e["first_calls"] == 0
    e = stats[("encode_stacked", ())]
    assert e["first_calls"] == 1 and e["nbytes"] == 7


def test_span_disabled_records_nothing_and_opens_no_annotation(monkeypatch):
    opened = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **kw: opened.append(a))
    snap = timing.snapshot()
    timing.set_enabled(False)
    try:
        with timing.span("prepare") as a, timing.span("place", key=(2,),
                                                      nbytes=5) as b:
            b.nbytes = 9
    finally:
        timing.set_enabled(True)
    assert a is b  # one shared null context
    assert opened == [] and timing.delta(snap) == {}


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((plane.name, line.name, e.name, e.start_ns, e.end_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith("ams."))
    return out


def test_fused_grant_spans_in_the_profiler_trace(timing_on, tmp_path):
    """One fused grant of two sessions (one phase in, so selection is the
    stacked gradient-guided launch): every stage is an ``ams.`` span on
    the dispatching thread, siblings that never overlap, and ``place``
    counts the replay batches' bytes."""
    batched.set_exec_mode("loop")
    try:
        sessions = _seg_sessions(2, size=12)
        train_phases_fused(sessions, 6.0, force_stack=True)
        prepped = []
        for s in sessions:
            orig = s._prepare_phase_deferred

            def prepare(t, orig=orig):
                prep = orig(t)
                prepped.append(prep[1].nbytes + prep[2].nbytes)
                return prep

            s._prepare_phase_deferred = prepare
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            deltas = train_phases_fused(sessions, 14.0, force_stack=True)
        finally:
            jax.profiler.stop_trace()
    finally:
        batched.set_exec_mode("auto")
    assert all(d is not None for d in deltas)
    spans = _host_spans(str(tmp_path))
    names = [n for _p, _l, n, _s, _e, _st in spans]
    assert sorted(names) == sorted(
        ["ams.prepare", "ams.prepare", "ams.select_stacked", "ams.place",
         "ams.train_fused", "ams.encode_stacked", "ams.commit"])
    assert len({(p, line) for p, line, *_ in spans}) == 1  # one thread
    ordered = sorted(spans, key=lambda sp: sp[3])
    for a, b in zip(ordered, ordered[1:]):
        assert a[4] <= b[3], f"{a[2]} overlaps {b[2]}"
    place = [sp for sp in spans if sp[2] == "ams.place"][0]
    assert place[5]["nbytes"] == sum(prepped) > 0


def _spans_module():
    """The benchmark's `_spans` helper, imported from ``bench/metrics``."""
    saved = list(sys.path)
    sys.path[:0] = [str(BENCH / "metrics"), str(BENCH)]
    try:
        for m in ("_spans", "_stages", "trace_reduce"):
            sys.modules.pop(m, None)
        import _spans

        return _spans
    finally:
        sys.path[:] = saved
        for m in ("_spans", "_stages", "trace_reduce"):
            sys.modules.pop(m, None)


def test_idle_gap_is_split_by_the_innermost_span():
    """A device gap over ``ams.prepare``, then ``ams.place``, then the bare
    ``bench.train_many`` around them is split three ways, exactly, where a
    midpoint rule would give it all to one."""
    spans = _spans_module()
    ms = 1_000_000
    ev = {"host": [["bench.traced", 0, 100 * ms],
                   ["bench.train_many", 10 * ms, 90 * ms],
                   ["ams.prepare", 20 * ms, 40 * ms],
                   ["ams.place", 40 * ms, 50 * ms],
                   ["ams.train_fused", 60 * ms, 90 * ms]],
          "devices": {"/device:TPU:0": [["op", 0, 30 * ms],
                                        ["op", 60 * ms, 100 * ms]]}}
    idle, window_s = spans.idle_by_span(ev, 1)
    assert window_s == pytest.approx(0.1)
    assert idle == pytest.approx({"ams.prepare": 0.010, "ams.place": 0.010,
                                  "bench.train_many": 0.010})
    prep = ("ams.prepare", "ams.place")
    assert spans.share(ev, 1, prep) == pytest.approx(20.0)
    # a second device idle over the whole of place: the mean of the two
    ev["devices"]["/device:TPU:1"] = [["op", 0, 40 * ms],
                                      ["op", 50 * ms, 100 * ms]]
    assert spans.share(ev, 2, prep) == pytest.approx((20.0 + 10.0) / 2)
    # a trace without the program's spans: nothing to read
    ev["host"] = [h for h in ev["host"] if not h[0].startswith("ams.")]
    assert spans.share(ev, 1, prep) is None


def test_traced_run_reads_the_host_preparation_spans(tmp_path):
    """A traced run of the tiny benchmark cell prints the host stage
    metrics; the bytes placed per update are the cell's replay batch."""
    sys.path.insert(0, str(BENCH / "tests"))
    try:
        from bench_testkit import run, tiny_root
    finally:
        sys.path.remove(str(BENCH / "tests"))
    root = tiny_root(tmp_path)
    try:
        # the check captures an update of one of the first three grants
        res = run(root, "mnv2-backlog", seconds=4.0, trace=True)
    finally:
        sys.modules.pop("_spans", None)
    assert res["correct"] is True
    for name in ("host_ms_per_update", "prepare_ms_per_update",
                 "place_ms_per_update", "commit_ms_per_update"):
        assert res["metrics"][name]["value"] > 0
    cfg = json.loads(
        (root / "bench/configs/ams-deeplabv3-mnv2.json").read_text())
    fr, ams = cfg["frame"], cfg["ams"]
    pixels = ams["k_iters"] * ams["batch_size"] * fr["height"] * fr["width"]
    # float32 frames of in_channels, int32 labels
    want = pixels * (4 * cfg["student"]["in_channels"] + 4) / 1e6
    assert res["metrics"]["batch_mb_per_update"]["value"] == \
        pytest.approx(want, rel=1e-12)
    # no device planes on the CPU: the trace's readers find nothing
    assert "prep_idle_share" not in res["metrics"]


_CHILD = r"""
import json, sys
import jax
sys.path.insert(0, "tests")
from test_sharded import _groups, _seg_sessions
from repro.core import batched, timing
from repro.core.batched import train_phases_sharded

assert len(jax.devices()) == 4
batched.set_exec_mode("loop")
batched.set_kernel_mode("xla")
timing.set_enabled(True)
fleet = _seg_sessions(8, seed0=800, size=12)
snap = timing.snapshot()
out = train_phases_sharded(_groups(fleet, 4, 2), 6.0, devices=jax.devices())
stats = timing.delta(snap)
print(json.dumps({"trained": sum(d is not None for g in out for d in g),
                  "stats": [[s, list(k), e["calls"], e["nbytes"]]
                            for (s, k), e in stats.items()]}))
"""


def test_sharded_grant_records_prepare_per_session_place_per_group():
    """Four groups of two on four host devices: one ``prepare`` per
    session, one ``place`` per group (keyed by its width). Runs in a
    subprocess: the device-count flag must be set before jax starts."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["XLA_FLAGS"] = host_mesh.host_device_count_flag(4)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")
    proc = subprocess.run([sys.executable, "-c", _CHILD], cwd=REPO,
                          capture_output=True, text=True, timeout=540,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["trained"] == 8
    calls = {}
    for stage, key, n, nbytes in out["stats"]:
        calls.setdefault(stage, []).append((tuple(key), n, nbytes))
    assert sum(n for _k, n, _b in calls["prepare"]) == 8
    assert [(k, n) for k, n, _b in calls["place"]] == [((2,), 4)]
    assert calls["place"][0][2] > 0
    assert sum(n for _k, n, _b in calls["commit"]) == 4
    assert sum(n for _k, n, _b in calls["train_sharded"]) == 1
