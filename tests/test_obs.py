"""Flight recorder: trace determinism + invariants, metrics registry,
modeled-vs-measured drift audit, unified debug snapshot.

The recorder must be an *observer*: with tracing off the engine's schedule
and results are bit-identical to the untraced run, and with tracing on the
emitted Chrome trace is deterministic (byte-identical across identical
runs) and structurally valid — spans never overlap on a stream, serialized
devices never run two streams at once, and a fused grant's
train/select/encode stages nest inside its device-grant span.
"""
import json

from _hyp import given, settings, st

from repro.core import timing
from repro.core.scheduler import GPUCostModel
from repro.serving import (
    ClientNetwork,
    FaultPlan,
    LinkSpec,
    MetricsRegistry,
    ServingConfig,
    ServingEngine,
    StreamModel,
    StubSession,
    Tracer,
    debug_snapshot,
    drift_report,
    validate_trace,
)

PRICED = dict(select_s=0.15, delta_comp_s_per_mb=5.0)


def _fleet(n, link=None, rate_head=0.15):
    link = link or LinkSpec(up_kbps=500.0, down_kbps=2000.0)
    return [StubSession(i, rate=rate_head if i < 2 else 1.0,
                        dynamics=0.0005 if i < 2 else 0.004,
                        net=ClientNetwork(link))
            for i in range(n)]


def _run(n=6, *, n_gpus=2, fuse=4, streams=None, cost=None, duration=90.0,
         fuse_updates=True, policy="fair", tracer=None, rate_head=0.15,
         faults=None):
    fkw = {} if faults is None else {"faults": faults}
    eng = ServingEngine(
        _fleet(n, rate_head=rate_head), policy=policy,
        cost=cost or GPUCostModel(),
        cfg=ServingConfig(duration=duration, n_gpus=n_gpus, fuse_train=fuse,
                          fuse_updates=fuse_updates,
                          streams=streams or StreamModel(), **fkw),
        tracer=tracer)
    return eng.run()


def _traced(n=6, **kw):
    tracer = Tracer()
    r = _run(n, tracer=tracer, **kw)
    return r, tracer


_WALL_KEYS = ("wall_s", "events_per_sec", "events_per_sec_steady",
              "observability")


def _stable(r):
    return {k: v for k, v in r.items() if k not in _WALL_KEYS}


# ---------------- trace determinism ----------------


def test_trace_byte_identical_across_runs():
    _, t1 = _traced(8, cost=GPUCostModel(**PRICED))
    _, t2 = _traced(8, cost=GPUCostModel(**PRICED))
    assert t1.to_json() == t2.to_json()


def test_tracing_does_not_perturb_the_schedule():
    plain = _run(8, cost=GPUCostModel(**PRICED))
    traced, _ = _traced(8, cost=GPUCostModel(**PRICED))
    assert _stable(plain) == _stable(traced)
    assert plain["observability"]["tracing"] is False
    assert traced["observability"]["tracing"] is True


def test_trace_has_layout_and_counters():
    r, tracer = _traced(6, n_gpus=2)
    trace = json.loads(tracer.to_json())
    evs = trace["traceEvents"]
    procs = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert "serving-engine" in procs
    assert {"gpu0", "gpu1"} <= procs
    assert {f"client{i}" for i in range(6)} <= procs
    threads = {e["args"]["name"] for e in evs
               if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert {"stream:label", "stream:train", "grants",
            "uplink", "downlink"} <= threads
    counters = {e["name"] for e in evs if e.get("ph") == "C"}
    assert {"queue_depth", "backlog_frames", "stream_util"} <= counters
    assert trace["otherData"]["n_gpus"] == 2
    # a grant -> downlink-delta causal arrow exists
    assert any(e.get("ph") == "s" for e in evs)
    assert any(e.get("ph") == "f" for e in evs)


# ---------------- client sampling past the fleet cap ----------------


def test_tracer_refuses_big_fleets_and_points_at_sampling():
    tracer = Tracer(max_clients=4)
    try:
        _run(6, tracer=tracer)
    except ValueError as e:
        assert "sample_clients=k" in str(e)
    else:
        raise AssertionError("expected the big-fleet refusal")
    try:
        Tracer(sample_clients=0)
    except ValueError as e:
        assert "sample_clients" in str(e)
    else:
        raise AssertionError("expected sample_clients >= 1 validation")


def test_tracer_sampling_is_deterministic_and_evenly_spaced():
    t1 = Tracer(max_clients=4, sample_clients=3)
    t2 = Tracer(max_clients=4, sample_clients=3)
    r1 = _run(9, tracer=t1)
    r2 = _run(9, tracer=t2)
    assert t1._sampled == t2._sampled  # same fleet -> same subset
    assert t1._sampled == frozenset({0, 3, 6})  # ids[(j*n)//k], spans range
    assert t1.meta["sampled_clients"] == 3
    assert all(t1.traces_client(c) == (c in {0, 3, 6}) for c in range(9))
    assert t1.client_span(1, "up", "x", 0.0, 1.0) is None  # span dropped
    assert t1.to_json() == t2.to_json()
    # sampling drops spans, never events: the schedule is untouched
    assert _stable(r1) == _stable(r2)
    assert _stable(r1) == _stable(_run(9))
    # sampled-client tracks exist; unsampled ones don't
    trace = json.loads(t1.to_json())
    procs = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert {"client0", "client3", "client6"} <= procs
    assert not {f"client{i}" for i in (1, 2, 4, 5, 7, 8)} & procs


def test_tracer_sampling_inactive_under_the_cap():
    tracer = Tracer(sample_clients=3)  # default cap 1000 >> fleet
    _run(6, tracer=tracer)
    assert tracer._sampled is None  # every client traced
    assert "sampled_clients" not in tracer.meta
    assert all(tracer.traces_client(c) for c in range(6))


# ---------------- trace invariants (property-style) ----------------


@settings(max_examples=10, deadline=None)
@given(n=st.integers(min_value=3, max_value=10),
       gpus=st.integers(min_value=1, max_value=3),
       overlap=st.booleans(), preempt=st.booleans(),
       fuse=st.sampled_from([1, 4]))
def test_trace_invariants_property(n, gpus, overlap, preempt, fuse):
    """Across stream models, pool sizes and fusing: non-negative durations,
    per-stream serial execution, cross-stream concurrency <= 1 (serialized)
    / <= 2 (overlap), and grant-tagged spans nested in their grant."""
    streams = StreamModel(mode="overlap" if overlap else "serialized",
                          slowdown=1.1 if overlap else 1.0,
                          preempt=preempt, preempt_cost_s=0.02)
    _, tracer = _traced(n, n_gpus=gpus, fuse=fuse, streams=streams,
                        cost=GPUCostModel(**PRICED), duration=60.0)
    trace = json.loads(tracer.to_json())
    assert validate_trace(trace) == []


def test_fused_grant_nests_train_select_encode():
    r, tracer = _traced(8, n_gpus=1, fuse=4, cost=GPUCostModel(**PRICED),
                        duration=120.0)
    trace = json.loads(tracer.to_json())
    assert validate_trace(trace) == []
    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    fused = [e for e in spans if e.get("cat") == "grant"
             and e["args"]["riders"] > 0]
    assert fused, "run produced no fused grants"
    by_grant: dict = {}
    for e in spans:
        g = e.get("args", {}).get("grant")
        if g is not None:
            by_grant.setdefault(g, set()).add(e["name"])
    for g in fused:
        names = by_grant.get(g["args"]["seq"], set())
        assert {"train", "select", "encode"} <= names, (
            f"fused grant {g['args']['seq']} has stages {sorted(names)}")


def test_preemption_is_a_schedule_edit_in_the_trace():
    # the known preemption-triggering shape from test_streams: 8 dynamic
    # clients on one serialized-era GPU with overlap+preempt streams
    streams = StreamModel("overlap", slowdown=1.1, preempt=True,
                          preempt_cost_s=0.02)
    tracer = Tracer()
    eng = ServingEngine(
        _fleet(8, rate_head=1.0), policy="fair",
        cfg=ServingConfig(duration=180.0, max_queue=64, streams=streams),
        tracer=tracer)
    r = eng.run()
    assert r["preemptions"] > 0
    trace = json.loads(tracer.to_json())
    assert validate_trace(trace) == []
    names = {e["name"] for e in trace["traceEvents"]}
    assert "preempt" in names  # the cut instant
    assert "preempt_cost" in names  # the modeled preemption charge


def test_validate_trace_rejects_tampering():
    _, tracer = _traced(6)
    good = json.loads(tracer.to_json())
    assert validate_trace(good) == []
    bad = json.loads(tracer.to_json())
    next(e for e in bad["traceEvents"] if e.get("ph") == "X")["dur"] = -5
    assert any("negative" in p for p in validate_trace(bad))
    gutted = dict(good, traceEvents=[e for e in good["traceEvents"]
                                     if e.get("name") != "queue_depth"])
    assert any("queue_depth" in p for p in validate_trace(gutted))


# ---------------- chaos traces ----------------


def _chaos_traced(n=10, duration=120.0, n_gpus=2):
    tracer = Tracer()
    r = _run(n, n_gpus=n_gpus, duration=duration, policy="gain",
             tracer=tracer,
             faults=FaultPlan.reference(duration, n_gpus=n_gpus))
    return r, tracer


def test_chaos_trace_validates_with_fault_vocabulary():
    r, tracer = _chaos_traced()
    trace = json.loads(tracer.to_json())
    assert validate_trace(trace) == []
    evs = trace["traceEvents"]
    names = {e.get("name") for e in evs}
    assert "outage" in names  # link-outage windows on client fault tracks
    assert "crash" in names  # the crash window on the device fault track
    assert "retry" in names  # retransmits occupy the link like transfers
    # the fault threads exist only because chaos is on
    fault_threads = [e for e in evs if e.get("ph") == "M"
                     and e.get("name") == "thread_name"
                     and e["args"]["name"] == "faults"]
    assert fault_threads
    assert r["chaos"]["uploads_lost"] > 0


def test_chaos_trace_byte_identical_across_runs():
    _, t1 = _chaos_traced()
    _, t2 = _chaos_traced()
    assert t1.to_json() == t2.to_json()


def test_validate_trace_rejects_retry_overlapping_live_transfer():
    _, tracer = _chaos_traced()
    trace = json.loads(tracer.to_json())
    assert validate_trace(trace) == []
    # forge a retry that double-books a client uplink while a real transfer
    # occupies it — link occupancy is serial, the validator must object
    up = next(e for e in trace["traceEvents"]
              if e.get("ph") == "X" and e.get("cat") == "net:up"
              and e["dur"] > 0)
    forged = dict(up, name="retry", ts=up["ts"] + up["dur"] // 2)
    trace["traceEvents"].append(forged)
    assert any("overlapping" in p for p in validate_trace(trace))


def test_validate_trace_rejects_misplaced_fault_events():
    _, tracer = _chaos_traced()
    base = tracer.to_json()
    # a crash span on a client's fault track is vocabulary abuse
    trace = json.loads(base)
    crash = next(e for e in trace["traceEvents"]
                 if e.get("ph") == "X" and e.get("cat") == "fault"
                 and e["name"] == "crash")
    outage = next(e for e in trace["traceEvents"]
                  if e.get("ph") == "X" and e.get("cat") == "fault"
                  and e["name"] == "outage")
    crash["pid"], crash["tid"] = outage["pid"], outage["tid"]
    assert any("crash span off a device fault track" in p
               for p in validate_trace(trace))
    # a supersede instant belongs to a client process, not the server
    trace2 = json.loads(base)
    sup = [e for e in trace2["traceEvents"]
           if e.get("ph") == "i" and e.get("name") == "supersede"]
    if sup:  # the reference plan produces these; guard stays for tuning
        sup[0]["pid"] = 1  # PID_SERVER
        assert any("supersede instant off a client" in p
                   for p in validate_trace(trace2))
    # an unknown fault-span name is rejected outright
    trace3 = json.loads(base)
    next(e for e in trace3["traceEvents"]
         if e.get("ph") == "X" and e.get("cat") == "fault")["name"] = "gremlin"
    assert any("unknown fault span" in p for p in validate_trace(trace3))


# ---------------- metrics registry ----------------


def test_registry_counters_gauges_histograms():
    m = MetricsRegistry()
    c = m.counter("a.b")
    c.inc()
    c.inc(2)
    m.gauge("a.g", 0).set_max(5)
    m.gauge("a.g").set_max(3)  # lower: keeps the max
    m.set("top", "x")
    h = m.histogram("lat")
    h.extend([1.0, 3.0])
    assert h.count == 2 and h.mean() == 2.0 and h.max() == 3.0
    out = m.as_results()
    assert out == {"a": {"b": 3, "g": 5}, "top": "x"}  # histograms skipped
    assert "lat" in m and m["lat"] is h


def test_registry_type_mismatch_raises():
    m = MetricsRegistry()
    m.counter("x")
    try:
        m.gauge("x")
    except TypeError:
        pass
    else:
        raise AssertionError("gauge('x') over a Counter should raise")


def test_results_assembled_from_registry():
    eng = ServingEngine(_fleet(5), policy="fair",
                        cfg=ServingConfig(duration=60.0))
    r = eng.run()
    # the counters the run accumulated are the values the dict reports
    assert r["phases_served"] == eng.served.value
    assert r["label_batches"] == eng.label_batches.value
    assert r["max_backlog"] == eng.max_backlog.value
    assert r["update_pipeline"]["batched_launches"] == \
        eng.update_batched_launches.value
    assert r == eng.metrics.as_results()


def test_events_per_sec_steady_present():
    r = _run(5, n_gpus=1, fuse=1)
    # stub fleets compile nothing, so steady == raw up to the clamp; with
    # compile attributed it can only be >= raw
    assert r["events_per_sec_steady"] >= r["events_per_sec"] > 0.0
    obs = r["observability"]
    assert obs["compile_s"] == 0.0 and obs["drift"] == {}


# ---------------- timing shim + drift audit ----------------


def test_timing_shim_first_vs_steady():
    snap = timing.snapshot()
    timing.record("train_fused", 0.5, first=True, key=(4, 20))
    timing.record("train_fused", 0.1, key=(4, 20))
    timing.record("train_fused", 0.1, key=(4, 20))
    stats = timing.delta(snap)
    e = stats[("train_fused", (4, 20))]
    assert e["calls"] == 3 and e["first_calls"] == 1
    assert abs(e["first_s"] - 0.5) < 1e-12
    assert abs(e["steady_s"] - 0.2) < 1e-12
    assert abs(timing.compile_s(stats) - 0.5) < 1e-12
    tot = timing.totals(stats)
    assert tot["train_fused"]["calls"] == 3


def test_timing_disabled_records_nothing():
    snap = timing.snapshot()
    timing.set_enabled(False)
    try:
        timing.record("train_fused", 1.0, key=(2, 5))
    finally:
        timing.set_enabled(True)
    assert timing.delta(snap) == {}


def test_drift_report_against_known_cost_model():
    cost = GPUCostModel(**PRICED)
    stats = {
        ("train_fused", (4, 20)): {"calls": 3, "first_calls": 1,
                                   "first_s": 2.0, "steady_s": 1.0,
                                   "nbytes": 0},
        ("select_stacked", (4,)): {"calls": 2, "first_calls": 0,
                                   "first_s": 0.0, "steady_s": 0.3,
                                   "nbytes": 0},
        ("encode_solo", ()): {"calls": 2, "first_calls": 0, "first_s": 0.0,
                              "steady_s": 0.1, "nbytes": 2_000_000},
    }
    d = drift_report(cost, stats)
    tf = d["train_fused"]
    # modeled steady = 3 * train_batch_s(4,20) scaled by 2/3 steady calls
    want = 3 * cost.train_batch_s(4, 20) * 2 / 3
    assert abs(tf["modeled_steady_s"] - want) < 1e-9
    assert tf["compile_s"] == 2.0 and tf["steady_calls"] == 2
    assert abs(tf["drift_ratio"] - 1.0 / want) < 1e-9
    sel = d["select_stacked"]
    want_sel = 2 * (cost.update_setup_s
                    + cost.select_s * (1 + cost.update_discount * 3))
    assert abs(sel["modeled_steady_s"] - want_sel) < 1e-9
    enc = d["encode_solo"]
    assert abs(enc["modeled_steady_s"] - cost.delta_comp_s(2_000_000)) < 1e-9
    assert abs(enc["measured_per_call_s"] - 0.05) < 1e-12


def test_debug_snapshot_unifies_hooks():
    snap = debug_snapshot()
    assert set(snap) == {"fused_train_cache", "auto_exec_modes",
                         "update_pipeline", "sharded",
                         "stacked_select_cache",
                         "stacked_encode_cache", "kernel_dispatch",
                         "stage_timings"}
    assert {"size", "hits", "misses"} <= set(snap["fused_train_cache"])
    assert {"batches", "groups", "sessions", "dispatch_launches",
            "spmd_launches", "distinct_devices"} <= set(snap["sharded"])
    assert {"stacked_select_launches",
            "stacked_encode_launches"} <= set(snap["update_pipeline"])
    assert {"mode", "auto_races"} <= set(snap["kernel_dispatch"])
    assert snap["kernel_dispatch"]["mode"] in ("auto", "pallas", "xla")
