"""The reduction from a profiler trace to busy time, top operations and
labelled idle gaps."""
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testkit import ROOT, bench_modules

DATA = Path(__file__).parent / "data"


@pytest.fixture()
def tr():
    with bench_modules(ROOT):
        import trace_reduce

        yield trace_reduce


def test_hand_made_trace(tr):
    ev = {"host": [["bench.traced", 0, 100], ["bench.ingest", 0, 30],
                   ["bench.train_many", 30, 100]],
          "devices": {"/device:TPU:0": [["conv", 10, 20], ["conv", 15, 40],
                                        ["adam", 60, 70], ["late", 95, 130]],
                      "/device:TPU:1": [["conv", 0, 50]]}}
    one = tr.reduce(ev, n_devices=1)
    assert one["window_s"] == pytest.approx(100e-9)
    assert one["busy_s"] == pytest.approx(45e-9)  # 10-40, 60-70, 95-100
    assert one["device_ops"][0] == ["conv", pytest.approx(35e-9)]
    assert one["idle_gaps"] == [["train_many", pytest.approx(25e-9)],
                                ["train_many", pytest.approx(20e-9)],
                                ["ingest", pytest.approx(10e-9)]]
    two = tr.reduce(ev, n_devices=2)
    assert two["busy_s"] == pytest.approx((45e-9 + 50e-9) / 2)
    with pytest.raises(ValueError):
        tr.reduce(ev, n_devices=3)


def _brute_force(ev, plane, ws, we):
    """Busy nanoseconds by marking a timeline (the reduction's check)."""
    t0 = ws
    line = np.zeros(int(we - ws), bool)
    for _n, s, e in ev["devices"][plane]:
        a, b = max(s, ws) - t0, min(e, we) - t0
        if b > a:
            line[int(a):int(b)] = True
    return int(line.sum())


def test_recorded_chip_trace(tr):
    """A trace recorded on a TPU v5e in a w10-backlog grant: one second
    where host preparation ends and the device starts, kept as events."""
    ev = tr.read_events(DATA / "v5e_grant_events.json.gz")
    (name, ws, we), = [h for h in ev["host"] if h[0] == "bench.traced"]
    plane = sorted(ev["devices"])[0]
    red = tr.reduce(ev, n_devices=1)
    want = _brute_force(ev, plane, ws, we)
    assert red["busy_s"] * 1e9 == pytest.approx(want, abs=2)
    assert 0 < red["busy_s"] <= red["window_s"]
    idle = sum(g[1] for g in red["idle_gaps"])
    assert idle <= red["window_s"] - red["busy_s"] + 1e-9
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    secs = [s for _n, s in red["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_load_events_reads_host_spans(tr, tmp_path):
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced"):
        with jax.profiler.TraceAnnotation("bench.train_many"):
            f(x).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    ev = tr.load_events(str(tmp_path))
    names = {h[0] for h in ev["host"]}
    assert {"bench.traced", "bench.train_many"} <= names
    assert all(n.startswith("bench.") for n in names)
    tr.save_events(ev, tmp_path / "ev.json.gz")
    assert tr.read_events(tmp_path / "ev.json.gz") == ev
