"""The harness on the CPU at a tiny size: files found by name, seeded
traffic, the result line, and no result without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench_testkit import ROOT, bench_modules, run, tiny_root

CELL, CONFIG = "mnv2-backlog", "ams-deeplabv3-mnv2"

KEYS = ["correct", "attempted", "failed", "metrics", "device", "check"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


def test_result_line_has_the_contract_keys(root):
    res = run(root, CELL)
    assert list(res) == KEYS  # check comes last
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "updates_per_s",
                                   "downlink_kbps"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert {"mask_miss", "wire_gap", "bad_deltas"} <= set(res["check"])
    json.dumps(res, allow_nan=False)


def test_traced_run_reads_the_program_spans(root):
    res = run(root, CELL, trace=True)
    assert res["correct"] is True
    # no device planes on the CPU: the trace's readers find nothing
    assert "idle_share" not in res["metrics"]
    for name in ("host_ms_per_update", "train_ms_per_update",
                 "select_ms_per_update", "encode_ms_per_update"):
        assert res["metrics"][name]["value"] > 0


FOUR_CHIPS = """
import json, sys
from pathlib import Path
sys.path.insert(0, sys.argv[2])
from bench_testkit import run
res = run(Path(sys.argv[1]), "pool-test", seconds=1.5)
print(json.dumps(res))
"""


def test_four_chip_cell_from_data_alone(tmp_path):
    """A four-chip cell, added as a mix file and a BENCHMARK.json entry,
    runs one sharded grant of a full stack per chip, with sessions
    resident on chip i mod 4, on four host devices."""
    root = tiny_root(tmp_path)
    (root / "bench/traffic/pool_test.json").write_text(json.dumps({
        "sessions_per_fuse": 1, "frames_per_upload": 2, "pool_frames": 4,
        "objects": 3, "motion_px_per_frame": 2.0, "noise": 0.02}))
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["workloads"].append({"name": "pool-test", "config": CONFIG,
                            "traffic": "pool_test", "chips": 4,
                            "why": "test"})
    for m in bm["end_to_end"]:
        if m["name"] == "updates_per_s":
            m["workloads"].append("pool-test")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", FOUR_CHIPS, str(root),
         str(Path(__file__).parent)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res
    assert res["device"]["count"] == 4
    assert res["metrics"]["updates_per_s"]["value"] > 0
    rec = json.loads((root / "results/bench/pool-test.json").read_text())
    assert {g["width"] for g in rec["grants"]} == {8}  # 4 chips x fuse_max 2
    assert sorted(u["session"] for u in rec["updates"][:8]) == list(range(8))


def test_new_config_traffic_and_metric_need_no_edit(tmp_path):
    """A configuration, a traffic mix and a metric added as new files and
    BENCHMARK.json entries are found and used; no other file changes."""
    root = tiny_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((root / f"bench/configs/{CONFIG}.json").read_text())
    cfg["name"] = "extra-cfg"
    cfg["ams"]["gamma"] = 0.1
    (root / "bench/configs/extra-cfg.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/extra_mix.json").write_text(json.dumps({
        "sessions_per_fuse": 1, "frames_per_upload": 3,
        "pool_frames": 3, "objects": 2, "motion_px_per_frame": 1.0,
        "noise": 0.0}))
    (root / "bench/metrics/uploads_seen.py").write_text(
        "def read(run):\n    return float(len(run.updates))\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "extra-cfg", "source": "test",
                          "file": "bench/configs/extra-cfg.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "extra-cell", "config": "extra-cfg",
                            "traffic": "extra_mix", "chips": 1,
                            "why": "test"})
    bm["end_to_end"].append({"name": "uploads_seen", "unit": "uploads",
                             "better": "higher", "bound": 0.25,
                             "source": "host_clock",
                             "workloads": ["extra-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    res = run(root, "extra-cell")
    assert res["correct"] is True
    assert res["metrics"]["uploads_seen"]["value"] == res["attempted"] > 0
    assert "uploads_seen" in res["metrics"]
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_traffic_is_a_pure_function_of_cell_and_seed(root):
    with bench_modules(root) as harness:
        import traffic_gen

        cell = harness.Cell(str(root), CELL)
        assert traffic_gen.n_sessions(cell.traffic, 2, 1) == 6
        assert traffic_gen.n_sessions(cell.traffic, 2, 4) == 24
        fr = cell.cfg["frame"]
        f1, l1 = traffic_gen.render_pools(cell.traffic, 2, fr, 5, 2 ** 33 + 5)
        f2, l2 = traffic_gen.render_pools(cell.traffic, 2, fr, 5, 2 ** 33 + 5)
        f3, _ = traffic_gen.render_pools(cell.traffic, 2, fr, 5, 2 ** 33 + 6)
        assert np.array_equal(f1, f2) and np.array_equal(l1, l2)
        assert not np.array_equal(f1, f3)
        assert f1.dtype == np.float32 and l1.dtype == np.int32
        assert 0 <= l1.min() and l1.max() < 5 and len(np.unique(l1)) > 1
        a = traffic_gen.upload((f1, l1), 1, 3, 2)
        b = traffic_gen.upload((f2, l2), 1, 3, 2)
        assert all(np.array_equal(x, y) for x, y in zip(a, b))
    # the same seed grants the same sessions in the same order; how many
    # grants a window holds depends on the clock
    grants = []
    for _ in range(2):
        run(root, CELL, seed=2 ** 32 + 9, seconds=2.0)
        rec = json.loads((root / f"results/bench/{CELL}.json").read_text())
        grants.append([u["session"] for u in rec["updates"]])
    n = min(map(len, grants))
    assert n >= 6 and grants[0][:n] == grants[1][:n]


def _bench_cmd(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    out = _bench_cmd(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_run_refuses_without_the_program(root):
    out = _bench_cmd(root)  # BENCHMARK.json and bench/ only
    assert out.returncode != 0
    assert out.stdout.strip() == ""
