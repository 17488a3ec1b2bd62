"""Sharded execution: fused grant lifecycles on a real jax device list.

`core.batched.train_phases_sharded` must reproduce the modeled path
exactly — the all-None (default-device) dispatch is the refactored fused
code itself and must be BYTE-identical to per-group `train_phases_fused`;
a forced multi-device host mesh (subprocess — the flag must be set before
jax initializes) must keep wire masks byte-identical and fp16 delta
values within 1 ULP. Plus the plumbing the sharded path rides on:
`launch.host_mesh` flag handling, `scripts/env.sh`, and
`GPUPool(device_backend=...)` bindings.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis, or a fallback when absent

from repro.core import batched
from repro.core.batched import train_phases_fused, train_phases_sharded
from repro.launch import host_mesh
from repro.serving.resources import GPUPool

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seg_sessions(n, k_iters=2, seed0=300, size=16):
    from repro.core.server import AMSConfig, AMSSession, Task
    from repro.data.video import VideoConfig
    from repro.models.seg.student import SegConfig, make_student
    from repro.sim.seg_world import SegWorld, phi_pixel_loss

    seg = SegConfig(n_classes=5)
    ams = AMSConfig(t_update=8.0, t_horizon=30.0, k_iters=k_iters,
                    batch_size=2, gamma=0.05, lr=2e-3, phi_target=0.15)
    pre = make_student(seg, jax.random.PRNGKey(0))
    out = []
    for i in range(n):
        world = SegWorld.make(
            VideoConfig(seed=seed0 + i, height=size, width=size, fps=2.0,
                        duration=20.0), seg)
        task = Task(loss_and_grad=world.loss_and_grad, teacher=None,
                    phi_loss=phi_pixel_loss)
        s = AMSSession(task, ams, jax.tree.map(lambda x: x, pre), seed=i)
        frames = np.stack([world.video.frame(j)[0] for j in range(6)])
        labels = np.stack([world.teacher.label(j) for j in range(6)])
        s.receive_labeled(frames, labels, 5.0)
        out.append(s)
    return out


def _groups(fleet, n_groups, group_b):
    return [fleet[g * group_b:(g + 1) * group_b] for g in range(n_groups)]


def _f16_ulp(a, b) -> int:
    def lex(x):
        u = (np.asarray(x, np.float16).reshape(-1).view(np.uint16)
             .astype(np.int32))
        return np.where(u >= 0x8000, 0x8000 - u, u)

    la, lb = lex(a), lex(b)
    return int(np.max(np.abs(la - lb))) if la.size else 0


# ---------------- host_mesh: flag plumbing ----------------


def test_forced_host_device_count_parses_xla_flags():
    f = host_mesh.forced_host_device_count
    assert f("") is None
    assert f("--xla_cpu_multi_thread_eigen=false") is None
    assert f(host_mesh.host_device_count_flag(4)) == 4
    # appended flags: the LAST occurrence wins (shell-append semantics)
    both = (host_mesh.host_device_count_flag(2) + " --other=1 "
            + host_mesh.host_device_count_flag(8))
    assert f(both) == 8


def test_host_device_count_flag_shape():
    assert host_mesh.host_device_count_flag(4) == \
        "--xla_force_host_platform_device_count=4"
    with pytest.raises(ValueError):
        host_mesh.host_device_count_flag(0)


def test_host_devices_raises_with_pointer_at_env_sh():
    want = len(jax.devices()) + 1
    with pytest.raises(RuntimeError, match="env.sh"):
        host_mesh.host_devices(want)
    # and the happy path returns concrete devices
    devs = host_mesh.host_devices(1)
    assert len(devs) == 1 and devs[0] is jax.devices()[0]


def test_session_mesh_and_shardings():
    from repro.launch.mesh import make_session_mesh

    mesh = make_session_mesh(1)
    assert mesh.axis_names == ("session",)
    assert mesh.devices.size == 1
    with pytest.raises(ValueError):
        make_session_mesh(len(jax.devices()) + 1)
    hm = host_mesh.make_host_mesh(1)
    assert hm.axis_names == ("session",)
    sh = host_mesh.session_sharding(hm)
    assert sh.spec == jax.sharding.PartitionSpec("session")
    rep = host_mesh.replicated_sharding(hm)
    assert rep.spec == jax.sharding.PartitionSpec()


def test_env_sh_forces_host_devices_and_strips_stale_flag():
    script = '. scripts/env.sh && printf "%s|%s" "$XLA_FLAGS" ' \
             '"$TF_CPP_MIN_LOG_LEVEL"'
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "TF_CPP_MIN_LOG_LEVEL",
                        "REPRO_HOST_DEVICES", "LD_PRELOAD")}
    out = subprocess.run(
        ["bash", "-c", script], cwd=REPO, capture_output=True, text=True,
        env={**env, "REPRO_HOST_DEVICES": "4",
             "XLA_FLAGS": host_mesh.host_device_count_flag(2) + " --keep=1"},
        check=True).stdout
    flags, tf_level = out.split("|")
    # stale count dropped, caller's other flags kept, new count appended
    assert flags.count("--xla_force_host_platform_device_count") == 1
    assert host_mesh.host_device_count_flag(4) in flags
    assert "--keep=1" in flags
    assert tf_level == "4"
    # without REPRO_HOST_DEVICES the caller's XLA_FLAGS pass through, and
    # an exported TF_CPP_MIN_LOG_LEVEL is respected
    out = subprocess.run(
        ["bash", "-c", script], cwd=REPO, capture_output=True, text=True,
        env={**env, "XLA_FLAGS": "--keep=1", "TF_CPP_MIN_LOG_LEVEL": "2"},
        check=True).stdout
    assert out == "--keep=1|2"


# ---------------- GPUPool device bindings ----------------


def test_gpupool_device_backend_validates_and_binds():
    with pytest.raises(ValueError, match="device_backend"):
        GPUPool(n_gpus=2, device_backend="cuda")
    modeled = GPUPool(n_gpus=2)
    assert modeled.device_backend == "modeled"
    assert all(d.jax_device is None for d in modeled.devices)
    assert modeled.distinct_jax_devices == 0
    live = jax.devices()
    n = len(live)
    bound = GPUPool(n_gpus=n, device_backend="jax")
    assert [d.gid for d in bound.devices] == list(range(n))
    assert bound.jax_devices() == list(live)
    assert bound.distinct_jax_devices == n
    # a pool wider than the live devices would share a device between
    # slots and run "parallel" grants serially: refused, not wrapped
    with pytest.raises(ValueError, match="live devices"):
        GPUPool(n_gpus=n + 1, device_backend="jax")


# ---------------- sharded == fused on the default device ----------------


def test_sharded_all_none_is_byte_identical_to_fused():
    """devices=[None]*D is the refactored fused launch/commit code on the
    default device: masks AND wire bytes must be byte-identical to
    per-group `train_phases_fused`, phase after phase (first phase uses
    random masks, the second defers gradient-guided selection)."""
    a = _seg_sessions(4)
    b = _seg_sessions(4)
    batched.sharded_reset()
    for t in (6.0, 14.0):
        ref = [d for g in _groups(a, 2, 2)
               for d in train_phases_fused(g, t, force_stack=True)]
        got = [d for grp in train_phases_sharded(
            _groups(b, 2, 2), t, devices=[None, None]) for d in grp]
        assert len(ref) == len(got) == 4
        for r, g in zip(ref, got):
            assert r.packed_mask == g.packed_mask
            assert np.array_equal(np.asarray(r.values),
                                  np.asarray(g.values))
    info = batched.sharded_info()
    assert info["batches"] == 2 and info["groups"] == 4
    assert info["sessions"] == 8 and info["dispatch_launches"] == 4
    assert info["spmd_launches"] == 0
    assert info["distinct_devices"] == 1  # all-None: nothing placed
    # the sessions themselves advanced identically
    for sa, sb in zip(a, b):
        assert sa.phase == sb.phase == 2


def test_sharded_handles_nothing_to_train_slots():
    """A session whose phase prep yields nothing (no ingested frames) gets
    None in its slot, same contract as `train_phases_fused`."""
    fleet = _seg_sessions(2)
    from repro.core.server import AMSConfig, AMSSession, Task
    from repro.sim.seg_world import phi_pixel_loss

    idle = AMSSession(
        Task(loss_and_grad=fleet[0].task.loss_and_grad, teacher=None,
             phi_loss=phi_pixel_loss),
        AMSConfig(t_update=8.0, t_horizon=30.0, k_iters=2, batch_size=2,
                  gamma=0.05, lr=2e-3, phi_target=0.15),
        jax.tree.map(lambda x: x, fleet[0].params), seed=9)
    out = train_phases_sharded([[idle, fleet[0]], [fleet[1]]], 6.0,
                               devices=[None, None])
    assert out[0][0] is None  # nothing ingested -> no phase
    assert out[0][1] is not None and out[1][0] is not None


def test_sharded_validates_inputs():
    fleet = _seg_sessions(2)
    with pytest.raises(ValueError, match="device bindings"):
        train_phases_sharded([[fleet[0]], [fleet[1]]], 6.0, devices=[None])
    mixed = _seg_sessions(1) + _seg_sessions(1, k_iters=3, seed0=400)
    with pytest.raises(ValueError, match="ONE compile key"):
        train_phases_sharded([mixed], 6.0, devices=[None])
    with pytest.raises(ValueError, match="concrete jax.Device"):
        train_phases_sharded([[fleet[0]], [fleet[1]]], 6.0,
                             devices=[None, None], spmd=True)


@settings(max_examples=4, deadline=None)
@given(layout=st.sampled_from(((1, 2), (2, 1), (2, 2), (3, 1))))
def test_sharded_grouping_property(layout):
    """Over (pool size D, group width B): flattened sharded results align
    slot-for-slot with per-group fused results, byte-identically, and the
    counters account for every session."""
    d, b = layout
    a = _seg_sessions(d * b, seed0=600)
    bb = _seg_sessions(d * b, seed0=600)
    ref = [x for g in _groups(a, d, b)
           for x in train_phases_fused(g, 6.0, force_stack=True)]
    batched.sharded_reset()
    got = train_phases_sharded(_groups(bb, d, b), 6.0, devices=[None] * d)
    assert [len(g) for g in got] == [b] * d
    flat = [x for grp in got for x in grp]
    for r, g in zip(ref, flat):
        assert r.packed_mask == g.packed_mask
        assert np.array_equal(np.asarray(r.values), np.asarray(g.values))
    info = batched.sharded_info()
    assert info["groups"] == d and info["sessions"] == d * b


# ---------------- forced 4-device mesh (subprocess) ----------------

_CHILD = r"""
import json, sys
import jax
import numpy as np

n_dev = len(jax.devices())
assert n_dev == 4, f"forced host mesh gave {n_dev} devices"
sys.path.insert(0, "tests")
from test_sharded import _f16_ulp, _groups, _seg_sessions

from repro.core import batched
from repro.core.batched import train_phases_fused, train_phases_sharded

# pin both auto races: the differential question is placement, not mode
batched.set_exec_mode("loop")
batched.set_kernel_mode("xla")
a = _seg_sessions(4, seed0=700, size=12)
b = _seg_sessions(4, seed0=700, size=12)
batched.sharded_reset()
masks_ok, max_ulp, n_ident = True, 0, 0
for t in (6.0, 14.0):
    ref = [d for g in _groups(a, 4, 1)
           for d in train_phases_fused(g, t, force_stack=True)]
    got = [d for grp in train_phases_sharded(
        _groups(b, 4, 1), t, devices=jax.devices()) for d in grp]
    for r, g in zip(ref, got):
        masks_ok &= r.packed_mask == g.packed_mask
        max_ulp = max(max_ulp, _f16_ulp(r.values, g.values))
        n_ident += np.array_equal(np.asarray(r.values),
                                  np.asarray(g.values))
info = batched.sharded_info()
print(json.dumps({"masks_ok": masks_ok, "max_ulp": max_ulp,
                  "n_identical": n_ident,
                  "distinct_devices": info["distinct_devices"],
                  "dispatch_launches": info["dispatch_launches"]}))
"""


def test_four_device_mesh_matches_single_device():
    """The ISSUE differential gate: the same fleet trained on a forced
    4-device host mesh vs the single-device modeled path — wire masks
    byte-identical, fp16 delta values within 1 ULP. Runs in a subprocess
    because the device-count flag must be set before jax initializes (this
    process's backend is already up)."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["XLA_FLAGS"] = host_mesh.host_device_count_flag(4)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")
    proc = subprocess.run([sys.executable, "-c", _CHILD], cwd=REPO,
                          capture_output=True, text=True, timeout=540,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["masks_ok"], "4-device mesh changed a streamed wire mask"
    assert out["max_ulp"] <= 1, (
        f"4-device wire deltas drifted {out['max_ulp']} f16 ULP (>1)")
    assert out["distinct_devices"] == 4
    assert out["dispatch_launches"] == 8  # 4 groups x 2 phases
