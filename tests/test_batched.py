"""Fused cross-session training (`core.batched`): stack/unstack round-trips,
fused-vs-sequential phase equivalence (B=1 bitwise, B>1 to tolerance), the
module-level executable cache, and the run_multiclient default-path golden."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis, or a fallback when absent

from repro.core import batched
from repro.core.server import AMSConfig, AMSSession, Task
from repro.data.video import VideoConfig
from repro.models.seg.student import SegConfig, make_student
from repro.sim.seg_world import SegWorld, phi_pixel_loss

SEG = SegConfig(n_classes=5)
AMS = AMSConfig(t_update=8.0, t_horizon=30.0, k_iters=3, batch_size=2,
                gamma=0.05, lr=2e-3, phi_target=0.15)


def _pretrained():
    return make_student(SEG, jax.random.PRNGKey(0))


def _session(i, pre, ams=AMS, n_feed=6):
    """A deterministic, fully-fed AMS session: same i -> identical state."""
    world = SegWorld.make(
        VideoConfig(seed=100 + i, height=24, width=24, fps=2.0,
                    duration=20.0), SEG)
    task = Task(loss_and_grad=world.loss_and_grad, teacher=None,
                phi_loss=phi_pixel_loss)
    s = AMSSession(task, ams, jax.tree.map(lambda x: x, pre), seed=i)
    if n_feed:
        frames = np.stack([world.video.frame(j)[0] for j in range(n_feed)])
        labels = np.stack([world.teacher.label(j) for j in range(n_feed)])
        s.receive_labeled(frames, labels, 5.0)
    return s


def _leaves_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _max_leaf_diff(a, b):
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------- stack / unstack ----------------


@settings(max_examples=10, deadline=None)
@given(b=st.integers(1, 5), n=st.integers(1, 8), m=st.integers(1, 4),
       seed=st.integers(0, 1 << 16))
def test_stack_unstack_roundtrip(b, n, m, seed):
    """unstack(stack(trees)) returns the original trees, leaf for leaf."""
    rng = np.random.default_rng(seed)
    trees = [{"w": jnp.asarray(rng.normal(size=(n, m)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(m,)), jnp.float32),
              "nest": {"c": jnp.asarray(rng.integers(0, 9, size=(n,)),
                                        jnp.int32)}}
             for _ in range(b)]
    stacked = batched.stack_trees(trees)
    assert all(l.shape[0] == b for l in jax.tree.leaves(stacked))
    back = batched.unstack_tree(stacked, b)
    assert len(back) == b
    for orig, got in zip(trees, back):
        assert _leaves_equal(orig, got)


def _state_like_tree(b, seed):
    """A stacked ``(params, MaskedAdamState, mask)`` shaped like a fused
    group's state: float32 leaves, the int32 step count (``(B,)`` stacked)
    and bool masks."""
    from repro.core.masked_adam import MaskedAdamState

    rng = np.random.default_rng(seed)

    def f32(*shape):
        return jnp.asarray(rng.normal(size=(b,) + shape), jnp.float32)

    params = {"conv": {"w": f32(3, 3, 4, 8), "b": f32(8)}, "head": f32(8, 5)}
    opt = MaskedAdamState(m=jax.tree.map(lambda l: l * 0.1, params),
                          v=jax.tree.map(jnp.square, params),
                          count=jnp.arange(b, dtype=jnp.int32) + 7)
    mask = jax.tree.map(lambda l: l > 0, params)
    return params, opt, mask


@pytest.mark.parametrize("b", [1, 4])
def test_unstack_tree_matches_per_leaf_slices(b):
    """The jitted unstack hands back exactly what per-leaf ``l[i]`` gives,
    dtype and shape included, and a second call with the same structure
    and ``n`` reuses the compiled program."""
    tree = _state_like_tree(b, seed=b)
    got = batched.unstack_tree(tree, b)
    assert isinstance(got, list) and len(got) == b
    for i, tree_i in enumerate(got):
        want = jax.tree.map(lambda l: l[i], tree)
        assert (jax.tree.structure(tree_i) == jax.tree.structure(want))
        for x, y in zip(jax.tree.leaves(tree_i), jax.tree.leaves(want)):
            assert x.dtype == y.dtype and x.shape == y.shape
            assert np.array_equal(np.asarray(x), np.asarray(y))
    assert got[0][1].count.dtype == jnp.int32 and got[0][1].count.shape == ()
    assert got[0][2]["head"].dtype == jnp.bool_
    size = batched._unstack_impl._cache_size()
    again = batched.unstack_tree(_state_like_tree(b, seed=b + 100), b)
    assert batched._unstack_impl._cache_size() == size
    assert not _leaves_equal(again, got)


def test_stack_trees_empty_raises():
    with pytest.raises(ValueError):
        batched.stack_trees([])


def test_tree_struct_discriminates():
    a = {"w": jnp.zeros((3, 2))}
    assert batched.tree_struct(a) == batched.tree_struct(
        {"w": jnp.ones((3, 2))})
    assert batched.tree_struct(a) != batched.tree_struct(
        {"w": jnp.zeros((2, 3))})
    assert batched.tree_struct(a) != batched.tree_struct(
        {"w": jnp.zeros((3, 2), jnp.float16)})
    assert batched.tree_struct(a) != batched.tree_struct(
        {"v": jnp.zeros((3, 2))})


# ---------------- fused vs sequential equivalence ----------------


def test_fused_b1_bitwise_equals_sequential():
    """A singleton fused phase IS the sequential phase: params, optimizer
    state, u, and the encoded delta must match bit for bit."""
    pre = _pretrained()
    a, b = _session(0, pre), _session(0, pre)
    da = a.train_phase(6.0)
    [db] = batched.train_phases_fused([b], 6.0)
    assert _leaves_equal(a.params, b.params)
    assert _leaves_equal(a.opt_state.m, b.opt_state.m)
    assert _leaves_equal(a.opt_state.v, b.opt_state.v)
    assert int(a.opt_state.count) == int(b.opt_state.count)
    assert _leaves_equal(a.u_prev, b.u_prev)
    assert np.array_equal(da.values, db.values)
    assert da.packed_mask == db.packed_mask
    assert da.total_bytes == db.total_bytes
    assert a.history == b.history


def test_fused_b4_matches_sequential_to_tolerance():
    """Four sessions stacked into one scan/vmap launch reproduce each
    session's sequential phase to float32 tolerance (vmap batches the convs
    differently, so bitwise is not expected — closeness is)."""
    pre = _pretrained()
    seqs = [_session(i, pre) for i in range(4)]
    fused = [_session(i, pre) for i in range(4)]
    for s in seqs:
        s.train_phase(6.0)
    deltas = batched.train_phases_fused(fused, 6.0, force_stack=True)
    assert all(d is not None for d in deltas)
    for s, f in zip(seqs, fused):
        assert _max_leaf_diff(s.params, f.params) < 1e-4
        # raw moments accumulate conv-reorder noise at gradient scale
        assert _max_leaf_diff(s.opt_state.m, f.opt_state.m) < 2e-3
        assert _max_leaf_diff(s.u_prev, f.u_prev) < 1e-4
        assert int(s.opt_state.count) == int(f.opt_state.count)
        assert s.phase == f.phase == 1


def test_fused_b1_force_stack_matches_to_tolerance():
    """Even B=1 pushed through the stacked executable (benchmarks do this)
    stays within float32 tolerance of the sequential loop."""
    pre = _pretrained()
    a, b = _session(1, pre), _session(1, pre)
    a.train_phase(6.0)
    [d] = batched.train_phases_fused([b], 6.0, force_stack=True)
    assert d is not None
    assert _max_leaf_diff(a.params, b.params) < 1e-4


def test_fused_commit_unstacks_in_one_launch(monkeypatch):
    """A stacked commit splits params, optimizer state and last update in
    ONE unstack launch, and each member adopts exactly its per-leaf slice
    of the stacked outputs; a singleton group never unstacks."""
    pre = _pretrained()
    fleet = [_session(i, pre) for i in range(3)]
    seen = []
    real = batched._commit_stacked

    def spy(members, t_now, out, results):
        seen.append(out[:3])
        real(members, t_now, out, results)

    monkeypatch.setattr(batched, "_commit_stacked", spy)
    batched.update_pipeline_reset()
    deltas = batched.train_phases_fused(fleet, 6.0, force_stack=True)
    assert all(d is not None for d in deltas)
    info = batched.update_pipeline_info()
    assert info["stacked_unstack_launches"] == 1
    assert info["stacked_unstack_sessions"] == 3
    [(params, opt, u)] = seen
    for j, s in enumerate(fleet):
        for got, stacked in ((s.params, params), (s.opt_state, opt),
                             (s.u_prev, u)):
            want = jax.tree.map(lambda l: l[j], stacked)
            for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                assert x.dtype == y.dtype and x.shape == y.shape
                assert np.array_equal(np.asarray(x), np.asarray(y))
    # a lone session takes the sequential path: no unstack at all
    [d] = batched.train_phases_fused([_session(5, pre)], 6.0)
    assert d is not None
    info = batched.update_pipeline_info()
    assert info["stacked_unstack_launches"] == 1
    assert info["stacked_unstack_sessions"] == 3


def test_fused_empty_buffer_yields_none_slot():
    """A session with nothing to train gets None, exactly like train_phase;
    its neighbors still train."""
    pre = _pretrained()
    full, empty = _session(0, pre), _session(1, pre, n_feed=0)
    # n_feed=0 leaves the replay buffer empty
    assert len(empty.buffer) == 0
    out = batched.train_phases_fused([empty, full], 6.0)
    assert out[0] is None and out[1] is not None
    assert empty.phase == 0 and full.phase == 1


def test_fused_mixed_keys_split_groups():
    """Sessions with different K cannot share an executable — they split
    into separate groups but all still train."""
    pre = _pretrained()
    other = AMSConfig(t_update=8.0, t_horizon=30.0, k_iters=2, batch_size=2,
                      gamma=0.05, lr=2e-3, phi_target=0.15)
    ss = [_session(0, pre), _session(1, pre),
          _session(2, pre, ams=other), _session(3, pre, ams=other)]
    out = batched.train_phases_fused(ss, 6.0, force_stack=True)
    assert all(d is not None for d in out)
    assert [s.phase for s in ss] == [1, 1, 1, 1]


def test_exec_modes_agree():
    """The scan-shaped executable (accelerator default) and the step-loop
    shape (CPU default) compute the same phase to float32 tolerance; bad
    modes are rejected."""
    pre = _pretrained()
    small = AMSConfig(t_update=8.0, t_horizon=30.0, k_iters=2, batch_size=2,
                      gamma=0.05, lr=2e-3, phi_target=0.15)
    try:
        batched.set_exec_mode("scan")
        a = [_session(i, pre, ams=small) for i in range(2)]
        batched.train_phases_fused(a, 6.0, force_stack=True)
        batched.set_exec_mode("loop")
        b = [_session(i, pre, ams=small) for i in range(2)]
        batched.train_phases_fused(b, 6.0, force_stack=True)
    finally:
        batched.set_exec_mode("auto")
    for x, y in zip(a, b):
        assert _max_leaf_diff(x.params, y.params) < 1e-5
    with pytest.raises(ValueError):
        batched.set_exec_mode("unrolled")


def test_auto_mode_races_once_and_caches_winner():
    """``auto`` settles scan-vs-loop by a one-shot timed race on the first
    real stacked batch (not the backend name): the measured winner is
    recorded per compile key, its executable cached, and every later call
    is a plain cache hit."""
    batched.cache_clear()
    pre = _pretrained()
    small = AMSConfig(t_update=8.0, t_horizon=30.0, k_iters=2, batch_size=2,
                      gamma=0.05, lr=2e-3, phi_target=0.15)
    assert batched.auto_mode_info() == {}
    batched.train_phases_fused([_session(i, pre, ams=small) for i in range(2)],
                               6.0, force_stack=True)
    decisions = batched.auto_mode_info()
    assert len(decisions) == 1
    ((backend, _), winner), = decisions.items()
    assert winner in ("scan", "loop")
    import jax as _jax
    assert backend == _jax.default_backend()
    # the race is one miss; the losing executable is not cached
    assert batched.cache_info() == {"size": 1, "hits": 0, "misses": 1}
    # second same-shaped fleet: decided key -> straight cache hit, and the
    # winner matches that mode's executable bit-for-bit
    fleet = [_session(i, pre, ams=small) for i in range(2)]
    batched.train_phases_fused(fleet, 6.0, force_stack=True)
    assert batched.cache_info() == {"size": 1, "hits": 1, "misses": 1}
    assert batched.auto_mode_info() == decisions  # no re-race
    try:
        batched.set_exec_mode(winner)
        forced = [_session(i, pre, ams=small) for i in range(2)]
        batched.train_phases_fused(forced, 6.0, force_stack=True)
    finally:
        batched.set_exec_mode("auto")
    for x, y in zip(fleet, forced):
        assert _leaves_equal(x.params, y.params)


# ---------------- executable cache ----------------


def test_phase_cache_compiles_once_for_same_shapes():
    batched.cache_clear()
    pre = _pretrained()
    batched.train_phases_fused([_session(i, pre) for i in range(3)], 6.0,
                               force_stack=True)
    info = batched.cache_info()
    assert info == {"size": 1, "hits": 0, "misses": 1}
    # a second same-shaped fleet reuses the executable
    batched.train_phases_fused([_session(i + 10, pre) for i in range(3)], 6.0,
                               force_stack=True)
    info = batched.cache_info()
    assert info["misses"] == 1 and info["hits"] == 1 and info["size"] == 1


# ---------------- run_multiclient default-path golden ----------------


def test_run_multiclient_default_kwargs_bit_for_bit():
    """The acceptance gate: with default kwargs (no fusing, 1 GPU) the shim
    reproduces the PR-2 numbers exactly — captured from the tree at the
    PR-2 commit (d38f266) before any of the fused-training changes."""
    from repro.sim.multiclient import run_multiclient

    pre = _pretrained()
    ams = AMSConfig(t_update=8.0, t_horizon=30.0, k_iters=2, batch_size=2,
                    gamma=0.05, lr=2e-3, phi_target=0.15)
    r = run_multiclient(2, pre, SEG, ams, duration=25.0,
                        video_kw=dict(height=24, width=24, fps=2.0))
    gold = {
        "mean_miou": 0.07633169618507302,
        "gpu_utilization": 0.22184800000000007,
        "phases_served": 5,
        "phases_deferred": 3,
        "mean_up_kbps": 0.456,
        "mean_down_kbps": 2.84592,
        "delta_latency_mean_s": 0.06422960000000053,
        "events_processed": 90,
        "labels_total": 40,
    }
    for k, v in gold.items():
        assert r[k] == v, (k, r[k], v)
    assert r["miou_per_client"] == [0.09255216388896606, 0.06011122848117999]
    assert r["fused_launches"] == 0 and r["fused_sessions"] == 0


# ---------------- batched teacher labeling ----------------


def test_receive_frames_batches_teacher_calls():
    """One stacked teacher launch instead of one per frame, identical
    buffer/φ outcomes."""
    calls = []

    def teacher(frames):
        calls.append(np.asarray(frames).shape[0])
        return np.asarray(frames).sum(axis=-1) > 0

    pre = _pretrained()
    task = Task(loss_and_grad=None, teacher=teacher,
                phi_loss=lambda a, b: float(np.mean(a != b)))
    s = AMSSession(task, AMS, pre, seed=0)
    frames = np.random.default_rng(0).normal(size=(5, 8, 8, 3))
    s.receive_frames(frames, 1.0)
    assert calls == [5]  # one batched call, not 5 singletons
    assert len(s.buffer) == 5
    assert s.asr.phi_ema >= 0.0  # φ ingest really ran
    s.receive_frames([], 2.0)  # empty batch: no teacher call, no crash
    assert calls == [5]
