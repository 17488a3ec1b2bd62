"""``correct`` at a tiny size on the CPU: sound runs pass; a run with the
timed path broken underneath fails, once per fault the backlog cell can
have and for a lost stored update (the next phase's selection input); and
the control (the reference in bfloat16 put in the program's place) fails
the configuration's limits."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench_testkit import bench_modules, run, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


def _unchanged(real):
    def phase(params, opt, mask, frames, labels):
        u = jax.tree.map(jnp.zeros_like, params)
        return params, opt, u, jnp.zeros(frames.shape[1])
    return phase


def _half_batch(real):
    def phase(params, opt, mask, frames, labels):
        half = frames.shape[2] // 2
        return real(params, opt, mask, frames[:, :, :half],
                    labels[:, :, :half])
    return phase


def _update_lost(real):
    def phase(params, opt, mask, frames, labels):
        new_p, new_opt, u, loss = real(params, opt, mask, frames, labels)
        return new_p, new_opt, jax.tree.map(jnp.zeros_like, u), loss
    return phase


FAULTS = {"state_unchanged": _unchanged, "half_batch": _half_batch,
          "update_lost": _update_lost}


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["value_altered"])
def test_fault_in_the_timed_path_is_not_correct(root, fault, monkeypatch):
    from repro.core import batched

    if fault == "value_altered":
        real_encode = batched.encode_delta_stack

        def encode(*a, **k):
            deltas = real_encode(*a, **k)
            for d in deltas:
                d.values[0] += 1.0
            return deltas

        monkeypatch.setattr(batched, "encode_delta_stack", encode)
    else:
        real_fn = batched.fused_phase_fn
        monkeypatch.setattr(batched, "fused_phase_fn",
                            lambda *a, **k: FAULTS[fault](real_fn(*a, **k)))
    res = run(root, "mnv2-backlog", seed=5)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["check"].values())


def test_sound_run_is_correct(root):
    res = run(root, "mnv2-backlog", seed=5)
    assert res["correct"] is True


def test_control_fails_where_the_program_passes(root):
    with bench_modules(root):
        import calibrate

        for seed in (3, 4, 2 ** 31 + 7):
            r = calibrate.readings(str(root), "mnv2-backlog", seed, True,
                                   time.perf_counter(), require_tpu=False)
            assert r["program_ok"] is True, r
            assert r["control_ok"] is False, r
