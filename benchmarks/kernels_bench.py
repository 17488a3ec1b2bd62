"""Kernel microbenchmarks: fused masked-Adam Pallas kernel vs the unfused
tree_map implementation, the flash kernel vs the naive oracle, and fused
cross-session training (`core.batched`) vs the sequential phase loop.

On this CPU container the Pallas kernels run in interpret mode, so wall time
is NOT the TPU story — the derived column reports the structural win instead:
HBM bytes per parameter per iteration (fused = one pass) and attention HBM
working set (flash = O(block^2) vs naive O(S^2)). The fused-training compare
IS a real wall-clock story here: collapsing B sessions x K iterations of
dispatch into stacked launches pays off on any backend."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Timer, emit, roofline_device_kind


def fused_phase_compare(n_sessions: int = 8, k_iters: int = 20,
                        size: int = 24) -> dict:
    """Wall-clock for ``n_sessions`` seg sessions x one training phase:
    the sequential per-session ``train_phase`` loop vs one fused stacked
    launch (`core.batched.train_phases_fused`). Both paths are warmed
    (compile excluded); identical twin fleets keep the math comparable."""
    from repro.core import batched
    from repro.core.server import AMSConfig, AMSSession, Task
    from repro.data.video import VideoConfig
    from repro.models.seg.student import SegConfig, make_student
    from repro.sim.seg_world import SegWorld, phi_pixel_loss

    seg = SegConfig(n_classes=5)
    ams = AMSConfig(t_update=10.0, t_horizon=60.0, k_iters=k_iters,
                    batch_size=4, gamma=0.05, lr=2e-3, phi_target=0.15)
    pre = make_student(seg, jax.random.PRNGKey(0))

    def fleet(offset: int):
        out = []
        for i in range(n_sessions):
            world = SegWorld.make(
                VideoConfig(seed=offset + i, height=size, width=size,
                            fps=2.0, duration=30.0), seg)
            task = Task(loss_and_grad=world.loss_and_grad, teacher=None,
                        phi_loss=phi_pixel_loss)
            s = AMSSession(task, ams, jax.tree.map(lambda x: x, pre), seed=i)
            frames = np.stack([world.video.frame(j)[0] for j in range(8)])
            labels = np.stack([world.teacher.label(j) for j in range(8)])
            s.receive_labeled(frames, labels, 5.0)
            out.append(s)
        return out

    for s in fleet(500):  # warm the sequential path
        s.train_phase(6.0)
    batched.train_phases_fused(fleet(600), 6.0)  # warm the fused executable

    seq = fleet(700)
    with Timer() as t_seq:
        for s in seq:
            s.train_phase(6.0)
    fused = fleet(800)
    with Timer() as t_fused:
        batched.train_phases_fused(fused, 6.0)
    ratio = t_fused.s / max(t_seq.s, 1e-9)
    emit(f"kernels.fused_train.sequential.n{n_sessions}", t_seq.us,
         f"k={k_iters};launches={n_sessions * k_iters}")
    emit(f"kernels.fused_train.stacked.n{n_sessions}", t_fused.us,
         f"k={k_iters};ratio_vs_sequential={ratio:.3f};"
         f"cache={batched.cache_info()['size']}")
    return {"n_sessions": n_sessions, "k_iters": k_iters,
            "sequential_s": t_seq.s, "fused_s": t_fused.s, "ratio": ratio}


def update_pipeline_compare(n_sessions: int = 8, k_iters: int = 20,
                            size: int = 24) -> dict:
    """Wall-clock for ``n_sessions`` seg sessions' post-train update
    production (gradient-guided selection + wire-delta encode): the
    per-session loop — B bisection/sort launches and B leaf-by-leaf
    device->host encodes — vs the fused pipeline: ONE stacked selection
    launch + ONE batched stacked encode (`core.selection` + `core.delta`).
    Parameters enter the batched path already stacked (that is the shape a
    fused train launch leaves them in); the u_prev stack is built inside the
    timed region. Both paths are warmed (compile excluded) and the batched
    deltas are asserted byte-identical to the per-session ones."""
    from repro.core import selection
    from repro.core.batched import stack_trees
    from repro.core.delta import encode_delta, encode_delta_stack

    sessions = _update_fleet(n_sessions, k_iters, size)
    gamma = sessions[0].cfg.gamma
    u_prevs = [s.u_prev for s in sessions]
    params = [s.params for s in sessions]
    params_stacked = stack_trees(params)  # a fused grant holds them stacked

    def sequential():
        out = []
        for u, p in zip(u_prevs, params):
            mask = selection.gradient_guided_mask(u, gamma)
            out.append(encode_delta(p, mask))
        return out

    def fused():
        masks = selection.stacked_gradient_guided_masks(
            stack_trees(u_prevs), gamma)
        return encode_delta_stack(params_stacked, masks, n_sessions)

    seq_d = sequential()  # warm both paths (jit compiles excluded)
    fus_d = fused()
    identical = all(
        np.array_equal(a.values, b.values) and a.packed_mask == b.packed_mask
        and a.total_bytes == b.total_bytes for a, b in zip(seq_d, fus_d))
    assert identical, "batched update pipeline changed wire bytes"
    reps = 5
    with Timer() as t_seq:
        for _ in range(reps):
            sequential()
    with Timer() as t_fused:
        for _ in range(reps):
            fused()
    ratio = t_fused.s / max(t_seq.s, 1e-9)
    emit(f"kernels.update_pipeline.sequential.n{n_sessions}", t_seq.us / reps,
         f"launches={2 * n_sessions};bytes={sum(d.total_bytes for d in seq_d)}")
    emit(f"kernels.update_pipeline.stacked.n{n_sessions}", t_fused.us / reps,
         f"launches=2;ratio_vs_sequential={ratio:.3f};byte_identical={identical}")
    return {"n_sessions": n_sessions, "sequential_s": t_seq.s / reps,
            "fused_s": t_fused.s / reps, "ratio": ratio,
            "byte_identical": bool(identical)}


def _update_fleet(n_sessions: int, k_iters: int, size: int):
    """Seg sessions one phase in (u_prev populated) — the state the update
    pipeline runs from."""
    from repro.core.server import AMSConfig, AMSSession, Task
    from repro.data.video import VideoConfig
    from repro.models.seg.student import SegConfig, make_student
    from repro.sim.seg_world import SegWorld, phi_pixel_loss

    seg = SegConfig(n_classes=5)
    ams = AMSConfig(t_update=10.0, t_horizon=60.0, k_iters=k_iters,
                    batch_size=4, gamma=0.05, lr=2e-3, phi_target=0.15)
    pre = make_student(seg, jax.random.PRNGKey(0))
    out = []
    for i in range(n_sessions):
        world = SegWorld.make(
            VideoConfig(seed=900 + i, height=size, width=size, fps=2.0,
                        duration=30.0), seg)
        task = Task(loss_and_grad=world.loss_and_grad, teacher=None,
                    phi_loss=phi_pixel_loss)
        s = AMSSession(task, ams, jax.tree.map(lambda x: x, pre), seed=i)
        frames = np.stack([world.video.frame(j)[0] for j in range(8)])
        labels = np.stack([world.teacher.label(j) for j in range(8)])
        s.receive_labeled(frames, labels, 5.0)
        s.train_phase(6.0)
        out.append(s)
    return out


def _f16_ulp_diff(a, b) -> int:
    """Max ULP distance between two float16 arrays (0 = byte-identical)."""
    def lex(x):
        u = np.asarray(x, np.float16).reshape(-1).view(np.uint16).astype(np.int32)
        return np.where(u >= 0x8000, 0x8000 - u, u)  # monotone in value
    la, lb = lex(a), lex(b)
    return int(np.max(np.abs(la - lb))) if la.size else 0


def kernel_equivalence_gate(n_sessions: int = 4, k_iters: int = 3,
                            size: int = 24) -> dict:
    """Serving-level XLA-vs-Pallas contract, asserted on the REAL fused
    path: identical twin seg fleets run two fused phases under
    ``kernel_mode("xla")`` and ``kernel_mode("pallas")``; the streamed
    wire deltas must carry byte-identical packed masks (selection is an
    exact integer search in both engines) and fp16 values within 1 ULP
    (the residue of XLA:CPU's context-dependent FMA contraction, which
    makes even the XLA path differ jit-vs-nojit — see
    `core.batched._build_phase_fn`)."""
    from repro.core import batched, kernel_dispatch, selection

    def run_mode(kern):
        batched.cache_clear()
        selection.stacked_cache_clear()
        kernel_dispatch.reset()
        batched.set_kernel_mode(kern)
        try:
            ss = _update_fleet(n_sessions, k_iters, size)
            r1 = batched.train_phases_fused(ss, 8.0, force_stack=True)
            r2 = batched.train_phases_fused(ss, 12.0, force_stack=True)
        finally:
            batched.set_kernel_mode("xla")
        return r1 + r2

    dx, dp = run_mode("xla"), run_mode("pallas")
    masks_ok = all(a.packed_mask == b.packed_mask for a, b in zip(dx, dp))
    assert masks_ok, "pallas kernel changed a streamed wire mask"
    max_ulp = max(_f16_ulp_diff(a.values, b.values) for a, b in zip(dx, dp))
    assert max_ulp <= 1, (
        f"pallas wire-delta values drifted {max_ulp} f16 ULP (>1) from XLA")
    n_identical = sum(np.array_equal(np.asarray(a.values),
                                     np.asarray(b.values))
                      for a, b in zip(dx, dp))
    emit(f"kernels.gate.equivalence.n{n_sessions}", 0.0,
         f"deltas={len(dx)};masks_byte_identical={masks_ok};"
         f"values_max_ulp={max_ulp};values_identical={n_identical}/{len(dx)}")
    return {"n_deltas": len(dx), "masks_byte_identical": bool(masks_ok),
            "values_max_f16_ulp": max_ulp,
            "values_byte_identical": int(n_identical)}


def kernel_roofline_compare(b: int = 4, n: int = 1 << 16) -> dict:
    """Standalone stacked-kernel timings vs their analytic HBM bounds.

    Times the fused Pallas masked-Adam and bit-pattern top-k against their
    XLA references on a synthetic B-stacked tree, reports each engine's
    achieved fraction of the memory roofline
    (`roofline.analysis.kernel_roofline_fraction` over
    `adam_step_hbm_bytes` / `topk_hbm_bytes`), and asserts the top-k masks
    are byte-identical. Interpret-mode wall-clock is not the TPU story —
    the fractions quantify the structural bytes story either way."""
    import functools
    import math

    from repro.core import selection
    from repro.core.batched import stack_trees
    from repro.core.masked_adam import init_state, masked_adam_update
    from repro.kernels.masked_adam.ops import masked_adam_stacked
    from repro.kernels.topk_mask import stacked_topk_masks
    from repro.roofline import analysis

    rng = np.random.default_rng(7)
    kind = roofline_device_kind()

    def fraction(nbytes, seconds):
        if kind is None:
            return None  # no published peaks for this device: not measured
        return analysis.kernel_roofline_fraction(nbytes, seconds,
                                                 device_kind=kind)

    def one_tree():
        return {"w": jnp.asarray(rng.normal(size=(n - 300,)), jnp.float32),
                "b": jnp.asarray(rng.normal(size=(300,)), jnp.float32)}

    trees = [one_tree() for _ in range(b)]
    grads = [one_tree() for _ in range(b)]
    masks = [jax.tree.map(lambda l: jnp.asarray(
        rng.integers(0, 2, l.shape), bool), t) for t in trees]
    p = stack_trees(trees)
    g = stack_trees(grads)
    m = stack_trees(masks)
    st = stack_trees([init_state(t) for t in trees])

    xla_adam = jax.jit(jax.vmap(lambda p_, g_, s_, m_: masked_adam_update(
        p_, g_, s_, m_)))
    pal_adam = jax.jit(functools.partial(masked_adam_stacked,
                                         lr=1e-3, b1=0.9, b2=0.999, eps=1e-8))
    reps = 3
    out = {}
    adam_nbytes = b * analysis.adam_step_hbm_bytes(n)
    times = {}
    for name, fn in (("xla", xla_adam), ("pallas", pal_adam)):
        jax.block_until_ready(jax.tree.leaves(fn(p, g, st, m))[0])  # warm
        with Timer() as t:
            for _ in range(reps):
                o = fn(p, g, st, m)
            jax.block_until_ready(jax.tree.leaves(o)[0])
        times[name] = t.s / reps
    out["adam"] = {
        "b": b, "n_per_session": n, "nbytes": adam_nbytes,
        "xla_s": times["xla"], "pallas_s": times["pallas"],
        "ratio": times["pallas"] / max(times["xla"], 1e-12),
        "roofline_fraction_xla": fraction(adam_nbytes, times["xla"]),
        "roofline_fraction_pallas": fraction(adam_nbytes, times["pallas"]),
    }

    u = stack_trees([one_tree() for _ in range(b)])
    frac = 0.05
    xla_topk = jax.jit(jax.vmap(functools.partial(
        selection._bitwise_topk_body, frac=frac)))
    mx = xla_topk(u)
    mp = stacked_topk_masks(u, frac=frac)
    identical = all(np.array_equal(np.asarray(a), np.asarray(c))
                    for a, c in zip(jax.tree.leaves(mx), jax.tree.leaves(mp)))
    assert identical, "pallas top-k masks differ from the XLA counting search"
    times = {}
    for name, fn in (("xla", xla_topk),
                     ("pallas", lambda t_: stacked_topk_masks(t_, frac=frac))):
        jax.block_until_ready(jax.tree.leaves(fn(u))[0])  # warm
        with Timer() as t:
            for _ in range(reps):
                o = fn(u)
            jax.block_until_ready(jax.tree.leaves(o)[0])
        times[name] = t.s / reps
    out["topk"] = {
        "b": b, "n_per_session": n, "frac": frac,
        "masks_byte_identical": bool(identical),
        "nbytes_pallas": b * analysis.topk_hbm_bytes(n, passes=1),
        "nbytes_xla": b * analysis.topk_hbm_bytes(n, passes=32),
        "xla_s": times["xla"], "pallas_s": times["pallas"],
        "ratio": times["pallas"] / max(times["xla"], 1e-12),
        "roofline_fraction_xla": fraction(
            b * analysis.topk_hbm_bytes(n, passes=32), times["xla"]),
        "roofline_fraction_pallas": fraction(
            b * analysis.topk_hbm_bytes(n, passes=1), times["pallas"]),
    }
    for group in ("adam", "topk"):
        for field in ("roofline_fraction_xla", "roofline_fraction_pallas"):
            v = out[group][field]
            assert kind is None or (v is not None and math.isfinite(v)
                                    and v > 0), (
                f"{group}.{field} not a finite positive fraction: {v!r}")
        emit(f"kernels.gate.{group}.pallas", out[group]["pallas_s"] * 1e6,
             f"roofline_fraction={out[group]['roofline_fraction_pallas']};"
             f"ratio_vs_xla={out[group]['ratio']:.3f}")
    return out


def run_kernel_gate(quick: bool = True) -> dict:
    """The ``scripts/ci.sh --kernels`` gate: serving-level XLA-vs-Pallas
    equivalence + kernel roofline fractions + an auto-mode race, merged
    into the ``observability.kernels`` section of BENCH_serving.json (and
    re-read to assert the roofline-fraction fields landed finite)."""
    import math

    from benchmarks import serving_scale
    from repro.core import batched, kernel_dispatch, selection

    results = {"equivalence": kernel_equivalence_gate(
        n_sessions=2 if quick else 4, k_iters=2 if quick else 3,
        size=16 if quick else 24)}
    results.update(kernel_roofline_compare(b=2 if quick else 4,
                                           n=1 << (14 if quick else 16)))
    # demonstrate the dispatch race: auto mode settles select_stacked once
    kernel_dispatch.reset()
    selection.stacked_cache_clear()
    batched.set_kernel_mode("auto")
    try:
        rng = np.random.default_rng(11)
        u = {"w": jnp.asarray(rng.normal(size=(2, 4096)), jnp.float32)}
        selection.stacked_gradient_guided_masks(u, 0.05)
    finally:
        batched.set_kernel_mode("xla")
    results["dispatch"] = kernel_dispatch.kernel_dispatch_info()
    assert results["dispatch"]["auto_races"], "auto race recorded no decision"

    # merge under observability.kernels without clobbering the drift audit
    bench = serving_scale._read_bench()
    obs = bench.get("observability") or {}
    obs["kernels"] = results
    serving_scale._write_bench({"observability": obs})
    written = serving_scale._read_bench()["observability"]["kernels"]
    kind = roofline_device_kind()
    for group in ("adam", "topk"):
        for field in ("roofline_fraction_xla", "roofline_fraction_pallas"):
            v = written[group][field]
            assert (v is None if kind is None
                    else isinstance(v, float) and math.isfinite(v)), (
                f"BENCH_serving.json observability.kernels.{group}.{field} "
                f"is {v!r} on device kind {kind!r}")
    return results


def run(quick: bool = True):
    n = 1 << 18
    rng = np.random.default_rng(0)
    p, g, m = (jnp.asarray(rng.normal(size=n), jnp.float32) for _ in range(3))
    v = jnp.asarray(rng.uniform(0.01, 1, n), jnp.float32)
    b = jnp.asarray(rng.integers(0, 2, n), jnp.float32)

    from repro.core.masked_adam import init_state, masked_adam_update
    from repro.kernels.masked_adam.ops import masked_adam_leaf

    tree = {"w": p}
    st = init_state(tree)
    mask = {"w": b}

    @jax.jit
    def unfused(tree, st, mask, grads):
        return masked_adam_update(tree, grads, st, mask)

    unfused(tree, st, mask, {"w": g})  # warm
    with Timer() as t1:
        for _ in range(5):
            out = unfused(tree, st, mask, {"w": g})
        jax.block_until_ready(out[0]["w"])
    # fused kernel (interpret mode)
    bc = jnp.float32(1e-3)
    masked_adam_leaf(p, g, m, v, b, bc)  # warm
    with Timer() as t2:
        for _ in range(5):
            o = masked_adam_leaf(p, g, m, v, b, bc)
        jax.block_until_ready(o[0])
    # structural: unfused XLA emits ~10 elementwise HLO ops -> >= 2 extra
    # round-trips without fusion; fused kernel = 6 reads + 4 writes exactly.
    emit("kernels.masked_adam.unfused", t1.us / 5, "hbm_passes=variable(XLA fusion)")
    emit("kernels.masked_adam.fused_pallas_interp", t2.us / 5,
         "hbm_bytes_per_param=40(6r+4w fixed)")

    from repro.kernels.flash_attention.ops import flash_attention_pallas
    from repro.kernels.flash_attention.ref import flash_attention_ref

    B, S, KV, G, hd = 1, 512, 2, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, KV, G, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    vv = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    q4 = q.transpose(0, 2, 3, 1, 4).reshape(B, KV * G, S, hd)
    ref = jax.jit(lambda a, b_, c: flash_attention_ref(a, b_, c))
    ref(q4, k.transpose(0, 2, 1, 3), vv.transpose(0, 2, 1, 3))
    with Timer() as t3:
        o = ref(q4, k.transpose(0, 2, 1, 3), vv.transpose(0, 2, 1, 3))
        jax.block_until_ready(o)
    with Timer() as t4:
        o = flash_attention_pallas(q, k, vv, block_q=128, block_k=128)
        jax.block_until_ready(o)
    naive_ws = S * S * KV * G * 4
    flash_ws = 128 * 128 * 4 * 2
    emit("kernels.flash.naive", t3.us, f"score_bytes={naive_ws}")
    emit("kernels.flash.pallas_interp", t4.us,
         f"vmem_tile_bytes={flash_ws};skip_blocks=causal/window")

    fused_phase_compare(n_sessions=4 if quick else 8)
    update_pipeline_compare(n_sessions=4 if quick else 8)


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    if "--kernels" in sys.argv[1:]:
        run_kernel_gate(quick="--full" not in sys.argv[1:])
    else:
        run()
