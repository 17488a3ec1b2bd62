"""Kernel dispatch (`core.kernel_dispatch`) and Pallas-vs-XLA equivalence
for the serving hot-path kernels.

Contract under test (interpret mode, CPU gating set):
  * fused masked-Adam (`kernels.masked_adam.ops.masked_adam_stacked`)
    matches ``vmap(core.masked_adam.masked_adam_update)`` to float32
    rounding across dtypes and non-lane-multiple shapes (byte identity of
    the raw f32 moments is NOT promised: XLA:CPU's context-dependent FMA
    contraction moves single ULPs between compilation contexts — it makes
    even the XLA path differ jit-vs-nojit);
  * bit-pattern top-k (`kernels.topk_mask`) produces BYTE-IDENTICAL masks
    to both the XLA counting search and the solo sort path, including
    negatives, ties, denormals, and all-zero updates;
  * the dispatch layer (`batched.set_kernel_mode`) validates modes, races
    ``auto`` once per (backend, compile key), caches the winner, and
    reports decisions through `serving.obs.debug_snapshot`.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batched, kernel_dispatch, selection
from repro.core.masked_adam import init_state, masked_adam_update
from repro.kernels import interpret_default, resolve_interpret
from repro.kernels.masked_adam.ops import masked_adam_stacked
from repro.kernels.topk_mask import stacked_topk_masks
from repro.kernels.topk_mask.ref import (topk_threshold_bits_ref,
                                         topk_threshold_sort_ref)


@pytest.fixture(autouse=True)
def _clean_dispatch():
    kernel_dispatch.reset()  # mode back to "xla", race table cleared
    selection.stacked_cache_clear()
    yield
    kernel_dispatch.reset()
    selection.stacked_cache_clear()


def _assert_close(a, b, tol=2e-6):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float64),
                                   np.asarray(y, np.float64),
                                   rtol=tol, atol=tol)


def _masks_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# fused masked-Adam vs vmapped tree_map reference
# ---------------------------------------------------------------------------

# deliberately awkward shapes: nothing is a multiple of the 128-lane tile
# or the 512-row block, one leaf is smaller than a single lane row
_SHAPES = ((37, 5), (130,), (511,), (3,))


def _adam_fixture(b=3, dtypes=None, seed=0):
    rng = np.random.default_rng(seed)
    dtypes = dtypes or ["float32"] * len(_SHAPES)
    trees, grads, masks = [], [], []
    for _ in range(b):
        t, g, m = {}, {}, {}
        for j, (shape, dt) in enumerate(zip(_SHAPES, dtypes)):
            t[f"l{j}"] = jnp.asarray(rng.normal(size=shape), dt)
            g[f"l{j}"] = jnp.asarray(rng.normal(size=shape), jnp.float32)
            m[f"l{j}"] = jnp.asarray(rng.integers(0, 2, shape), bool)
        trees.append(t)
        grads.append(g)
        masks.append(m)
    return (batched.stack_trees(trees), batched.stack_trees(grads),
            batched.stack_trees([init_state(t) for t in trees]),
            batched.stack_trees(masks))


def _xla_adam(p, g, st, m, **hp):
    return jax.vmap(lambda p_, g_, s_, m_: masked_adam_update(
        p_, g_, s_, m_, **hp))(p, g, st, m)


@pytest.mark.parametrize("seed", [0, 1])
def test_masked_adam_stacked_matches_xla(seed):
    hp = dict(lr=2e-3, b1=0.9, b2=0.999, eps=1e-8)
    p, g, st, m = _adam_fixture(seed=seed)
    px, sx, ux = _xla_adam(p, g, st, m, **hp)
    pp, sp, up = masked_adam_stacked(p, g, st, m, **hp)
    _assert_close(px, pp)
    _assert_close(ux, up)
    _assert_close(sx.m, sp.m)
    _assert_close(sx.v, sp.v)
    assert np.array_equal(np.asarray(sx.count), np.asarray(sp.count))
    # masked coordinates must not move, bit for bit — the mask application
    # is p - u*mask with mask 0.0, which is exact in both engines
    for lp, lx, lm in zip(jax.tree.leaves(pp), jax.tree.leaves(p),
                          jax.tree.leaves(m)):
        frozen = ~np.asarray(lm)
        assert np.array_equal(np.asarray(lp)[frozen],
                              np.asarray(lx)[frozen])


def test_masked_adam_stacked_mixed_dtypes():
    """bf16 + f32 param leaves split into per-dtype kernel launches; the
    bf16 cast after f32 arithmetic tolerates the FMA ULP wobble."""
    hp = dict(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8)
    dtypes = ["bfloat16", "float32", "bfloat16", "float32"]
    p, g, st, m = _adam_fixture(dtypes=dtypes, seed=2)
    px, sx, ux = _xla_adam(p, g, st, m, **hp)
    pp, sp, up = masked_adam_stacked(p, g, st, m, **hp)
    for lx, lp in zip(jax.tree.leaves(px), jax.tree.leaves(pp)):
        assert lx.dtype == lp.dtype
        tol = 1e-2 if lx.dtype == jnp.bfloat16 else 2e-6
        np.testing.assert_allclose(np.asarray(lx, np.float64),
                                   np.asarray(lp, np.float64),
                                   rtol=tol, atol=tol)
    _assert_close(ux, up)  # u is always f32
    _assert_close(sx.v, sp.v)


def test_masked_adam_stacked_per_session_counts():
    """Sessions in one stack at different Adam step counts each get their
    own bias correction (the (B,) count -> per-session grid scalar)."""
    hp = dict(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8)
    p, g, st, m = _adam_fixture(seed=3)
    st = type(st)(st.m, st.v, jnp.asarray([0, 5, 40]))
    px, sx, ux = _xla_adam(p, g, st, m, **hp)
    pp, sp, up = masked_adam_stacked(p, g, st, m, **hp)
    _assert_close(px, pp)
    _assert_close(ux, up)
    assert np.array_equal(np.asarray(sp.count), np.asarray([1, 6, 41]))


def test_masked_adam_stacked_under_jit_and_grad_context():
    """Traceable inside a jitted closure (the phase-executable context)."""
    hp = dict(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8)
    p, g, st, m = _adam_fixture(seed=4)

    @jax.jit
    def step(p, g, st, m):
        return masked_adam_stacked(p, g, st, m, **hp)

    pj, sj, uj = step(p, g, st, m)
    pe, se, ue = masked_adam_stacked(p, g, st, m, **hp)
    _assert_close(pj, pe)
    _assert_close(uj, ue)


@pytest.mark.parametrize("n", [1, 128, 129, 1024, 8193, 65_537, 2_014_885])
def test_stacking_rows_are_whole_tpu_tiles(n):
    """Kernel buffers hold whole (8, 128) tiles and a row block of at
    least 64 rows divides them above 64 rows, so no Pallas block breaks the
    TPU tiling rule; padding stays under one alignment unit."""
    from repro.kernels import stacking

    rows = stacking.padded_rows(n)
    assert rows * stacking.LANES >= n
    assert rows % stacking.SUBLANES == 0
    assert rows - (-(-n // stacking.LANES)) < max(
        stacking.SUBLANES, stacking.BLOCK_ALIGN if rows > 64 else 0)
    br = stacking.row_block(rows, 512)
    assert br % stacking.SUBLANES == 0 and rows % br == 0 and br <= 512
    assert br >= min(rows, stacking.BLOCK_ALIGN)
    with pytest.raises(ValueError):
        stacking.row_block(rows + 1, 512)


# ---------------------------------------------------------------------------
# bit-pattern top-k vs sort-path / counting-search references
# ---------------------------------------------------------------------------


def _u_case(case: str, rng, b=3):
    shapes = ((57, 7), (301,))

    def leaf(shape):
        n = int(np.prod(shape))
        if case == "mixed":
            x = rng.normal(size=n)
        elif case == "negatives":
            x = -np.abs(rng.normal(size=n)) - 0.1
        elif case == "ties":
            x = rng.choice([0.5, -0.5, 2.0, -2.0, 0.0], size=n)
        elif case == "denormals":
            x = rng.normal(size=n) * 1e-41  # subnormal f32 magnitudes
        elif case == "zeros":
            x = np.zeros(n)
        else:
            raise ValueError(case)
        return jnp.asarray(x.reshape(shape), jnp.float32)

    return [{"a": leaf(shapes[0]), "b": leaf(shapes[1])} for _ in range(b)]


@pytest.mark.parametrize("case", ["mixed", "negatives", "ties", "denormals",
                                  "zeros"])
def test_stacked_topk_masks_byte_identical(case):
    rng = np.random.default_rng(5)
    frac = 0.07
    trees = _u_case(case, rng)
    stacked = batched.stack_trees(trees)
    mp = stacked_topk_masks(stacked, frac=frac)
    # vs the XLA counting search the serving path vmaps
    mx = jax.jit(jax.vmap(functools.partial(
        selection._bitwise_topk_body, frac=frac)))(stacked)
    assert _masks_equal(mp, mx), f"pallas mask != XLA counting mask ({case})"
    # vs each session's SOLO sort-path mask (the original per-session API)
    for i, t in enumerate(trees):
        solo = selection.gradient_guided_mask(t, frac)
        mine = jax.tree.map(lambda l: l[i], mp)
        assert _masks_equal(solo, mine), f"session {i} mask drifted ({case})"


def test_topk_threshold_is_exact_sort_value():
    rng = np.random.default_rng(6)
    trees = _u_case("mixed", rng, b=1)
    leaves = jax.tree.leaves(trees[0])
    n = sum(int(np.prod(l.shape)) for l in leaves)
    k = max(int(0.05 * n), 1)
    bits = topk_threshold_bits_ref(leaves, k)
    thr = float(jax.lax.bitcast_convert_type(bits, jnp.float32))
    assert thr == topk_threshold_sort_ref(leaves, k)


# ---------------------------------------------------------------------------
# dispatch layer
# ---------------------------------------------------------------------------


def test_set_kernel_mode_validates():
    with pytest.raises(ValueError):
        batched.set_kernel_mode("cuda")
    batched.set_kernel_mode("pallas")
    assert kernel_dispatch.kernel_mode() == "pallas"


def test_forced_pallas_selection_byte_identical_to_xla():
    rng = np.random.default_rng(7)
    u = {"w": jnp.asarray(rng.normal(size=(2, 2048)), jnp.float32)}
    mx = selection.stacked_gradient_guided_masks(u, 0.1)
    selection.stacked_cache_clear()
    batched.set_kernel_mode("pallas")
    mp = selection.stacked_gradient_guided_masks(u, 0.1)
    assert _masks_equal(mx, mp)


def test_auto_race_runs_once_and_caches_winner():
    rng = np.random.default_rng(8)
    u = {"w": jnp.asarray(rng.normal(size=(2, 2048)), jnp.float32)}
    batched.set_kernel_mode("auto")
    m1 = selection.stacked_gradient_guided_masks(u, 0.1)
    races = kernel_dispatch.auto_info()
    assert len(races) == 1
    (site, backend, _key), entry = next(iter(races.items()))
    assert site == "select_stacked" and backend == jax.default_backend()
    assert entry["winner"] in ("xla", "pallas")
    assert set(entry["times"]) == {"xla", "pallas"}
    assert all(t > 0 for t in entry["times"].values())
    # the race was one miss; the next call is a plain hit on the winner
    info0 = selection.stacked_cache_info()
    assert info0["misses"] == 1 and info0["hits"] == 0
    m2 = selection.stacked_gradient_guided_masks(u, 0.1)
    info1 = selection.stacked_cache_info()
    assert info1["hits"] == 1 and info1["misses"] == 1
    assert len(kernel_dispatch.auto_info()) == 1  # no re-race
    assert _masks_equal(m1, m2)


def test_kernel_dispatch_info_is_json_friendly():
    import json

    kernel_dispatch.record_auto("select_stacked", "cpu", ("k", 1), "pallas",
                                {"xla": 0.2, "pallas": 0.1})
    info = kernel_dispatch.kernel_dispatch_info()
    assert info["mode"] == "xla"
    json.dumps(info)  # must not raise
    (label, entry), = info["auto_races"].items()
    assert label.startswith("select_stacked:cpu:")
    assert entry["winner"] == "pallas"


def test_debug_snapshot_reports_kernel_dispatch():
    from repro.serving import debug_snapshot

    batched.set_kernel_mode("pallas")
    kernel_dispatch.record_auto("train_fused", "cpu", ("k",), "xla",
                                {"xla": 0.1, "pallas": 0.3})
    snap = debug_snapshot()
    assert snap["kernel_dispatch"]["mode"] == "pallas"
    assert len(snap["kernel_dispatch"]["auto_races"]) == 1


# ---------------------------------------------------------------------------
# fused phase executable: pallas kernel inside the compiled phase
# ---------------------------------------------------------------------------


def _toy_loss_and_grad(p, f, l):
    def loss_fn(p):
        pred = f @ p["w"] + p["b"]
        return jnp.mean((pred - l) ** 2)

    loss, grads = jax.value_and_grad(loss_fn)(p)
    return loss, grads


@pytest.mark.parametrize("mode", ["loop", "scan"])
def test_build_phase_fn_pallas_matches_xla(mode):
    rng = np.random.default_rng(9)
    b, k, batch, din, dout = 2, 3, 4, 7, 3
    params = batched.stack_trees([
        {"w": jnp.asarray(rng.normal(size=(din, dout)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(dout,)), jnp.float32)}
        for _ in range(b)])
    opt = batched.stack_trees([init_state(
        {"w": jnp.zeros((din, dout)), "b": jnp.zeros((dout,))})
        for _ in range(b)])
    mask = jax.tree.map(lambda x: jnp.asarray(
        rng.integers(0, 2, x.shape), bool), params)
    frames = jnp.asarray(rng.normal(size=(k, b, batch, din)), jnp.float32)
    labels = jnp.asarray(rng.normal(size=(k, b, batch, dout)), jnp.float32)
    hp = dict(lr=1e-2, b1=0.9, b2=0.999, eps=1e-8, momentum=0.0)
    outs = {}
    for kern in ("xla", "pallas"):
        fn = batched._build_phase_fn(_toy_loss_and_grad, "adam", hp["lr"],
                                     hp["b1"], hp["b2"], hp["eps"],
                                     hp["momentum"], mode, kern)
        outs[kern] = fn(params, opt, mask, frames, labels)
    px, ox, ux, lx = outs["xla"]
    pp, op, up, lp = outs["pallas"]
    _assert_close(px, pp, tol=5e-6)
    _assert_close(ux, up, tol=5e-6)
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lp), rtol=5e-6)
    assert np.array_equal(np.asarray(ox.count), np.asarray(op.count))
    # frozen coordinates are bit-frozen through the whole phase
    for l_p, l_x, l_m in zip(jax.tree.leaves(pp), jax.tree.leaves(params),
                             jax.tree.leaves(mask)):
        frozen = ~np.asarray(l_m)
        assert np.array_equal(np.asarray(l_p)[frozen],
                              np.asarray(l_x)[frozen])


# ---------------------------------------------------------------------------
# backend-aware interpret default
# ---------------------------------------------------------------------------


def test_interpret_default_backend_and_env(monkeypatch):
    """Interpret mode follows the backend and the explicit kwarg only: no
    environment variable can send a kernel to the interpreter."""
    assert interpret_default() == (jax.default_backend() == "cpu")
    for value in ("0", "1"):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", value)
        assert interpret_default() == (jax.default_backend() == "cpu")
    assert resolve_interpret(None) == (jax.default_backend() == "cpu")
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True
