"""Coordinate-subset selection strategies (paper §3.1.2 + Table 3).

`gradient_guided` implements the paper's method: pick the γ-fraction of
coordinates with the largest |u_{n-1}| (last Adam update of the previous
phase). The γ-quantile threshold is found by *bisection over per-leaf counts*
rather than a global sort — O(log(range)) passes of O(N) reductions, exactly
shardable under pjit, and scales to 4e11-parameter pytrees where a global
sort/concat is infeasible (DESIGN.md §5, hardware adaptation).

Also provides the Table-3 ablation strategies: random, first layers, last
layers, first&last.
"""
from __future__ import annotations

import functools
import math
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kernel_dispatch, timing


def tree_size(tree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# gradient-guided (the paper's strategy)
# ---------------------------------------------------------------------------


def _count_above(tree, thr) -> jax.Array:
    return sum(jnp.sum(jnp.abs(l.astype(jnp.float32)) > thr) for l in jax.tree.leaves(tree))


def global_threshold(tree, frac: float, iters: int = 32) -> jax.Array:
    """Bisection for t with |{x : |x| > t}| ~= frac * N. jit-friendly."""
    n_target = jnp.asarray(frac * tree_size(tree), jnp.float32)
    hi = jnp.maximum(
        jnp.stack([jnp.max(jnp.abs(l.astype(jnp.float32))) for l in jax.tree.leaves(tree)]).max(),
        1e-20,
    )
    lo = jnp.zeros(())

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = _count_above(tree, mid).astype(jnp.float32)
        # too many above -> raise threshold
        return jnp.where(cnt > n_target, mid, lo), jnp.where(cnt > n_target, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return 0.5 * (lo + hi)


_SMALL = 20_000_000  # below this, exact concat-quantile beats bisection


def _mask_small_body(u_tree, frac: float):
    flat = jnp.concatenate([jnp.abs(l.astype(jnp.float32)).reshape(-1)
                            for l in jax.tree.leaves(u_tree)])
    k = max(int(frac * flat.size), 1)
    thr = jnp.sort(flat)[flat.size - k]
    return jax.tree.map(lambda u: (jnp.abs(u.astype(jnp.float32)) >= thr), u_tree)


def _mask_large_body(u_tree, frac: float):
    thr = global_threshold(u_tree, frac)
    return jax.tree.map(lambda u: (jnp.abs(u.astype(jnp.float32)) > thr), u_tree)


_mask_small = jax.jit(_mask_small_body, static_argnames=("frac",))
_mask_large = jax.jit(_mask_large_body, static_argnames=("frac",))


def gradient_guided_mask(u_tree, frac: float):
    """Mask of the γ-fraction largest-|u| coordinates (paper Alg. 2 line 1).

    Small pytrees: exact global top-k threshold via one sort. Large pytrees
    (sharded, up to 4e11 params): bisection over per-leaf counts — no concat,
    no sort, log2(range) all-reduce-sized passes."""
    body = _mask_small if tree_size(u_tree) <= _SMALL else _mask_large
    if not timing.enabled():
        return body(u_tree, frac)
    key = _stack_key(u_tree, frac)
    first = key not in _SOLO_SEEN
    _SOLO_SEEN.add(key)
    with timing.span("select_solo", first=first):
        out = body(u_tree, frac)
        timing.block(out)
    return out


# shapes already selected on, so the first jit compile of a solo selection
# (per shape/γ) is attributed to the compile bucket, not steady-state
_SOLO_SEEN: set = set()


# ---------------------------------------------------------------------------
# stacked selection (fused post-train update pipeline)
# ---------------------------------------------------------------------------

# One cached executable per (shape/dtype struct, γ, path): B co-resident
# sessions' gradient-guided selections run as ONE vmapped launch over the
# leading session axis instead of B separate bisection/sort dispatches —
# same compile-key cache pattern as `core.batched`'s phase executables.
_STACK_CACHE: dict = {}
_STACK_HITS = 0
_STACK_MISSES = 0


def stacked_cache_info() -> dict:
    """Hook for tests/telemetry: how often did fused grants share a stacked
    selection executable?"""
    return {"size": len(_STACK_CACHE), "hits": _STACK_HITS,
            "misses": _STACK_MISSES}


def stacked_cache_clear() -> None:
    global _STACK_HITS, _STACK_MISSES
    _STACK_CACHE.clear()
    _STACK_HITS = _STACK_MISSES = 0


def _stack_key(tree, frac: float):
    leaves, treedef = jax.tree.flatten(tree)
    return (treedef,
            tuple((tuple(l.shape), l.dtype.name) for l in leaves),
            float(frac))


def _bitwise_topk_body(u_tree, frac: float):
    """Exact sort-path threshold without the sort.

    Non-negative float32s order exactly as their unsigned bit patterns, so
    the k-th largest |u| is found by binary search over the 32-bit space:
    32 unrolled counting passes (compare + reduce, fully vectorized) replace
    the XLA sort that dominated a selection launch on CPU. The resulting
    threshold is the *exact* value ``sort(|u|)[N-k]``, so the `>= thr` masks
    are bit-identical to `_mask_small_body`'s — this is an implementation
    swap, not a numerics change."""
    leaves = jax.tree.leaves(u_tree)
    n = sum(int(np.prod(l.shape)) for l in leaves)
    k = max(int(frac * n), 1)
    bits = [jax.lax.bitcast_convert_type(
        jnp.abs(l.astype(jnp.float32)).reshape(-1), jnp.uint32)
        for l in leaves]
    thr_bits = jnp.uint32(0)
    for bit in range(31, -1, -1):
        cand = thr_bits | jnp.uint32(1 << bit)
        cnt = sum(jnp.sum(b >= cand) for b in bits)
        thr_bits = jnp.where(cnt >= k, cand, thr_bits)
    thr = jax.lax.bitcast_convert_type(thr_bits, jnp.float32)
    return jax.tree.map(
        lambda u: (jnp.abs(u.astype(jnp.float32)) >= thr), u_tree)


def _make_stack_fn(per_session: int, frac: float, kern: str):
    """One stacked-selection executable: ``kern`` picks the top-k engine.

    Large trees always take the vmapped bisection regardless of ``kern``
    (the bit-pattern search concatenates, which is exactly what the large
    path exists to avoid)."""
    if per_session > _SMALL:
        return jax.jit(jax.vmap(functools.partial(_mask_large_body,
                                                  frac=frac)))
    if kern == "pallas":
        from repro.kernels.topk_mask import stacked_topk_masks
        return functools.partial(stacked_topk_masks, frac=frac)
    return jax.jit(jax.vmap(functools.partial(_bitwise_topk_body,
                                              frac=frac)))


def _resolved_select_kernel(per_session: int, base_key) -> str | None:
    """``xla`` | ``pallas`` for the stacked selection, or None when
    ``kernel_mode("auto")`` still owes this (backend, struct key) a race.
    Sessions too large for the single-block kernel's VMEM budget (or on
    the bisection path entirely) are pinned to ``xla``."""
    if per_session > _SMALL:
        return "xla"
    from repro.kernels.topk_mask import pallas_topk_supported
    if not pallas_topk_supported(per_session):
        return "xla"
    km = kernel_dispatch.kernel_mode()
    if km != "auto":
        return km
    return kernel_dispatch.auto_winner("select_stacked",
                                       jax.default_backend(), base_key)


def _select_nbytes(b: int, per_session: int) -> int:
    """Analytic minimum HBM traffic for the stacked selection: one f32
    read of every |u| coordinate plus one bool mask write — what the
    fused kernel achieves; the 32-pass XLA lowering re-reads the buffer
    per pass (`roofline.analysis.topk_hbm_bytes` models both)."""
    return b * per_session * 5


def stacked_gradient_guided_masks(u_stacked, frac: float):
    """Per-session gradient-guided masks for a B-stacked update tree, in one
    launch.

    ``u_stacked`` is ``stack_trees([u_1, ..., u_B])``: every leaf carries a
    leading session axis. The per-session selection is vmapped over that
    axis, so the B thresholds and the B mask trees come out of ONE cached
    executable — session b's slice matches
    ``gradient_guided_mask(u_b, frac)``. Small trees take the bit-pattern
    top-k search: under ``kernel_mode("xla")`` the 32 unrolled counting
    passes of `_bitwise_topk_body`; under ``pallas`` the fused
    `repro.kernels.topk_mask` kernel that runs all 32 passes in VMEM off
    ONE HBM read; ``auto`` races the two once per (backend, struct key)
    and caches the measured winner (`core.kernel_dispatch`). All paths
    produce byte-identical masks — the kernel reproduces the exact
    counting search and the masks use the same float compare. Large trees
    vmap the same per-leaf bisection the solo path runs. Returns the
    stacked mask tree (leading axis preserved)."""
    global _STACK_HITS, _STACK_MISSES
    leaves = jax.tree.leaves(u_stacked)
    if not leaves:
        raise ValueError("stacked selection needs at least one leaf")
    per_session = sum(int(np.prod(l.shape[1:])) for l in leaves)
    b = int(leaves[0].shape[0])
    base = _stack_key(u_stacked, frac)
    kern = _resolved_select_kernel(per_session, base)
    if kern is not None:
        key = base + (kern,)
        fn = _STACK_CACHE.get(key)
        first = fn is None
        if first:
            _STACK_MISSES += 1
            fn = _make_stack_fn(per_session, frac, kern)
            _STACK_CACHE[key] = fn
        else:
            _STACK_HITS += 1
        if not timing.enabled():
            return fn(u_stacked)
        with timing.span("select_stacked", first=first, key=(b,),
                         nbytes=_select_nbytes(b, per_session)):
            out = fn(u_stacked)
            timing.block(out)
        return out
    # kernel_mode("auto"), undecided: race XLA vs Pallas on this real
    # batch — byte-identical outputs make the race numerics-free; one
    # cache miss, loser discarded uncounted (mirrors `batched`'s races)
    _STACK_MISSES += 1
    outs, times = {}, {}
    for kn in ("xla", "pallas"):
        fn = _STACK_CACHE.get(base + (kn,))
        if fn is None:
            fn = _make_stack_fn(per_session, frac, kn)
        timing.block(fn(u_stacked))  # compile + warm, off the clock
        best = float("inf")
        out = None
        for _ in range(2):  # best-of-2: damp scheduler/GC jitter
            t0 = time.perf_counter()
            out = fn(u_stacked)
            timing.block(out)
            best = min(best, time.perf_counter() - t0)
        times[kn], outs[kn] = best, (fn, out)
    winner = min(times, key=lambda kn: (times[kn], kn))
    kernel_dispatch.record_auto("select_stacked", jax.default_backend(),
                                base, winner, times)
    _STACK_CACHE[base + (winner,)] = outs[winner][0]
    if timing.enabled():
        timing.record("select_stacked", times[winner], first=True, key=(b,),
                      nbytes=_select_nbytes(b, per_session))
    return outs[winner][1]


# ---------------------------------------------------------------------------
# ablation strategies (Table 3)
# ---------------------------------------------------------------------------


def random_mask(rng, params, frac: float):
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(rng, len(leaves))
    masks = [jax.random.bernoulli(k, frac, l.shape) for k, l in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, masks)


def _positional_mask(params, frac: float, *, reverse: bool):
    """Select whole leaves in flattened traversal order until γN params are
    covered (partial fill on the boundary leaf). Host-side, numpy."""
    leaves, treedef = jax.tree.flatten(params)
    order = list(range(len(leaves)))
    if reverse:
        order = order[::-1]
    budget = int(frac * sum(int(np.prod(l.shape)) for l in leaves))
    masks = [None] * len(leaves)
    for idx in order:
        n = int(np.prod(leaves[idx].shape))
        if budget >= n:
            masks[idx] = np.ones(leaves[idx].shape, bool)
            budget -= n
        elif budget > 0:
            flat = np.zeros(n, bool)
            flat[:budget] = True
            masks[idx] = flat.reshape(leaves[idx].shape)
            budget = 0
        else:
            masks[idx] = np.zeros(leaves[idx].shape, bool)
    return jax.tree.unflatten(treedef, [jnp.asarray(m) for m in masks])


def first_layers_mask(params, frac: float):
    return _positional_mask(params, frac, reverse=False)


def last_layers_mask(params, frac: float):
    return _positional_mask(params, frac, reverse=True)


def first_last_mask(params, frac: float):
    a = _positional_mask(params, frac / 2, reverse=False)
    b = _positional_mask(params, frac / 2, reverse=True)
    return jax.tree.map(jnp.logical_or, a, b)


def make_mask(strategy: str, *, params=None, u_prev=None, frac: float, rng=None):
    if strategy == "gradient_guided":
        assert u_prev is not None
        return gradient_guided_mask(u_prev, frac)
    if strategy == "random":
        assert rng is not None
        return random_mask(rng, params, frac)
    if strategy == "first":
        return first_layers_mask(params, frac)
    if strategy == "last":
        return last_layers_mask(params, frac)
    if strategy == "first_last":
        return first_last_mask(params, frac)
    if strategy == "full":
        return jax.tree.map(lambda p: jnp.ones(p.shape, bool), params)
    raise ValueError(strategy)


def mask_fraction(mask) -> float:
    n = tree_size(mask)
    sel = sum(int(jnp.sum(l)) for l in jax.tree.leaves(mask))
    return sel / max(n, 1)
