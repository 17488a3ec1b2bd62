"""jit'd wrapper: stacked bit-pattern top-k masks from one kernel launch.

The serving entry point is `core.selection.stacked_gradient_guided_masks`
with ``kernel_mode("pallas")`` — it calls `stacked_topk_masks` here, which
flattens a B-stacked |u| tree into one lane-aligned uint32 bit buffer
(`repro.kernels.stacking` plan, cached per struct), launches the per-session
threshold kernel, and materializes the masks with the same ``|u| >= thr``
jnp comparison the XLA path uses — byte-identical masks, one HBM read of
the bit buffer instead of 32."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret, stacking
from repro.kernels.topk_mask.topk_mask import (PALLAS_TOPK_MAX_PER_SESSION,
                                               topk_threshold_bits_3d)


def _abs_bits(l):
    return jax.lax.bitcast_convert_type(
        jnp.abs(l.astype(jnp.float32)).reshape(l.shape[0], -1), jnp.uint32)


@functools.partial(jax.jit, static_argnames=("frac", "interpret"))
def stacked_topk_masks(u_stacked, *, frac: float, interpret=None):
    """Per-session gradient-guided masks for a B-stacked update tree.

    Matches ``vmap(core.selection._bitwise_topk_body)`` byte-for-byte:
    same exact threshold (the kernel reproduces the 32-pass counting
    search bit-for-bit, zero padding never counts), same mask comparison
    (float ``>=`` on the original leaves, so NaN/denormal/zero semantics
    are untouched). ``frac`` static per executable — one γ per fused
    group. Returns the stacked bool mask tree."""
    interpret = resolve_interpret(interpret)
    leaves = jax.tree.leaves(u_stacked)
    plan = stacking.stack_plan(
        jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32),
                     u_stacked))
    b = plan.b
    n = sum(g.n for g in plan.groups)
    k = max(int(frac * n), 1)
    # |u| bits for every leaf, concatenated across ALL groups in plan
    # order (the source tree may mix dtypes; bits are uniformly uint32)
    parts = []
    for group in plan.groups:
        for i in group.indices:
            parts.append(_abs_bits(leaves[i]))
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    pad = stacking.padded_rows(n) * stacking.LANES - n
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    bits = flat.reshape(b, -1, stacking.LANES)
    thr_bits = topk_threshold_bits_3d(bits, k, interpret=interpret)
    thr = jax.lax.bitcast_convert_type(thr_bits, jnp.float32)

    def leaf_mask(l):
        t = thr.reshape((b,) + (1,) * (l.ndim - 1))
        return jnp.abs(l.astype(jnp.float32)) >= t

    return jax.tree.map(leaf_mask, u_stacked)


def pallas_topk_supported(per_session: int) -> bool:
    """Whether one session's coordinates fit the single-block kernel's
    VMEM budget (the dispatch layer's fallback test)."""
    return per_session <= PALLAS_TOPK_MAX_PER_SESSION
