"""jit'd wrappers for the fused masked-Adam kernel.

`masked_adam_leaf` applies the 2-D kernel to one leaf of any shape/dtype
(pad + reshape to lane-aligned 2-D, undo afterwards). `masked_adam_stacked`
is the serving hot path: a fused grant's whole ``(params, opt_state, mask)``
stack — every leaf carrying a leading session axis B — runs as one
``pallas_call`` per distinct param dtype over flattened-and-concatenated
``(B, rows, 128)`` buffers (`repro.kernels.stacking` caches the offsets per
shape struct, so the unstack is bit-exact). The arithmetic is the same
float32 expression tree as `core.masked_adam.masked_adam_update`; outputs
agree with the XLA tree_map path to float32 rounding — XLA:CPU's
context-dependent FMA contraction moves single ULPs between compilation
contexts (it makes even the XLA path differ jit-vs-nojit), so byte
identity is asserted downstream where it actually holds: selection masks
and packed wire masks (tests/test_kernel_dispatch.py, ``ci.sh --kernels``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret, stacking
from repro.kernels.masked_adam.masked_adam import (masked_adam_2d,
                                                   masked_adam_stacked_3d)


def _to_2d(x, n_pad):
    flat = x.reshape(-1)
    if n_pad:
        flat = jnp.pad(flat, (0, n_pad))
    return flat.reshape(-1, stacking.LANES)


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "interpret"))
def masked_adam_leaf(p, g, m, v, b, bc, *, b1=0.9, b2=0.999, eps=1e-8,
                     interpret=None):
    """Fused Algorithm-2 inner update for a single parameter leaf.
    bc is the scalar lr * sqrt(1-b2^i)/(1-b1^i). Returns (p', m', v', u).
    ``interpret=None`` resolves backend-aware (interpret only on CPU)."""
    interpret = resolve_interpret(interpret)
    shape = p.shape
    n = p.size
    n_pad = stacking.padded_rows(n) * stacking.LANES - n
    args = [_to_2d(a, n_pad) for a in (p, g, m, v)]
    bmask = _to_2d(b.astype(jnp.float32), n_pad)
    bc2 = jnp.asarray(bc, jnp.float32).reshape(1, 1)
    po, mo, vo, uo = masked_adam_2d(*args, bmask, bc2, b1=b1, b2=b2, eps=eps,
                                    interpret=interpret)

    def _back(x, dtype=None):
        flat = x.reshape(-1)[:n]
        return flat.reshape(shape) if dtype is None else flat.reshape(shape).astype(dtype)

    return _back(po), _back(mo), _back(vo), _back(uo)


def masked_adam_stacked(params, grads, state, mask, *, lr=1e-3, b1=0.9,
                        b2=0.999, eps=1e-8, interpret=None):
    """One masked-Adam inner iteration for a B-stacked session group, as
    Pallas launches over concatenated leaf buffers.

    Drop-in for ``vmap(masked_adam_update)`` on stacked trees: ``params``
    / ``grads`` / ``mask`` and ``state``'s moment trees all carry a
    leading session axis; ``state.count`` is (B,) so sessions at different
    Adam step counts get their own bias correction (fed to the kernel as a
    (B,) SMEM array indexed by the session's grid step). Returns ``(params', state', u)`` with ``u``
    float32 like the tree_map path. Designed to be traced inside the
    cached phase executables (`core.batched`) — under jit the per-struct
    `stacking.stack_plan` keeps retracing flat.
    """
    interpret = resolve_interpret(interpret)
    i = state.count + 1
    i32 = i.astype(jnp.float32)
    bc = lr * jnp.sqrt(1.0 - b2 ** i32) / (1.0 - b1 ** i32)
    plan = stacking.stack_plan(params)
    b_sessions = plan.b
    bc = bc.astype(jnp.float32).reshape(b_sessions)

    leaves_p = jax.tree.leaves(params)
    leaves_g = jax.tree.leaves(grads)
    leaves_m = jax.tree.leaves(state.m)
    leaves_v = jax.tree.leaves(state.v)
    leaves_b = jax.tree.leaves(mask)
    n_leaves = len(leaves_p)
    out_p: list = [None] * n_leaves
    out_m: list = [None] * n_leaves
    out_v: list = [None] * n_leaves
    out_u: list = [None] * n_leaves
    for group in plan.groups:
        pb = stacking.flatten_group(leaves_p, group, b_sessions)
        gb = stacking.flatten_group(leaves_g, group, b_sessions)
        mb = stacking.flatten_group(leaves_m, group, b_sessions)
        vb = stacking.flatten_group(leaves_v, group, b_sessions)
        bb = stacking.flatten_group(leaves_b, group, b_sessions,
                                    transform=lambda l: l.astype(jnp.float32))
        po, mo, vo, uo = masked_adam_stacked_3d(
            pb, gb, mb, vb, bb, bc, b1=b1, b2=b2, eps=eps,
            interpret=interpret)
        stacking.unflatten_group(po, group, b_sessions, plan.shapes, out=out_p)
        stacking.unflatten_group(mo, group, b_sessions, plan.shapes, out=out_m)
        stacking.unflatten_group(vo, group, b_sessions, plan.shapes, out=out_v)
        stacking.unflatten_group(uo, group, b_sessions, plan.shapes, out=out_u)
    treedef = plan.treedef
    params_new = jax.tree.unflatten(treedef, out_p)
    m_new = jax.tree.unflatten(treedef, out_m)
    v_new = jax.tree.unflatten(treedef, out_v)
    u = jax.tree.unflatten(treedef, out_u)
    return params_new, type(state)(m_new, v_new, i), u
