"""Pallas TPU kernel: fused masked-Adam inner update (Algorithm 2, lines 8-13).

On the server this op touches every parameter 4x per iteration (p, m, v plus
the emitted update u) — at 0 FLOP/byte it is purely HBM-bandwidth bound, so
the win is one HBM->VMEM pass with all arithmetic fused, instead of the
~10 separate elementwise HLO ops XLA emits for the unfused tree_map version.

Tiling: parameters are flattened and reshaped to (rows, 128) lanes with rows
a multiple of 8 (`kernels.stacking.padded_rows`); each grid step processes
a (block, 128) tile resident in VMEM, the block being the largest multiple
of 8 up to BLOCK_ROWS that divides the rows (6 input + 4 output tiles
~= 2.6 MB at 512 rows — comfortably under the ~16 MB VMEM/core). The
per-session bias corrections of a stacked call live in SMEM as one (B,)
array, read by the session's grid index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.stacking import LANES, row_block

BLOCK_ROWS = 512


def _adam(p_ref, g_ref, m_ref, v_ref, b_ref, bc, po_ref, mo_ref, vo_ref,
          uo_ref, *, b1, b2, eps):
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...].astype(jnp.float32) + (1.0 - b1) * g
    v = b2 * v_ref[...].astype(jnp.float32) + (1.0 - b2) * g * g
    u = bc * m / jnp.sqrt(v + eps)
    p = p_ref[...].astype(jnp.float32) - u * b_ref[...].astype(jnp.float32)
    po_ref[...] = p.astype(po_ref.dtype)
    mo_ref[...] = m.astype(mo_ref.dtype)
    vo_ref[...] = v.astype(vo_ref.dtype)
    uo_ref[...] = u.astype(uo_ref.dtype)


def _kernel_2d(p_ref, g_ref, m_ref, v_ref, b_ref, s_ref, *outs, **hp):
    # bc = lr * sqrt(1-b2^i)/(1-b1^i), precomputed on host
    _adam(p_ref, g_ref, m_ref, v_ref, b_ref, s_ref[0, 0], *outs, **hp)


def _kernel_stacked(p_ref, g_ref, m_ref, v_ref, b_ref, s_ref, *outs, **hp):
    # this grid step's session picks its own bias correction from SMEM
    _adam(p_ref, g_ref, m_ref, v_ref, b_ref, s_ref[pl.program_id(0)], *outs,
          **hp)


def _out_shapes(p, m, v):
    return (jax.ShapeDtypeStruct(p.shape, p.dtype),
            jax.ShapeDtypeStruct(m.shape, m.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(p.shape, jnp.float32))


def masked_adam_2d(p, g, m, v, b, bc, *, b1: float, b2: float, eps: float,
                   interpret: bool, block_rows: int = BLOCK_ROWS):
    """Core 2-D tiled call. All tensors (R, 128) with R a multiple of 8;
    bc: (1,1) f32."""
    br = row_block(p.shape[0], block_rows)
    tile = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    scal = pl.BlockSpec((1, 1), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel_2d, b1=b1, b2=b2, eps=eps),
        grid=(p.shape[0] // br,),
        in_specs=[tile, tile, tile, tile, tile, scal],
        out_specs=(tile, tile, tile, tile),
        out_shape=_out_shapes(p, m, v),
        interpret=interpret,
    )(p, g, m, v, b, bc)


def masked_adam_stacked_3d(p, g, m, v, b, bc, *, b1: float, b2: float,
                           eps: float, interpret: bool,
                           block_rows: int = BLOCK_ROWS):
    """Stacked-layout call for a fused grant: all tensors (B, R, 128), R a
    multiple of 8, with the vmapped session axis as the leading GRID
    dimension; bc (B,) f32 per-session bias correction (sessions in one
    stack can sit at different Adam step counts), held whole in SMEM. One
    ``pallas_call`` covers the whole group: grid (B, R/br), each step
    streaming a (1, br, 128) tile of p/g/m/v/mask through VMEM exactly once
    — the same single-HBM-pass math as `masked_adam_2d`, without a
    per-session dispatch loop."""
    B, R, _ = p.shape
    br = row_block(R, block_rows)
    tile = pl.BlockSpec((1, br, LANES), lambda s, i: (s, i, 0))
    scal = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel_stacked, b1=b1, b2=b2, eps=eps),
        grid=(B, R // br),
        in_specs=[tile, tile, tile, tile, tile, scal],
        out_specs=(tile, tile, tile, tile),
        out_shape=_out_shapes(p, m, v),
        interpret=interpret,
    )(p, g, m, v, b, bc)
