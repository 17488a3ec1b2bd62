"""Pallas TPU kernel: fused RMSNorm (gemma-style (1+w) scale).

Pre-norms run 2x per block x every token; unfused XLA emits square/reduce/
rsqrt/mul chains with an HBM round-trip at the reduction. One VMEM pass:
each grid step loads a (rows, d) tile, reduces, scales, writes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 256


def _kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    w = w_ref[...].astype(jnp.float32)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * (1.0 + w)).astype(o_ref.dtype)


def rms_norm_2d(x, w, *, interpret: bool, eps: float = 1e-6,
                block_rows: int = BLOCK_ROWS):
    """x: (R, d); w: (d,). Returns (R, d)."""
    R, d = x.shape
    br = min(block_rows, R)
    while R % br:
        br -= 1
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, w)
