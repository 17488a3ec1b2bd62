"""Pallas kernel: bit-pattern top-k threshold search, one session per grid
step.

Gradient-guided selection needs the exact k-th largest |u| per session.
Non-negative float32s order exactly as their unsigned bit patterns, so the
threshold is found by binary search over the 32-bit space — the same 32
counting passes `core.selection._bitwise_topk_body` unrolls in XLA. The
XLA lowering re-reads the |u| buffer from HBM on every pass (32 x 4N
bytes); this kernel keeps each session's bit buffer resident in VMEM and
runs all 32 passes on-chip — ONE HBM read of 4N bytes per session, which
is the analytic roofline bound `roofline.analysis.topk_hbm_bytes` states.

The kernel emits only the per-session threshold BITS, broadcast over one
(8, 128) tile per session so the output block is a whole TPU tile; the
caller reads element [0, 0], bitcasts to float and materializes the
``|u| >= thr`` masks with the same jnp comparison the XLA path uses, so
the masks are byte-identical by construction (including NaN semantics,
which a bits-space ``>=`` would get wrong).

VMEM bound: one session's whole buffer is a single block, double-buffered
across the session grid, so the call raises the scoped-VMEM limit to
`VMEM_LIMIT_BYTES` (half of a v5e core's 128 MiB). Compiled for a TPU v5e
at B=4, the compiler's default limit refuses even the width-10 student
(2,014,885 coordinates, 15,744 rows) with RESOURCE_EXHAUSTED; under the
raised limit it accepts 5,603,328 coordinates (43,776 rows) and refuses
5,611,520. `PALLAS_TOPK_MAX_PER_SESSION` sits at the accepted side
(tests/test_tpu_compile.py compiles it), and the dispatch layer
(`core.selection`) falls back to the XLA path above it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.stacking import LANES, SUBLANES

VMEM_LIMIT_BYTES = 64 << 20
# per-session coordinate budget for the single-block kernel under
# VMEM_LIMIT_BYTES (see module docstring)
PALLAS_TOPK_MAX_PER_SESSION = 5_600_000


def _kernel(bits_ref, thr_ref, *, k: int):
    bits = bits_ref[...]  # (1, R, LANES) uint32 — |u| bit patterns, 0-padded
    thr = jnp.uint32(0)
    # 32 counting passes, all in VMEM: zero padding never counts (cand >= 1)
    for bit in range(31, -1, -1):
        cand = thr | jnp.uint32(1 << bit)
        cnt = jnp.sum((bits >= cand).astype(jnp.int32))
        thr = jnp.where(cnt >= k, cand, thr)
    thr_ref[...] = jnp.full(thr_ref.shape, thr, jnp.uint32)


def topk_threshold_bits_3d(bits, k: int, *, interpret: bool):
    """Per-session exact top-k threshold bits.

    ``bits``: (B, R, 128) uint32 — each session's |u| float32 bit patterns,
    flattened/concatenated and zero-padded (`repro.kernels.stacking`).
    ``k``: static per-session selection count (same for every session in a
    stack — one γ per fused group by compile-key construction). Returns
    (B,) uint32: the bit pattern of ``sort(|u|)[N-k]``, exactly."""
    B, R, _ = bits.shape
    out = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(B,),
        in_specs=[pl.BlockSpec((1, R, LANES), lambda s: (s, 0, 0))],
        out_specs=pl.BlockSpec((1, SUBLANES, LANES), lambda s: (s, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, SUBLANES, LANES), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(bits)
    return out[:, 0, 0]
