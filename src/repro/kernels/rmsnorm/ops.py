"""jit'd wrapper: RMSNorm kernel over arbitrary leading dims."""
from __future__ import annotations

import functools

import jax

from repro.kernels import resolve_interpret
from repro.kernels.rmsnorm.rmsnorm import rms_norm_2d


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def rms_norm_pallas(x, w, *, eps: float = 1e-6, interpret: bool | None = None):
    """x: (..., d); w: (d,). ``interpret=None`` resolves per backend
    (`repro.kernels.interpret_default`: interpret on CPU, compiled on
    TPU)."""
    interpret = resolve_interpret(interpret)
    shape = x.shape
    out = rms_norm_2d(x.reshape(-1, shape[-1]), w, eps=eps, interpret=interpret)
    return out.reshape(shape)
