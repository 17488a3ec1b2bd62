"""Pallas kernels for the serving hot path.

Three kernel families live here, each as ``<name>.py`` (the pallas_call) +
``ops.py`` (jit'd shape-polymorphic wrapper) + ``ref.py`` (pure-jnp
oracle):

* ``masked_adam`` — the fused Algorithm-2 inner update. Beyond the
  original per-leaf 2-D kernel, `ops.masked_adam_stacked` runs a whole
  fused grant's optimizer step as ONE launch: every session's pytree is
  flattened and concatenated into per-dtype ``(B, rows, 128)`` buffers
  (`repro.kernels.stacking` caches the offsets per shape struct, so the
  unstack is bit-exact) and the vmapped session axis becomes a grid
  dimension. p/g/m/v/mask move HBM→VMEM exactly once per iteration.
* ``topk_mask`` — the bit-pattern top-k threshold behind gradient-guided
  selection: 32 counting passes over the f32 bit space collapse into one
  kernel that reads each session's |u| bits ONCE in VMEM — byte-identical
  masks to `core.selection`'s exact sort-path threshold.
* ``flash_attention`` / ``rmsnorm`` — model-side kernels (pre-serving).

Dispatch: the serving executables do NOT call these directly — they go
through `core.batched.set_kernel_mode` (``"xla"`` default |
``"pallas"`` | ``"auto"``, which races both per (backend, compile key)
and caches the measured winner). See ROADMAP item 5 for how the kernels'
achieved-fraction-of-roofline lands in ``BENCH_serving.json``.

Interpret mode: the low-level kernel functions take ``interpret`` as a
required keyword; the jit'd wrappers take ``interpret=None``, resolved by
`interpret_default()`: interpret only when the default jax backend is CPU,
so an accelerator host always runs the kernels compiled. On CPU interpret
mode measures CORRECTNESS (byte-identical outputs, CI-gated via
``scripts/ci.sh`` → ``kernels_bench --kernels``), never speed.
"""
from __future__ import annotations


def interpret_default() -> bool:
    """Whether Pallas kernels run in interpret mode when the caller passed
    ``interpret=None``: yes only on a CPU default backend (there is no
    Mosaic there). Resolved at trace time — the backend does not change
    mid-process."""
    import jax

    return jax.default_backend() == "cpu"


def resolve_interpret(interpret) -> bool:
    """``interpret=None`` → backend-aware default; booleans pass through."""
    return interpret_default() if interpret is None else bool(interpret)
