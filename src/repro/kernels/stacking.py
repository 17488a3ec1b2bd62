"""Flatten-and-concatenate plans for stacked (B, ...) pytrees.

The stacked Pallas kernels (masked-Adam, bit-pattern top-k) want each
session's parameters as ONE lane-aligned buffer — ``(B, rows, 128)`` — so a
whole fused group moves through HBM in a single grid sweep instead of one
dispatch per leaf. The flatten is reshape + concat + zero-pad (rows padded
to whole TPU tiles, `padded_rows`) and the unflatten is slice + reshape:
all bit-exact re-layouts, so a kernel output unstacks to exactly the
per-leaf arrays the tree_map path would have produced.

A `StackPlan` caches the host-side bookkeeping per shape/dtype struct —
leaf order grouped by dtype, per-leaf sizes and offsets, pad amount, row
count — so repeated launches for the same compile key re-derive nothing.
(The device-side ops are traced into the surrounding jit either way; the
plan keeps Python trace time flat at fleet scale, mirroring
`core.batched`'s executable cache.)
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128
SUBLANES = 8  # a TPU tile is (8, 128): row blocks must be multiples of 8
BLOCK_ALIGN = 64  # row alignment above BLOCK_ALIGN rows


def padded_rows(n: int) -> int:
    """Rows of the ``(rows, LANES)`` buffer that holds ``n`` values.

    A multiple of `SUBLANES`, so every row block a kernel takes is a legal
    TPU tile; above `BLOCK_ALIGN` rows a multiple of `BLOCK_ALIGN`, so a
    divisor of at least 64 rows exists (`row_block`) and no buffer falls
    back to 8-row tiles."""
    rows = -(-n // LANES)
    align = SUBLANES if rows <= BLOCK_ALIGN else BLOCK_ALIGN
    return -(-rows // align) * align


def row_block(rows: int, block_rows: int) -> int:
    """The largest multiple of `SUBLANES` that divides ``rows`` and is at
    most ``block_rows``: the row tile of a grid that covers ``rows``
    exactly."""
    if rows % SUBLANES:
        raise ValueError(f"rows must be a multiple of {SUBLANES}, got {rows}")
    br = max(min(block_rows, rows) // SUBLANES * SUBLANES, SUBLANES)
    while rows % br:
        br -= SUBLANES
    return br


class DtypeGroup(NamedTuple):
    dtype: str            # param dtype name of every leaf in the group
    indices: tuple        # leaf positions (flatten order) in the source tree
    sizes: tuple          # per-session flat size of each leaf
    offsets: tuple        # start of each leaf inside the concat buffer
    n: int                # per-session valid elements (sum of sizes)
    rows: int             # padded_rows(n) — buffer is (B, rows, LANES)


class StackPlan(NamedTuple):
    b: int                # session-axis length
    groups: tuple         # DtypeGroup per distinct leaf dtype
    shapes: tuple         # per-leaf full shapes (B first), flatten order
    treedef: object


_PLANS: dict = {}
_PLAN_HITS = 0
_PLAN_MISSES = 0


def plan_cache_info() -> dict:
    return {"size": len(_PLANS), "hits": _PLAN_HITS, "misses": _PLAN_MISSES}


def plan_cache_clear() -> None:
    global _PLAN_HITS, _PLAN_MISSES
    _PLANS.clear()
    _PLAN_HITS = _PLAN_MISSES = 0


def stack_plan(tree) -> StackPlan:
    """The (cached) flatten/concat plan for a stacked pytree whose every
    leaf carries a leading session axis B. Leaves are grouped by dtype —
    one ``(B, rows, 128)`` kernel buffer per distinct dtype — and within a
    group keep tree-flatten order, so offsets are deterministic."""
    global _PLAN_HITS, _PLAN_MISSES
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        raise ValueError("stack_plan needs at least one leaf")
    key = (treedef,
           tuple((tuple(l.shape), l.dtype.name) for l in leaves))
    plan = _PLANS.get(key)
    if plan is not None:
        _PLAN_HITS += 1
        return plan
    _PLAN_MISSES += 1
    b = int(leaves[0].shape[0])
    for l in leaves:
        if l.shape[0] != b:
            raise ValueError(
                f"inconsistent session axis: {l.shape[0]} vs {b}")
    by_dtype: dict[str, list[int]] = {}
    for i, l in enumerate(leaves):
        by_dtype.setdefault(l.dtype.name, []).append(i)
    groups = []
    for dt in sorted(by_dtype):
        idx = tuple(by_dtype[dt])
        sizes = tuple(int(np.prod(leaves[i].shape[1:])) for i in idx)
        offsets, off = [], 0
        for s in sizes:
            offsets.append(off)
            off += s
        groups.append(DtypeGroup(dt, idx, sizes, tuple(offsets), off,
                                 padded_rows(off)))
    plan = StackPlan(b, tuple(groups),
                     tuple(tuple(l.shape) for l in leaves), treedef)
    _PLANS[key] = plan
    return plan


def flatten_group(leaves, group: DtypeGroup, b: int, transform=None):
    """Concat a dtype group's leaves into the kernel buffer
    ``(B, rows, LANES)``, zero-padded past ``group.n``. ``transform`` maps
    each leaf before flattening (e.g. abs-bit-pattern for top-k); padding
    zeros are appended AFTER the transform, so a transform need only be
    elementwise."""
    parts = []
    for i in group.indices:
        l = leaves[i]
        if transform is not None:
            l = transform(l)
        parts.append(l.reshape(b, -1))
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    pad = group.rows * LANES - group.n
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat.reshape(b, group.rows, LANES)


def unflatten_group(buf, group: DtypeGroup, b: int, shapes, out=None,
                    dtype=None):
    """Inverse of `flatten_group`: slice each leaf back out of the
    ``(B, rows, LANES)`` buffer into ``out`` (a list indexed like the
    source tree's flat leaves). Padding is discarded; the round trip is
    bit-exact. ``dtype`` optionally casts every leaf (top-k thresholds
    aside, kernels emit leaves in their source dtype already)."""
    flat = buf.reshape(b, group.rows * LANES)
    out = [None] * (max(group.indices) + 1) if out is None else out
    for i, size, off in zip(group.indices, group.sizes, group.offsets):
        leaf = flat[:, off:off + size].reshape(shapes[i])
        if dtype is not None:
            leaf = leaf.astype(dtype)
        out[i] = leaf
    return out
