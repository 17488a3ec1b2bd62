"""Sparse model-update codec (§3.1.2, downlink payload).

A ModelDelta carries, per leaf: the new values of masked coordinates as
fp16, plus one global gzip'd bit-vector marking their positions — exactly
the paper's wire format ("it sends a bit-vector identifying the location of
the parameters... compressed [with] gzip").
"""
from __future__ import annotations

import gzip
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import timing


@dataclass
class ModelDelta:
    values: np.ndarray  # concatenated masked values (value_dtype)
    packed_mask: bytes  # gzip'd packed bit-vector over the flat param space
    n_total: int  # total parameter count (for unpacking)
    value_dtype: str = "float16"

    # --- wire accounting -------------------------------------------------
    @property
    def value_bytes(self) -> int:
        return self.values.nbytes

    @property
    def mask_bytes(self) -> int:
        return len(self.packed_mask)

    @property
    def total_bytes(self) -> int:
        return self.value_bytes + self.mask_bytes


def _flatten(tree) -> np.ndarray:
    leaves = [np.asarray(l).reshape(-1) for l in jax.tree.leaves(tree)]
    return np.concatenate(leaves) if leaves else np.zeros((0,))


# reusable flat-mask scratch: encode_delta runs once per train phase per
# session, and re-allocating an N-bool buffer (plus two full flatten/concat
# passes) per call showed up at fleet scale. Keyed by (n_total, value_dtype)
# so interleaved encodes of same-sized trees at different wire dtypes can
# never alias each other's in-flight buffer (a hazard once callers hold a
# delta across a later encode). Not thread-safe — the serving engine is
# single-threaded by construction.
_MASK_SCRATCH: dict[tuple[int, str], np.ndarray] = {}


def _pack_mask_bits(flat_m: np.ndarray) -> bytes:
    """gzip'd packed bit-vector over one flat bool mask — the wire format.

    mtime=0 pins the 4-byte gzip MTIME header field: the wire encoding is
    a pure function of the mask (same total_bytes, no wall-clock leakage)."""
    return gzip.compress(np.packbits(flat_m).tobytes(), compresslevel=6,
                         mtime=0)


def encode_delta(params_new, mask, value_dtype="float16") -> ModelDelta:
    """Single pass over paired (param, mask) leaves: masked values are
    gathered per leaf (never materializing the full flat parameter vector)
    and mask bits are written into a reused scratch buffer before packing.
    Byte-identical to the two-pass flatten/concat encoding."""
    # pure host work (asarray syncs the device): no compile split needed
    with timing.span("encode_solo") as sp:
        d = _encode_delta_impl(params_new, mask, value_dtype)
        sp.nbytes = d.total_bytes
    return d


def _encode_delta_impl(params_new, mask, value_dtype) -> ModelDelta:
    p_leaves = jax.tree.leaves(params_new)
    m_leaves = jax.tree.leaves(mask)
    n_total = sum(l.size for l in p_leaves)
    key = (n_total, str(value_dtype))
    flat_m = _MASK_SCRATCH.get(key)
    if flat_m is None or n_total == 0:
        flat_m = _MASK_SCRATCH.setdefault(key, np.empty(n_total, bool))
    picked, off = [], 0
    for p, m in zip(p_leaves, m_leaves):
        m_flat = np.asarray(m).reshape(-1).astype(bool)
        flat_m[off:off + m_flat.size] = m_flat
        picked.append(np.asarray(p).reshape(-1)[m_flat])
        off += m_flat.size
    values = (np.concatenate(picked) if picked
              else np.zeros((0,))).astype(value_dtype)
    packed = _pack_mask_bits(flat_m)
    return ModelDelta(values=values, packed_mask=packed, n_total=n_total,
                      value_dtype=value_dtype)


# ---------------------------------------------------------------------------
# batched encode (fused post-train update pipeline)
# ---------------------------------------------------------------------------

# One cached flatten/cast executable per (stacked struct, value_dtype) —
# the `core.batched` compile-key cache pattern. The executable keeps the
# masked-value cast and the mask flattening ON DEVICE for the whole stack,
# so a fused grant's B deltas cost ONE stacked device->host transfer pair
# instead of B x n_leaves leaf-by-leaf `np.asarray` pulls.
_STACK_CACHE: dict = {}
_STACK_HITS = 0
_STACK_MISSES = 0


def stack_cache_info() -> dict:
    """Hook for tests/telemetry: how often did fused grants share a stacked
    encode executable?"""
    return {"size": len(_STACK_CACHE), "hits": _STACK_HITS,
            "misses": _STACK_MISSES}


def stack_cache_clear() -> None:
    global _STACK_HITS, _STACK_MISSES
    _STACK_CACHE.clear()
    _STACK_HITS = _STACK_MISSES = 0


def _stack_flatten_fn(value_dtype: str):
    @jax.jit
    def flatten(params_stacked, mask_stacked):
        vals = jnp.concatenate(
            [l.reshape(l.shape[0], -1).astype(value_dtype)
             for l in jax.tree.leaves(params_stacked)], axis=1)
        bits = jnp.concatenate(
            [l.reshape(l.shape[0], -1).astype(bool)
             for l in jax.tree.leaves(mask_stacked)], axis=1)
        return vals, bits

    return flatten


def encode_delta_stack(params_stacked, mask_stacked, n_sessions: int,
                       value_dtype="float16") -> list[ModelDelta]:
    """B sessions' deltas from stacked trees in one device round-trip.

    ``params_stacked``/``mask_stacked`` carry a leading session axis (the
    shape a fused train launch already holds them in). The fp16 cast and the
    per-leaf flattening run on device over the whole stack, then ONE stacked
    transfer pair lands ``(B, n_total)`` values + mask bits on the host; the
    per-session gather and the gzip'd bit-vector pack reuse `encode_delta`'s
    wire format. Each returned delta is byte-identical to
    ``encode_delta(params_b, mask_b, value_dtype)`` — the cast commutes with
    the gather elementwise, so casting device-side first changes no bytes."""
    global _STACK_HITS, _STACK_MISSES
    p_leaves, treedef = jax.tree.flatten(params_stacked)
    n_total = sum(int(np.prod(l.shape[1:])) for l in p_leaves)
    key = (treedef,
           tuple((tuple(l.shape), l.dtype.name) for l in p_leaves),
           str(value_dtype))
    fn = _STACK_CACHE.get(key)
    first = fn is None
    if first:
        _STACK_MISSES += 1
        fn = _stack_flatten_fn(str(value_dtype))
        _STACK_CACHE[key] = fn
    else:
        _STACK_HITS += 1
    with timing.span("encode_stacked", first=first,
                     key=(n_sessions,)) as sp:
        vals_dev, bits_dev = fn(params_stacked, mask_stacked)
        vals = np.asarray(vals_dev)  # ONE stacked pull each, not B x leaves
        bits = np.asarray(bits_dev)
        out = []
        for b in range(n_sessions):
            flat_m = bits[b]
            out.append(ModelDelta(values=vals[b][flat_m],
                                  packed_mask=_pack_mask_bits(flat_m),
                                  n_total=n_total, value_dtype=value_dtype))
        sp.nbytes = sum(d.total_bytes for d in out)
    return out


def apply_delta(params_old, delta: ModelDelta):
    """Edge-side: overwrite masked coordinates with streamed values."""
    flat_m = np.unpackbits(
        np.frombuffer(gzip.decompress(delta.packed_mask), np.uint8)
    )[: delta.n_total].astype(bool)
    leaves, treedef = jax.tree.flatten(params_old)
    out, off_p, off_v = [], 0, 0
    vals = delta.values
    for leaf in leaves:
        n = int(np.prod(leaf.shape))
        m = flat_m[off_p : off_p + n]
        k = int(m.sum())
        flat = np.asarray(leaf).reshape(-1).copy()
        flat[m] = vals[off_v : off_v + k].astype(flat.dtype)
        out.append(jnp.asarray(flat.reshape(leaf.shape), dtype=leaf.dtype))
        off_p += n
        off_v += k
    assert off_p == delta.n_total and off_v == vals.size
    return jax.tree.unflatten(treedef, out)


def full_model_bytes(params, value_dtype="float16") -> int:
    """Wire cost of a naive full-model update (the paper's 3.2 Mbps case)."""
    return _flatten(params).astype(value_dtype).nbytes
