"""Pallas TPU kernel: blocked causal/sliding-window GQA flash attention.

The student forward (and the pod-side teacher labeling pass) is dominated by
attention; this is the TPU-native analogue of the jnp chunked path in
models/attention.py.

Design (MXU/VMEM-aware, DESIGN.md §5):
  * grid = (batch, q_heads, q_blocks, kv_blocks); the kv dimension is the
    innermost, sequential ("arbitrary") axis — the online-softmax state
    (m, l, acc) lives in VMEM scratch across kv steps.
  * GQA without materializing repeated K/V: the K/V BlockSpec index_map
    folds the q-head -> kv-head mapping (h // group), so each kv head's
    tile is fetched once per group directly from HBM.
  * block sizes default to 128/128: MXU-aligned (128x128 systolic array),
    q/k/v/o tiles + scratch ~= 0.4 MB in VMEM at head_dim 128.
  * fully-masked (q_block, kv_block) pairs are skipped with @pl.when —
    causal wedges and sliding windows skip ~half / ~all-but-W/S of steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, softcap, block_q, block_k, nk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # static-shape positions; masks built per tile
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # skip tiles that are entirely masked out
    live = jnp.asarray(True)
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + block_q - 1)
    if window:
        live = jnp.logical_and(live, k_start + block_k - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (block_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (block_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(p, v)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_4d(q, k, v, *, interpret: bool, causal=True, window=0,
                       softcap=0.0, block_q=128, block_k=128):
    """q: (B, H, Sq, hd); k, v: (B, KV, Skv, hd) with H % KV == 0.
    Returns (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    group = H // KV
    bq = min(block_q, Sq)
    while Sq % bq:
        bq -= 1
    bk = min(block_k, Skv)
    while Skv % bk:
        bk -= 1
    nq, nk = Sq // bq, Skv // bk
    grid = (B, H, nq, nk)
    scale = hd**-0.5

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=bq, block_k=bk, nk=nk,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
