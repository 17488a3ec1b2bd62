"""Attention: GQA/MQA/MHA with full / sliding-window / cross variants.

Projection weights keep heads factored as (kv_heads, q_per_group) so that
either factor can be tensor-parallel sharded depending on the arch/mesh
(see launch/shardings.py):

    wq: (d, KV, G, hd)   q = einsum('bsd,dkgh->bskgh')
    wk: (d, KV, hd)      k = einsum('bsd,dkh->bskh')
    wv: (d, KV, hd)
    wo: (KV, G, hd, d)

The full-sequence path is a chunked flash attention (online softmax, memory
O(q_chunk * kv_chunk)) written in pure jnp — the TPU production path swaps in
the Pallas kernel (kernels/flash_attention) behind cfg.use_pallas.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, ParamMeta
from repro.models.layers import apply_rope, softcap

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Metas
# ---------------------------------------------------------------------------


def attn_metas(cfg: ModelConfig) -> dict:
    d, kv, hd = cfg.d_model, cfg.num_kv_heads, cfg.resolved_head_dim
    g = cfg.num_heads // kv
    return {
        "wq": ParamMeta((d, kv, g, hd), ("attn_embed", "kv_heads", "qgroups", "unsharded")),
        "wk": ParamMeta((d, kv, hd), ("attn_embed", "kv_heads", "unsharded")),
        "wv": ParamMeta((d, kv, hd), ("attn_embed", "kv_heads", "unsharded")),
        "wo": ParamMeta((kv, g, hd, d), ("kv_heads", "qgroups", "unsharded", "attn_embed")),
    }


# ---------------------------------------------------------------------------
# Chunked flash attention (jnp reference/production-CPU path)
# ---------------------------------------------------------------------------


def _pick_chunk(n: int, target: int) -> int:
    c = min(target, n)
    while n % c:
        c -= 1
    return c


def flash_attention(
    q,  # (B, Sq, KV, G, hd)
    k,  # (B, Skv, KV, hd)
    v,  # (B, Skv, KV, hd)
    *,
    causal: bool = True,
    window: int = 0,
    logit_softcap: float = 0.0,
    q_offset: int = 0,  # absolute position of q[0] (for decode-style calls)
    q_chunk: int = 512,
    kv_chunk: int = 512,
    unroll: bool = False,
):
    B, Sq, KV, G, hd = q.shape
    Skv = k.shape[1]
    scale = hd**-0.5
    qc = _pick_chunk(Sq, q_chunk)
    kc = _pick_chunk(Skv, kv_chunk)
    nq, nk = Sq // qc, Skv // kc

    qr = q.reshape(B, nq, qc, KV, G, hd)
    kr = k.reshape(B, nk, kc, KV, hd)
    vr = v.reshape(B, nk, kc, KV, hd)

    q_pos = q_offset + jnp.arange(Sq).reshape(nq, qc)
    k_pos = jnp.arange(Skv).reshape(nk, kc)

    def q_chunk_body(_, qin):
        qi, qp = qin  # (B,qc,KV,G,hd), (qc,)

        def kv_step(carry, kin):
            m, l, acc = carry
            ki, vi, kp = kin  # (B,kc,KV,hd), (B,kc,KV,hd), (kc,)
            s = jnp.einsum(
                "bqkgh,bskh->bkgqs", qi, ki, preferred_element_type=jnp.float32
            ) * scale  # (B,KV,G,qc,kc)
            if logit_softcap:
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            mask = jnp.ones((qc, kc), bool)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if window:
                mask &= qp[:, None] - kp[None, :] < window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bqkgh", p, vi.astype(jnp.float32))
            acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, qc), jnp.float32)
        a0 = jnp.zeros((B, qc, KV, G, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step,
            (m0, l0, a0),
            (kr.transpose(1, 0, 2, 3, 4), vr.transpose(1, 0, 2, 3, 4), k_pos),
            unroll=unroll,
        )
        l = jnp.maximum(l, 1e-30)
        out = acc / l.transpose(0, 3, 1, 2)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(
        q_chunk_body, None, (qr.transpose(1, 0, 2, 3, 4, 5), q_pos), unroll=unroll
    )
    # outs: (nq, B, qc, KV, G, hd)
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, KV, G, hd)


def attention_ref(q, k, v, *, causal=True, window=0, logit_softcap=0.0, q_offset=0):
    """Naive O(S^2)-memory oracle used by tests."""
    B, Sq, KV, G, hd = q.shape
    Skv = k.shape[1]
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k, preferred_element_type=jnp.float32)
    s = s * (hd**-0.5)
    if logit_softcap:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    qp = q_offset + jnp.arange(Sq)
    kp = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= qp[:, None] >= kp[None, :]
    if window:
        mask &= qp[:, None] - kp[None, :] < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Block-level apply
# ---------------------------------------------------------------------------


def _proj_qkv(cfg: ModelConfig, p: dict, x, x_kv=None, positions=None, rope: bool = True):
    x_kv = x if x_kv is None else x_kv
    q = jnp.einsum("bsd,dkgh->bskgh", x, p["wq"])
    k = jnp.einsum("bsd,dkh->bskh", x_kv, p["wk"])
    v = jnp.einsum("bsd,dkh->bskh", x_kv, p["wv"])
    if rope and cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def self_attention(cfg: ModelConfig, p: dict, x, *, window: int, positions, causal=True):
    """Full-sequence self attention. Returns (out, (k, v)) — k/v feed the
    prefill KV cache."""
    q, k, v = _proj_qkv(cfg, p, x, positions=positions)
    eff_window = window
    if cfg.attn_window_override and not window:
        eff_window = cfg.attn_window_override  # long-context SWA variant
    o = flash_attention(
        q, k, v, causal=causal, window=eff_window, logit_softcap=cfg.attn_logit_softcap,
        q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk, unroll=cfg.scan_unroll,
    )
    out = jnp.einsum("bskgh,kghd->bsd", o, p["wo"])
    return out, (k, v)


def cross_attention(cfg: ModelConfig, p: dict, x, memory):
    """Cross attention onto stubbed frontend embeddings (B, Sm, d).
    No causal mask, no rope (memory has its own implicit positions)."""
    q, k, v = _proj_qkv(cfg, p, x, x_kv=memory, rope=False)
    o = flash_attention(q, k, v, causal=False, window=0, logit_softcap=cfg.attn_logit_softcap,
                        q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
                        unroll=cfg.scan_unroll)
    return jnp.einsum("bskgh,kghd->bsd", o, p["wo"])


def _cache_write(cache, new, slot):
    """Write one decode position into a (B, R, KV, hd) cache leaf.

    Under an explicit-sharding mesh the new entry carries the activations'
    batch sharding, while the cache carries its own or none at all;
    dynamic_update_slice wants both typed alike. The update takes the
    cache's sharding, or, where the cache is typed without a mesh, the
    cache takes the update's."""
    new = new.astype(cache.dtype)
    c_sh, n_sh = jax.typeof(cache).sharding, jax.typeof(new).sharding
    if c_sh != n_sh:
        if not c_sh.mesh.empty:
            new = jax.sharding.reshard(new, c_sh.spec)
        elif not n_sh.mesh.empty:
            cache = jax.sharding.reshard(cache, n_sh.spec)
    return jax.lax.dynamic_update_slice(cache, new, (0, slot, 0, 0))


def decode_self_attention(cfg: ModelConfig, p: dict, x, cache, pos, *, window: int):
    """One-token decode. x: (B,1,d); cache: dict(k,v) each (B,R,KV,hd);
    pos: scalar int32 — current position (same for the whole batch).

    §Perf hillclimb A (ring cache): when cfg.decode_window_slicing is on and
    the block is windowed, R == min(seq, window) and the cache is a ring
    buffer — slot j holds absolute position pos - ((pos - j) mod R). Reads
    are O(window) and *static* (no dynamic_slice across a sharded dim, which
    GSPMD would implement as a full-cache gather — measured and refuted in
    EXPERIMENTS.md §Perf A.1). Writes stay a single-slot DUS.
    Returns (out, new_cache)."""
    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    q, k_new, v_new = _proj_qkv(cfg, p, x, positions=positions)
    R = cache["k"].shape[1]
    eff_window = window or cfg.attn_window_override
    ring = bool(cfg.decode_window_slicing and eff_window)
    slot = pos % R if ring else pos
    k = _cache_write(cache["k"], k_new, slot)
    v = _cache_write(cache["v"], v_new, slot)
    j = jnp.arange(R)
    if ring:
        kp = pos - jnp.mod(pos - j, R)  # absolute position held by slot j
    else:
        kp = j
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k, preferred_element_type=jnp.float32)
    s = s * (cfg.resolved_head_dim**-0.5)
    if cfg.attn_logit_softcap:
        s = cfg.attn_logit_softcap * jnp.tanh(s / cfg.attn_logit_softcap)
    mask = (kp <= pos) & (kp >= 0)
    if eff_window:
        mask &= kp > pos - eff_window
    s = jnp.where(mask, s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", pr, v.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bskgh,kghd->bsd", o, p["wo"])
    return out, {"k": k, "v": v}


def decode_cross_attention(cfg: ModelConfig, p: dict, x, mem_cache):
    """Decode-time cross attention; memory K/V precomputed at prefill.
    mem_cache: dict(k,v) each (B,Sm,KV,hd)."""
    q = jnp.einsum("bsd,dkgh->bskgh", x, p["wq"])
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, mem_cache["k"], preferred_element_type=jnp.float32)
    s = s * (cfg.resolved_head_dim**-0.5)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", pr, mem_cache["v"].astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("bskgh,kghd->bsd", o, p["wo"])


def precompute_cross_cache(cfg: ModelConfig, p: dict, memory):
    k = jnp.einsum("bsd,dkh->bskh", memory, p["wk"])
    v = jnp.einsum("bsd,dkh->bskh", memory, p["wv"])
    return {"k": k, "v": v}
