"""Mixture-of-Experts layer: top-k router + capacity-based scatter dispatch.

Dispatch avoids the classic GShard (tokens, experts, capacity) one-hot —
instead tokens are scattered into an (E, C, d) buffer via cumulative position
assignment (O(T*E) ints, no T*E*C tensor). Experts shard over the "model"
mesh axis (expert parallelism); XLA inserts the token all-to-all at the
scatter/gather boundaries.

`moe_ref` is the dense oracle (every expert on every token) used by tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, ParamMeta, dense_meta
from repro.models.layers import mlp_apply, mlp_metas


def moe_metas(cfg: ModelConfig) -> dict:
    d, E, ff = cfg.d_model, cfg.num_experts, cfg.expert_d_ff
    # expert weights get their own embed logical axis: under the §Perf
    # "moe_shard" lever it is detached from the FSDP data axis so the expert
    # matmuls contract an unsharded d (no capacity-buffer-sized all-reduces).
    m = {
        "router": ParamMeta((d, E), ("embed", "unsharded")),
        "wg": ParamMeta((E, d, ff), ("experts", "expert_embed", "expert_ff")),
        "wu": ParamMeta((E, d, ff), ("experts", "expert_embed", "expert_ff")),
        "wd": ParamMeta((E, ff, d), ("experts", "expert_ff", "expert_embed")),
    }
    if cfg.num_shared_experts:
        m["shared"] = mlp_metas(cfg, d_ff=cfg.num_shared_experts * ff)
    return m


def _act(cfg, g):
    return jax.nn.silu(g) if cfg.mlp_act == "swiglu" else jax.nn.gelu(g, approximate=True)


def _route(cfg: ModelConfig, p: dict, x_flat):
    """Returns (weights (T,k), idx (T,k), aux_loss)."""
    logits = (x_flat @ p["router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # Switch/GShard load-balance auxiliary loss.
    E = cfg.num_experts
    one_hot = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)  # primary assignment
    frac_tokens = one_hot.mean(axis=0)
    mean_probs = probs.mean(axis=0)
    aux = E * jnp.sum(frac_tokens * mean_probs)
    return weights.astype(x_flat.dtype), idx, aux


def moe_apply(cfg: ModelConfig, p: dict, x):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    B, S, d = x.shape
    k, E = cfg.experts_per_token, cfg.num_experts
    x_flat = x.reshape(B * S, d)
    T = B * S
    weights, idx, aux = _route(cfg, p, x_flat)

    cap = max(int(cfg.capacity_factor * T * k / E), 1)
    cap = min(cap, T)

    idx_f = idx.reshape(T * k)  # expert id per slot
    w_f = weights.reshape(T * k)
    # position of each slot within its expert, via cumulative count
    one_hot = (idx_f[:, None] == jnp.arange(E)[None, :]).astype(jnp.int32)  # (T*k, E)
    pos_f = (jnp.cumsum(one_hot, axis=0) * one_hot).sum(axis=-1) - 1  # (T*k,)
    keep = pos_f < cap

    tok_idx = jnp.arange(T * k) // k  # token of each slot: repeat(arange(T), k)
    contrib = x_flat[tok_idx] * keep[:, None].astype(x_flat.dtype)
    buffer = jnp.zeros((E, cap, d), x_flat.dtype)
    buffer = buffer.at[idx_f, jnp.where(keep, pos_f, cap)].add(contrib, mode="drop")

    def _ep_constrain(t):
        # §Perf "moe_shard": pin expert-parallel layout (experts over the EP
        # axis, capacity over the data axes) so GSPMD routes tokens with an
        # all-to-all instead of reducing capacity-buffer partial sums.
        if cfg.moe_ep_axis is None and cfg.moe_cap_axes is None:
            return t
        from jax.sharding import PartitionSpec as P

        spec = P(cfg.moe_ep_axis, tuple(cfg.moe_cap_axes) if cfg.moe_cap_axes else None,
                 None)
        return jax.lax.with_sharding_constraint(t, spec)

    buffer = _ep_constrain(buffer)
    h = _act(cfg, jnp.einsum("ecd,edf->ecf", buffer, p["wg"]))
    h = h * jnp.einsum("ecd,edf->ecf", buffer, p["wu"])
    h_out = _ep_constrain(jnp.einsum("ecf,efd->ecd", h, p["wd"]))

    gathered = h_out[idx_f, jnp.where(keep, pos_f, 0)]  # (T*k, d)
    gathered = gathered * (w_f * keep.astype(w_f.dtype))[:, None]
    out = gathered.reshape(T, k, d).sum(axis=1)

    if cfg.num_shared_experts:
        out = out + mlp_apply(cfg, p["shared"], x_flat)
    return out.reshape(B, S, d), aux


def moe_ref(cfg: ModelConfig, p: dict, x):
    """Dense oracle: run every expert on every token (no capacity drops)."""
    B, S, d = x.shape
    x_flat = x.reshape(B * S, d)
    weights, idx, aux = _route(cfg, p, x_flat)
    h = _act(cfg, jnp.einsum("td,edf->tef", x_flat, p["wg"]))
    h = h * jnp.einsum("td,edf->tef", x_flat, p["wu"])
    all_out = jnp.einsum("tef,efd->ted", h, p["wd"])  # (T, E, d)
    sel = jnp.take_along_axis(all_out, idx[..., None], axis=1)  # (T, k, d)
    out = (sel * weights[..., None]).sum(axis=1)
    if cfg.num_shared_experts:
        out = out + mlp_apply(cfg, p["shared"], x_flat)
    return out.reshape(B, S, d), aux
