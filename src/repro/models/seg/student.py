"""MobileNetV2-style separable-conv segmentation student (pure JAX).

Same family as the paper's DeeplabV3+MobileNetV2 edge model (inverted
residual blocks + a lite ASPP head + bilinear upsample), scaled by `width`.
Parameter counts at ``n_classes=5``: 24,661 at `width=1.0` (the CPU
experiment size), 334,405 at 4.0, and 2,014,885 at 10.0 — the paper's
~2M-parameter operating point.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.models.common import ParamMeta, abstract_params, init_params, param_count


@dataclass(frozen=True)
class SegConfig:
    name: str = "seg-student"
    in_channels: int = 3
    n_classes: int = 5
    width: float = 1.0
    # (expansion, out_ch, stride) per inverted-residual block
    blocks: tuple = ((3, 24, 2), (3, 24, 1), (3, 32, 2), (3, 32, 1))
    stem: int = 16
    head: int = 64

    def ch(self, c: int) -> int:
        return max(8, int(round(c * self.width)))


def _conv_meta(kh, kw, cin, cout):
    return ParamMeta((kh, kw, cin, cout), ("unsharded", "unsharded", "embed", "ff"))


def _dw_meta(kh, kw, c):
    return ParamMeta((kh, kw, 1, c), ("unsharded", "unsharded", "unsharded", "ff"))


def _bn_meta(c):  # folded scale/offset pair
    return {
        "scale": ParamMeta((c,), ("unsharded",), init="zeros"),
        "bias": ParamMeta((c,), ("unsharded",), init="zeros"),
    }


def seg_metas(cfg: SegConfig) -> dict:
    m: dict = {}
    c_in = cfg.in_channels
    stem = cfg.ch(cfg.stem)
    m["stem"] = {"w": _conv_meta(3, 3, c_in, stem), "bn": _bn_meta(stem)}
    c_prev = stem
    blocks = {}
    for i, (exp, out, stride) in enumerate(cfg.blocks):
        hidden, c_out = c_prev * exp, cfg.ch(out)
        blocks[f"b{i}"] = {
            "expand": {"w": _conv_meta(1, 1, c_prev, hidden), "bn": _bn_meta(hidden)},
            "dw": {"w": _dw_meta(3, 3, hidden), "bn": _bn_meta(hidden)},
            "project": {"w": _conv_meta(1, 1, hidden, c_out), "bn": _bn_meta(c_out)},
        }
        c_prev = c_out
    m["blocks"] = blocks
    head = cfg.ch(cfg.head)
    m["aspp"] = {
        "local": {"w": _conv_meta(1, 1, c_prev, head), "bn": _bn_meta(head)},
        "ctx": {"w": _conv_meta(1, 1, c_prev, head), "bn": _bn_meta(head)},
    }
    m["classifier"] = {"w": _conv_meta(1, 1, head, cfg.n_classes),
                       "b": ParamMeta((cfg.n_classes,), ("unsharded",), init="zeros")}
    return m


def _conv(x, w, stride=1, groups=1):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=groups,
    )


def _bn_act(x, bn, act=True):
    # folded-norm affine (no running stats: online setting, tiny batches)
    mu = x.mean(axis=(0, 1, 2), keepdims=True)
    var = x.var(axis=(0, 1, 2), keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + 1e-5)
    x = x * (1.0 + bn["scale"]) + bn["bias"]
    return jnp.clip(x, 0.0, 6.0) if act else x


def seg_forward(cfg: SegConfig, params: dict, img):
    """img: (B,H,W,3) float -> logits (B,H,W,n_classes)."""
    x = img
    H, W = x.shape[1:3]
    x = _bn_act(_conv(x, params["stem"]["w"], stride=2), params["stem"]["bn"])
    c_prev = x.shape[-1]
    for i, (exp, out, stride) in enumerate(cfg.blocks):
        p = params["blocks"][f"b{i}"]
        h = _bn_act(_conv(x, p["expand"]["w"]), p["expand"]["bn"])
        h = _bn_act(_conv(h, p["dw"]["w"], stride=stride, groups=h.shape[-1]), p["dw"]["bn"])
        h = _bn_act(_conv(h, p["project"]["w"]), p["project"]["bn"], act=False)
        x = x + h if (stride == 1 and h.shape == x.shape) else h
    # lite-ASPP head: local 1x1 + global context
    loc = _bn_act(_conv(x, params["aspp"]["local"]["w"]), params["aspp"]["local"]["bn"])
    ctx = x.mean(axis=(1, 2), keepdims=True)
    ctx = _bn_act(_conv(ctx, params["aspp"]["ctx"]["w"]), params["aspp"]["ctx"]["bn"])
    h = loc + ctx
    logits = _conv(h, params["classifier"]["w"]) + params["classifier"]["b"]
    return jax.image.resize(logits, (logits.shape[0], H, W, cfg.n_classes), "bilinear")


def seg_loss(cfg: SegConfig, params: dict, img, labels):
    """Pixel cross-entropy distillation loss against teacher hard labels."""
    logits = seg_forward(cfg, params, img).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return (lse - lab).mean()


def seg_predict(cfg: SegConfig, params: dict, img):
    return jnp.argmax(seg_forward(cfg, params, img), axis=-1)


def make_student(cfg: SegConfig, rng):
    metas = seg_metas(cfg)
    params = init_params(metas, rng, jnp.float32)
    return params


def seg_param_count(cfg: SegConfig) -> int:
    return param_count(seg_metas(cfg))
