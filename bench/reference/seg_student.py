"""Plain reference of one AMS update of the segmentation student.

Written from the published description (arXiv:2006.06628, Algorithms 1-2,
and a MobileNetV2-style student with folded batch norm, a lite ASPP head and
bilinear upsampling), in straightforward `jax.numpy`, one session at a time,
with no kernels, stacking or caches. It imports nothing of the system under
test. Given a session's state before a training phase, the K replay
minibatches the phase trained on and the AMS settings, it computes:

- the coordinate mask: the gamma-fraction of coordinates with the largest
  |u| of the previous phase (ties at the threshold included);
- K iterations of masked Adam (moments over all coordinates, only masked
  coordinates move), with the loss of each iteration;
- the parameters, moments and last update after the phase.

``dtype=float32`` runs every convolution and contraction at ``HIGHEST``
precision: the reference. ``dtype=bfloat16`` holds parameters, activations
and optimizer state in bfloat16 at default precision: the control, which
the comparison has to refuse.
"""
from __future__ import annotations

import gzip

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _ch(student: dict, c: int) -> int:
    return max(8, int(round(c * student["width"])))


def param_shapes(student: dict) -> dict:
    """The student's parameter layout: name -> shape (HWIO convolutions,
    folded-norm scale/bias pairs)."""
    shapes = {}

    def conv(name, kh, kw, cin, cout):
        shapes[f"{name}/w"] = (kh, kw, cin, cout)
        shapes[f"{name}/bn/scale"] = (cout,)
        shapes[f"{name}/bn/bias"] = (cout,)

    stem = _ch(student, student["stem"])
    conv("stem", 3, 3, student["in_channels"], stem)
    c_prev = stem
    for i, (exp, out, _stride) in enumerate(student["blocks"]):
        hidden, c_out = c_prev * exp, _ch(student, out)
        conv(f"blocks/b{i}/expand", 1, 1, c_prev, hidden)
        conv(f"blocks/b{i}/dw", 3, 3, 1, hidden)
        conv(f"blocks/b{i}/project", 1, 1, hidden, c_out)
        c_prev = c_out
    head = _ch(student, student["head"])
    conv("aspp/local", 1, 1, c_prev, head)
    conv("aspp/ctx", 1, 1, c_prev, head)
    shapes["classifier/w"] = (1, 1, head, student["n_classes"])
    shapes["classifier/b"] = (student["n_classes"],)
    return shapes


def flatten(tree, prefix: str = "") -> dict:
    """Nested dict of arrays -> {"a/b/c": array}."""
    out = {}
    for k in sorted(tree):
        v = tree[k]
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, name + "/"))
        else:
            out[name] = v
    return out


def _precision(dtype):
    return HIGHEST if dtype == jnp.float32 else None


def _conv(x, w, stride, groups, dtype):
    # XLA's SAME padding: total max((ceil(n/s)-1)*s + k - n, 0), low half
    # rounded down
    pads = []
    for n, k in zip(x.shape[1:3], w.shape[:2]):
        out = -(-n // stride)
        total = max((out - 1) * stride + k - n, 0)
        pads.append((total // 2, total - total // 2))
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=pads,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=_precision(dtype))


def _norm(x, scale, bias, act):
    mu = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=(0, 1, 2), keepdims=True)
    y = (x - mu) / jnp.sqrt(var + 1e-5) * (1 + scale) + bias
    return jnp.minimum(jnp.maximum(y, 0), 6) if act else y


def _upsample_matrix(n_out: int, n_in: int) -> np.ndarray:
    """Bilinear interpolation at pixel centres, edges clamped."""
    pos = (np.arange(n_out) + 0.5) * (n_in / n_out) - 0.5
    pos = np.clip(pos, 0, n_in - 1)
    lo = np.floor(pos).astype(int)
    hi = np.minimum(lo + 1, n_in - 1)
    frac = pos - lo
    a = np.zeros((n_out, n_in), np.float64)
    a[np.arange(n_out), lo] += 1 - frac
    a[np.arange(n_out), hi] += frac
    return a


def forward(student: dict, p: dict, img, dtype):
    """img (N, H, W, 3) -> logits (N, H, W, n_classes); ``p`` is flat."""
    x = img.astype(dtype)

    def block(x, name, stride=1, groups=1, act=True):
        y = _conv(x, p[f"{name}/w"], stride, groups, dtype)
        return _norm(y, p[f"{name}/bn/scale"], p[f"{name}/bn/bias"], act)

    height, width = img.shape[1:3]
    x = block(x, "stem", stride=2)
    for i, (_exp, _out, stride) in enumerate(student["blocks"]):
        h = block(x, f"blocks/b{i}/expand")
        h = block(h, f"blocks/b{i}/dw", stride=stride, groups=h.shape[-1])
        h = block(h, f"blocks/b{i}/project", act=False)
        x = x + h if stride == 1 and h.shape == x.shape else h
    local = block(x, "aspp/local")
    ctx = block(jnp.mean(x, axis=(1, 2), keepdims=True), "aspp/ctx")
    h = local + ctx
    logits = _conv(h, p["classifier/w"], 1, 1, dtype) + p["classifier/b"]
    a_h = jnp.asarray(_upsample_matrix(height, logits.shape[1]), dtype)
    a_w = jnp.asarray(_upsample_matrix(width, logits.shape[2]), dtype)
    return jnp.einsum("hi,nijc,wj->nhwc", a_h, logits, a_w,
                      precision=_precision(dtype))


def loss(student: dict, p: dict, img, labels, dtype):
    """Mean pixel cross-entropy against hard labels."""
    logits = forward(student, p, img, dtype).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def topk_mask(u: dict, gamma: float, dtype) -> dict:
    """The gamma-fraction of coordinates with the largest |u|, over all
    leaves together; every coordinate tied with the k-th largest is in."""
    mags = {k: jnp.abs(v.astype(dtype)).astype(jnp.float32)
            for k, v in u.items()}
    flat = jnp.concatenate([m.reshape(-1) for m in mags.values()])
    k = max(int(gamma * flat.size), 1)
    thr = jnp.sort(flat)[flat.size - k]
    return {name: m >= thr for name, m in mags.items()}


def _make_step(student: dict, ams: dict, dtype):
    lr, b1, b2, eps = ams["lr"], ams["b1"], ams["b2"], ams["eps"]

    @jax.jit
    def step(p, m, v, count, mask, img, labels):
        val, g = jax.value_and_grad(
            lambda q: loss(student, q, img, labels, dtype))(p)
        i = (count + 1).astype(jnp.float32)
        corr = (lr * jnp.sqrt(1 - b2 ** i) / (1 - b1 ** i)).astype(dtype)
        new_p, new_m, new_v, u = {}, {}, {}, {}
        for k in p:
            gk = g[k].astype(dtype)
            new_m[k] = (b1 * m[k] + (1 - b1) * gk).astype(dtype)
            new_v[k] = (b2 * v[k] + (1 - b2) * gk * gk).astype(dtype)
            u[k] = (corr * new_m[k] / jnp.sqrt(new_v[k] + eps)).astype(dtype)
            new_p[k] = jnp.where(mask[k], p[k] - u[k], p[k]).astype(dtype)
        return new_p, new_m, new_v, count + 1, u, val, g

    return step


_STEPS: dict = {}


def run_phase(student: dict, ams: dict, state: dict, frames, labels,
              dtype=jnp.float32) -> dict:
    """One training phase of one session.

    ``state`` holds flat dicts ``params``, ``m``, ``v``, ``u_prev`` and the
    scalar ``count``; ``frames`` is (K, batch, H, W, 3) and ``labels``
    (K, batch, H, W). Returns the mask, the state after the phase
    (float32 copies), each iteration's loss and the per-leaf norm of the
    first iteration's gradient, and ``v_decay`` (b2 to the power K, what
    the phase leaves of the second moment it started from)."""
    key = (repr(sorted(student.items())), repr(sorted(ams.items())),
           jnp.dtype(dtype).name)
    step = _STEPS.get(key)
    if step is None:
        step = _STEPS[key] = _make_step(student, ams, dtype)
    cast = lambda t: {k: jnp.asarray(v).astype(dtype) for k, v in t.items()}  # noqa: E731
    p, m, v = cast(state["params"]), cast(state["m"]), cast(state["v"])
    count = jnp.asarray(state["count"], jnp.int32)
    mask = topk_mask(state["u_prev"], ams["gamma"], dtype)
    losses, grad_norms, u = [], None, None
    for k in range(frames.shape[0]):
        p, m, v, count, u, val, g = step(p, m, v, count, mask,
                                         jnp.asarray(frames[k]),
                                         jnp.asarray(labels[k]))
        losses.append(float(val))
        if grad_norms is None:
            grad_norms = {n: float(jnp.linalg.norm(x.astype(jnp.float32)))
                          for n, x in g.items()}
    f32 = lambda t: {k: np.asarray(x, np.float32) for k, x in t.items()}  # noqa: E731
    return {"mask": {k: np.asarray(x) for k, x in mask.items()},
            "params": f32(p), "m": f32(m), "v": f32(v), "u": f32(u),
            "losses": losses, "grad_norms": grad_norms,
            "v_decay": float(ams["b2"]) ** frames.shape[0]}


def decode_delta(values: np.ndarray, packed_mask: bytes, n_total: int):
    """The wire format: a gzip'd big-endian bit-vector over the flat
    parameter space (leaves in sorted-name order) and the masked
    coordinates' new values in that order. Returns (flat mask, values as
    float32)."""
    bits = np.unpackbits(np.frombuffer(gzip.decompress(packed_mask),
                                       np.uint8))[:n_total].astype(bool)
    vals = np.asarray(values).astype(np.float32)
    if int(bits.sum()) != vals.size:
        raise ValueError(f"wire mask marks {int(bits.sum())} coordinates "
                         f"but carries {vals.size} values")
    return bits, vals
