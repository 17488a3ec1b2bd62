"""The one generator every traffic mix goes through.

A mix is a JSON file of parameters under ``bench/traffic/``. Every mix is
a closed loop: each session always has an upload waiting, so each grant
is a full stack on every chip.

- ``sessions_per_fuse``: sessions per chip, as a multiple of the
  configuration's ``fuse_max``;
- ``frames_per_upload``: frames in one upload;
- ``pool_frames``: frames rendered per session at set-up; uploads cycle
  through them with fresh timestamps;
- ``objects``, ``motion_px_per_frame``, ``noise``: what a frame shows.

Frames imitate the repository's synthetic video: a drifting textured
background and moving disks and squares of ``n_classes - 1`` object
classes, with exact labels. All sessions' pools are rendered on the device
in one jitted call from the seed.
"""
from __future__ import annotations

import functools

import numpy as np

# named random streams drawn from one run seed
STREAM_FRAMES, STREAM_SESSIONS, STREAM_CHECK = 1, 3, 4


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def n_sessions(traffic: dict, fuse_max: int, chips: int) -> int:
    return int(traffic["sessions_per_fuse"]) * int(fuse_max) * int(chips)


@functools.lru_cache(maxsize=None)
def _render_fn(n_sess: int, n_frames: int, height: int, width: int,
               n_classes: int, n_objects: int):
    import jax
    import jax.numpy as jnp

    yy, xx = jnp.meshgrid(jnp.arange(height, dtype=jnp.float32),
                          jnp.arange(width, dtype=jnp.float32), indexing="ij")
    side = float(min(height, width))

    def hsv(h):
        k = jnp.stack([5.0, 3.0, 1.0])
        c = (k + h[..., None] * 6.0) % 6.0
        return 0.9 - 0.9 * 0.75 * jnp.clip(jnp.minimum(c, 4.0 - c), 0, 1)

    def frame(sess, f, key):
        drift = sess["drift"] * f
        pal = hsv((sess["hue"] + drift) % 1.0)  # (n_classes, 3)
        k = 2 * jnp.pi / 8.0
        tex = (jnp.sin(k * xx + sess["tex"][0] + 6.3 * drift)
               + jnp.sin(k * yy + sess["tex"][1] - 4.4 * drift)) / 2.0
        img = pal[0] * (0.6 + 0.4 * tex[..., None])
        lab = jnp.zeros((height, width), jnp.int32)
        ang = sess["phase"] + sess["omega"] * f
        cx = (sess["cx"] + 0.45 * side * jnp.cos(ang)) % width
        cy = (sess["cy"] + 0.45 * side * jnp.sin(ang)) % height
        for j in range(n_objects):  # largest first, smaller on top
            r = sess["radius"][j]
            disk = (xx - cx[j]) ** 2 + (yy - cy[j]) ** 2 <= r * r
            box = (jnp.abs(xx - cx[j]) <= r) & (jnp.abs(yy - cy[j]) <= r)
            inside = jnp.where(sess["square"][j], box, disk)
            c = sess["cls"][j]
            img = jnp.where(inside[..., None], pal[c], img)
            lab = jnp.where(inside, c, lab)
        img = img + sess["noise"] * jax.random.normal(key, img.shape)
        return jnp.clip(img, 0.0, 1.0), lab

    def render(sess, keys):
        per_frame = jax.vmap(frame, in_axes=(None, 0, 0))
        frames = jnp.arange(n_frames, dtype=jnp.float32)
        return jax.vmap(per_frame, in_axes=(0, None, 0))(sess, frames, keys)

    return jax.jit(render)


def render_pools(traffic: dict, n: int, frame: dict, n_classes: int,
                 seed: int):
    """(frames (n, P, H, W, 3) float32, labels (n, P, H, W) int32) on the
    host: every session's pool of ``pool_frames`` frames."""
    import jax

    g = rng(seed, STREAM_FRAMES)
    n_obj = int(traffic["objects"])
    h, w, p = int(frame["height"]), int(frame["width"]), int(
        traffic["pool_frames"])
    side = min(h, w)
    radius = -np.sort(-g.uniform(0.09, 0.22, size=(n, n_obj)), axis=1) * side
    speed = float(traffic["motion_px_per_frame"])
    sess = {
        "hue": (np.linspace(0, 1, n_classes, endpoint=False)[None]
                + g.uniform(0, 1, size=(n, 1))).astype(np.float32),
        "drift": g.uniform(0.002, 0.01, size=n).astype(np.float32),
        "tex": g.uniform(0, 2 * np.pi, size=(n, 2)).astype(np.float32),
        "phase": g.uniform(0, 2 * np.pi, size=(n, n_obj)).astype(np.float32),
        "omega": (g.uniform(0.5, 1.0, size=(n, n_obj)) * speed
                  / (0.45 * side)).astype(np.float32),
        "cx": g.uniform(0, w, size=(n, n_obj)).astype(np.float32),
        "cy": g.uniform(0, h, size=(n, n_obj)).astype(np.float32),
        "radius": radius.astype(np.float32),
        "square": g.integers(0, 2, size=(n, n_obj)).astype(bool),
        "cls": g.integers(1, n_classes, size=(n, n_obj)).astype(np.int32),
        "noise": np.full(n, float(traffic["noise"]), np.float32),
    }
    keys = jax.random.split(jax.random.PRNGKey(int(g.integers(2 ** 31))),
                            n * p).reshape(n, p, -1)
    fn = _render_fn(n, p, h, w, n_classes, n_obj)
    frames, labels = fn(sess, keys)
    return np.asarray(frames), np.asarray(labels)


def upload(pools, session: int, number: int, per_upload: int):
    """The frames and labels of a session's ``number``-th upload."""
    frames, labels = pools
    p = frames.shape[1]
    idx = [(number * per_upload + j) % p for j in range(per_upload)]
    return frames[session, idx], labels[session, idx]
