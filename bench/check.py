"""The comparison that decides ``correct``.

One session update that the timed window ran is compared with the plain
reference (`bench/reference`) fed the same state before the phase and the
same replay minibatches. An update is summarised as a dict of flat
float32 arrays keyed by parameter name (``params``, ``m``, ``v``, ``u``),
the flat wire mask and wire values decoded, and the loss of the phase's
last iteration; `update_from_reference` builds the same summary from a
reference run, so the lower-precision control goes through the very same
comparison.

The numbers (each a reading in which 0 is perfect agreement):

- ``mask_miss``: coordinates on which the decoded wire mask and the
  reference's top-gamma mask of the previous update differ (selection);
- ``wire_gap``: the distance between the parameters an edge device holds
  after applying the decoded delta and the reference's parameters after the
  phase, over the norm of the reference's change (encode and train);
- ``dparam_gap``: the worst leaf's gap between the program's and the
  reference's norm of the parameter change, over the larger of that leaf's
  and the median leaf's reference norm (train);
- ``loss_gap``: relative gap of the last iteration's loss (train);
- ``gsq_gap``: relative gap of the squared gradients the phase added to
  the second moment, summed over all coordinates: sum(v_after - b2^K
  v_before), which is (1 - b2) times the phase's discounted sum of squared
  gradients (train: a minibatch cut short or a step lost moves it);
- ``u_gap``: the worst leaf's gap of the norms of the phase's last update,
  which the program keeps as the next phase's selection input, measured
  like ``dparam_gap`` (a wrong stored update drives the next mask);
- ``opt_gap``: the same of the moments' norms (optimizer state).

``dparam_leaf`` names the leaf that sets ``dparam_gap``.

Leaves whose reference gradient is nought to rounding (norm under a
thousandth of the median leaf's) move by round-off alone; they are left out
of the leaf-wise gaps by that rule.
"""
from __future__ import annotations

import math

import numpy as np

ZERO_GRAD_SHARE = 1e-3


def _leafwise_gap(got: dict, want: dict, names: list) -> tuple:
    """(worst leaf's gap, that leaf's name)."""
    norms_w = {k: float(np.linalg.norm(want[k])) for k in names}
    med = float(np.median(list(norms_w.values()))) if names else 0.0
    worst, leaf = 0.0, None
    for k in names:
        den = max(norms_w[k], med)
        if den == 0.0:
            continue
        gap = abs(float(np.linalg.norm(got[k])) - norms_w[k]) / den
        if gap > worst:
            worst, leaf = gap, k
    return worst, leaf


def moving_leaves(grad_norms: dict) -> list:
    """Leaves whose first-iteration reference gradient is above round-off."""
    med = float(np.median(list(grad_norms.values())))
    return [k for k, g in grad_norms.items() if g >= ZERO_GRAD_SHARE * med]


def readings(update: dict, ref: dict, before: dict) -> dict:
    """The comparison's numbers for one update against the reference run
    ``ref`` from the flat state ``before``."""
    names = list(ref["params"])
    p0 = before["params"]
    flat0 = np.concatenate([p0[k].reshape(-1) for k in names])
    ref_after = np.concatenate([ref["params"][k].reshape(-1) for k in names])
    ref_mask = np.concatenate([ref["mask"][k].reshape(-1) for k in names])
    mask = update["mask_flat"]
    edge = flat0.copy()
    edge[mask] = update["wire_values"]
    change = float(np.linalg.norm(ref_after - flat0))
    moving = moving_leaves(ref["grad_norms"])
    d_got = {k: update["params"][k] - p0[k] for k in names}
    d_ref = {k: ref["params"][k] - p0[k] for k in names}
    opt_names = [f"{s}:{k}" for s in ("m", "v") for k in moving]
    opt_got = {f"{s}:{k}": update[s][k] for s in ("m", "v") for k in moving}
    opt_ref = {f"{s}:{k}": ref[s][k] for s in ("m", "v") for k in moving}
    loss_ref = ref["losses"][-1]
    decay = ref["v_decay"]
    gsq = [sum(float(np.sum(np.asarray(t[k], np.float64)
                            - decay * before["v"][k])) for k in names)
           for t in (update["v"], ref["v"])]
    dparam_gap, dparam_leaf = _leafwise_gap(d_got, d_ref, moving)
    return {
        "mask_miss": int(np.count_nonzero(mask != ref_mask)),
        "wire_gap": (float(np.linalg.norm(edge - ref_after)) / change
                     if change else math.inf),
        "dparam_gap": dparam_gap,
        "dparam_leaf": dparam_leaf,
        "loss_gap": abs(update["loss"] - loss_ref) / abs(loss_ref),
        "gsq_gap": abs(gsq[0] - gsq[1]) / gsq[1],
        "opt_gap": _leafwise_gap(opt_got, opt_ref, opt_names)[0],
        "u_gap": _leafwise_gap(update["u"], ref["u"], moving)[0],
    }


def update_from_reference(out: dict, value_dtype: str) -> dict:
    """A reference (or control) run summarised as the program's update:
    its wire values are its parameters at its mask, cast to the wire type."""
    names = list(out["params"])
    mask = np.concatenate([out["mask"][k].reshape(-1) for k in names])
    flat = np.concatenate([out["params"][k].reshape(-1) for k in names])
    return {"mask_flat": mask,
            "wire_values": flat[mask].astype(value_dtype).astype(np.float32),
            "params": out["params"], "m": out["m"], "v": out["v"],
            "u": out["u"], "loss": out["losses"][-1]}


def judge(values: dict, limits: dict) -> list:
    """[(name, value, limit, ok)] for every number that has a limit."""
    out = []
    for name, limit in limits.items():
        v = values[name]
        ok = v is not None and math.isfinite(v) and v <= limit
        out.append((name, v, limit, ok))
    return out
