"""Downlink one session needs at its cadence: mean wire bytes of the
window's deltas x 8 / T_update / 1000."""


def read(run):
    sizes = [u["bytes"] for u in run.window_updates() if u["ok"]]
    if not sizes:
        return None
    return sum(sizes) / len(sizes) * 8 / run.cfg["ams"]["t_update"] / 1000
