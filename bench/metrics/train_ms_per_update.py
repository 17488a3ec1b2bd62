"""Fused train wall time per session trained, in ms (`core.timing`
``train_fused``, timed around block_until_ready)."""
import _stages


def read(run):
    s = _stages.seconds(run, "train_fused")
    if not s:
        return None
    return s / _stages.sessions_trained(run) * 1000
