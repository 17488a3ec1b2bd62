"""Delta encode wall time per update, in ms (`core.timing`
``encode_stacked`` and ``encode_solo``)."""
import _stages


def read(run):
    s = _stages.seconds(run, "encode_stacked", "encode_solo")
    n = sum(g["width"] for g in run.grants)
    return s / n * 1000 if s is not None and n else None
