"""Selection wall time per update, in ms (`core.timing` ``select_stacked``
and ``select_solo``)."""
import _stages


def read(run):
    s = _stages.seconds(run, "select_stacked", "select_solo")
    n = sum(g["width"] for g in run.grants)
    return s / n * 1000 if s is not None and n else None
